"""From a profiler trace to per-device numbers. The benchmark's own
reduction: every device is reduced on its own timeline, then averaged.

A trace is a list of `Device`s; a device holds its lines (profiler
threads) by name, each a list of `(name, start_s, duration_s)`:
- "XLA Ops": one span per executed HLO op, serial on the device's main
  stream (control-flow ops enclose their bodies' spans);
- "XLA Modules": one span per program run (`jit_train_step(...)`);
- "Async XLA Ops": asynchronous ops from start to done, where the
  profiler records them (collectives, copies); they overlap "XLA Ops";
- "Steps" and the overlays are roll-ups and are never counted.

Loaders: `load_xplane` (what `jax.profiler.stop_trace` writes) and
`load_chrome` (a Chrome/Perfetto trace JSON, the committed fixture).
"""

import bisect
import collections
import glob
import gzip
import json
import os
import re

OPS, MODULES, ASYNC = "XLA Ops", "XLA Modules", "Async XLA Ops"
STEP_MODULE = "jit_train_step"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
# ops whose span encloses the spans of the ops they run
ENCLOSING = ("while", "conditional", "call")
KERNEL_PREFIX = "tpu_custom_call"

Device = collections.namedtuple("Device", "name lines")


def load_xplane(path):
    from jax.profiler import ProfileData
    devices = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(op_name(ev.name), ev.start_ns * 1e-9,
                                 ev.duration_ns * 1e-9)
                                for ev in line.events]
        devices.append(Device(plane.name, lines))
    return sorted(devices, key=lambda d: d.name)


def op_name(text):
    """The profiler names a device op by its whole HLO instruction,
    `%fusion.12 = bf16[...] fusion(...)`; the op's name is `fusion.12`."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def newest_xplane(profile_dir):
    found = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return found[-1]


def load_chrome(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    devices = {pid: Device(name, {}) for pid, name in procs.items()
               if name.startswith("/device:")}
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in devices:
            continue
        line = threads.get((e["pid"], e.get("tid")))
        devices[e["pid"]].lines.setdefault(line, []).append(
            (e.get("name") or "", float(e["ts"]) * 1e-6,
             float(e.get("dur", 0.0)) * 1e-6))
    return sorted(devices.values(), key=lambda d: d.name)


# ---------------------------------------------------------------------------
# interval arithmetic


def union(intervals):
    """Merged, sorted, disjoint."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def subtract(a, b):
    """Parts of the disjoint sorted `a` that the disjoint sorted `b` does
    not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def stem(name):
    """`fusion.123` -> `fusion`."""
    return re.sub(r"[.\d]+$", "", name) or name


def is_collective(name):
    return name.startswith(COLLECTIVES)


def _spans(events):
    return [(s, s + d) for _, s, d in events]


# ---------------------------------------------------------------------------
# per device


def step_spans(dev, module=STEP_MODULE):
    """(start, end) of every run of the train-step program."""
    return sorted((s, s + d) for n, s, d in dev.lines.get(MODULES, ())
                  if n.startswith(module))


def window(dev, module=STEP_MODULE):
    """From the first train-step program's start to the last one's end;
    None if the device ran none."""
    steps = step_spans(dev, module)
    return (steps[0][0], steps[-1][1]) if steps else None


def busy_intervals(dev):
    """Where an operation ran on the device: the union of "XLA Ops" and of
    those "Async XLA Ops" that carry a duration."""
    evs = list(dev.lines.get(OPS, ())) + [
        e for e in dev.lines.get(ASYNC, ()) if e[2] > 0]
    return union(_spans(evs))


def busy_and_window(dev, module=STEP_MODULE):
    w = window(dev, module)
    if w is None:
        return None
    return length(clip(busy_intervals(dev), *w)), w[1] - w[0]


def kernel_seconds(dev, prefix=KERNEL_PREFIX, module=STEP_MODULE):
    """Summed device time of the events named `prefix*` inside the
    window, and how many train steps the window holds."""
    w = window(dev, module)
    if w is None:
        return None
    total = sum(d for n, s, d in dev.lines.get(OPS, ())
                if n.startswith(prefix) and w[0] <= s < w[1])
    return total, len(step_spans(dev, module))


def exposed_collective_seconds(dev, module=STEP_MODULE):
    """Collective spans of this device (either line) that no compute span
    of the same device covers, inside the window; with the step count."""
    w = window(dev, module)
    if w is None:
        return None
    ops = dev.lines.get(OPS, ())
    coll = [e for e in ops if is_collective(e[0])] + [
        e for e in dev.lines.get(ASYNC, ()) if is_collective(e[0])]
    compute = [e for e in ops if not is_collective(e[0])
               and not e[0].startswith(ENCLOSING)]
    exposed = subtract(union(clip(_spans(coll), *w)),
                       union(clip(_spans(compute), *w)))
    return length(exposed), len(step_spans(dev, module))


def mean_over_devices(devices, fn):
    vals = [v for v in (fn(d) for d in devices) if v is not None]
    if not vals:
        return None
    return tuple(sum(col) / len(vals) for col in zip(*vals))


# ---------------------------------------------------------------------------
# where the time goes


def top_device_ops(devices, n=10, module=STEP_MODULE):
    """[stem, seconds] of the ops that took most device time in the
    window, averaged over devices; enclosing ops left out."""
    totals = collections.Counter()
    used = 0
    for dev in devices:
        w = window(dev, module)
        if w is None:
            continue
        used += 1
        for name, s, d in dev.lines.get(OPS, ()):
            if w[0] <= s < w[1] and not name.startswith(ENCLOSING):
                totals[stem(name)] += d
    return [[k, v / used] for k, v in totals.most_common(n)] if used else []


def top_idle_gaps(devices, n=10, module=STEP_MODULE):
    """The longest gaps of the first device's window, each named by where
    it lies: between two programs (the host was dispatching, staging the
    next batch or reading the loss) or inside a program after an op."""
    for dev in devices:
        w = window(dev, module)
        if w is None:
            continue
        busy = clip(busy_intervals(dev), *w)
        gaps = subtract([w], busy)
        modules = sorted((s, s + d, n_) for n_, s, d
                         in dev.lines.get(MODULES, ()))
        ops = sorted((s + d, n_) for n_, s, d in dev.lines.get(OPS, ()))
        ends = [e for e, _ in ops]
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            inside = [m for m in modules if m[0] <= s and e <= m[1]]
            if inside:
                i = bisect.bisect_right(ends, s + 1e-12) - 1
                after = stem(ops[i][1]) if i >= 0 else "start"
                label = "inside {} after {}".format(
                    inside[0][2].split("(")[0], after)
            else:
                prev = [m for m in modules if m[1] <= s + 1e-12]
                nxt = [m for m in modules if m[0] >= e - 1e-12]
                label = "between {} and {}".format(
                    prev[-1][2].split("(")[0] if prev else "start",
                    nxt[0][2].split("(")[0] if nxt else "end")
            out.append([label, e - s])
        return out
    return []


def describe(devices):
    """What a trace holds, small enough for a line of output: devices,
    the lines of the first with their event counts, its programs, and
    the collective and asynchronous ops by stem with count and seconds."""
    if not devices:
        return dict(devices=[])
    dev = devices[0]

    def by_stem(events, keep=lambda n: True):
        acc = collections.defaultdict(lambda: [0, 0.0])
        for n, _, d in events:
            if keep(n):
                acc[stem(n)][0] += 1
                acc[stem(n)][1] += d
        return {k: v for k, v in sorted(acc.items(),
                                        key=lambda kv: -kv[1][1])[:12]}

    return dict(
        devices=[d.name for d in devices],
        lines={k: len(v) for k, v in dev.lines.items()},
        programs=by_stem([(n.split("(")[0], s, d)
                          for n, s, d in dev.lines.get(MODULES, ())]),
        collectives_on_ops_line=by_stem(dev.lines.get(OPS, ()),
                                        is_collective),
        async_line=by_stem(dev.lines.get(ASYNC, ())),
        steps_per_device=[len(step_spans(d)) for d in devices])
