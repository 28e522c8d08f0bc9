"""From a trace session's artifact to numbers: the program's own spans
(`flexflow_tpu.obs.start_trace` / `stop_trace`), alone or laid over the
device trace of the same session on the profiler's clock.

A `--trace 2` run leaves the artifact of its unfenced tail under
`chipbench_out/<cell>/session/` (`*.events.jsonl`: a header line, then
one span a line with `name`, `ts`, `dur` in microseconds since the
tracer's origin, `id`, `parent`, `call`). The header's `clock_shift_us`
ties the two clocks: a profiler timestamp in microseconds plus the shift
is a span's `ts`. Readers under `layer_metrics/` find the artifact here
on their own; where there is none (a `--trace 1` run, an older program)
`find` returns None and the reader reports nothing.

Spans come back in seconds on the profiler's timebase, the one
`trace_reduce` puts device events on, so both kinds compare directly.
"""

import collections
import glob
import json
import os
import statistics

from benchmarks import trace_reduce as tr

OUT_DIR = "chipbench_out"    # git-ignored; traces and reductions, a cell each
SESSION = "session"          # the unfenced, profiled tail (part-A traffic)
FENCED = "session_fenced"    # one epoch of fenced one-step calls (part B)
# spans that only frame the others: device idle time under them alone is
# time the tracing does not explain
FRAMES = ("fit", "step")
STAGING = ("data_load", "device_put")
BETWEEN_CALLS = "idle_between_fit_calls"

Span = collections.namedtuple("Span", "name start end id parent call args")
Session = collections.namedtuple("Session", "header spans")


def out_dir(root, cell_name, which=SESSION):
    return os.path.join(root, OUT_DIR, cell_name, which)


def load(directory):
    """The newest session artifact in `directory`, or None."""
    files = sorted(glob.glob(os.path.join(directory, "*.events.jsonl")))
    if not files:
        return None
    with open(files[-1]) as f:
        header = json.loads(f.readline())
        rows = [json.loads(line) for line in f]
    shift = header.get("clock_shift_us") or 0.0
    spans = [Span(r["name"], (r["ts"] - shift) * 1e-6,
                  (r["ts"] + r["dur"] - shift) * 1e-6, r.get("id"),
                  r.get("parent"), r.get("call"), r.get("args") or {})
             for r in rows if not r.get("instant")]
    return Session(header, sorted(spans, key=lambda s: s.start))


def find(ctx, reader_file, which=SESSION):
    """The session of the run that `ctx` belongs to, from a reader: the
    checkout is the one the reader's own file lies in."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file))))
    return load(out_dir(root, ctx["cell"]["name"], which))


def durations_ms(session, name):
    return [(s.end - s.start) * 1e3 for s in session.spans if s.name == name]


def median_ms(session, name):
    got = durations_ms(session, name) if session else []
    return statistics.median(got) if got else None


# ---------------------------------------------------------------------------
# spans against the device trace


def tied(session):
    """Whether the session's spans are on the profiler's clock."""
    return session is not None and session.header.get(
        "clock_shift_us") is not None


def self_intervals(spans):
    """name -> disjoint sorted intervals in which a span of that name is
    the innermost one open (its own extent less its children's)."""
    children = collections.defaultdict(list)
    for s in spans:
        children[s.parent].append((s.start, s.end))
    out = collections.defaultdict(list)
    for s in spans:
        out[s.name] += tr.subtract([(s.start, s.end)],
                                   tr.union(children.get(s.id, ())))
    return {name: tr.union(iv) for name, iv in out.items()}


def intersect(a, b):
    """Of the disjoint sorted `a`, the parts that the disjoint sorted `b`
    covers."""
    return tr.subtract(a, tr.subtract(a, b))


def idle_gaps(dev):
    """(window, its idle intervals) of one device, or None."""
    w = tr.window(dev)
    if w is None:
        return None
    return w, tr.subtract([w], tr.clip(tr.busy_intervals(dev), *w))


def idle_by_span(dev, spans):
    """(label -> idle seconds, window seconds) of one device: its idle
    time split by the innermost program span open on the host at that
    moment, `idle_in_<span>`, and `idle_between_fit_calls` where none
    is. The parts add up to the window's idle time."""
    got = idle_gaps(dev)
    if got is None:
        return None
    w, gaps = got
    out = {}
    for name, own in self_intervals(spans).items():
        seconds = tr.length(intersect(gaps, own))
        if seconds > 0:
            out["idle_in_" + name] = seconds
    rest = tr.length(gaps) - sum(out.values())
    out[BETWEEN_CALLS] = max(rest, 0.0)
    return out, w[1] - w[0]


def idle_shares_pct(devices, spans):
    """label -> share of the window in percent, per device then the mean;
    None if no device ran a train step."""
    acc, used = collections.Counter(), 0
    for dev in devices:
        got = idle_by_span(dev, spans)
        if got is None or not got[1]:
            continue
        used += 1
        for label, seconds in got[0].items():
            acc[label] += 100.0 * seconds / got[1]
    if not used:
        return None
    return {label: v / used for label, v in acc.items()}


def share_of(shares, names):
    """Sum of the `idle_in_<name>` shares for `names`."""
    return sum(shares.get("idle_in_" + n, 0.0) for n in names)


def unnamed_share(shares):
    """Idle share that no span but the frames covers."""
    return share_of(shares, FRAMES) + shares.get(BETWEEN_CALLS, 0.0)


def innermost(spans, t):
    """Name of the innermost span open at `t`, or None."""
    best = None
    for s in spans:     # sorted by start: the last one that covers wins
        if s.start > t:
            break
        if s.end >= t:
            best = s
    return best.name if best else None


def labelled_idle_gaps(devices, spans, n=10):
    """[label, seconds] of the first device's longest idle gaps, each
    named by the program span open on the host at the gap's middle."""
    for dev in devices:
        got = idle_gaps(dev)
        if got is None:
            continue
        out = []
        for s, e in sorted(got[1], key=lambda g: g[0] - g[1])[:n]:
            name = innermost(spans, (s + e) / 2)
            out.append(["idle_in_" + name if name else BETWEEN_CALLS, e - s])
        return out
    return []


def launched_steps(dev, spans):
    """[(dispatch span, device start, device end)] of the device's
    train-step programs, each with the `dispatch` span that launched it:
    the k-th program is the k-th dispatch. None unless the session holds
    exactly as many dispatches as the device ran programs."""
    steps = tr.step_spans(dev)
    dispatches = [s for s in spans if s.name == "dispatch"]
    if not steps or len(steps) != len(dispatches):
        return None
    return [(d, s, e) for d, (s, e) in zip(dispatches, steps)]


def dispatch_leads_s(dev, spans):
    """Per train step, the device program's start less the start of the
    `dispatch` span that launched it: positive if the tie holds."""
    steps = launched_steps(dev, spans)
    return steps and [s - d.start for d, s, _ in steps]


def epoch_gaps_s(dev, spans):
    """Device idle seconds between the last train step of one `fit` call
    and the first of the next, for every pair of neighbouring calls."""
    steps = launched_steps(dev, spans)
    if steps is None:
        return None
    busy = tr.busy_intervals(dev)
    out = []
    for (a, _, end_a), (b, start_b, _) in zip(steps, steps[1:]):
        if a.call != b.call:
            out.append(tr.length(tr.subtract([(end_a, start_b)],
                                             tr.clip(busy, end_a, start_b))))
    return out
