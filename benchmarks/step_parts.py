"""A train step's device time by part and direction, from the join table
the program writes.

A session with `device=True` (`flexflow_tpu.obs.start_trace`) leaves
`<stem>.step_scopes.json` beside its spans: for every instruction of the
compiled train step its `op_name`, its `part` (`optimizer_update`,
`loss`, `head`, `attention`, `experts`, `ssm` or `op_<kind>`; null where
no scope of the program holds it), its `direction` (`forward`,
`backward`, `optimizer`, `none`) and, for a fusion, `parts`: how many
instructions of its body lie in which part. A reader finds the table
where `session_reduce.out_dir` puts the session of the run's cell; where
there is none (a program without the table, a `--trace 1` run) every
function here returns None and the reader reports nothing.

Only the events inside the train-step programs' own spans count
(`trace_reduce.step_spans`): between two steps the device runs
`jit_unpack_batch` and `jit_add`, whose `fusion.N` carry the names of
other instructions of the step. Shares are over the busy seconds of the
same spans, a device at a time, then the mean over devices. See
STEP_PARTS.md.
"""

import bisect
import collections
import functools
import glob
import json
import os

from benchmarks import session_reduce as sr
from benchmarks import trace_reduce as tr

SUFFIX = ".step_scopes.json"
BREAKDOWN = "step_parts.json"    # in the session's directory
NAMED = ("forward", "backward", "optimizer")


def root_of(reader_file):
    """The checkout a reader under layer_metrics/ lies in."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file))))


@functools.lru_cache(maxsize=2)
def _load(path, _mtime):
    with open(path) as f:
        return json.load(f).get("instructions") or None


def find_table(ctx, reader_file):
    """instruction -> row of the run's train step, or None."""
    files = sorted(glob.glob(os.path.join(
        sr.out_dir(root_of(reader_file), ctx["cell"]["name"]),
        "*" + SUFFIX)))
    if not files:
        return None
    return _load(files[-1], os.path.getmtime(files[-1]))


def step_events(dev, module=tr.STEP_MODULE):
    """(spans of the train-step programs, the device's op events that
    start inside one of them; enclosing ops left out)."""
    spans = tr.step_spans(dev, module)
    starts = [s for s, _ in spans]
    events = []
    for n, s, d in dev.lines.get(tr.OPS, ()):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1] and not n.startswith(tr.ENCLOSING):
            events.append((n, s, d))
    return spans, events


def neighbour(row):
    """Label of an instruction without a part: the part it feeds or is
    fed by, as the table found it among its neighbours."""
    for key in ("feeds", "fed_by"):
        if row.get(key):
            return f"None, {key.replace('_', ' ')} {row[key]}"
    return "None"


def device_seconds(dev, table):
    """Of one device: seconds by (part, direction), seconds of events
    whose name the table does not hold, seconds in fusions whose body
    holds more than one part, seconds by (stem, part), seconds in
    fusions whose body holds more than one direction by (the root's
    direction, the body's directions), busy seconds of the train-step
    spans, and their number. None if it ran none."""
    spans, events = step_events(dev)
    if not spans:
        return None
    by_part = collections.Counter()
    by_stem = collections.Counter()
    by_body = collections.Counter()
    unknown = mixed = 0.0
    for n, _, d in events:
        row = table.get(n)
        if row is None:
            unknown += d
            by_stem[(tr.stem(n), "not in the table")] += d
            continue
        by_part[(row["part"], row["direction"])] += d
        by_stem[(tr.stem(n), row["part"] or neighbour(row))] += d
        if len(row.get("parts") or ()) > 1:
            mixed += d
        held = row.get("directions") or ()
        if len(held) > 1:
            by_body[(row["direction"], "+".join(sorted(held)))] += d
    busy = tr.length(sr.intersect(tr.busy_intervals(dev), tr.union(spans)))
    return dict(by_part=by_part, by_stem=by_stem, by_body=by_body,
                unknown=unknown, mixed=mixed, busy=busy, steps=len(spans))


def reduce(devices, table):
    """Mean over the devices that ran a train step: share of busy time
    in percent by (part, direction), by direction, `unknown`, `mixed`;
    milliseconds a step by (part, direction), by (stem, part) and, for
    fusions of several directions, by (root's direction, body's
    directions); busy milliseconds a step. None if no device ran one."""
    rows = [r for r in (device_seconds(d, table) for d in devices)
            if r is not None and r["busy"] > 0]
    if not rows:
        return None
    n = len(rows)

    def mean(fn):
        acc = collections.Counter()
        for r in rows:
            for k, v in fn(r).items():
                acc[k] += v / n
        return acc

    share = mean(lambda r: {k: 100.0 * v / r["busy"]
                            for k, v in r["by_part"].items()})
    by_direction = collections.Counter()
    for (_, direction), v in share.items():
        by_direction[direction] += v
    return dict(
        share_pct=share, direction_pct=by_direction,
        unknown_pct=sum(100.0 * r["unknown"] / r["busy"] for r in rows) / n,
        mixed_pct=sum(100.0 * r["mixed"] / r["busy"] for r in rows) / n,
        ms_a_step=mean(lambda r: {k: 1e3 * v / r["steps"]
                                  for k, v in r["by_part"].items()}),
        stem_ms_a_step=mean(lambda r: {k: 1e3 * v / r["steps"]
                                       for k, v in r["by_stem"].items()}),
        body_ms_a_step=mean(lambda r: {k: 1e3 * v / r["steps"]
                                       for k, v in r["by_body"].items()}),
        busy_ms_a_step=sum(1e3 * r["busy"] / r["steps"] for r in rows) / n,
        steps=sum(r["steps"] for r in rows) / n)


_LAST = (None, None)    # a run's device list and its reduction


def reduced(ctx, reader_file):
    """`reduce` of the run, or None without a table. The first reader
    that asks also leaves the whole breakdown as `step_parts.json` in
    the session's directory, for who writes PERF.md."""
    global _LAST
    table = find_table(ctx, reader_file)
    if not table:
        return None
    if _LAST[0] is not ctx["devices"]:
        _LAST = (ctx["devices"], reduce(ctx["devices"], table))
        if _LAST[1] is not None:
            _write_breakdown(ctx, reader_file, _LAST[1])
    return _LAST[1]


def _write_breakdown(ctx, reader_file, got):
    def rows(counter, n=None):
        return [[*(str(p) for p in k), v]
                for k, v in counter.most_common(n)]

    path = os.path.join(sr.out_dir(root_of(reader_file),
                                   ctx["cell"]["name"]), BREAKDOWN)
    with open(path, "w") as f:
        json.dump(dict(
            cell=ctx["cell"]["name"], steps=got["steps"],
            busy_ms_a_step=got["busy_ms_a_step"],
            direction_pct=dict(got["direction_pct"]),
            unknown_pct=got["unknown_pct"], mixed_pct=got["mixed_pct"],
            part_direction_ms_a_step=rows(got["ms_a_step"]),
            mixed_direction_ms_a_step=rows(got["body_ms_a_step"]),
            stem_part_ms_a_step=rows(got["stem_ms_a_step"], 60)), f,
            indent=1)


def direction_share_pct(ctx, reader_file, direction):
    got = reduced(ctx, reader_file)
    return None if got is None else got["direction_pct"].get(direction, 0.0)


def unscoped_share_pct(ctx, reader_file):
    """What is neither forward, backward nor optimizer: events without a
    direction or a row, and busy time that is no op event at all."""
    got = reduced(ctx, reader_file)
    if got is None:
        return None
    return 100.0 - sum(got["direction_pct"].get(d, 0.0) for d in NAMED)


def part_share_pct(ctx, reader_file, parts):
    got = reduced(ctx, reader_file)
    if got is None:
        return None
    return sum(v for (part, _), v in got["share_pct"].items()
               if part in parts)


def scope_share_pct(ctx, reader_file, scope):
    """Share of the events whose `op_name` holds `jit(<scope>)`, a scope
    inside a part (the expert layers' `moe_combine`)."""
    table = find_table(ctx, reader_file)
    if not table:
        return None
    inside = {n: dict(part=scope, direction=row["direction"])
              for n, row in table.items()
              if f"jit({scope})" in row["op_name"]}
    got = reduce(ctx["devices"], inside)
    if got is None:
        return None
    return sum(got["share_pct"].values())
