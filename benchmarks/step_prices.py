"""The search's prices of a train step beside the device's milliseconds,
by part and direction.

Since PR 50 the `<stem>.step_scopes.json` a session with `device=True`
leaves (`step_parts.py` reads its `instructions`) also holds `prices`:
one replay of the EXECUTED strategy through the native simulator, the
thing the search ranks strategies with, cut by the same parts and
directions as the table (`flexflow_tpu/obs/simtrace.py` `step_prices`),
and the session's header holds `device_peak_bytes`, the allocator's
peak. This module joins `prices.by_part` with
`step_parts.reduced(ctx)["ms_a_step"]` on (part, direction) and reads
three numbers of the search layer from the join; the first reader that
asks leaves the whole join as `step_prices.json` in the session's
directory. Where the file holds no `prices` (the parent's program, a
model no search compiled, a replay that failed, a `--trace 1` run)
every function here returns None and the reader reports nothing. See
STEP_PRICES.md.
"""

import functools
import glob
import json
import os

from benchmarks import session_reduce as sr
from benchmarks import step_parts

BREAKDOWN = "step_prices.json"    # in the session's directory
# the directions of the rows that price compute; a `collectives` row's
# direction is its task kind (`comm`, `gradsync`) and joins nothing
COMPUTE = ("forward", "backward", "optimizer")
WITHIN = 2.0     # a price within [measured / 2, 2 * measured] is "sane"


@functools.lru_cache(maxsize=2)
def _load(path, _mtime):
    with open(path) as f:
        return json.load(f).get("prices") or None


def find_prices(ctx, reader_file):
    """The `prices` object beside the table `step_parts.find_table`
    finds for the run, or None."""
    files = sorted(glob.glob(os.path.join(
        sr.out_dir(step_parts.root_of(reader_file), ctx["cell"]["name"]),
        "*" + step_parts.SUFFIX)))
    if not files:
        return None
    return _load(files[-1], os.path.getmtime(files[-1]))


def join(prices, measured_ms, busy_ms=None, device_peak_bytes=None,
         table_parts=()):
    """`prices` against milliseconds a step by (part, direction)
    (`step_parts.reduce`'s `ms_a_step`; {} without a device lane): the
    rows both sides hold as [part, direction, priced ms, measured ms,
    priced / measured], largest measured first; the priced rows no
    measured part answers ([part, direction, priced ms]: the
    collectives, and an op whose instructions all went into another
    part's fusions); the measured parts no price answers ([part,
    direction, measured ms]: `loss`, the part-less events); the totals.
    `table_parts`: the parts the join table gives some instruction."""
    priced = {(part, direction): 1e3 * seconds
              for part, direction, seconds, *_ in prices["by_part"]}
    measured = dict(measured_ms)
    both = sorted((k for k in priced if k in measured and k[1] in COMPUTE),
                  key=lambda k: -measured[k])
    within = sum(measured[k] for k in both if measured[k] > 0
                 and measured[k] / WITHIN <= priced[k]
                 <= measured[k] * WITHIN)
    compute_ms = sum(ms for (_, d), ms in priced.items() if d in COMPUTE)
    joined_ms = sum(priced[k] for k in both)
    in_table_ms = sum(ms for (p, d), ms in priced.items()
                      if d in COMPUTE and p in table_parts)

    def ms(key):
        return None if prices.get(key) is None else 1e3 * prices[key]

    def by_direction(direction, table):
        return sum(v for (_, d), v in table.items() if d == direction)

    return dict(
        rows=[[str(p), d, priced[(p, d)], measured[(p, d)],
               priced[(p, d)] / measured[(p, d)] if measured[(p, d)] else None]
              for p, d in both],
        priced_only=[[str(p), d, v] for (p, d), v in sorted(
            priced.items(), key=lambda kv: -kv[1]) if (p, d) not in both],
        measured_only=[[str(p), d, v] for (p, d), v in sorted(
            measured.items(), key=lambda kv: -kv[1]) if (p, d) not in both],
        totals=dict(
            priced_step_ms=ms("step_s"), busy_ms_a_step=busy_ms,
            search_predicted_step_ms=ms("search_predicted_s"),
            priced_ms={d: by_direction(d, priced) for d in COMPUTE},
            measured_ms={d: by_direction(d, measured) for d in COMPUTE},
            priced_compute_ms=compute_ms,
            # of the priced compute milliseconds, those in rows the
            # join holds on both sides
            priced_compute_joined_pct=(100.0 * joined_ms / compute_ms
                                       if compute_ms else None),
            # ... and those in parts the table gives some instruction
            # (an op that only ever fuses into its neighbours has
            # instructions, and no event rooted in them)
            priced_compute_in_table_pct=(100.0 * in_table_ms / compute_ms
                                         if compute_ms else None),
            priced_comm_ms=ms("comm_s"),
            priced_gradsync_ms=ms("gradsync_s"),
            priced_hidden_comm_ms=ms("hidden_comm_s"),
            measured_within_2x_ms=within,
            priced_memory_bytes=prices.get("memory_bytes"),
            search_predicted_memory_bytes=prices.get(
                "search_predicted_memory_bytes"),
            device_peak_bytes=device_peak_bytes,
            cost_sources=prices.get("cost_sources")))


_LAST = (None, None)    # a run's device list and its join


def joined(ctx, reader_file):
    """`join` of the run, or None without `prices`. The first reader
    that asks also leaves it as `step_prices.json` in the session's
    directory, with or without a device lane."""
    global _LAST
    prices = find_prices(ctx, reader_file)
    if not prices:
        return None
    if _LAST[0] is not ctx["devices"]:
        got = step_parts.reduced(ctx, reader_file) or {}
        session = sr.find(ctx, reader_file)
        table = step_parts.find_table(ctx, reader_file) or {}
        out = join(prices, got.get("ms_a_step") or {},
                   got.get("busy_ms_a_step"),
                   session.header.get("device_peak_bytes")
                   if session else None,
                   {row["part"] for row in table.values()})
        _LAST = (ctx["devices"], out)
        path = os.path.join(sr.out_dir(step_parts.root_of(reader_file),
                                       ctx["cell"]["name"]), BREAKDOWN)
        with open(path, "w") as f:
            json.dump(dict(cell=ctx["cell"]["name"], **out), f, indent=1)
    return _LAST[1]


def _error_pct(believed, found):
    if believed is None or not found:
        return None
    return 100.0 * abs(believed - found) / found


def step_price_error_pct(ctx, reader_file):
    """100 |P - M| / M: P the replayed schedule's step time, M the
    train-step programs' busy milliseconds a step."""
    got = joined(ctx, reader_file)
    if got is None:
        return None
    totals = got["totals"]
    return _error_pct(totals["priced_step_ms"], totals["busy_ms_a_step"])


def memory_price_error_pct(ctx, reader_file):
    """100 |Pm - A| / A: Pm the replayed strategy's bytes a chip, A the
    allocator's peak in the session's header."""
    got = joined(ctx, reader_file)
    if got is None:
        return None
    totals = got["totals"]
    return _error_pct(totals["priced_memory_bytes"],
                      totals["device_peak_bytes"])


def priced_within_2x_share_pct(ctx, reader_file):
    """Of the busy milliseconds a step, those in (part, direction) rows
    whose price lies within [measured / 2, 2 measured]; a measured part
    without a price and busy time without a part count as outside."""
    got = joined(ctx, reader_file)
    if got is None or not got["totals"]["busy_ms_a_step"]:
        return None
    totals = got["totals"]
    return 100.0 * totals["measured_within_2x_ms"] / totals["busy_ms_a_step"]
