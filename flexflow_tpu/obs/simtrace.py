"""Simulated-schedule observability: the search's predicted timeline.

The native simulator already produces a full task schedule for the
strategy it ranked best — per-task ``start``/``finish`` seconds on the
{compute, ICI} streams (``ffs_sim.hpp`` list scheduler, returned by
``ffs_simulate``). Until now that schedule existed only inside the cost
model; this module renders it as Perfetto lanes (``sim:compute`` /
``sim:comms``) on the SAME lane layout as the measured device lanes the
devtrace capture injects (``device:compute`` / ``device:comms``,
obs/devtrace.py), so the predicted and the measured step sit side by
side in one merged timeline — the SCALE-Sim-style simulator validation
view (PAPERS.md): if the simulator believes the right schedule, the two
lane groups should look alike; where they diverge is exactly the
calibration signal.

Also emits the ``.simtrace.json`` artifact: the predicted step
breakdown plus per-op priced rows joined against measured per-op
seconds where a profile table exists — the (op class x shape x sharding
-> priced terms, measured seconds) corpus rows the learned-TPU-cost-
model direction trains on ("A Learned Performance Model for TPUs",
PAPERS.md 2008.01040).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

# Perfetto lane tids for the predicted schedule, disjoint from the
# devtrace lanes (64-66) and below the merge tid-block size (256), so
# sim lanes keep their own rows in both per-host and merged traces.
SIM_TID_COMPUTE, SIM_TID_COMMS = 72, 73
SIM_LANE_THREADS = {SIM_TID_COMPUTE: "sim:compute",
                    SIM_TID_COMMS: "sim:comms"}

# SimTask kind -> lane (mirrors the simulator's two-stream scheduler:
# comm/gradsync ride the ICI stream, everything else the compute
# stream). Public: explain.py's timeline rendering uses the same map.
SIM_COMMS_KINDS = ("comm", "gradsync")

# Corpus-row schema version of the ``per_op`` rows below. v2 added the
# featurization fields the learned cost model trains on (flops,
# io_bytes, param_bytes, dtype_size, mesh degrees, ring sizes); v3 adds
# the ``impl`` column — WHICH KERNEL ran the op (einsum/flash/ring/
# conv/conv_bn_fused/triad/fused, the searched ``_k:`` dimension) — so
# ``scripts/costmodel.py train`` learns per-impl coefficients
# ("TYPE:impl" classes) instead of blending two lowerings into one
# regression. The costmodel corpus loader
# (flexflow_tpu/costmodel/corpus.py) refuses rows NEWER than what it
# understands, so a schema drift here fails the CI costmodel stage
# loudly instead of silently training on garbage; v2 rows stay
# trainable (impl derived from the choice suffix).
CORPUS_SCHEMA_VERSION = 3


def sim_lane_events(tasks: List[Dict[str, Any]],
                    name_of: Dict[int, str],
                    t0_us: float = 0.0) -> List[Dict[str, Any]]:
    """Chrome-trace ``X`` events for a simulated task schedule.

    ``tasks``: ``ffs_simulate`` response rows ({kind, node, start,
    finish, collective?, bytes?}, seconds). Zero-duration rows (the
    census records pipe simulation emits) are skipped — they carry
    bytes, not time. ``name_of`` maps node INDEX -> op name. ``t0_us``
    places the schedule on the host timeline (e.g. at a measured step's
    start) so predicted and measured lanes share a clock base."""
    events: List[Dict[str, Any]] = []
    for t in tasks:
        start = float(t.get("start", 0.0))
        finish = float(t.get("finish", 0.0))
        if finish <= start:
            continue
        kind = str(t.get("kind", ""))
        tid = SIM_TID_COMMS if kind in SIM_COMMS_KINDS else SIM_TID_COMPUTE
        node = t.get("node", -1)
        label = name_of.get(node, "step")
        args: Dict[str, Any] = dict(kind=kind)
        if t.get("collective"):
            args["collective"] = t["collective"]
            args["bytes"] = t.get("bytes", 0)
        if t.get("hidden_s"):
            # predicted-hidden interval (ISSUE 9): seconds of this comm
            # task the simulator scheduled under busy compute — in the
            # merged view, compare against the devtrace lanes' measured
            # overlapped_comms_s to check the hiding actually landed
            args["hidden_s"] = round(float(t["hidden_s"]), 9)
        events.append(dict(
            name=f"{label}:{kind}", ph="X", tid=tid,
            ts=round(t0_us + start * 1e6, 3),
            dur=round((finish - start) * 1e6, 3),
            cat="simtrace", args=args))
    return events


def task_seconds(task: Dict[str, Any]) -> float:
    """A scheduled task's duration: per-chip seconds of the schedule."""
    return max(0.0, float(task.get("finish", 0.0))
               - float(task.get("start", 0.0)))


def per_op_predicted(tasks: List[Dict[str, Any]]
                     ) -> Dict[int, Dict[str, float]]:
    """Node index -> priced seconds per term, aggregated from the
    simulated schedule (fwd_s / bwd_s / comm_s / gradsync_s / update_s).
    Collective census bytes accumulate under ``collective_bytes``. The
    optimizer's ``update`` task belongs to no node (index -1): its
    seconds stand under that key, the only negative one kept."""
    out: Dict[int, Dict[str, float]] = {}
    for t in tasks:
        node = t.get("node", -1)
        kind = str(t.get("kind", ""))
        if node is None or (node < 0 and kind != "update"):
            continue
        row = out.setdefault(int(node), dict(
            fwd_s=0.0, bwd_s=0.0, comm_s=0.0, gradsync_s=0.0, update_s=0.0,
            hidden_s=0.0, collective_bytes=0.0))
        if f"{kind}_s" in row:
            row[f"{kind}_s"] += task_seconds(t)
        row["hidden_s"] += float(t.get("hidden_s", 0.0))
        if t.get("collective"):
            row["collective_bytes"] += float(t.get("bytes", 0.0))
    return out


# a task's kind -> the direction of the join table (obs/step_scopes.py)
# its seconds are compared with
DIRECTION_OF_KIND = {"fwd": "forward", "bwd": "backward",
                     "update": "optimizer"}
COLLECTIVES = "collectives"    # the part of every `comm` / `gradsync` task


def prices_by_part(ff, resp: Dict[str, Any]) -> List[List[Any]]:
    """The replayed schedule's seconds as the join table of the compiled
    step cuts the device's: rows ``[part, direction, seconds, ops]``
    (``ops``: the tasks added up, one an op and direction),
    ``part`` by ``GraphExecutor.part_of_node`` (the rule the step's
    ``op_name``s are read back with) and ``direction`` ``forward`` /
    ``backward`` for the ``fwd`` / ``bwd`` tasks, ``optimizer`` under
    the part ``optimizer_update`` for the ``update`` tasks (which
    belong to no node). The ``comm`` and ``gradsync`` tasks stand under
    the part ``collectives`` with their kind as the direction and a
    fifth field, the seconds the schedule hid under compute. Seconds
    are the schedule's per-chip durations, as ``corpus_rows`` documents;
    a pipe mesh's replay returns census records of no duration and so
    no seconds here."""
    nodes = ff.executor.nodes
    rows: Dict[Any, List[float]] = {}
    for t in resp.get("tasks") or []:
        kind, node = str(t.get("kind", "")), t.get("node", -1)
        if kind in SIM_COMMS_KINDS:
            key = (COLLECTIVES, kind)
        elif kind == "update":
            key = ("optimizer_update", DIRECTION_OF_KIND[kind])
        elif kind in DIRECTION_OF_KIND and 0 <= node < len(nodes):
            key = (ff.executor.part_of_node(nodes[node]),
                   DIRECTION_OF_KIND[kind])
        else:
            continue
        row = rows.setdefault(key, [0.0, 0, 0.0])
        row[0] += task_seconds(t)
        row[1] += 1
        row[2] += float(t.get("hidden_s", 0.0))
    return [[part, direction, seconds, count]
            + ([hidden] if part == COLLECTIVES else [])
            for (part, direction), (seconds, count, hidden) in rows.items()]


def predicted_totals(resp: Dict[str, Any]) -> Dict[str, Any]:
    """The replay's own totals, seconds a step and bytes a chip."""
    return dict(
        step_s=resp.get("iteration_time"),
        fwd_s=resp.get("fwd_time"),
        bwd_s=resp.get("bwd_time"),
        comm_s=resp.get("comm_time"),
        gradsync_s=resp.get("gradsync_time"),
        # predicted comm seconds hidden under compute (the schedule's
        # overlapped intervals + the '_ovl'/pipeline analytic hidden
        # terms) — the predicted twin of the devtrace's measured
        # overlapped_comms_s
        hidden_comm_s=resp.get("hidden_comm_time"),
        memory_bytes=resp.get("memory"))


def step_prices(ff, resp: Dict[str, Any]) -> Dict[str, Any]:
    """The ``prices`` object of a session's ``<stem>.step_scopes.json``:
    what the cost model says of the strategy that ran, in the terms the
    device trace of the same step is read in (``benchmarks/
    step_prices.py`` joins the two). ``memory_bytes`` is the replayed
    strategy's, ``search_predicted_*`` what the search itself returned
    for the strategy it chose (they differ where the executor runs
    another choice than the search priced: ``ExecPlan.executed_choice``)."""
    from flexflow_tpu.obs.inspect import search_predictions

    census: Dict[str, int] = {}
    for source in (resp.get("cost_sources") or {}).values():
        census[source] = census.get(source, 0) + 1
    searched = search_predictions(ff)
    return dict(
        predicted_totals(resp),
        update_s=sum(task_seconds(t) for t in resp.get("tasks") or []
                     if t.get("kind") == "update"),
        search_predicted_s=searched["search_predicted_step_s"],
        search_predicted_memory_bytes=searched[
            "search_predicted_memory_bytes"],
        cost_sources=census,
        by_part=prices_by_part(ff, resp))


def corpus_rows(ff, resp: Dict[str, Any],
                measured: Optional[Dict[str, float]] = None
                ) -> List[Dict[str, Any]]:
    """Learned-cost-model corpus rows: one per op, joining the op's
    identity (class, shape, sharding choice) -> the simulator's priced
    terms -> measured per-op seconds where a profile table has them
    (``ff.op_profile`` from ``--profiling`` / ``--search-measure-ops``,
    or an explicit ``measured`` table). ``measured.source`` records
    whether the measured half is real ("measured") or absent (None) so
    a training-set builder can filter."""
    from flexflow_tpu.obs.drift import work_division

    measured = measured if measured is not None else (ff.op_profile or {})
    priced = per_op_predicted(resp.get("tasks") or [])
    # which model priced each node's compute (analytic roofline vs
    # learned regression vs measured profile) — ffs_simulate reports it
    # per guid when the machine carried a learned table
    sources = resp.get("cost_sources") or {}
    mesh_axes = dict(zip(ff.mesh.axis_names,
                         (int(d) for d in ff.mesh.devices.shape)))
    from flexflow_tpu.search.unity import executed_kernel_choices
    impls = executed_kernel_choices(
        ff.executor.nodes, ff.strategy, mesh_axes, training=True,
        recorded=ff.executor.kernel_choices)
    rows: List[Dict[str, Any]] = []
    for idx, node in enumerate(ff.executor.nodes):
        op = node.op
        st = (ff.strategy or {}).get(op.guid)
        p = priced.get(idx, dict(fwd_s=0.0, bwd_s=0.0, comm_s=0.0,
                                 gradsync_s=0.0, collective_bytes=0.0))
        mf = measured.get(f"{op.guid}:fwd")
        mb = measured.get(f"{op.guid}:bwd")
        dts = op.dtype.size
        # native total_io_bytes convention (ffs_graph.hpp): params +
        # every input + every output at the op's dtype width — the
        # byte half of the learned model's featurization
        io_bytes = float(op.params_elems()) * dts
        for s in op.input_shapes:
            io_bytes += float(math.prod(s)) * dts
        for s in op.output_shapes:
            io_bytes += float(math.prod(s)) * dts
        rows.append(dict(
            schema=CORPUS_SCHEMA_VERSION,
            guid=op.guid,
            name=op.name,
            type=op.op_type.name,
            out_shape=list(op.output_shapes[0]) if op.output_shapes else [],
            choice=st.choice if st is not None else None,
            # which kernel implementation executed the op; None for ops
            # with no registered kernel alternatives
            impl=impls.get(op.name),
            # priced terms are PER-CHIP SHARDED schedule durations;
            # measured fwd/bwd are WHOLE-OP unsharded profile seconds —
            # work_div is the strategy's split so consumers can compare
            # measured/work_div against priced fwd+bwd (compute only)
            work_div=work_division(node, ff.mesh),
            # featurization fields (op class x shape x choice x mesh):
            # whole-op analytic FLOPs/bytes; the trainer shards them by
            # work_div to match the per-chip pricing the DP queries
            flops=float(op.flops()),
            io_bytes=io_bytes,
            param_bytes=float(op.params_elems()) * dts,
            dtype_size=dts,
            mesh_axes=mesh_axes,
            priced=dict(p, source=sources.get(str(op.guid), "analytic")),
            measured=dict(
                fwd_s=mf, bwd_s=mb,
                source="measured" if mf is not None else None),
        ))
    return rows


def simtrace_report(ff, resp: Dict[str, Any],
                    measured: Optional[Dict[str, float]] = None,
                    resp_analytic: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    """The ``.simtrace.json`` payload: predicted step breakdown + the
    per-op corpus rows + the mesh the prediction assumed.

    ``resp_analytic``: a second simulation of the same strategy with the
    learned cost table disabled — when the active prediction used
    learned per-op costs, the analytic twin rides along so the obs
    report can show simulator accuracy analytic-vs-learned side by side
    (the SCALE-Sim-style tracked metric)."""
    from flexflow_tpu.obs.inspect import search_predictions

    rows = corpus_rows(ff, resp, measured=measured)
    src_census: Dict[str, int] = {}
    for r in rows:
        s = (r.get("priced") or {}).get("source") or "analytic"
        src_census[s] = src_census.get(s, 0) + 1
    report = dict(
        corpus_schema=CORPUS_SCHEMA_VERSION,
        predicted=predicted_totals(resp),
        search_predicted_s=search_predictions(ff)[
            "search_predicted_step_s"],
        mesh_axes=dict(zip(ff.mesh.axis_names, ff.mesh.devices.shape)),
        tasks=sum(1 for t in (resp.get("tasks") or [])
                  if float(t.get("finish", 0.0))
                  > float(t.get("start", 0.0))),
        # which model priced the compute terms, per op (the learned
        # cost model's engagement census: all-analytic when no trained
        # table is loaded / FFS_NO_LEARNED_COSTS is set)
        cost_sources=src_census,
        per_op=rows,
    )
    if resp_analytic is not None:
        report["predicted_analytic"] = dict(
            step_s=resp_analytic.get("iteration_time"),
            fwd_s=resp_analytic.get("fwd_time"),
            bwd_s=resp_analytic.get("bwd_time"),
            comm_s=resp_analytic.get("comm_time"),
            gradsync_s=resp_analytic.get("gradsync_time"),
        )
    return report


def write_simtrace(ff, tracer, align_ts_us: Optional[float] = None
                   ) -> Optional[Dict[str, Any]]:
    """Replay the compiled strategy through the native simulator, write
    the ``.simtrace.json`` artifact, and inject the predicted schedule
    as ``sim:`` Perfetto lanes into the tracer's export (must run BEFORE
    ``tracer.export()``).

    ``align_ts_us``: where on the tracer timeline the simulated step
    begins. Defaults to the start of the LAST traced step (steady state
    — never the compile-carrying first step) so the predicted lanes
    overlay a measured step in the merged view. Returns the simtrace
    report, or None when the tracer is inactive."""
    if not getattr(tracer, "active", False):
        return None
    from flexflow_tpu.obs.artifacts import write_artifact
    from flexflow_tpu.search.validate import simulate_strategy
    import os

    resp = simulate_strategy(ff)
    resp_analytic = None
    if any(v == "learned" for v in (resp.get("cost_sources") or {}).values()):
        # the prediction used learned per-op costs: simulate the same
        # strategy once more with the table disabled so the artifact
        # carries analytic-vs-learned accuracy side by side
        try:
            resp_analytic = simulate_strategy(ff, learned=False)
        except Exception:
            resp_analytic = None
    report = simtrace_report(ff, resp, resp_analytic=resp_analytic)
    if align_ts_us is None:
        align_ts_us = tracer.last_step_start_us() or 0.0
    name_of = {i: n.op.name for i, n in enumerate(ff.executor.nodes)}
    events = sim_lane_events(resp.get("tasks") or [], name_of,
                             t0_us=align_ts_us)
    if events:
        tracer.add_trace_events(events, dict(SIM_LANE_THREADS))
    stem = os.path.join(tracer.trace_dir, tracer.file_stem)
    write_artifact(stem + ".simtrace.json", report,
                   host_id=tracer.host_id, kind="simtrace",
                   header_extra=dict(run_name=tracer.run_name,
                                     run_seq=tracer.run_seq))
    return report
