"""Priced-vs-emitted collective validation.

SURVEY §7 hard-part 3 / VERDICT r3 Next #3: the native simulator prices a
set of collectives for a strategy (reshard / psum / all-gather / ring /
gradient all-reduce); GSPMD independently decides which collectives the
compiled step actually contains. This module extracts both sides so tests
can assert they agree — and alert on collectives XLA inserted that the
simulator never charged (the classic way a searched strategy silently
underperforms its prediction).

Emitted side: lower + compile the jitted train step on the live mesh and
scan the optimized HLO for collective ops, summing payload bytes by kind.
Priced side: replay the searched assignment through the native simulator
(ffs_simulate), whose SimTasks now carry (collective, bytes).
"""

from __future__ import annotations

from collections import defaultdict
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

from flexflow_tpu.obs.inspect import PRICED_MIN_BYTES, collective_census

# kind normalization: HLO op -> the simulator's collective vocabulary
_HLO_KINDS = {
    "all-reduce": "allreduce",
    "reduce-scatter": "allreduce",      # ar decomposition half
    "all-gather": "allgather",
    "collective-permute": "ppermute",
    "all-to-all": "reshard",
}

# which priced kinds cover an emitted (or statically-inferred) kind —
# the ONE definition shared by diff_collectives and the fflint
# collective-inference pass, so the two layers can never classify the
# same collective differently. An emitted all-gather is covered by a
# priced allreduce because XLA decomposes large ARs into reduce-scatter
# + all-gather (observed on the dp_head psum at the residual add — the
# RS half keeps the 'allreduce' bucket, the AG half lands here);
# 'reshard' prices cover permute/all-to-all layout changes.
COLLECTIVE_COVER = {
    "allreduce": {"allreduce"},
    "allgather": {"allgather", "reshard", "allreduce"},
    "ppermute": {"ppermute", "reshard"},
    "reshard": {"reshard", "allgather", "ppermute"},
}


def emitted_collectives(hlo_text: str, min_bytes: float = PRICED_MIN_BYTES
                        ) -> Dict[str, float]:
    """Collective kind -> summed payload bytes in the optimized HLO.

    A normalization of the obs collective census onto the simulator's
    vocabulary. Byte counting uses each op's OUTPUT shape
    (per-partition in the SPMD module). Ops below ``min_bytes`` are
    ignored (loss/metric scalar reductions the simulator deliberately
    does not price); async -start/-done pairs count once.
    """
    out: Dict[str, float] = defaultdict(float)
    for kind, entry in collective_census(hlo_text,
                                         min_bytes=min_bytes).items():
        out[_HLO_KINDS.get(kind, kind)] += entry["bytes"]
    return dict(out)


def compiled_train_step(ff):
    """Lower + compile the model's jitted train step on the live mesh."""
    ex = ff.executor
    rs = np.random.RandomState(0)
    xs = []
    for t in ff.input_tensors:
        xs.append(rs.randn(*t.shape).astype(np.float32))
    inputs = ff._stage_inputs(xs)
    # label shape: match the designated output
    out_shape = None
    for node in ex.nodes:
        if node.op.guid == ex.final_ref[0]:
            out_shape = node.op.output_shapes[ex.final_ref[1]]
    labels = ff._shard_batch(rs.randn(*out_shape).astype(np.float32))
    step = ex.make_train_step()
    lowered = step.lower(ff.params, ff.opt_state, ff.state, inputs, labels,
                         jax.random.PRNGKey(0))
    return lowered.compile()


def train_step_hlo(ff) -> str:
    """Lower + compile the model's train step; return optimized HLO text."""
    return compiled_train_step(ff).as_text()


def compiled_footprint_bytes(compiled) -> float:
    """Per-device peak the HBM budget must cover: live arguments
    (params + optimizer state + staged batch, resident for the whole
    step) plus XLA's temp allocation. Single definition shared by the
    validator and scripts/calibrate.py."""
    ma = compiled.memory_analysis()
    return float(getattr(ma, "argument_size_in_bytes", 0)
                 + getattr(ma, "temp_size_in_bytes", 0))


def predicted_vs_actual_memory(ff) -> Dict[str, float]:
    """Search-predicted per-device memory vs XLA's compiled memory
    analysis of the train step (SURVEY §7 hard-part 4 / VERDICT r4 #6).

    `actual` counts live arguments (params + optimizer state + staged
    batch, all resident for the step) plus XLA's temp allocation — the
    per-device peak the HBM budget actually has to cover. Requires a
    search-compiled model (compile with search_budget > 0) so
    `search_info["predicted_memory"]` exists.
    """
    info = ff.search_info if isinstance(ff.search_info, dict) else {}
    predicted = info.get("predicted_memory")
    if not predicted:
        raise ValueError(
            "predicted_vs_actual_memory needs a search-compiled model "
            "(set search_budget so predicted_memory is recorded)")
    actual = compiled_footprint_bytes(compiled_train_step(ff))
    return dict(predicted=float(predicted), actual=actual,
                ratio=actual / float(predicted))


def simulate_strategy(ff, learned: Any = "auto") -> Dict[str, Any]:
    """Replay the strategy FFModel.compile selected through the native
    simulator; returns the FULL response — iteration_time / memory /
    fwd/bwd/comm/gradsync breakdown plus the scheduled task list
    (per-task start/finish seconds and collective census records). The
    task schedule is what ``obs/simtrace.py`` renders as the predicted
    Perfetto timeline next to the measured device lanes.

    ``learned``: "auto" (default) prices with the same discovered
    learned cost table the search used (so replayed predictions match
    searched ones); False forces pure analytic pricing (the
    analytic-vs-learned accuracy comparison's control arm); an explicit
    native-table dict uses that table."""
    from flexflow_tpu.parallel.choice import Choice
    from flexflow_tpu.search.native import native_simulate
    from flexflow_tpu.search.unity import machine_to_json, serialize_graph

    if learned == "auto":
        try:
            from flexflow_tpu.costmodel import load_native_table
            learned = load_native_table()
        except Exception:
            learned = None
    elif not learned:
        learned = None

    nodes = ff.executor.nodes
    # replay what the executor EXECUTES, not what the DP picked: the
    # plan sets each choice's suffixes to the runtime state. The native
    # side falls back along the suffix lattice when an op spawns no
    # matching twin.
    plan = ff.executor.plan
    assignment = {}
    for node in nodes:
        st = (ff.strategy or {}).get(node.op.guid)
        searched = (st.parsed if st is not None and st.choice is not None
                    else Choice.parse(_infer_choice(node, st)))
        assignment[str(node.op.guid)] = str(
            plan.executed_choice(node, searched))
    axes = dict(zip(ff.mesh.axis_names, ff.mesh.devices.shape))
    req = dict(
        nodes=serialize_graph(
            nodes, final_guid=ff.executor.final_ref[0],
            act_dtype_size=ff.executor.compute_dtype.dtype.itemsize),
        machine=machine_to_json(ff.machine_spec, ff.mesh.devices.size,
                                learned=learned),
        config=dict(training=True, overlap=True,
                    opt_state_factor=getattr(ff.config, "opt_state_factor",
                                             2.0)),
        mesh={"data": axes.get("data", 1), "model": axes.get("model", 1),
              "seq": axes.get("seq", 1), "expert": axes.get("expert", 1),
              "pipe": axes.get("pipe", 1)},
        assignment=assignment,
        measured={},
    )
    if axes.get("pipe", 1) > 1:
        # pipe meshes replay through simulate_pipeline: ship the detected
        # repeated-block metadata plus the executor's actual microbatch
        # count / schedule / queue layout so the priced census matches
        # the program the lowering emits
        from flexflow_tpu.parallel.pipeline_detect import pipeline_meta_json
        ex = ff.executor
        req["pipeline"] = dict(
            pipeline_meta_json(nodes, ex.pb),
            microbatches=int(ex.microbatches),
            schedule=ex.schedule,
            shard_queue=bool(ex.shard_queue))
    return native_simulate(req)


def priced_collectives(ff, min_bytes: float = 1 << 12) -> Dict[str, float]:
    """Collective kind -> summed bytes the native simulator charged for
    the strategy FFModel.compile selected."""
    resp = simulate_strategy(ff)
    out: Dict[str, float] = defaultdict(float)
    for t in resp.get("tasks", []):
        if t.get("collective") and t.get("bytes", 0) >= min_bytes:
            out[t["collective"]] += t["bytes"]
    return dict(out)


def _infer_choice(node, st) -> str:
    """Native choice name for a heuristic (non-searched) strategy entry,
    derived from its PartitionSpecs — so explicit-mesh strategies (e.g.
    ring attention over a user mesh) can be replayed through the
    simulator. Mirrors the naming in native/ffs_strategy.hpp
    enumerate_choices."""
    from flexflow_tpu.ffconst import OperatorType

    specs = (st.output_specs if st is not None else None) or []
    entries = list(specs[0]) if specs and specs[0] is not None else []
    base = "dp" if entries and entries[0] == "data" else "rep"
    params = (st.param_specs if st is not None else None) or {}
    kspec = params.get("kernel")
    if kspec is not None and "model" in tuple(kspec):
        if node.op.op_type == OperatorType.LINEAR:
            base = "dp_col" if base == "dp" else "col"
    wq = params.get("wq")
    if wq is not None and tuple(wq) and tuple(wq)[0] == "model":
        base = "dp_head" if base == "dp" else "head"
    if "seq" in entries:
        suffix = ("_ring" if node.op.op_type ==
                  OperatorType.MULTIHEAD_ATTENTION else "_sp")
        base += suffix
    return base


def diff_collectives(priced: Dict[str, float], emitted: Dict[str, float],
                     tol_factor: float = 3.0) -> List[str]:
    """Discrepancy report. Empty list = the priced set covers what XLA
    emitted (within tol_factor on bytes) and vice versa.

    reduce-scatter counts toward allreduce (XLA decomposes big ARs);
    'reshard' prices cover permute/all-to-all layout changes, so emitted
    ppermute/all-to-all match priced 'reshard' too.
    """
    problems = []
    cover = COLLECTIVE_COVER
    for kind, eb in emitted.items():
        pb = sum(priced.get(k, 0.0) for k in cover.get(kind, {kind}))
        if pb <= 0:
            problems.append(
                f"XLA emitted {kind} ({eb / 1e6:.2f} MB) but the simulator "
                f"priced none")
        elif eb > pb * tol_factor:
            problems.append(
                f"{kind}: emitted {eb / 1e6:.2f} MB vs priced "
                f"{pb / 1e6:.2f} MB (> {tol_factor}x)")
    for kind, pb in priced.items():
        eb = sum(emitted.get(k, 0.0) for k in cover.get(kind, {kind}))
        if eb <= 0 and pb > (1 << 16):
            problems.append(
                f"simulator priced {kind} ({pb / 1e6:.2f} MB) but XLA "
                f"emitted none")
    return problems
