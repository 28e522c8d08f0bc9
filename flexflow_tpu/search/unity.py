"""Unity-style graph optimization: op graph → (mesh shape, per-op shardings).

The Python half of the search stack: serialize the materialized op graph
(analog of the PCG handed to Graph::graph_optimize_task,
src/runtime/graph.cc:2047) to the native core, decode the returned strategy
into PartitionSpecs, and provide strategy file export/import
(--export-strategy / --import-strategy, reference config.h:141-142).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from flexflow_tpu.ffconst import CompMode, OperatorType
from flexflow_tpu.ops.base import DimRole, exported_reads
from flexflow_tpu.parallel.strategy import OpStrategy, Strategy


def _param_shapes(op) -> Dict[str, List[int]]:
    """Parameter name → shape, without materializing arrays."""
    try:
        tree = jax.eval_shape(op.init_params, jax.random.PRNGKey(0))
    except Exception:
        return {}
    return {k: list(v.shape) for k, v in tree.items()}


def _node_attrs(op) -> Dict[str, Any]:
    attrs = {}
    for k in ("num_heads", "num_kv_heads", "groups", "axis", "out_dim",
              "k", "n", "n_experts", "hidden_size", "alpha",
              "out_channels", "dropout"):
        v = getattr(op, k, None)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            attrs[k] = v
    # a head width that is not the split of the model width (the flash
    # gate prices the kernel's real tile), and what an op with a wide
    # interior keeps for its backward pass (remat gate, memory)
    if (getattr(op, "head_dim", None) and getattr(op, "embed_dim", None)
            and op.head_dim != op.embed_dim // op.num_heads):
        attrs["head_dim"] = int(op.head_dim)
    # latent attention: the rotated lanes a head's query and key carry
    # beside `head_dim` (the flash gate's two-part score)
    if getattr(op, "rope_dim", 0):
        attrs["rope_head_dim"] = int(op.rope_dim)
    # a sliding window that hides something: the scores an attention op
    # forms (and einsum keeps) are S x window, not S^2
    if getattr(op, "windowed", False):
        attrs["window"] = int(op.window)
    # a block-diffusion mask: the keys a query meets, on average, are the
    # visible pairs over the queries (a quarter of the sequence and a few)
    if getattr(op, "block_diffusion", None):
        attrs["keys_seen"] = -(-op.visible_pairs // op.input_shapes[0][1])
    if hasattr(op, "interior_bytes"):
        attrs["interior_bytes"] = float(op.interior_bytes())
    # learned sparse attention: the mask (a byte a pair) and the
    # indexer's operands are what the forward keeps beside its output
    if getattr(op, "sparse_index", None):
        attrs["interior_bytes"] = float(op.sparse_saved_bytes())
    # conv/pool geometry (stored as (h, w) tuples on the op): needed so a
    # rewrite that re-emits the op (Conv+BN fold) replays into a real
    # Conv2D
    for name, keys in (("kernel", ("kernel_h", "kernel_w")),
                       ("stride", ("stride_h", "stride_w")),
                       ("padding", ("padding_h", "padding_w"))):
        v = getattr(op, name, None)
        if isinstance(v, tuple) and len(v) == 2:
            attrs[keys[0]], attrs[keys[1]] = int(v[0]), int(v[1])
    # explicit mesh-axis name of a Repartition (repartition(axis=...)) —
    # mesh enumeration pins the NAMED axis, not the dim-derived default
    mesh_axis = getattr(op, "axis", None)
    if isinstance(mesh_axis, str):
        attrs["mesh_axis"] = mesh_axis
    # BatchNorm's fused relu flag (PM_RELU in the substitution engine)
    relu = getattr(op, "relu", None)
    if isinstance(relu, bool):
        attrs["relu"] = int(relu)
    # FusedParallelOp step chain (4th element: the step's mesh-axis name,
    # so the native cost model prices the axis the executor uses)
    fused = getattr(op, "fused_ops", None)
    if fused:
        attrs["ops"] = [[k.name if hasattr(k, "name") else str(k),
                         int(d), int(g)] + ([a] if isinstance(a, str)
                                            else [])
                        for (k, d, g, a) in fused]
    # the substitution engine matches on these (PM_* keys, ffs_subst.hpp)
    act = getattr(op, "activation", None)
    if act is not None and hasattr(act, "value"):
        attrs["activation"] = int(act.value)
    use_bias = getattr(op, "use_bias", None)
    if isinstance(use_bias, bool):
        attrs["use_bias"] = int(use_bias)
    for prefix in ("repartition", "combine", "reduction"):
        d = getattr(op, f"{prefix}_dim", None)
        if d is not None:
            attrs["dim"] = int(d)
        g = getattr(op, f"{prefix}_degree", None)
        if g is not None:
            attrs["degree"] = int(g)
    rdeg = getattr(op, "replicate_degree", None)
    if rdeg is not None:
        attrs["degree"] = int(rdeg)
    sizes = getattr(op, "sizes", None)
    if sizes is not None:
        attrs["sizes"] = [int(s) for s in sizes]
    return attrs


def executed_kernel_choices(nodes, strategy, mesh_axes,
                            training: bool = False,
                            recorded=None) -> Dict[str, str]:
    """{op name -> kernel impl} a node list will EXECUTE: the searched
    kernel of the op's choice wins, then the executor's ``recorded``
    kernel choices; attention ops with neither report their static
    dispatch (``selected_impl`` — ring/flash/einsum on this platform at
    these shapes). The ONE extraction the serve bucket reports, the
    bench provenance column and the corpus rows share, so the recorded
    impls cannot drift between surfaces."""
    out: Dict[str, str] = {}
    for node in nodes:
        st = (strategy or {}).get(node.op.guid)
        if st is not None and st.parsed.kernel is not None:
            out[node.op.name] = st.parsed.kernel
        elif node.op.name in (recorded or {}):
            out[node.op.name] = recorded[node.op.name]
        elif hasattr(node.op, "selected_impl"):
            try:
                out[node.op.name] = node.op.selected_impl(
                    mesh_axes, training=training)
            except Exception:
                pass
    return out


def serialize_graph(nodes, final_guid: Optional[int] = None,
                    act_dtype_size: Optional[int] = None
                    ) -> List[Dict[str, Any]]:
    """``act_dtype_size``: the element size of what an op's forward
    leaves for its backward pass, where it is not the op's own (2 under
    mixed precision: bfloat16 activations beside float32 leaves); the
    native memory terms count saved outputs and interiors at it."""
    from flexflow_tpu.layout import train_fusable_conv_guids
    from flexflow_tpu.search.rewrite import external_input_ids
    neg_of = external_input_ids(nodes)
    # ops no rewrite may re-form (ffs_subst.hpp `pinned`): the readers of
    # another op's leaves and their owners (a rewritten op would hold
    # leaves of its own, under a new name), a full-precision product
    shared = {owner for n in nodes for owner, _ in
              getattr(n.op, "tied_params", {}).values()}
    # and the two ends of a tensor one op makes for other layers to read
    # (`Op.exports`): a rewrite that re-formed the producer or a reader
    # would drop the edge, and a remat twin of the producer would price
    # as freed a tensor that lives to its last reader's backward
    reads_exported = {reader for reader, _ in exported_reads(nodes)[1]}
    # conv guids whose sole consumer is a foldable BatchNorm — the
    # legality the native "_k:conv_bn_fused" kernel twin gates on
    # (shipped as a node attr: the gate is a GRAPH property the native
    # per-node enumeration cannot re-derive). ``final_guid`` excludes
    # the designated model output exactly as the executor's
    # fuse_conv_bn_train does — the search must never price a fusion
    # the executor refuses.
    bn_fusable = train_fusable_conv_guids(
        nodes, keep_guids=() if final_guid is None else {final_guid})
    out = []
    for node in nodes:
        op = node.op
        inputs = []
        for ref in node.input_refs:
            if ref[0] == "op":
                inputs.append([ref[1], ref[2]])
            else:  # graph input staged from host — unique negative guid so
                   # substitution patterns can bind distinct externals
                inputs.append([neg_of[tuple(ref)], 0])
        roles = [[r.value for r in rr] for rr in op.output_dim_roles()]
        attrs = _node_attrs(op)
        if op.guid in bn_fusable:
            attrs["bn_fusable"] = 1
        if (op.name in shared or getattr(op, "tied_params", None)
                or getattr(op, "full_precision", False)
                or op.exports or op.guid in reads_exported):
            attrs["pinned"] = 1
        if op.exports:
            attrs["exports"] = int(op.exports)
        if getattr(op, "aliased_outputs", 0):
            # its last outputs are inputs handed through: no bytes
            attrs["aliased_outputs"] = int(op.aliased_outputs)
        if (getattr(op, "differential", False)
                or getattr(op, "sparse_index", None)
                or op.op_type in (OperatorType.DELTA_MIXER,
                                  OperatorType.HC_PRE)):
            # its lambda (the indexer's loss and the counts of pairs)
            # leaves the step beside its output
            attrs["side_counters"] = 1
        out.append(dict(
            guid=op.guid,
            type=op.op_type.name,
            name=op.name,
            inputs=inputs,
            input_shapes=[list(s) for s in op.input_shapes],
            output_shapes=[list(s) for s in op.output_shapes],
            roles=roles,
            params=_param_shapes(op),
            flops=float(op.flops()),
            dtype_size=op.dtype.size,
            act_dtype_size=min(act_dtype_size or op.dtype.size,
                               op.dtype.size),
            attrs=attrs,
        ))
    return out


def machine_to_json(spec, num_devices: int,
                    comm_bytes_factor: float = 1.0,
                    learned: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    """``learned``: the trained cost-model coefficient table
    (flexflow_tpu/costmodel ``native_table()``) the native evaluator
    prices covered op classes with; None (the default and the
    FFS_NO_LEARNED_COSTS state) keeps pure analytic pricing —
    bit-identical to pre-costmodel behavior."""
    # arbitrary inter-slice fabrics: ship the RAW per-pair link matrix —
    # the native pricer applies the bottleneck-link rule per collective
    # SPAN (MachineModel::dcn_ring), so a 2-slice collective on a fabric
    # whose far link is slow prices at the near pair's bandwidth instead
    # of the global collapse. The scalar (dcn_bw, dcn_latency) stays the
    # uniform fallback; without links, effective_dcn() returns it as-is.
    dcn_links = list(getattr(spec, "dcn_links", None) or [])
    if dcn_links:
        dcn_bw, dcn_latency = spec.dcn_bw, spec.dcn_latency
    else:
        dcn_bw, dcn_latency = (spec.effective_dcn()
                               if hasattr(spec, "effective_dcn")
                               else (spec.dcn_bw, spec.dcn_latency))
    out = dict(
        num_devices=num_devices,
        flops=spec.flops,
        hbm_bw=spec.hbm_bw,
        hbm_cap=spec.hbm_cap,
        ici_bw=spec.ici_bw,
        ici_latency=spec.ici_latency,
        dcn_bw=dcn_bw,
        dcn_latency=dcn_latency,
        num_slices=spec.num_slices,
        mxu_efficiency=getattr(spec, "mxu_efficiency", 0.55),
        # conv-class asymptote (ffs_strategy.hpp node_cost): predicted
        # conv times track the measured conv-vs-matmul efficiency gap
        # instead of assuming matmul-grade MXU utilization
        conv_efficiency=getattr(spec, "conv_efficiency", 0.35),
        min_op_time=getattr(spec, "min_op_time", 5e-7),
        # per-bucket launch cost of the bucketed async gradient sync —
        # the term that stops the '_ovl' bucket sweep from degenerating
        # to infinitely many tiny buckets (ffs_machine.hpp)
        collective_launch_overhead=getattr(spec, "collective_launch_overhead",
                                           2e-6),
        # bf16 activations/grads under mixed precision: collectives move
        # half the nominal f32 bytes (ffs_machine.hpp comm_bytes_factor)
        comm_bytes_factor=comm_bytes_factor,
        # per-slice ICI torus extents — drives the native model's
        # per-axis ring pricing (ffs_machine.hpp assign_torus)
        torus=[int(t) for t in getattr(spec, "torus", None) or []],
    )
    if dcn_links:
        out["dcn_links"] = [[int(a), int(b), float(bw)]
                            for a, b, bw in dcn_links]
    if learned:
        out["learned"] = learned
    return out


def _entries_to_spec(entries: List[Optional[Any]]) -> P:
    while entries and entries[-1] is None:
        entries = entries[:-1]
    return P(*entries)


def decode_strategy(resp: Dict[str, Any], nodes) -> Tuple[Dict[str, int], Strategy]:
    mesh_axes = {k: int(v) for k, v in resp["mesh"].items() if int(v) > 1}
    if not mesh_axes:
        mesh_axes = {"data": 1}
    valid = set(mesh_axes)
    strategy: Strategy = {}
    for node in nodes:
        oj = resp["ops"].get(str(node.op.guid))
        if oj is None:
            continue
        def _entry(e):
            # "data+model": 2-D sample partition -> a PartitionSpec tuple
            # entry over both axes (sample parallelism, config.h:134)
            if e == "data+model":
                axes = tuple(a for a in ("data", "model") if a in valid)
                return axes if len(axes) > 1 else (axes[0] if axes else None)
            return e if e in valid else None

        outs = []
        for entries in oj["outputs"]:
            outs.append(_entries_to_spec([_entry(e) for e in entries]))
        params = {}
        # the native side enumerates param specs from the op TYPE (e.g. a
        # Linear always gets kernel+bias entries); filter against the
        # parameters the materialized op actually owns, or a bias-less
        # rewrite-fused Linear carries a phantom 'bias' spec forever
        # (fflint FFL103)
        owned = _param_shapes(node.op)
        for pname, entries in oj.get("params", {}).items():
            if owned and pname not in owned:
                continue
            params[pname] = _entries_to_spec([_entry(e) for e in entries])
        strategy[node.op.guid] = OpStrategy(
            output_specs=outs, param_specs=params, choice=oj.get("choice"))
    return mesh_axes, strategy


def graph_optimize(nodes, machine_spec, config, num_devices: int,
                   measured: Optional[Dict[str, float]] = None,
                   batch: int = 0,
                   final_ref: Optional[Tuple[int, int]] = None,
                   ) -> Tuple[Dict[str, int], Strategy, Dict[str, Any]]:
    """Run the native Unity search. Returns (mesh_axes, strategy, info).

    When the substitution engine rewrites the graph, ``info`` carries
    ``rewritten_nodes`` (the new OpNode list the strategy is keyed to) and
    ``final_ref`` (where the designated output moved).

    Raises RuntimeError/ImportError when the native core is unavailable —
    callers fall back to the data-parallel default, matching the
    reference's --only-data-parallel escape hatch.
    """
    from flexflow_tpu.search.native import native_optimize

    rules: List[Any] = []
    subst_rules = None
    if (not config.substitution_json
            and getattr(config, "enable_substitution", True)):
        # default shipped corpus (analog of the reference loading
        # substitutions/graph_subst_3_v2.json at search start)
        default = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            "substitutions", "ffs_subst_v1.json")
        if os.path.exists(default):
            try:
                with open(default) as f:
                    subst_rules = json.load(f)
            except (OSError, ValueError):
                subst_rules = None
    if config.substitution_json:
        # an explicitly-requested rules file must fail loudly (ValueError is
        # not in compile()'s fallback set, so a bad path/contents aborts
        # instead of silently degrading to data-parallel)
        try:
            with open(config.substitution_json) as f:
                data = json.load(f)
        except OSError as e:
            raise ValueError(
                f"--substitution-json {config.substitution_json}: {e}") from e
        if isinstance(data, dict) and "rules" in data:
            # native per-op choice filters ({"rules": [{op_type, allow}]})
            rules = data["rules"]
        else:
            # graph-rewrite rule corpus: the reference RuleCollection
            # format ({"rule": [...]}, substitution_loader.cc) or this
            # repo's native list-of-rules form
            subst_rules = data
    threshold = 0
    mem_correction = 1.0
    if config.memory_search and config.memory_threshold_mb:
        threshold = config.memory_threshold_mb * (1 << 20)
    elif config.memory_search:
        threshold = config.memory_per_chip_mb * (1 << 20)
    if threshold:
        # calibrated predicted->actual memory correction (SURVEY §7 hard
        # part 4): when the chip's XLA footprint runs `corr`x the
        # simulator's prediction, the DP must aim for budget/corr so the
        # ACTUAL bytes fit
        mem_correction = _memory_correction()
        if mem_correction > 1.0:
            threshold /= mem_correction
    # mixed precision (TPU): activations + grads move in bf16 — halve the
    # collective payloads the cost model prices (matches the executor's
    # master-weight regime; CPU/f32 machines keep 1.0)
    mixed = (getattr(config, "allow_mixed_precision", True)
             and machine_spec.chip != "cpu-sim")
    comm_factor = 0.5 if mixed else 1.0
    # learned per-op-class cost table (flexflow_tpu/costmodel): trained
    # COSTMODEL.json coefficients the DP queries where coverage exists,
    # analytic fallback elsewhere. None (no trained model, platform
    # mismatch, or FFS_NO_LEARNED_COSTS) keeps pre-costmodel pricing.
    try:
        from flexflow_tpu.costmodel import load_native_table
        learned = load_native_table()
    except Exception:
        learned = None
    # provenance is about THIS graph, not the table: a model whose
    # classes never intersect the graph's op types prices nothing here
    # (everything stays analytic), and claiming "learned" would both
    # misreport and suppress fflint's all-analytic FFL701 warning
    graph_types = {n.op.op_type.name for n in nodes}
    learned_classes = sorted(
        c for c in set((learned or {}).get("classes") or ())
        # per-impl classes ("TYPE:impl", the searched kernel dimension)
        # cover a graph exactly when their base type appears in it
        if c.split(":", 1)[0] in graph_types)
    request = dict(
        nodes=serialize_graph(
            nodes,
            final_guid=final_ref[0] if final_ref is not None else None,
            # ... and every op's forward leaves bf16 for its backward
            # pass (a looped model's step is saved activations first:
            # 24 layer applications over one set of leaves)
            act_dtype_size=2 if mixed else None),
        machine=machine_to_json(machine_spec, num_devices,
                                comm_bytes_factor=comm_factor,
                                learned=learned),
        config=dict(
            budget=config.search_budget,
            alpha=config.search_alpha,
            only_data_parallel=config.only_data_parallel,
            enable_parameter_parallel=config.enable_parameter_parallel
                or config.enable_attribute_parallel,
            overlap=config.search_overlap_backward_update,
            # CompMode.INFERENCE (ffconst.h:46): forward-only cost model —
            # no backward tasks, no gradient sync, no opt-state memory
            training=getattr(config, "computation_mode",
                             CompMode.TRAINING) == CompMode.TRAINING,
            memory_threshold=threshold,
            # the refinement's random walk has a seed of its own (the
            # native default): the same graph on the same machine gets the
            # same strategy whatever seeds the weights. With `config.seed`
            # here, runs of one four-chip cell that differed only in
            # their data seed came back as {data:4}, as {data:2,model:2}
            # and with one op off the `_ovl` lattice (chip runs, PR 27)
            seed=0,
            batch=batch,
            rules=rules,
            enable_substitution=getattr(config, "enable_substitution", True),
            enable_sample_parallel=getattr(config, "enable_sample_parallel",
                                           True),
            # optimizer-state copies (0 SGD / 1 momentum / 2 Adam), set by
            # FFModel.compile from the actual optimizer
            opt_state_factor=getattr(config, "opt_state_factor", 2.0),
            enable_pipeline_parallel=getattr(
                config, "enable_pipeline_parallel", True),
            pipeline_microbatches=getattr(
                config, "pipeline_microbatches", 0),
            # 'auto' lets the simulator price gpipe vs circular per mesh
            # (the schedule is a searched dimension, ffs_sim.hpp)
            pipeline_schedule=getattr(config, "pipeline_schedule", "auto"),
            # --pipeline-replicated-queue: price the queue layout the
            # lowering will actually emit (memory model differs by ~pp)
            pipeline_shard_queue=getattr(config, "pipeline_shard_queue",
                                         True),
            # --disable-fusion: gate the fuse_parallel_ops rewrite family
            # (kernel fusion itself belongs to XLA)
            perform_fusion=getattr(config, "perform_fusion", True),
            # weight-update sharding as a searched dimension: "auto"/"on"
            # enumerate the reduce-scatter+all-gather "_wus" choice twins
            # (ffs_strategy.hpp); "off" removes them
            weight_update_sharding=getattr(config, "weight_update_sharding",
                                           "auto"),
            # comms-compute overlap as a searched dimension: anything but
            # off/0 enumerates the '_ovl' latency-hiding choice twins
            # whose gradient sync is priced as bucketed async collectives
            # hidden under remaining backward compute (ffs_strategy.hpp)
            comm_overlap=("off" if str(getattr(
                config, "overlap_bucket_mb", "auto")).lower() in ("0", "off")
                else "auto"),
            # kernel-implementation choice as a searched dimension
            # (ISSUE 15): "auto" enumerates the "_k:<impl>" twins
            # (flash attention / fused optimizer update / train-time
            # Conv+BN); "off" or FFS_NO_KERNEL_SEARCH removes the
            # dimension — searches then reproduce pre-kernel-search
            # results bit-identically
            kernel_search=("off" if (
                str(getattr(config, "kernel_search", "auto")).lower()
                == "off" or os.environ.get("FFS_NO_KERNEL_SEARCH"))
                else "auto"),
            # rematerialization as a searched dimension (ISSUE 20):
            # "auto" spawns the "_r" remat choice twins (checkpoint the
            # op, recompute its interior in backward) and the pipeline
            # block-body remat dimension; "off" or FFS_NO_REMAT removes
            # the dimension — searches then reproduce pre-remat-search
            # results bit-identically
            remat_search=("off" if (
                str(getattr(config, "remat_search", "auto")).lower()
                == "off" or os.environ.get("FFS_NO_REMAT"))
                else "auto"),
            # search provenance: per-mesh candidates + rejection reasons,
            # frontier-DP evolution, per-op candidate cost table
            # (--search-trace / FFS_SEARCH_TRACE; explain.py sets it too)
            emit_search_trace=bool(getattr(config, "search_trace", False)
                                   or os.environ.get("FFS_SEARCH_TRACE")),
        ),
        measured=measured or {},
    )
    # repeated-block pipeline metadata: lets the native search enumerate
    # 'pipe' meshes (GPipe cost model, native/ffs_sim.hpp)
    pipe_blocks = None
    if getattr(config, "enable_pipeline_parallel", True):
        from flexflow_tpu.parallel.pipeline_detect import (
            detect_repeated_blocks, pipeline_meta_json)
        pipe_blocks = detect_repeated_blocks(nodes)
        if pipe_blocks is not None:
            request["pipeline"] = pipeline_meta_json(nodes, pipe_blocks)
    if subst_rules is not None:
        request["subst_rules"] = subst_rules
    if final_ref is not None:
        request["final"] = [int(final_ref[0]), int(final_ref[1])]
    # search introspection (reference's RecursiveLogger around the DP —
    # graph.cc's get_logger() tree); on by --profiling or FF_LOG_SEARCH
    from flexflow_tpu.utils.logger import RecursiveLogger
    log = RecursiveLogger("unity", enabled=bool(
        getattr(config, "profiling", False)
        or os.environ.get("FF_LOG_SEARCH")))
    with log.enter(f"graph_optimize: {len(nodes)} ops on "
                   f"{num_devices} devices"):
        resp = native_optimize(request)
        stats = resp.get("stats", {})
        with log.enter(f"searched {stats.get('mesh_candidates')} meshes, "
                       f"{stats.get('states_explored')} DP states, "
                       f"{stats.get('rules_loaded')} rules"):
            for rw in resp.get("rewrites", []):
                log.info(f"rewrite {rw['rule']}: removed {rw['removed']}, "
                         f"added {[a['name'] for a in rw['added']]}")
        log.info(f"best mesh {resp.get('mesh')} predicted "
                 f"{resp.get('predicted_time', 0) * 1e3:.3f} ms "
                 f"({stats.get('rewrites_applied', 0)} rewrites)")
    new_nodes = nodes
    new_final = final_ref
    if resp.get("rewrites"):
        from flexflow_tpu.search.rewrite import apply_rewrites
        new_nodes, new_final = apply_rewrites(nodes, resp["rewrites"],
                                              final_ref)
    mesh_axes, strategy = decode_strategy(resp, new_nodes)
    # the search OBJECTIVE is part of the answer's provenance: TRAINING
    # minimizes simulated step time (fwd+bwd+update+sync), INFERENCE
    # minimizes simulated per-batch latency (forward only, no gradient
    # sync / '_wus' / opt-state terms) — the serving engine records it
    # per batch bucket and the strategy/search-trace artifacts carry it
    training_mode = request["config"]["training"]
    objective = "step_time" if training_mode else "latency"
    info = dict(predicted_time=resp.get("predicted_time"),
                predicted_memory=resp.get("predicted_memory"),
                memory_correction=mem_correction,
                objective=objective,
                # cost-model provenance: which pricing regime the search
                # ran under, and (when learned) which of this GRAPH's op
                # classes the trained table covered — fflint's staleness
                # lint and the strategy artifacts read this
                cost_model="learned" if learned_classes else "analytic",
                stats=resp.get("stats", {}),
                rewrites=resp.get("rewrites", []))
    if learned_classes:
        info["learned_cost_classes"] = learned_classes
    if resp.get("search_trace"):
        trace = dict(resp["search_trace"])
        trace.setdefault("objective", objective)
        info["search_trace"] = trace
    if resp.get("overlap"):
        # byte-weighted winning bucket size across the '_ovl' choices —
        # the searched value --overlap-bucket-mb 'auto' follows
        info["overlap"] = resp["overlap"]
    if resp.get("pipeline") and mesh_axes.get("pipe", 1) > 1:
        # the search picked a GPipe strategy: hand compile() what the
        # lowering onto pipeline_spmd needs (rewrites never fire together
        # with pipe meshes — block identity would break — so the detected
        # blocks are still valid for new_nodes == nodes)
        info["pipeline"] = dict(resp["pipeline"], blocks=pipe_blocks)
    if new_nodes is not nodes:
        info["rewritten_nodes"] = new_nodes
        info["final_ref"] = new_final
        # static rewrite verification (FFL213): the accepted rewrite's
        # post-rewrite edge-spec map must be collective-equivalent-or-
        # cheaper than the pre-rewrite map under the same strategy —
        # a substitution that wins on op-local simulated terms while
        # opening a reshard seam is caught here, before compile
        from flexflow_tpu.analysis.dataflow import verify_rewrite_dataflow
        try:
            info["rewrite_verification"] = verify_rewrite_dataflow(
                nodes, new_nodes, strategy, dict(mesh_axes),
                rewrites=resp.get("rewrites", []))
        except Exception as e:  # never let verification break the search
            info["rewrite_verification"] = dict(
                ok=True, findings=[], error=repr(e))
    return mesh_axes, strategy, info


def _memory_correction() -> float:
    """Median actual/predicted memory ratio from CALIBRATION.json's
    per-model `mem_ratio` rows (written by scripts/calibrate.py), 1.0
    when no calibration exists. FFS_CALIBRATION_FILE overrides the path
    (tests)."""
    path = os.environ.get("FFS_CALIBRATION_FILE") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "CALIBRATION.json")
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return 1.0
    ratios = sorted(r["mem_ratio"] for r in data.get("results", [])
                    if isinstance(r.get("mem_ratio"), (int, float))
                    and r["mem_ratio"] > 0)
    if not ratios:
        return 1.0
    return float(ratios[len(ratios) // 2])


# ---- strategy files (--export-strategy / --import-strategy) ---------------

def strategy_json(mesh_axes: Dict[str, int], strategy: Strategy,
                  nodes, objective: Optional[str] = None) -> Dict[str, Any]:
    """Strategy keyed by op *name* (stable across runs, unlike guids —
    the reference keys by FFConfig::get_hash_id, strategy.cc:26) as a
    JSON-able dict: the body of a strategy file, also embedded verbatim
    in v2 checkpoint manifests (flexflow_tpu/ckpt) so a same-topology
    resume can reuse the searched strategy without re-searching."""
    by_guid = {n.op.guid: n.op.name for n in nodes}
    ops = {}
    for guid, st in strategy.items():
        name = by_guid.get(guid)
        if name is None:
            continue
        ops[name] = dict(
            choice=st.choice,
            outputs=[list(s) if s is not None else None for s in st.output_specs],
            params={k: list(v) for k, v in st.param_specs.items()},
        )
    out = dict(version=1, mesh=dict(mesh_axes), ops=ops)
    if objective:
        # "step_time" (TRAINING) vs "latency" (INFERENCE serving): a
        # strategy file / checkpoint manifest records which objective
        # the recorded shardings were searched under
        out["objective"] = objective
    return out


def export_strategy_file(path: str, mesh_axes: Dict[str, int],
                         strategy: Strategy, nodes,
                         objective: Optional[str] = None) -> None:
    with open(path, "w") as f:
        json.dump(strategy_json(mesh_axes, strategy, nodes,
                                objective=objective), f, indent=1)


def import_strategy_file(path: str, nodes) -> Tuple[Dict[str, int], Strategy]:
    with open(path) as f:
        data = json.load(f)
    mesh_axes = {k: int(v) for k, v in data["mesh"].items()}
    strategy: Strategy = {}
    for node in nodes:
        oj = data["ops"].get(node.op.name)
        if oj is None:
            continue
        outs = [
            (P(*e) if e is not None else None)
            for e in oj["outputs"]
        ]
        params = {k: P(*v) for k, v in oj.get("params", {}).items()}
        strategy[node.op.guid] = OpStrategy(
            output_specs=outs, param_specs=params, choice=oj.get("choice"))
    return mesh_axes, strategy
