"""Pipeline-parallel graph executor: FFModel.compile's lowering when the
search (or an explicit mesh) picks a 'pipe' axis.

Completes the capability the reference only stubs (OP_PIPELINE,
/root/reference/include/flexflow/ffconst.h:153): the repeated-block body
of the graph executes as an SPMD pipeline (parallel/pipeline.py) while
head/tail ops run under ordinary GSPMD around it. Body parameters live
STACKED — params['__pipe_body__']['op<j>'] with leading dim
R = num_blocks sharded over 'pipe' — so each device holds only its
stage's R/S block slices (1/pp of the body weights, matching the native
search's memory model, native/ffs_sim.hpp simulate_pipeline).

Schedules (searched by the native cost model, ``--pipeline-schedule``):
``gpipe`` keeps each stage's k = R/S blocks consecutive; ``circular``
stores them round-robin (stage s holds blocks s, s+S, ...) and runs one
block per tick, shrinking the bubble toward (S-1)/(kM+S-1).

Weight-update sharding composes with the pipeline: the stacked body
gradients reduce-scatter over the data axes onto a
P('pipe', ..., 'data') master/optimizer-state layout, and the next
step's compute params all-gather back inside the optimizer fusion —
the same invariants as the flat executor (tests/test_wus.py).

Comms-compute overlap at pp > 1 (ISSUE 9): the sharded microbatch
queue's input stream is double-buffered inside pipeline_spmd (tick
t+1's hop issues while tick t's block runs), matching the simulator's
bandwidth-only stream pricing. The stacked body gradient sync stays
unbucketed — it is ONE stacked reduce-scatter whose hiding window is
the optimizer-fusion tail, which simulate_pipeline's '_ovl' pricing
models; the per-op bucket partition applies to head/tail ops through
the base executor. Per-op '_wus' granularity (wus_ops) likewise gates
head/tail leaves; the body shards all-or-nothing with
weight_update_sharding.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from flexflow_tpu.executor import (COMPUTE_PARAMS_KEY, GraphExecutor,
                                   settled_spec)
from flexflow_tpu.ops.base import OpContext

BODY_KEY = "__pipe_body__"


class PipelineGraphExecutor(GraphExecutor):
    def __init__(self, *args, pipe_blocks=None, microbatches: int = 0,
                 pipe_axis: str = "pipe", schedule: str = "auto",
                 shard_queue: bool = True, **kwargs):
        # ``self.body_remat`` (the plan's, set by the base): the searched
        # pipeline's block-level 'remat' bit (ISSUE 20). Each block body
        # runs under jax.checkpoint, so a stage keeps only block BOUNDARY
        # activations per in-flight microbatch and recomputes block
        # interiors in backward — the HBM term ffs_sim.hpp prices as
        # k*block_out/dp + one transient interior. False = bit-identical
        # to pre-remat execution.
        super().__init__(*args, **kwargs)
        if pipe_blocks is None:
            raise ValueError("PipelineGraphExecutor needs detected blocks")
        self.pb = pipe_blocks
        self.pipe_axis = pipe_axis
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        self.num_stages = sizes.get(pipe_axis, 1)
        R = self.pb.num_blocks
        if self.num_stages < 2:
            raise ValueError("mesh has no 'pipe' axis > 1")
        if R % self.num_stages:
            raise ValueError(
                f"{R} repeated blocks cannot split into "
                f"{self.num_stages} pipeline stages")
        self.blocks_per_stage = R // self.num_stages
        if schedule not in ("auto", "gpipe", "circular"):
            raise ValueError(
                f"pipeline schedule expects auto|gpipe|circular, "
                f"got {schedule!r}")
        self.microbatches = microbatches or 2 * self.num_stages
        if schedule == "auto":
            # circular only pays off (and only differs) with k > 1, and
            # its recirculation buffer needs M >= S — 'auto' falls back
            # to gpipe rather than rejecting a valid GPipe config
            schedule = ("circular" if self.blocks_per_stage > 1
                        and self.microbatches >= self.num_stages
                        else "gpipe")
        if schedule == "circular" and self.blocks_per_stage == 1:
            schedule = "gpipe"  # identical schedule, natural storage order
        self.schedule = schedule
        self.shard_queue = bool(shard_queue)
        if self.schedule == "circular" \
                and self.microbatches < self.num_stages:
            raise ValueError(
                f"circular schedule needs microbatches >= stages "
                f"({self.microbatches} < {self.num_stages})")
        batch = self.nodes[self.pb.blocks[0][0]].op.output_shapes[0][0]
        dp = sizes.get("data", 1)
        if batch % (self.microbatches * dp):
            raise ValueError(
                f"batch {batch} must divide microbatches*data "
                f"({self.microbatches}*{dp})")
        for blk in self.pb.blocks:
            for ni in blk:
                op = self.nodes[ni].op
                # backstop — detection already refuses these
                # (pipeline_detect.stateless); a mismatch here means the
                # blocks came from somewhere else. fflint surfaces the
                # same condition pre-compile as FFL107.
                if getattr(op, "dropout", 0.0) or hasattr(op, "init_state"):
                    raise ValueError(
                        f"op '{op.name}': dropout/stateful ops inside "
                        f"pipelined blocks are not supported by the "
                        f"pipeline lowering yet")
        self._head = [self.nodes[i] for i in self.pb.head]
        self._tail = [self.nodes[i] for i in self.pb.tail]
        # map full op name -> (template param key, storage row) for the
        # per-layer weight I/O API (FFModel.get/set_parameter). Under the
        # circular schedule block b lives at row (b % S) * k + b // S so
        # the pipe sharding hands stage s the round-robin set.
        self.body_param_map: Dict[str, tuple] = {}
        for b, blk in enumerate(self.pb.blocks):
            for j, ni in enumerate(blk):
                self.body_param_map[self.nodes[ni].op.name] = \
                    (f"op{j}", self._storage_row(b))

    def _storage_row(self, block_idx: int) -> int:
        if self.schedule == "circular":
            return (block_idx % self.num_stages) * self.blocks_per_stage \
                + block_idx // self.num_stages
        return block_idx

    # ---- parameters -------------------------------------------------------
    def init_params_and_state(self, rng):
        from flexflow_tpu.parallel.pipeline import circular_block_order

        # storage row -> block index (the inverse of _storage_row — the
        # same permutation stack_stage_params callers use)
        order = (circular_block_order(self.pb.num_blocks, self.num_stages)
                 if self.schedule == "circular"
                 else list(range(self.pb.num_blocks)))

        def _init(rng):
            # one key per node in graph order, exactly as GraphExecutor
            # draws them: a seed gives the same weights whichever
            # executor the strategy lands on, so searched-vs-data-parallel
            # loss curves compare
            by_node = []
            for node in self.nodes:
                rng, sub = jax.random.split(rng)
                by_node.append(node.op.init_params(sub))
            p: Dict[str, Any] = {}
            for i in list(self.pb.head) + list(self.pb.tail):
                if by_node[i]:
                    p[self.nodes[i].op.name] = by_node[i]
            per_block: List[Dict] = [
                {f"op{j}": by_node[ni] for j, ni in enumerate(blk)
                 if by_node[ni]}
                for blk in self.pb.blocks]
            p[BODY_KEY] = jax.tree.map(
                lambda *ws: jnp.stack([ws[b] for b in order]), *per_block)
            return p

        params = jax.jit(_init)(rng)
        params = jax.device_put(params,
                                self.param_shardings(params, master=True))
        state: Dict[str, Any] = {}
        for node in self._head + self._tail:
            if hasattr(node.op, "init_state"):
                state[node.op.name] = node.op.init_state()
        if self.use_master_copy:
            state[COMPUTE_PARAMS_KEY] = self.cast_compute_copy(params)
        return params, state

    # ---- weight-update sharding over the stacked body ---------------------
    def _body_wus_spec(self, shape) -> Optional[P]:
        """Master/optimizer-state spec for a stacked body leaf
        [R, ...]: dim 0 carries the pipe axis; the data axes land on the
        first later dim the data degree divides (None when no dim
        divides — that leaf's state stays pipe-sharded only)."""
        if not self.weight_update_sharding:
            return None
        deg = self._data_degree()
        entries = [self.pipe_axis] + [None] * (len(shape) - 1)
        for d in range(1, len(shape)):
            if shape[d] > 0 and shape[d] % deg == 0:
                entries[d] = self._wus_axis_entry()
                return P(*entries)
        return None

    def _body_compute_spec(self, shape) -> P:
        return P(self.pipe_axis, *([None] * (len(shape) - 1)))

    def wus_param_specs(self) -> Dict[str, Dict[str, P]]:
        """Per-op sharded-state specs fflint verifies. Body entries are
        reported against the op's OWN (unstacked) parameter shapes: the
        per-block slice of the master shards over the data axes on the
        dim after the stacked leading dim."""
        if not self.weight_update_sharding:
            return {}
        from flexflow_tpu.search.unity import _param_shapes
        out: Dict[str, Dict[str, P]] = {}
        body_rows = {n.op.name for blk in self.pb.blocks
                     for n in (self.nodes[i] for i in blk)}
        for node in self.nodes:
            for pname, shp in _param_shapes(node.op).items():
                if node.op.name in body_rows:
                    spec = self._body_wus_spec((self.pb.num_blocks,)
                                               + tuple(shp))
                    if spec is not None:
                        out.setdefault(node.op.name, {})[pname] = \
                            P(*tuple(spec)[1:])
                else:
                    spec = self.wus_spec(node.op.name, pname, tuple(shp))
                    if spec is not None:
                        out.setdefault(node.op.name, {})[pname] = spec
        return out

    def _wus_shard(self, tree):
        if not self.weight_update_sharding:
            return tree

        def leaf(path, x):
            if not hasattr(x, "shape"):
                return x
            if path and getattr(path[0], "key", None) == BODY_KEY:
                spec = self._body_wus_spec(x.shape)
            elif len(path) >= 2:
                spec = self.wus_spec(getattr(path[-2], "key", None),
                                     getattr(path[-1], "key", None), x.shape)
            else:
                return x
            if spec is None:
                return x
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map_with_path(leaf, tree)

    def _constrain_compute(self, tree):
        if not self.weight_update_sharding:
            return tree

        def leaf(path, x):
            if not hasattr(x, "shape"):
                return x
            if path and getattr(path[0], "key", None) == BODY_KEY:
                spec = self._body_compute_spec(x.shape)
            elif len(path) >= 2:
                node = self._by_name.get(getattr(path[-2], "key", None))
                if node is None:
                    return x
                spec = node.param_specs.get(getattr(path[-1], "key", None),
                                            P())
            else:
                return x
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map_with_path(leaf, tree)

    def param_shardings(self, params, master: bool = False):
        def head_tail(op_name, sub):
            out = {}
            for pn, arr in sub.items():
                if master:
                    spec = self.master_spec(
                        op_name, pn, tuple(getattr(arr, "shape", ())))
                else:
                    spec = self._by_name[op_name].param_specs.get(pn, P())
                out[pn] = NamedSharding(self.mesh, settled_spec(spec))
            return out

        def body_leaf(w):
            spec = self._body_wus_spec(w.shape) if master else None
            if spec is None:
                spec = self._body_compute_spec(w.shape)
            return NamedSharding(self.mesh, settled_spec(spec))

        out = {}
        for op_name, sub in params.items():
            if op_name == BODY_KEY:
                out[BODY_KEY] = jax.tree.map(body_leaf, sub)
            else:
                out[op_name] = head_tail(op_name, sub)
        return out

    # ---- body execution ---------------------------------------------------
    def _run_block_template(self, pblock, x, ctx: OpContext):
        """One block's ops (block-0 structure) on params slice ``pblock``."""
        tmpl = self.pb.blocks[0]
        values = {}
        for j, ni in enumerate(tmpl):
            node = self.nodes[ni]
            args = []
            for ref in node.input_refs:
                key = (ref[1], ref[2]) if ref[0] == "op" else None
                if key is not None and key in values:
                    args.append(values[key])
                else:
                    args.append(x)  # block boundary input
            outs = node.op.forward(pblock.get(f"op{j}", {}), args, ctx)
            for oi, o in enumerate(outs):
                values[(node.op.guid, oi)] = o
        # block boundary: the TEMPLATE's last node, at body_out's out_idx
        # (body_out itself names the LAST block's node)
        last_guid = self.nodes[tmpl[-1]].op.guid
        return values[(last_guid, self.pb.body_out[2])]

    def _stage_fn(self, training: bool):
        ctx = OpContext(training=training, compute_dtype=self.compute_dtype)
        run = lambda pb, x: self._run_block_template(pb, x, ctx)  # noqa: E731
        if training and self.body_remat:
            # per-BLOCK checkpoint (not per-stage): backward peak holds one
            # block interior regardless of blocks_per_stage
            run = jax.checkpoint(run)
        if self.schedule == "circular" and self.blocks_per_stage > 1:
            # circular: pipeline_spmd indexes the round's block slice and
            # hands ONE block's params per tick
            def stage_fn(p_block, x):
                return run(p_block, x)

            return stage_fn
        k = self.blocks_per_stage

        def stage_fn(p_local, x):
            for b in range(k):
                pb = jax.tree.map(lambda w: w[b], p_local)
                x = run(pb, x)
            return x

        return stage_fn

    # ---- data staging -----------------------------------------------------
    def batch_sharding(self):
        # Sharded microbatch queue: when the pipeline consumes the graph
        # input directly (no head ops), stage the batch sharded over the
        # pipe axis too — reshaping [B, ...] to [M, B/M, ...] splits dim 0
        # microbatch-major, so a dim-0 pipe shard IS the queue layout and
        # the staged batch argument (alive for the whole step) drops by
        # ~pp per device instead of replicating over the pipe axis.
        # single-controller only: multi-process staging infers the global
        # batch from per-host rows x the LABEL sharding's partitions, so
        # inputs and labels must agree on the batch-dim layout there
        if (self.shard_queue and self.microbatches % self.num_stages == 0
                and self.pb.body_in[0] == "input" and not self._head
                and jax.process_count() == 1):
            da = tuple(self.data_axes)
            return NamedSharding(self.mesh, P((self.pipe_axis,) + da))
        return super().batch_sharding()

    def label_sharding(self):
        # labels never enter the pipeline; they meet the loss on the
        # data-sharded boundary layout
        return GraphExecutor.batch_sharding(self)

    # ---- graph traversal (head -> pipeline -> tail) -----------------------
    def run_graph(self, params, state, inputs, ctx: OpContext, nodes=None,
                  counters=None):
        # `counters` collects from the head and the tail; what an op inside
        # the stage body counts stays inside the shard_map and is not read
        # `nodes` (the base executor's Conv+BN-folded inference list) is
        # ignored: pipeline bodies are transformer blocks — nothing folds —
        # and the head/tail partition is fixed at construction
        from flexflow_tpu.parallel.pipeline import pipeline_spmd

        values: Dict = {}
        new_state: Dict[str, Any] = {}
        aux: List = []
        self._run_nodes(self._head, params, state, inputs, values,
                        new_state, aux, ctx, counters=counters)
        if self.pb.body_in[0] == "input":
            x = inputs[self.pb.body_in[1]]
        else:
            x = values[(self.pb.body_in[1], self.pb.body_in[2])]
        y = pipeline_spmd(
            self._stage_fn(ctx.training), params[BODY_KEY], x, self.mesh,
            num_microbatches=self.microbatches, axis=self.pipe_axis,
            data_axis="data", stage_leading_dim=True,
            schedule=self.schedule, shard_queue=self.shard_queue)
        if ctx.training:
            # pin the boundary back to the data-sharded layout the tail +
            # loss run on: the queue layout (replicated or pipe-sharded)
            # must not leak into the loss-reduction grouping, or schedule/
            # queue variants drift at the last ulp instead of staying
            # bit-identical. Forward-only executables skip the gather —
            # the pipe-sharded output buffer is the memory win there.
            da = tuple(self.data_axes)
            spec = P(da) if da else P()
            y = jax.lax.with_sharding_constraint(
                y, NamedSharding(self.mesh, spec))
        values[(self.pb.body_out[1], self.pb.body_out[2])] = y
        self._run_nodes(self._tail, params, state, inputs, values,
                        new_state, aux, ctx, counters=counters)
        return values, new_state, aux
