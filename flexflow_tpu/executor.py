"""Graph executor: lowers the materialized op graph to jitted step functions.

This is the TPU replacement for the reference's execution loop
(FFModel::forward/backward/update, src/runtime/model.cc:2415-2475, plus the
Legion trace around each iteration): instead of launching per-op index
tasks that a mapper routes to devices, the whole iteration — forward, loss,
autodiff backward, metrics, optimizer update (with its gradient psum over
the data axis) — is one XLA computation compiled by jax.jit against a
``jax.sharding.Mesh``. The per-op sharding decisions from the strategy are
applied as (a) NamedShardings on parameters and (b)
``with_sharding_constraint`` on op outputs (the four parallel ops of the
PCG become constraint boundaries — SURVEY §2.3 mapping).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flexflow_tpu.ffconst import CompMode, LossType, OperatorType
from flexflow_tpu.losses import (class_ids, expected_exit_loss, get_loss_fn,
                                 part_nll_sums, target_log_probs,
                                 target_positions, weighted_nll_mean)
from flexflow_tpu.metrics import Metrics
from flexflow_tpu.obs.registry import get_registry
from flexflow_tpu.obs.step_scopes import scope_part
from flexflow_tpu.ops.base import Op, OpContext, exported_reads, scoped
from flexflow_tpu.parallel.choice import ExecPlan


# pseudo-entry in the op-state dict holding the bf16 parameter working
# copy under the master-weight mixed-precision regime (never collides with
# op names, which come from Layer naming)
COMPUTE_PARAMS_KEY = "__compute_params__"
# beside a counter that is a mean over ops and steps: how many were added
COUNT_SUFFIX = "#n"
# what an op's forward leaves on the op for `_run_nodes` to pick up; traced
# values, so a forward run as a nested call hands them out as results
SIDE_CHANNELS = ("_aux_loss", "_counters", "_new_state", "_new_states")
# `Op.traced_gauges` keys that say a size, not a count: the model's is the
# largest of its ops', where every other key is added up
GAUGES_OF_THE_LARGEST_OP = frozenset({"executor.delta_rule_heads_a_step",
                                      "hc/streams", "hc/sinkhorn_iters"})
# the op kinds of which a decoder layer holds one
SEQUENCE_MIXERS = (OperatorType.MULTIHEAD_ATTENTION, OperatorType.SSM_MIXER,
                   OperatorType.SHORT_CONV, OperatorType.MAMBA_MIXER,
                   OperatorType.DELTA_MIXER)


def settled_spec(spec: P) -> P:
    """``spec`` as jit reports it on an output: without trailing Nones.
    ``P('data', None)`` and ``P('data')`` place an array identically but do
    not compare equal, so a parameter placed with the first comes back
    from the train step with the second, and jit compiles the whole step
    again for what it takes to be a new input type."""
    entries = list(spec)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def op_params(op, params) -> Dict[str, jax.Array]:
    """The parameters ``op``'s forward reads: its own subtree of
    ``params`` and, for an op with ``tied_params``, the leaves it reads
    out of their owners' subtrees (a tied head's table). One array, two
    readers: differentiated through both."""
    own = params.get(op.name, {})
    if not op.tied_params:
        return own
    return {**own, **{local: params[owner][leaf] for local, (owner, leaf)
                      in op.tied_params.items()}}


class OpNode:
    """One materialized operator + where its inputs come from.

    ``input_refs``: list of ('op', producer_guid, out_idx) or
    ('input', input_name) or ('label', 0).
    """

    def __init__(self, op: Op, input_refs: List[Tuple]):
        self.op = op
        self.input_refs = input_refs
        # sharding decision: per-output PartitionSpec (set by the strategy)
        self.output_specs: List[Optional[P]] = [None] * len(op.output_shapes)
        self.param_specs: Dict[str, P] = {}

    @property
    def guid(self):
        return self.op.guid


class GraphExecutor:
    def __init__(
        self,
        nodes: List[OpNode],
        input_names: List[str],
        final_ref,
        mesh: Mesh,
        loss_type: LossType,
        metrics: Metrics,
        optimizer,
        compute_dtype=jnp.bfloat16,
        data_axes: Tuple[str, ...] = ("data",),
        final_is_softmax: bool = False,
        fold_conv_bn: bool = True,
        plan: ExecPlan = ExecPlan(),
    ):
        self.nodes = nodes
        self.by_guid = {n.guid: n for n in nodes}
        self.input_names = input_names
        # (guid, out_idx) of the user-designated model output
        self.final_ref = tuple(final_ref)
        self.mesh = mesh
        self.loss_type = loss_type
        self.metrics = metrics
        self.optimizer = optimizer
        self.compute_dtype = compute_dtype
        self.data_axes = data_axes
        self.final_is_softmax = final_is_softmax
        # names of the equal parts along the sequence of a weighted
        # loss's logits, or of a looped model's passes (FFModel.compile
        # sets it from the model), and the weight of the entropy bonus
        # of a looped model's exit distribution; `exit_uniform` puts
        # 1 / T in the distribution's place (a control)
        self.loss_parts = None
        # names of the op counters of kind "max", noted while the forward
        # is traced: `FFModel.fit` carries their largest over an epoch's
        # steps where it adds every other counter up
        self.max_counters = set()
        self.exit_entropy_beta = 0.0
        self.exit_uniform = False
        # mixed-precision master-weight regime (bf16 compute): forward and
        # backward run on a bf16 copy of the parameters that is produced
        # INSIDE the previous step's optimizer fusion (state key
        # '__compute_params__'), so the per-step f32->bf16 cast costs one
        # extra bf16 write instead of an f32 read + bf16 write, gradients
        # arrive in bf16 (halving the backward dW writes and any
        # data-parallel gradient psum bytes), and the f32 master copy is
        # touched only by the optimizer. Measured on v5e (r4,
        # scripts/measure_flat_opt.py): the per-leaf update is already
        # bandwidth-bound (~620 GB/s marginal), so byte reduction — not a
        # flat-buffer layout — is the lever.
        self.use_master_copy = compute_dtype != jnp.float32
        self.fold_conv_bn = fold_conv_bn
        # The plan's fields (parallel/choice.py says when each engages)
        # are this executor's state from here on; tests and scripts set
        # them. How each is run:
        # WUS: the data-axis gradient sync is a reduce-scatter onto a
        # per-param shard spec, the f32 master copy + optimizer moments
        # live sharded over the data axes, and the next step's bf16
        # compute params are all-gathered inside the same optimizer
        # fusion (preserving the one-extra-bf16-write property). Per-chip
        # optimizer HBM then scales with params/chip. With ``wus_ops``
        # only those ops' params/state shard; the rest keep the plain
        # all-reduce, closing the priced-vs-emitted gap on mixed strategies.
        self.weight_update_sharding = plan.wus
        self.wus_ops = (set(plan.wus_ops) if plan.wus_ops is not None
                        else None)
        # overlap: the WUS gradient sync issues as size-targeted bucketed
        # async reduce-scatters in reverse-backward order (each bucket's
        # collective depends only on its own grads plus the previous
        # bucket's issue, so XLA's async collective scheduler hides it
        # under the remaining backward compute), and the next step's bf16
        # param all-gathers chain in forward order under the optimizer
        # fusion tail. Identity on values: bit-for-bit with the
        # synchronous sync.
        self.grad_overlap = plan.overlap
        self.overlap_bucket_bytes = plan.bucket_bytes
        # kernels, {op name -> impl}: "fused" routes the op's optimizer
        # update through the one-dispatch fused region
        # (ops/fused_update.py, bit-compatible with the triad);
        # "conv_bn_fused" runs the Conv2D and its BatchNorm consumer as
        # one train-time region (layout.TrainFusedConvBN); attention
        # impls live on the op itself (MultiHeadAttention.kernel_impl).
        self.kernel_choices = (dict(plan.kernel_choices)
                               if plan.kernel_choices else None)
        # remat: these ops' forward runs under jax.checkpoint, so backward
        # keeps only the op's boundary (inputs + params) and recomputes
        # the interior. The native gate (ffs_strategy.hpp remat_gate)
        # only spawns '_r' twins for stateless, collective-free ops, so
        # the plain-forward branch of the step is the only wrap point.
        self.remat_ops = set(plan.remat_ops) if plan.remat_ops else None
        self.body_remat = plan.body_remat  # PipelineGraphExecutor's
        self.fused_update_ops = {
            n for n, impl in (self.kernel_choices or {}).items()
            if impl == "fused"}
        self._by_name = {n.op.name: n for n in nodes}
        self._jit_train = None
        self._jit_eval = None
        self._jit_fwd = {}  # keyed by training flag

    @property
    def plan(self) -> ExecPlan:
        """What this executor runs now, as the record it was built from."""
        return ExecPlan(
            wus=self.weight_update_sharding,
            wus_ops=(frozenset(self.wus_ops) if self.wus_ops is not None
                     else None),
            overlap=self.grad_overlap,
            bucket_bytes=self.overlap_bucket_bytes,
            kernel_choices=self.kernel_choices,
            remat_ops=frozenset(self.remat_ops) if self.remat_ops else None,
            body_remat=self.body_remat)

    # ---- weight-update sharding (WUS) -------------------------------------
    def _data_degree(self) -> int:
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        deg = 1
        for a in self.data_axes:
            deg *= sizes.get(a, 1)
        return deg

    def _wus_axis_entry(self):
        da = tuple(self.data_axes)
        return da[0] if len(da) == 1 else da

    def wus_spec(self, op_name: str, pname: str,
                 shape: Tuple[int, ...]) -> Optional[P]:
        """Data-sharded spec for a master-param/optimizer-state leaf, or
        None when the leaf stays replicated (WUS off, scalar, or no free
        dim the data degree divides). Composes with the strategy's param
        spec: the data axes land on the first unsharded dividing dim, so
        a model-sharded kernel shards 2-D (model x data)."""
        if not self.weight_update_sharding:
            return None
        if self.wus_ops is not None and op_name not in self.wus_ops:
            return None  # the search chose plain sync for this op
        node = self._by_name.get(op_name)
        if node is None:
            return None
        base = node.param_specs.get(pname, P())
        entries = (list(base) + [None] * len(shape))[:len(shape)]
        deg = self._data_degree()
        for d, e in enumerate(entries):
            if e is None and shape[d] > 0 and shape[d] % deg == 0:
                entries[d] = self._wus_axis_entry()
                return P(*entries)
        return None

    def wus_param_specs(self) -> Dict[str, Dict[str, P]]:
        """{op name: {param name: sharded spec}} of every leaf WUS
        actually shards — the sharded-state truth fflint's sharding pass
        verifies against the mesh."""
        if not self.weight_update_sharding:
            return {}
        from flexflow_tpu.search.unity import _param_shapes
        out: Dict[str, Dict[str, P]] = {}
        for node in self.nodes:
            for pname, shp in _param_shapes(node.op).items():
                spec = self.wus_spec(node.op.name, pname, tuple(shp))
                if spec is not None:
                    out.setdefault(node.op.name, {})[pname] = spec
        return out

    def _wus_shard(self, tree):
        """Constrain every float leaf of a params-shaped (sub)tree onto
        its WUS spec. Applied to the gradients inside the train step,
        this turns the data-axis gradient psum GSPMD would emit as an
        all-reduce into a reduce-scatter (each chip keeps only its shard
        of the summed gradient); applied to the updated params/moments it
        pins the shard layout through the optimizer fusion.

        Under ``grad_overlap`` the constraints apply bucket by bucket in
        reverse-backward order (``_chain_constrained``): each bucket's
        reduce-scatter depends only on its own grads plus the previous
        bucket's issue, so XLA's async collective machinery hides it
        under the remaining backward compute instead of sinking one
        combined sync to the end of the step."""
        if not self.weight_update_sharding:
            return tree
        if self.grad_overlap:
            leaves = self._collect_spec_leaves(tree, self.wus_spec)
            if not leaves:
                return tree
            return self._chain_constrained(
                tree, leaves, self._bucket_order(leaves, reverse=True))

        def leaf(path, x):
            if len(path) < 2 or not hasattr(x, "shape"):
                return x
            spec = self.wus_spec(getattr(path[-2], "key", None),
                                 getattr(path[-1], "key", None), x.shape)
            if spec is None:
                return x
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map_with_path(leaf, tree)

    # ---- bucketed async constraint chaining (comms-compute overlap) -------
    def _collect_spec_leaves(self, tree, spec_fn):
        """{(op name, param name): (leaf, spec)} for every float leaf of
        a params-shaped tree where ``spec_fn(op, pname, shape)`` returns
        a PartitionSpec (None = leave alone)."""
        out: Dict[Tuple[str, str], Tuple[jax.Array, P]] = {}

        def leaf(path, x):
            if len(path) >= 2 and hasattr(x, "shape"):
                op_name = getattr(path[-2], "key", None)
                pname = getattr(path[-1], "key", None)
                spec = spec_fn(op_name, pname, x.shape)
                if spec is not None:
                    out[(op_name, pname)] = (x, spec)
            return x

        jax.tree_util.tree_map_with_path(leaf, tree)
        return out

    def _bucket_order(self, leaves, reverse: bool):
        """Leaf keys in graph-topological op order (``reverse=True`` for
        the backward-completion order the gradient buckets follow)."""
        by_op: Dict[str, list] = {}
        for k in leaves:
            by_op.setdefault(k[0], []).append(k)
        order = []
        for node in (reversed(self.nodes) if reverse else self.nodes):
            order.extend(by_op.pop(node.op.name, ()))
        for rest in by_op.values():  # unknown ops: stable tail
            order.extend(rest)
        return order

    def _chain_constrained(self, tree, leaves, order):
        """Apply sharding constraints to ``leaves`` in size-targeted
        buckets (``overlap_bucket_bytes`` of payload each), chaining
        consecutive buckets through ``lax.optimization_barrier``: bucket
        k's constraint inputs depend on one of bucket k-1's constrained
        outputs, so the lowered collectives issue in bucket order — the
        structure XLA's async collective scheduler needs to hide each
        bucket under the compute still running when it fires. The
        barrier is the identity on values, so this path is bit-for-bit
        identical to the unchained constraints (tests/test_overlap.py).
        """
        buckets, cur, size = [], [], 0
        for key in order:
            x, _ = leaves[key]
            cur.append(key)
            size += int(x.size) * x.dtype.itemsize
            if size >= self.overlap_bucket_bytes:
                buckets.append(cur)
                cur, size = [], 0
        if cur:
            buckets.append(cur)
        done: Dict[Tuple[str, str], jax.Array] = {}
        prev = None
        for bucket in buckets:
            vals = [leaves[k][0] for k in bucket]
            if prev is not None:
                chained = jax.lax.optimization_barrier(tuple(vals) + (prev,))
                vals = list(chained[:-1])
            vals = [
                jax.lax.with_sharding_constraint(
                    v, NamedSharding(self.mesh, leaves[k][1]))
                for k, v in zip(bucket, vals)
            ]
            prev = vals[0]
            done.update(zip(bucket, vals))

        def replace(path, x):
            if len(path) >= 2:
                k = (getattr(path[-2], "key", None),
                     getattr(path[-1], "key", None))
                if k in done:
                    return done[k]
            return x

        return jax.tree_util.tree_map_with_path(replace, tree)

    def _constrain_compute(self, tree):
        """Constrain a params-shaped tree onto the strategy (compute)
        specs — the all-gather over the data axes that rebuilds the next
        step's replicated bf16 working copy from the WUS shards, fused
        into the optimizer update.

        Under ``grad_overlap`` the gathers chain in FORWARD op order
        (``_chain_constrained``): the first layers' compute params — the
        ones the next step's forward needs first — prefetch under the
        optimizer fusion tail while later leaves' update math still
        runs."""
        if not self.weight_update_sharding:
            return tree
        if self.grad_overlap:
            def spec_fn(op_name, pname, shape):
                node = self._by_name.get(op_name)
                if node is None:
                    return None
                return node.param_specs.get(pname, P())

            leaves = self._collect_spec_leaves(tree, spec_fn)
            if not leaves:
                return tree
            return self._chain_constrained(
                tree, leaves, self._bucket_order(leaves, reverse=False))

        def leaf(path, x):
            if len(path) < 2 or not hasattr(x, "shape"):
                return x
            node = self._by_name.get(getattr(path[-2], "key", None))
            if node is None:
                return x
            spec = node.param_specs.get(getattr(path[-1], "key", None), P())
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map_with_path(leaf, tree)

    # ---- parameter / state initialization ---------------------------------
    def init_params_and_state(self, rng) -> Tuple[Dict, Dict]:
        params: Dict[str, Dict[str, jax.Array]] = {}
        state: Dict[str, Dict[str, jax.Array]] = {}

        # a key a node, split off one after the other OUTSIDE the
        # program that draws the leaves: inside it the chain of splits
        # is compiled link by link (half a second a node on a v5e; a
        # looped model's 321 nodes took 176 s), and the keys are the
        # same either way
        keys = []
        for _ in self.nodes:
            rng, sub = jax.random.split(rng)
            keys.append(sub)

        def _init(keys):
            p = {}
            for node, sub in zip(self.nodes, keys):
                ps = node.op.init_params(sub)
                if ps:
                    p[node.op.name] = ps
            return p

        params = jax.jit(_init)(keys)
        params = jax.device_put(params, self.param_shardings(params,
                                                            master=True))
        for node in self.nodes:
            if hasattr(node.op, "init_state"):
                state[node.op.name] = node.op.init_state()
        if self.use_master_copy:
            state[COMPUTE_PARAMS_KEY] = self.cast_compute_copy(params)
        return params, state

    def cast_compute_copy(self, params):
        """bf16 copy of the float parameter leaves (the forward/backward
        working set under the master-weight regime). Under WUS the master
        leaves are data-sharded, so the copy is all-gathered back onto the
        compute (strategy) shardings here."""
        if not hasattr(self, "_cast_jit"):
            # cached: repeated refreshes (per-weight import loops) must not
            # retrace a fresh jit each call
            self._cast_jit = jax.jit(
                lambda p: self._cast_tree(p))
        out = self._cast_jit(params)
        if self.weight_update_sharding:
            out = jax.device_put(out, self.param_shardings(out))
        return out

    def _cast_leaf(self, x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(self.compute_dtype)
        return x

    def _cast_tree(self, params):
        """The compute copy of a params tree: floating leaves in the
        compute dtype, but for the leaves an op states it needs whole
        (``Op.full_precision_params``: a router's bias and weights, a
        scan's decay rates — small, and rounding them moves every token
        alike), which stay float32."""
        keep = self._full_precision_leaves
        if not keep:
            return jax.tree.map(self._cast_leaf, params)

        def cast(path, x):
            # a leaf's last two keys are (op name, parameter name)
            at = tuple(getattr(k, "key", None) for k in path[-2:])
            return x if at in keep else self._cast_leaf(x)

        return jax.tree_util.tree_map_with_path(cast, params)

    @property
    def _full_precision_leaves(self):
        return {(n.op.name, p) for n in self.nodes
                for p in getattr(n.op, "full_precision_params", ())}

    def param_shardings(self, params, master: bool = False):
        """NamedShardings tree for a params-shaped tree: the compute
        (strategy) shardings, or — ``master=True`` under WUS — the
        data-sharded master layout the optimizer state follows (zeros_like
        inherits it, so sharded params get sharded m/v for free)."""
        def spec_for(op_name, pname, arr):
            if master:
                spec = self.master_spec(op_name, pname, tuple(arr.shape))
            else:
                spec = self._by_name[op_name].param_specs.get(pname, P())
            return NamedSharding(self.mesh, settled_spec(spec))

        return {
            op_name: {
                pn: spec_for(op_name, pn, a) for pn, a in sub.items()
            }
            for op_name, sub in params.items()
        }

    def master_spec(self, op_name: str, pname: str,
                    shape: Tuple[int, ...]) -> P:
        """Spec the f32 master copy and the optimizer moments of one
        leaf live on: its WUS spec where WUS shards it, else the
        strategy's (compute) spec."""
        w = self.wus_spec(op_name, pname, shape)
        if w is not None:
            return w
        return self._by_name[op_name].param_specs.get(pname, P())

    # ---- forward graph traversal ------------------------------------------
    def _output_layout(self, guid: int, idx: int) -> str:
        """Physical layout of a produced value (layout pass metadata on
        the producing node; absent = NCHW, the boundary contract)."""
        node = self.by_guid.get(guid)
        ols = getattr(node, "output_layouts", None) if node is not None else None
        return ols[idx] if ols and idx < len(ols) else "NCHW"

    def run_graph(self, params, state, inputs: Dict[str, jax.Array],
                  ctx: OpContext, nodes=None, counters=None):
        """Evaluate ops in topo order; returns (values, new_state, aux_losses).

        ``counters`` (a dict, filled in place) collects what ops count
        during forward (``op._counters``: name -> ("sum" | "mean" | "max",
        value), e.g. the expert layers' routing counts): sums add up over
        ops, means keep a count beside them under ``name + COUNT_SUFFIX``,
        of a "max" the largest stays (``self.max_counters``); an
        INTEGER sum stays a vector of the ops' counts, exact, for the
        host to add. The train step returns them with the metrics.

        aux_losses collects regularizer terms ops emit during forward (e.g.
        the MoE load-balance loss the reference computes inside Aggregate's
        backward, src/ops/aggregate.cu) — they are added to the objective.
        ``nodes`` overrides the node list (the inference executables run
        the Conv+BN-folded graph).
        """
        values: Dict[Tuple[int, int], jax.Array] = {}
        new_state: Dict[str, Any] = {}
        aux_losses: List[jax.Array] = []
        self._run_nodes(nodes if nodes is not None else self.nodes,
                        params, state, inputs, values,
                        new_state, aux_losses, ctx, counters=counters)
        # the designated output leaves in the boundary layout whatever the
        # execution layout of its producer was
        if self._output_layout(*self.final_ref) == "NHWC":
            from flexflow_tpu.layout import TO_NCHW
            values[self.final_ref] = jnp.transpose(
                values[self.final_ref], TO_NCHW)
        return values, new_state, aux_losses

    def scope_names(self, op) -> List[str]:
        """The nested calls ``op``'s forward runs under in the train
        step, outermost first: the scope the model's builder put the
        layer under (``FFModel.scope``: ``mtp``, ``ut<t>``), ``head`` for
        the op that produces the model's output, then the op's own
        (``Op.scopes_itself``; an attention op's is the stem, the route
        of a trace gives the rest) or ``op_<operator type, lower
        case>``. The one rule from an op to the names its instructions
        carry: ``_scoped_forward`` nests these, ``part_of_node`` reads
        them as ``obs.step_scopes.part_of`` reads an ``op_name`` back."""
        layer = getattr(op, "layer", None)
        names = [n for n in (getattr(layer, "properties", {}).get("scope"),)
                 if n]
        own = getattr(op, "scopes_itself", "")
        if op.guid == self.final_ref[0]:
            names.append("head")
        elif not own:
            names.append(
                "op_" + getattr(layer, "op_type", op.op_type).name.lower())
        if own:
            names.append(own)
        return names

    def part_of_node(self, node) -> Optional[str]:
        """The part of the train step ``node``'s instructions lie in
        (obs/step_scopes.py): that of the outermost of its scopes that
        names one."""
        for name in self.scope_names(node.op):
            part = scope_part(name)
            if part is not None:
                return part
        return None

    def _scoped_forward(self, op, ctx: OpContext):
        """``op``'s forward as ``forward(params, args[, state])``, for
        the device trace. An op that names its own nested calls
        (``Op.scopes_itself``) runs as it is. Every other op runs as
        one nested call (``ops.base.scoped``) named for its kind,
        ``op_<operator type, lower case>``, and the op that produces the
        model's output as ``head``: every instruction of the compiled
        step then says in its ``op_name`` which op kind it came from,
        and ``jvp`` / ``transpose`` beside it which direction
        (obs/step_scopes.py). What a forward leaves behind as traced
        values (``SIDE_CHANNELS`` on the op, the context's rng) is
        handed out of the nested call and put back where
        ``_run_nodes`` looks for it, so no tracer of the inner trace
        outlives it."""
        stateful = (getattr(op, "param_sources", None) is not None
                    or hasattr(op, "init_state"))

        def plain(params, args, state=None):
            if stateful:
                return op.forward(params, args, ctx, state=state)
            return op.forward(params, args, ctx)

        names = self.scope_names(op)
        if getattr(op, "scopes_itself", ""):
            names = names[:-1]      # the last is the op's own call
        if not names:
            return plain

        kinds = {}   # a counter's "sum" | "mean" is no traced value

        def inner(params, args, state, rng):
            ctx.rng = rng
            outs = plain(params, args, state)
            side = {}
            for k in SIDE_CHANNELS:
                if getattr(op, k, None) is not None:
                    side[k] = getattr(op, k)
                    setattr(op, k, None)
            if "_counters" in side:
                kinds.update((n, kind) for n, (kind, _) in
                             side["_counters"].items())
                side["_counters"] = {n: v for n, (_, v) in
                                     side["_counters"].items()}
            # None unless the op drew from the rng
            return tuple(outs), side, None if ctx.rng is rng else ctx.rng

        nested = inner
        for name in reversed(names):
            nested = scoped(name, nested)

        def forward(params, args, state=None):
            rng = ctx.rng
            try:
                outs, side, drawn = nested(
                    params, list(args), state, rng)
            finally:
                ctx.rng = rng
            if drawn is not None:
                ctx.rng = drawn
            if "_counters" in side:
                side["_counters"] = {n: (kinds[n], v) for n, v in
                                     side["_counters"].items()}
            for k, v in side.items():
                setattr(op, k, v)
            return outs

        return forward

    def _run_nodes(self, nodes, params, state, inputs, values, new_state,
                   aux_losses, ctx: OpContext, counters=None):
        """Evaluate the given nodes in order, reading/writing the shared
        ``values`` dict (lets the pipeline executor run head/tail subsets
        around the shard_map'd body).

        Values are stored in their producer's execution layout (the layout
        pass metadata, flexflow_tpu/layout.py); where a consumer expects
        the other layout, the transpose materializes HERE, cached per
        (value, layout) — so after propagation each conv chain pays one
        boundary pair, not one pair per op."""
        from flexflow_tpu.layout import TO_NCHW, TO_NHWC

        relayout_cache: Dict[Tuple, jax.Array] = {}

        def fetch(ref, want: str):
            if ref[0] == "op":
                have = self._output_layout(ref[1], ref[2])
                v = values[(ref[1], ref[2])]
            else:  # graph inputs are staged NCHW (API boundary)
                have = "NCHW"
                v = inputs[ref[1]]
            if want == have or getattr(v, "ndim", 0) != 4:
                return v
            key = (tuple(ref), want)
            if key not in relayout_cache:
                relayout_cache[key] = jnp.transpose(
                    v, TO_NHWC if want == "NHWC" else TO_NCHW)
            return relayout_cache[key]

        for node in nodes:
            op = node.op
            in_layouts = getattr(node, "input_layouts", None)
            args = [
                fetch(ref, in_layouts[j] if in_layouts else "NCHW")
                for j, ref in enumerate(node.input_refs)
            ]
            forward = self._scoped_forward(op, ctx)
            sources = getattr(op, "param_sources", None)
            if sources is not None:
                # fused execution-time op (FoldedConvBN eval fold /
                # TrainFusedConvBN searched kernel): reads the
                # parameter/state subtrees of the ops it folded
                outs = forward(
                    {s: params.get(s, {}) for s in sources}, args,
                    {s: state.get(s) for s in sources})
                # train-time fused regions update their sources' state
                # (BN running stats) under the SOURCE names, keeping the
                # state tree's shape checkpoint-compatible
                ns = getattr(op, "_new_states", None)
                if ns:
                    new_state.update(ns)
                    op._new_states = None
                else:
                    for s in sources:
                        if s in state and state[s] is not None \
                                and hasattr(self._by_name.get(s, None),
                                            "op") \
                                and hasattr(self._by_name[s].op,
                                            "init_state"):
                            new_state.setdefault(s, state[s])
            elif hasattr(op, "init_state"):
                outs = forward(op_params(op, params), args,
                               state.get(op.name))
                if getattr(op, "_new_state", None) is not None:
                    new_state[op.name] = op._new_state
                    op._new_state = None
                elif op.name in state:
                    new_state[op.name] = state[op.name]
            elif ctx.training and self.remat_ops \
                    and op.name in self.remat_ops:
                # searched '_r' choice: checkpoint the op's boundary and
                # recompute its interior in backward (gate-legal ops are
                # stateless with no aux side channel); the op's scope
                # lies inside the checkpoint, so the recomputation
                # carries it too
                outs = jax.checkpoint(
                    lambda p_, a_: tuple(forward(p_, list(a_)))
                )(op_params(op, params), tuple(args))
            else:
                outs = forward(op_params(op, params), args)
            if getattr(op, "_aux_loss", None) is not None:
                aux_losses.append(op._aux_loss)
                op._aux_loss = None
            if getattr(op, "_counters", None) is not None:
                if counters is not None:
                    for cname, (kind, v) in op._counters.items():
                        v = jnp.asarray(v)
                        if jnp.issubdtype(v.dtype, jnp.integer):
                            # an integer count leaves the step as it is,
                            # one element an op: `FFModel` adds an epoch's
                            # up on the host (int32 wraps at 2^31 pairs)
                            counters[cname] = jnp.concatenate(
                                [counters.get(cname, jnp.zeros(0, v.dtype)),
                                 jnp.reshape(v, 1)])
                            continue
                        if kind == "max":
                            # the largest over the ops of a step, and
                            # (`FFModel.fit`) over the steps of an epoch
                            counters[cname] = jnp.maximum(
                                counters.get(cname, -jnp.inf), v)
                            self.max_counters.add(cname)
                            continue
                        counters[cname] = counters.get(cname, 0.0) + v
                        if kind == "mean":
                            n = cname + COUNT_SUFFIX
                            counters[n] = counters.get(n, 0.0) + 1.0
                op._counters = None
            out_layouts = getattr(node, "output_layouts", None)
            for i, o in enumerate(outs):
                spec = node.output_specs[i]
                if spec is not None:
                    if out_layouts and i < len(out_layouts) \
                            and out_layouts[i] == "NHWC" \
                            and getattr(o, "ndim", 0) == 4:
                        from flexflow_tpu.layout import permute_spec_nhwc
                        spec = permute_spec_nhwc(spec)
                    o = jax.lax.with_sharding_constraint(
                        o, NamedSharding(self.mesh, spec)
                    )
                values[(op.guid, i)] = o

    # ---- jitted steps ------------------------------------------------------
    def _loss_value(self, logits, labels, counted=None):
        """The loss of the model's output. ``counted`` (the train step's
        dict) receives what the weighted loss counts on the device."""
        fn = get_loss_fn(self.loss_type)
        # whether the loss, as last traced, reached the logits through
        # `losses.target_log_probs` (`traced_gauges`)
        self._loss_own_vjp = False
        if self.final_is_softmax and self.loss_type in (
            LossType.CATEGORICAL_CROSSENTROPY,
            LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        ):
            # final op already produced probabilities (reference pairs a
            # Softmax op with CE loss — loss_functions.cc:41)
            logp = jnp.log(jnp.clip(logits.astype(jnp.float32), 1e-12, 1.0))
            if self.loss_type == LossType.CATEGORICAL_CROSSENTROPY:
                return -jnp.mean(jnp.sum(labels * logp, axis=-1))
            lab = labels.reshape(labels.shape[0], -1)[:, 0].astype(jnp.int32)
            return -jnp.mean(jnp.take_along_axis(logp, lab[:, None], axis=-1))
        if self.loss_type == LossType.WEIGHTED_SPARSE_CATEGORICAL_CROSSENTROPY:
            # the targets' log-probabilities ONCE, for the loss and for
            # what leaves the step with the ops' counters: the positions
            # that carried a target, and for a model whose logits are
            # several parts laid end to end each part's unweighted
            # cross-entropy
            self._loss_own_vjp = True
            logp = target_log_probs(logits, class_ids(logits, labels))
            if counted is not None:
                counted["loss/target_positions"] = target_positions(labels)
                if self.loss_parts:
                    counted.update(
                        (f"loss/{part}_nll", v) for part, v in part_nll_sums(
                            logp, labels, self.loss_parts).items())
            return weighted_nll_mean(logp, labels)
        if self.loss_type == \
                LossType.EXPECTED_EXIT_SPARSE_CATEGORICAL_CROSSENTROPY:
            # the T passes' logits lie end to end: their targets'
            # log-probabilities ONCE, then the mixture under the exit
            # distribution and its entropy, over [B, T, S] in float32
            self._loss_own_vjp = True
            loss, sums = expected_exit_loss(
                logits, labels, len(self.loss_parts),
                self.exit_entropy_beta, uniform=self.exit_uniform)
            if counted is not None:
                for key, value in sums.items():
                    if value.ndim == 0:
                        counted[key] = value
                        continue
                    counted.update((f"{key}_{part}", value[i]) for i, part
                                   in enumerate(self.loss_parts))
            return loss
        self._loss_own_vjp = (
            self.loss_type == LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
        return fn(logits, labels)

    def traced_gauges(self) -> Dict[str, float]:
        """What the trace of the step recorded on the host, by gauge key:
        the ops' own `Op.traced_gauges` added up over the nodes (which
        kernels and operand forms their forwards took; 0 until one has
        been traced; the largest of them for GAUGES_OF_THE_LARGEST_OP);
        `executor.loss_own_vjp`, 1 when the loss as last
        traced took the log-probability of a row's target from
        `losses.target_log_probs` (its own backward, PR 40), else 0 (MSE,
        dense one-hot labels, probabilities in); and, in a model whose
        attention ops differ in their query heads, each op's
        (`attention/heads_by_op/<op>`, PR 41); `executor.shared_tensors`
        and `executor.shared_tensor_readers` (PR 52). The ONE list of them:
        published as registry gauges when the train step is traced, in
        `FFModel.op_counters` and in every trace header
        (`obs.model_context`)."""
        out: Dict[str, float] = {}
        for n in self.nodes:
            for key, value in n.op.traced_gauges().items():
                out[key] = (max(out.get(key, 0), value)
                            if key in GAUGES_OF_THE_LARGEST_OP
                            else out.get(key, 0) + value)
        out["executor.loss_own_vjp"] = int(
            getattr(self, "_loss_own_vjp", False))
        # leaves with more than one reader: the ops that read another
        # op's (a tied head, every further application of a looped
        # layer: `op_params` hands them the owner's array, no copy),
        # the distinct leaves so read, and the sequence mixers the step
        # runs, one a layer APPLICATION
        read = [tuple(getattr(n.op, "tied_params", {}).values())
                for n in self.nodes]
        out["executor.shared_weight_ops"] = sum(map(bool, read))
        out["executor.shared_leaves"] = len(
            {leaf for leaves in read for leaf in leaves})
        out["executor.layer_applications"] = sum(
            n.op.op_type in SEQUENCE_MIXERS for n in self.nodes)
        # tensors one op makes for other layers to read (`Op.exports`: a
        # scan's memory, an attention op's keys and values), and the
        # readers of them all; published where the model has one
        exported, reads = exported_reads(self.nodes)
        if exported:
            out["executor.shared_tensors"] = len(exported)
            out["executor.shared_tensor_readers"] = len(reads)
        heads = {n.op.name: n.op.num_heads for n in self.nodes
                 if hasattr(n.op, "num_kv_heads")}
        if len(set(heads.values())) > 1:
            out.update((f"attention/heads_by_op/{name}", h)
                       for name, h in heads.items())
        return out

    def _training_nodes(self):
        """Node list the TRAIN step runs: (Conv2D, BatchNorm) pairs whose
        searched kernel choice is ``_k:conv_bn_fused`` execute as one
        fused region (layout.TrainFusedConvBN — batch-stats BN, state
        updates preserved); everything else is ``self.nodes`` untouched.
        Built once per executor."""
        names = {n for n, impl in (self.kernel_choices or {}).items()
                 if impl == "conv_bn_fused"}
        if not names:
            return self.nodes
        if not hasattr(self, "_train_fused_nodes"):
            from flexflow_tpu.layout import fuse_conv_bn_train
            self._train_fused_nodes = fuse_conv_bn_train(
                self.nodes, names, keep_guids={self.final_ref[0]})
        return self._train_fused_nodes

    def _optimizer_update(self, grads, opt_state, params):
        """Optimizer update honoring per-op ``_k:fused`` kernel choices:
        the chosen ops' leaves update through the one-dispatch fused
        region (ops/fused_update.py, bit-compatible with the reference
        triad); the rest take ``optimizer.update`` unchanged. No fused
        choices = exactly the pre-kernel-search call."""
        # sorted: the fused regions are traced in this order, and a set's
        # order follows the process's hash seed, so that two processes
        # would lower two different programs and miss each other's
        # entries in the persistent compile cache
        fused = sorted(n for n in self.fused_update_ops if n in params)
        if not fused:
            return self.optimizer.update(grads, opt_state, params)
        from flexflow_tpu.ops.fused_update import fused_optimizer_update
        return fused_optimizer_update(self.optimizer, grads, opt_state,
                                      params, fused, mesh=self.mesh,
                                      spec_of=self.master_spec)

    def _train_step_fn(self):
        """The raw (unjitted) train-step function, for composition into
        multi-step scans."""
        train_nodes = self._training_nodes()

        def train_step(params, opt_state, state, inputs, labels, rng):
            cparams = (state[COMPUTE_PARAMS_KEY]
                       if self.use_master_copy else params)

            def loss_fn(p):
                ctx = OpContext(training=True, rng=rng,
                                compute_dtype=self.compute_dtype,
                                mesh=self.mesh)
                counters = {}
                values, new_state, aux = self.run_graph(
                    p, state, inputs, ctx, nodes=train_nodes,
                    counters=counters)
                logits = values[self.final_ref]

                def loss_of(logits, labels, aux):
                    counted = {}
                    loss = self._loss_value(logits, labels, counted)
                    for a in aux:
                        loss = loss + a
                    return loss, counted

                loss, counted = scoped("loss", loss_of)(logits, labels, aux)
                counters.update(counted)
                return loss, (logits, new_state, counters)

            (loss, (logits, new_state, counters)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(cparams)
            # the ops' forwards have run (this is trace time): what
            # they and the loss witnessed
            for gauge, value in self.traced_gauges().items():
                get_registry().gauge(gauge, value)
            # gradient sync over the data axes is inserted by GSPMD here
            # (in bf16 under the master-weight regime — half the bytes).
            # Under WUS the shard constraint turns that all-reduce into a
            # reduce-scatter: each chip receives only the gradient shard
            # whose master-param/moment shard it owns.
            grads = self._wus_shard(grads)

            def update(grads, opt_state, params):
                new_params, new_opt_state = self._optimizer_update(
                    grads, opt_state, params)
                new_params = self._wus_shard(new_params)
                if not self.use_master_copy:
                    return new_params, new_opt_state, None
                # next step's bf16 working copy, fused into the update
                # loop (one extra bf16 write instead of a separate cast
                # pass; under WUS the compute-spec constraint is the
                # all-gather that rebuilds the replicated copy from the
                # shards)
                return new_params, new_opt_state, self._constrain_compute(
                    self._cast_tree(new_params))

            new_params, new_opt_state, compute_copy = scoped(
                "optimizer_update", update)(grads, opt_state, params)
            if compute_copy is not None:
                new_state[COMPUTE_PARAMS_KEY] = compute_copy
            metric_vals = self.metrics.compute(logits, labels)
            # what the ops counted leaves the step with the metrics and is
            # read with them, once an epoch
            metric_vals.update(counters)
            return new_params, new_opt_state, new_state, loss, metric_vals

        return train_step

    def make_train_step(self):
        if getattr(self, "comp_mode", CompMode.TRAINING) == CompMode.INFERENCE:
            raise RuntimeError(
                "model compiled with CompMode.INFERENCE is forward-only; "
                "re-compile with CompMode.TRAINING to train")
        if self._jit_train is None:
            self._jit_train = jax.jit(self._train_step_fn(),
                                      donate_argnums=(0, 1, 2))
            get_registry().inc("executor.train_step_jits")
            get_registry().gauge("executor.num_ops", len(self.nodes))
            for gauge, kind in (("executor.ssm_ops", OperatorType.SSM_MIXER),
                                ("executor.expert_ops",
                                 OperatorType.MOE_LAYER)):
                get_registry().gauge(gauge, sum(
                    n.op.op_type == kind for n in self.nodes))
        return self._jit_train

    def make_multi_step(self, num_iters: int, stacked: bool = False):
        """Compile ``num_iters`` training steps into ONE XLA program via
        lax.scan — the TPU analog of the reference's Legion trace replay
        (begin_trace/end_trace around each iteration, flexflow_cffi.py:2079):
        after the first compile the whole iteration block runs with zero
        per-step dispatch overhead.

        ``stacked=False``: (inputs, labels) is one batch reused every
        iteration (the reference examples' 'load data once' benchmark mode).
        ``stacked=True``: each array carries a leading [num_iters] axis and
        iteration i consumes slice i.
        """
        if getattr(self, "comp_mode", CompMode.TRAINING) == CompMode.INFERENCE:
            raise RuntimeError(
                "model compiled with CompMode.INFERENCE is forward-only; "
                "re-compile with CompMode.TRAINING to train")

        step = self._train_step_fn()

        def multi(params, opt_state, state, inputs, labels, rng):
            def body(carry, xs):
                params, opt_state, state, rng = carry
                rng, sub = jax.random.split(rng)
                inp, lab = xs if stacked else (inputs, labels)
                params, opt_state, state, loss, mvals = step(
                    params, opt_state, state, inp, lab, sub)
                return (params, opt_state, state, rng), loss

            xs = (inputs, labels) if stacked else None
            (params, opt_state, state, rng), losses = jax.lax.scan(
                body, (params, opt_state, state, rng), xs,
                length=None if stacked else num_iters)
            return params, opt_state, state, losses

        return jax.jit(multi, donate_argnums=(0, 1, 2))

    def _inference_nodes(self):
        """Node list the forward-only executables run: eligible Conv2D→
        BatchNorm(+ReLU) pairs folded into single convolutions
        (flexflow_tpu/layout.fold_conv_bn — eval BN is an affine transform
        of running stats, which collapses into the conv weights; the
        training step keeps the full graph since batch statistics cannot
        fold). Built once per executor."""
        if not self.fold_conv_bn:
            return self.nodes
        if not hasattr(self, "_folded_nodes"):
            from flexflow_tpu.layout import fold_conv_bn
            self._folded_nodes = fold_conv_bn(
                self.nodes, keep_guids={self.final_ref[0]})
        return self._folded_nodes

    def make_eval_step(self):
        if self._jit_eval is not None:
            return self._jit_eval
        inf_nodes = self._inference_nodes()

        def eval_step(params, state, inputs, labels):
            ctx = OpContext(training=False, compute_dtype=self.compute_dtype,
                            mesh=self.mesh)
            values, _, _ = self.run_graph(params, state, inputs, ctx,
                                          nodes=inf_nodes)
            logits = values[self.final_ref]
            loss = self._loss_value(logits, labels)
            return loss, logits, self.metrics.compute(logits, labels)

        self._jit_eval = jax.jit(eval_step)
        get_registry().inc("executor.eval_step_jits")
        return self._jit_eval

    def make_forward(self, training: bool = False):
        if training in self._jit_fwd:
            return self._jit_fwd[training]
        inf_nodes = None if training else self._inference_nodes()

        def fwd(params, state, inputs, rng):
            ctx = OpContext(training=training, rng=rng,
                            compute_dtype=self.compute_dtype, mesh=self.mesh)
            values, new_state, _ = self.run_graph(params, state, inputs, ctx,
                                                  nodes=inf_nodes)
            return values[self.final_ref], new_state

        self._jit_fwd[training] = jax.jit(fwd)
        return self._jit_fwd[training]

    def batch_sharding(self):
        da = tuple(self.data_axes)
        return NamedSharding(self.mesh, P(da) if da else P())

    def label_sharding(self):
        """Sharding for staged label arrays. Defaults to the batch
        sharding; executors that stage inputs in a different layout
        (the pipeline's pipe-sharded microbatch queue) keep labels
        data-sharded — labels only meet the loss, after the boundary
        output is already back in the data layout."""
        return self.batch_sharding()
