"""Parallelization strategies: per-op sharding assignment over the mesh.

The reference expresses a strategy as per-op MachineViews + the four
resharding ops inserted in the PCG (SURVEY §2.3); on TPU a strategy is a
map op-guid -> OpStrategy{output PartitionSpecs, param PartitionSpecs}.
GSPMD then inserts the collectives that the reference's
Repartition/Combine/Replicate/Reduction ops perform explicitly.

``data_parallel_strategy`` is the analog of
``--only-data-parallel`` (graph.cc:1939-1964): batch dim of every
activation sharded over the 'data' axis, parameters replicated (their
gradient psum is the NCCL allreduce analog).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from jax.sharding import PartitionSpec as P

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops.base import DimRole
from flexflow_tpu.parallel.choice import Choice


@dataclasses.dataclass
class OpStrategy:
    output_specs: List[Optional[P]]
    param_specs: Dict[str, P] = dataclasses.field(default_factory=dict)
    # the search's name for its pick (None on heuristic strategies): the
    # form strategy files and reports hold; code reads ``parsed``
    choice: Optional[str] = None

    @property
    def parsed(self) -> Choice:
        return Choice.parse(self.choice)


Strategy = Dict[int, OpStrategy]


def _axis_size(mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def data_parallel_strategy(nodes, mesh) -> Strategy:
    """Batch dim over 'data'; if the mesh carries a 'seq' axis, SEQ-role
    dims shard over it too (context parallelism: activations stay
    seq-sharded between ring-attention ops)."""
    dp = _axis_size(mesh, "data")
    sp = _axis_size(mesh, "seq")
    strategy: Strategy = {}
    for node in nodes:
        specs = []
        for shp, roles in zip(node.op.output_shapes, node.op.output_dim_roles()):
            entries = [None] * len(shp)
            if (dp > 1 and shp and roles and roles[0] == DimRole.SAMPLE
                    and shp[0] % dp == 0):
                entries[0] = "data"
            if sp > 1:
                for d, role in enumerate(roles):
                    if role == DimRole.SEQ and shp[d] % sp == 0:
                        entries[d] = "seq"
                        break
            specs.append(P(*entries) if any(e for e in entries) else None)
        strategy[node.op.guid] = OpStrategy(output_specs=specs)
    return strategy


def tensor_parallel_overrides(nodes, mesh, strategy: Strategy) -> Strategy:
    """Shard weight-heavy ops on the 'model' axis: Linear column-parallel
    (kernel [in, out] -> out sharded), attention head-parallel, embedding
    vocab-parallel. Analog of the parameter/attribute-parallel
    substitutions (substitution.cc:1756-1809)."""
    mp = _axis_size(mesh, "model")
    if mp <= 1:
        return strategy
    for node in nodes:
        op = node.op
        st = strategy[op.guid]
        if op.op_type == OperatorType.LINEAR and op.out_dim % mp == 0:
            st.param_specs["kernel"] = P(None, "model")
            st.param_specs["bias"] = P("model")
            shp = op.output_shapes[0]
            base = st.output_specs[0] or P(*([None] * len(shp)))
            st.output_specs[0] = P(*list(base)[:-1], "model")
        elif op.op_type == OperatorType.MULTIHEAD_ATTENTION and op.num_heads % mp == 0:
            st.param_specs.update({
                "wq": P("model", None, None),
                "wo": P("model", None, None),
            })
            # GQA: wk/wv carry num_kv_heads (< num_heads) on dim 0 — only
            # shard them when the kv-head count divides the axis too
            if getattr(op, "num_kv_heads", op.num_heads) % mp == 0:
                st.param_specs.update({
                    "wk": P("model", None, None),
                    "wv": P("model", None, None),
                })
        elif op.op_type == OperatorType.EMBEDDING and op.out_dim % mp == 0:
            st.param_specs["kernel"] = P(None, "model")
    return strategy


# ops that preserve shape and follow their input's sharding: a manual
# parallel op's layout propagates through these until the next layout- or
# value-changing op (matches the reference, where a Repartition changes the
# ParallelTensor layout every consumer then sees)
_FOLLOW_OPS = frozenset({
    OperatorType.RELU, OperatorType.GELU, OperatorType.SIGMOID,
    OperatorType.TANH, OperatorType.ELU, OperatorType.EXP, OperatorType.SIN,
    OperatorType.COS, OperatorType.POW, OperatorType.RSQRT,
    OperatorType.IDENTITY, OperatorType.SCALAR_MULTIPLY,
    OperatorType.SCALAR_ADD, OperatorType.SCALAR_SUB,
    OperatorType.SCALAR_TRUE_DIV, OperatorType.DROPOUT, OperatorType.CAST,
    OperatorType.SOFTMAX, OperatorType.LAYERNORM, OperatorType.RMSNORM,
})


def _axis_entry_valid(entry, valid_axes) -> bool:
    if entry is None:
        return True
    axes = entry if isinstance(entry, tuple) else (entry,)
    return all(a in valid_axes for a in axes)


def apply_strategy(nodes, strategy: Strategy, mesh) -> None:
    by_guid = {n.op.guid: n for n in nodes}
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    # guid -> spec entries forced by an upstream manual parallel op
    forced: Dict[int, List] = {}
    for node in nodes:
        st = strategy.get(node.op.guid)
        if st is not None:
            node.output_specs = list(st.output_specs)
            node.param_specs = dict(st.param_specs)
            # the searched choice switches the op's execution path (the
            # analog of a substitution rewrite changing the op's task
            # implementation): "flash"/"einsum" say which kernel runs an
            # attention op (plan_execution clears it again when the kernel
            # dimension is off; "fused"/"conv_bn_fused" are the
            # executor's), a ring choice runs it over the mesh's 'seq'
            # axis, a head choice keeps heads distributed under shard_map
            choice = st.parsed
            if hasattr(node.op, "seq_parallel"):
                if choice.kernel in ("flash", "einsum"):
                    node.op.kernel_impl = choice.kernel
                if choice.ring and axis_sizes.get("seq", 1) > 1:
                    node.op.seq_parallel = "seq"
                if choice.head and axis_sizes.get("model", 1) > 1:
                    node.op.head_parallel = "model"
                # record the batch-dim sharding (may be a tuple under the
                # sample2 'data+model' 2-D partition) so the flash-attention
                # shard_map keeps the joint sharding instead of forcing an
                # all-gather over the model axis (advisor r3 finding)
                spec0 = st.output_specs[0] if st.output_specs else None
                if spec0:
                    entries = list(spec0)
                    node.op.batch_parallel = entries[0] if entries else None
            if (hasattr(node.op, "expert_parallel") and choice.expert
                    and axis_sizes.get("expert", 1) > 1):
                node.op.expert_parallel = "expert"
        op = node.op
        is_par = getattr(op, "is_parallel_op", False)
        if (is_par and hasattr(op, "preferred_spec_update")) or (
            op.op_type in _FOLLOW_OPS and node.input_refs
            and node.input_refs[0][0] == "op"
            and node.input_refs[0][1] in forced
        ):
            ref = node.input_refs[0]
            nd = len(op.output_shapes[0])
            if ref[0] == "op" and ref[1] in forced:
                src = forced[ref[1]]
            elif ref[0] == "op" and ref[1] in by_guid:
                src = by_guid[ref[1]].output_specs[ref[2]]
            else:
                src = None
            entries = (list(src) + [None] * nd)[:nd] if src else [None] * nd
            if is_par:
                if (op.op_type == OperatorType.REPARTITION
                        and op.axis in axis_sizes
                        and op.repartition_degree != axis_sizes[op.axis]):
                    raise ValueError(
                        f"repartition degree {op.repartition_degree} != mesh "
                        f"axis '{op.axis}' size {axis_sizes[op.axis]} — under "
                        f"GSPMD the degree must equal the axis extent")
                entries = op.preferred_spec_update(entries)
            entries = [e if _axis_entry_valid(e, axis_sizes) else None
                       for e in entries]
            used = [e for e in entries if e is not None]
            if len(used) != len(set(used)):
                raise ValueError(
                    f"parallel op '{op.name}' would shard two dims over the "
                    f"same mesh axis ({entries}); repartition a dim that is "
                    f"not already sharded on that axis")
            node.output_specs = [P(*entries)] + node.output_specs[1:]
            forced[op.guid] = entries


