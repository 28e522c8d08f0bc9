"""Op base class and registry.

Analog of the reference's ``Op`` (include/flexflow/operator.h:51) with the
Legion task plumbing removed: an Op here is (a) a pure forward function
``forward(params, inputs, ctx)`` traced into the jitted step, (b) parameter
initialization, (c) cost metadata (flops / bytes) for the simulator, and
(d) dimension-role metadata that tells the search which dims are legal to
shard (the reference encodes this as is_valid_parallel_config +
substitution applicability).

The reference's per-op ``*Params`` structs (dedup/cache keys,
include/flexflow/ops/linear_params.h) map to ``Op.param_key()``.
"""

from __future__ import annotations

import enum
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.ffconst import DataType, OperatorType
from flexflow_tpu.layer import Layer
from flexflow_tpu.tensor import Tensor


class DimRole(enum.Enum):
    """Role of an output dimension — drives the legal sharding axes."""

    SAMPLE = "sample"  # batch dim: data parallelism
    CHANNEL = "channel"  # feature dim: parameter (tensor) parallelism
    HEAD = "head"  # attention head dim: attribute parallelism
    SEQ = "seq"  # sequence dim: context parallelism
    EXPERT = "expert"  # MoE expert dim
    OTHER = "other"  # never sharded


class OpContext:
    """Per-call context threaded through forward: training flag, rng, policy."""

    def __init__(self, training: bool = False, rng: Optional[jax.Array] = None,
                 compute_dtype=jnp.float32, seq_length: Optional[int] = None,
                 mesh=None):
        self.training = training
        self.rng = rng
        self.compute_dtype = compute_dtype
        self.seq_length = seq_length
        self.mesh = mesh  # jax.sharding.Mesh for ops needing manual collectives

    def next_rng(self) -> jax.Array:
        if self.rng is None:
            raise ValueError("op needs rng but none provided")
        self.rng, sub = jax.random.split(self.rng)
        return sub


def scoped(name: str, fn: Callable) -> Callable:
    """`fn` as a nested jit called `name`, for the device trace: every
    instruction it lowers to carries `jit(<name>)` in its `op_name`,
    forward (`jvp(jit(name))`) and backward (`transpose(jvp(jit(name)))`).
    A `jax.named_scope` alone is not enough on the TPU: in a large step
    the compiled program's metadata keeps the scope only for instructions
    that come from a nested call (read on the v5e, PR 27: `dot_general`
    where the same step compiled for a described chip had
    `jit(train_step)/jvp(ssm_mixer)/ssd_scan/dot_general`). XLA inlines
    the call, so nothing changes in what runs.

    The one mechanism behind every name of a train step
    (`obs/step_scopes.py` holds the rule that reads them back). Taken:
    `loss`, `optimizer_update` (the executor's step), `head` (the op
    that produces the model's output), `op_<operator type, lower case>`
    (every op that does not scope itself; `GraphExecutor._run_nodes`),
    `ssm_mixer` / `ssd_scan`, `moe_layer` / `moe_route` / `moe_combine`
    / `moe_grouped_matmul` / `moe_shared`, `attention_window` /
    `attention_full` / `attention_block_diffusion` with `flash_window` /
    `flash_full` / `flash_block_diffusion` in them, and
    `attention_plain`; `gated_conv` inside `op_short_conv` (PR 45);
    `mamba_mixer` / `selective_scan`, `gated_memory`,
    `attention_diff_full` / `attention_diff_window` /
    `attention_diff_cross` with `flash_diff` in them (PR 52);
    `attention_sparse` with `flash_sparse` in it, and `sparse_indexer`
    beside it (PR 54). The
    benchmark's readers match nine of them as bare substrings of an
    `op_name`, so a new name holds none of them.

    A nested call also renames the events of the kernels in it: XLA
    names a custom call after the innermost scope (`flash_window.N`,
    `gmm.N`) where a top-level one reads `tpu_custom_call.N`, and
    `kernels.flash_roofline` sums the events of that name. So the
    non-causal attention op scopes what lies around its kernels
    (`attention_plain`: projections, rotary, repeat, output projection)
    and calls the kernels themselves at the top level; the join table
    names such an instruction by the kernel's own `name=`."""
    def call(*args):
        return fn(*args)

    call.__name__ = call.__qualname__ = name
    return jax.jit(call)


def _unless_reader(method: Callable, nothing: Callable) -> Callable:
    """``method`` (an op's ``init_params`` / ``params_elems``) for an op
    that owns its leaves; ``nothing()`` for one that reads them out of
    another op (``Op.tied_params``): it holds none."""
    @functools.wraps(method)
    def owned(self, *args):
        return nothing() if self.tied_params else method(self, *args)

    return owned


# layer properties that place an op and shape nothing of it
PLACING_PROPERTIES = ("scope", "shared_op")


class Op:
    op_type: OperatorType = OperatorType.NOOP
    # parameter names the executor keeps in float32 in the compute copy
    full_precision_params: Tuple[str, ...] = ()
    # the nested call (`scoped`) the op's forward makes around itself;
    # "": the executor wraps the op in one named for its kind
    scopes_itself: str = ""
    # outputs beyond the first that OTHER LAYERS read (a scan's memory,
    # an attention op's projected keys and values): PCG tensors like any
    # other, whose readers take them as inputs; the producer's gradient
    # sums over them. No rewrite re-forms such an op or its readers, and
    # no remat twin stands for it (search/unity.py `serialize_graph`)
    exports: int = 0

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        for name, nothing in (("init_params", dict), ("params_elems", int)):
            if name in cls.__dict__:
                setattr(cls, name, _unless_reader(cls.__dict__[name],
                                                  nothing))

    @property
    def tied_params(self) -> Dict[str, Tuple[str, str]]:
        """Leaves this op's forward reads out of ANOTHER op's parameters:
        {name in this op's `params`: (owner op's name, its leaf's name)}.
        Filled for ALL of the op's leaves where its layer names an owner
        (`FFModel._add_layer(shared_op=)`, upstream's argument of that
        name). The owner holds the one leaf (and its optimizer state);
        the executor hands it in (`executor.op_params`), inside the
        differentiated function, so its gradient sums every use. A
        reader holds no leaf: `init_params` gives {}, `params_elems` 0."""
        if "_tied_params" not in self.__dict__:
            layer = getattr(self, "layer", None)
            owner = getattr(layer, "properties", {}).get("shared_op")
            self._tied_params = {
                leaf: (owner, leaf) for leaf in self.shared_leaves()[1]
            } if owner else {}
        return self._tied_params

    def shared_leaves(self) -> Tuple[OperatorType,
                                     Dict[str, Tuple[int, ...]]]:
        """(kind, {leaf: shape}) of the op whose leaves this one can
        read: by default an op of its own kind with the leaves it would
        initialise itself. `FFModel.compile` holds a reader's owner to
        it."""
        if "_own_leaves" not in self.__dict__:
            own = getattr(type(self).init_params, "__wrapped__",
                          type(self).init_params)
            tree = jax.eval_shape(lambda rng: own(self, rng),
                                  jax.random.PRNGKey(0))
            self._own_leaves = {k: tuple(v.shape) for k, v in tree.items()}
        return self.op_type, self._own_leaves

    def traced_gauges(self) -> Dict[str, float]:
        """{gauge key: value} of what this op's forward, as last traced,
        did on the host (which kernel or operand form it took, what its
        kernels will visit): {} for an op that witnesses nothing. The
        ONE place an op says it; `GraphExecutor.traced_gauges` adds the
        ops' dicts up key by key and publishes the sums to the registry's
        gauges, `FFModel.op_counters` and every trace header (there under
        the key's last dotted part with `/` as `_`). Values are 0 before
        a forward has been traced."""
        return {}

    def __init__(self, layer: Layer, input_shapes: Sequence[Tuple[int, ...]]):
        self.layer = layer
        self.name = layer.name
        self.guid = layer.guid
        self.input_shapes: List[Tuple[int, ...]] = [tuple(s) for s in input_shapes]
        self.output_shapes: List[Tuple[int, ...]] = self.compute_output_shapes()
        self.dtype: DataType = layer.data_type

    # ---- graph-construction interface -------------------------------------
    def compute_output_shapes(self) -> List[Tuple[int, ...]]:
        raise NotImplementedError

    def init_params(self, rng: jax.Array) -> Dict[str, jax.Array]:
        """Initialize trainable parameters; {} for param-free ops."""
        return {}

    def forward(self, params: Dict[str, jax.Array], inputs: List[jax.Array],
                ctx: OpContext) -> List[jax.Array]:
        raise NotImplementedError

    # ---- search metadata ---------------------------------------------------
    def output_dim_roles(self) -> List[Tuple[DimRole, ...]]:
        """Per-output tuple of DimRoles; default: dim0=SAMPLE, rest OTHER."""
        roles = []
        for shp in self.output_shapes:
            roles.append(
                tuple(
                    DimRole.SAMPLE if i == 0 else DimRole.OTHER
                    for i in range(len(shp))
                )
            )
        return roles

    def flops(self) -> int:
        """Forward-pass FLOPs (global, unsharded). Backward ≈ 2x."""
        return 2 * sum(int(np.prod(s)) for s in self.output_shapes)

    def params_elems(self) -> int:
        return 0

    def tied_param_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """Shapes of the ``tied_params``, for who runs the op alone."""
        return self.shared_leaves()[1] if self.tied_params else {}

    def param_key(self) -> Tuple:
        """Structural identity for node dedup / cost caching
        (analog of *Params hashing, model.h:677): where a layer lies
        (its trace scope, whose leaves it reads) is no part of it, so
        the applications of one layer are measured once."""
        return (
            self.op_type,
            tuple(self.input_shapes),
            tuple(sorted(
                (k, repr(v)) for k, v in self.layer.properties.items()
                if k not in PLACING_PROPERTIES
            )),
        )

    def __repr__(self):
        return f"{type(self).__name__}({self.name})"


class OpRegistry:
    _by_type: Dict[OperatorType, Callable[..., Op]] = {}

    @classmethod
    def create(cls, layer: Layer, input_shapes) -> Op:
        if layer.op_type not in cls._by_type:
            raise NotImplementedError(f"no Op registered for {layer.op_type}")
        return cls._by_type[layer.op_type](layer, input_shapes)


def register_op(op_type: OperatorType):
    def deco(klass):
        klass.op_type = op_type
        OpRegistry._by_type[op_type] = klass
        return klass

    return deco


def exported_reads(nodes) -> Tuple[set, List[Tuple[int, Tuple[int, int]]]]:
    """(outputs, reads) of a node list: the (guid, index) of every output
    beyond an op's first that other layers read (``Op.exports``), and the
    input edges that read one, as (reader's guid, (guid, index)). The one
    place the PCG's exported edges are found: the executor counts them,
    the search pins both their ends."""
    outputs = {(n.guid, i) for n in nodes
               for i in range(1, 1 + n.op.exports)}
    reads = [(n.guid, tuple(ref[1:3])) for n in nodes for ref in n.input_refs
             if ref[0] == "op" and tuple(ref[1:3]) in outputs]
    return outputs, reads


def shared_leaves_error(op: "Op", ops: Dict[str, "Op"]) -> Optional[str]:
    """Why ``op`` cannot read its leaves out of the op its layer names
    as their owner (``_add_layer(shared_op=)``), or None: the owner has
    to be in the graph, hold its own leaves, and be of the kind and
    with the leaves' shapes that the reader states
    (``Op.shared_leaves``: its own, or for a tied head an embedding's
    table). ``ops``: name -> op. `FFModel.compile` raises on it, fflint's
    hygiene pass reports it (FFL605)."""
    owners = {owner for owner, _ in op.tied_params.values()}
    if not owners:
        return None
    (name,) = owners
    owner = ops.get(name)
    if owner is None:
        return (f"reads its leaves out of '{name}', which is no layer of "
                f"the graph")
    if owner.tied_params:
        return (f"reads its leaves out of '{name}', which holds none of "
                f"its own")
    wanted, has = op.shared_leaves(), owner.shared_leaves()
    if wanted != has:
        return (f"reads its leaves out of '{name}', a {has[0].name} with "
                f"leaves {has[1]}, and needs a {wanted[0].name}'s "
                f"{wanted[1]}")
    return None
