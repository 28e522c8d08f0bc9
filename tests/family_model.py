"""What the ten family files share (ROADMAP D24): the tiny cut of a cell
built once a module, one op alone, the program's and the reference's
loss as functions of the weights, the leaf-by-leaf comparison, the
controls' stated side made once, and the shares of an expert layer as
ONE program. A test's cost on the CPU is the programs it compiles
(`tests/one_program.py`), so whatever several tests read is made here
once and kept.

Nothing here outlives its module's tests but what the caller keeps: a
family module builds through `build_tiny`, which takes nothing from the
module before it (`tests/test_keye.py::
test_every_gradient_leaf_matches_the_reference` builds two in turn and
holds the second to the first's bits)."""

import collections
import contextlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness as hs  # noqa: E402
from benchmarks import manifest as mf  # noqa: E402
from flexflow_tpu.ffconst import OperatorType  # noqa: E402
from flexflow_tpu.layer import Layer  # noqa: E402
from flexflow_tpu.ops.base import OpContext, OpRegistry  # noqa: E402

# a rate at which two Adam steps move the loss, moments in float32
ADAM = dict(alpha=1e-3, beta1=0.9, beta2=0.999, epsilon=1e-8,
            weight_decay=0.0, state_dtype="float32")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


@contextlib.contextmanager
def pallas(mode):
    """`FLEXFLOW_TPU_PALLAS` for a module-scoped fixture, which
    `monkeypatch` does not serve: "interpret" runs the kernels."""
    old = os.environ.get("FLEXFLOW_TPU_PALLAS")
    os.environ["FLEXFLOW_TPU_PALLAS"] = mode
    try:
        yield
    finally:
        if old is None:
            del os.environ["FLEXFLOW_TPU_PALLAS"]
        else:
            os.environ["FLEXFLOW_TPU_PALLAS"] = old


def highest():
    """float32 products, as a NEW context a use: one object entered
    inside itself forgets what it has to restore and leaves "highest"
    behind in the worker (PR 47)."""
    return jax.default_matmul_precision("highest")


def make_op(kind, props, shapes):
    layer = Layer(kind, "op", [])
    layer.properties.update(props)
    return OpRegistry.create(layer, shapes)


def op_program(op):
    """(params, inputs) -> the op's first output as a numpy array, in
    float32 products: ONE program for every call of one shape, so a loop
    over shares compiles once."""
    ctx = OpContext(training=False, compute_dtype=jnp.float32)
    program = jax.jit(lambda p, x: op.forward(p, x, ctx)[0])

    def run(params, inputs):
        with highest():
            return np.asarray(program(params, list(inputs)))

    return run


def run_op(op, params, inputs):
    return op_program(op)(params, inputs)


def load_cell(cell, adam=ADAM, **config):
    """(family, config, traffic) of a cell of the manifest, with
    ``adam``'s rate and moments' dtype (None: the cell's own) and
    whatever ``config`` overrides."""
    _, stated, traffic = mf.find_cell(mf.load_manifest(ROOT), cell, ROOT)
    family = hs.load_by_path("families", stated["family"], ROOT)
    if adam:
        config["adam"] = dict(stated["adam"], alpha=adam["alpha"],
                              state_dtype=adam["state_dtype"])
    return family, dict(stated, **config), traffic


Tiny = collections.namedtuple(
    "Tiny", "family config s traffic xs y weights ff")


def build_model(family, config, s, seed=11):
    """(model, weights, xs, y): the sizes ``s`` built through the
    family's own `build`, the seed's weights installed, the seed's
    batch."""
    xs, y = family.make_data(s, seed)
    weights = jax.device_get(family.make_weights(s, seed))
    ff = family.build(config, s, 1, seed)
    family.install_weights(ff, weights)
    return ff, weights, xs, y


def build_tiny(cell, sizes, seed=11):
    """The cut ``sizes`` of a cell (its name, or what `load_cell` gave)
    as a `Tiny`."""
    family, config, traffic = load_cell(cell) if isinstance(cell, str) \
        else cell
    s = family.sizes(config, traffic, sizes)
    ff, weights, xs, y = build_model(family, config, s, seed)
    return Tiny(family, config, s, traffic, xs, y, weights, ff)


def built_by_name(cell, sizes):
    """name -> `build_tiny(cell, sizes[name])`, each built once."""
    cache = {}

    def get(name):
        if name not in cache:
            cache[name] = build_tiny(cell, sizes[name])
        return cache[name]

    return get


def as_arrays(weights):
    return jax.tree.map(jnp.asarray, weights)


def program_loss_of(ff, xs, y):
    """The program's loss on the whole batch as a function of its
    parameters: the executor's graph and loss, no optimizer."""
    ex = ff.executor
    inputs = ff._stage_inputs([xs[0]])
    labels = ff._shard_batch(y)

    def loss(p):
        ctx = OpContext(training=True, rng=jax.random.PRNGKey(0),
                        compute_dtype=ex.compute_dtype, mesh=ex.mesh)
        values, _, _ = ex.run_graph(p, {}, inputs, ctx)
        return ex._loss_value(values[ex.final_ref], labels)

    return loss


def reference_gradient(tiny):
    """The reference's gradient of its loss on the whole epoch's batch,
    by the harness's own driver (`references.common`): chunk by chunk
    through the ONE program that the three-losses test compiles too,
    whichever of the two runs first."""
    from benchmarks.references import common
    ref, kw, chunk = tiny.family.reference(tiny.s, tiny.traffic)
    assert ref.loss_denominator(tiny.y) == tiny.y.size
    return common.loss_and_grads(ref, as_arrays(tiny.weights), tiny.xs[0],
                                 tiny.y, chunk, **kw)[1]


def gradients_of(tiny):
    """(the weights as arrays, the program's gradient of its loss, the
    reference's of its own) on the whole epoch's batch."""
    params = as_arrays(tiny.weights)
    with highest():
        got = jax.jit(jax.grad(program_loss_of(tiny.ff, tiny.xs, tiny.y)))(
            params)
    return params, got, reference_gradient(tiny)


def assert_leaves_close(got, want, atol=2e-4, still=()):
    """Leaf by leaf, to ``atol`` of the leaf's largest entry; a leaf
    whose name holds one of ``still`` moves no gradient on the program's
    side and is not counted. -> the leaves compared."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    leaves = 0
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if any(part in name for part in still):
            assert not np.any(np.asarray(g)), name
            continue
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=atol,
                                   err_msg=name)
        leaves += 1
    return leaves


def predictions(ff, tiny):
    """``ff``'s float32 predictions on the module's first batch."""
    return np.asarray(ff.predict([tiny.xs[0][:tiny.s["batch"]]])).astype(
        np.float32)


def control_model(tiny, sizes, weights=None):
    """The program built otherwise (``sizes`` hold `program_*` keys,
    which reach `family.build` alone) on the module's weights."""
    s = tiny.family.sizes(tiny.config, tiny.traffic, sizes)
    ff = tiny.family.build(tiny.config, s, 1, 11)
    tiny.family.install_weights(ff, tiny.weights if weights is None
                                else weights)
    return ff, s


def reference_predictions(tiny, s=None, weights=None):
    """The reference's predictions and the loss of them (`steps=1`: no
    gradient is taken) on the module's first batch."""
    s = s or tiny.s
    return hs.reference_side(
        tiny.family, tiny.weights if weights is None else weights, s,
        tiny.traffic, tiny.config, tiny.xs, tiny.y, s["batch"], steps=1)


def compiled_step_text(tiny):
    """The optimized text of the module's train step on its first
    batch."""
    ff, batch = tiny.ff, tiny.s["batch"]
    return ff.executor.make_train_step().lower(
        ff.params, ff.opt_state, ff.state,
        ff._stage_inputs([tiny.xs[0][:batch]]),
        ff._shard_batch(tiny.y[:batch]),
        jax.random.PRNGKey(0)).compile().as_text()


def expert_shares(props, params, inputs, held, chips, reference=None,
                  rtol=2e-4, atol=2e-5, leaves=EXPERT_LEAVES):
    """The outputs of ``chips`` expert layers that hold ``held`` experts
    each, chip c those from c * held on, with chip c's slice of the
    ``leaves`` of the uncut layer's ``params``. ONE program: a chip's
    offset only moves the expert ids (`moe.route_held_experts`), so the
    wrapper hands it over as an operand where the property
    `expert_offset` would make a program a chip. No pair overflows a
    chip's buffer, or the shares would not add up to the uncut layer.
    ``reference(share, offset)``: the reference's own share, one program
    too, which each part is held to."""
    inputs = list(inputs)
    op = make_op(OperatorType.MOE_LAYER,
                 dict(props, experts_held=held, expert_offset=0),
                 [x.shape for x in inputs])
    ctx = OpContext(training=False, compute_dtype=jnp.float32)

    def share(p, xs, offset):
        op.expert_offset = offset
        try:
            out = op.forward(p, xs, ctx)[0]
            return out, op._counters["moe/overflow_slots"][1]
        finally:
            op.expert_offset, op._counters = 0, None

    share = jax.jit(share)
    reference = reference and jax.jit(reference)
    parts = []
    with highest():
        for chip in range(chips):
            offset = held * chip
            cut = dict(params, **{n: params[n][offset:offset + held]
                                  for n in leaves})
            out, overflow = share(cut, inputs, jnp.int32(offset))
            assert int(overflow) == 0, chip
            parts.append(np.asarray(out))
            if reference:
                np.testing.assert_allclose(
                    parts[-1], reference(cut, jnp.int32(offset)),
                    rtol=rtol, atol=atol)
    return parts
