"""The heads' norm and rotary as one lane-dense pass (PR 42):
`pallas_kernels.rotary_lanes` over a projection's `[B, S, H*128]` result
against the forms it stands in for, `rotary_embedding` / `rotary_partial`
over a `[B, S, H, D]` view (and `_heads_normed` before them), which every
shape the pass does not take still runs. Since PR 47 also at heads of 64,
two a 128-lane column (`FORMS_64`).

Both sides of a comparison are jitted: compiled alone on the CPU, a
product and the sum it enters may or may not contract into one fused
multiply-add, a unit in the last place that says nothing about either
form. On the chip the two read the same (`scripts/gate_lab.py`)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.layer import Layer
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops.attention import (rotary_embedding, rotary_frequencies,
                                        rotary_partial, rotary_tables)
from flexflow_tpu.ops.base import OpContext, OpRegistry

YARN = dict(rope_type="yarn", factor=64, beta_fast=64, beta_slow=1,
            original_max_position_embeddings=4096,
            attention_factor=1.4158883083359672)
B, S, H, D = 2, 256, 3, 128
EPS = 1e-6
# name -> (theta, rotated lanes, scaling, wrap)
FORMS = {"whole": (10000.0, D, None, 0),
         "wrapped": (1000000.0, D, None, S // 2),
         "partial_yarn": (500000.0, D // 2, YARN, 0)}


@pytest.fixture(autouse=True)
def _interpreted(monkeypatch):
    """The kernels run interpreted in these tests, and only in these: set
    as the module is imported, the variable would reach every test of a
    worker that collected this file."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")


# the same at heads of 64 (PR 47): name -> (theta, rotated lanes,
# scaling, wrap, head_dim); four heads, two 128-lane columns
H64, D64 = 4, 64
FORMS_64 = {"whole_64": (1000000.0, D64, None, 0, D64),
            "wrapped_64": (10000.0, D64, None, S // 2, D64),
            "partial_64": (500000.0, D64 // 2, None, 0, D64),
            "partial_yarn_64": (500000.0, D64 // 2, YARN, 0, D64)}


def form_of(form):
    """(theta, rotated lanes, scaling, wrap, head_dim) of a form's name."""
    return FORMS_64[form] if form in FORMS_64 else (*FORMS[form], D)


def operand(seed, dtype=jnp.float32, heads=H, d=D):
    return jnp.asarray(np.random.RandomState(seed).randn(B, S, heads * d),
                       dtype)


def by_view(x, form, scale=None):
    """What the op ran until PR 42, and runs wherever the pass does not
    go: the norm and the rotation over the [B, S, H, D] view."""
    theta, r, scaling, wrap, d = form_of(form)
    xh = x.reshape(B, S, -1, d)
    if scale is not None:
        xh = xh * jax.lax.rsqrt(jnp.mean(xh * xh, axis=-1, keepdims=True)
                                + EPS) * scale
    if r == d and not scaling:
        y = rotary_embedding(xh, theta=theta, seq_axis=1, wrap=wrap)
    else:
        inv_freq, factor = rotary_frequencies(r, theta, scaling)
        y = rotary_partial(xh, inv_freq, rotary_dim=r,
                           attention_factor=factor)
    return y.reshape(x.shape)


def by_lanes(x, form, dtype=jnp.float32, scale=None):
    theta, r, scaling, wrap, d = form_of(form)
    inv_freq, factor = rotary_frequencies(r, theta, scaling)
    cos, sin = rotary_tables(S, d, inv_freq, factor, wrap=wrap)
    sin = jnp.where(jnp.arange(d) < r // 2, -sin, sin)
    return pk.rotary_lanes(x, cos, sin, r // 2, dtype,
                           norm=None if scale is None else (scale, EPS))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "rounded_to_bf16"])
@pytest.mark.parametrize("form", [*FORMS, *FORMS_64])
def test_the_pass_is_the_view_forms_rotation_bit_for_bit(form, dtype):
    x = operand(0, heads=H64 if form in FORMS_64 else H, d=form_of(form)[4])
    got = jax.jit(lambda x: by_lanes(x, form, dtype))(x)
    want = jax.jit(lambda x: by_view(x, form).astype(dtype))(x)
    assert got.dtype == dtype and got.shape == x.shape
    got32, want32 = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if form in FORMS_64:
        # over a 64-wide minor axis the CPU compiles the view form's
        # x cos + rotated sin with another contraction into multiply-adds
        # than the kernel's (the module's docstring): a float32 unit in
        # one lane of seven, which a rounding to bfloat16 carries into
        # fewer than one lane in a thousand
        unit = 2.0 ** -23 if dtype == jnp.float32 else 2.0 ** -8
        np.testing.assert_allclose(got32, want32, rtol=0,
                                   atol=unit * np.abs(want32).max())
        assert dtype == jnp.float32 or np.mean(got32 != want32) < 1e-3
    else:
        np.testing.assert_array_equal(got32, want32)
    # (and it rotated something: a row past the first is not its input)
    assert not np.array_equal(np.asarray(got[:, 1:], np.float32),
                              np.asarray(x[:, 1:].astype(dtype), np.float32))


@pytest.mark.parametrize("form", [*FORMS, *FORMS_64])
def test_its_own_backward_is_autodiffs_of_the_view_form(form):
    """dx = g cos - partner(g) sin against autodiff's transpose of the
    slices and the concatenation (or the signed permutation's product),
    to a float32 unit; the cotangent arrives in bfloat16 and leaves in
    the operand's float32, as autodiff has it."""
    shape = dict(heads=H64 if form in FORMS_64 else H, d=form_of(form)[4])
    x, g = operand(1, **shape), operand(2, **shape)

    def through(rotate):
        return jax.jit(jax.grad(lambda x: jnp.sum(
            rotate(x).astype(jnp.float32) * g)))(x)

    got = through(lambda x: by_lanes(x, form, jnp.bfloat16))
    want = through(lambda x: by_view(x, form).astype(jnp.bfloat16))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=4 * 2.0 ** -23 * float(
        jnp.abs(want).max()))


@pytest.mark.parametrize("form", ["whole", "wrapped", "whole_64",
                                  "wrapped_64", "partial_64"])
def test_with_the_norm_forward_and_both_gradients(form):
    """The per-head RMS norm rides in the pass: forward, dx and d scale
    against `_heads_normed`'s arithmetic and the view form's rotation, to
    a few float32 units (the kernel's sums over a head's lanes and over
    the rows run in another order). At heads of 64 the mean is over each
    64-lane half of a column and d scale the sum of both halves'."""
    d = form_of(form)[4]
    shape = dict(heads=H64 if form in FORMS_64 else H, d=d)
    x, g = operand(3, **shape), operand(4, **shape)
    scale = jnp.asarray(1 + 0.2 * np.random.RandomState(5).randn(d),
                        jnp.float32)

    def both(rotate):
        return jax.jit(jax.value_and_grad(lambda x, scale: jnp.sum(
            rotate(x, scale) * g), argnums=(0, 1)))(x, scale)

    got_y = jax.jit(lambda x, s: by_lanes(x, form, scale=s))(x, scale)
    want_y = jax.jit(lambda x, s: by_view(x, form, scale=s))(x, scale)
    unit = 2.0 ** -23
    np.testing.assert_allclose(got_y, want_y, rtol=0, atol=8 * unit * float(
        jnp.abs(want_y).max()))
    (_, (got_dx, got_ds)) = both(lambda x, s: by_lanes(x, form, scale=s))
    (_, (want_dx, want_ds)) = both(lambda x, s: by_view(x, form, scale=s))
    np.testing.assert_allclose(got_dx, want_dx, rtol=0, atol=16 * unit * float(
        jnp.abs(want_dx).max()))
    # B * S * H = 1,536 terms a lane (2,048 at four heads of 64)
    np.testing.assert_allclose(got_ds, want_ds, rtol=0, atol=64 * unit * float(
        jnp.abs(want_ds).max()))
    assert got_ds.shape == (d,) and got_ds.dtype == scale.dtype


@pytest.mark.parametrize("rows,heads", [(128, 1), (256, 3), (128, 3)])
def test_any_block_of_rows_and_heads_gives_the_same(rows, heads):
    x = operand(6)
    theta, r, _, _ = FORMS["whole"]
    cos, sin = rotary_tables(S, D, rotary_frequencies(r, theta)[0])
    want = pk.rotary_lanes(x, cos, sin, r // 2, jnp.float32)
    got = pk._rotary_lanes_call((x,), cos, sin, None, r // 2, None,
                                jnp.float32, True, False, (rows, heads))
    np.testing.assert_array_equal(got, want)


def test_blocks_follow_from_the_shape():
    assert pk._rotary_block(8192, 64) == (512, 4)
    assert pk._rotary_block(16384, 7) == (512, 1)
    assert pk._rotary_block(8192, 48) == (512, 4)
    assert pk._rotary_block(384, 6) == (128, 3)
    assert pk.rotary_lanes_shape_legal(8192, 128)
    assert not pk.rotary_lanes_shape_legal(8200, 128)
    # heads of 64 two a column (PR 47): an even number of them
    assert pk.rotary_lanes_shape_legal(16384, 64, 32)
    assert pk.rotary_lanes_shape_legal(16384, 64, 8)
    assert not pk.rotary_lanes_shape_legal(16384, 64, 3)
    assert not pk.rotary_lanes_shape_legal(16384, 32, 4)
    assert not pk.rotary_lanes_shape_legal(16384, 256, 4)


# ---------------------------------------------------------------------------
# the op: which path a forward takes follows from its shapes alone


def make_op(seq, hidden, **props):
    layer = Layer(OperatorType.MULTIHEAD_ATTENTION, "op", [])
    layer.properties.update(dict(embed_dim=hidden, bias=False, rope=True,
                                 **props))
    return OpRegistry.create(layer, [(B, seq, hidden)] * 3)


def step_of(op, x, dtype, lanes=None):
    """(params, x) -> the loss and gradients of one training forward +
    backward of the op; ``lanes`` False steers it to the view form
    whatever its shapes."""
    if lanes is False:
        route = op.route
        op.route = lambda *a, **k: dataclasses.replace(
            route(*a, **k), rotary_in_lanes=False)
    ctx = OpContext(training=True, compute_dtype=dtype)
    g = jnp.asarray(np.random.RandomState(9).randn(*x.shape), jnp.float32)
    return jax.value_and_grad(lambda p, x: jnp.sum(
        op.forward(p, [x], ctx)[0].astype(jnp.float32) * g), argnums=(0, 1))


def step(op, params, x, dtype, lanes=None):
    out = jax.jit(step_of(op, x, dtype, lanes))(params, x)
    return out, op._route.rotary_in_lanes


OPS = {
    "gqa_window_whole": dict(num_heads=4, num_kv_heads=2, head_dim=D,
                             causal=True, window=128),
    "gated_partial_yarn": dict(num_heads=6, num_kv_heads=2, head_dim=D,
                               causal=True, gate=True, rope_theta=500000.0,
                               partial_rotary_factor=0.5, rope_scaling=YARN),
    "normed_wrapped_block_diffusion": dict(
        num_heads=4, num_kv_heads=1, head_dim=D, qk_norm=True,
        rope_wrap=S // 2, block_diffusion=(S // 2, 4)),
    "plain_heads_no_repeat": dict(num_heads=2, head_dim=D, causal=True),
    # heads of 64 (PR 47): lfm2's op in small, and one without the norm
    "gqa_normed_heads_of_64": dict(num_heads=8, num_kv_heads=2, head_dim=64,
                                   causal=True, qk_norm=True,
                                   rope_theta=1000000.0),
    "gqa_partial_heads_of_64": dict(num_heads=4, num_kv_heads=2, head_dim=64,
                                    causal=True, partial_rotary_factor=0.5),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kind", OPS)
def test_a_training_step_with_and_without_the_pass(kind, dtype):
    """One mathematics: the loss and every gradient of an op's step with
    the pass against the same op on the view form, within the flash
    tests' tolerance (`tests/test_flash_kernels.py`: 2e-2 of the largest
    entry in bfloat16, 2e-4 in float32)."""
    hidden = 64
    x = operand(7, heads=1)[..., :hidden].astype(dtype)
    ops = [make_op(S, hidden, **OPS[kind]) for _ in range(2)]
    params = ops[0].init_params(jax.random.PRNGKey(0))
    if "q_norm" in params:
        params["q_norm"] = params["q_norm"] + 0.1 * operand(8)[
            0, 0, :params["q_norm"].shape[0]]
    ((got, got_grads), engaged) = step(ops[0], params, x, dtype)
    ((want, want_grads), fell_back) = step(ops[1], params, x, dtype, False)
    assert engaged and not fell_back
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(got, want, rtol=tol)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0, atol=tol * np.abs(b).max())


@pytest.mark.parametrize("kind,seq,props", [
    ("head_dim_32", S, dict(num_heads=4, num_kv_heads=2, head_dim=32,
                            causal=True)),
    ("three_heads_of_64", S, dict(num_heads=3, head_dim=64, causal=True)),
    ("odd_length", S + 8, dict(num_heads=2, head_dim=D, causal=True)),
    ("pallas_off", S, dict(num_heads=2, head_dim=D, causal=True)),
], ids=["head_dim_32", "three_heads_of_64", "odd_length", "pallas_off"])
def test_shapes_the_pass_does_not_take_run_the_view_form(kind, seq, props,
                                                         monkeypatch):
    """head_dim 32, three heads of 64 (one and a half columns), a length
    that is no multiple of 128, Pallas off: the forward takes
    `rotary_embedding` over the view, flags nothing, and gives the
    numbers that path gives with the pass forced off."""
    if kind == "pallas_off":
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    hidden = 64
    x = jnp.asarray(np.random.RandomState(11).randn(B, seq, hidden),
                    jnp.float32)
    ops = [make_op(seq, hidden, **props) for _ in range(2)]
    params = ops[0].init_params(jax.random.PRNGKey(1))
    ((got, got_grads), engaged) = step(ops[0], params, x, jnp.float32)
    assert not engaged and np.isfinite(got)
    # ONE program, character for character, and it has run
    assert str(jax.make_jaxpr(step_of(ops[1], x, jnp.float32, False))(
        params, x)) == str(jax.make_jaxpr(step_of(ops[0], x, jnp.float32))(
            params, x))


def test_one_device_only():
    """A bare kernel call has no partitioning: on a mesh of several
    devices the forward keeps the view form."""
    from flexflow_tpu.machine import make_mesh
    op = make_op(S, 64, num_heads=2, head_dim=D, causal=True)
    mesh = make_mesh(2, {"data": 2})
    assert not op.route(dict(zip(mesh.axis_names, mesh.devices.shape)),
                        False).rotary_in_lanes
    assert op.route({}, False).rotary_in_lanes


def test_decode_forward_rotates_at_its_offset_as_before():
    """`decode_forward` holds [B, H, S, D] at positions that start at an
    offset: it calls `rotary_embedding` itself, and prefill + decode
    still reproduce the full forward's last row, which takes the pass."""
    hidden, t = 64, S
    op = make_op(t, hidden, num_heads=2, head_dim=D, causal=True)
    params = op.init_params(jax.random.PRNGKey(2))
    x = jnp.asarray(np.random.RandomState(12).randn(1, t, hidden),
                    jnp.float32)
    ctx = OpContext(compute_dtype=jnp.float32)
    full = op.forward(params, [x], ctx)[0]
    assert op._route.rotary_in_lanes
    cache = jnp.zeros((1, 2, t, D), jnp.float32)
    y, kc, vc = op.decode_forward(params, [x[:, :t - 1]], ctx, cache, cache,
                                  0)
    last, _, _ = op.decode_forward(params, [x[:, t - 1:]], ctx, kc, vc,
                                   t - 1)
    np.testing.assert_allclose(last[:, 0], full[:, -1], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(y, full[:, :-1], rtol=2e-4, atol=2e-5)
