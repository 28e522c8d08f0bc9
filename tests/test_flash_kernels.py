"""The flash kernels on the operands the caller stored (PR 28), in the
form the projections leave them (PR 30).

`ops/attention.py` hands the kernels q, k, v in the compute dtype as
[B, S, H*D], the heads side by side along the lanes. With bf16 stored,
every product's MXU operands are bf16 and its accumulator float32; `P`
and `dS`, the two float32 intermediates that are an operand of a later
product, are rounded to bf16 for that product only, as the einsum path
rounds `probs`. Softmax statistics, `exp`, scale and mask stay float32.
With float32 stored, every product stays a float32 product
(tests/test_ring_flash_attention.py keeps those tolerances).

The kernels run in interpret mode on the CPU: values and structure, no
times.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import pallas_kernels as pk

# bf16 keeps 8 significant bits: rounding to nearest moves a value by at
# most 2^-9 of itself.
U = 2.0 ** -9


def _qkv(s, d, dtype, seed=0, b=1, h=2):
    """q, k, v, dO as [B, S, H*D]."""
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(b, s, h * d).astype(np.float32)
                             ).astype(dtype) for _ in range(4))


def _reference(q, k, v, h, causal):
    """The float32 einsum attention, a head at a time, on [B, S, H*D]:
    (o [B, S, H*D], lse [B, H, S])."""
    b, s, hd = q.shape
    fold = lambda x: pk.split_heads(x, h).reshape(b * h, s, hd // h)
    o, lse = pk._xla_attention_lse(fold(q), fold(k), fold(v), causal)
    return (pk.merge_heads(o.reshape(b, h, s, hd // h)),
            lse.reshape(b, h, s))


def _grads(fn, q, k, v, do):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32)
                       * do.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def _assert_grads_close(g, gr, what=""):
    """The gradient limits of the docstring below."""
    for name, a, b in zip(("dq", "dk", "dv"), g, gr):
        assert _rel_rms(a, b) < 4 * U, (what, name, _rel_rms(a, b) / U)
        worst = float(np.max(np.abs(np.asarray(a, np.float32)
                                    - np.asarray(b))))
        assert worst < 8 * U * float(jnp.max(jnp.abs(b))), (what, name)


# S <= MAX_BWD_SEQ runs flash_fwd_whole + flash_bwd, S > MAX_BWD_SEQ runs
# flash_fwd + flash_bwd_blocked; head_dim 64 is bert_ae's (two heads a
# column block of 128 lanes), 128 the nemotron cell's (one; causal).
SEQS = (512, 2 * pk.MAX_BWD_SEQ)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("seq", SEQS)
def test_bf16_operands_match_float32_attention(seq, head_dim, causal):
    """Output, logsumexp and dQ/dK/dV of the kernels on bf16 q/k/v/dO
    against the float32 einsum attention on the same bf16 values, two
    batch rows of four heads: two column blocks of two heads at
    head_dim 64, four of one at 128.

    Tolerances, from bf16's rounding (U = 2^-9 relative):
    - output, element-wise: `P` rounded moves a row's sum of p_ij v_j by
      at most U * sum_j p_ij |v_j| <= U * max|v| after the division by
      l = sum_j p_ij, and the bf16 output is one more rounding of
      |o| <= max|v|: 2 U max|v|;
    - logsumexp: float32 statistics of a float32-accumulated product of
      the same bf16 values, so float32's own error;
    - dV = P^T dO and dK, dQ from dS: an operand rounded by at most U an
      element and a bf16 result rounded by at most U. Rows of dS sum to
      zero, so dQ and dK are sums of terms that cancel and a relative
      bound per element does not exist; over the whole tensor the
      roundings are independent and the RMS of the error stays under
      4 U of the RMS of the gradient (measured here: 0.6-1.2 U for dV,
      1.0-2.4 U for dQ and dK), with no element further off than 8 U of
      the largest one.
    A float32-operand kernel passes the same test (its only rounding is
    the bf16 result); a product with an operand rounded to 5 bits or
    less, a wrong mask, a missing scale or a head read from its
    neighbour's lanes does not."""
    h = 4
    q, k, v, do = _qkv(seq, head_dim, jnp.bfloat16, seed=seq + head_dim,
                       b=2, h=h)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]

    got, lse = pk._flash_fwd(q, k, v, h, causal, True)
    want, want_lse = _reference(*f32[:3], h, causal)
    assert got.dtype == jnp.bfloat16 and got.shape == q.shape
    vmax = float(jnp.max(jnp.abs(f32[2])))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=0,
                               atol=2 * U * vmax)
    assert lse.shape == (2, h, 1, seq)
    np.testing.assert_allclose(np.asarray(lse[:, :, 0]),
                               np.asarray(want_lse), rtol=1e-5, atol=1e-5)

    g = _grads(lambda q, k, v: pk._flash(q, k, v, h, causal, True),
               q, k, v, do)
    gr = _grads(lambda q, k, v: _reference(q, k, v, h, causal)[0], *f32)
    assert all(a.dtype == jnp.bfloat16 for a in g)
    _assert_grads_close(g, gr)


# (heads, head_dim) -> heads a column block, or None where the gate
# refuses: the heads of a block have to divide the heads and fill 128
# lanes, unless one block is the whole row
PAIRINGS = {
    (16, 64): 2, (4, 128): 1, (2, 64): 2, (16, 8): 16, (32, 16): 8,
    # the whole row is one block: a single head, or H*D <= 128
    (1, 64): 1, (4, 8): 4, (12, 8): 12, (1, 96): 1,
    # an odd head out, lanes that do not fill, a head_dim off the
    # sublanes or past the VMEM budget
    (3, 64): None, (4, 96): None, (24, 8): None, (6, 48): None,
    (2, 60): None, (2, 256): None,
}


@pytest.mark.parametrize("heads,head_dim", list(PAIRINGS))
def test_heads_tile_the_lanes_or_the_gate_refuses(heads, head_dim,
                                                  monkeypatch):
    """The rule `flash_shape_legal` states, and that what it admits runs:
    forward and gradients at S = 128 against the float32 reference, in
    float32 so that a head mixed with its neighbour cannot hide in
    bf16's rounding."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    per_block = PAIRINGS[(heads, head_dim)]
    legal = per_block is not None
    assert pk.flash_shape_legal(128, head_dim, heads) == legal
    assert pk.flash_attention_available(128, head_dim, heads) == legal
    if not legal:
        return
    assert pk._heads_per_block(heads, head_dim) == per_block
    q, k, v, do = _qkv(128, head_dim, jnp.float32, seed=heads, b=2, h=heads)
    got = pk.flash_attention(q, k, v, heads, causal=True)
    want, _ = _reference(q, k, v, heads, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    g = _grads(lambda q, k, v: pk.flash_attention(q, k, v, heads,
                                                  causal=True), q, k, v, do)
    gr = _grads(lambda q, k, v: _reference(q, k, v, heads, True)[0],
                q, k, v, do)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("seq", SEQS)
def test_grouped_query_heads_repeated_into_the_lanes(seq):
    """GQA as `ops/attention.py` feeds it: one K/V head of 128 repeated
    under four Q heads (the nemotron cell's layer), causal. The gradient
    of the un-repeated K/V is the sum over its group, so a column block
    that wrote another head's dK would show."""
    h, hk, d = 4, 1, 128
    q, _, _, do = _qkv(seq, d, jnp.bfloat16, seed=seq, h=h)
    k, v, _, _ = _qkv(seq, d, jnp.bfloat16, seed=seq + 1, h=hk)
    rep = lambda x: jnp.repeat(x.reshape(1, seq, hk, d), h // hk, axis=2
                               ).reshape(1, seq, h * d)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    g = _grads(lambda q, k, v: pk._flash(q, rep(k), rep(v), h, True, True),
               q, k, v, do)
    gr = _grads(lambda q, k, v: _reference(q, rep(k), rep(v), h, True)[0],
                *f32)
    assert g[1].shape == (1, seq, hk * d)
    _assert_grads_close(g, gr)


def test_tiles_divide_what_they_tile():
    """Batch rows a step and K/V rows a block, for every shape the gate
    admits: a block that did not divide would drop the rest in silence."""
    for s in range(pk.BLK_Q, pk.MAX_FLASH_SEQ + 1, pk.BLK_Q):
        blk = pk._seq_block(s)
        assert blk in (128, 256, 512, 1024) and s % blk == 0, s
        assert s % pk._q_block(s) == 0 and pk._q_block(s) in (128, 256), s
    for s in range(pk.BLK_Q, pk.MAX_BWD_SEQ + 1, pk.BLK_Q):
        for batch in (1, 2, 6, 12, 16, 32):
            for per_block in (1, 2, 4, 12, 16):
                rows = pk._rows_per_step(batch, per_block, s)
                assert rows in (1, 2, 4, 8) and batch % rows == 0, (batch, s)
                assert rows * per_block <= 8 or rows == 1
    # bert_ae's step: 8 heads a grid step, as before the lanes held two
    assert pk._rows_per_step(32, 2, 512) == 4
    assert pk._rows_per_step(32, 2, pk.MAX_BWD_SEQ) == 1
    assert pk._rows_per_step(32, 1, pk.MAX_BWD_SEQ) == 2
    assert pk._seq_block(8192) == 1024 and pk._seq_block(1152) == 128
    assert pk._seq_block(16384) == 1024 and pk._seq_block(1536) == 512
    assert pk._q_block(1152) == 128 and pk._q_block(16384) == 256


def test_blocks_that_do_not_divide_by_the_widest_block():
    """S = 9 x 128 past MAX_BWD_SEQ (Q- and K-blocked kernels at 128
    rows, one head as the whole row) and three batch rows of two heads
    at S = 640 (one row a step): same limits as above."""
    for b, h, seq, causal in ((1, 1, pk.MAX_BWD_SEQ + 128, True),
                              (3, 2, 640, False)):
        q, k, v, do = _qkv(seq, 64, jnp.bfloat16, seed=seq, b=b, h=h)
        f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
        g = _grads(lambda q, k, v: pk._flash(q, k, v, h, causal, True),
                   q, k, v, do)
        gr = _grads(lambda q, k, v: _reference(q, k, v, h, causal)[0],
                    *f32)
        _assert_grads_close(g, gr, seq)


def test_tolerance_refuses_a_float8_operand():
    """The limits above are not so wide that a lower precision passes:
    `P` rounded to float8_e4m3 (2^-4 relative) fails the output's."""
    q, k, v, _ = _qkv(256, 64, jnp.bfloat16, seed=3, b=2, h=1)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) / 8.0
    p = jax.nn.softmax(s, axis=-1)
    p8 = p.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    coarse = jnp.einsum("bqk,bkd->bqd", p8, vf) / jnp.sum(
        p, axis=-1, keepdims=True)
    want = pk._xla_attention(qf, kf, vf, False)
    vmax = float(jnp.max(jnp.abs(vf)))
    assert float(jnp.max(jnp.abs(coarse - want))) > 2 * U * vmax


def _kernel_dots(fn, *args):
    """(kernel name, operand dtypes, preferred type, result dtype) of
    every dot_general inside every pallas_call that `fn` traces to."""
    found = []

    def sub_jaxprs(params):
        for val in params.values():
            for x in (val if isinstance(val, (tuple, list)) else (val,)):
                if hasattr(x, "eqns"):
                    yield x
                elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                    yield x.jaxpr

    def walk(jaxpr, kernel):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general" and kernel:
                found.append((kernel,
                              tuple(v.aval.dtype for v in eqn.invars),
                              eqn.params["preferred_element_type"],
                              eqn.outvars[0].aval.dtype))
            name = kernel
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
            for sub in sub_jaxprs(eqn.params):
                walk(sub, name)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return found


def _flash_grads(q, k, v, do):
    return _grads(lambda q, k, v: pk._flash(q, k, v, 4, True, True),
                  q, k, v, do)


def _flash_lse_grads(q, k, v, do):
    def loss(q, k, v):
        o, lse = pk.flash_attention_lse(q, k, v, 4, True, True)
        return jnp.sum(o * do.astype(jnp.float32)) + jnp.sum(lse)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("grads", [_flash_grads, _flash_lse_grads])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("seq", SEQS)
def test_every_product_takes_the_stored_dtype(seq, dtype, grads):
    """Structure, not values: with bf16 stored no dot_general in any of
    the three kernels has a float32 operand, with float32 stored every
    one keeps float32 operands, and every accumulator is float32. Two
    products a head in the forward, five in either backward; a kernel
    holds them for the heads of a column block, the whole-tile ones for
    as many batch rows as they unroll besides."""
    dots = _kernel_dots(grads, *_qkv(seq, 64, dtype, h=4))
    forward, backward = (("flash_fwd_whole", "flash_bwd")
                         if seq <= pk.MAX_BWD_SEQ
                         else ("flash_fwd", "flash_bwd_blocked"))
    names = [name for name, *_ in dots]
    assert sorted(set(names)) == sorted(
        pk.KERNEL_NAME_PREFIX + n for n in (forward, backward))
    assert names.count(pk.KERNEL_NAME_PREFIX + forward) % 2 == 0
    assert names.count(pk.KERNEL_NAME_PREFIX + backward) % 5 == 0
    for name, operands, preferred, result in dots:
        assert operands == (dtype, dtype), (name, operands)
        assert preferred == jnp.float32 and result == jnp.float32, name
