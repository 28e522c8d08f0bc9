"""The flash kernels on the operands the caller stored (PR 28).

`ops/attention.py` hands the kernels q, k, v in the compute dtype. With
bf16 stored, every product's MXU operands are bf16 and its accumulator
float32; `P` and `dS`, the two float32 intermediates that are an operand
of a later product, are rounded to bf16 for that product only, as the
einsum path rounds `probs`. Softmax statistics, `exp`, scale and mask
stay float32. With float32 stored, every product stays a float32
product (tests/test_ring_flash_attention.py keeps those tolerances).

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


def _qkv(s, d, dtype, seed=0, bh=2):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(bh, s, d).astype(np.float32)).astype(dtype)
                 for _ in range(4))


def _grads(fn, q, k, v, do):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32)
                       * do.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


# S <= MAX_BWD_SEQ runs flash_fwd_whole + flash_bwd, S > MAX_BWD_SEQ runs
# flash_fwd + flash_bwd_blocked; head_dim 64 is bert_ae's, 128 the
# nemotron cell's (which is causal).
SEQS = (256, 2 * pk.MAX_BWD_SEQ)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("seq", SEQS)
def test_bf16_operands_match_float32_attention(seq, head_dim, causal):
    """Output and dQ/dK/dV of the kernels on bf16 q/k/v/dO against the
    float32 einsum attention on the same bf16 values.

    Tolerances, from bf16's rounding (U = 2^-9 relative):
    - output, element-wise: `P` rounded moves a row's sum of p_ij v_j by
      at most U * sum_j p_ij |v_j| <= U * max|v| after the division by
      l = sum_j p_ij, and the bf16 output is one more rounding of
      |o| <= max|v|: 2 U max|v|;
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
    less, a wrong mask or a missing scale does not."""
    q, k, v, do = _qkv(seq, head_dim, jnp.bfloat16, seed=seq + head_dim)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]

    got = pk._flash(q, k, v, causal, True)
    want = pk._xla_attention(*f32[:3], causal)
    assert got.dtype == jnp.bfloat16
    vmax = float(jnp.max(jnp.abs(f32[2])))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=0,
                               atol=2 * U * vmax)

    g = _grads(lambda q, k, v: pk._flash(q, k, v, causal, True),
               q, k, v, do)
    gr = _grads(lambda q, k, v: pk._xla_attention(q, k, v, causal), *f32)
    for name, a, b in zip(("dq", "dk", "dv"), g, gr):
        assert a.dtype == jnp.bfloat16, name
        assert _rel_rms(a, b) < 4 * U, (name, _rel_rms(a, b) / U)
        worst = float(np.max(np.abs(np.asarray(a, np.float32)
                                    - np.asarray(b))))
        assert worst < 8 * U * float(jnp.max(jnp.abs(b))), name


def test_tiles_divide_what_they_tile():
    """Heads a step and K/V rows a block, for every shape the gate
    admits: a block that did not divide would drop the rest in silence."""
    for s in range(pk.BLK_Q, pk.MAX_FLASH_SEQ + 1, pk.BLK_Q):
        blk = pk._kv_block(s)
        assert blk in (128, 256, 512) and s % blk == 0, s
        assert blk * s <= 1 << 22 or blk == pk.BLK_Q, s
    for s in range(pk.BLK_Q, pk.MAX_BWD_SEQ + 1, pk.BLK_Q):
        for bh in (1, 2, 6, 12, 16, 512):
            heads = pk._heads_per_step(bh, s)
            assert heads in (1, 2, 4, 8) and bh % heads == 0, (bh, s)
    assert pk._heads_per_step(512, 512) == 8
    assert pk._heads_per_step(512, pk.MAX_BWD_SEQ) == 2
    assert pk._kv_block(8192) == 512 and pk._kv_block(1152) == 128


def test_blocks_that_do_not_divide_by_the_widest_block():
    """S = 9 x 128 past MAX_BWD_SEQ (Q- and K-blocked kernels at 128
    rows) and 6 heads at S = 640 (2 a step): same limits as above."""
    for bh, seq, causal in ((1, pk.MAX_BWD_SEQ + 128, True), (6, 640, False)):
        q, k, v, do = _qkv(seq, 64, jnp.bfloat16, seed=seq, bh=bh)
        f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
        g = _grads(lambda q, k, v: pk._flash(q, k, v, causal, True),
                   q, k, v, do)
        gr = _grads(lambda q, k, v: pk._xla_attention(q, k, v, causal),
                    *f32)
        for name, a, b in zip(("dq", "dk", "dv"), g, gr):
            assert _rel_rms(a, b) < 4 * U, (seq, name, _rel_rms(a, b) / U)


def test_tolerance_refuses_a_float8_operand():
    """The limits above are not so wide that a lower precision passes:
    `P` rounded to float8_e4m3 (2^-4 relative) fails the output's."""
    q, k, v, _ = _qkv(256, 64, jnp.bfloat16, seed=3)
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
    return _grads(lambda q, k, v: pk._flash(q, k, v, True, True),
                  q, k, v, do)


def _flash_lse_grads(q, k, v, do):
    def loss(q, k, v):
        o, lse = pk.flash_attention_lse(q, k, v, True, True)
        return jnp.sum(o * do.astype(jnp.float32)) + jnp.sum(lse)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("grads", [_flash_grads, _flash_lse_grads])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("seq", SEQS)
def test_every_product_takes_the_stored_dtype(seq, dtype, grads):
    """Structure, not values: with bf16 stored no dot_general in any of
    the three kernels has a float32 operand, with float32 stored every
    one keeps float32 operands, and every accumulator is float32. Two
    products a head in the forward, five in either backward; the
    whole-tile kernels hold them for as many heads as they unroll."""
    dots = _kernel_dots(grads, *_qkv(seq, 64, dtype, bh=4))
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
