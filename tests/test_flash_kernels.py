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

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import pallas_kernels as pk
from one_program import output_and_gradients

# bf16 keeps 8 significant bits: rounding to nearest moves a value by at
# most 2^-9 of itself.
U = 2.0 ** -9


# What a case costs here is the programs it compiles, not its operands'
# size (ROADMAP D10): operands and expectations are numpy arrays (numpy's
# cast to ml_dtypes' bfloat16 rounds as XLA's does), and what has to be
# jax runs under one `jax.jit` a call, so that a case compiles the
# kernels it is about and little else.
def _qkv(s, d, dtype, seed=0, b=1, h=2):
    """q, k, v, dO as [B, S, H*D], numpy arrays."""
    rs = np.random.RandomState(seed)
    return tuple(rs.randn(b, s, h * d).astype(np.float32).astype(dtype)
                 for _ in range(4))


def _f32(*xs):
    return [np.asarray(x, np.float32) for x in xs]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _reference(q, k, v, h, causal):
    """The float32 einsum attention, a head at a time, on [B, S, H*D]:
    (o [B, S, H*D], lse [B, H, S])."""
    b, s, hd = q.shape
    fold = lambda x: pk.split_heads(x, h).reshape(b * h, s, hd // h)
    o, lse = pk._xla_attention_lse(fold(q), fold(k), fold(v), causal)
    return (pk.merge_heads(o.reshape(b, h, s, hd // h)),
            lse.reshape(b, h, s))


def _grads(fn, q, k, v, do, output=False):
    """dQ, dK, dV of sum(fn(q, k, v) * dO), one program; with ``output``
    (fn's output, the gradients) of that same program."""
    o, g = output_and_gradients(fn, do.astype(jnp.float32), q, k, v)
    return (o, g) if output else g


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
        assert worst < 8 * U * float(np.max(np.abs(b))), (what, name)


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
    f32 = _f32(q, k, v, do)

    got, lse = jax.jit(lambda q, k, v: pk._flash_fwd(
        q, k, v, h, causal, True))(q, k, v)
    want, want_lse = _reference(*f32[:3], h, causal)
    assert got.dtype == jnp.bfloat16 and got.shape == q.shape
    vmax = float(np.max(np.abs(f32[2])))
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
    # a head of two lane blocks (PR 58): a column block of its own, the
    # kernels that take one tile a grid step
    (2, 256): 1,
    # an odd head out, lanes that do not fill, a head_dim off the
    # sublanes, past one lane block and not two exactly
    (3, 64): None, (4, 96): None, (24, 8): None, (6, 48): None,
    (2, 60): None, (2, 192): None, (2, 384): None,
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
    got, g = _grads(lambda q, k, v: pk.flash_attention(
        q, k, v, heads, causal=True), q, k, v, do, output=True)
    want, _ = _reference(q, k, v, heads, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
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
    f32 = _f32(q, k, v, do)
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
        f32 = _f32(q, k, v, do)
        g = _grads(lambda q, k, v: pk._flash(q, k, v, h, causal, True),
                   q, k, v, do)
        gr = _grads(lambda q, k, v: _reference(q, k, v, h, causal)[0],
                    *f32)
        _assert_grads_close(g, gr, seq)


def test_tolerance_refuses_a_float8_operand():
    """The limits above are not so wide that a lower precision passes:
    `P` rounded to float8_e4m3 (2^-4 relative) fails the output's."""
    q, k, v, _ = _qkv(256, 64, jnp.bfloat16, seed=3, b=2, h=1)
    qf, kf, vf = _f32(q, k, v)

    @jax.jit
    def off(qf, kf, vf):
        s = jnp.einsum("bqd,bkd->bqk", qf, kf) / 8.0
        p = jax.nn.softmax(s, axis=-1)
        p8 = p.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        coarse = jnp.einsum("bqk,bkd->bqd", p8, vf) / jnp.sum(
            p, axis=-1, keepdims=True)
        want = pk._xla_attention(qf, kf, vf, False)
        return jnp.max(jnp.abs(coarse - want))

    assert float(off(qf, kf, vf)) > 2 * U * float(np.max(np.abs(vf)))


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
        return jnp.sum(o * jnp.asarray(do, jnp.float32)) + jnp.sum(lse)
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


# the three masks the blocked kernels know: (causal, window,
# block_diffusion) at a length past MAX_BWD_SEQ
KINDS = {
    "causal": (2048, True, 0, None),
    "causal-4096": (4096, True, 0, None),
    "window-128": (2048, True, 128, None),
    "window-1000": (4096, True, 1000, None),
    "window-2500": (4096, True, 2500, None),
    "window-4096": (4096, True, 4096, None),     # hides nothing: causal
    "block-diffusion-4": (4096, False, 0, (2048, 4)),
    "block-diffusion-32": (4096, False, 0, (2048, 32)),
    # a half is one K chunk: every tile holds a hidden pair
    "block-diffusion-short": (2048, False, 0, (1024, 4)),
    # one block of B holds whole tiles: the noised diagonal has
    # interior tiles too
    "block-diffusion-768": (3072, False, 0, (1536, 768)),
    "not-causal": (2048, False, 0, None),
}


def _tiles(seq, causal, window, bd, blk_q, blk_k, reduce):
    """``reduce`` (numpy's all or any) of the mask over every
    [blk_q queries, blk_k keys] tile, from ``visible`` pair by pair."""
    i = np.arange(seq)
    seen = (np.asarray(pk.visible(i[:, None], i[None, :], window, bd))
            if causal or bd else np.ones((seq, seq), bool))
    return reduce(seen.reshape(seq // blk_q, blk_q, seq // blk_k, blk_k),
                  axis=(1, 3))


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_split_keeps_the_ranges_and_masks_the_tiles_with_a_hidden_pair(
        kind, direction):
    """`_k_split` / `_q_split` (PR 35): the sub-ranges are the ranges of
    `_k_ranges` / `_q_ranges`, chunk for chunk in the loop's order, and a
    tile is classed interior iff `visible` holds on the whole of it."""
    seq, causal, window, bd = KINDS[kind]
    window = pk.normalized_window(seq, causal, window)
    blk_k = pk._seq_block(seq, bd, window)
    if direction == "forward":
        blk, ranges = pk._q_block(seq, bd), pk._k_ranges
        split = lambda *a: pk._k_split(*a)[0]               # noqa: E731
        last_is_one = pk._k_split(0, blk, blk_k, seq, causal, window, bd)[1]
    else:
        blk, ranges, split, last_is_one = blk_k, pk._q_ranges, pk._q_split, 0
    whole = _tiles(seq, causal, window, bd, blk, blk_k, np.all)
    if direction == "backward":
        whole = whole.T
    interior = 0
    for n, x0 in enumerate(range(0, seq, blk)):
        args = (x0, blk, blk_k, seq, causal, window, bd)
        cut = split(*args)
        assert ([c for lo, hi in ranges(*args) for c in range(lo, hi)]
                == [c for lo, hi, _ in cut for c in range(lo, hi)]), x0
        if last_is_one:     # the chunk the forward runs outside a loop
            assert cut[-1][1] - cut[-1][0] == 1, (x0, cut)
        for lo, hi, edge in cut:
            assert lo <= hi and isinstance(edge, bool)
            assert all(whole[n, c] != edge for c in range(lo, hi)), (x0, cut)
            interior += 0 if edge else hi - lo
    if direction == "forward":
        visited, _ = pk.kv_blocks(seq, causal, window, bd)
        assert pk.kv_blocks_masked(seq, causal, window, bd) == (
            visited - interior)
    # every masked kind but a B that does not divide the Q block
    assert bool(last_is_one) == (direction == "forward" and (
        causal or (bd is not None and bd[1] < 256)))
    # what the case is there for: a narrow window leaves no tile whole
    # (at window 1000 the chunk is 512 since PR 41: the forward's
    # [256, 512] tiles at distance 512 are whole, the backward's
    # [512, 512] ones are not)
    assert (interior > 0) == (kind not in (
        "window-128", "block-diffusion-short")
        and (kind, direction) != ("window-1000", "backward"))


def test_masked_tiles_of_the_cells_layers():
    """By hand: of the sdar cell's 320 tiles a head, the noised diagonal
    tile of each of the 32 noised Q blocks and the last clean chunk of
    each of the 64; a full causal layer at 16,384 the 64 diagonal tiles;
    a window of 4096 those and the far edge of the 48 Q blocks whose
    window starts inside a chunk; the whole-tile kernels mask their one
    tile under any mask."""
    assert pk.kv_blocks_masked(16384, False, 0, (8192, 4)) == 96
    assert pk.kv_blocks_masked(16384, True, 0) == 64
    assert pk.kv_blocks_masked(16384, True, 4096) == 64 + 48
    assert pk.kv_blocks_masked(16384, True, 1 << 20) == 64
    assert pk.kv_blocks_masked(8192, True, 0) == 32
    assert pk.kv_blocks_masked(8192, False, 0) == 0
    assert pk.kv_blocks_masked(512, True, 128) == 1
    assert pk.kv_blocks_masked(512, False, 0, (256, 4)) == 1
    assert pk.kv_blocks_masked(512, False, 0) == 0


def _every_tile_edge(ranges):
    """The split of the kernels before PR 35: every visited tile of a
    masked op runs the masked body, each range in one loop."""
    def split(x0, blk_a, blk_b, s, causal, window, block_diffusion=None):
        cut = tuple(
            (lo, hi, causal or block_diffusion is not None)
            for lo, hi in ranges(x0, blk_a, blk_b, s, causal, window,
                                 block_diffusion))
        return (cut, False) if ranges is pk._k_ranges else cut
    return split


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("kind", ["causal", "window-2500",
                                  "block-diffusion-4",
                                  "block-diffusion-768", "not-causal"])
def test_unmasked_interior_tiles_give_the_bits_of_masking_every_tile(
        kind, head_dim, monkeypatch):
    """`jnp.where(all true, s, _MASKED)` is `s` and the chunks keep their
    order: output, logsumexp, dQ, dK and dV of the blocked kernels are
    bitwise those of the same kernels with every tile classed edge,
    which is the program before PR 35.

    Bitwise at head_dim 64, whose scale 1/8 is a power of two. At 128
    (one head a column block, the decoder cells' width) the interpreter
    itself stands in the way: XLA's CPU backend contracts the unmasked
    body's `dot * scale - m` into one fused multiply-add, which it cannot
    across the masked body's select, and with a scale that is not a power
    of two the product's rounding then differs in about one element of a
    thousand by one unit in the last place (measured: 13-920 elements of
    0.3-1M; the logsumexp by 1e-6 at most). Held there to a sixteenth of
    one bf16 rounding over the whole tensor."""
    seq, causal, window, bd = KINDS[kind]
    h = 2
    q, k, v, do = _qkv(seq, head_dim, jnp.bfloat16, seed=seq + window, h=h)

    def run():
        # a new function a call: each is traced under the split in force
        def both(q, k, v, do):
            o, lse = pk._flash_fwd(q, k, v, h, causal, True, window=window,
                                   block_diffusion=bd)
            return (o, lse) + tuple(pk._flash_bwd(
                q, k, v, o, lse, do, h, causal, True, window=window,
                block_diffusion=bd))
        return jax.jit(both)(q, k, v, do)

    if kind != "not-causal":      # the case is not vacuous
        assert 0 < pk.kv_blocks_masked(seq, causal, window, bd) < (
            pk.kv_blocks(seq, causal, window, bd)[0])
    # one block a grid step, whole tiles (PR 51): which part of a chunk
    # a sub-tile takes goes by `_k_split`'s own order of sub-ranges
    monkeypatch.setattr(pk, "super_block", lambda *a, **k: (
        (1, 1, False), 1))
    got = run()
    monkeypatch.setattr(pk, "_k_split", _every_tile_edge(pk._k_ranges))
    monkeypatch.setattr(pk, "_q_split", _every_tile_edge(pk._q_ranges))
    want = run()
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if head_dim == 64:
            assert np.array_equal(a, b), name
        else:
            assert _rel_rms(a, b) < U / 16, (name, _rel_rms(a, b) / U)


# ---------------------------------------------------------------------------
# grouped-query keys read at the KV heads (PR 43)

GROUPED_MASKS = {
    "causal": lambda s: dict(causal=True),
    "window": lambda s: dict(causal=True, window=300 if s > 1024 else 100),
    "block_diffusion": lambda s: dict(causal=False,
                                      block_diffusion=(s // 2, 4)),
}


def _repeated(x, hk, rep):
    """[B, S, Hk*D] with every head repeated ``rep`` times, as
    `ops/attention.py` `_qkv` lays it out for the cores that want whole
    heads."""
    b, s, w = x.shape
    xp = np if isinstance(x, np.ndarray) else jnp    # operands are numpy
    return xp.repeat(x.reshape(b, s, hk, w // hk), rep, axis=2
                     ).reshape(b, s, rep * w)


def _assert_grouped_is_the_repeated_form(q, k, v, do, h, hk, causal, kw):
    """o, lse and dQ of the kernels on k, v [B, S, Hk*D] equal, bit for
    bit, those on the `jnp.repeat`ed keys; dK and dV the groups' float32
    sums within a bf16 rounding of each head's partial; the public call
    the same values."""
    rep, seq, d = h // hk, q.shape[1], k.shape[-1] // hk

    def both_ways(**grouped):   # a form is ONE program: forward, backward
        def run(q, k, v, do):
            o, lse = pk._flash_fwd(q, k, v, h, causal, True, **grouped, **kw)
            direct = (o, lse) + tuple(pk._flash_bwd(
                q, k, v, o, lse, do, h, causal, True, **grouped, **kw))
            if not grouped:
                return direct
            # and through the public call, in the same program (where the
            # two are one computation the compiler makes them one): float32
            # keys in, as the op hands them, float32 group sums out
            out, vjp = jax.vjp(lambda q, k, v: pk.flash_attention(
                q, k, v, h, causal, num_kv_heads=hk, **kw),
                q, k.astype(jnp.float32), v.astype(jnp.float32))
            return direct + ((out,) + vjp(do),)
        return jax.jit(run)

    o, lse, dq, dk, dv, public = both_ways(num_kv_heads=hk)(q, k, v, do)
    want_o, want_lse, want_dq, dkr, dvr = both_ways()(
        q, _repeated(k, hk, rep), _repeated(v, hk, rep), do)
    assert np.array_equal(np.asarray(o, np.float32),
                          np.asarray(want_o, np.float32))
    assert np.array_equal(np.asarray(lse), np.asarray(want_lse))
    assert np.array_equal(np.asarray(dq, np.float32),
                          np.asarray(want_dq, np.float32))
    for name, got, parts in (("dk", dk, dkr), ("dv", dv, dvr)):
        assert got.dtype == jnp.float32 and got.shape == (1, seq, hk * d)
        parts = np.asarray(parts, np.float32).reshape(1, seq, hk, rep, d)
        want, room = parts.sum(3), np.abs(parts).sum(3)
        off = np.abs(np.asarray(got).reshape(want.shape) - want)
        assert np.all(off <= 2.02 * U * room + 1e-6 * np.abs(want).max()), (
            name, float(np.max(off / (U * room + 1e-30))))
        # and it is the group's sum, not one head's: the partials differ
        assert np.abs(want).max() > 0 and not np.allclose(want,
                                                          parts[..., 0, :])

    # the public call: the same values
    assert [a.dtype for a in public] == [jnp.bfloat16, jnp.bfloat16,
                                         jnp.float32, jnp.float32]
    for a, b in zip(public, (o, dq, dk, dv)):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


# S <= MAX_BWD_SEQ: `flash_fwd_whole` + `flash_bwd`; past it the blocked
# kernels (K blocks of 256 at 1280 positions, of 128 under the
# block-diffusion mask: five and ten a head)
@pytest.mark.parametrize("seq", [256, 1280])
@pytest.mark.parametrize("mask", list(GROUPED_MASKS))
@pytest.mark.parametrize("rep", [1, 4, 6, 7, 8])
def test_grouped_keys_match_the_repeated_ones(rep, mask, seq, monkeypatch):
    """`num_kv_heads`: K and V as [B, S, Hk*128], a K / V BlockSpec
    picking the query column block's group (`j // rep`), against the same
    call on `jnp.repeat`ed keys, two KV heads of ``rep`` query heads
    each. The kernels' bodies see the same blocks: o, lse and dQ are
    equal bit for bit. dK and dV are a group's sums, float32, added up
    in the kernels from their float32 tiles; the repeated form rounds
    every head's dK and dV to bf16 first, so the two differ by at most
    one bf16 rounding of each partial, half a unit in the last of its 8
    bits: |difference| <= 2 U * sum over the group of |partial| (and a
    float32 rounding of the sums).

    ``rep`` 1 is the call without ``num_kv_heads``: the same jaxpr,
    character for character, through `flash_attention` and through its
    gradient."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    hk, d = 2, 128
    h = hk * rep
    kw = GROUPED_MASKS[mask](seq)
    causal = kw.pop("causal")
    q, _, _, do = _qkv(seq, d, jnp.bfloat16, seed=seq + rep, h=h)
    k, v, _, _ = _qkv(seq, d, jnp.bfloat16, seed=seq + rep + 1, h=hk)

    if rep == 1:
        def grads(**more):
            def run(q, k, v):
                return jax.grad(lambda q, k, v: jnp.sum(pk._flash(
                    q, k, v, h, causal, True, kw.get("window", 0),
                    kw.get("block_diffusion"), None, **more).astype(
                        jnp.float32)), argnums=(0, 1, 2))(q, k, v)
            return str(jax.make_jaxpr(run)(q, k, v))
        assert grads(num_kv_heads=h) == grads()
        assert str(jax.make_jaxpr(lambda q, k, v: pk.flash_attention(
            q, k, v, h, causal, num_kv_heads=h, **kw))(q, k, v)) == str(
                jax.make_jaxpr(lambda q, k, v: pk.flash_attention(
                    q, k, v, h, causal, **kw))(q, k, v))
        return

    _assert_grouped_is_the_repeated_form(q, k, v, do, h, hk, causal, kw)


def _attention_op(h, hk, d, e, s, b=1, **props):
    from flexflow_tpu.ffconst import DataType, OperatorType
    from flexflow_tpu.layer import Layer
    from flexflow_tpu.ops import OpRegistry

    layer = Layer(OperatorType.MULTIHEAD_ATTENTION, "attn", [],
                  data_type=DataType.FLOAT)
    layer.properties.update(dict(embed_dim=e, num_heads=h, num_kv_heads=hk,
                                 head_dim=d, causal=True, bias=False,
                                 rope=True), **props)
    return OpRegistry.create(layer, [(b, s, e)] * 3)


# what the op does with grouped-query keys, by the one rule on static
# shapes (`MultiHeadAttention.route`): (heads, KV heads, head_dim, mesh axes,
# whether the flash kernels get the keys at the KV heads)
GROUPED_OPS = {
    "heads_of_128": (4, 2, 128, None, True),
    "one_kv_head": (7, 1, 128, None, True),
    # a column block of 128 lanes holds two heads of 64: of one group
    # where the group's size is even (PR 47) ...
    "heads_of_64": (4, 2, 64, None, True),
    "heads_of_64_32_of_8": (32, 8, 64, None, True),
    # ... of two where it is odd, and three KV heads of 64 are no whole
    # lane blocks: the repeat stays
    "heads_of_64_group_of_3_repeats": (6, 2, 64, None, False),
    "heads_of_64_odd_kv_heads_repeat": (6, 3, 64, None, False),
    "every_head_its_own_keys": (4, 4, 128, None, False),
    # a head axis of 4 over 8 query heads: 4 KV heads leave a shard one
    # whole group, 2 KV heads would be cut
    "head_axis_keeps_whole_groups": (8, 4, 128, {"data": 2, "model": 4},
                                     True),
    "head_axis_would_split_a_group_repeats": (8, 2, 128,
                                              {"data": 2, "model": 4}, False),
    # at heads of 64 a shard has to hold whole K / V lane blocks too: 8 KV
    # heads over 4 shards leave each one block, 4 KV heads half a block
    "head_axis_keeps_whole_lane_blocks_of_64": (
        16, 8, 64, {"data": 2, "model": 4}, True),
    "head_axis_would_split_a_lane_block_of_64_repeats": (
        16, 4, 64, {"data": 2, "model": 4}, False),
}


@pytest.mark.parametrize("case", list(GROUPED_OPS))
def test_the_op_hands_over_grouped_keys_where_the_shapes_allow(
        case, monkeypatch):
    """`MultiHeadAttention` under grouped-query attention: where the
    rule admits it the flash kernels get K and V as [B, S, Hk*D]
    (the route's `grouped_kv`, counted by
    `executor.flash_grouped_kv_ops`), elsewhere the repeat stays; either way the op's output and every
    gradient match the same op steered to the repeat."""
    from flexflow_tpu.machine import make_mesh
    from flexflow_tpu.ops.base import OpContext

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    h, hk, d, axes, grouped = GROUPED_OPS[case]
    b, s, e = 2, 256, 32
    mesh = make_mesh(8, axes) if axes else None
    props = dict(head_parallel="model") if axes else {}
    op = _attention_op(h, hk, d, e, s, b, **props)
    steered = _attention_op(h, hk, d, e, s, b, **props)
    route = steered.route
    steered.route = lambda *a, **k: dataclasses.replace(
        route(*a, **k), grouped_kv=False)
    params = op.init_params(jax.random.PRNGKey(0))
    rs = np.random.RandomState(5)
    x, g = (jnp.asarray(rs.randn(b, s, e).astype(np.float32))
            for _ in range(2))

    def both_ways(op):
        def loss(p, x):
            ctx = OpContext(training=True, mesh=mesh,
                            compute_dtype=jnp.bfloat16)
            return jnp.sum(op.forward(p, [x], ctx)[0] * g)
        return jax.value_and_grad(loss, argnums=(0, 1))

    got = jax.jit(both_ways(op))(params, x)
    if grouped:
        want = jax.jit(both_ways(steered))(params, x)
    else:
        # the rule left the repeat in place: steering changes nothing,
        # the two trace to ONE program, character for character, and it
        # has run
        assert str(jax.make_jaxpr(both_ways(steered))(params, x)) == str(
            jax.make_jaxpr(both_ways(op))(params, x))
        want = got
    assert op._route.grouped_kv == grouped and op._route.core == "flash"
    assert op.traced_gauges()["executor.flash_grouped_kv_ops"] == grouped
    assert not steered._route.grouped_kv
    # under a mesh, at heads of 64, the CPU's partitioned programs
    # contract the view form's float32 rotation differently ahead of the
    # two forms (tests/test_rotary_lanes.py): a last place that a
    # bfloat16 rounding carries into a key in a few thousand, and from
    # there into the output
    last_place = bool(grouped and axes and d < 128)
    np.testing.assert_allclose(float(got[0]), float(want[0]),
                               rtol=1e-4 if last_place else 1e-6)
    for (path, a), b_ in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                             jax.tree.leaves(want[1])):
        if not grouped or (not last_place and (
                "wq" in str(path) or "wo" in str(path))):
            # the same program, or a gradient the keys' form cannot reach
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_),
                                          err_msg=str(path))
        else:
            assert _rel_rms(a, b_) < 2 * U, (path, _rel_rms(a, b_) / U)
