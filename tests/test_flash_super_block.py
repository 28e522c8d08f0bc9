"""Several blocks a grid step of the chunk-loop flash kernels (PR 51):
`pallas_kernels.super_block` and the kernels under it, interpreted on the
CPU: values and counts, no times. The helpers and the tolerances are
`tests/test_flash_kernels.py`'s; a file of its own because the driver
hands a test file to ONE worker and that file is already the longest.
"""

import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import pallas_kernels as pk
from one_program import output_and_gradients
from test_flash_kernels import (U, _assert_grads_close, _f32, _qkv,
                                      _rel_rms, _repeated)

ROPE = 64   # the rotated lanes of the two-part score's query and key

# name: (S, heads, KV heads | None, head_dim, two-part score, the mask,
# what `super_block` answers: ((Q blocks a grid step, Q blocks a loop
# iteration, trimmed), sub-blocks of the backward's diagonal chunk))
SUPER_BLOCKS = {
    "causal": (2048, 1, None, 128, False, dict(causal=True),
               ((4, 4, True), 4)),
    # smallthinker's window, whole K chunks: S has to lie past it
    "window-4096": (5120, 1, None, 128, False,
                    dict(causal=True, window=4096), ((4, 4, True), 4)),
    "block_diffusion": (4096, 1, None, 128, False,
                        dict(causal=False, block_diffusion=(2048, 4)),
                        ((4, 4, True), 4)),
    "rope": (2048, 2, None, 128, True, dict(causal=True), ((4, 4, True), 4)),
    "grouped-128": (2048, 4, 1, 128, False, dict(causal=True),
                    ((4, 4, True), 4)),
    "grouped-64": (2048, 4, 2, 64, False, dict(causal=True),
                   ((4, 4, True), 4)),
    "not-causal": (2048, 2, None, 64, False, dict(causal=False),
                   ((4, 4, True), 4)),
    # `_seq_block` at S = 1536, 1280, 1152: chunks of 512, 256, 128
    "chunk-512": (1536, 1, None, 128, False, dict(causal=True),
                  ((2, 2, True), 2)),
    "chunk-256": (1280, 1, None, 128, False, dict(causal=True),
                  ((1, 1, True), 1)),
    "chunk-128": (1152, 1, None, 128, False, dict(causal=True),
                  ((1, 1, True), 1)),
    # an L that 1024 does not divide: chunks of 512
    "block_diffusion-chunk-512": (
        3072, 1, None, 128, False,
        dict(causal=False, block_diffusion=(1536, 4)), ((2, 2, True), 2)),
    # B does not divide a Q block: a super-block's Q blocks see
    # different last clean chunks
    "block_diffusion-12": (
        3072, 1, None, 128, False,
        dict(causal=False, block_diffusion=(1536, 12)), ((1, 1, False), 2)),
    # the window ends inside a chunk: the later Q blocks of a super-block
    # have left the first one's far chunk behind; the backward's
    # diagonal chunk does not ask about the window
    "window-inside-a-chunk": (2048, 1, None, 128, False,
                              dict(causal=True, window=1500),
                              ((1, 1, False), 4)),
}


def _super_block_operands(case):
    seq, h, hk, d, rope, mask, _ = SUPER_BLOCKS[case]
    q, k, v, do = _qkv(seq, d, jnp.bfloat16, seed=seq + d + h, h=h)
    if hk:
        k, v = k[..., :hk * d], v[..., :hk * d]
    kw = dict(mask, num_kv_heads=hk)
    if rope:
        qr, kr, *_ = _qkv(seq, ROPE, jnp.bfloat16, seed=1, h=h)
        kw["rope"] = (qr, kr[..., :ROPE])
    return q, k, v, do, h, kw


def _one_block(form):
    """``form`` as `super_block` would answer it, for every shape."""
    return lambda s, window=0, block_diffusion=None: form


_FORWARDS = {}


def _forward(case, form=None):
    """`_flash_fwd`'s (o, lse) of a case with ``form`` the forward's
    part of `super_block`'s answer; None, and the form that ships, is
    the rule's own answer, unpatched. One program a (case, form) for
    the module: the two tests below both ask for the one that ships."""
    q, k, v, _, h, kw = _super_block_operands(case)
    seq, bd = q.shape[1], kw.get("block_diffusion")
    causal = kw.pop("causal")
    shipped = pk.super_block(
        seq, pk.normalized_window(seq, causal, kw.get("window", 0)), bd)[0]
    form = form or shipped
    if (case, form) not in _FORWARDS:
        rope = kw.pop("rope", None)
        with (contextlib.nullcontext() if form == shipped else
              mock.patch.object(pk, "super_block", _one_block((form, 1)))):
            _FORWARDS[case, form] = jax.jit(
                lambda q, k, v, rope: pk._flash_fwd(
                    q, k, v, h, causal, True, rope=rope, **kw))(
                        q, k, v, rope)
    return _FORWARDS[case, form]


@pytest.mark.parametrize("case", list(SUPER_BLOCKS))
def test_the_rule_of_the_blocks_a_grid_step(case):
    """`super_block` at the case's shape, and that the counts of tiles
    do not ask it: a super-block is only taken where its union's
    sub-ranges are each Q block's own (`_union_is_each`)."""
    seq, _, _, _, rope, mask, want = SUPER_BLOCKS[case]
    causal = mask["causal"]
    window = pk.normalized_window(seq, causal, mask.get("window", 0))
    bd = mask.get("block_diffusion")
    assert pk.super_block(seq, window, bd) == want
    assert pk.super_block_engaged(seq, causal, window, bd,
                                  ROPE if rope else 0) == (want[0][0] > 1)
    blk_q, blk_k = pk._q_block(seq, bd), pk._seq_block(seq, bd, window)
    chains = want[0][1]
    assert chains in (1, blk_k // blk_q)
    for first in range(0, seq, chains * blk_q):
        union = pk._k_split(first, chains * blk_q, blk_k, seq, causal,
                            window, bd)
        for q0 in range(first, first + chains * blk_q, blk_q):
            assert pk._k_split(q0, blk_q, blk_k, seq, causal, window,
                               bd) == union, (first, q0)


@pytest.mark.parametrize("case", list(SUPER_BLOCKS))
def test_super_block_forward_gives_the_bits_of_one_block_a_step(
        case, monkeypatch):
    """Items 1-2 of PR 51: with the sub-tiles whole, `o` and `lse` of a
    super-block a grid step (and of two a step, the loop that carries
    nothing) are those of one Q block a step BIT FOR BIT: the same
    tiles in the same order, each with its own running sums. Trimmed
    (what ships), a diagonal sub-tile's sums run over fewer masked
    zeros: exp(_MASKED - m) is 0.0 exactly, so the bits stand here too;
    the chip's compiler may add a row up in another order, which the
    tolerances of the next test allow."""
    q, _, _, _, _, kw = _super_block_operands(case)
    seq, bd = q.shape[1], kw.get("block_diffusion")
    window = pk.normalized_window(seq, kw["causal"], kw.get("window", 0))
    (_, chains, trimmed), _ = pk.super_block(seq, window, bd)

    want = _forward(case, (1, 1, False))
    forms = [(chains, chains, False), (chains, chains, trimmed)]
    if bd is None and seq % (2 * chains * pk._q_block(seq)) == 0:
        forms.append((2 * chains, chains, trimmed))
    for form in forms:
        for name, a, b in zip(("o", "lse"), _forward(case, form), want):
            assert np.array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32)), (form, name)


def _float32_attention(q, k, v, h, hk, rope, mask):
    """The float32 einsum attention a head at a time on [B, S, H*D]
    operands: grouped keys repeated, the two-part score as ONE product
    of the joined widths (its scale is then 1 / sqrt(D + R))."""
    b, s, hd = q.shape
    d = hd // h
    if hk:
        k, v = _repeated(k, hk, h // hk), _repeated(v, hk, h // hk)
    qh, kh, vh = (pk.split_heads(x, h) for x in (q, k, v))
    if rope is not None:
        qr, kr = rope
        qh = jnp.concatenate([qh, pk.split_heads(qr, h)], axis=-1)
        kh = jnp.concatenate([kh, jnp.broadcast_to(
            kr[:, None], (b, h, s, kr.shape[-1]))], axis=-1)
    # the reference scales by the joined width; v keeps its own
    width = qh.shape[-1]
    o, lse = pk._xla_attention_lse(
        qh.reshape(b * h, s, width), kh.reshape(b * h, s, width),
        jnp.pad(vh, ((0, 0),) * 3 + ((0, width - d),)).reshape(
            b * h, s, width),
        mask["causal"], mask.get("window", 0), mask.get("block_diffusion"))
    return (pk.merge_heads(o.reshape(b, h, s, width)[..., :d]),
            lse.reshape(b, h, s))


@pytest.mark.parametrize("case", list(SUPER_BLOCKS))
def test_super_block_kernels_match_float32_attention(case):
    """Both directions of the kernels as shipped (the forward's
    super-block with its trimmed diagonal, the backward's diagonal
    chunk in sub-blocks) against the float32 einsum attention, within
    the tolerances `test_bf16_operands_match_float32_attention` derives
    from bf16's rounding. A sub-tile that stopped a block short, or
    started one late, of its visible keys fails the output's bound by
    orders of magnitude."""
    q, k, v, do, h, kw = _super_block_operands(case)
    mask = SUPER_BLOCKS[case][5]
    hk, rope = kw["num_kv_heads"], kw.get("rope")
    causal = mask["causal"]

    def kernels(q, k, v, *r):
        return pk._flash(q, k, v, h, causal, True, mask.get("window", 0),
                         mask.get("block_diffusion"), r or None, hk)

    def reference(q, k, v, *r):
        return _float32_attention(q, k, v, h, hk, r or None, mask)

    operands = (q, k, v) + (rope or ())
    # grouped keys go in as float32 (`flash_attention`), their sums come
    # back float32
    given = tuple(x.astype(np.float32) if hk and n in (1, 2) else x
                  for n, x in enumerate(operands))
    (d_o,) = _f32(do)
    _, g = output_and_gradients(kernels, d_o, *given)
    (want, want_lse), gr = output_and_gradients(reference, d_o,
                                                *_f32(*operands))

    got, lse = _forward(case)
    vmax = float(np.max(np.abs(v.astype(np.float32))))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=0, atol=2 * U * vmax)
    np.testing.assert_allclose(np.asarray(lse[:, :, 0]),
                               np.asarray(want_lse), rtol=1e-5, atol=1e-5)
    _assert_grads_close(g[:3], gr[:3], case)
    for name, a, b in zip(("dq_rope", "dk_rope"), g[3:], gr[3:]):
        assert _rel_rms(a, b) < 4 * U, (case, name, _rel_rms(a, b) / U)


def test_visited_pairs_count_what_the_super_blocks_leave_out():
    """`visited_pairs` by the kernels' own lines (PR 51: `_kept`,
    `_key_trim`, `_query_trim`), by hand. At 4,096 positions (ouro,
    joyai), Q blocks of 256 and K chunks of 1024: the forward's 40
    [256, 1024] tiles a head less, of each of the 4 super-blocks'
    diagonal chunk, 0 + 1 + 2 + 3 squares of 256 keys past a sub-tile's
    last query; the backward's 10 [1024, 1024] tiles less, of each of
    the 4 K blocks' own chunk, 0 + 1 + 2 + 3 squares of 256 queries
    ahead of a sub-block's first key. The tiles themselves (`kv_blocks`,
    `kv_blocks_masked`) are the parent's: a trimmed tile is still
    visited."""
    sq = 256 * 256
    assert pk.visited_pairs(4096, True) == (
        (40 * 256 * 1024 - 4 * 6 * sq) + (10 * 1024 * 1024 - 4 * 6 * sq))
    assert pk.kv_blocks(4096, True) == (40, 64)
    assert pk.kv_blocks_masked(4096, True) == 16
    # 8,192 (laguna, nemotron): 144 and 36 tiles, 8 diagonals each way
    assert pk.visited_pairs(8192, True) == (
        144 * 256 * 1024 + 36 * 1024 * 1024 - 2 * 8 * 6 * sq)
    # smallthinker's window: 280 tiles and (12 x 5 + 4 + 3 + 2 + 1 =) 70,
    # 16 diagonals each way; and the far chunk a whole window behind (of
    # the 12 super-blocks from position 4,096 on) or ahead (of the 12 K
    # blocks up to 12,288), the transposed 6 squares each
    assert pk.visited_pairs(16384, True, 4096) == (
        280 * 256 * 1024 + 70 * 1024 * 1024 - 2 * (16 + 12) * 6 * sq)
    assert 1.06 < pk.visited_pairs(16384, True, 4096) / (
        2 * pk.visible_pairs(16384, True, 4096)) < 1.07      # 1.14 whole
    # sdar, 320 and 80 tiles. Forward: the 16 super-blocks' last clean
    # chunk (6 squares) and the 8 noised ones' own noised chunk, of which
    # a sub-tile takes its own 256 keys (12 of 16 squares left out).
    # Backward: the 8 noised K blocks' own chunk and the 8 clean ones'
    # two (their noised and their clean queries), 6 squares each: that a
    # noised block's chunk also ENDS with the sub-block is not known
    # where the kernel is traced
    assert pk.visited_pairs(16384, False, 0, (8192, 4)) == (
        320 * 256 * 1024 - (16 * 6 + 8 * 12) * sq
        + 80 * 1024 * 1024 - (8 + 2 * 8) * 6 * sq)
    # a window that ends inside a chunk: one Q block a step, whole
    # tiles forward; the backward's 2 diagonals are trimmed all the same
    assert pk.super_block(2048, 1500) == ((1, 1, False), 4)
    assert pk.visited_pairs(2048, True, 1500) == (
        12 * 256 * 1024 + 3 * 1024 * 1024 - 2 * 6 * sq)
    # chunks of 512 (S = 1536): two parts, one square a diagonal
    assert pk.visited_pairs(1536, True) == (
        12 * 256 * 512 + 6 * 512 * 512 - 2 * 3 * sq)
    # chunks of one Q block (S = 1280, 1152): nothing to leave out
    assert pk.visited_pairs(1280, True) == 2 * 15 * sq
    assert pk.visited_pairs(1152, True) == 2 * 45 * 128 * 128
    # without a mask there is no diagonal: the whole square, twice
    assert pk.visited_pairs(2048, False) == 2 * 2048 * 2048
    # against the pairs the mask leaves, twice: 1.06 at 4,096 (1.25)
    assert 1.06 < pk.visited_pairs(4096, True) / (
        2 * pk.visible_pairs(4096, True)) < 1.07
