"""Pallas TPU kernels for the ops where XLA's default lowering underperforms.

Analog of the reference's hand-written CUDA kernels (src/ops/kernels/*.cu)
— but only where needed: XLA already fuses elementwise chains into matmuls,
so the win is in attention, where materializing the [B,H,S,S] score tensor
in HBM is the bottleneck. ``flash_attention`` keeps a head's scores in
VMEM: forward and backward recompute them from Q and K, and only the
per-row logsumexp goes to HBM beside the output.

Four kernels, told apart in a trace by name. Up to MAX_BWD_SEQ a grid
step holds the whole S x S tile of several heads (`flash_fwd_whole`,
`flash_bwd`: dQ/dK/dV from P recomputed out of the saved LSE); longer
sequences take the Q-blocked forward (`flash_fwd`) and the K-blocked
backward (`flash_bwd_blocked`), up to MAX_FLASH_SEQ — the one upper bound
the gate (``flash_attention_available``), the backward dispatch and the
native ``kernel_gate`` share; past it attention runs the einsum path.
Every MXU product takes its operands in the dtype the caller stored and
accumulates in float32 (``_dot``); softmax statistics, ``exp``, scale
and mask are float32. Tile sizes follow from the shape
(``_heads_per_step``, ``_kv_block``); the timings quoted beside them are
the kernels alone on a v5e (PR 28), and PERF.md has what they gave
through the benchmark's full step.

CPU fallback: the same kernels run under ``interpret=True`` when
FLEXFLOW_TPU_PALLAS=interpret (used by the deviceless tests); otherwise
non-TPU backends take the XLA path.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLK_Q = 128  # rows of Q per grid step of the Q-blocked forward

# Mosaic's default scoped-VMEM budget on v5e is 16 MiB, which the
# kernels below outgrow long before the chip's 128 MiB of VMEM is used
# (they keep whole [S, D] panels resident): at 16 MiB the compiler
# refuses the bf16 K-blocked backward from S = 8192 and the forward from
# S = 16384. Every flash pallas_call asks for 96 MiB instead; with that
# the deviceless v5e compile accepts forward and both backwards for
# S <= MAX_FLASH_SEQ at head_dim <= MAX_FLASH_HEAD_DIM in bf16 and f32
# (tests/test_tpu_compile.py), and refuses head_dim 256 at S = 16384.
_FLASH_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=96 << 20)

# Every kernel's ``pallas_call`` carries a ``name=``: XLA names the custom
# call's HLO instruction after it, and the profiler names a device event
# by its instruction, so a chip trace shows `tpu_custom_call_flash_fwd.3`
# where it showed `tpu_custom_call.3`. The names keep the prefix an
# unnamed kernel gets: reductions of a trace find kernels by it
# (benchmarks/trace_reduce.KERNEL_PREFIX).
KERNEL_NAME_PREFIX = "tpu_custom_call_"

# Longest sequence whose whole S x S f32 score tile of a head is one grid
# step's work, forward (`flash_fwd_whole`) and backward (`flash_bwd`)
# alike. Re-measured in PR 28 (v5e, kernel alone, bf16, head_dim 64): at
# S = 1024 the whole-tile backward takes 652 us for 128 heads against 745
# for the K-blocked one, the whole-tile forward 408 against 700 for the
# Q-blocked one; past it the tile (4 bytes x S^2, and three more like it)
# outgrows the VMEM budget.
MAX_BWD_SEQ = 1024
# Upper bounds of the flash path, forward and K-blocked backward alike.
# What binds is the VMEM budget above, not the chip's physical VMEM: the
# blocked backward holds the Q/dO/dQ panels ([S, D], lanes padded to 128)
# plus [BLK, S] f32 score tiles, and the forward holds the K/V panels
# plus a [BLK_Q, S] tile. Mirrored by the native kernel_gate
# (native/ffs_strategy.hpp) so the search never prices a length the
# compiler refuses.
MAX_FLASH_SEQ = 16384
MAX_FLASH_HEAD_DIM = 128

_NT = (((1,), (1,)), ((), ()))  # a[m, c] . b[n, c] -> [m, n]
_NN = (((1,), (0,)), ((), ()))  # a[m, c] . b[c, n] -> [m, n]


def _dot(a, b, dims):
    """One MXU product: operands in the dtype they have, f32 accumulator.
    q, k, v, dO come as the caller stored them; the kernels round `P` and
    `dS` to that dtype for the product they enter and for nothing else
    (as the einsum path rounds `probs`), so bf16 storage means bf16
    products and f32 storage f32 products."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _heads_per_step(bh: int, s: int) -> int:
    """Heads a grid step of the whole-tile kernels works through: the
    largest of 8, 4, 2, 1 that keeps S^2 x heads within 2^21 score
    elements and divides ``bh``. A step costs about 0.35 us before it
    computes. v5e, bf16,
    head_dim 64, forward / backward us for the same work with every
    head of a step unrolled (PR 28): S = 512: 618 / 864 at 1, 462 / 752
    at 4, 449 / 737 at 8; S = 1024: 422 / 681 at 1, 408 / 652 at 2;
    S = 256: 499 / 735 at 4, 438 / 704 at 8, 422 / 705 at 16."""
    heads = 8
    while heads > 1 and (heads * s * s > 1 << 21 or bh % heads):
        heads //= 2
    return heads


def _for_heads(heads: int, unroll: int, body) -> None:
    """``body(h)`` for every head of a grid step: a loop whose iteration
    works through ``unroll`` heads, so that the scheduler can fill one
    head's MXU waits with the next one's VPU passes without the kernel
    growing with ``heads`` (both powers of two). v5e, S = 512, 8 heads a
    step (PR 28): the forward takes 584 us at one head an iteration, 509
    at 2, 474 at 4, 449 all 8 unrolled; the backward 777, 734, 737, 737.
    Twelve layers'
    kernels lower and compile (deviceless, cold) in 0.7 + 1.2 s at
    forward 4 / backward 2 as at the parent, in 1.5 + 3.7 s unrolled."""
    unroll = min(unroll, heads)

    def step(i, carry):
        for u in range(unroll):
            body(i * unroll + u)
        return carry

    jax.lax.fori_loop(0, heads // unroll, step, None)


def _kv_block(s: int) -> int:
    """K/V rows a grid step of the K-blocked backward takes: the largest
    of 512, 256, 128 that divides S and keeps the [BLK, S] tile within
    2^22 elements. v5e, bf16, causal, head_dim 128 (PR 28): S = 8192:
    2221 us at 128, 1923 at 512; S = 16384: 4343 at 128, 3923 at 256,
    4414 at 512."""
    blk = 512
    while blk > BLK_Q and (blk * s > 1 << 22 or s % blk):
        blk //= 2
    return blk


def _mask_causal(st, k0):
    """-inf where the query may not see the key, in a [k, q] tile whose
    first row is key ``k0`` and first column query 0."""
    kk = k0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
    qq = jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
    return jnp.where(kk <= qq, st, -jnp.inf)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool,
                      scale: float, blk_q: int):
    """One (batch*head, q-block) grid cell: q [1,BLK_Q,D] against the full
    K/V [1,S,D] resident in VMEM; scores never touch HBM. Also emits the
    per-row logsumexp so the fused backward can recompute P exactly.
    The forward of sequences past MAX_BWD_SEQ."""
    q = q_ref[0]  # [BLK_Q, D]
    k = k_ref[0]  # [S, D]
    v = v_ref[0]
    s = _dot(q, k, _NT) * scale
    if causal:
        blk = pl.program_id(1)
        rows = blk * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= rows, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = _dot(p.astype(v.dtype), v, _NN)
    o_ref[0] = (o / l).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def _flash_fwd_whole_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                            causal: bool, scale: float, heads: int):
    """``heads`` (batch*head)s a grid cell, each with its whole sequence:
    the forward up to MAX_BWD_SEQ. The score tile is held as [k, q]: the
    softmax's max and sum then run down the sublanes (vreg against vreg)
    and come out as the [1, S] rows the logsumexp is stored as, where the
    [q, k] tile needs two cross-lane reductions a row and a relayout; and
    O^T = V^T P^T streams D rows through the MXU against the tile, which
    a head_dim of 64 fills where P V fills half its width."""
    def head(h):
        q, k, v = q_ref[h], k_ref[h], v_ref[h]      # [S, D]
        st = _dot(k, q, _NT) * scale                 # [k, q]
        if causal:
            st = _mask_causal(st, 0)
        m = jnp.max(st, axis=0, keepdims=True)       # [1, S]
        pt = jnp.exp(st - m)
        l = jnp.sum(pt, axis=0, keepdims=True)
        ot = _dot(v.T, pt.astype(v.dtype), _NN)      # [D, q]
        o_ref[h] = (ot / l).T.astype(o_ref.dtype)
        lse_ref[h] = m + jnp.log(l)

    _for_heads(heads, 4, head)


def _flash_fwd(q, k, v, causal: bool, interpret: bool, out_dtype=None):
    """q,k,v: [BH, S, D] with S % BLK_Q == 0 -> (o, lse[BH, 1, S])."""
    bh, s, d = q.shape
    scale = 1.0 / float(d) ** 0.5
    # lse is (bh, 1, s): TPU requires the last two block dims be
    # (8,128)-aligned or span the array — a middle singleton satisfies
    # that while keeping one row per (batch*head)
    out_shape = (jax.ShapeDtypeStruct((bh, s, d), out_dtype or q.dtype),
                 jax.ShapeDtypeStruct((bh, 1, s), jnp.float32))
    if s <= MAX_BWD_SEQ:
        heads = _heads_per_step(bh, s)
        seq_spec = pl.BlockSpec((heads, s, d), lambda b: (b, 0, 0))
        return pl.pallas_call(
            functools.partial(_flash_fwd_whole_kernel, causal=causal,
                              scale=scale, heads=heads),
            name=KERNEL_NAME_PREFIX + "flash_fwd_whole",
            out_shape=out_shape,
            grid=(bh // heads,),
            in_specs=[seq_spec, seq_spec, seq_spec],
            out_specs=(seq_spec,
                       pl.BlockSpec((heads, 1, s), lambda b: (b, 0, 0))),
            interpret=interpret,
            compiler_params=_FLASH_COMPILER_PARAMS,
        )(q, k, v)
    # Q blocks of 128 rows: at S = 8192 and 16384 blocks of 256 take the
    # same time within 2% (v5e, kernel alone, PR 28); not re-measured
    # through a full step, where an older round had 128 ahead
    blk = BLK_Q
    return pl.pallas_call(
        functools.partial(_flash_fwd_kernel, causal=causal, scale=scale,
                          blk_q=blk),
        name=KERNEL_NAME_PREFIX + "flash_fwd",
        out_shape=out_shape,
        grid=(bh, s // blk),
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, blk, d), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, 1, blk), lambda b, i: (b, 0, i))),
        interpret=interpret,
        compiler_params=_FLASH_COMPILER_PARAMS,
    )(q, k, v)


def _flash_bwd_tile(q, k, v, do, lse, delta, glse, scale: float, k0):
    """FlashAttention-2 backward of one [k, q] tile: k, v [Bk, D] from key
    ``k0`` on (None: not causal) against q, dO [Bq, D] and the [1, Bq]
    rows lse, delta = rowsum(dO * O) and g_lse, the upstream gradient on
    the logsumexp output (zero when only o is consumed; nonzero under
    ring attention's streaming merge, whose weights are functions of
    each block's lse). Recompute P from the saved lse, then dV = P^T dO,
    dS = P * (dO V^T - delta + g_lse), dQ = dS K * scale,
    dK = dS^T Q * scale; returns float32 dQ [Bq, D], dK, dV [Bk, D].

    With the tile as [k, q] the three products that contract over the
    sequence stream the D rows of dO^T, Q^T, K^T against it and come out
    as [D, S]: none transposes the tile, a head_dim of 64 fills the MXU,
    and the row statistics broadcast down the sublanes as they are
    stored. v5e, bf16, S = 512, head_dim 64, 512 heads, one a step (PR
    28): 864 us this way; 989 with the tile as [q, k] and dK, dV
    contracting over its rows; 980 as [k, q] with [S, D] results, and
    980 still with the element-wise work taken out of that one."""
    st = _dot(k, q, _NT) * scale                     # [k, q]
    if k0 is not None:
        st = _mask_causal(st, k0)
    pt = jnp.exp(st - lse)                           # exact softmax probs
    dpt = _dot(v, do, _NT)
    dst = (pt * (dpt - (delta - glse))).astype(q.dtype)
    dvt = _dot(do.T, pt.astype(do.dtype), _NT)       # [D, k]
    dkt = _dot(q.T, dst, _NT)                        # [D, k]
    dqt = _dot(k.T, dst, _NN)                        # [D, q]
    return (dqt * scale).T, (dkt * scale).T, dvt.T


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      glse_ref, dq_ref, dk_ref, dv_ref, *, causal: bool,
                      scale: float, heads: int):
    """``heads`` (batch*head)s a grid cell with the whole sequence in VMEM
    (gated by MAX_BWD_SEQ). Scores/probabilities never touch HBM — the
    reason XLA's einsum backward loses at these shapes."""
    def head(h):
        dq, dk, dv = _flash_bwd_tile(
            q_ref[h], k_ref[h], v_ref[h], do_ref[h], lse_ref[h],
            delta_ref[h], glse_ref[h], scale, 0 if causal else None)
        dq_ref[h] = dq.astype(dq_ref.dtype)
        dk_ref[h] = dk.astype(dk_ref.dtype)
        dv_ref[h] = dv.astype(dv_ref.dtype)

    _for_heads(heads, 2, head)


def _flash_bwd_blocked_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, glse_ref, dq_ref, dk_ref, dv_ref,
                              *, causal: bool, scale: float, blk: int):
    """FA2 backward for sequences past MAX_BWD_SEQ: grid cell = one
    (batch*head, K-block). The full Q/dO panels are resident; the
    [BLK, S] score tile for this K-block is recomputed in VMEM; dK/dV
    write their block, and dQ accumulates in-place across the K-block
    grid dimension (same output block revisited -> Pallas keeps it in
    VMEM between consecutive steps)."""
    j = pl.program_id(1)
    dq_blk, dk, dv = _flash_bwd_tile(
        q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0], delta_ref[0],
        glse_ref[0], scale, j * blk if causal else None)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(j == 0)
    def _init():
        dq_ref[0] = dq_blk

    @pl.when(j > 0)
    def _acc():
        dq_ref[0] += dq_blk


def _flash_bwd(q, k, v, o, lse, do, causal: bool, interpret: bool,
               glse=None):
    """dq, dk, dv from the saved (o, lse[BH, 1, S]): one whole-tile step
    for several heads up to MAX_BWD_SEQ, K-blocked past it — scores stay
    in VMEM tiles at every length the gate admits
    (flash_attention_available caps S at MAX_FLASH_SEQ)."""
    bh, s, d = q.shape
    scale = 1.0 / float(d) ** 0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]
    # the ring's merge hands a float32 dO (its o is float32): as an MXU
    # operand it takes the stored dtype, like P and dS
    do = do.astype(q.dtype)
    if glse is None:
        glse = jnp.zeros((bh, 1, s), jnp.float32)
    if s <= MAX_BWD_SEQ:
        heads = _heads_per_step(bh, s)
        seq_spec = pl.BlockSpec((heads, s, d), lambda b: (b, 0, 0))
        row_spec = pl.BlockSpec((heads, 1, s), lambda b: (b, 0, 0))
        return pl.pallas_call(
            functools.partial(_flash_bwd_kernel, causal=causal,
                              scale=scale, heads=heads),
            name=KERNEL_NAME_PREFIX + "flash_bwd",
            out_shape=(jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                       jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                       jax.ShapeDtypeStruct((bh, s, d), v.dtype)),
            grid=(bh // heads,),
            in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, row_spec,
                      row_spec, row_spec],
            out_specs=(seq_spec, seq_spec, seq_spec),
            interpret=interpret,
            compiler_params=_FLASH_COMPILER_PARAMS,
        )(q, k, v, do, lse, delta, glse)
    blk = _kv_block(s)
    seq_spec = pl.BlockSpec((1, s, d), lambda b, j: (b, 0, 0))
    kblk_spec = pl.BlockSpec((1, blk, d), lambda b, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, 1, s), lambda b, j: (b, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_blocked_kernel, causal=causal,
                          scale=scale, blk=blk),
        name=KERNEL_NAME_PREFIX + "flash_bwd_blocked",
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), jnp.float32),  # dq acc
                   jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)),
        grid=(bh, s // blk),
        in_specs=[seq_spec, kblk_spec, kblk_spec, seq_spec, row_spec,
                  row_spec, row_spec],
        out_specs=(seq_spec, kblk_spec, kblk_spec),
        interpret=interpret,
        compiler_params=_FLASH_COMPILER_PARAMS,
    )(q, k, v, do, lse, delta, glse)
    return dq.astype(q.dtype), dk, dv


def _xla_attention(q, k, v, causal: bool):
    """Reference einsum attention the kernel tests compare against."""
    return _xla_attention_lse(q, k, v, causal)[0]


def _xla_attention_lse(q, k, v, causal: bool):
    """Reference einsum path that also emits the per-row logsumexp."""
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(d))
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, interpret):
    return _flash_fwd(q, k, v, causal, interpret)[0]


def _flash_vjp_fwd(q, k, v, causal, interpret):
    o, lse = _flash_fwd(q, k, v, causal, interpret)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, interpret, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, causal, interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_lse(q, k, v, causal, interpret):
    """Flash attention returning (o, lse[BH, S]) — the streaming-merge
    primitive ring attention accumulates per K/V block. Differentiable:
    the backward kernel carries the upstream lse gradient (the merge
    weights are functions of lse). q,k,v: [BH, S, D]. ``o`` is emitted in
    f32: the ring merge accumulates in f32, and rounding each block's
    normalized output to bf16 first would compound per-block error."""
    o, lse = _flash_fwd(q, k, v, causal, interpret, out_dtype=jnp.float32)
    return o, lse[:, 0, :]


def _flash_lse_vjp_fwd(q, k, v, causal, interpret):
    o, lse = _flash_fwd(q, k, v, causal, interpret, out_dtype=jnp.float32)
    return (o, lse[:, 0, :]), (q, k, v, o, lse)


def _flash_lse_vjp_bwd(causal, interpret, res, gs):
    q, k, v, o, lse = res
    g_o, g_lse = gs
    glse = g_lse[:, None, :].astype(jnp.float32)
    return _flash_bwd(q, k, v, o, lse, g_o, causal, interpret, glse=glse)


flash_attention_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def pallas_mode() -> str:
    """'tpu' (compile), 'interpret' (CPU emulation for tests), or 'off'."""
    env = os.environ.get("FLEXFLOW_TPU_PALLAS", "auto")
    if env in ("interpret", "off"):
        return env
    return "tpu" if jax.default_backend() == "tpu" else "off"


# From S = 512 up the search may choose flash on hardware. The bound is
# from a chip round older than the benchmark (flash forward+backward
# 601 us against the einsum path's 1124 us at b8 h16 s512 d64, and the
# einsum path out of memory at S = 8192) and was not re-measured in PR
# 28, whose kernels take 54% of the time of that round's at S = 512;
# whether shorter sequences would gain now is open.
MIN_SEQ_FOR_FLASH = 512


def flash_shape_legal(seq_len: int, head_dim: int) -> bool:
    """The shape half of the flash gate, platform aside: Q-block tile
    divisibility, lane-aligned head dim, and the VMEM-budget upper
    bounds past which the compiler refuses the kernels. The native
    ``kernel_gate`` (native/ffs_strategy.hpp) admits the same shapes."""
    return (seq_len % BLK_Q == 0 and head_dim % 8 == 0
            and seq_len <= MAX_FLASH_SEQ and head_dim <= MAX_FLASH_HEAD_DIM)


def flash_attention_available(seq_len: int, head_dim: int) -> bool:
    mode = pallas_mode()
    if mode == "off" or not flash_shape_legal(seq_len, head_dim):
        return False
    # interpret mode (tests) exercises any legal shape; on hardware only
    # take over where the kernel beats XLA
    return mode == "interpret" or seq_len >= MIN_SEQ_FOR_FLASH


def flash_attention(q, k, v, causal: bool = False):
    """q,k,v: [B, H, S, D] → [B, H, S, D]. Caller checks
    flash_attention_available first; self-attention only (Sq == Sk)."""
    b, h, s, d = q.shape
    interpret = pallas_mode() == "interpret"
    fold = lambda x: x.reshape(b * h, x.shape[2], d)
    o = _flash(fold(q), fold(k), fold(v), causal, interpret)
    return o.reshape(b, h, s, d)


def flash_attention_sharded(q, k, v, mesh, batch_axis=None, head_axis=None,
                            causal: bool = False):
    """Flash attention inside a GSPMD-sharded jit: a bare ``pallas_call``
    is an unpartitionable custom call to the partitioner, so wrap it in
    ``shard_map`` over the mesh axes the batch/head dims are sharded on —
    each device runs the kernel on its local [B/dp, H/mp, S, D] block
    (scores never cross shards; no collectives needed). Axes not named
    stay replicated, which GSPMD enforces on entry."""
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axis, head_axis, None, None)
    fn = functools.partial(flash_attention, causal=causal)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
