"""Pallas TPU kernels for the ops where XLA's default lowering underperforms.

Analog of the reference's hand-written CUDA kernels (src/ops/kernels/*.cu)
— but only where needed: XLA already fuses elementwise chains into matmuls,
so the win is in attention, where materializing the [B,H,S,S] score tensor
in HBM is the bottleneck. ``flash_attention`` streams K/V through VMEM per
Q block with the standard online-softmax accumulation, keeping scores
on-chip.

Forward is the Pallas kernel (it also emits the per-row logsumexp).
Backward: for sequences whose full S x S score tile fits VMEM
(S <= MAX_BWD_SEQ) a fused Pallas backward kernel recomputes P from the
saved LSE and produces dQ/dK/dV without ever materializing scores in HBM
— slope-measured 1.87x over the XLA einsum fwd+bwd at the bench shape
(b8 h16 s512 d64; 601us vs 1124us). Longer sequences take the K-blocked
backward kernel, up to MAX_FLASH_SEQ — the one upper bound the gate
(``flash_attention_available``), the backward dispatch and the native
``kernel_gate`` share; past it attention runs the einsum path.

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

BLK_Q = 128  # rows of Q per grid step (MXU-aligned)

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


def _fwd_blk(s: int) -> int:
    """Q-block rows for the forward kernel. 128 everywhere: a same-chip
    A/B through the FULL bert train step measured 228.1 samples/s at 128
    vs 222.3 at 256 (r5) — an isolated-kernel microbench had suggested
    256, but in the fused step the larger block loses (and a 256-block
    forward feeding the single-block backward triggers a pathological
    relayout in standalone use). Keep the block parameterized so the
    experiment stays one-line."""
    return BLK_Q


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool,
                      scale: float, blk_q: int):
    """One (batch*head, q-block) grid cell: q [1,BLK_Q,D] against the full
    K/V [1,S,D] resident in VMEM; scores never touch HBM. Also emits the
    per-row logsumexp so the fused backward can recompute P exactly."""
    q = q_ref[0].astype(jnp.float32)  # [BLK_Q, D]
    k = k_ref[0].astype(jnp.float32)  # [S, D]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        blk = pl.program_id(1)
        rows = blk * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= rows, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0] = (o / l).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def _flash_fwd(q, k, v, causal: bool, interpret: bool, out_dtype=None):
    """q,k,v: [BH, S, D] with S % BLK_Q == 0 -> (o, lse[BH, S])."""
    bh, s, d = q.shape
    scale = 1.0 / float(d) ** 0.5
    blk = _fwd_blk(s)
    kern = functools.partial(_flash_fwd_kernel, causal=causal, scale=scale,
                             blk_q=blk)
    return pl.pallas_call(
        kern,
        name=KERNEL_NAME_PREFIX + "flash_fwd",
        # lse is (bh, 1, s): TPU requires the last two block dims be
        # (8,128)-aligned or span the array — a middle singleton satisfies
        # that while keeping one row per (batch*head)
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), out_dtype or q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, s), jnp.float32)),
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


# Longest sequence whose full S x S f32 score tile (plus q/k/v/do/dq/dk/dv
# panels) fits the VMEM budget in the single-block backward kernel.
MAX_BWD_SEQ = 1024
# Upper bounds of the flash path, forward and K-blocked backward alike.
# What binds is the VMEM budget above, not the chip's physical VMEM: the
# blocked backward holds the Q/dO/dQ panels ([S, D], lanes padded to 128)
# plus [S, BLK_Q] f32 score tiles, and the forward holds the K/V panels
# plus a [BLK_Q, S] tile — at S = 16384 and D <= 128 the f32 case needs
# between 48 and 64 MiB (deviceless compile, v5e). Mirrored by the native
# kernel_gate (native/ffs_strategy.hpp) so the search never prices a
# length the compiler refuses.
MAX_FLASH_SEQ = 16384
MAX_FLASH_HEAD_DIM = 128


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      glse_ref, dq_ref, dk_ref, dv_ref, *, causal: bool,
                      scale: float):
    """FlashAttention-2 backward, one (batch*head) per grid cell with the
    whole sequence in VMEM (gated by MAX_BWD_SEQ): recompute P from Q,K and
    the saved LSE, then dV = P^T dO; dS = P * (dO V^T - delta + g_lse);
    dQ = dS K * scale; dK = dS^T Q * scale. Scores/probabilities never
    touch HBM — the reason XLA's einsum backward loses at these shapes.
    ``g_lse`` is the upstream gradient on the logsumexp output (zero when
    only o is consumed; nonzero under ring attention's streaming merge,
    where the merge weights are functions of each block's lse)."""
    q = q_ref[0].astype(jnp.float32)   # [S, D]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]                 # [S]
    delta = delta_ref[0, 0]             # [S] rowsum(dO * O)
    glse = glse_ref[0, 0]               # [S] upstream d/d lse
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= rows, s, -jnp.inf)
    p = jnp.exp(s - lse[:, None])       # exact softmax probs
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None] + glse[:, None])
    dq_ref[0] = (jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                 * scale).astype(dq_ref.dtype)
    dk_ref[0] = (jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                 * scale).astype(dk_ref.dtype)
    dv_ref[0] = jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32
                                    ).astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, causal: bool, interpret: bool,
               glse=None):
    bh, s, d = q.shape
    scale = 1.0 / float(d) ** 0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]
    if glse is None:
        glse = jnp.zeros((bh, 1, s), jnp.float32)
    kern = functools.partial(_flash_bwd_kernel, causal=causal, scale=scale)
    seq_spec = pl.BlockSpec((1, s, d), lambda b: (b, 0, 0))
    row_spec = pl.BlockSpec((1, 1, s), lambda b: (b, 0, 0))
    return pl.pallas_call(
        kern,
        name=KERNEL_NAME_PREFIX + "flash_bwd",
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)),
        grid=(bh,),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, row_spec,
                  row_spec, row_spec],
        out_specs=(seq_spec, seq_spec, seq_spec),
        interpret=interpret,
        compiler_params=_FLASH_COMPILER_PARAMS,
    )(q, k, v, do, lse, delta, glse)


def _flash_bwd_blocked_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, glse_ref, dq_ref, dk_ref, dv_ref,
                              *, causal: bool, scale: float, blk: int):
    """FA2 backward for sequences past MAX_BWD_SEQ: grid cell = one
    (batch*head, K-block). The full Q/dO panels are resident; the
    [S, BLK] score tile for this K-block is recomputed in VMEM; dK/dV
    write their block, and dQ accumulates in-place across the K-block
    grid dimension (same output block revisited -> Pallas keeps it in
    VMEM between consecutive steps)."""
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)    # [S, D]
    k = k_ref[0].astype(jnp.float32)    # [BLK, D]
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)  # [S, D]
    lse = lse_ref[0, 0]                 # [S]
    delta = delta_ref[0, 0]
    glse = glse_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = j * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= rows, s, -jnp.inf)
    p = jnp.exp(s - lse[:, None])       # [S, BLK]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None] + glse[:, None])
    dk_ref[0] = (jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                 * scale).astype(dk_ref.dtype)
    dv_ref[0] = jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32
                                    ).astype(dv_ref.dtype)
    dq_blk = (jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
              * scale)

    @pl.when(j == 0)
    def _init():
        dq_ref[0] = dq_blk

    @pl.when(j > 0)
    def _acc():
        dq_ref[0] += dq_blk


def _flash_bwd_blocked(q, k, v, o, lse, do, causal: bool, interpret: bool,
                       glse=None):
    bh, s, d = q.shape
    scale = 1.0 / float(d) ** 0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]
    if glse is None:
        glse = jnp.zeros((bh, 1, s), jnp.float32)
    blk = BLK_Q
    kern = functools.partial(_flash_bwd_blocked_kernel, causal=causal,
                             scale=scale, blk=blk)
    seq_spec = pl.BlockSpec((1, s, d), lambda b, j: (b, 0, 0))
    kblk_spec = pl.BlockSpec((1, blk, d), lambda b, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, 1, s), lambda b, j: (b, 0, 0))
    dq, dk, dv = pl.pallas_call(
        kern,
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
    if q.shape[1] <= MAX_BWD_SEQ:
        return _flash_bwd(q, k, v, o, lse, g, causal, interpret)
    # K-blocked kernel: scores stay in VMEM tiles at every length the
    # gate admits (flash_attention_available caps S at MAX_FLASH_SEQ)
    return _flash_bwd_blocked(q, k, v, o, lse, g, causal, interpret)


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
    if q.shape[1] <= MAX_BWD_SEQ:
        return _flash_bwd(q, k, v, o, lse, g_o, causal, interpret,
                          glse=glse)
    return _flash_bwd_blocked(q, k, v, o, lse, g_o, causal, interpret,
                              glse=glse)


flash_attention_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def pallas_mode() -> str:
    """'tpu' (compile), 'interpret' (CPU emulation for tests), or 'off'."""
    env = os.environ.get("FLEXFLOW_TPU_PALLAS", "auto")
    if env in ("interpret", "off"):
        return env
    return "tpu" if jax.default_backend() == "tpu" else "off"


# Slope-measured on v5e (b=8 h=16 d=64, per-call dispatch cancelled by
# the two-length slope): flash fwd 261us vs XLA 375us at S=512, and with
# the fused Pallas backward fwd+bwd 601us vs 1124us — flash wins from
# S=512 up (and XLA OOMs at S=8192 where flash still runs).
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
