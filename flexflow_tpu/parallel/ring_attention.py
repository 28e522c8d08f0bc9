"""Ring attention: sequence/context parallelism over the ICI ring.

First-class long-context support — new scope the reference lacks entirely
(SURVEY §5.7: FlexFlow's only sequence handling is seq_length iteration
config; no ring attention / Ulysses / context parallelism exists there).

Design: the sequence dim of Q/K/V is sharded over a 'seq' mesh axis. Each
device holds its local Q block permanently and its K/V block initially;
K/V blocks rotate around the ring via ``jax.lax.ppermute`` (pure ICI
neighbor traffic, no all-gather), and each step's partial attention is
merged with the running result using the numerically-stable streaming
log-sum-exp accumulation of blockwise/flash attention:

    m_new = max(m, m_blk);  l = l*e^{m-m_new} + l_blk*e^{m_blk-m_new}
    o = (o*l*e^{m-m_new} + o_blk*l_blk*e^{m_blk-m_new}) / l_new

Causal masking is exact: a rotating K/V block is fully visible when its
ring index < the local index, fully masked when greater, and
triangle-masked when equal — so later steps skip no compute but contribute
zero probability (XLA's static schedule cannot skip iterations; the
*communication* is what sequence parallelism saves).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _attn_block(q, k, v, scale, mask):
    """One Q-block × KV-block partial attention.

    q: [B,H,Sq,D], k/v: [B,H,Sk,D]; mask broadcastable to [B,H,Sq,Sk] or
    None. Returns (o_blk [B,H,Sq,D] *unnormalized*, m_blk [B,H,Sq],
    l_blk [B,H,Sq]).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)
    # fully-masked rows: keep m finite so exp() underflows to 0, not NaN
    m_safe = jnp.where(jnp.isfinite(m_blk), m_blk, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    l_blk = jnp.sum(p, axis=-1)
    o_blk = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
    return o_blk, m_safe, l_blk


# large-negative stand-in for -inf in the streaming lse accumulation:
# keeps every exp()/logaddexp() finite so gradients through the merge
# weights never see inf - inf (NaN) while still underflowing to exactly 0
_NEG = -1e30


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """Per-shard body under shard_map. q,k,v: [B,H,S_loc,D] local blocks.

    When the Pallas flash kernel is available for the local block shape,
    each Q-block x KV-block partial runs inside it — the S_loc x S_loc
    score tile lives in VMEM only, in BOTH forward and backward (the
    K-blocked backward kernel covers every shard length the gate admits,
    up to MAX_FLASH_SEQ; longer shards take the einsum body below). The
    einsum inner body materializes per-shard scores in HBM, quadratic
    in the shard length at exactly the long contexts ring attention
    exists for. The merge accumulates (o_normalized, lse) blockwise:
        lse' = logaddexp(lse, lse_blk)
        o'   = o * e^{lse - lse'} + o_blk * e^{lse_blk - lse'}
    """
    from flexflow_tpu.ops.pallas_kernels import (flash_attention_available,
                                                 flash_attention_lse,
                                                 merge_heads, pallas_mode,
                                                 split_heads)

    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    b, h, sq, d = q.shape
    if flash_attention_available(sq, d, h) and sq == k.shape[2]:
        interpret = pallas_mode() == "interpret"

        def _run(q_, k_, v_, blk_causal):
            # the kernels take [B, S, H*D]; the ring holds [B, H, S, D]
            o, lse = flash_attention_lse(merge_heads(q_), merge_heads(k_),
                                         merge_heads(v_), h, blk_causal,
                                         interpret)
            return split_heads(o.astype(jnp.float32), h), lse

        def block(k_cur, v_cur, kv_idx):
            if not causal:
                return _run(q, k_cur, v_cur, False)
            mode = jnp.where(kv_idx < my_idx, 0,
                             jnp.where(kv_idx == my_idx, 1, 2))
            return jax.lax.switch(mode, [
                lambda _: _run(q, k_cur, v_cur, False),   # fully visible
                lambda _: _run(q, k_cur, v_cur, True),    # diagonal: tri
                lambda _: (jnp.zeros((b, h, sq, d), jnp.float32),  # masked
                           jnp.full((b, h, sq), _NEG, jnp.float32)),
            ], None)

        def fstep(carry, _):
            o, lse, k_cur, v_cur, kv_idx = carry
            o_blk, lse_blk = block(k_cur, v_cur, kv_idx)
            lse_blk = jnp.maximum(lse_blk, _NEG)  # finite always
            lse_new = jnp.logaddexp(lse, lse_blk)
            w1 = jnp.exp(lse - lse_new)
            w2 = jnp.exp(lse_blk - lse_new)
            o = o * w1[..., None] + o_blk * w2[..., None]
            perm = [(i, (i + 1) % n) for i in range(n)]
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            return (o, lse_new, k_nxt, v_nxt, (kv_idx - 1) % n), None

        o0 = jnp.zeros((b, h, sq, d), jnp.float32)
        lse0 = jnp.full((b, h, sq), _NEG, jnp.float32)
        (o, _, _, _, _), _ = jax.lax.scan(
            fstep, (o0, lse0, k, v, my_idx), None, length=n)
        return o.astype(q.dtype)

    qf = q.astype(jnp.float32)

    def mask_for(kv_idx):
        if not causal:
            return None
        # kv block strictly earlier: visible; strictly later: masked;
        # same block: lower triangle
        tri = (jnp.arange(sq)[:, None] >= jnp.arange(sq)[None, :])
        full = kv_idx < my_idx
        none = kv_idx > my_idx
        blk = jnp.where(none, False, jnp.where(full, True, tri))
        return blk[None, None, :, :]

    def step(carry, _):
        o, m, l, k_cur, v_cur, kv_idx = carry
        o_blk, m_blk, l_blk = _attn_block(
            qf, k_cur.astype(jnp.float32), v_cur.astype(jnp.float32),
            scale, mask_for(kv_idx))
        m_new = jnp.maximum(m, m_blk)
        c1 = jnp.exp(m - m_new)
        c2 = jnp.exp(m_blk - m_new)
        o = o * c1[..., None] + o_blk * c2[..., None]
        l = l * c1 + l_blk * c2
        # rotate K/V to the next device on the ring (ICI neighbor hop)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        kv_nxt = (kv_idx - 1) % n
        return (o, m_new, l, k_nxt, v_nxt, kv_nxt), None

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (o, m, l, _, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v, my_idx), None, length=n)
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, seq_axis: str = "seq",
                   batch_axis: Optional[str] = "data",
                   head_axis: Optional[str] = None,
                   causal: bool = False):
    """Sequence-parallel attention. q,k,v: [B, H, S, D] global arrays whose
    S dim is (to be) sharded over ``seq_axis``; B over ``batch_axis`` and
    H over ``head_axis`` if those axes exist in the mesh (heads are
    independent, so keeping them sharded composes head parallelism with the
    seq ring instead of gathering heads at the shard_map boundary).

    Runs under shard_map: all mesh axes manual, ppermute over the seq ring.
    """
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ba = batch_axis if batch_axis in axes else None
    ha = (head_axis if head_axis in axes and q.shape[1] % axes[head_axis] == 0
          else None)
    spec = P(ba, ha, seq_axis, None)
    fn = functools.partial(_ring_attention_local, axis_name=seq_axis,
                          causal=causal)
    # axes not named in the specs replicate, which is the intended layout
    # for dp x sp attention
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
