"""MultiHeadAttention.

Analog of src/ops/attention.cc/.cu (cuDNN cudnnMultiHeadAttnForward,
attention.cu:35). TPU design: the four projections are MXU matmuls.
Weights are stored [num_heads, ...] so the head dim is a first-class
shardable axis (attribute parallelism, substitution.cc:1764-1770
create_partition_attention_combine); the products themselves run as
plain 2-D matmuls over the [E, heads*head_dim] view of those weights, so
q, k, v and o are [B, S, heads*head_dim] from the projections to the
output projection (PR 30): the form the Pallas flash-attention kernels
(ops/pallas_kernels.py) take, with no layout copy between a projection
and a kernel. The einsum core and ring attention, which want
[B, H, S, D], convert at their own boundary; XLA fuses that. Rotary and
the per-head norm before it keep to that form too where heads fill
128-lane columns, one of 128 (PR 42: `MultiHeadAttention._rotated`) or
two of 64 (PR 47), and there the keys and values of grouped-query
attention reach the flash kernels at their own [B, S, Hk*D], never
repeated (PR 43; at heads of 64 where a column's two query heads share
a KV head, PR 47).

Which core an op runs and in which operand form is decided in ONE place,
`MultiHeadAttention.route`, whose answer is an `AttentionRoute`: `forward`
computes it once and hands it down, `selected_impl`, fflint (FFL208 /
FFL209) and `parallel/choice.py` read the same function, and
`traced_gauges` publishes what the last forward's route says. A new
property of the op or a new operand form of the kernels is a field of the
route and an entry of that dict, in this file.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.initializers import DefaultWeightInitializer
from flexflow_tpu.ops.base import (DimRole, Op, OpContext, register_op,
                                   scoped)


def mrope_angles(positions, sections, inv_freq):
    """[S, r/2] angles of a multimodal rotary embedding: ``positions``
    [3, S] (temporal, height, width streams), ``sections`` the rotary
    pairs each stream turns, contiguous and in that order; pair i turns
    by its stream's position times ``inv_freq[i]``."""
    pos = jnp.asarray(positions, jnp.float32)
    if sum(sections) != inv_freq.shape[0] or pos.shape[0] != len(sections):
        raise ValueError(f"mrope: sections {tuple(sections)} over "
                         f"{inv_freq.shape[0]} rotary pairs, positions "
                         f"{pos.shape}")
    stream = np.repeat(np.arange(len(sections)), sections)      # [r/2]
    return pos[stream].T * inv_freq[None, :]


def rotary_tables(s: int, d: int, inv_freq, attention_factor: float = 1.0,
                  position_offset=0, wrap: int = 0, angles=None):
    """(cos, sin) [S, d] float32 of a rotary embedding over the first
    ``2 len(inv_freq)`` lanes of a ``d``-wide head, half-split pairs: row
    i stands at position offset + i (mod ``wrap`` where that is not 0),
    a pair's angle is position * ``inv_freq``, both halves of the rotated
    lanes hold the same cos and sin times ``attention_factor``, and the
    lanes past them 1 and 0. The one place the tables are formed: every
    form of the rotation below multiplies by the same bits."""
    if angles is None:      # (``angles`` [S, r/2] given: `mrope_angles`)
        pos = position_offset + jnp.arange(s, dtype=jnp.float32)
        if wrap:
            pos = pos % wrap
        angles = pos[:, None] * inv_freq[None, :]           # [S, r/2]
    rest = (s, d - 2 * inv_freq.shape[0])
    cos = jnp.concatenate([jnp.cos(angles) * attention_factor] * 2
                          + [jnp.ones(rest, jnp.float32)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angles) * attention_factor] * 2
                          + [jnp.zeros(rest, jnp.float32)], axis=-1)
    return cos, sin


def rotary_embedding(x, *, theta: float = 10000.0, position_offset=0,
                     seq_axis: int = 2, wrap: int = 0, angles=None):
    """Apply RoPE to [B, H, S, D], or with ``seq_axis=1`` to [B, S, H, D]
    (HF Llama rotate-half convention): positions offset..offset+S-1,
    inv_freq = theta^(-2i/D). ``position_offset`` (static or traced
    scalar) is the absolute position of the first row — the
    incremental-decode path rotates the new token at its true position,
    not at 0. ``wrap``: positions repeat with this period (row i stands
    at position (offset + i) mod wrap), for a sequence that holds several
    copies of one sample side by side."""
    s, d = x.shape[seq_axis], x.shape[-1]
    cos, sin = rotary_tables(s, d, rotary_frequencies(d, theta)[0],
                             position_offset=position_offset, wrap=wrap,
                             angles=angles)
    if seq_axis == 1:
        cos, sin = cos[:, None, :], sin[:, None, :]        # [S, 1, D]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * cos + rotated.astype(jnp.float32) * sin
            ).astype(x.dtype)


def rotary_interleaved(x, *, theta: float, head_dim: int, lanes_of=None,
                       inv_freq=None, factor: float = 1.0):
    """RoPE over the ADJACENT pairs (2i, 2i+1) of x [B, S, n*D], n heads of
    ``head_dim`` D side by side along the minor axis, positions 0..S-1. A
    lane's partner is its neighbour, fetched by a roll of the minor axis:
    no strided slice, and no head is taken apart. ``lanes_of`` (first,
    total): the D lanes are those from ``first`` of a rotated head
    ``total`` wide, whose frequencies they take (a control's).
    ``inv_freq`` [D / 2]: a scaled table (`rotary_frequencies`) in place
    of theta's, and ``factor`` on its cos and sin."""
    seq_axis, d = 1, head_dim
    s, width = x.shape[seq_axis], x.shape[-1]
    first, total = lanes_of or (0, d)
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** ((first + jnp.arange(
            0, d, 2, dtype=jnp.float32)) / total))
    pos = jnp.arange(s, dtype=jnp.float32)
    angles = jnp.repeat(pos[:, None] * inv_freq[None, :], 2, axis=-1)  # [S, D]
    angles = jnp.tile(angles, (1, width // d))
    shape = [1] * x.ndim
    shape[seq_axis], shape[-1] = s, width
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    even = jax.lax.broadcasted_iota(jnp.int32, shape, x.ndim - 1) % 2 == 0
    # lane 2i gets -x[2i+1], lane 2i+1 gets x[2i]
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                        jnp.roll(xf, 1, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype)


def rotary_frequencies(dim: int, theta: float, scaling=None):
    """(inv_freq [dim/2] float32, attention_factor) of a rotary embedding
    over ``dim`` rotated lanes: f_j = theta^(-2j/dim) and 1, or under
    ``scaling`` = {"rope_type": "yarn", factor, original_max_position_
    embeddings, beta_fast, beta_slow, attention_factor} (the keys of a
    public ``config.json``) YaRN's table as the `transformers` library
    forms it: with c(r) = dim ln(original / (2 pi r)) / (2 ln theta),
    low = floor(c(beta_fast)) and high = ceil(c(beta_slow)) held to
    [0, dim - 1], m_j = 1 - clip((j - low) / (high - low), 0, 1):
        inv_freq_j = (f_j / factor) (1 - m_j) + f_j m_j
    (the fast lanes keep their frequency, the slow ones are interpolated)
    and cos and sin scaled by ``attention_factor`` (0.1 ln factor + 1
    where the config gives none)."""
    f = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    # `type` is the key's older name (DeepSeek-V2's configs and their kin)
    kind = ((scaling or {}).get("rope_type") or (scaling or {}).get("type")
            or "default")
    if kind == "default":
        return f, 1.0
    if kind != "yarn":
        raise ValueError(f"rotary embedding: unknown rope_type {kind!r} "
                         f"(known: default, yarn)")
    factor = float(scaling["factor"])
    original = scaling["original_max_position_embeddings"]

    def correction(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction(scaling.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    m = 1.0 - ramp
    factor_of_attention = scaling.get("attention_factor") or (
        0.1 * math.log(factor) + 1.0)
    return (f / factor) * (1.0 - m) + f * m, float(factor_of_attention)


def latent_yarn_factors(scaling):
    """(factor of cos and sin, factor of the softmax scale) of latent
    attention under YaRN as DeepSeek-V2 states it: with m(s) = 0.1 s ln
    factor + 1 (1 where factor <= 1), cos and sin times m(mscale) /
    m(mscale_all_dim), and the softmax scale times m(mscale_all_dim)^2
    where ``mscale_all_dim`` is given and not 0."""
    factor = float(scaling["factor"])

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0

    all_dim = scaling.get("mscale_all_dim", 0) or 0
    return (m(scaling.get("mscale", 1)) / m(all_dim),
            m(all_dim) ** 2 if all_dim else 1.0)


def rotary_partial(x, inv_freq, *, rotary_dim: int,
                   attention_factor: float = 1.0):
    """RoPE over the FIRST ``rotary_dim`` lanes of every head of x
    [B, S, H, D], half-split pairs (j, j + rotary_dim / 2) as
    ``rotary_embedding`` has them, positions 0..S-1, angles position *
    ``inv_freq`` (``rotary_frequencies``), cos and sin times
    ``attention_factor``; the other lanes pass as they are (they meet cos
    1 and sin 0). A lane's partner comes by a product of the head's D
    lanes with a signed permutation [D, D] at precision `highest` (every
    sum has one term: exact), so no head is cut at half a vreg and
    nothing is laid end to end again. The form of every shape the
    lane-dense pass does not take (`MultiHeadAttention._rotated`).

    v5e, 48 heads of 128 at 8,192 positions, forward and backward ms of
    THE ROTATION ALONE, a [B, S, H, D] array in and out
    (`scripts/gate_lab.py`, PR 41): 1.90 this way, 6.12 with the rotated
    halves sliced out and concatenated, 7.30 with the partner by two
    rolls and a select; 64 heads 2.54 / 8.36 / 9.90 (`rotary_embedding`'s
    whole-head form, which slices: 4.77 and 6.37). That is not what a
    step pays: between a projection's [B, S, H*D] product and a flash
    kernel's [B, S, H*D] operand the 4-D view costs copies of the whole
    float32 array each way, and the 48-head op as a whole, forward and
    backward, reads 39.67 ms this way against 33.53 with the pass and
    32.01 with no rotary at all (`rotary.in_context.partial`, my chip
    runs, PR 42): 7.7 ms, not 1.9."""
    s, d = x.shape[1], x.shape[-1]
    half = rotary_dim // 2
    cos, sin = rotary_tables(s, d, inv_freq, attention_factor)
    # partner = x @ turn: lane j < half gets -x[j + half], lane
    # half <= j < rotary_dim gets x[j - half], the others nothing
    turn = np.zeros((d, d), np.float32)
    lanes = np.arange(half)
    turn[lanes + half, lanes] = -1.0
    turn[lanes, lanes + half] = 1.0
    xf = x.astype(jnp.float32)
    partner = jnp.dot(xf, jnp.asarray(turn),
                      precision=jax.lax.Precision.HIGHEST)
    return (xf * cos[:, None, :] + partner * sin[:, None, :]).astype(x.dtype)


# the per-head output gate's activation; "sigmoid" is a control
GATE_ACTIVATIONS = {"softplus": jax.nn.softplus, "sigmoid": jax.nn.sigmoid}


def gate_lanes(a, head_dim: int):
    """a [B, S, H] float32, one value a head, laid along the lanes of a
    [B, S, H * head_dim] operand: head n's value over its head_dim lanes.
    By a product with the 0/1 matrix [H, H * head_dim] at precision
    `highest` (every sum has one term: exact), not by a broadcast through
    a [B, S, H, D] view: H is 48 or 64 lanes of a [.., H] array, and XLA
    turns that broadcast into a float32 array of the output's size and a
    copy of the view (`scripts/gate_lab.py` has both forms' times). The
    transpose, the backward's sum over a head's lanes, is a product too."""
    h = a.shape[-1]
    of_head = jnp.arange(h * head_dim, dtype=jnp.int32) // head_dim
    spread = (of_head[None, :] == jnp.arange(h, dtype=jnp.int32)[:, None])
    return jnp.dot(a, spread.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _mesh_axes(ctx: OpContext) -> dict:
    """{axis name: size} of the context's mesh ({} without one)."""
    return (dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
            if ctx.mesh is not None else {})


def rms_normed(x, scale, eps):
    """RMS norm over the minor axis in float32, with a learned scale."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


def scaled_dot_product_attention(q, k, v, *, causal=False, dropout_rate=0.0,
                                 rng=None, compute_dtype=jnp.float32,
                                 window=0, block_diffusion=None):
    """q,k,v: [B, H, S, D] -> [B, H, S, D]. Softmax in f32 for stability.
    ``window``: under ``causal``, a query sees only its last ``window``
    keys; ``block_diffusion`` (L, B): the three-part block mask over a
    noised and a clean copy (the rule is the flash kernels':
    ``pallas_kernels.visible``)."""
    d = q.shape[-1]
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk",
        q.astype(compute_dtype),
        k.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    ) / jnp.sqrt(jnp.float32(d))
    if causal or block_diffusion:
        from flexflow_tpu.ops.pallas_kernels import visible

        s_q, s_k = scores.shape[-2], scores.shape[-1]
        mask = visible(jnp.arange(s_q)[:, None] + (s_k - s_q),
                       jnp.arange(s_k)[None, :], window, block_diffusion)
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0 and rng is not None:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd",
        probs.astype(compute_dtype),
        v.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _index_kl_loss(qi, ki, w, lse_i, mask, q, k, lse, num_heads):
    """The indexer's loss of one op through `pallas_kernels.index_kl`:
    mean over the B S queries of a row's KL. The kernel forms the
    gradient to (qi, ki, w) with the value, so the backward is a
    scale."""
    return _index_kl_fwd(qi, ki, w, lse_i, mask, q, k, lse, num_heads)[0]


def _index_kl_fwd(qi, ki, w, lse_i, mask, q, k, lse, num_heads):
    from flexflow_tpu.ops.pallas_kernels import index_kl

    weight = 1.0 / (qi.shape[0] * qi.shape[1])
    kl, dq, dw, dk = index_kl(qi, ki, w, lse_i, mask, q, k, lse, num_heads,
                              weight)
    return jnp.sum(kl) * weight, (dq.astype(qi.dtype), dk.astype(ki.dtype),
                                  dw.astype(w.dtype))


def _index_kl_bwd(num_heads, grads, g):
    dq, dk, dw = grads
    return (g.astype(dq.dtype) * dq, g.astype(dk.dtype) * dk,
            g.astype(dw.dtype) * dw, None, None, None, None, None)


_index_kl_loss.defvjp(_index_kl_fwd, _index_kl_bwd)


@dataclasses.dataclass(frozen=True)
class AttentionRoute:
    """What one forward of an attention op runs, as
    `MultiHeadAttention.route` decides it from the op's properties, its
    static shapes, the mesh's axis sizes, ``training`` and
    `pallas_mode()`. `forward` keeps the route of the last trace as
    ``op._route`` and decides nothing else."""

    # "ring" | "flash" | "einsum"
    core: str
    # why the flash kernels cannot lower for this op whatever the
    # platform: None | "cross_attention" | "shape" | "dropout"
    blocked: Optional[str] = None
    # a forced `kernel_impl="flash"` that runs einsum: the sentence
    # FFL209 shows (`op._kernel_fallback`), else None
    fallback: Optional[str] = None
    # the scopes' kind: "plain" (non-causal) | "full" | "window" |
    # "block_diffusion" | "latent" (`attention_<scope>`, `flash_<scope>`)
    scope: str = "plain"
    # (batch_axis, head_axis) of the `shard_map` the flash kernels run
    # under on a mesh of several devices; None: a bare kernel call
    shard_axes: Optional[Tuple] = None
    # K and V reach the flash kernels at the KV heads, [B, S, Hk*D]
    grouped_kv: bool = False
    # the heads' norm and rotary run as the one lane-dense pass
    rotary_in_lanes: bool = False
    # the blocked flash kernels take a block's reachable positions as
    # ONE tile, no chunk loop (`pallas_kernels.one_span`: a causal
    # window narrower than a K chunk at S past the whole-tile kernels)
    one_span: bool = False
    # the chunk-loop flash kernels take several blocks a grid step
    # (`pallas_kernels.super_block`)
    super_block: bool = False
    # of the flash forward, a head: (visited, total, masked) K blocks,
    # and under a window that hides something (the (query, key) pairs in
    # the tiles the kernels work through, forward and backward; twice the
    # pairs visible); None where flash does not run
    kv_blocks: Optional[Tuple[int, int, int]] = None
    window_pairs: Optional[Tuple[int, int]] = None
    # a head of two 128-lane blocks (256): the flash kernels that take
    # one [Q block, K block] tile a grid step
    wide_head: bool = False
    # of them the backward, a head: the tiles whose scores it forms
    wide_bwd_score_tiles: int = 0
    # learned sparse attention: the selection and the indexer's loss run
    # the kernels `index_select` / `index_kl` (with core "flash": the
    # chunk-loop kernels with the mask operand); else `ops/sparse_index`
    sparse_kernels: bool = False


# the indexer's leaves of an op with ``sparse_index``
INDEXER_LEAVES = ("w_iq", "w_ik", "w_iw", "ik_norm_scale", "ik_norm_bias")


@register_op(OperatorType.MULTIHEAD_ATTENTION)
class MultiHeadAttention(Op):
    """inputs: query [B,Sq,E], key [B,Sk,E], value [B,Sk,E] -> [B,Sq,E].

    Weight layout keeps an explicit head axis: wq/wk/wv [H, E, D],
    wo [H, D, E] — the head axis is the attribute-parallel dim the search
    may shard on the model mesh axis (reference attention.cc:214). The
    forward multiplies through their [E, H*D] / [H*D, E] views.

    Latent attention (PR 39; DeepSeek-V2's MLA) is this op with further
    properties, ``q_lora_rank`` / ``kv_lora_rank`` / ``qk_rope_head_dim``:
    queries and keys/values come out of low-rank latents with an RMS norm
    on each, a head's query and key are ``[head_dim not rotated ;
    qk_rope_head_dim rotated]``, the rotated key part is ONE vector a
    position shared by all heads, values are ``head_dim`` wide, and the
    softmax scale is (head_dim + qk_rope_head_dim)^-0.5:
        c_q = rms_norm(x wq_a);  q_nope, q_rope = c_q wq_b_nope, c_q wq_b_rope
        [c_kv ; k_r] = x wkv_a;  c_kv = rms_norm(c_kv)
        k_nope, v = c_kv wkv_b_k, c_kv wkv_b_v
        scores = (q_nope k_nope^T + rope(q_rope) rope(k_r)^T) * scale
    with rotary over the ADJACENT pairs (2i, 2i+1) of the rotated lanes;
    under ``rope_scaling`` (PR 64; DeepSeek-V2's keys: ``type`` "yarn",
    ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``, ``mscale``, ``mscale_all_dim``) YaRN's table
    (`rotary_frequencies`), cos and sin and the softmax scale times
    `latent_yarn_factors`.
    Leaves: wq_a [E, Rq], q_a_norm [Rq], wq_b_nope [H, Rq, D], wq_b_rope
    [H, Rq, R], wkv_a [E, Rkv + R], kv_a_norm [Rkv], wkv_b_k, wkv_b_v
    [H, Rkv, D], wo [H, D, E]. What differs from the plain op is the
    projections (``_qkv_latent``, ``init_params``, the counts); the core's
    dispatch, scopes, counters and kernel choice are shared, and the
    flash kernels take the rotated parts as two more operands
    (``pallas_kernels._flash_fwd``), so nothing is assembled per head.

    Three more properties of the plain op (PR 41), off by default.
    ``gate``: a leaf ``w_gate`` [E, H] and one scalar a head and
    position, a = softplus(x w_gate) in float32, that multiplies the
    head's output lanes between the core and ``wo`` (scope
    ``attention_gate``; XLA around the kernels).
    ``partial_rotary_factor``: rotary over the first ``head_dim *
    factor`` lanes of every query and key head, the others pass.
    ``rope_scaling``: the frequency table and ``attention_factor`` of
    ``rotary_frequencies`` (YaRN). Whole-head rotary with plain
    frequencies runs under the scope ``rotary_whole``, anything else
    under ``rotary_partial_yarn``.

    The per-head pass between a projection and the core (PR 42): where
    the heads are whole 128-lane columns (one of 128, or since PR 47 two
    of 64), S whole blocks of 128 rows, Pallas on and the mesh one
    device, the heads' norm (``qk_norm``) and the rotation run as ONE
    kernel over the projection's float32 [B, S, H*D] result as it lies
    (``pallas_kernels.rotary_lanes``, with its own backward), which
    writes the core's operand in the compute dtype. Every other shape
    takes ``_heads_normed`` and ``rotary_embedding`` /
    ``rotary_partial`` over a [B, S, H, D] view:
    the same float32 products from the same tables, at the price of
    XLA's copies of the whole array between the two layouts.

    Three more (PR 52), off by default. ``differential`` (Ye et al.,
    "Differential Transformer"): the heads go in pairs; pair p's two
    query heads (2p, 2p + 1) meet key heads (2P, 2P + 1) of its
    key/value pair P = p // (H / Hk) in TWO softmax maps, whose
    difference A1 - lambda A2 weighs the pair's values [v_2P ; v_2P+1],
    2 D wide; the pair's output is RMS-normed over its 2 D lanes
    (``diff_norm``, eps ``diff_norm_eps``), scaled by 1 - lambda_init and
    goes back as heads 2p, 2p + 1. lambda = exp(lambda_q1 . lambda_k1) -
    exp(lambda_q2 . lambda_k2) + ``lambda_init``, four float32 leaves of
    ``head_dim``. Both maps run the op's ordinary core on heads of 2 D:
    a pair's lanes of q as they lie are [q1 ; q2], of k [k1 ; k2] and of
    v the pair's values, so map j is the core at H / 2 heads of 2 D over
    Hk / 2 key/value heads with the OTHER query head's lanes zeroed, q
    times sqrt(2) (the cores scale by (2 D)^-1/2, the maps by D^-1/2):
    two flash calls a forward (scope ``flash_diff``), no kernel of its
    own, and the MXU contracts 128 lanes where D = 64 would fill half.
    ``kv_given``: inputs [x, k, v] with k, v [B, S, Hk * D] ALREADY
    projected by another op; the op then holds wq and wo alone
    (``attention_diff_cross`` / ``attention_cross``), and at Sq == Sk it
    takes the flash route like any self-attention. ``export_kv``: the
    op's projected k and v (after their biases) are its second and third
    OUTPUT, for such readers.

    ``route`` is where every such choice is made (core, rotary form, K/V
    form, the `shard_map`'s axes): a further property or operand form is
    decided there, recorded as a field of `AttentionRoute` and published
    by ``traced_gauges``; ``_qkv``, ``_rotated`` and ``_core`` take the
    route and decide nothing.
    """

    # the stem of the op's own scope: `forward` adds the route's kind
    scopes_itself = "attention_"
    # the gate's product is float32, as a router's (a differential op
    # adds lambda's four vectors, `__init__`)
    full_precision_params = ("w_gate",)

    def __init__(self, layer, input_shapes):
        p = layer.properties
        self.embed_dim = p["embed_dim"]
        self.num_heads = p["num_heads"]
        self.kdim = p.get("kdim") or self.embed_dim
        self.vdim = p.get("vdim") or self.embed_dim
        # a head's width is a property of its own where the model gives
        # one (a few wide heads on a width they do not divide); the
        # default is the split of the model width
        self.head_dim = p.get("head_dim") or self.embed_dim // self.num_heads
        self.dropout = p.get("dropout", 0.0)
        self.causal = p.get("causal", False)
        # sliding window (causal only): a query sees its last `window`
        # keys, itself among them; 0 is none, and one that covers the
        # whole sequence is none either
        self.window = p.get("window", 0) or 0
        if self.window and not self.causal:
            raise ValueError(f"attention '{layer.name}': a sliding window "
                             f"needs causal attention")
        # block-diffusion mask (L, B): the sequence is a noised copy of
        # an L-token sample and then the clean one, in blocks of B; a
        # noised block sees itself and the clean blocks before it, a
        # clean block the clean ones up to itself (pallas_kernels.visible)
        self.block_diffusion = tuple(p.get("block_diffusion") or ()) or None
        if self.block_diffusion:
            from flexflow_tpu.ops.pallas_kernels import (
                checked_block_diffusion)

            checked_block_diffusion(input_shapes[0][1], self.causal,
                                    self.window, self.block_diffusion)
        self.use_bias = p.get("bias", True)
        # grouped-query attention (Llama-family): kv heads may be fewer
        # than query heads; the flash kernels read a group's K and V as
        # they are where the route says so (`grouped_kv`), every other
        # core gets them repeated to H (`_qkv`)
        self.num_kv_heads = p.get("num_kv_heads") or self.num_heads
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"attention '{layer.name}': num_heads ({self.num_heads}) "
                f"must be a multiple of num_kv_heads ({self.num_kv_heads})")
        # rotary position embeddings applied to q/k after projection
        self.rope = p.get("rope", False)
        self.rope_theta = p.get("rope_theta", 10000.0)
        # rotary positions repeat with this period (0: they do not)
        self.rope_wrap = p.get("rope_wrap", 0) or 0
        # RMS norm of every query and key head over head_dim, ahead of
        # the rotary embedding, with a learned scale each
        self.qk_norm = p.get("qk_norm", False)
        self.qk_norm_eps = p.get("qk_norm_eps", 1e-6)
        # rotary over the first `rotary_dim` lanes of a head, and the
        # frequency table's scaling (`rotary_frequencies`; None: plain)
        self.rotary_dim = int(self.head_dim
                              * p.get("partial_rotary_factor", 1.0))
        self.rope_scaling = dict(p.get("rope_scaling") or {}) or None
        if self.rope and (self.rotary_dim % 2 or not self.rotary_dim):
            raise ValueError(
                f"attention '{layer.name}': partial_rotary_factor leaves "
                f"{self.rotary_dim} rotated lanes of {self.head_dim}")
        # a per-head gate on the core's output, and its activation
        self.gate = bool(p.get("gate", False))
        self.gate_activation = p.get("gate_activation", "softplus")
        if self.gate_activation not in GATE_ACTIVATIONS:
            raise ValueError(f"attention '{layer.name}': unknown "
                             f"gate_activation {self.gate_activation!r}")
        # a gate a LANE (PR 58): the query projection is [E, H x 2 x D], a
        # head's columns [query ; gate], and sigmoid(gate) multiplies the
        # core's output ahead of the output projection
        self.lane_gate = bool(p.get("lane_gate", False))
        if self.lane_gate and self.gate:
            raise ValueError(f"attention '{layer.name}': one output gate, "
                             f"a head's scalar (gate) or a lane's "
                             f"(lane_gate)")
        # the heads' norm is zero-centred: the scale is 1 + the leaf
        self.qk_norm_offset = 1.0 if p.get("qk_norm_zero_centered") else 0.0
        # latent attention: (q rank, kv rank, rotated width) or None
        self.latent = None
        if p.get("kv_lora_rank"):
            self.latent = (p["q_lora_rank"], p["kv_lora_rank"],
                           p["qk_rope_head_dim"])
            self.latent_norm_eps = p.get("latent_norm_eps", 1e-6)
            # a control, not a model: every lane of a head is rotated,
            # as one rotary over head_dim + qk_rope_head_dim lanes
            self.rope_whole_head = p.get("rope_whole_head", False)
            if not (self.causal and self.num_kv_heads == self.num_heads
                    and len(input_shapes) == 3
                    and input_shapes[0] == input_shapes[1]) or (
                        self.window or self.block_diffusion or self.qk_norm
                        or self.use_bias or self.rope_wrap or self.gate
                        or self.lane_gate
                        or self.rotary_dim != self.head_dim
                        or p.get("seq_parallel")):
                raise ValueError(
                    f"attention '{layer.name}': latent attention is causal "
                    f"self-attention with as many key/value heads as query "
                    f"heads, no bias, window, mask, head norm, gate, "
                    f"partial rotary, or ring")
            if self.rope_scaling and self.rope_whole_head:
                raise ValueError(
                    f"attention '{layer.name}': rope_whole_head (a "
                    f"control) takes plain frequencies")
        # separate q/k/v projection biases (torch nn.MultiheadAttention
        # parity — in_proj_bias). Off by default: they cost an extra
        # elementwise pass over q/k/v every step and native models
        # initialize them to zero anyway.
        self.qkv_bias = p.get("qkv_bias", False)
        # sequence/context parallelism: run the attention core as ring
        # attention over this mesh axis (SURVEY §5.7 — new vs reference)
        self.seq_parallel = p.get("seq_parallel", None)
        # head/attribute parallelism axis (set by the search when it picks a
        # "head" choice) so ring attention keeps heads sharded in shard_map
        self.head_parallel = p.get("head_parallel", None)
        # searched kernel implementation (ISSUE 15, set by apply_strategy
        # from the "_k:<impl>" choice suffix or pinned by model.compile):
        # "flash" forces the Pallas kernel where available, "einsum" pins
        # the reference einsum path even when flash is available, None =
        # availability-based auto pick (pre-kernel-search behavior).
        # When a forced "flash" cannot run (platform/shape), forward
        # falls back to einsum and records why in _kernel_fallback (the
        # route's `fallback`) — fflint FFL209 surfaces the
        # priced-vs-executed gap.
        self.kernel_impl = p.get("kernel_impl", None)
        # differential attention, keys/values given, keys/values exported
        self.differential = bool(p.get("differential", False))
        self.lambda_init = float(p.get("lambda_init", 0.8))
        self.lambda_scale = float(p.get("lambda_scale", 1.0))   # a control
        if self.differential:
            self.full_precision_params += ("lambda_q1", "lambda_k1",
                                           "lambda_q2", "lambda_k2")
        self.diff_norm_eps = p.get("diff_norm_eps", 1e-5)
        self.kv_given = bool(p.get("kv_given", False))
        self.export_kv = bool(p.get("export_kv", False))
        if self.differential and (
                self.latent or self.rope or self.qk_norm or self.gate
                or self.lane_gate
                or self.block_diffusion or self.seq_parallel
                or self.num_heads % 2 or self.num_kv_heads % 2):
            raise ValueError(
                f"attention '{layer.name}': differential attention takes "
                f"even numbers of query and key/value heads and no latent, "
                f"rotary, head norm, gate, block-diffusion mask or ring")
        # learned sparse attention (PR 54): (index heads, their width,
        # keys a query keeps), the dtype of the index products' operands,
        # the rotary pairs each of three position streams turns and, where
        # the streams differ, their positions [3][S] (static: the same for
        # every sample); whether the indexer's loss joins the step's
        self.sparse_index = tuple(p.get("sparse_index") or ()) or None
        self.indexer_dtype = p.get("indexer_dtype", "float32")
        self.mrope_section = tuple(p.get("mrope_section") or ()) or None
        self.mrope_positions = p.get("mrope_positions")
        self.index_loss = bool(p.get("index_loss", True))
        if self.sparse_index:
            refused = [name for name, on in (
                ("window", self.window),
                ("block_diffusion", self.block_diffusion),
                ("differential", self.differential),
                ("kv_given", self.kv_given), ("export_kv", self.export_kv),
                ("latent attention", self.latent), ("gate", self.gate),
                ("lane_gate", self.lane_gate),
                ("seq_parallel", self.seq_parallel),
                ("dropout", self.dropout)) if on]
            if refused or not self.causal or len(input_shapes) != 3 \
                    or input_shapes[0] != input_shapes[1]:
                raise ValueError(
                    f"attention '{layer.name}': sparse_index is causal "
                    f"self-attention without {', '.join(refused) or 'cross inputs'}"
                    f" (nobody has needed the combination yet)")
            if self.indexer_dtype not in ("float32", "bfloat16"):
                raise ValueError(
                    f"attention '{layer.name}': indexer_dtype "
                    f"{self.indexer_dtype!r}")
            if self.indexer_dtype == "float32":
                self.full_precision_params += INDEXER_LEAVES
        if (self.kv_given or self.export_kv) and (self.latent or self.rope
                                                  or self.qk_norm):
            raise ValueError(
                f"attention '{layer.name}': keys and values are given or "
                f"exported as projected: no latent, rotary or head norm")
        if self.kv_given and (len(input_shapes) != 3 or self.export_kv or any(
                tuple(shape[2:]) != (self.num_kv_heads * self.head_dim,)
                for shape in input_shapes[1:])):
            raise ValueError(
                f"attention '{layer.name}': kv_given takes [x, k, v] with "
                f"k, v [B, S, {self.num_kv_heads} * {self.head_dim}] "
                f"(got {list(input_shapes)}), and exports none")
        # the heads the CORE runs: under differential attention a pair
        # of heads is one head of twice the width
        pair = 2 if self.differential else 1
        self.core_heads = (self.num_heads // pair,
                           self.num_kv_heads // pair, self.head_dim * pair)
        self._kernel_fallback = None
        # the `AttentionRoute` of the last forward traced (what
        # `traced_gauges` publishes); None until one has been
        self._route = None
        # batch-dim sharding (str or tuple of mesh axes under the sample2
        # 'data+model' 2-D partition), recorded by apply_strategy
        self.batch_parallel = p.get("batch_parallel", None)
        self.kernel_init = p.get("kernel_initializer") or DefaultWeightInitializer()
        super().__init__(layer, input_shapes)

    @property
    def exports(self) -> int:
        """Outputs beyond the first that other layers read."""
        return 2 if self.export_kv else 0

    def compute_output_shapes(self):
        b, sq, _ = self.input_shapes[0]
        sk = self.input_shapes[1][1] if len(self.input_shapes) > 1 else sq
        return [(b, sq, self.embed_dim)] + [
            (b, sk, self.num_kv_heads * self.head_dim)] * self.exports

    def init_params(self, rng):
        h, e, d = self.num_heads, self.embed_dim, self.head_dim
        hk = self.num_kv_heads
        if self.latent:
            rq, rkv, r = self.latent
            shapes = {"wq_a": (e, rq), "wq_b_nope": (h, rq, d),
                      "wq_b_rope": (h, rq, r), "wkv_a": (e, rkv + r),
                      "wkv_b_k": (h, rkv, d), "wkv_b_v": (h, rkv, d),
                      "wo": (h, d, e)}
            params = {n: self.kernel_init(k, shape) for (n, shape), k in zip(
                shapes.items(), jax.random.split(rng, len(shapes)))}
            params["q_a_norm"] = jnp.ones((rq,))
            params["kv_a_norm"] = jnp.ones((rkv,))
            return params
        ks = jax.random.split(rng, 4)
        params = {
            "wq": self.kernel_init(ks[0], (h, e, (2 if self.lane_gate
                                                  else 1) * d)),
            "wk": self.kernel_init(ks[1], (hk, self.kdim, d)),
            "wv": self.kernel_init(ks[2], (hk, self.vdim, d)),
            "wo": self.kernel_init(ks[3], (h, d, e)),
        }
        if self.kv_given:
            del params["wk"], params["wv"]
        if self.differential:
            # keys of their own: the four above stay what they were
            for i, name in enumerate(("lambda_q1", "lambda_k1",
                                      "lambda_q2", "lambda_k2")):
                params[name] = 0.1 * jax.random.normal(
                    jax.random.fold_in(rng, 5 + i), (d,), jnp.float32)
            params["diff_norm"] = jnp.ones((2 * d,))
        if self.qk_norm:
            params["q_norm"] = jnp.full((d,), 1.0 - self.qk_norm_offset)
            params["k_norm"] = jnp.full((d,), 1.0 - self.qk_norm_offset)
        if self.gate:
            # a key of its own: the four above stay what they were
            params["w_gate"] = self.kernel_init(jax.random.fold_in(rng, 4),
                                                (e, h))
        if self.sparse_index:
            hi, di, _ = self.sparse_index
            for i, (name, shape) in enumerate((
                    ("w_iq", (e, hi * di)), ("w_ik", (e, di)),
                    ("w_iw", (e, hi)))):
                params[name] = self.kernel_init(
                    jax.random.fold_in(rng, 16 + i), shape)
            params["ik_norm_scale"] = jnp.ones((di,))
            params["ik_norm_bias"] = jnp.zeros((di,))
        if self.use_bias:
            params["bo"] = jnp.zeros((e,))
            if self.qkv_bias:
                # head axis first so attribute parallelism shards them
                # with the weights (torch in_proj_bias parity); bk/bv
                # carry the kv-head count under GQA
                params["bq"] = jnp.zeros((h, d))
                if not self.kv_given:
                    params["bk"] = jnp.zeros((hk, d))
                    params["bv"] = jnp.zeros((hk, d))
        return params

    def _project(self, x, w, bias, cd):
        """x [B, S, E] through w [H, E, D] (and bias [H, D]) to
        [B, S, H*D] in float32: one plain 2-D product over the [E, H*D]
        view of the weight, whose result has the heads side by side along
        the lanes. Spelled as an einsum onto [B, H, S, D] (or
        [B, S, H, D]) XLA writes the product sequence-minor and pays a
        whole transposing copy of it before a kernel can take it."""
        h, e, d = w.shape
        w2 = w.astype(cd).transpose(1, 0, 2).reshape(e, h * d)
        y = jnp.dot(x.astype(cd), w2, preferred_element_type=jnp.float32)
        if bias is not None:
            y = y + bias.reshape(h * d)
        return y

    def _heads_normed(self, x, heads, scale):
        """RMS norm over head_dim of every head of x [B, S, heads*D],
        float32, which the projection left it in."""
        b, s, _ = x.shape
        xh = x.reshape(b, s, heads, self.head_dim)
        rms = jax.lax.rsqrt(jnp.mean(xh * xh, axis=-1, keepdims=True)
                            + self.qk_norm_eps)
        return (xh * rms * (scale.astype(jnp.float32)
                            + self.qk_norm_offset)).reshape(x.shape)

    @property
    def rope_dim(self) -> int:
        """Rotated lanes a head's query and key carry beside ``head_dim``
        (latent attention's two-part score), else 0."""
        return self.latent[2] if self.latent else 0

    @property
    def windowed(self) -> bool:
        """The window hides something at this sequence length."""
        return 0 < self.window < self.input_shapes[0][1]

    @property
    def visible_pairs(self) -> int:
        """(query, key) pairs of one sequence and head that the mask
        leaves, counted exactly."""
        sq = self.input_shapes[0][1]
        sk = self.input_shapes[1][1] if len(self.input_shapes) > 1 else sq
        if self.block_diffusion:
            length, b = self.block_diffusion
            n = length // b
            # noised-noised n B^2, noised-clean and clean-clean B^2 a
            # pair of blocks: n(n-1)/2 and n(n+1)/2 of those
            return b * b * (n + n * n)
        w = min(sk, self.window) if self.window else sk
        return sq * w

    def route(self, mesh_axes: Dict[str, int], training: bool, *,
              batch: Optional[int] = None, sq: Optional[int] = None,
              sk: Optional[int] = None) -> AttentionRoute:
        """What a forward of this op runs on a mesh of ``mesh_axes``
        ({axis: size}) on THIS platform (`pallas_mode()`): a function of
        the op's properties, the static shapes (``batch``, ``sq``, ``sk``
        default to ``input_shapes``) and ``training``, with no other
        input. The one place the core, the rotary's form, the K/V form
        and the `shard_map`'s axes are chosen; `forward`,
        ``selected_impl``, fflint's FFL208 / FFL209 and
        `parallel/choice.py` all read it.

        The core: ring attention where the ``seq_parallel`` axis is
        larger than 1 (self-attention only); else the flash kernels
        unless einsum is pinned, the probabilities are dropped in
        training, the op is cross-attention, or the shape or the platform
        refuse them; else einsum. Under flash on a mesh of several
        devices the kernels run per shard (``shard_axes``): the batch
        axes (possibly the joint ('data','model') sample2 partition) and,
        when the search picked a head choice, the head axis, where a
        shard's heads still tile the lanes. Grouped-query K and V stay at
        the KV heads (PR 43) where `pallas_kernels.grouped_kv_shape_legal`
        admits the heads A SHARD holds: a head of 128 a column block, or
        (PR 47) two of 64 that share one KV head of a K / V lane block of
        two, with a head axis leaving every shard whole groups and whole
        K / V lane blocks. The heads' norm and rotary run as the
        lane-dense pass (PR 42) with Pallas on, heads that are whole
        128-lane columns (`rotary_lanes_shape_legal`: one of 128, two of
        64), whole row blocks, and one device (a bare kernel call has no
        partitioning). Both rules are functions of shapes alone."""
        from flexflow_tpu.ops import pallas_kernels as pk

        shapes = self.input_shapes
        batch = shapes[0][0] if batch is None else batch
        sq = shapes[0][1] if sq is None else sq
        if sk is None:
            sk = shapes[1][1] if len(shapes) > 1 else sq
        h, hk, d = self.core_heads
        dropout_rate = self.dropout if training else 0.0

        def legal(heads):   # the kernels' shape rule, at these widths
            return pk.flash_shape_legal(sq, d, heads, self.rope_dim)

        if sq != sk:
            blocked = "cross_attention"
        elif dropout_rate > 0:
            blocked = "dropout"
        elif not legal(h) or (d > pk.LANES and (
                self.block_diffusion or self.sparse_index)):
            # the kernels for a head of two lane blocks take causal
            # attention and a window, no other mask
            blocked = "shape"
        else:
            blocked = None
        if (self.seq_parallel and sq == sk
                and mesh_axes.get(self.seq_parallel, 1) > 1):
            core = "ring"
        elif (self.kernel_impl != "einsum" and blocked is None
              and pk.flash_attention_available(sq, d, h, self.rope_dim)):
            core = "flash"
        else:
            core = "einsum"
        fallback = None
        if core == "einsum" and self.kernel_impl == "flash":
            # the search chose flash and the einsum core runs
            fallback = (
                # this platform/shape cannot run the kernels
                f"flash unavailable at runtime (seq={sq}, head_dim={d}, "
                f"heads={h}) — einsum executed instead"
                if blocked in (None, "shape") else
                # attention-prob dropout in training, or cross-attention
                f"flash has no lowering for this forward "
                f"(dropout_rate={dropout_rate}, Sq={sq}, Sk={sk}) — "
                f"einsum executed instead")
        if self.block_diffusion:
            scope = "block_diffusion"
        elif self.latent:
            scope = "latent"
        elif self.sparse_index:
            scope = "sparse"
        elif self.causal:
            scope = ("cross" if self.kv_given
                     else "window" if self.windowed else "full")
            if self.differential:
                scope = "diff_" + scope
        else:
            scope = "plain"
        one_device = not any(n > 1 for n in mesh_axes.values())
        if self.sparse_index and core == "flash" and not (
                one_device and pk.masked_flash_legal(sq, h, d)):
            # the mask operand is the chunk-loop kernels', on one device
            core = "einsum"
        rotary_in_lanes = bool(
            self.rope and not self.latent and pk.pallas_mode() != "off"
            and pk.rotary_lanes_shape_legal(sq, d, h)
            and pk.rotary_lanes_shape_legal(sk, d, hk) and one_device)
        if core != "flash":
            return AttentionRoute(core, blocked, fallback, scope,
                                  rotary_in_lanes=rotary_in_lanes)

        shard_axes = None
        if not one_device:
            # the raw pallas_call would be an unpartitionable custom
            # call under GSPMD: it runs per shard via shard_map
            bp = self.batch_parallel or "data"
            bp = bp if isinstance(bp, tuple) else (bp,)
            bp = tuple(a for a in bp if mesh_axes.get(a, 1) > 1)
            bsz = int(np.prod([mesh_axes[a] for a in bp])) if bp else 1
            batch_axis = (bp if bp and batch % bsz == 0 else None)
            if batch_axis is not None and len(batch_axis) == 1:
                batch_axis = batch_axis[0]
            hp = self.head_parallel
            in_batch = batch_axis if isinstance(batch_axis, tuple) \
                else (batch_axis,)
            head_axis = (hp if hp and hp not in in_batch
                         and not self.latent    # one key for all heads
                         and mesh_axes.get(hp, 1) > 1
                         and h % mesh_axes[hp] == 0
                         and legal(h // mesh_axes[hp])
                         else None)
            shard_axes = (batch_axis, head_axis)
        # of the heads a shard holds: whole groups, whole K / V lane blocks
        shards = mesh_axes[shard_axes[1]] if (
            shard_axes is not None and shard_axes[1] is not None) else 1
        grouped_kv = bool(hk % shards == 0 and pk.grouped_kv_shape_legal(
            h // shards, hk // shards, d))
        kind = (sq, self.causal, self.window, self.block_diffusion,
                self.rope_dim)
        if d > pk.LANES:    # a tile a grid step: no span, no super-block
            return AttentionRoute(
                core, blocked, fallback, scope, shard_axes, grouped_kv,
                rotary_in_lanes,
                kv_blocks=pk.wide_kv_blocks(sq, self.causal, self.window),
                wide_head=True, wide_bwd_score_tiles=pk.wide_bwd_score_tiles(
                    sq, self.causal, self.window))
        if self.sparse_index:
            return AttentionRoute(
                core, blocked, fallback, scope, shard_axes, grouped_kv,
                rotary_in_lanes, super_block=pk.super_block_engaged(*kind),
                sparse_kernels=(self.sparse_index[1] == pk.INDEX_LANES
                                and self.sparse_index[0] % 2 == 0))
        return AttentionRoute(
            core, blocked, fallback, scope, shard_axes, grouped_kv,
            rotary_in_lanes, one_span=pk.one_span(*kind) is not None,
            super_block=pk.super_block_engaged(*kind),
            kv_blocks=(*pk.kv_blocks(*kind), pk.kv_blocks_masked(*kind)),
            window_pairs=(
                (pk.visited_pairs(*kind),
                 2 * pk.visible_pairs(sq, self.causal, self.window))
                if self.windowed else None))

    def selected_impl(self, mesh_axes=None, training: bool = False) -> str:
        """``route(mesh_axes or {}, training).core`` at the op's static
        shapes: the core ('ring' | 'flash' | 'einsum') a forward runs on
        THIS platform, from the function `forward` itself reads. Serve
        observability, the benchmark's families and `bench.py` record
        it. The KV-cache ``decode_forward`` is always the cached einsum —
        flash has no incremental decomposition there."""
        return self.route(mesh_axes or {}, training).core

    def traced_gauges(self) -> Dict[str, float]:
        """What the last traced forward's route says, and the op's kind
        (0 for everything a route holds until a forward has been
        traced). `executor.flash_lane_dense_ops`: the flash kernels took
        [B, S, heads*head_dim] operands (PR 30);
        `executor.rotary_lane_dense_ops`: the heads' norm and rotary ran
        as the one lane-dense pass (PR 42; heads of 64 too since PR 47);
        `executor.flash_grouped_kv_ops`: K and V reached the kernels at
        the KV heads (PR 43; at heads of 64 through a half of a lane
        block since PR 47); `executor.flash_one_span_ops`: the blocked
        flash kernels took a block's reachable positions as one tile
        (PR 46); `executor.flash_super_block_ops`: the chunk-loop flash
        kernels took more than one block a grid step (PR 51);
        `executor.window_attention_ops` (the window
        hides something at this length, PR 31), `executor.
        block_diffusion_attention_ops` (PR 34), `executor.
        latent_attention_ops` (PR 39); `attention/kv_blocks_*`: the
        [Q block, K chunk] tiles a head's flash forward works through,
        those of the whole square, and of the first those that hold a
        hidden pair and run the masked body (PR 35);
        `attention/window_keys_*`: of a window op that ran flash, the
        (query, key) pairs a head's kernels work through, forward and
        backward, against twice the pairs visible (PR 41);
        `executor.flash_diff_ops`: a differential op whose two maps ran
        the flash kernels (PR 52); `executor.flash_wide_head_ops`: a
        head of 256 lanes ran the wide-head kernels (PR 58), and
        `executor.flash_wide_bwd_score_tiles`: the tiles a head whose
        scores their backward forms, each once (PR 59)."""
        route = self._route or AttentionRoute("einsum")   # not traced
        visited, total, masked = route.kv_blocks or (0, 0, 0)
        keys_visited, keys_visible = route.window_pairs or (0, 0)
        # only a differential op publishes its key, as a tied product its
        extra = ({"executor.flash_diff_ops": int(route.core == "flash")}
                 if self.differential else {})
        if self.head_dim > 128:     # published only where the model has one
            extra["executor.flash_wide_head_ops"] = int(route.wide_head)
            extra["executor.flash_wide_bwd_score_tiles"] = (
                route.wide_bwd_score_tiles)
        if self.sparse_index:   # published only where the model has one
            kernels = route.core == "flash" and route.sparse_kernels
            extra.update({
                "executor.sparse_attention_ops": 1,
                # the selection ran `index_select`, the loss `index_kl`
                "executor.sparse_kernel_ops": int(bool(kernels))})
        return {
            **extra,
            "executor.flash_lane_dense_ops": int(route.core == "flash"),
            "executor.rotary_lane_dense_ops": int(route.rotary_in_lanes),
            "executor.flash_grouped_kv_ops": int(route.grouped_kv),
            "executor.flash_one_span_ops": int(route.one_span),
            "executor.flash_super_block_ops": int(route.super_block),
            "executor.window_attention_ops": int(self.windowed),
            "executor.block_diffusion_attention_ops": int(
                bool(self.block_diffusion)),
            "executor.latent_attention_ops": int(bool(self.latent)),
            "attention/kv_blocks_visited": visited,
            "attention/kv_blocks_total": total,
            "attention/kv_blocks_masked": masked,
            "attention/window_keys_visited": keys_visited,
            "attention/window_keys_visible": keys_visible,
        }

    def forward(self, params, inputs, ctx: OpContext):
        # the op's rng is split off here: inside the nested call below it
        # would leave a tracer of that call in `ctx`
        rng = ctx.next_rng() if (self.dropout > 0 and ctx.training) else None
        # every op carries a scope for the device trace. Under the
        # causal visibility rule `attention_window` where the window
        # hides something, else `attention_full`; in it `flash_window` /
        # `flash_full` around the kernel calls. XLA names an instruction
        # after the first scope inside the nested call it came from, so a
        # causal op's kernel events read `flash_window.N` / `flash_full.N`
        # (as the grouped products' read `gmm.N`); the kernel's own name
        # stays in `op_name`. Under the block-diffusion mask the scopes
        # are `attention_block_diffusion` / `flash_block_diffusion`.
        # `attention_latent` / `flash_latent` since PR 39. The one
        # decision of this forward (this is trace time):
        query, key = inputs[0], inputs[1 if len(inputs) > 1 else 0]
        route = self._route = self.route(
            _mesh_axes(ctx), ctx.training, batch=query.shape[0],
            sq=query.shape[1], sk=key.shape[1])
        # a forward that cannot take the flash branch at all (dropout,
        # cross-attention) leaves an earlier record as it is
        if route.fallback and (self._kernel_fallback is None
                               or route.blocked in (None, "shape")):
            self._kernel_fallback = route.fallback
        if self.sparse_index:
            return self._forward_sparse(params, inputs, ctx, route)
        if route.scope == "plain":
            outs, lam = self._forward(params, inputs, ctx, rng, route)
        else:
            outs, lam = scoped(self.scopes_itself + route.scope,
                               lambda params, inputs: self._forward(
                                   params, inputs, ctx, rng, route))(
                                       params, inputs)
        if lam is not None:
            # the layer's lambda leaves the step with the ops' counters
            self._counters = {"attention/diff_lambda_"
                              + self.name.removesuffix("_attn"):
                              ("mean", lam)}
        return outs

    def _forward(self, params, inputs, ctx: OpContext, rng,
                 route: AttentionRoute):
        """Projections, core, output projection. A non-causal op's
        kernel events keep the name of a top-level call,
        `tpu_custom_call*` (what `kernels.flash_roofline` sums): its
        scope `attention_plain` lies ``around`` every piece but a flash
        kernel call, not over the op."""
        around = (functools.partial(scoped, self.scopes_itself + "plain")
                  if route.scope == "plain" else lambda fn: fn)
        q, k, v, rope = around(lambda params, inputs: (
            self._qkv_latent(params, inputs, ctx) if self.latent
            else self._qkv(params, inputs, ctx, route)))(params, inputs)
        dtype = inputs[0].dtype
        exported = [t.astype(dtype) for t in (k, v)] if self.export_kv else []
        k, v = self._kv_for_core(k, v, ctx, route)
        lam = None
        if self.differential:
            o, lam = self._differential(params, q, k, v, ctx, rng, route,
                                        around)
        else:
            o = self._core(q.astype(ctx.compute_dtype), k, v, ctx, rng,
                           route, around, rope)
        if self.gate:
            o = scoped("attention_gate", lambda w, x, o: self._gated(
                w, x, o, ctx))(params["w_gate"], inputs[0], o)
        if self.lane_gate:
            o = scoped("attention_gate", lambda w, x, o: self._lane_gated(
                w, x, o, ctx))(params["wq"], inputs[0], o)
        return [around(lambda params, o: self._output(
            params, o, ctx, dtype))(params, o)] + exported, lam

    # ---- learned sparse attention (PR 54) ---------------------------------
    @property
    def _index_products(self):
        """(operand dtype, XLA precision) of the indexer's products:
        float32 at `highest`, or bfloat16 operands as they are."""
        if self.indexer_dtype == "float32":
            return jnp.float32, jax.lax.Precision.HIGHEST
        return jnp.bfloat16, None

    def _indexer(self, params, x, ctx: OpContext):
        """The indexer's operands from a DETACHED copy of the op's
        input x [B, S, E]: q_I [B, S, Hi * Di] and the ONE key k_I
        [B, S, Di], both rotated over the
        whole Di lanes at the temporal position stream, the key after a
        LayerNorm; w [B, S, Hi] with Hi^-1/2 and Di^-1/2 folded in. The
        products take ``indexer_dtype`` operands (float32 at `highest`)
        and accumulate in float32."""
        hi, di, _ = self.sparse_index
        f32 = jnp.float32
        od, precision = self._index_products
        x = jax.lax.stop_gradient(x)
        b, s, _ = x.shape

        def product(w):
            return jnp.dot(x.astype(od), w.astype(od), precision=precision,
                           preferred_element_type=f32)

        q, k, w = (product(params[n]) for n in ("w_iq", "w_ik", "w_iw"))
        mean = jnp.mean(k, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
        k = ((k - mean) * jax.lax.rsqrt(var + self.qk_norm_eps)
             * params["ik_norm_scale"].astype(f32)
             + params["ik_norm_bias"].astype(f32))
        angles = None
        if self.mrope_positions is not None:   # the temporal stream
            angles = (jnp.asarray(self.mrope_positions[0], f32)[:, None]
                      * rotary_frequencies(di, self.rope_theta)[0][None, :])
        q = rotary_embedding(q.reshape(b, s, hi, di), theta=self.rope_theta,
                             seq_axis=1, angles=angles).reshape(b, s, hi * di)
        k = rotary_embedding(k[:, :, None, :], theta=self.rope_theta,
                             seq_axis=1, angles=angles)[:, :, 0, :]
        return q.astype(od), k.astype(od), w * (hi * di) ** -0.5

    def _kept_keys(self, params, x, ctx: OpContext, kernels: bool):
        """The indexer's operands (`_indexer`) and the selection from
        them: (q_I, k_I, w, mask [B, S, S] int8, the log-sum-exp of a
        row's kept scores [B, S, 1], the pairs kept a tile or None). With
        ``kernels`` `pallas_kernels.index_select` (float32 operands as
        three bfloat16 products in two MXU passes), else
        `ops/sparse_index.py`'s whole arrays; `lax.top_k`'s set either
        way."""
        from flexflow_tpu.ops import pallas_kernels as pk
        from flexflow_tpu.ops import sparse_index as si

        topk = self.sparse_index[2]
        od, precision = self._index_products
        qi, ki, w = self._indexer(params, x, ctx)
        if kernels:   # the selection is piecewise constant
            mask, lse_i, counts = pk.index_select(*(
                jax.lax.stop_gradient(t) for t in (qi, ki, w)), topk,
                pk.BF16_3X if od == jnp.float32 else None)
        else:
            scores = si.index_scores(qi, ki, w, precision)
            mask = si.select(scores, topk)
            lse_i, counts = si.kept_lse(scores, mask), None
        return qi, ki, w, mask, jax.lax.stop_gradient(lse_i), counts

    def _forward_sparse(self, params, inputs, ctx: OpContext,
                        route: AttentionRoute):
        """An op with ``sparse_index``, as three nested calls: the
        indexer's operands and the selection (`sparse_indexer`), the main
        attention over the kept pairs with its projections
        (`attention_sparse`, the kernels under `flash_sparse`), and in
        training the indexer's loss (`sparse_indexer` again), which
        leaves on the executor's ``_aux_loss`` side channel. The main
        attention hands the indexer nothing but detached values: no
        gradient of the language-model loss reaches the indexer's
        leaves, and none of the indexer's loss any other leaf."""
        from flexflow_tpu.ops import pallas_kernels as pk
        from flexflow_tpu.ops import sparse_index as si

        h, hk, _ = self.core_heads
        cd, dtype = ctx.compute_dtype, inputs[0].dtype
        precision = self._index_products[1]
        kernels = route.core == "flash" and route.sparse_kernels

        qi, ki, w, mask, lse_i, counts = scoped(
            "sparse_indexer", lambda params, x: self._kept_keys(
                params, x, ctx, kernels))(params, inputs[0])

        def attend(params, inputs, mask, counts):
            q, k, v, _ = self._qkv(params, inputs, ctx, route)
            q = q.astype(cd)
            if route.core == "flash":
                k, v = self._kv_for_core(k, v, ctx, route)
                o, lse = scoped("flash_sparse", lambda q, k, v, mask, counts: (
                    pk.flash_attention_masked(
                        q, k, v, mask, h, hk if route.grouped_kv else None,
                        counts)))(q, k, v, mask, counts)
                # (k stays at the KV heads: at heads of 128 every group
                # is one the kernels read as it is)
                lse = lse[:, :, 0, :].transpose(0, 2, 1)     # [B, S, H]
            else:
                k, v = k.astype(cd), v.astype(cd)
                o, lse = si.masked_attention(q, k, v, mask, h, hk)
            # the keys at the KV heads, for the indexer's loss
            return self._output(params, o, ctx, dtype), q, k, lse

        y, q, k, lse = scoped(self.scopes_itself + route.scope, attend)(
            params, inputs, mask, counts)
        pairs = (jnp.sum(mask, dtype=jnp.int32) if counts is None
                 else jnp.sum(counts))
        skipped = visited = jnp.int32(0)
        if route.core == "flash":
            # the pairs in the tiles the kernels work through, forward
            # and backward added (`pallas_kernels.visited_pairs`), less
            # the tiles their summaries let them skip
            n = mask.shape[1]
            visited = jnp.int32(mask.shape[0] * pk.visited_pairs(n, True))
            for rows, keys in pk.masked_tiles(n):
                tiles = pk.mask_tiles_any(mask, rows, keys, counts)
                at_q = jnp.arange(tiles.shape[1])[:, None]
                at_k = jnp.arange(tiles.shape[2])[None, :]
                empty = jnp.sum((tiles == 0) & (at_k * keys
                                                < (at_q + 1) * rows),
                                dtype=jnp.int32)
                skipped = skipped + empty
                visited = visited - empty * (rows * keys)
        self._counters = {
            "attention/selected_pairs": ("sum", pairs),
            "attention/visited_pairs": ("sum", visited),
            "attention/mask_tiles_skipped": ("sum", skipped)}
        if ctx.training and self.index_loss:
            q, k, lse = (jax.lax.stop_gradient(t) for t in (q, k, lse))

            def loss(qi, ki, w, lse_i, mask, q, k, lse):
                if kernels:
                    return _index_kl_loss(qi, ki, w, lse_i, mask, q, k, lse,
                                          h)
                scores = si.index_scores(qi, ki, w, precision)
                return si.index_kl(scores, mask, si.head_sum(
                    q, k, lse, mask, h, hk))

            kl = scoped("sparse_indexer", loss)(qi, ki, w, lse_i, mask, q,
                                                k, lse)
            self._aux_loss = kl
            self._counters["loss/index_kl"] = ("sum", kl)
        return [y]

    def _qkv(self, params, inputs, ctx: OpContext, route: AttentionRoute):
        """q [B, S, H*D] in the compute dtype and k, v: the projections,
        the heads' norms and rotary. Under grouped-query attention
        (Hk < H) k and v are repeated to [B, S, H*D] here, for every
        core that wants whole heads; where the core is the flash kernels
        and they take a group's keys as they are (``route.grouped_kv``,
        PR 43) k and v stay [B, S, Hk*D], in float32: the kernels round
        what they read, and the groups' dK and dV come back as float32
        sums, as the repeat's backward gave them."""
        query, key, value = (inputs + inputs[:1] * 2)[:3] if len(inputs) == 1 else inputs
        cd = ctx.compute_dtype
        h, hk = self.num_heads, self.num_kv_heads
        biased = self.qkv_bias and "bq" in params
        # q, k, v and o stay [B, S, heads*head_dim] from the projections
        # to the output projection: what the products write, what the
        # flash kernels take, and no minor dimension under 128 lanes
        wq = params["wq"]
        if self.lane_gate:      # a head's first D columns; `_lane_gated`
            wq = wq[..., :self.head_dim]    # takes the rest
        q = self._project(query, wq, params["bq"] if biased else None, cd)
        if self.kv_given:   # as another op projected (and exported) them
            return q, key, value, None
        k = self._project(key, params["wk"], params["bk"] if biased else None, cd)
        v = self._project(value, params["wv"], params["bv"] if biased else None, cd)
        if self.rope:
            q, k = self._rotated(q, k, params, ctx, route.rotary_in_lanes)
        elif self.qk_norm:
            q = self._heads_normed(q, h, params["q_norm"])
            k = self._heads_normed(k, hk, params["k_norm"])
        return q, k, v, None

    def _kv_for_core(self, k, v, ctx: OpContext, route: AttentionRoute):
        """k and v as the core takes them: at the KV heads, as they are
        (float32 where the projection left them so), where the flash
        kernels read a group's keys (``route.grouped_kv``); else repeated
        to the core's heads, in the compute dtype."""
        if self.latent or route.grouped_kv:
            return k, v
        h, hk, d = self.core_heads
        b, sk = k.shape[0], k.shape[1]
        if hk != h:
            k, v = (jnp.repeat(x.reshape(b, sk, hk, d), h // hk, axis=2
                               ).reshape(b, sk, h * d) for x in (k, v))
        # the attention core consumes q/k/v in the compute dtype (the
        # projections accumulate in f32): softmax/accumulation inside every
        # path below is f32 regardless, and bf16 kernel I/O halves the
        # flash kernel's HBM traffic
        cd = ctx.compute_dtype
        return k.astype(cd), v.astype(cd)

    def _differential(self, params, q, k, v, ctx: OpContext, rng,
                      route: AttentionRoute, around):
        """(o [B, S, H*D] in the compute dtype, lambda): the two maps as
        two runs of the core at heads of 2 D (class docstring), their
        difference, the pairs' norm and 1 - lambda_init."""
        f32, cd = jnp.float32, ctx.compute_dtype
        d2 = 2 * self.head_dim
        first = (jnp.arange(q.shape[-1]) % d2) < self.head_dim
        q = q.astype(f32) * (2.0 ** 0.5)
        maps = [self._core(jnp.where(keep, q, 0.0).astype(cd), k, v, ctx,
                           rng, route, around)
                for keep in (first, ~first)]
        lam = (jnp.exp(jnp.sum(params["lambda_q1"].astype(f32)
                               * params["lambda_k1"].astype(f32)))
               - jnp.exp(jnp.sum(params["lambda_q2"].astype(f32)
                                 * params["lambda_k2"].astype(f32)))
               + self.lambda_init)
        o = maps[0].astype(f32) - (self.lambda_scale * lam
                                   ) * maps[1].astype(f32)
        pairs = rms_normed(o.reshape(o.shape[:2] + (-1, d2)),
                           params["diff_norm"], self.diff_norm_eps)
        return (pairs * (1.0 - self.lambda_init)).reshape(o.shape).astype(
            cd), lam

    def _rotated(self, q, k, params, ctx: OpContext, lanes: bool):
        """The heads' norms (``qk_norm``) and rotary of q [B, S, H*D] and
        k [B, S, Hk*D], float32 as the projections left them, under the
        scope of the rotary's form: `rotary_whole` (every lane, plain
        frequencies) or `rotary_partial_yarn`. With ``lanes`` (the
        route's ``rotary_in_lanes``) both are ONE pass of the kernel
        `pallas_kernels.rotary_lanes` over the operands as they lie,
        which leaves them in the compute dtype; else the norm, and the
        rotation over a [B, S, H, D] view (`rotary_embedding` /
        `rotary_partial`), in float32. The same float32 products and sum
        either way, from the same tables."""
        from flexflow_tpu.ops.pallas_kernels import rotary_lanes

        d, r = self.head_dim, self.rotary_dim
        whole = r == d and not self.rope_scaling
        if self.rope_wrap and not whole:
            raise NotImplementedError(
                f"attention '{self.name}': wrapped positions with partial "
                f"or scaled rotary")
        scales = ((params["q_norm"], params["k_norm"]) if self.qk_norm
                  else (None, None))
        if lanes and self.qk_norm and self.qk_norm_offset:
            scales = tuple(t.astype(jnp.float32) + self.qk_norm_offset
                           for t in scales)
        if not lanes and self.qk_norm:
            q, k = (self._heads_normed(t, t.shape[-1] // d, scale)
                    for t, scale in zip((q, k), scales))

        # a group's keys stay float32: dK of the group arrives float32,
        # from the repeat's backward or, under grouped flash keys, from
        # the kernels (which read the keys rounded either way)
        cd = ctx.compute_dtype
        dtypes = (cd, cd if self.num_kv_heads == self.num_heads
                  else jnp.float32)

        def rotate(q, k, scales):
            inv_freq, factor = rotary_frequencies(r, self.rope_theta,
                                                  self.rope_scaling)
            # three position streams that differ (equal streams are the
            # plain rotation: nothing is formed)
            angles = (mrope_angles(self.mrope_positions, self.mrope_section,
                                   inv_freq)
                      if self.mrope_positions is not None else None)
            if lanes:
                def tables(s):  # one head's; the sine has the partner's sign
                    cos, sin = rotary_tables(s, d, inv_freq, factor,
                                             wrap=self.rope_wrap,
                                             angles=angles)
                    return cos, jnp.where(jnp.arange(d) < r // 2, -sin, sin)

                of_length = {s: tables(s) for s in {q.shape[1], k.shape[1]}}
                return tuple(rotary_lanes(
                    t, *of_length[t.shape[1]], r // 2, dtype,
                    norm=None if scale is None else (scale, self.qk_norm_eps))
                    for t, scale, dtype in zip((q, k), scales, dtypes))
            out = []
            for t in (q, k):
                b, s, width = t.shape
                t = t.reshape(b, s, width // d, d)
                t = (rotary_embedding(t, theta=self.rope_theta, seq_axis=1,
                                      wrap=self.rope_wrap, angles=angles)
                     if whole
                     else rotary_partial(t, inv_freq, rotary_dim=r,
                                         attention_factor=factor))
                out.append(t.reshape(b, s, width))
            return tuple(out)

        return scoped("rotary_whole" if whole else "rotary_partial_yarn",
                      rotate)(q, k, scales)

    def _gated(self, w_gate, x, o, ctx: OpContext):
        """o [B, S, H*D] with head n's lanes times a_n = act(x w_gate)_n,
        one scalar a head and position, the product and the activation
        in float32 (the backward: a 128-lane sum of dO * o a head and
        position, and dO rescaled on its way into the kernels)."""
        a = GATE_ACTIVATIONS[self.gate_activation](jnp.dot(
            x.astype(jnp.float32), w_gate.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))           # [B, S, H]
        return (o.astype(jnp.float32) * gate_lanes(a, self.head_dim)
                ).astype(ctx.compute_dtype)

    def _lane_gated(self, wq, x, o, ctx: OpContext):
        """o [B, S, H*D] times sigmoid of the gate a lane, the second D
        columns of every head of the query projection wq [H, E, 2 D]:
        one more [E, H*D] product, the sigmoid and the multiply in
        float32."""
        gate = self._project(x, wq[..., self.head_dim:], None,
                             ctx.compute_dtype)
        return (o.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(
            ctx.compute_dtype)

    def _qkv_latent(self, params, inputs, ctx: OpContext):
        """q_nope, k_nope, v [B, S, H*D] and (q_rope [B, S, H*R], the one
        rotated key [B, S, R]) in the compute dtype: both latents, their
        norms, the up-projections (the not-rotated and the rotated parts
        as products of their own, so that each comes out lane-dense and
        nothing is sliced out of a 192-wide head) and rotary."""
        x = inputs[0]
        cd = ctx.compute_dtype
        _, rkv, r = self.latent
        eps, theta = self.latent_norm_eps, self.rope_theta

        def dot(a, w):
            return jnp.dot(a.astype(cd), w.astype(cd),
                           preferred_element_type=jnp.float32)

        # YaRN on the rotated lanes (PR 64): the scaled table, cos and
        # sin times one factor, the softmax scale times another, which
        # the float32 queries take on ahead of their rounding
        inv_freq, of_tables, of_scores = None, 1.0, 1.0
        if self.rope_scaling:
            inv_freq = rotary_frequencies(r, theta, self.rope_scaling)[0]
            of_tables, of_scores = latent_yarn_factors(self.rope_scaling)

        def rotated(t):     # [B, S, n*R], n heads side by side
            return rotary_interleaved(
                t, theta=theta, head_dim=r,
                lanes_of=(self.head_dim, self.head_dim + r)
                if self.rope_whole_head else None, inv_freq=inv_freq,
                factor=of_tables)

        c_q = rms_normed(dot(x, params["wq_a"]), params["q_a_norm"], eps)
        q_nope = self._project(c_q, params["wq_b_nope"], None, cd)
        q_rope = rotated(self._project(c_q, params["wq_b_rope"], None, cd))
        if of_scores != 1.0:
            q_nope, q_rope = q_nope * of_scores, q_rope * of_scores
        kv = dot(x, params["wkv_a"])
        c_kv = rms_normed(kv[..., :rkv], params["kv_a_norm"], eps)
        k_nope = self._project(c_kv, params["wkv_b_k"], None, cd)
        v = self._project(c_kv, params["wkv_b_v"], None, cd)
        k_rope = rotated(kv[..., rkv:])
        if self.rope_whole_head:
            q_nope, k_nope = (rotary_interleaved(
                t, theta=theta, head_dim=self.head_dim,
                lanes_of=(0, self.head_dim + r)) for t in (q_nope, k_nope))
        return (q_nope.astype(cd), k_nope.astype(cd), v.astype(cd),
                (q_rope.astype(cd), k_rope.astype(cd)))

    def _output(self, params, o, ctx: OpContext, dtype):
        cd = ctx.compute_dtype
        h, d = self.num_heads, self.head_dim
        y = jnp.dot(o.astype(cd), params["wo"].astype(cd).reshape(h * d, -1),
                    preferred_element_type=jnp.float32)
        if self.use_bias:
            y = y + params["bo"]
        return y.astype(dtype)

    def _core(self, q, k, v, ctx: OpContext, rng, route: AttentionRoute,
              around, rope=None):
        from flexflow_tpu.ops.pallas_kernels import merge_heads, split_heads

        cd = ctx.compute_dtype
        h, hk, _ = self.core_heads
        rope_dim = self.rope_dim
        dropout_rate = self.dropout if ctx.training else 0.0

        def heads_first(core):
            """A core that wants [B, H, S, D], at its own boundary. Under
            latent attention a head's query and key are assembled here,
            the obvious way: [not rotated ; rotated], the one rotated key
            repeated to every head."""
            def whole(q, k, v, rope):
                q, k, v = (split_heads(t, h) for t in (q, k, v))
                if rope is not None:
                    q = jnp.concatenate([q, split_heads(rope[0], h)], -1)
                    k = jnp.concatenate([k, jnp.broadcast_to(
                        rope[1][:, None], k.shape[:3] + (rope_dim,))], -1)
                return merge_heads(core(q, k, v))
            return around(whole)(q, k, v, rope)

        if route.core == "ring":
            if self.windowed or self.block_diffusion or self.latent:
                raise NotImplementedError(
                    f"attention '{self.name}': ring attention has no "
                    f"sliding window and no block-diffusion mask (each "
                    f"block of the ring would need its own offset into it) "
                    f"and no latent attention (the ring would pass the "
                    f"latent and the one rotated key, not whole heads)")
            if dropout_rate > 0.0 and not getattr(self, "_warned_dropout", False):
                import warnings

                warnings.warn(
                    f"attention '{self.name}': attention-prob dropout "
                    f"(rate={dropout_rate}) is not applied under "
                    f"seq_parallel ring attention; training proceeds "
                    f"without it", stacklevel=2)
                self._warned_dropout = True
            # ring attention over the 'seq' mesh axis: K/V rotate on the ICI
            # ring, scores never leave the shard
            from flexflow_tpu.parallel.ring_attention import ring_attention

            return heads_first(lambda q, k, v: ring_attention(
                q, k, v, ctx.mesh, seq_axis=self.seq_parallel,
                head_axis=self.head_parallel, causal=self.causal))
        if route.core == "flash":
            from flexflow_tpu.ops.pallas_kernels import (
                flash_attention, flash_attention_sharded)

            flash_scope = (None if route.scope == "plain"
                           else "flash_diff" if self.differential
                           else "flash_" + route.scope)

            def flash(kernel, **where):
                if route.grouped_kv:   # k, v are [B, S, Hk*D]
                    where["num_kv_heads"] = hk
                call = functools.partial(
                    kernel, num_heads=h, causal=self.causal,
                    window=self.window,
                    block_diffusion=self.block_diffusion, **where)
                if rope is not None:
                    return scoped(flash_scope, lambda q, k, v, rope: call(
                        q, k, v, rope=rope))(q, k, v, rope)
                return (scoped(flash_scope, call) if flash_scope
                        else call)(q, k, v)

            if route.shard_axes is not None:
                # a mesh of several devices: per shard via shard_map
                batch_axis, head_axis = route.shard_axes
                return flash(flash_attention_sharded, mesh=ctx.mesh,
                             batch_axis=batch_axis, head_axis=head_axis)
            return flash(flash_attention)
        return heads_first(lambda q, k, v: scaled_dot_product_attention(
            q, k, v, causal=self.causal, dropout_rate=dropout_rate,
            rng=rng, compute_dtype=cd, window=self.window,
            block_diffusion=self.block_diffusion))

    def decode_forward(self, params, inputs, ctx: OpContext,
                       k_cache, v_cache, pos):
        """KV-cache incremental forward (flexflow_tpu/serve/kv_cache.py).
        An op with ``sparse_index`` is refused (the selection over a
        cache is not written: ROADMAP Reach).

        ``inputs``: the NEW token block only — query/key/value rows
        ``[B, T, E]`` at absolute positions ``pos..pos+T-1`` (prefill is
        T = prompt length at pos 0; decode is T = 1). ``k_cache`` /
        ``v_cache``: ``[B, Hk, S_max, D]`` with positions < ``pos``
        already filled. Projects the new rows, writes them into the
        cache at ``pos``, and attends the new queries over the filled
        prefix + themselves with the exact causal mask — so prefill +
        N decode steps is numerically the full-sequence forward
        restricted to the last row, without recomputing prior K/V.
        Returns ``(y [B, T, E], k_cache, v_cache)``.

        Only causal attention has a valid incremental decomposition
        (a bidirectional row would need future K/V that doesn't exist
        yet); non-causal ops refuse rather than silently drift.
        """
        if self.sparse_index:
            raise NotImplementedError(
                f"attention '{self.name}': KV-cache incremental decode has "
                f"no learned sparse attention (a new query would score "
                f"every cached position with the indexer, whose key the "
                f"cache does not hold, and attend over the kept ones)")
        if self.lane_gate:
            raise NotImplementedError(
                f"attention '{self.name}': KV-cache incremental decode has "
                f"no gate a lane out of the query projection (this path "
                f"projects whole [E, H*D] queries and applies no gate)")
        if self.differential or self.kv_given or self.export_kv:
            raise NotImplementedError(
                f"attention '{self.name}': KV-cache incremental decode has "
                f"no differential attention and no keys/values shared "
                f"between layers (ONE cache entry would serve the producer "
                f"and every reader; this path holds one a layer)")
        if self.latent:
            raise NotImplementedError(
                f"attention '{self.name}': KV-cache incremental decode has "
                f"no latent attention (its cache would hold the compressed "
                f"latent and the one rotated key a position, read in the "
                f"absorbed form; this path caches whole heads)")
        if self.block_diffusion:
            raise NotImplementedError(
                f"attention '{self.name}': KV-cache incremental decode has "
                f"no block-diffusion mask (a block is generated over "
                f"several denoising passes, not a token a step)")
        if self.gate or self.rope_scaling or self.rotary_dim != self.head_dim:
            raise NotImplementedError(
                f"attention '{self.name}': KV-cache incremental decode has "
                f"no per-head output gate (`gate`), no partial rotary "
                f"(`partial_rotary_factor`) and no scaled frequencies "
                f"(`rope_scaling`); the cached path would drift from the "
                f"training forward")
        if self.qk_norm or self.rope_wrap:
            raise NotImplementedError(
                f"attention '{self.name}': KV-cache incremental decode "
                f"applies no query/key norm and no wrapped positions; the "
                f"cached path would drift from the training forward")
        if not self.causal:
            raise NotImplementedError(
                f"attention '{self.name}': KV-cache incremental decode "
                f"requires causal attention (bidirectional rows depend "
                f"on future positions)")
        if self.window:
            raise NotImplementedError(
                f"attention '{self.name}': KV-cache incremental decode has "
                f"no sliding window (window {self.window}): the cached "
                f"path would attend over the whole prefix and drift from "
                f"the training forward")
        query, key, value = (inputs + inputs[:1] * 2)[:3] \
            if len(inputs) == 1 else inputs
        cd = ctx.compute_dtype
        q = jnp.einsum("bse,hed->bhsd", query.astype(cd),
                       params["wq"].astype(cd),
                       preferred_element_type=jnp.float32)
        k = jnp.einsum("bse,hed->bhsd", key.astype(cd),
                       params["wk"].astype(cd),
                       preferred_element_type=jnp.float32)
        v = jnp.einsum("bse,hed->bhsd", value.astype(cd),
                       params["wv"].astype(cd),
                       preferred_element_type=jnp.float32)
        if self.qkv_bias and "bq" in params:
            q = q + params["bq"][None, :, None, :]
            k = k + params["bk"][None, :, None, :]
            v = v + params["bv"][None, :, None, :]
        if self.rope:
            q = rotary_embedding(q, theta=self.rope_theta,
                                 position_offset=pos)
            k = rotary_embedding(k, theta=self.rope_theta,
                                 position_offset=pos)
        # write the new rows into the cache at their absolute positions
        # (cache dtype is the cache's own policy — serve keeps bf16/f32
        # per the executor compute dtype)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, 0, pos, 0))
        b, _, t, d = q.shape
        s_max = k_cache.shape[2]
        rep = self.num_heads // self.num_kv_heads
        # GQA: contract the grouped query heads against the UN-expanded
        # cache (a jnp.repeat here would materialize rep x the whole
        # cache's bytes every decode step — the cache read dominates a
        # single-token step)
        grouped = rep > 1
        if grouped:
            qq = q.reshape(b, self.num_kv_heads, rep, t, d)
            scores = jnp.einsum("bgrqd,bgkd->bgrqk", qq.astype(cd),
                                k_cache.astype(cd),
                                preferred_element_type=jnp.float32)
        else:
            scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(cd),
                                k_cache.astype(cd),
                                preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(self.head_dim))
        # causal over absolute positions: key j visible to the query at
        # absolute position pos+i iff j <= pos+i (this also masks every
        # not-yet-written cache slot, since those have j >= pos+t)
        qpos = pos + jnp.arange(t)[:, None]
        visible = jnp.arange(s_max)[None, :] <= qpos
        scores = jnp.where(visible[(None, None, None) if grouped
                                   else (None, None)],
                           scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1)
        if grouped:
            o = jnp.einsum("bgrqk,bgkd->bgrqd", probs.astype(cd),
                           v_cache.astype(cd),
                           preferred_element_type=jnp.float32
                           ).reshape(b, self.num_heads, t, d)
        else:
            o = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(cd),
                           v_cache.astype(cd),
                           preferred_element_type=jnp.float32)
        y = jnp.einsum("bhsd,hde->bse", o.astype(cd),
                       params["wo"].astype(cd),
                       preferred_element_type=jnp.float32)
        if self.use_bias:
            y = y + params["bo"]
        return y.astype(query.dtype), k_cache, v_cache

    def output_dim_roles(self):
        return [(DimRole.SAMPLE, DimRole.SEQ, DimRole.CHANNEL)] * (
            1 + self.exports)

    def flops(self):
        b, sq, e = self.input_shapes[0]
        sk = self.input_shapes[1][1] if len(self.input_shapes) > 1 else sq
        h, d = self.num_heads, self.head_dim
        if self.latent:
            # both latents' projections and the output's through their
            # weights; scores over d + rope lanes, values over d
            matrices = self.params_elems() - self.latent[0] - self.latent[1]
            return (2 * b * sq * matrices
                    + 2 * b * h * self.visible_pairs * (2 * d + self.rope_dim))
        hk = self.num_kv_heads  # GQA: k/v projections use the kv heads
        proj = 2 * b * h * d * (sq * e + sq * e)
        if not self.kv_given:
            proj += 2 * b * hk * d * (sk * self.kdim + sk * self.vdim)
        # under a window a query meets at most `window` keys: S x W
        # products, not S^2 (a plain causal layer is priced at the whole
        # square, as it always was); under the block-diffusion mask the
        # pairs it leaves, counted exactly
        # (a differential pair's two maps weigh values twice as wide)
        core = 2 * b * h * self.visible_pairs * d * (
            3 if self.differential else 2)
        # the gate's product and its multiply of the core's output
        gate = (2 * e + d) * b * sq * h if self.gate else 0
        if self.lane_gate:  # its columns of the query projection, a lane
            gate = (2 * e + 1) * d * b * sq * h
        if self.sparse_index:
            # the main products over the pairs the kernels visit (every
            # causal tile: a query's kept keys lie scattered), the
            # indexer's projections, its scores over every causal pair
            # and, with the loss, the main heads' probabilities and the
            # three gradient products over them once more
            hi, di, _ = self.sparse_index
            pairs = b * sq * (sq + 1) // 2
            core = 4 * h * d * pairs
            index = (2 * b * sq * e * (hi * di + di + hi)
                     + 2 * hi * di * pairs)
            if self.index_loss:
                index += (2 * hi * di * 3 + 2 * h * d) * pairs
            return proj + core + index
        return proj + core + gate

    def sparse_saved_bytes(self) -> int:
        """What an op with ``sparse_index`` keeps for its backward pass
        beside its output: the mask, a byte a (query, key) pair, and the
        indexer's operands with their gradients, which the loss's
        kernel forms with its value."""
        b, s, _ = self.input_shapes[0]
        hi, di, _ = self.sparse_index
        width = 2 if self.indexer_dtype == "bfloat16" else 4
        return b * s * s + b * s * (hi * di + di + hi) * (width + 4)

    def params_elems(self):
        h, e, d = self.num_heads, self.embed_dim, self.head_dim
        if self.latent:
            rq, rkv, r = self.latent
            return (e * rq + rq + rq * h * (d + r) + e * (rkv + r) + rkv
                    + rkv * h * 2 * d + h * d * e)
        hk = 0 if self.kv_given else self.num_kv_heads
        n = h * d * (e + e) + hk * d * (self.kdim + self.vdim)
        if self.qk_norm:
            n += 2 * d
        if self.gate:
            n += e * h
        if self.lane_gate:
            n += h * d * e
        if self.sparse_index:
            hi, di, _ = self.sparse_index
            n += e * (hi * di + di + hi) + 2 * di
        if self.differential:
            n += 6 * d
        if self.use_bias:
            n += e + ((h + 2 * hk) * d if self.qkv_bias else 0)
        return n
