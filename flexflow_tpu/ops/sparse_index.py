"""Learned sparse attention in `jax.numpy`: the form of every piece the
attention op's ``sparse_index`` path runs where the Pallas kernels do not
(the CPU, a short sequence, heads the kernels do not tile), and what the
kernel tests compare `pallas_kernels.index_select`,
`flash_attention_masked` and `index_kl` against.

An indexer scores every causal (query, key) pair,
    I_ts = sum_j w_tj relu(q_tj . k_s)        (w with every scale folded in)
a query keeps the min(t + 1, topk) keys of largest I (ties to the lower
key: `lax.top_k`'s order), the main attention's softmax runs over the
kept keys alone, and the indexer's loss is the mean over queries of
KL(p_t || softmax of I_t over the kept keys), p the main attention's
probabilities summed over the heads held and normalised, detached.
These forms hold whole [B, S, S] arrays: they are for short sequences.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def index_scores(q, k, w, precision=HIGHEST):
    """I [B, S, S] float32 from q [B, S, heads * d], the one key k
    [B, S, d] and w [B, S, heads]; the products take the operands in the
    dtype they come in and accumulate in float32."""
    b, s, d = k.shape
    heads = q.shape[-1] // d
    dots = jnp.einsum("bthd,bsd->bhts", q.reshape(b, s, heads, d), k,
                      precision=precision,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bhts,bth->bts", jnp.maximum(dots, 0.0),
                      w.astype(jnp.float32), precision=HIGHEST)


def select(scores, topk: int):
    """mask [B, S, S] int8 from the whole scores: a row's min(t + 1,
    topk) causal keys of largest score, by `lax.top_k`."""
    b, s, _ = scores.shape
    seen = jnp.tril(jnp.ones((s, s), bool))
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), min(topk, s))
    mask = jnp.zeros((b, s, s), jnp.int8).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
        idx].set(1)
    return mask * seen.astype(jnp.int8)


def kept_lse(scores, mask):
    """[B, S, 1]: log-sum-exp of a row's kept scores."""
    return jax.scipy.special.logsumexp(
        jnp.where(mask != 0, scores, -jnp.inf), axis=-1, keepdims=True)


def masked_attention(q, k, v, mask, num_heads: int, num_kv_heads: int):
    """(o [B, S, H * D] float32, lse [B, S, H]): the main attention over
    the kept pairs; q [B, S, H * D], k, v [B, S, Hk * D] in the dtype
    the products are to take, softmax in float32."""
    b, s, width = q.shape
    d = width // num_heads
    rep = num_heads // num_kv_heads
    qh = q.reshape(b, s, num_kv_heads, rep, d)
    kh, vh = (t.reshape(b, s, num_kv_heads, d) for t in (k, v))
    scores = jnp.einsum("btgrd,bsgd->bgrts", qh, kh,
                        preferred_element_type=jnp.float32) * d ** -0.5
    scores = jnp.where((mask != 0)[:, None, None], scores, -jnp.inf)
    lse = jax.scipy.special.logsumexp(scores, axis=-1)      # [B, G, R, S]
    probs = jnp.exp(scores - lse[..., None])
    o = jnp.einsum("bgrts,bsgd->btgrd", probs.astype(v.dtype), vh,
                   preferred_element_type=jnp.float32)
    return (o.reshape(b, s, width),
            lse.reshape(b, num_heads, s).transpose(0, 2, 1))


def head_sum(q, k, lse, mask, num_heads: int, num_kv_heads: int):
    """[B, S, S] float32: the main attention's probabilities on the kept
    pairs, summed over the heads (NOT normalised), from q, k and the
    saved log-sum-exps [B, S, H]."""
    b, s, width = q.shape
    d = width // num_heads
    rep = num_heads // num_kv_heads
    scores = jnp.einsum(
        "btgrd,bsgd->bgrts", q.reshape(b, s, num_kv_heads, rep, d),
        k.astype(q.dtype).reshape(b, s, num_kv_heads, d),
        preferred_element_type=jnp.float32) * d ** -0.5
    probs = jnp.exp(scores - lse.transpose(0, 2, 1).reshape(
        b, num_kv_heads, rep, s)[..., None])
    return jnp.sum(jnp.where((mask != 0)[:, None, None], probs, 0.0),
                   axis=(1, 2))


def index_kl(scores, mask, summed):
    """The indexer's loss: mean over the B S queries of KL(p || softmax
    of the kept scores), p = ``summed`` (`head_sum`, detached) over its
    row sum. Differentiable in ``scores``."""
    kept = mask != 0
    summed = jax.lax.stop_gradient(summed)
    p = summed / jnp.sum(summed, axis=-1, keepdims=True)
    logq = scores - kept_lse(scores, mask)
    terms = jnp.where(kept & (p > 0),
                      p * (jnp.log(jnp.maximum(p, 1e-37)) - logq), 0.0)
    return jnp.sum(terms) / (scores.shape[0] * scores.shape[1])
