"""Plain reference of the `keye` family: forward, loss; gradients by
`jax.grad`, Adam in `common.py`.

The architecture is the language model of Keye-VL-2.0-30B-A3B as the
catalog's `config` gives it (`model_type` `KeyeVL2`): the Qwen3-MoE
decoder layer, 48 alike, whose attention is LEARNED SPARSE ATTENTION
(`sa_config`; DeepSeek Sparse Attention as the DeepSeek-V3.2-Exp report
describes it, written from memory), a multimodal rotary embedding, a
final RMSNorm and an untied head, trained causally on the next token.
Written out here from the arithmetic alone, in float32 `jax.numpy` under
matmul precision `highest`; it shares no code with `flexflow_tpu`.

Layer l (x the residual stream, positions t, s; three position streams
p = (p_t, p_h, p_w) a position, all the token's index for text):
    h   = RMSNorm(x), eps 1e-6, a learned scale
    q, k, v = h W_q, h W_k, h W_v  (H query heads, H_kv key/value heads
                                   of d = 128, no bias)
    q = RMSNorm(q over d) * s_q;   k = RMSNorm(k over d) * s_k
    q, k = mrope(q, p), mrope(k, p)   64 rotary pairs (i, i + 64) a head,
                                   pair i turns by inv_freq_i =
                                   theta^(-2i/d) times p_t (pairs 0-15),
                                   p_h (16-39) or p_w (40-63)
  the indexer, on hd = stop_gradient(h) (whole on every chip):
    qI  = rope(hd W_iq)            [Hi = 16 heads of Di = 64], over the
    kI  = rope(LayerNorm(hd W_ik)) whole 64 lanes at p_t, pairs (i, i+32)
    wI  = hd W_iw * Hi^-1/2
    I_ts = sum_j wI_tj relu(qI_tj . kI_s) * Di^-1/2         for s <= t
    S_t = the min(t + 1, topk) keys s <= t of largest I_ts, ties to the
          lower s (`lax.top_k`)
  the main attention over S_t alone, the same set for every head:
    A_tgs = softmax_{s in S_t}(q_tg . k_s / sqrt(d));  a = (A v) W_o
  the indexer's loss, p detached:
    p_ts = sum_g A_tgs / sum_{s'} sum_g A_tgs'   (g over the heads HELD)
    L_I  = mean_t KL(p_t || softmax_{s in S_t}(I_t))
    x'  = x + a
    g   = RMSNorm(x');  r = g W_r  (float32 whatever `operand` is)
    experts: softmax over all 128, the 8 largest renormalised, the held
    ones' SwiGLU outputs weighted and added:  x'' = x' + m
After the last layer  z = RMSNorm(x) W_head  and the step's loss is
    mean_t(-log softmax(z_t)[next token]) + sum_l L_I(l).

No gradient of the first term reaches the indexer's leaves (the
selection is piecewise constant, its input detached), and none of the
second any other leaf (p detached).

`common.py` drives a reference through `forward` (the logits) and
`sample_losses(pred, y)`, which sees the logits alone; the indexers'
loss is no function of them. So `forward` also keeps the per-sample sum
of the layers' KL terms where `sample_losses` finds it: the traced value
inside one trace (`common.compiled`'s `chunk_sum` calls both in one),
and the last concrete values, through `jax.debug.callback`, for
`common.loss_of`, which is handed the predictions of a `predict` call
made just before. `forward_and_index_kl` is the function without that.

Scores are formed in blocks of QUERY_BLOCK queries, each under
`jax.checkpoint`, every expert is under one and every layer too (as
`references/sdar.py`, for its reason).

`operand` rounds the operands of every matrix multiplication that the
configuration states in bfloat16 (not the router's and not the
indexer's, stated float32): `"f32"`, `"bf16"`, `"fp8"`.
"""

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def round_operand(x, operand):
    if operand == "f32":
        return x
    if operand == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if operand == "fp8":
        scale = jnp.max(jnp.abs(x)) / 448.0  # e4m3's largest finite value
        scale = jnp.where(scale > 0, scale, 1.0)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"unknown operand precision {operand!r}")


def matmul(spec, a, b, operand):
    return jnp.einsum(spec, round_operand(a, operand),
                      round_operand(b, operand), precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def position_streams(positions, seq):
    """[3, S] float32: the three streams, or the token's index thrice."""
    if positions is None:
        return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.float32), (3, seq))
    return jnp.asarray(positions, jnp.float32)


def rotary(x, angles):
    """x [b, h, s, d] by angles [s, d/2]: pairs (x_i, x_{i + d/2})."""
    d = x.shape[3]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def mrope_angles(streams, d, theta, sections):
    """[s, d/2]: pair i turns by its section's stream."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    stream = np.repeat(np.arange(len(sections)), sections)
    return streams[stream].T * inv_freq[None, :]


def index_operands(hd, p, streams, theta, eps):
    """(qI [b, Hi, s, Di], kI [b, s, Di], wI [b, s, Hi])."""
    di = p["w_ik"].shape[1]
    heads = p["w_iw"].shape[1]
    b, s, _ = hd.shape
    q = jnp.einsum("bse,ef->bsf", hd, p["w_iq"], precision=HIGHEST)
    k = jnp.einsum("bse,ef->bsf", hd, p["w_ik"], precision=HIGHEST)
    w = jnp.einsum("bse,ef->bsf", hd, p["w_iw"], precision=HIGHEST)
    k = layer_norm(k, p["ik_norm_scale"], p["ik_norm_bias"], eps)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, di, 2, dtype=jnp.float32)
                                / di))
    angles = streams[0][:, None] * inv_freq[None, :]
    q = rotary(q.reshape(b, s, heads, di).transpose(0, 2, 1, 3), angles)
    k = rotary(k[:, None], angles)[:, 0]
    return q, k, w * heads ** -0.5


def index_scores(qi, ki, wi):
    """I [b, rows, s] of a block of queries: qI [b, Hi, rows, Di]
    against the one key kI [b, s, Di], weights wI [b, rows, Hi]."""
    dots = jnp.einsum("bhqd,bkd->bhqk", qi, ki, precision=HIGHEST)
    return jnp.einsum("bhqk,bqh->bqk", jnp.maximum(dots, 0.0), wi,
                      precision=HIGHEST) * ki.shape[-1] ** -0.5


def select(scores, start, topk):
    """kept [b, rows, s] bool for the queries start.. of one block:
    `lax.top_k` of the causal scores, min(t + 1, topk) a row."""
    b, rows, s = scores.shape
    t = start + jnp.arange(rows)[:, None]
    causal = jnp.arange(s)[None, :] <= t
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                           min(topk, s))
    kept = jnp.zeros((b, rows, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(rows)[None, :, None],
        idx].set(True)
    return kept & causal


def attention(h, p, *, streams, theta, eps, sections, topk, operand,
              select_all=False, head_sums=False):
    """(the op's output [b, s, e], the KL terms [b, s] of its indexer,
    kept pairs [b]): grouped-query attention over the keys the indexer
    keeps; scores in blocks of queries. ``select_all``: every causal key
    kept. ``head_sums`` (the share test, short sequences): two more
    results, the heads' probabilities summed and NOT normalised
    [b, s, s] and the kept pairs [b, s, s]."""
    q = matmul("bse,hed->bhsd", h, p["wq"], operand)
    k = matmul("bse,hed->bhsd", h, p["wk"], operand)
    v = matmul("bse,hed->bhsd", h, p["wv"], operand)
    q = rms_norm(q, p["q_norm"], eps)
    k = rms_norm(k, p["k_norm"], eps)
    positions, d = q.shape[2], q.shape[3]
    angles = mrope_angles(streams, d, theta, sections)
    q, k = rotary(q, angles), rotary(k, angles)
    rep = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    qi, ki, wi = index_operands(jax.lax.stop_gradient(h), p, streams, theta,
                                eps)

    @jax.checkpoint
    def block(qb, qib, wib, start):
        index = index_scores(qib, ki, wib)
        kept = select(jax.lax.stop_gradient(index), start,
                      positions if select_all else topk)
        scores = matmul("bhqd,bhkd->bhqk", qb, k, operand) / jnp.sqrt(
            jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(kept[:, None], scores, -jnp.inf),
                               -1)
        out = matmul("bhqk,bhkd->bhqd", probs, v, operand)
        summed = jax.lax.stop_gradient(jnp.sum(probs, axis=1))
        target = summed / jnp.sum(summed, axis=-1, keepdims=True)
        logq = jax.nn.log_softmax(jnp.where(kept, index, -jnp.inf), -1)
        terms = jnp.where(kept & (target > 0), target * (
            jnp.log(jnp.maximum(target, 1e-37)) - jnp.where(kept, logq, 0.0)),
            0.0)
        return (out, jnp.sum(terms, axis=-1), jnp.sum(kept, axis=(1, 2))) + (
            (summed, kept) if head_sums else ())

    size = min(QUERY_BLOCK, positions)
    starts = jnp.arange(0, positions, size)

    def blocks_of(x, axis):
        shape = x.shape[:axis] + (-1, size) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    outs, kl, kept, *whole = jax.lax.map(lambda a: block(*a), (
        blocks_of(q, 2), blocks_of(qi, 2), blocks_of(wi, 1), starts))
    out = jnp.moveaxis(outs, 0, 2).reshape(q.shape)
    return (matmul("bhsd,hde->bse", out, p["wo"], operand),
            jnp.moveaxis(kl, 0, 1).reshape(q.shape[0], positions),
            jnp.sum(kept, axis=0)) + tuple(
                jnp.moveaxis(t, 0, 1).reshape(q.shape[0], positions,
                                              positions) for t in whole)


def router_logits(g, w_router):
    """g W_r: float32 at `highest` whatever the operand."""
    return jnp.einsum("bse,en->bsn", g, w_router, precision=HIGHEST)


def route(g, w_router, k):
    top, idx = jax.lax.top_k(jax.nn.softmax(router_logits(g, w_router), -1),
                             k)
    return top / jnp.sum(top, axis=-1, keepdims=True), idx


def experts(g, p, *, k, offset, operand):
    """The held experts' part for the positions g."""
    weights, idx = route(g, p["w_router"], k)

    @jax.checkpoint
    def weighted_expert(g, w_e, gate, up, down):
        hidden = (jax.nn.silu(matmul("bse,ef->bsf", g, gate, operand))
                  * matmul("bse,ef->bsf", g, up, operand))
        return w_e[..., None] * matmul("bsf,fe->bse", hidden, down, operand)

    def add_expert(out, held):
        e, gate, up, down = held
        w_e = jnp.sum(jnp.where(idx == e + offset, weights, 0.0), axis=-1)
        return out + weighted_expert(g, w_e, gate, up, down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(g), (
        jnp.arange(p["w_up"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return out


def attention_kw(kw, seq):
    return dict(streams=position_streams(kw.get("mrope_positions"), seq),
                theta=kw["rope_theta"], eps=kw["eps"],
                sections=kw["mrope_section"], topk=kw["topk"])


def layer(x, w, i, kw, operand):
    h = rms_norm(x, w[f"b{i}_norm"]["scale"], kw["eps"])
    a, kl, kept = attention(h, w[f"b{i}_attn"], operand=operand,
                            **attention_kw(kw, x.shape[1]))
    x = x + a
    g = rms_norm(x, w[f"b{i}_post_norm"]["scale"], kw["eps"])
    return x + experts(g, w[f"b{i}_mixer"], k=kw["num_experts_per_tok"],
                       offset=kw["expert_offset"], operand=operand), kl, kept


LAYER_LEAVES = ("norm", "attn", "post_norm", "mixer")


def hidden_states(w, ids, layers, kw, operand):
    """(the residual stream [b, s, e] after the first `layers` layers,
    their indexers' KL terms [layers, b, s], kept pairs [layers, b])."""
    x = w["embed_tokens"]["kernel"][ids]
    kls, kepts = [], []
    for i in range(layers):
        def run(x, leaves, i=i):
            return layer(x, leaves, i, kw, operand)
        x, kl, kept = jax.checkpoint(run)(
            x, {f"b{i}_{n}": w[f"b{i}_{n}"] for n in LAYER_LEAVES})
        kls.append(kl)
        kepts.append(kept)
    return x, jnp.stack(kls), jnp.stack(kepts)


def forward_and_index_kl(w, ids, *, operand="f32", **kw):
    """ids [b, s] int32 -> (logits [b, s, vocabulary held], the layers'
    KL terms [layers, b, s], kept pairs [layers, b])."""
    x, kl, kept = hidden_states(w, ids, kw["num_hidden_layers"], kw, operand)
    x = rms_norm(x, w["final_ln"]["scale"], kw["eps"])
    return matmul("bse,ev->bsv", x, w["lm_head"]["kernel"], operand), kl, kept


# the per-sample sums of the KL terms of the last `forward`: the traced
# value of the trace that made them, and the last concrete ones
_index_kl = {"traced": None, "kept": []}


def _keep(kl):
    _index_kl["kept"] = (_index_kl["kept"] + list(np.asarray(kl)))[-64:]


def forward(w, ids, *, operand="f32", **kw):
    """The logits; the indexers' loss terms are kept for `sample_losses`
    (module docstring)."""
    logits, kl, _ = forward_and_index_kl(w, ids, operand=operand, **kw)
    per_sample = jnp.sum(kl, axis=(0, 2))
    _index_kl["traced"] = per_sample
    jax.debug.callback(_keep, per_sample)
    return logits


def sample_losses(pred, y):
    """Per-sample sums over the positions of the next token's
    cross-entropy plus the layers' KL terms; y [b, s, 2] holds (the
    target, its weight 1). The loss is their total over the number of
    positions."""
    logp = jax.nn.log_softmax(pred, axis=-1)
    ids = y[..., 0].astype(jnp.int32)
    tok = jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
    nll = -jnp.sum(y[..., 1] * tok, axis=-1)
    if isinstance(pred, jax.core.Tracer):   # one trace with `forward`
        return nll + _index_kl["traced"]
    kept = _index_kl["kept"][-pred.shape[0]:]
    return nll + jnp.asarray(kept, jnp.float32)


def loss_denominator(y):
    return y.shape[0] * y.shape[1]


def kept_pairs(w, ids, i, **kw):
    """kept [b, s, s] bool of layer `i` (for short sequences)."""
    x = hidden_states(w, ids, i, kw, "f32")[0] if i else w[
        "embed_tokens"]["kernel"][ids]
    h = rms_norm(x, w[f"b{i}_norm"]["scale"], kw["eps"])
    a = attention_kw(kw, x.shape[1])
    qi, ki, wi = index_operands(h, w[f"b{i}_attn"], a["streams"],
                                a["theta"], a["eps"])
    index = index_scores(qi, ki, wi)
    return select(index, 0, a["topk"]), index
