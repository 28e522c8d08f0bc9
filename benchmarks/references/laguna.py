"""Plain reference of the `laguna` family: forward, loss; gradients by
`jax.grad`, Adam in `common.py`.

The architecture is Laguna-XS.2 as its public `config.json` gives it: an
embedding, layers whose attention is full or windowed by `layer_types`
and whose feed-forward is dense or sparse by `mlp_layer_types`, a final
RMSNorm and an untied head. What the config does not state (the gate's
form, the router's scoring, YaRN's truncation and pairing) is the
family's convention and stands under `assumed` in the configuration's
file. Written out here from the arithmetic alone, in float32 `jax.numpy`
under matmul precision `highest`; it shares no code with `flexflow_tpu`.

Layer i (x is the residual stream, S tokens):
    h    = RMSNorm(x)
    q, k, v = h W_q, h W_k, h W_v      H_i query heads (the layer's own
                                        count: W_q's leading axis), 8
                                        key/value heads, d lanes a head;
                                        key/value head n // (H_i / 8)
                                        serves query head n
    sliding_attention: rotary over all d lanes, theta_s, plain
        frequencies; query i sees key j iff 0 <= i - j < window
    full_attention: rotary over the FIRST r = d * partial_rotary_factor
        lanes of every head, the rest pass; YaRN frequencies over those
        r lanes (below), cos and sin times attention_factor; j <= i
    rotary pairs lanes (j, j + r/2) of the rotated part (rotate_half)
    o    = softmax(q k^T / sqrt(d) over the visible j) v
    a    = softplus(h W_g)             float32 whatever `operand` is: one
                                        scalar a head and position
    x'   = x + concat_n(a_n o_n) W_o
    g    = RMSNorm(x')
    dense:   x'' = x' + (silu(g G) * (g U)) W_down   (the leaf
             `gate_up_proj` is [G ; U] side by side)
    sparse:  s = sigmoid(g W_r)        float32 whatever `operand` is
             T = the k largest of s + b;  w_j = s_j / (sum over T + 1e-20) * c
             x'' = x' + sum_{j in T, j held} w_j (silu(g G_j) * (g U_j)) D_j
                      + (silu(g G_s) * (g U_s)) D_s     the shared expert
Head:  logits = RMSNorm(x_L) W_head.
Loss: mean over ALL S positions of the cross-entropy of logits[:, t]
against labels[:, t] (the data file makes labels the next token, the
last position's too: a sample is S + 1 ids).

YaRN over r rotated lanes, theta, factor, original context L0, beta_fast,
beta_slow (the `transformers` library's form):
    f_j   = theta^(-2j/r), j = 0..r/2-1
    c(b)  = r ln(L0 / (2 pi b)) / (2 ln theta)
    low   = max(floor(c(beta_fast)), 0);  high = min(ceil(c(beta_slow)), r-1)
    m_j   = 1 - clip((j - low) / (high - low), 0, 1)
    inv_freq_j = (f_j / factor)(1 - m_j) + f_j m_j

A slot routed to an expert that is not held contributes nothing, here as
in the program: the chips that hold it add that part. The experts are a
scan over the held ones, each over all positions, weighted by w (zero
where not chosen), every expert under `jax.checkpoint` and every layer
too; scores are formed in blocks of QUERY_BLOCK queries, one after the
other (`lax.map`), each under `jax.checkpoint`, so that 64 heads of
S x S scores never exist. None changes the arithmetic.

`operand` rounds the operands of every matrix multiplication that the
configuration states in bfloat16 (not the router's nor the gate's, stated
float32): `"f32"` not at all (the reference), `"bf16"` to bfloat16,
`"fp8"` to float8_e4m3 with one scale a tensor (the control).
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256


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


def inverse_frequencies(r, rope):
    """(inv_freq [r/2], attention_factor) for `rope`, a dict of one
    attention kind's `rope_parameters`."""
    theta = float(rope["rope_theta"])
    f = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    if rope.get("rope_type", "default") == "default":
        return f, 1.0
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def c(rotations):
        return (r * math.log(original / (2 * math.pi * rotations))
                / (2 * math.log(theta)))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), r - 1)
    m = 1.0 - jnp.clip((jnp.arange(r // 2, dtype=jnp.float32) - low)
                       / (high - low), 0.0, 1.0)
    return (f / factor) * (1.0 - m) + f * m, float(rope["attention_factor"])


def rotary(x, rope):
    """x [b, h, s, d]: position t turns the pairs (x_j, x_{j + r/2}) of
    the first r = d * partial_rotary_factor lanes by t * inv_freq_j, cos
    and sin times the attention factor; the other lanes pass."""
    s, d = x.shape[2], x.shape[3]
    r = int(d * rope.get("partial_rotary_factor", 1))
    inv_freq, scale = inverse_frequencies(r, rope)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def gate_values(h, w_gate):
    """softplus(h W_g) [b, s, H]: float32 at `highest` whatever the
    operand."""
    return jax.nn.softplus(jnp.einsum("bse,en->bsn", h, w_gate,
                                      precision=HIGHEST))


def attention(h, p, *, rope, window, operand):
    """Causal grouped-query attention with the per-head output gate,
    under a sliding window if `window`; scores in blocks of queries."""
    q = rotary(matmul("bse,hed->bhsd", h, p["wq"], operand), rope)
    k = rotary(matmul("bse,hed->bhsd", h, p["wk"], operand), rope)
    v = matmul("bse,hed->bhsd", h, p["wv"], operand)
    rep = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    positions, d = q.shape[2], q.shape[3]

    @jax.checkpoint
    def block(qb, start):
        scores = matmul("bhqd,bhkd->bhqk", qb, k, operand) / jnp.sqrt(
            jnp.float32(d))
        i = start + jnp.arange(qb.shape[2])[:, None]
        j = jnp.arange(positions)[None, :]
        seen = j <= i
        if window:
            seen = seen & (i - j < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        return matmul("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v,
                      operand)

    size = min(QUERY_BLOCK, positions)
    starts = jnp.arange(0, positions, size)
    blocks = jnp.moveaxis(q.reshape(q.shape[:2] + (-1, size, d)), 2, 0)
    outs = jax.lax.map(lambda a: block(*a), (blocks, starts))
    out = jnp.moveaxis(outs, 0, 2).reshape(q.shape)
    if "w_gate" in p:
        a = gate_values(h, p["w_gate"])                     # [b, s, H]
        out = out * jnp.moveaxis(a, 2, 1)[..., None]
    return matmul("bhsd,hde->bse", out, p["wo"], operand)


def swiglu(g, gate, up, down, operand):
    hidden = (jax.nn.silu(matmul("bse,ef->bsf", g, gate, operand))
              * matmul("bse,ef->bsf", g, up, operand))
    return matmul("bsf,fe->bse", hidden, down, operand)


def router_scores(g, w_router):
    """sigmoid(g W_r): float32 at `highest` whatever the operand."""
    return jax.nn.sigmoid(jnp.einsum("bse,en->bsn", g, w_router,
                                     precision=HIGHEST))


def route(g, p, k, scaling):
    """(weights [.., k], experts [.., k]): the k largest of s + b, their
    weights s_j / (sum of the k + 1e-20) * scaling."""
    s = router_scores(g, p["w_router"])
    _, idx = jax.lax.top_k(s + p["e_bias"], k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    return top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * scaling, idx


def shared_expert(g, p, operand):
    return swiglu(g, p["ws_gate"], p["ws_up"], p["ws_down"], operand)


def routed_experts_part(g, p, *, k, scaling, offset, operand):
    """What the held experts (those of `p`, the published experts from
    `offset` on) add for the positions g, weighted on their output."""
    weights, idx = route(g, p, k, scaling)

    @jax.checkpoint
    def weighted_expert(g, w_e, gate, up, down):
        return w_e[..., None] * swiglu(g, gate, up, down, operand)

    def add_expert(out, held):
        e, gate, up, down = held
        w_e = jnp.sum(jnp.where(idx == e + offset, weights, 0.0), axis=-1)
        return out + weighted_expert(g, w_e, gate, up, down), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(g),
        (jnp.arange(p["w_up"].shape[0]), p["w_gate"], p["w_up"],
         p["w_down"]))
    return out


def experts(g, p, **kw):
    """The held experts' part and the shared expert."""
    return routed_experts_part(g, p, **kw) + shared_expert(g, p,
                                                           kw["operand"])


LAYER_LEAVES = ("norm", "attn", "post_norm", "mixer", "gate_up_proj",
                "down_proj")


def rope_of(kw, i):
    """(layer i's rotary parameters as a dict, its window or 0)."""
    kind = kw["layer_types"][i]
    windowed = kind == "sliding_attention"
    return (dict(kw["rope_sliding" if windowed else "rope_full"]),
            kw["sliding_window"] if windowed else 0)


def attended(x, w, i, kw, operand):
    """(x', g): the stream after layer i's attention, and its norm, which
    the feed-forward and the router read."""
    rope, window = rope_of(kw, i)
    h = rms_norm(x, w[f"b{i}_norm"]["scale"], kw["eps"])
    x = x + attention(h, w[f"b{i}_attn"], rope=rope, window=window,
                      operand=operand)
    return x, rms_norm(x, w[f"b{i}_post_norm"]["scale"], kw["eps"])


def layer(x, w, i, kw, operand):
    """One decoder layer from the leaves `b<i>_*`: the dense kind where it
    has `b<i>_gate_up_proj`, else the sparse kind."""
    x, g = attended(x, w, i, kw, operand)
    if f"b{i}_gate_up_proj" in w:
        gate, up = jnp.split(w[f"b{i}_gate_up_proj"]["kernel"], 2, axis=1)
        return x + swiglu(g, gate, up, w[f"b{i}_down_proj"]["kernel"],
                          operand)
    return x + experts(g, w[f"b{i}_mixer"], k=kw["num_experts_per_tok"],
                       scaling=kw["routed_scaling_factor"],
                       offset=kw["expert_offset"], operand=operand)


def hidden_states(w, ids, layers, kw, operand):
    """The residual stream after the first `layers` layers."""
    x = w["embed_tokens"]["kernel"][ids]
    for i in range(layers):
        leaves = {f"b{i}_{n}": w[f"b{i}_{n}"] for n in LAYER_LEAVES
                  if f"b{i}_{n}" in w}
        x = jax.checkpoint(
            lambda x, leaves, i=i: layer(x, leaves, i, kw, operand))(
                x, leaves)
    return x


def forward(w, ids, *, operand="f32", **kw):
    """ids [b, S] int32 -> logits [b, S, vocabulary held]."""
    x = hidden_states(w, ids, kw["num_hidden_layers"], kw, operand)
    x = rms_norm(x, w["final_ln"]["scale"], kw["eps"])
    return matmul("bse,ev->bsv", x, w["lm_head"]["kernel"], operand)


def routed_experts(w, ids, i, **kw):
    """The experts [b, S, k] that layer `i` chooses."""
    x = hidden_states(w, ids, i, kw, "f32")
    _, g = attended(x, w, i, kw, "f32")
    return route(g, w[f"b{i}_mixer"], kw["num_experts_per_tok"],
                 kw["routed_scaling_factor"])[1]


def sample_losses(pred, y):
    """Per-sample sums of the positions' cross-entropy; the loss is their
    total over the number of positions (`loss_denominator`)."""
    logp = jax.nn.log_softmax(pred, axis=-1)
    tok = jnp.take_along_axis(logp, y.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.sum(tok[..., 0], axis=-1)


def loss_denominator(y):
    return y.size
