"""Plain reference of the `smallthinker` family: forward, loss; gradients
by `jax.grad`, Adam in `common.py`.

The architecture is SmallThinker-21BA3B-Instruct as its public
`config.json` and the catalog's `described_as` give it: an embedding,
52 layers that are all alike but for the attention's kind, a final
RMSNorm and an untied head. Written out here from the arithmetic alone,
in float32 `jax.numpy` under matmul precision `highest`; it shares no
code with `flexflow_tpu`.

Layer l (x is the residual stream):
    h   = RMSNorm(x), eps 1e-6, a learned scale
    r   = h W_r                    float32 whatever `operand` is: the
                                   router reads the PRE-attention norm
    T   = the k largest of r;  p_j = exp(r_j) / sum_{i in T} exp(r_i)
    q, k, v = h W_q, h W_k, h W_v  (H query heads, H_kv key/value heads
                                   of d, no bias)
    rope_layout[l] == 1: rotary(q), rotary(k), theta, over the whole
                                   head, half-split (rotate_half) layout
    query i sees key j iff  j <= i  and  (sliding_window_layout[l] == 0
                                   or  i - j < window)
    a   = softmax(q k^T / sqrt(d) over the visible j) v W_o
    x'  = x + a
    g   = RMSNorm(x')
    m   = sum_{j in T, j held} p_j (relu(g G_j) * (g U_j)) D_j
    x'' = x' + m
After the last layer  x <- RMSNorm(x);  logits = x W_head.
Loss: mean over tokens of the cross-entropy of logits[:, t] against
labels[:, t] (the data file makes labels the next token).

A slot routed to an expert that is not held contributes nothing, here as
in the program: the chips that hold it add that part. The experts are a
loop over the held ones, each over all tokens, weighted by p (zero where
not chosen). Scores are formed in blocks of QUERY_BLOCK queries, each
under `jax.checkpoint`, and every layer is under one too: neither changes
the arithmetic, and the backward pass of a 16,384-token full layer (7.5
GB of float32 probabilities otherwise) then keeps one block's.

Departures from the published model, each also in the configuration file:
- "secondary experts" (`described_as`) have no key in the config and are
  not built;
- no auxiliary loss;
- a key at distance exactly `window` is masked (i - j < window);
- the half-split rotary layout is assumed.

`operand` rounds the operands of every matrix multiplication that the
configuration states in bfloat16 (not the router's, stated float32):
`"f32"` not at all (the reference), `"bf16"` to bfloat16, `"fp8"` to
float8_e4m3 with one scale a tensor (the control).
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 1024


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


def rotary(x, theta):
    """x [b, h, s, d]: position t turns the pairs (x_i, x_{i + d/2}) by
    t * theta^(-2i/d)."""
    s, d = x.shape[2], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def router_logits(h, w_router):
    """h W_r: float32 at `highest` whatever the operand."""
    return jnp.einsum("bse,en->bsn", h, w_router, precision=HIGHEST)


def route(h, w_router, k):
    """(p [.., k], experts [.., k]): the k largest logits and the softmax
    over them."""
    top, idx = jax.lax.top_k(router_logits(h, w_router), k)
    return jax.nn.softmax(top, axis=-1), idx


def attention(h, p, *, rope, window, theta, operand):
    """Causal grouped-query attention, with a sliding window if `window`;
    scores in blocks of queries."""
    q = matmul("bse,hed->bhsd", h, p["wq"], operand)
    k = matmul("bse,hed->bhsd", h, p["wk"], operand)
    v = matmul("bse,hed->bhsd", h, p["wv"], operand)
    if rope:
        q, k = rotary(q, theta), rotary(k, theta)
    rep = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    length, d = q.shape[2], q.shape[3]

    def block(qb, k, v, start):
        scores = matmul("bhqd,bhkd->bhqk", qb, k, operand) / jnp.sqrt(
            jnp.float32(d))
        i = start + jnp.arange(qb.shape[2])[:, None]
        j = jnp.arange(length)[None, :]
        seen = j <= i
        if window:
            seen = seen & (i - j < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        return matmul("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v,
                      operand)

    outs = [jax.checkpoint(functools.partial(block, start=start))(
        q[:, :, start:start + QUERY_BLOCK], k, v)
        for start in range(0, length, QUERY_BLOCK)]
    return matmul("bhsd,hde->bse", jnp.concatenate(outs, axis=2), p["wo"],
                  operand)


def experts(g, h, p, *, k, offset, operand):
    """The held experts' part for the tokens g, chosen from h."""
    weights, idx = route(h, p["w_router"], k)
    out = jnp.zeros_like(g)
    for e in range(p["w_up"].shape[0]):            # the experts held
        w_e = jnp.sum(jnp.where(idx == e + offset, weights, 0.0), axis=-1)
        hidden = (jax.nn.relu(matmul("bse,ef->bsf", g, p["w_gate"][e],
                                     operand))
                  * matmul("bse,ef->bsf", g, p["w_up"][e], operand))
        out = out + w_e[..., None] * matmul("bsf,fe->bse", hidden,
                                            p["w_down"][e], operand)
    return out


def layer(x, w, i, kw, operand):
    h = rms_norm(x, w[f"b{i}_norm"]["scale"], kw["eps"])
    windowed = bool(kw["sliding_window_layout"][i])
    x = x + attention(
        h, w[f"b{i}_attn"], rope=bool(kw["rope_layout"][i]),
        window=kw["sliding_window_size"] if windowed else 0,
        theta=kw["rope_theta"], operand=operand)
    g = rms_norm(x, w[f"b{i}_post_norm"]["scale"], kw["eps"])
    return x + experts(g, h, w[f"b{i}_mixer"], k=kw["num_experts_per_tok"],
                       offset=kw["expert_offset"], operand=operand)


LAYER_LEAVES = ("norm", "attn", "post_norm", "mixer")


def hidden_states(w, ids, layers, kw, operand):
    """The residual stream after the first `layers` layers."""
    x = w["embed_tokens"]["kernel"][ids]
    for i in range(layers):
        def run(x, leaves, i=i):
            return layer(x, leaves, i, kw, operand)
        x = jax.checkpoint(run)(
            x, {f"b{i}_{n}": w[f"b{i}_{n}"] for n in LAYER_LEAVES})
    return x


def forward(w, ids, *, operand="f32", **kw):
    """ids [b, s] int32 -> logits [b, s, vocabulary held]."""
    x = hidden_states(w, ids, kw["num_hidden_layers"], kw, operand)
    x = rms_norm(x, w["final_ln"]["scale"], kw["eps"])
    return matmul("bse,ev->bsv", x, w["lm_head"]["kernel"], operand)


def routed_experts(w, ids, i, **kw):
    """The experts [b, s, k] that layer `i` chooses."""
    x = hidden_states(w, ids, i, kw, "f32")
    h = rms_norm(x, w[f"b{i}_norm"]["scale"], kw["eps"])
    return route(h, w[f"b{i}_mixer"]["w_router"],
                 kw["num_experts_per_tok"])[1]


def sample_losses(pred, y):
    """Per-sample sums of the tokens' cross-entropy; the loss is their
    total over the number of tokens."""
    logp = jax.nn.log_softmax(pred, axis=-1)
    tok = jnp.take_along_axis(logp, y.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.sum(tok[..., 0], axis=-1)


def loss_denominator(y):
    return y.size
