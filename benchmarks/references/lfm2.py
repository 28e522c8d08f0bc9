"""Plain reference of the `lfm2` family: forward, loss; gradients by
`jax.grad`, Adam in `common.py`.

The architecture is LFM2-8B-A1B as its public `config.json` gives it: an
embedding, layers whose mixer is a gated short convolution or causal
grouped-query attention by `layer_types` and whose feed-forward is a
SwiGLU MLP in the first `num_dense_layers` layers and sparse experts
after, a final RMSNorm and a head that reads the embedding's table.
What the config does not state (the tie, the norm of the heads, the
rotary's pairing, the router's epsilon) is the family's convention and
stands under `assumed` in the configuration's file. Written out here
from the arithmetic alone, in float32 `jax.numpy` under matmul precision
`highest`; it shares no code with `flexflow_tpu`.

Layer i (x is the residual stream, S tokens; norm = RMSNorm with a
learned scale, eps `norm_eps`):
    h    = norm(x)                                     operator_norm
    conv:            [B ; C ; u] = h W_in              E -> 3 E, no bias
                     z   = B * u
                     c_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t
                           (a tap a lane; z_{-1} = z_{-2} = 0: three
                           shifted products of the zero-padded sample)
                     x'  = x + (C * c) W_out           no activation
    full_attention:  q, k, v = h W_q, h W_k, h W_v     32 query and 8
                           key/value heads of d = 64 lanes; key/value
                           head n // 4 serves query head n
                     q, k <- every head RMS-normed over its d lanes,
                           scales q_norm / k_norm of d (shared by heads)
                     rotary over all d lanes, pairs (j, j + d/2), theta
                     o   = softmax(q k^T / sqrt(d) over j <= i) v
                     x'  = x + concat_n(o_n) W_o       no bias
    g    = norm(x')                                    ffn_norm
    dense:   x'' = x' + (silu(g G) * (g U)) W_down     (the leaf
             `gate_up_proj` is [G ; U] side by side)
    sparse:  s = sigmoid(g W_r)        float32 whatever `operand` is
             T = the k largest of s + b;  w_j = s_j / (sum over T + 1e-20) * c
             x'' = x' + sum_{j in T, j held} w_j (silu(g G_j) * (g U_j)) D_j
             (no shared expert)
Head:  logits = norm(x_L) E^T with E the embedding's table: ONE leaf,
read twice, so `jax.grad` sums the head's dW and the gather's
scatter-add.
Loss: mean over ALL S positions of the cross-entropy of logits[:, t]
against labels[:, t] (the data file makes labels the next token, the
last position's too: a sample is S + 1 ids).

A slot routed to an expert that is not held contributes nothing, here as
in the program: the chips that hold it add that part. The experts are a
scan over the held ones, each over all positions, weighted by w (zero
where not chosen), every expert under `jax.checkpoint` and every layer
too; scores are formed in blocks of QUERY_BLOCK queries, one after the
other (`lax.map`), each under `jax.checkpoint`, so that 32 heads of
S x S scores never exist. None changes the arithmetic.

`operand` rounds the operands of every matrix multiplication that the
configuration states in bfloat16 (not the router's, stated float32):
`"f32"` not at all (the reference), `"bf16"` to bfloat16, `"fp8"` to
float8_e4m3 with one scale a tensor (the control).
"""

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


def rotary(x, theta):
    """x [b, h, s, d]: position t turns the pairs (x_j, x_{j + d/2}) by
    t * theta^(-2j/d)."""
    s, d = x.shape[2], x.shape[3]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def short_conv(h, p, operand):
    """The gated short convolution: three shifted products of the
    zero-padded sample between the gates B and C."""
    b, c, u = jnp.split(matmul("bse,ef->bsf", h, p["w_in"], operand), 3,
                        axis=-1)
    z = b * u
    taps, positions = p["conv_w"].shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(p["conv_w"][j] * padded[:, j:j + positions]
               for j in range(taps))
    return matmul("bsf,fe->bse", c * conv, p["w_out"], operand)


def attention(h, p, *, theta, eps, operand):
    """Causal grouped-query attention with a norm of every query and key
    head ahead of rotary; scores in blocks of queries."""
    q = matmul("bse,hed->bhsd", h, p["wq"], operand)
    k = matmul("bse,hed->bhsd", h, p["wk"], operand)
    v = matmul("bse,hed->bhsd", h, p["wv"], operand)
    q = rotary(rms_norm(q, p["q_norm"], eps), theta)
    k = rotary(rms_norm(k, p["k_norm"], eps), theta)
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
        scores = jnp.where(j <= i, scores, -jnp.inf)
        return matmul("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v,
                      operand)

    size = min(QUERY_BLOCK, positions)
    starts = jnp.arange(0, positions, size)
    blocks = jnp.moveaxis(q.reshape(q.shape[:2] + (-1, size, d)), 2, 0)
    outs = jax.lax.map(lambda a: block(*a), (blocks, starts))
    out = jnp.moveaxis(outs, 0, 2).reshape(q.shape)
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


def experts(g, p, *, k, scaling, offset, operand):
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


LAYER_LEAVES = ("norm", "conv", "attn", "post_norm", "mixer",
                "gate_up_proj", "down_proj")


def mixed(x, w, i, kw, operand):
    """(x', g): the stream after layer i's mixer, and its norm, which
    the feed-forward and the router read."""
    h = rms_norm(x, w[f"b{i}_norm"]["scale"], kw["eps"])
    if kw["layer_types"][i] == "conv":
        x = x + short_conv(h, w[f"b{i}_conv"], operand)
    else:
        x = x + attention(h, w[f"b{i}_attn"], theta=kw["rope_theta"],
                          eps=kw["eps"], operand=operand)
    return x, rms_norm(x, w[f"b{i}_post_norm"]["scale"], kw["eps"])


def layer(x, w, i, kw, operand):
    """One decoder layer from the leaves `b<i>_*`: the dense kind where it
    has `b<i>_gate_up_proj`, else the sparse kind."""
    x, g = mixed(x, w, i, kw, operand)
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
    """ids [b, S] int32 -> logits [b, S, vocabulary held], through the
    table the ids were gathered from."""
    x = hidden_states(w, ids, kw["num_hidden_layers"], kw, operand)
    x = rms_norm(x, w["final_ln"]["scale"], kw["eps"])
    return matmul("bse,ve->bsv", x, w["embed_tokens"]["kernel"], operand)


def routed_experts(w, ids, i, **kw):
    """The experts [b, S, k] that layer `i` chooses."""
    x = hidden_states(w, ids, i, kw, "f32")
    _, g = mixed(x, w, i, kw, "f32")
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
