"""Plain reference of the `xing4` family: forward, loss; gradients by
`jax.grad`, Adam in `common.py`.

The architecture is Xing4.0-29B-A4B as its public `config.json` gives it
(`model_type` xing4_0): DeepSeek-V3's decoder (latent attention with a
query latent, leading dense layers, sigmoid top-k experts with a
score-correction bias and one shared expert, one multi-token-prediction
module) with YaRN on the rotated lanes in DeepSeek-V2's form, and with
every residual connection replaced by a manifold-constrained
hyper-connection ("mHC: Manifold-Constrained Hyper-Connections",
DeepSeek-AI, arXiv:2512.24880; the keys `hc_mult`, `hc_sinkhorn_iters`,
`hc_eps`, `mhc_h_res_clamp_min` / `_max`). Written out here from the
arithmetic alone, in float32 `jax.numpy` under matmul precision
`highest`; it shares no code with `flexflow_tpu`.

Streams. X_0 = [e; e; ...; e], n = hc_mult copies of the token's
embedding row, [S, n, C]. After the last layer x_L = sum_i X_L[i];
z = RMSNorm(x_L) W_head.

One sublayer (two a layer: latent attention, then the feed-forward) with
its own leaves phi_pre, phi_post [nC, n], phi_res [nC, n^2], b_pre,
b_post [n], b_res [n, n], alpha [3], all float32 whatever `operand` is
(the configuration states the maps in float32), F the branch with its
pre-norm:
    x      = vec(X)                               in R^{nC}
    r      = (mean(x^2) + hc_eps)^-1/2            no learned scale
    H_pre  = sigmoid(alpha_0 r (x phi_pre) + b_pre)
    H_post = 2 sigmoid(alpha_1 r (x phi_post) + b_post)
    M_0    = exp(clip(alpha_2 r mat(x phi_res) + b_res, lo, hi))
    M_t    = rows(cols(M_{t-1})), t = 1..hc_sinkhorn_iters, with
             cols(M) = M / (sum_i M[i, j] + hc_eps),
             rows(M) = M / (sum_j M[i, j] + hc_eps)
    H_res  = the last M
    h      = sum_i H_pre[i] X[i];   y = F(h)
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y

Latent attention (H heads held, a head's query and key [D not rotated ;
R rotated], values D wide), h the sublayer's input after its norm:
    c_q  = RMSNorm(h W_qa);            q_n, q_r = c_q W_qb
    [c_kv ; k_r] = h W_kva;            c_kv = RMSNorm(c_kv)
    k_n, v = c_kv W_kvb;               k_r ONE vector a position
    rotary over the ADJACENT pairs (2j, 2j+1) of the R lanes at YaRN's
    frequencies: f_j = theta^(-2j/R); d(b) = R ln(original / (2 pi b)) /
    (2 ln theta); low = floor(d(beta_fast)), high = ceil(d(beta_slow))
    (held to [0, R - 1]); g_j = clip((j - low) / (high - low), 0, 1);
    inv_freq_j = f_j / factor * g_j + f_j * (1 - g_j); with m(s) = 0.1 s
    ln(factor) + 1, cos and sin times m(mscale) / m(mscale_all_dim)
    a    = softmax(q k^T (D + R)^-1/2 m(mscale_all_dim)^2 over j <= i) v W_o
`rope_scaling` None is plain theta and the plain scale.

Feed-forward: a layer with `<prefix>_gate_up_proj` is dense,
(silu(g G) * (g U)) W_down; else, with g the branch's normed input,
    s = sigmoid(g W_r)                  float32 whatever `operand` is
    T = the k largest of s + b;  w_j = s_j / (sum over T + 1e-20) * c
    sum_{j in T, j held} w_j (silu(g G_j) * (g U_j)) D_j
        + (silu(g G_s) * (g U_s)) D_s                the shared expert
A slot routed to an expert that is not held contributes nothing.

Multi-token-prediction module (depth 1), e the embedding:
    u_i  = [RMSNorm_e(e(t_{i+1})) ; RMSNorm_h(x_L,i)] W_eh
    U_0  = [u; u; ...; u];  one expert layer with hyper-connections of
    its own;  z'_i = RMSNorm_s(sum_i U_1[i]) W_head   (the SAME e, W_head)
`forward` returns both halves laid end to end, [b, 2S, V];
    loss = mean_{i < S-1} ce(z_i, t_{i+1}) + LOSS_WEIGHT * mean_{i < S-2}
           ce(z'_i, t_{i+2})
The labels [b, 2S, 2] carry the targets' ids at [..., 0]; which rows
have a target and what the module's loss weighs follow from the
objective, here.

Every layer runs under `jax.checkpoint` (the gradient is formed a block
at a time), every held expert too; scores are formed in blocks of
QUERY_BLOCK queries, one after the other. None changes the arithmetic.

`operand` rounds the operands of every matrix multiplication that the
configuration states in bfloat16 (not the router's nor the
hyper-connections', stated float32): `"f32"` not at all (the
reference), `"bf16"`, `"fp8"` (float8_e4m3, one scale a tensor: the
control).
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
# the weight of the module's loss beside the main model's; the family
# sets it from the configuration (`mtp_loss_weight`)
LOSS_WEIGHT = 0.3


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


# ---------------------------------------------------------------------------
# hyper-connections


def sinkhorn(m, iters, eps):
    """m [.., n, n] positive -> doubly stochastic: a column step, then a
    row step, `iters` times, eps in every division."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def hyper_connection_maps(x, p, kw):
    """(H_pre [b, s, n], H_post [b, s, n], H_res [b, s, n, n]) of the
    streams x [b, s, n, C], float32."""
    b, s, n, c = x.shape
    flat = x.reshape(b, s, n * c)
    r = jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                      + kw["hc_eps"])

    def products(phi):
        return jnp.einsum("bsk,km->bsm", flat, phi, precision=HIGHEST)

    alpha = p["alpha"]
    h_pre = jax.nn.sigmoid(alpha[0] * r * products(p["phi_pre"])
                           + p["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * r * products(p["phi_post"])
                                  + p["b_post"])
    logits = (alpha[2] * r * products(p["phi_res"])).reshape(
        b, s, n, n) + p["b_res"]
    m = jnp.exp(jnp.clip(logits, kw["hc_clamp_min"], kw["hc_clamp_max"]))
    return h_pre, h_post, sinkhorn(m, kw["hc_sinkhorn_iters"], kw["hc_eps"])


def hc_read(x, p, kw):
    """(h [b, s, C], the other two maps) of a sublayer."""
    h_pre, h_post, h_res = hyper_connection_maps(x, p, kw)
    n = x.shape[2]
    return sum(h_pre[..., i, None] * x[:, :, i] for i in range(n)), (
        h_post, h_res)


def hc_write(x, y, maps):
    """X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y, as float32
    multiply-adds (no product's rounding)."""
    h_post, h_res = maps
    n = x.shape[2]
    return jnp.stack([
        sum(h_res[..., i, j, None] * x[:, :, j] for j in range(n))
        + h_post[..., i, None] * y for i in range(n)], axis=2)


def as_streams(x, kw):
    return jnp.repeat(x[:, :, None, :], kw["hc_mult"], axis=2)


# ---------------------------------------------------------------------------
# latent attention


def yarn(rope_dim, theta, scaling):
    """(inv_freq [R/2], factor of cos and sin, factor of the softmax
    scale); `scaling` None: plain."""
    f = 1.0 / (theta ** (jnp.arange(0, rope_dim, 2, dtype=jnp.float32)
                         / rope_dim))
    if not scaling:
        return f, 1.0, 1.0
    sc = dict(scaling)
    factor, original = float(sc["factor"]), sc[
        "original_max_position_embeddings"]

    def d(rotations):
        return (rope_dim * math.log(original / (2 * math.pi * rotations))
                / (2 * math.log(theta)))

    low = max(math.floor(d(sc["beta_fast"])), 0)
    high = min(math.ceil(d(sc["beta_slow"])), rope_dim - 1)
    g = jnp.clip((jnp.arange(rope_dim // 2, dtype=jnp.float32) - low)
                 / max(high - low, 0.001), 0.0, 1.0)

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0

    all_dim = sc.get("mscale_all_dim", 0) or 0
    return (f / factor * g + f * (1.0 - g), m(sc.get("mscale", 1)) / m(all_dim),
            m(all_dim) ** 2 if all_dim else 1.0)


def rotary(x, inv_freq, factor):
    """x [b, h, s, r]: the row at position i turns the adjacent pairs
    (x_2j, x_2j+1) by i * inv_freq_j; cos and sin times `factor`."""
    s = x.shape[2]
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def latent_attention(h, p, *, kw, operand):
    """Causal attention whose queries and keys/values come out of the two
    latents; scores in blocks of queries."""
    eps = kw["eps"]
    c_q = rms_norm(matmul("bse,er->bsr", h, p["wq_a"], operand),
                   p["q_a_norm"], eps)
    q_nope = matmul("bsr,hrd->bhsd", c_q, p["wq_b_nope"], operand)
    q_rope = matmul("bsr,hrd->bhsd", c_q, p["wq_b_rope"], operand)
    kv = matmul("bse,er->bsr", h, p["wkv_a"], operand)
    rank = p["kv_a_norm"].shape[0]
    c_kv = rms_norm(kv[..., :rank], p["kv_a_norm"], eps)
    k_nope = matmul("bsr,hrd->bhsd", c_kv, p["wkv_b_k"], operand)
    v = matmul("bsr,hrd->bhsd", c_kv, p["wkv_b_v"], operand)
    inv_freq, of_tables, of_scores = yarn(q_rope.shape[-1], kw["rope_theta"],
                                          kw["rope_scaling"])
    k_rope = rotary(kv[:, None, :, rank:], inv_freq, of_tables)
    q = jnp.concatenate([q_nope, rotary(q_rope, inv_freq, of_tables)],
                        axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, k_nope.shape[:3] + k_rope.shape[3:])], axis=-1)
    positions, width = q.shape[2], q.shape[3]
    scale = of_scores / math.sqrt(width)

    @jax.checkpoint
    def block(qb, start):
        scores = matmul("bhqd,bhkd->bhqk", qb, k, operand) * scale
        i = start + jnp.arange(qb.shape[2])[:, None]
        j = jnp.arange(positions)[None, :]
        scores = jnp.where(j <= i, scores, -jnp.inf)
        return matmul("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v,
                      operand)

    size = min(QUERY_BLOCK, positions)
    starts = jnp.arange(0, positions, size)
    blocks = jnp.moveaxis(q.reshape(q.shape[:2] + (-1, size, width)), 2, 0)
    outs = jax.lax.map(lambda a: block(*a), (blocks, starts))
    out = jnp.moveaxis(outs, 0, 2).reshape(v.shape)
    return matmul("bhsd,hde->bse", out, p["wo"], operand)


# ---------------------------------------------------------------------------
# feed-forward


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
    """The held experts' part and the shared expert for the positions g."""
    weights, idx = route(g, p, k, scaling)

    @jax.checkpoint
    def weighted_expert(g, w_e, gate, up, down):
        return w_e[..., None] * swiglu(g, gate, up, down, operand)

    def add_expert(out, held):
        e, gate, up, down = held
        w_e = jnp.sum(jnp.where(idx == e + offset, weights, 0.0), axis=-1)
        return out + weighted_expert(g, w_e, gate, up, down), None

    out, _ = jax.lax.scan(
        add_expert,
        swiglu(g, p["ws_gate"], p["ws_up"], p["ws_down"], operand),
        (jnp.arange(p["w_up"].shape[0]), p["w_gate"], p["w_up"],
         p["w_down"]))
    return out


# ---------------------------------------------------------------------------
# layers and the model

LAYER_LEAVES = ("hc_attn", "norm", "attn", "hc_ffn", "post_norm", "mixer",
                "gate_up_proj", "down_proj")


def attended(x, w, prefix, kw, operand):
    """(X', g, maps): the streams after the layer's attention sublayer,
    the feed-forward's normed input (which the router reads), and the
    feed-forward sublayer's write maps."""
    h, maps = hc_read(x, w[f"{prefix}_hc_attn"], kw)
    h = rms_norm(h, w[f"{prefix}_norm"]["scale"], kw["eps"])
    x = hc_write(x, latent_attention(h, w[f"{prefix}_attn"], kw=kw,
                                     operand=operand), maps)
    g, maps = hc_read(x, w[f"{prefix}_hc_ffn"], kw)
    return x, rms_norm(g, w[f"{prefix}_post_norm"]["scale"], kw["eps"]), maps


def layer(x, w, prefix, kw, operand):
    """One decoder layer over the streams x [b, s, n, C] from the leaves
    `<prefix>_*`: the dense kind where it has `<prefix>_gate_up_proj`,
    else the expert kind."""
    x, g, maps = attended(x, w, prefix, kw, operand)
    if f"{prefix}_gate_up_proj" in w:
        gate, up = jnp.split(w[f"{prefix}_gate_up_proj"]["kernel"], 2, axis=1)
        y = swiglu(g, gate, up, w[f"{prefix}_down_proj"]["kernel"], operand)
    else:
        y = experts(g, w[f"{prefix}_mixer"], k=kw["num_experts_per_tok"],
                    scaling=kw["routed_scaling_factor"],
                    offset=kw["expert_offset"], operand=operand)
    return hc_write(x, y, maps)


def checkpointed_layer(x, w, prefix, kw, operand):
    leaves = {f"{prefix}_{n}": w[f"{prefix}_{n}"] for n in LAYER_LEAVES
              if f"{prefix}_{n}" in w}
    return jax.checkpoint(
        lambda x, leaves: layer(x, leaves, prefix, kw, operand))(x, leaves)


def hidden_states(w, ids, layers, kw, operand):
    """(the embedding [b, S, C], the streams [b, S, n, C] after the first
    `layers` layers)."""
    embedded = w["embed_tokens"]["kernel"][ids]
    x = as_streams(embedded, kw)
    for i in range(layers):
        x = checkpointed_layer(x, w, f"b{i}", kw, operand)
    return embedded, x


def mtp_input(w, embedded, x_last, kw, operand):
    """u [b, S, C]: what the module's layer reads, n times; x_last is the
    SUM of the main model's streams."""
    e_next = jnp.roll(embedded, -1, axis=1)      # row i reads e(t_{i+1})
    u = jnp.concatenate(
        [rms_norm(e_next, w["mtp_enorm"]["scale"], kw["eps"]),
         rms_norm(x_last, w["mtp_hnorm"]["scale"], kw["eps"])], axis=-1)
    return matmul("bsc,ce->bse", u, w["mtp_eh_proj"]["kernel"], operand)


def mtp_hidden(w, embedded, x_last, kw, operand):
    """The module's hidden states ahead of the shared head."""
    u = as_streams(mtp_input(w, embedded, x_last, kw, operand), kw)
    u = checkpointed_layer(u, w, "mtp", kw, operand)
    return rms_norm(jnp.sum(u, axis=2), w["mtp_final_ln"]["scale"], kw["eps"])


def forward(w, ids, *, operand="f32", **kw):
    """ids [b, S] int32 -> logits [b, 2S, vocabulary held]: the main
    model's, then the multi-token-prediction module's."""
    embedded, x = hidden_states(w, ids, kw["num_hidden_layers"], kw, operand)
    x_last = jnp.sum(x, axis=2)
    both = jnp.concatenate(
        [rms_norm(x_last, w["final_ln"]["scale"], kw["eps"]),
         mtp_hidden(w, embedded, x_last, kw, operand)], axis=1)
    return matmul("bse,ev->bsv", both, w["lm_head"]["kernel"], operand)


def routed_experts(w, ids, prefix, **kw):
    """The experts [b, S, k] that the layer `prefix` chooses (`b<i>`, or
    `mtp` for the module's)."""
    layers = (kw["num_hidden_layers"] if prefix == "mtp"
              else int(prefix[1:]))
    embedded, x = hidden_states(w, ids, layers, kw, "f32")
    if prefix == "mtp":
        x = as_streams(mtp_input(w, embedded, jnp.sum(x, axis=2), kw, "f32"),
                       kw)
    _, g, _ = attended(x, w, prefix, kw, "f32")
    return route(g, w[f"{prefix}_mixer"], kw["num_experts_per_tok"],
                 kw["routed_scaling_factor"])[1]


def sample_losses(pred, y):
    """Per-sample loss: mean over the S-1 rows of the first half that
    have a next token of ce(z_i, t_{i+1}), plus LOSS_WEIGHT times the mean
    over the S-2 rows of the second half that have a token after next of
    ce(z'_i, t_{i+2}); y [b, 2S, 2] holds the targets' ids at [..., 0]."""
    logp = jax.nn.log_softmax(pred, axis=-1)
    ids = y[..., 0].astype(jnp.int32)
    nll = -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
    s = pred.shape[1] // 2
    main = jnp.sum(nll[:, :s - 1], axis=1) / (s - 1)
    mtp = jnp.sum(nll[:, s:2 * s - 2], axis=1) / (s - 2)
    return main + LOSS_WEIGHT * mtp


def loss_denominator(y):
    return y.shape[0]
