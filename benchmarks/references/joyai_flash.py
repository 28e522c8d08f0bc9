"""Plain reference of the `joyai_flash` family: forward, loss; gradients
by `jax.grad`, Adam in `common.py`.

The architecture is JoyAI-LLM-Flash as its public `config.json` gives it:
DeepSeek-V3's decoder key for key (latent attention, a leading dense
layer, sigmoid top-k experts with a score-correction bias and a shared
expert, one multi-token-prediction module), whose equations are those of
the DeepSeek-V2 and DeepSeek-V3 technical reports. Written out here from
the arithmetic alone, in float32 `jax.numpy` under matmul precision
`highest`; it shares no code with `flexflow_tpu`.

Layer l (x is the residual stream, S tokens, H heads):
    h    = RMSNorm(x)
    c_q  = RMSNorm(h W_qa);            q_n, q_r = c_q W_qb   a head: D not
                                        rotated, R rotated lanes
    [c_kv ; k_r] = h W_kva;            c_kv = RMSNorm(c_kv)
    k_n, v = c_kv W_kvb                 a head: D and D lanes
    q_r, k_r = rotary(q_r), rotary(k_r) theta, over the ADJACENT pairs
                                        (2i, 2i+1) of the R lanes
                                        (`rope_interleave`), positions
                                        0..S-1; k_r is ONE vector a
                                        position for all heads
    q = [q_n ; q_r], k = [k_n ; k_r]   assembled per head
    a    = softmax(q k^T / sqrt(D + R) over j <= i) v W_o
    x'   = x + a
    g    = RMSNorm(x')
    l < first_k_dense_replace:  x'' = x' + (silu(g G) * (g U)) W_down
                                (the leaf `gate_up_proj` is [G ; U] side
                                by side, as the program multiplies by it)
    else:
      s    = sigmoid(g W_r)            float32 whatever `operand` is
      T    = the k largest of s + b;   w_j = s_j / (sum over T + 1e-20) * c
      x''  = x' + sum_{j in T, j held} w_j (silu(g G_j) * (g U_j)) D_j
                + (silu(g G_s) * (g U_s)) D_s          the shared expert
Head:  z = RMSNorm(x_L) W_head.
Multi-token-prediction module (depth 1; DeepSeek-V3 report, 2.2), with
e the embedding and x_L the last layer's output before the final norm:
    u_i  = [RMSNorm_e(e(t_{i+1})) ; RMSNorm_h(x_L,i)] W_eh
    z'_i = RMSNorm_s(layer_mtp(u)_i) W_head     an expert layer of its own;
                                                the SAME e and W_head
z_i predicts t_{i+1} and z'_i predicts t_{i+2}. `forward` returns both
laid end to end, [b, 2S, V]. Row S-1 of the module has no next token: it
reads e(t_0) (the shift wraps), carries no target and, attention being
causal, reaches no other row.
    loss = mean_{i < S-1} ce(z_i, t_{i+1}) + LOSS_WEIGHT * mean_{i < S-2}
           ce(z'_i, t_{i+2})
The labels [b, 2S, 2] carry the targets' ids at [..., 0]; which rows
have a target and what the module's loss weighs follow from the
objective, here, and not from the labels' weights at [..., 1] (the
program's loss reads those: a program fed other weights fails the
comparison). `LOSS_WEIGHT` is set by the family from the configuration.

A slot routed to an expert that is not held contributes nothing, here as
in the program: the chips that hold it add that part. The experts are a
scan over the held ones, each over all positions, weighted by w (zero
where not chosen), every expert under `jax.checkpoint` and every layer
too; scores are formed in blocks of QUERY_BLOCK queries, one after the
other (`lax.map`), each under `jax.checkpoint`. None changes the
arithmetic.

`operand` rounds the operands of every matrix multiplication that the
configuration states in bfloat16 (not the router's, stated float32):
`"f32"` not at all (the reference), `"bf16"` to bfloat16, `"fp8"` to
float8_e4m3 with one scale a tensor (the control).
"""

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


def rotary(x, theta):
    """x [b, h, s, r]: the row at position i turns the adjacent pairs
    (x_2j, x_2j+1) by i * theta^(-2j/r)."""
    s, r = x.shape[2], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def latent_attention(h, p, *, theta, eps, operand):
    """Causal attention whose queries and keys/values come out of the two
    latents; scores in blocks of queries."""
    c_q = rms_norm(matmul("bse,er->bsr", h, p["wq_a"], operand),
                   p["q_a_norm"], eps)
    q_nope = matmul("bsr,hrd->bhsd", c_q, p["wq_b_nope"], operand)
    q_rope = matmul("bsr,hrd->bhsd", c_q, p["wq_b_rope"], operand)
    kv = matmul("bse,er->bsr", h, p["wkv_a"], operand)
    rank = p["kv_a_norm"].shape[0]
    c_kv = rms_norm(kv[..., :rank], p["kv_a_norm"], eps)
    k_nope = matmul("bsr,hrd->bhsd", c_kv, p["wkv_b_k"], operand)
    v = matmul("bsr,hrd->bhsd", c_kv, p["wkv_b_v"], operand)
    k_rope = rotary(kv[:, None, :, rank:], theta)        # one a position
    q = jnp.concatenate([q_nope, rotary(q_rope, theta)], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, k_nope.shape[:3] + k_rope.shape[3:])], axis=-1)
    positions, width = q.shape[2], q.shape[3]

    @jax.checkpoint
    def block(qb, start):
        scores = matmul("bhqd,bhkd->bhqk", qb, k, operand) / jnp.sqrt(
            jnp.float32(width))
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


LAYER_LEAVES = ("norm", "attn", "post_norm", "mixer", "gate_up_proj",
                "down_proj")


def attended(x, w, prefix, kw, operand):
    """(x', g): the stream after the layer's attention, and its norm,
    which the feed-forward and the router read."""
    h = rms_norm(x, w[f"{prefix}_norm"]["scale"], kw["eps"])
    x = x + latent_attention(h, w[f"{prefix}_attn"], theta=kw["rope_theta"],
                             eps=kw["eps"], operand=operand)
    return x, rms_norm(x, w[f"{prefix}_post_norm"]["scale"], kw["eps"])


def layer(x, w, prefix, kw, operand):
    """One decoder layer from the leaves `<prefix>_*`: the dense kind
    where it has `<prefix>_gate_up_proj`, else the expert kind."""
    x, g = attended(x, w, prefix, kw, operand)
    if f"{prefix}_gate_up_proj" in w:
        gate, up = jnp.split(w[f"{prefix}_gate_up_proj"]["kernel"], 2, axis=1)
        return x + swiglu(g, gate, up, w[f"{prefix}_down_proj"]["kernel"],
                          operand)
    return x + experts(g, w[f"{prefix}_mixer"], k=kw["num_experts_per_tok"],
                       scaling=kw["routed_scaling_factor"],
                       offset=kw["expert_offset"], operand=operand)


def checkpointed_layer(x, w, prefix, kw, operand):
    leaves = {f"{prefix}_{n}": w[f"{prefix}_{n}"] for n in LAYER_LEAVES
              if f"{prefix}_{n}" in w}
    return jax.checkpoint(
        lambda x, leaves: layer(x, leaves, prefix, kw, operand))(x, leaves)


def hidden_states(w, ids, layers, kw, operand):
    """(the embedding [b, S, e], the residual stream after the first
    `layers` layers)."""
    embedded = x = w["embed_tokens"]["kernel"][ids]
    for i in range(layers):
        x = checkpointed_layer(x, w, f"b{i}", kw, operand)
    return embedded, x


def mtp_input(w, embedded, x_last, kw, operand):
    """u: what the module's layer reads."""
    e_next = jnp.roll(embedded, -1, axis=1)      # row i reads e(t_{i+1})
    u = jnp.concatenate(
        [rms_norm(e_next, w["mtp_enorm"]["scale"], kw["eps"]),
         rms_norm(x_last, w["mtp_hnorm"]["scale"], kw["eps"])], axis=-1)
    return matmul("bsc,ce->bse", u, w["mtp_eh_proj"]["kernel"], operand)


def mtp_hidden(w, embedded, x_last, kw, operand):
    """The module's hidden states ahead of the shared head."""
    u = checkpointed_layer(mtp_input(w, embedded, x_last, kw, operand), w,
                           "mtp", kw, operand)
    return rms_norm(u, w["mtp_final_ln"]["scale"], kw["eps"])


def forward(w, ids, *, operand="f32", **kw):
    """ids [b, S] int32 -> logits [b, 2S, vocabulary held]: the main
    model's, then the multi-token-prediction module's."""
    embedded, x = hidden_states(w, ids, kw["num_hidden_layers"], kw, operand)
    both = jnp.concatenate(
        [rms_norm(x, w["final_ln"]["scale"], kw["eps"]),
         mtp_hidden(w, embedded, x, kw, operand)], axis=1)
    return matmul("bse,ev->bsv", both, w["lm_head"]["kernel"], operand)


def routed_experts(w, ids, prefix, **kw):
    """The experts [b, S, k] that the layer `prefix` chooses (`b<i>`, or
    `mtp` for the module's)."""
    layers = (kw["num_hidden_layers"] if prefix == "mtp"
              else int(prefix[1:]))
    embedded, x = hidden_states(w, ids, layers, kw, "f32")
    if prefix == "mtp":
        x = mtp_input(w, embedded, x, kw, "f32")
    _, g = attended(x, w, prefix, kw, "f32")
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
