"""Plain reference of the `ouro` family: forward, loss; gradients by
`jax.grad`, Adam in `common.py`.

The architecture is Ouro-2.6B as its public `config.json` gives the
sizes (hidden 2048, 16 query and 16 key/value heads of 128, SwiGLU 5632,
`rope_theta` 1e6, `rms_norm_eps` 1e-6, an untied head, `total_ut_steps`
4) and as the released `modeling_ouro.py` and the LoopLM report
(arXiv:2510.25741) give the equations, which stand under `assumed` in
the configuration's file: a looped language model. ONE stack of layers
is applied `total_ut_steps` = T times; the weights tree holds every leaf
ONCE and plain Python applies it T times, so `jax.grad` sums the T uses.
Written out here from the arithmetic alone, in float32 `jax.numpy` under
matmul precision `highest`; it shares no code with `flexflow_tpu`.

    x^(0) = E[ids]
    for t = 1..T:                              the SAME leaves every pass
        x = x^(t-1)
        for layer l (rms = RMSNorm with a learned scale, eps):
            a = attention_l(rms(x; g1_l))      causal, 16 heads of d = 128,
                                               rotary over the whole head,
                                               pairs (j, j + d/2), theta;
                                               scale d^-1/2; no bias
            x = x + rms(a; g2_l)               sandwich: a norm on the
                                               branch's OUTPUT too
            m = (silu(h G_l) * (h U_l)) D_l,   h = rms(x; g3_l)  (the leaf
                                               `gate_up_proj` is [G ; U])
            x = x + rms(m; g4_l)
        x^(t) = rms(x; g_final)                closes EVERY pass and is the
                                               next pass's input
        logits^(t) = x^(t) W_head              [S, V]
        g^(t) = x^(t) w_gate + b_gate          the exit gate's logit, one
                                               scalar a position: float32
                                               at `highest` whatever
                                               `operand` is
    forward = [logits ; g] of all passes laid end to end, pass-major:
              [b, T * S, V + 1]

Loss (`sample_losses`, from that array alone): lambda^(t) = sigmoid(g^(t));
    p_t = lambda^(t) prod_{j<t} (1 - lambda^(j))  for t < T
    p_T = prod_{j<T} (1 - lambda^(j))             lambda^(T) is not read
    loss_i = sum_t p_t,i CE(logits^(t)_i, y_i) - beta H(p_.,i)
    H = -sum_t p_t log p_t,  beta = EXIT_ENTROPY_BETA
and the loss is the mean over the S positions (labels the next token).
At T = 1: p_1 = 1, H = 0, the plain cross-entropy.

Every layer application runs under `jax.checkpoint`; scores are formed
in blocks of QUERY_BLOCK queries, one after the other (`lax.map`), each
under `jax.checkpoint`, so that 16 heads of S x S scores never exist.
Neither changes the arithmetic.

`operand` rounds the operands of every matrix multiplication that the
configuration states in bfloat16 (not the gate's, stated float32):
`"f32"` not at all (the reference), `"bf16"` to bfloat16, `"fp8"` to
float8_e4m3 with one scale a tensor (the control).
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
# the weight of the exit distribution's entropy in the loss (the report's
# stage-one value; the configuration's `exit_entropy_beta` states the same)
EXIT_ENTROPY_BETA = 0.1


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


def attention(h, p, *, theta, operand):
    """Causal attention, as many key/value heads as query heads; scores
    in blocks of queries."""
    q = rotary(matmul("bse,hed->bhsd", h, p["wq"], operand), theta)
    k = rotary(matmul("bse,hed->bhsd", h, p["wk"], operand), theta)
    v = matmul("bse,hed->bhsd", h, p["wv"], operand)
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


LAYER_LEAVES = ("norm", "attn", "attn_out_norm", "post_norm",
                "gate_up_proj", "down_proj", "mlp_out_norm")


def layer(x, w, i, kw, operand):
    """One application of layer i from the leaves `b<i>_*`."""
    eps = kw["eps"]
    a = attention(rms_norm(x, w[f"b{i}_norm"]["scale"], eps),
                  w[f"b{i}_attn"], theta=kw["rope_theta"], operand=operand)
    x = x + rms_norm(a, w[f"b{i}_attn_out_norm"]["scale"], eps)
    gate, up = jnp.split(w[f"b{i}_gate_up_proj"]["kernel"], 2, axis=1)
    m = swiglu(rms_norm(x, w[f"b{i}_post_norm"]["scale"], eps), gate, up,
               w[f"b{i}_down_proj"]["kernel"], operand)
    return x + rms_norm(m, w[f"b{i}_mlp_out_norm"]["scale"], eps)


def passes(w, ids, kw, operand):
    """[x^(1), ..., x^(T)]: the normed stream after every pass."""
    x, out = w["embed_tokens"]["kernel"][ids], []
    for _ in range(kw["total_ut_steps"]):
        for i in range(kw["num_hidden_layers"]):
            leaves = {f"b{i}_{n}": w[f"b{i}_{n}"] for n in LAYER_LEAVES}
            x = jax.checkpoint(
                lambda x, leaves, i=i: layer(x, leaves, i, kw, operand))(
                    x, leaves)
        x = rms_norm(x, w["final_ln"]["scale"], kw["eps"])
        out.append(x)
    return out


def forward(w, ids, *, operand="f32", **kw):
    """ids [b, S] int32 -> [b, T * S, V + 1]: the T passes' logits laid
    end to end, pass-major, and beside each row its exit gate's logit."""
    x = jnp.concatenate(passes(w, ids, kw, operand), axis=1)
    logits = matmul("bse,ev->bsv", x, w["lm_head"]["kernel"], operand)
    gate = jnp.einsum("bse,eo->bso", x, w["exit_gate"]["kernel"],
                      precision=HIGHEST) + w["exit_gate"]["bias"]
    return jnp.concatenate([logits, gate], axis=-1)


def exit_distribution(gate_logits):
    """p [b, T, S] from the gates' logits [b, T, S]."""
    lam = jax.nn.sigmoid(gate_logits)
    left = jnp.cumprod(1.0 - lam, axis=1)       # prod_{j<=t} (1 - lambda_j)
    before = jnp.concatenate([jnp.ones_like(left[:, :1]), left[:, :-1]],
                             axis=1)            # prod_{j<t}
    return jnp.concatenate([lam[:, :-1] * before[:, :-1], before[:, -1:]],
                           axis=1)


def position_losses(pred, y):
    """(loss_i [b, S], p [b, T, S], ce [b, T, S]) from the model's output
    [b, T * S, V + 1] and labels [b, S]."""
    b, s = y.shape
    t = pred.shape[1] // s
    logp = jax.nn.log_softmax(pred[..., :-1], axis=-1)
    ids = jnp.tile(y.astype(jnp.int32), (1, t))
    ce = -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
    ce = ce.reshape(b, t, s)
    p = exit_distribution(pred[..., -1].reshape(b, t, s))
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                                 0.0), axis=1)
    return jnp.sum(p * ce, axis=1) - EXIT_ENTROPY_BETA * entropy, p, ce


def sample_losses(pred, y):
    """Per-sample sums of the positions' loss; the loss is their total
    over the number of positions (`loss_denominator`)."""
    return jnp.sum(position_losses(pred, y)[0], axis=-1)


def loss_denominator(y):
    return y.size
