"""Plain reference of the `qwen3_next` family: forward, loss; gradients by
`jax.grad`, Adam in `common.py`.

The architecture is Qwen3-Next-80B-A3B-Instruct as its public
`config.json` gives it (`model_type` `qwen3_next`): an embedding, layers
whose mixer is a GATED DELTA-RULE linear-attention mixer or GATED softmax
attention by `layer_types` (three and one of every four) and whose
feed-forward is sparse experts with a gated shared expert, a final
RMSNorm and an untied head. What the config does not state stands under
`assumed` in the configuration's file. Written out here from the
arithmetic alone, in float32 `jax.numpy` under matmul precision
`highest`; it shares no code with `flexflow_tpu`, and in particular no
chunk algebra: the delta rule runs ONE POSITION A `lax.scan` STEP.

Every norm of the stream, the final norm and the q / k head norms are
ZERO-CENTRED: norm(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w). Layer i
(x the residual stream, S tokens):
    h  = norm(x; b<i>_norm)
    linear_attention:
        [q ; k ; v ; z] = h W_qkvz      widths Hk Dk, Hk Dk, Hv Dv, Hv Dv
                                        (this order of column groups)
        [b ; a] = h W_ba                Hv each
        [q ; k ; v] <- silu(conv(.))    causal, depthwise, K taps a lane,
                                        no bias, zeros ahead of the start
        beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)
        key head j serves value heads j * Hv/Hk .. ; a head's q and k
        L2-normed: q / sqrt(sum q^2 + 1e-6) * Dk^-1/2, k / sqrt(sum k^2
        + 1e-6); a value head's state S [Dk, Dv], zero at the start:
            S <- exp(g_t) S;  r = S^T k_t
            S <- S + k_t (x) (beta_t (v_t - r));  o_t = S^T q_t
        y = (o * rsqrt(mean(o^2) + eps) * w_n) * silu(z)   a value head
                                        (w_n [Dv], NOT zero-centred)
        x' = x + y W_out
    full_attention:
        [query_n ; gate_n] = (h W_q)_n a head (wq [H, E, 2 D]), k, v
        query, key heads norm(.; q_norm / k_norm) over D; rotary over
        the FIRST `rotary_dim` lanes, pairs (j, j + rotary_dim / 2),
        theta; causal softmax, scale D^-1/2, KV head n // (H / Hk)
        x' = x + (o * sigmoid(gate)) W_o
    g  = norm(x'; b<i>_post_norm)
    p  = softmax(g W_r) over ALL outputs, float32; T = the k largest
         (`lax.top_k`); w_j = p_j / sum_T p (norm_topk_prob) or p_j
    x'' = x' + sum_{j in T, j held} w_j (silu(g G_j) * (g U_j)) D_j
             + sigmoid(g w_sg) * (silu(g G_s) * (g U_s)) D_s
Head: logits = norm(x_L; final_ln) W_head. Loss: mean over ALL S
positions of the cross-entropy against labels (the next token).

A slot routed to an expert that is not held contributes nothing, here as
in the program. The experts are a scan over the held ones under
`jax.checkpoint`; attention's scores in blocks of QUERY_BLOCK queries;
the delta rule's scan under `jax.checkpoint` a block of DELTA_BLOCK
positions, so that a backward pass holds one block's states and one
entering state a block, never a state a position. None changes the
arithmetic.

The keyword arguments `delta_correction`, `decay`, `attention_gate`,
`shared_gate` (all true) switch ONE mechanism off each: the controls of
`scripts/program_controls.py` alter the reference with them, and the
unaltered program must then read not correct. `operand` rounds the
operands of every matrix product that the configuration states in
bfloat16 (and q, k, v entering the recurrence; never the router's, the
decays, beta or the state): `"f32"`, `"bf16"`, `"fp8"`.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
DELTA_BLOCK = 128


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


def rms_norm(x, w, eps):
    """Zero-centred: the learned part is w, the scale 1 + w."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def rotary(x, theta, rotary_dim):
    """x [b, h, s, d]: position t turns the pairs (x_j, x_{j + r/2}) of
    the first r = rotary_dim lanes by t * theta^(-2j/r); the rest pass."""
    s, r = x.shape[2], rotary_dim
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def causal_conv_silu(x, taps):
    """x [b, s, c], taps [K, c]: silu of sum_j taps[j] x_{t-(K-1)+j}."""
    k, positions = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(taps[j] * padded[:, j:j + positions]
                           for j in range(k)))


def delta_rule(q, k, v, g, beta, *, correction=True):
    """The recurrence a position at a time. q, k [b, s, Hv, Dk] (a key
    head already laid out for each of its value heads), v [b, s, Hv, Dv],
    g, beta [b, s, Hv] -> o [b, s, Hv, Dv]."""
    b, s, hv, dk = q.shape
    dv = v.shape[-1]
    block = min(DELTA_BLOCK, s)
    pad = (-s) % block
    seq = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
           for t in (q, k, v, g, beta)]        # k = 0, beta = 0: no write
    seq = [jnp.moveaxis(t, 1, 0).reshape((-1, block) + t.shape[:1]
                                         + t.shape[2:]) for t in seq]

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None, None] * state
        r = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HIGHEST) \
            if correction else 0.0
        write = beta_t[..., None] * (v_t - r)
        state = state + k_t[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=HIGHEST)

    @jax.checkpoint
    def run_block(state, blk):
        return jax.lax.scan(step, state, blk)

    _, out = jax.lax.scan(run_block, jnp.zeros((b, hv, dk, dv), jnp.float32),
                          tuple(seq))
    out = out.reshape((-1,) + out.shape[2:])[:s]
    return jnp.moveaxis(out, 0, 1)


def delta_mixer(h, p, *, key_heads, eps, operand, correction=True,
                decay=True):
    b, s, _ = h.shape
    hv, dv = p["a_log"].shape[0], p["norm_scale"].shape[0]
    dk = (p["conv_w"].shape[1] - hv * dv) // (2 * key_heads)
    kd, vd = key_heads * dk, hv * dv
    proj = matmul("bse,ef->bsf", h, p["w_qkvz"], operand)
    z = proj[..., 2 * kd + vd:]
    qkv = causal_conv_silu(proj[..., :2 * kd + vd], p["conv_w"])
    ba = matmul("bse,ef->bsf", h, p["w_ba"], operand)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    if not decay:
        g = jnp.zeros_like(g)
    q = qkv[..., :kd].reshape(b, s, key_heads, dk)
    k = qkv[..., kd:2 * kd].reshape(b, s, key_heads, dk)
    v = qkv[..., 2 * kd:].reshape(b, s, hv, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(t, hv // key_heads, axis=2) for t in (q, k))
    o = delta_rule(round_operand(q, operand), round_operand(k, operand),
                   round_operand(v, operand), g, beta,
                   correction=correction)
    y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * p["norm_scale"]
    y = y * jax.nn.silu(z.reshape(b, s, hv, dv))
    return matmul("bsf,fe->bse", y.reshape(b, s, vd), p["w_out"], operand)


def attention(h, p, *, theta, rotary_dim, eps, operand, gate=True):
    """Causal grouped-query attention, a norm of every query and key head
    ahead of partial rotary, a sigmoid gate a lane out of the query
    projection; scores in blocks of queries."""
    d = p["wk"].shape[-1]
    qg = matmul("bse,hed->bhsd", h, p["wq"], operand)
    q, pre_gate = qg[..., :d], qg[..., d:]
    k = matmul("bse,hed->bhsd", h, p["wk"], operand)
    v = matmul("bse,hed->bhsd", h, p["wv"], operand)
    q = rotary(rms_norm(q, p["q_norm"], eps), theta, rotary_dim)
    k = rotary(rms_norm(k, p["k_norm"], eps), theta, rotary_dim)
    rep = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    positions = q.shape[2]

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
    if gate:
        out = out * jax.nn.sigmoid(pre_gate)
    return matmul("bhsd,hde->bse", out, p["wo"], operand)


def swiglu(g, gate, up, down, operand):
    hidden = (jax.nn.silu(matmul("bse,ef->bsf", g, gate, operand))
              * matmul("bse,ef->bsf", g, up, operand))
    return matmul("bsf,fe->bse", hidden, down, operand)


def route(g, p, k, norm_topk):
    """(weights [.., k], experts [.., k]): softmax over ALL the router's
    outputs in float32 at `highest` whatever the operand, the k largest,
    renormalised to sum 1 where `norm_topk`."""
    probs = jax.nn.softmax(jnp.einsum("bse,en->bsn", g, p["w_router"],
                                      precision=HIGHEST), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return top, idx


def experts(g, p, *, k, norm_topk, offset, operand, shared_gate=True):
    """What the held experts (those of `p`, the published experts from
    `offset` on) add for the positions g, weighted on their output, and
    the shared expert times its gate."""
    weights, idx = route(g, p, k, norm_topk)

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
    both = swiglu(g, p["ws_gate"], p["ws_up"], p["ws_down"], operand)
    if shared_gate:
        both = both * jax.nn.sigmoid(matmul("bse,ef->bsf", g,
                                            p["w_shared_gate"], operand))
    return out + both


LAYER_LEAVES = ("norm", "delta", "attn", "post_norm", "mixer")


def mixed(x, w, i, kw, operand):
    """(x', g): the stream after layer i's mixer, and its norm, which
    the experts and the router read."""
    h = rms_norm(x, w[f"b{i}_norm"]["scale"], kw["eps"])
    if kw["layer_types"][i] == "linear_attention":
        x = x + delta_mixer(
            h, w[f"b{i}_delta"], key_heads=kw["linear_num_key_heads"],
            eps=kw["eps"], operand=operand,
            correction=kw.get("delta_correction", True),
            decay=kw.get("decay", True))
    else:
        x = x + attention(h, w[f"b{i}_attn"], theta=kw["rope_theta"],
                          rotary_dim=kw["rotary_dim"], eps=kw["eps"],
                          operand=operand,
                          gate=kw.get("attention_gate", True))
    return x, rms_norm(x, w[f"b{i}_post_norm"]["scale"], kw["eps"])


def layer(x, w, i, kw, operand):
    x, g = mixed(x, w, i, kw, operand)
    return x + experts(g, w[f"b{i}_mixer"], k=kw["num_experts_per_tok"],
                       norm_topk=kw["norm_topk_prob"],
                       offset=kw["expert_offset"], operand=operand,
                       shared_gate=kw.get("shared_gate", True))


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
    _, g = mixed(x, w, i, kw, "f32")
    return route(g, w[f"b{i}_mixer"], kw["num_experts_per_tok"],
                 kw["norm_topk_prob"])[1]


def sample_losses(pred, y):
    """Per-sample sums of the positions' cross-entropy; the loss is their
    total over the number of positions (`loss_denominator`)."""
    logp = jax.nn.log_softmax(pred, axis=-1)
    tok = jnp.take_along_axis(logp, y.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.sum(tok[..., 0], axis=-1)


def loss_denominator(y):
    return y.size
