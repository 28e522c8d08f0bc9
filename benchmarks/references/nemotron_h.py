"""Plain reference of the `nemotron_h` family: forward, loss; gradients by
`jax.grad`, Adam in `common.py`.

The architecture is `nemotron_h` as its public `config.json` and modelling
code describe it (NVIDIA-Nemotron-3-Nano-30B-A3B): an embedding, blocks
chosen letter by letter from `hybrid_override_pattern`, a final RMSNorm and
an untied head. Written out here from the arithmetic alone, in float32
`jax.numpy` under matmul precision `highest`; it shares no code with
`flexflow_tpu`.

Block i:  x <- x + mixer_i(RMSNorm(x)), eps 1e-5, a learned scale.
After the last block  x <- RMSNorm(x);  logits = x W_head.
Loss: mean over tokens of the cross-entropy of logits[:, t] against
labels[:, t] (the data file makes labels the next token).

`M`, Mamba-2 (H heads of P, G groups, state N, d_inner = H P):
    [z, xBC, dt] = x W_in        widths d_inner, d_inner + 2 G N, H
    xBC = silu(conv1d_causal_depthwise(xBC, k) + b_conv)
    x [H, P], B [G, N], C [G, N] = split(xBC)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)  (a head)
    head h reads group h // (H / G)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t;  y_t = h_t C_t + D x_t
    state zero at the start of every sequence
    y = GroupRMSNorm(y silu(z)), groups of d_inner / G channels, a scale
    out = y W_out                 (no biases but the convolution's)
  Here the recurrence runs one position a step (`lax.scan`); the program
  computes it in chunks.

`E`, experts (E_all routed, top-k, the `held` experts from `offset`):
    s = sigmoid(x W_r)   float32 whatever `operand` is, over all E_all
    choose the k largest of s + b; w_j = s_j / (sum of the k + 1e-20) *
    routed_scaling_factor;  expert_j(x) = relu(x U_j)^2 D_j
    out = sum over the chosen j that are held of w_j expert_j(x)
          + shared(x)   (the same form, wider)
  A slot routed to an expert that is not held contributes nothing, here
  as in the program: the chips that hold it add that part. The experts are
  a loop over the held ones, each over all tokens, masked by its weight.

`*`, attention: causal GQA (h Q heads, h_kv K/V heads of d), no biases,
    scale d^-1/2, scores formed in blocks of queries.

Departures from the published model, each also in the configuration file:
- `e_score_correction_bias` b is a leaf of the weights (`e_bias`): the
  published training adjusts it outside the gradient so that the experts'
  loads even out; here the benchmark sets it once, from the seed, to the
  balanced state (`families/nemotron_h.py`, `balance_routers`), and the
  measured steps do not update it (its gradient is exactly zero). There
  is no auxiliary loss.
- no rotary embedding in `*`: the `nemotron_h` attention block applies
  none (`rope_theta` is unused there). Unconfirmed without network access.
- `n_group` = `topk_group` = 1: no group-limited routing.

`operand` rounds the operands of every matrix multiplication that the
configuration states in bfloat16 (not the router's, stated float32):
`"f32"` not at all (the reference), `"bf16"` to bfloat16, `"fp8"` to
float8_e4m3 with one scale a tensor (the control).
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 1024
SCAN_SEGMENT = 128


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


def mamba2(x, p, *, heads, head_dim, groups, state, eps, operand):
    b, length, _ = x.shape
    d_inner = heads * head_dim
    gn = groups * state
    proj = matmul("bse,ef->bsf", x, p["w_in"], operand)
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner:2 * d_inner + 2 * gn]
    dt = jax.nn.softplus(proj[..., 2 * d_inner + 2 * gn:] + p["dt_bias"])
    k = p["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = p["conv_b"] + sum(padded[:, j:j + length] * p["conv_w"][j]
                             for j in range(k))
    xbc = jax.nn.silu(conv)
    xs = round_operand(xbc[..., :d_inner], operand).reshape(
        b, length, heads, head_dim)
    bm = round_operand(xbc[..., d_inner:d_inner + gn], operand).reshape(
        b, length, groups, state)
    cm = round_operand(xbc[..., d_inner + gn:], operand).reshape(
        b, length, groups, state)
    a = -jnp.exp(p["a_log"])
    rep = heads // groups

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp                  # [b,H,P] [b,H] [b,G,N]
        b_h = jnp.repeat(b_t, rep, axis=1)
        c_h = jnp.repeat(c_t, rep, axis=1)
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_h, precision=HIGHEST)

    # one position a step; in segments under `jax.checkpoint`, so that a
    # backward pass keeps the state at the segments' starts and one
    # segment's steps (the arithmetic is the same)
    seg = SCAN_SEGMENT if length % SCAN_SEGMENT == 0 else length
    seq = tuple(jnp.moveaxis(t, 1, 0).reshape(
        (length // seg, seg) + t.shape[:1] + t.shape[2:])
        for t in (xs, dt, bm, cm))
    _, ys = jax.lax.scan(
        jax.checkpoint(lambda h, inp: jax.lax.scan(step, h, inp)),
        jnp.zeros((b, heads, head_dim, state), jnp.float32), seq)
    y = jnp.moveaxis(ys.reshape((length,) + ys.shape[2:]), 0, 1) \
        + p["d"][:, None] * xs
    y = y.reshape(b, length, d_inner) * jax.nn.silu(z)
    yg = y.reshape(b, length, groups, d_inner // groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    y = yg.reshape(b, length, d_inner) * p["norm_scale"]
    return matmul("bsf,fe->bse", y, p["w_out"], operand)


def router_scores(x, w_router):
    """sigmoid(x W_r): float32 at `highest` whatever the operand."""
    return jax.nn.sigmoid(jnp.einsum("bse,en->bsn", x, w_router,
                                     precision=HIGHEST))


def route(x, p, k, scaling):
    """(weights [.., k], experts [.., k]): the k largest of s + b, their
    weights s_j / (sum of the k + 1e-20) * scaling."""
    s = router_scores(x, p["w_router"])
    _, idx = jax.lax.top_k(s + p["e_bias"], k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    return top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * scaling, idx


def relu2_mlp(x, up, down, operand):
    h = jnp.square(jax.nn.relu(matmul("bse,ef->bsf", x, up, operand)))
    return matmul("bsf,fe->bse", h, down, operand)


def experts(x, p, *, k, scaling, offset, operand):
    weights, idx = route(x, p, k, scaling)
    out = relu2_mlp(x, p["ws_up"], p["ws_down"], operand)
    for e in range(p["w_up"].shape[0]):            # the experts held
        w_e = jnp.sum(jnp.where(idx == e + offset, weights, 0.0), axis=-1)
        out = out + w_e[..., None] * relu2_mlp(x, p["w_up"][e],
                                               p["w_down"][e], operand)
    return out


def attention(x, p, operand):
    """Causal grouped-query attention, scores in blocks of queries."""
    q = matmul("bse,hed->bhsd", x, p["wq"], operand)
    k = matmul("bse,hed->bhsd", x, p["wk"], operand)
    v = matmul("bse,hed->bhsd", x, p["wv"], operand)
    rep = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    length, d = q.shape[2], q.shape[3]
    outs = []
    for start in range(0, length, QUERY_BLOCK):
        qb = q[:, :, start:start + QUERY_BLOCK]
        scores = matmul("bhqd,bhkd->bhqk", qb, k, operand) / jnp.sqrt(
            jnp.float32(d))
        rows = start + jnp.arange(qb.shape[2])[:, None]
        scores = jnp.where(jnp.arange(length)[None, :] <= rows, scores,
                           -jnp.inf)
        outs.append(matmul("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v,
                           operand))
    return matmul("bhsd,hde->bse", jnp.concatenate(outs, axis=2), p["wo"],
                  operand)


def mixer(letter, h, p, kw, operand):
    if letter == "M":
        return mamba2(h, p, heads=kw["mamba_num_heads"],
                      head_dim=kw["mamba_head_dim"], groups=kw["n_groups"],
                      state=kw["ssm_state_size"], eps=kw["eps"],
                      operand=operand)
    if letter == "E":
        return experts(h, p, k=kw["num_experts_per_tok"],
                       scaling=kw["routed_scaling_factor"],
                       offset=kw["expert_offset"], operand=operand)
    if letter == "*":
        return attention(h, p, operand)
    raise ValueError(f"unknown block letter {letter!r}")


def hidden_states(w, ids, blocks, kw, operand):
    """The residual stream after the first `blocks` blocks. Each block is
    under `jax.checkpoint`: it changes no arithmetic, and a float32
    backward pass at 8,192 tokens then keeps one block's interior."""
    x = w["embed_tokens"]["kernel"][ids]
    for i, letter in enumerate(kw["pattern"][:blocks]):
        def block(x, p_norm, p_mixer, letter=letter):
            return x + mixer(letter, rms_norm(x, p_norm["scale"], kw["eps"]),
                             p_mixer, kw, operand)
        x = jax.checkpoint(block)(x, w[f"b{i}_norm"], w[f"b{i}_mixer"])
    return x


def forward(w, ids, *, operand="f32", **kw):
    """ids [b, s] int32 -> logits [b, s, vocabulary held]."""
    x = hidden_states(w, ids, len(kw["pattern"]), kw, operand)
    x = rms_norm(x, w["final_ln"]["scale"], kw["eps"])
    return matmul("bse,ev->bsv", x, w["lm_head"]["kernel"], operand)


def routed_experts(w, ids, block, **kw):
    """The experts [b, s, k] that block `block` (an `E`) chooses."""
    x = hidden_states(w, ids, block, kw, "f32")
    x = rms_norm(x, w[f"b{block}_norm"]["scale"], kw["eps"])
    return route(x, w[f"b{block}_mixer"], kw["num_experts_per_tok"],
                 kw["routed_scaling_factor"])[1]


def sample_losses(pred, y):
    """Per-sample sums of the tokens' cross-entropy; the loss is their
    total over the number of tokens."""
    logp = jax.nn.log_softmax(pred, axis=-1)
    tok = jnp.take_along_axis(logp, y.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.sum(tok[..., 0], axis=-1)


def loss_denominator(y):
    return y.size
