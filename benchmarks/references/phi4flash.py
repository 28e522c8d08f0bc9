"""Plain reference of the `phi4flash` family: forward, loss; gradients by
`jax.grad`, Adam in `common.py`.

The architecture is Phi-4-mini-flash-reasoning's (SambaY, "Decoder-Hybrid-
Decoder Architecture for Efficient Reasoning with Long Generation",
arXiv:2507.06607) with Differential Attention (Ye et al., "Differential
Transformer", arXiv:2410.05258), written from memory of the released
`modeling_phi4flash.py` / `configuration_phi4flash.py` without network
access: every item the public `config.json` does not state stands under
`assumed` in the configuration's file. Written out here from the
arithmetic alone, in float32 `jax.numpy` under matmul precision
`highest`; it shares no code with `flexflow_tpu`.

A stage runs `num_hidden_layers` layers from the published index
`first_layer_index` of `published_num_hidden_layers` L; the leaves of
the j-th layer that runs are `b<j>_*`, its published index i =
first_layer_index + j. LN = LayerNorm with scale and bias, eps `eps`.

    x'  = x + mixer_i(LN(x));   x'' = x' + mlp(LN(x'))
    mlp(g) = (silu(G) * U) W_2,  [G ; U] = g W_1   (`gate_up_proj`, the
             gate the FIRST half; no biases)
    after the last layer LN, then logits = x E^T, E the embedding's table

Kind of layer i: even i is of the Mamba family, odd i attention.
i < L/2: Mamba / attention under a causal window of `sliding_window`;
i = L/2: Mamba, whose scan output m is kept as the MEMORY; i = L/2 + 1:
full causal attention, whose projected k, v are kept as the SHARED keys
and values; i >= L/2 + 2: gated memory unit / cross-attention.

Mamba (d_inner C, state N, K taps, rank R, all read off the leaves):
    [x ; z] = h W_in;   x = silu(conv(x) + b_conv), causal depthwise,
              zeros ahead of a sample (K shifted products)
    [r ; B ; C] = x W_x;   dt = softplus(r W_dt + b_dt);   A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) (x) B_t,  h_{-1} = 0
    y_t = h_t C_t + D x_t;   memory m = y;   out = (y silu(z)) W_out
  one position a step (`lax.scan`), in segments under `jax.checkpoint`.
Gated memory unit:  out = (silu(h W_1) * m) W_2.
Attention (H heads of d, Hk key/value heads; pairs p < H/2, key/value
pairs P = p // (H / Hk)):
    q, k, v = h W_q + b_q, h W_k + b_k, h W_v + b_v     (cross: q alone,
              k, v the shared ones)
    q1, q2 = q heads 2p, 2p+1;  k1, k2 = k heads 2P, 2P+1;
    v = [v_2P ; v_2P+1], 2d wide
    A^j = softmax over the visible s of q^j_t . k^j_s / sqrt(d)
          (s <= t; under the window also s > t - window)
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 i)
    o = (A^1 v - lambda A^2 v);  o = o rsqrt(mean_2d(o^2) + eps) g
        (1 - lambda_init);  its 2d lanes go back as heads 2p, 2p+1
    out = concat(o) W_o + b_o
  scores in blocks of QUERY_BLOCK queries, one after the other, each
  under `jax.checkpoint`; every layer under `jax.checkpoint` too. None
  changes the arithmetic.
Loss: mean over ALL S positions of the cross-entropy of logits[:, t]
against labels[:, t] (the data file makes labels the next token).

Departures from the published code, each also in the configuration file:
- the stage reads the embedding's rows for its input where the
  deployment hands it the stage before's output (the table's rows it
  holds are real work either way; the head reads the same rows);
- the two maps weigh the values in two products (A^1 v, A^2 v) whose
  difference is taken after, as `multihead_flashdiff_2` does with two
  flash calls, not (A^1 - lambda A^2) v in one;
- dropout 0 (the published `attention_dropout`, `resid_pdrop`,
  `embd_pdrop` are 0 in training configs of the release, assumed);
- no position embedding of any kind, as the SambaY report states.

`operand` rounds the operands of every matrix multiplication that the
configuration states in bfloat16: `"f32"` not at all (the reference),
`"bf16"` to bfloat16, `"fp8"` to float8_e4m3 with one scale a tensor
(the control). dt, A, the decays, the state, y and lambda are float32
whatever it is.
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
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


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def layer_kind(i, total):
    """The kind of published layer i of `total`."""
    half = total // 2
    if i % 2 == 0:
        return "mamba" if i <= half else "gated_memory"
    return ("window" if i < half else "full" if i == half + 1
            else "cross")


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def mamba(h, p, operand):
    """(memory y [b, S, C], the mixer's output [b, S, E])."""
    c = p["conv_w"].shape[1]
    n = p["a_log"].shape[1]
    r = p["w_dt"].shape[0]
    xz = matmul("bse,ef->bsf", h, p["w_in"], operand)
    x, z = xz[..., :c], xz[..., c:]
    taps, positions = p["conv_w"].shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(p["conv_w"][j] * padded[:, j:j + positions]
                        for j in range(taps)) + p["conv_b"])
    rbc = matmul("bsc,cf->bsf", x, p["w_x"], operand)
    dt = jax.nn.softplus(matmul("bsr,rc->bsc", rbc[..., :r], p["w_dt"],
                                operand) + p["dt_bias"])
    bm, cm = rbc[..., r:r + n], rbc[..., r + n:]
    a = -jnp.exp(p["a_log"])

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t[..., None] * a) * state
                 + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return state, jnp.einsum("bcn,bn->bc", state, c_t, precision=HIGHEST)

    seg = SCAN_SEGMENT if positions % SCAN_SEGMENT == 0 else positions
    seq = tuple(jnp.moveaxis(t, 1, 0).reshape(
        (positions // seg, seg) + t.shape[:1] + t.shape[2:])
        for t in (x, dt, bm, cm))
    _, ys = jax.lax.scan(
        jax.checkpoint(lambda s, inp: jax.lax.scan(step, s, inp)),
        jnp.zeros((x.shape[0], c, n), jnp.float32), seq)
    y = jnp.moveaxis(ys.reshape((positions,) + ys.shape[2:]), 0, 1) \
        + p["d"] * x
    return y, matmul("bsc,ce->bse", y * jax.nn.silu(z), p["w_out"], operand)


def gated_memory(h, memory, w_in, w_out, operand):
    gate = jax.nn.silu(matmul("bse,ec->bsc", h, w_in, operand))
    return matmul("bsc,ce->bse", gate * memory, w_out, operand)


def projected_kv(h, p, operand):
    """(k, v) [b, Hk, S, d]: what a full-attention layer keeps for the
    cross-attention layers."""
    return tuple(matmul("bse,hed->bhsd", h, p[w], operand)
                 + p[b][None, :, None, :] for w, b in (("wk", "bk"),
                                                       ("wv", "bv")))


def differential_attention(h, p, kv, *, depth, window, eps, operand):
    """Differential attention of published layer `depth` over the keys
    and values `kv`; scores in blocks of queries."""
    q = matmul("bse,hed->bhsd", h, p["wq"], operand) \
        + p["bq"][None, :, None, :]
    k, v = kv
    pairs, kv_pairs = q.shape[1] // 2, k.shape[1] // 2
    rep = pairs // kv_pairs
    q1, q2 = q[:, 0::2], q[:, 1::2]
    k1, k2 = (jnp.repeat(t, rep, axis=1) for t in (k[:, 0::2], k[:, 1::2]))
    vv = jnp.repeat(jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1),
                    rep, axis=1)                        # [b, pairs, S, 2d]
    positions, d = q.shape[2], q.shape[3]
    lam0 = lambda_init(depth)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)

    @jax.checkpoint
    def block(qb1, qb2, start):
        i = start + jnp.arange(qb1.shape[2])[:, None]
        j = jnp.arange(positions)[None, :]
        seen = j <= i
        if window:
            seen = seen & (j > i - window)

        def weighed(qb, keys):
            scores = matmul("bhqd,bhkd->bhqk", qb, keys, operand) \
                / jnp.sqrt(jnp.float32(d))
            scores = jnp.where(seen, scores, -jnp.inf)
            return matmul("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), vv,
                          operand)

        return weighed(qb1, k1) - lam * weighed(qb2, k2)

    size = min(QUERY_BLOCK, positions)
    starts = jnp.arange(0, positions, size)

    def blocks(t):
        return jnp.moveaxis(t.reshape(t.shape[:2] + (-1, size, d)), 2, 0)

    outs = jax.lax.map(lambda a: block(*a), (blocks(q1), blocks(q2), starts))
    o = jnp.moveaxis(outs, 0, 2).reshape(q1.shape[:3] + (2 * d,))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * p["diff_norm"] * (1.0 - lam0)
    # a pair's 2d lanes are heads 2p and 2p + 1
    o = o.reshape(o.shape[:3] + (2, d))
    o = jnp.moveaxis(o, 3, 2).reshape(q.shape)
    return matmul("bhsd,hde->bse", o, p["wo"], operand) + p["bo"]


LAYER_LEAVES = ("norm", "mixer", "attn", "memory_in_proj", "memory_out_proj",
                "post_norm", "gate_up_proj", "down_proj")


def layer(x, w, j, kw, operand, shared):
    """(x'', shared): layer j of the stage from the leaves `b<j>_*`;
    `shared` = (memory, (k, v)) as made so far, None where not yet."""
    depth = kw["first_layer_index"] + j
    kind = layer_kind(depth, kw["published_num_hidden_layers"])
    memory, kv = shared
    h = layer_norm(x, w[f"b{j}_norm"], kw["eps"])
    if kind == "mamba":
        y, out = mamba(h, w[f"b{j}_mixer"], operand)
        if depth == kw["published_num_hidden_layers"] // 2:
            memory = y
    elif kind == "gated_memory":
        out = gated_memory(h, memory, w[f"b{j}_memory_in_proj"]["kernel"],
                           w[f"b{j}_memory_out_proj"]["kernel"], operand)
    else:
        p = w[f"b{j}_attn"]
        if kind == "full":
            kv = projected_kv(h, p, operand)
        out = differential_attention(
            h, p, kv if kind != "window" else projected_kv(h, p, operand),
            depth=depth, eps=kw["eps"], operand=operand,
            window=kw["sliding_window"] if kind == "window" else 0)
    x = x + out
    g = layer_norm(x, w[f"b{j}_post_norm"], kw["eps"])
    gate, up = jnp.split(matmul("bse,ef->bsf", g,
                                w[f"b{j}_gate_up_proj"]["kernel"], operand),
                         2, axis=-1)
    x = x + matmul("bsf,fe->bse", jax.nn.silu(gate) * up,
                   w[f"b{j}_down_proj"]["kernel"], operand)
    return x, (memory, kv)


def forward(w, ids, *, operand="f32", **kw):
    """ids [b, S] int32 -> logits [b, S, vocabulary held], through the
    table the ids were gathered from."""
    x = w["embed_tokens"]["kernel"][ids]
    shared = (None, None)
    for j in range(kw["num_hidden_layers"]):
        leaves = {f"b{j}_{n}": w[f"b{j}_{n}"] for n in LAYER_LEAVES
                  if f"b{j}_{n}" in w}
        x, shared = jax.checkpoint(
            lambda x, leaves, shared, j=j: layer(x, leaves, j, kw, operand,
                                                 shared))(x, leaves, shared)
    x = layer_norm(x, w["final_ln"], kw["eps"])
    return matmul("bse,ve->bsv", x, w["embed_tokens"]["kernel"], operand)


def sample_losses(pred, y):
    """Per-sample sums of the positions' cross-entropy; the loss is their
    total over the number of positions (`loss_denominator`)."""
    logp = jax.nn.log_softmax(pred, axis=-1)
    tok = jnp.take_along_axis(logp, y.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.sum(tok[..., 0], axis=-1)


def loss_denominator(y):
    return y.size
