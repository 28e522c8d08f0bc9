"""Plain reference of the `sdar` family: forward, loss; gradients by
`jax.grad`, Adam in `common.py`.

The architecture is SDAR-30B-A3B-Chat as its public `config.json`
(`model_type` `sdar_moe`) and the catalog's `described_as` give it: the
Qwen3-MoE decoder layer, 48 alike, a final RMSNorm and an untied head,
trained by the block-diffusion objective. Written out here from the
arithmetic alone, in float32 `jax.numpy` under matmul precision
`highest`; it shares no code with `flexflow_tpu`.

A sample is L tokens x0. A step sees ONE sequence of 2L positions: the
noised copy xt at 0..L-1 (xt_i is the mask token with probability t_b,
else x0_i; one t_b a block b = i // B), the clean copy x0 at L..2L-1.
With  half(i) = i // L,  blk(i) = (i mod L) // B,  pos(i) = i mod L:

    visible(i, j) = blk(j) == blk(i)   half(i) == 0 and half(j) == 0
                    blk(j) <  blk(i)   half(i) == 0 and half(j) == 1
                    blk(j) <= blk(i)   half(i) == 1 and half(j) == 1
                    never              half(i) == 1 and half(j) == 0

Layer l (x is the residual stream):
    h   = RMSNorm(x), eps 1e-6, a learned scale
    q, k, v = h W_q, h W_k, h W_v  (H query heads, H_kv key/value heads
                                   of d, no bias)
    q = RMSNorm(q over d) * s_q;   k = RMSNorm(k over d) * s_k
    q, k = rotary(q, pos), rotary(k, pos)   theta, over the whole head,
                                   half-split (rotate_half) layout; the
                                   two copies share positions
    a   = softmax(q k^T / sqrt(d) over the visible j) v W_o
    x'  = x + a
    g   = RMSNorm(x')
    r   = g W_r                    float32 whatever `operand` is
    p   = softmax(r) over all experts; T = the k largest;
    w_j = p_j / sum_{i in T} p_i
    m   = sum_{j in T, j held} w_j (silu(g G_j) * (g U_j)) D_j
    x'' = x' + m
After the last layer, on the noised half only:
    z    = RMSNorm(x[0:L]) W_head
    loss = (1 / L) sum_{i < L} c_i * (-log softmax(z_i)[x0_i]),
    c_i  = [xt_i is the mask token] / t_blk(i)
averaged over the batch. The labels carry x0_i and c_i a position
([b, L, 2] float32).

A slot routed to an expert that is not held contributes nothing, here as
in the program: the chips that hold it add that part. The experts are a
loop over the held ones, each over all positions, weighted by w (zero
where not chosen). Scores are formed in blocks of QUERY_BLOCK queries,
one after the other, each under `jax.checkpoint`, every expert is under
one and every layer too: none changes the arithmetic, and the backward pass of 16,384
positions then keeps one block's probabilities and one expert's hidden
activations and output (all sixteen experts' are 2.4 and 2.1 GB a layer,
with which the gradient program asked for 7.82 GB beside Adam's 4.8 GB
of state and did not load on the chip).

Departures from the published model, each also in the configuration file:
- no auxiliary loss;
- block length, noise schedule, the per-head query/key norm, the
  half-split rotary layout and shared positions are the family's
  conventions, not keys of the config (`assumed` there).

`operand` rounds the operands of every matrix multiplication that the
configuration states in bfloat16 (not the router's, stated float32):
`"f32"` not at all (the reference), `"bf16"` to bfloat16, `"fp8"` to
float8_e4m3 with one scale a tensor (the control).
"""

import jax
import jax.numpy as jnp

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


def rotary(x, pos, theta):
    """x [b, h, s, d]: the row at position `pos[s]` turns the pairs
    (x_i, x_{i + d/2}) by pos * theta^(-2i/d)."""
    d = x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def block_mask(i, j, length, block_length):
    """visible(i, j) of the block-diffusion objective, from `half` and
    `blk` as the module's docstring writes them."""
    half_i, half_j = i // length, j // length
    blk_i = (i % length) // block_length
    blk_j = (j % length) // block_length
    return (((half_i == 0) & (half_j == 0) & (blk_j == blk_i))
            | ((half_i == 0) & (half_j == 1) & (blk_j < blk_i))
            | ((half_i == 1) & (half_j == 1) & (blk_j <= blk_i)))


def causal_mask(i, j, length, block_length):
    return j <= i


MASKS = {"block_diffusion": block_mask, "causal": causal_mask}


def attention(h, p, *, mask, block_length, shared_positions, theta, eps,
              operand):
    """Grouped-query attention over the 2L positions under `mask`, with
    the per-head query/key norm and rotary positions i mod L (or, for the
    control, i); scores in blocks of queries."""
    q = matmul("bse,hed->bhsd", h, p["wq"], operand)
    k = matmul("bse,hed->bhsd", h, p["wk"], operand)
    v = matmul("bse,hed->bhsd", h, p["wv"], operand)
    q = rms_norm(q, p["q_norm"], eps)
    k = rms_norm(k, p["k_norm"], eps)
    positions, d = q.shape[2], q.shape[3]
    length = positions // 2
    pos = jnp.arange(positions)
    if shared_positions:
        pos = pos % length
    q, k = rotary(q, pos, theta), rotary(k, pos, theta)
    rep = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)

    @jax.checkpoint
    def block(qb, start):
        scores = matmul("bhqd,bhkd->bhqk", qb, k, operand) / jnp.sqrt(
            jnp.float32(d))
        i = start + jnp.arange(qb.shape[2])[:, None]
        j = jnp.arange(positions)[None, :]
        scores = jnp.where(MASKS[mask](i, j, length, block_length), scores,
                           -jnp.inf)
        return matmul("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v,
                      operand)

    # one block of queries after the other (`lax.map`): left to itself the
    # compiler forms all the blocks' scores side by side, 8.4 GB a layer
    size = min(QUERY_BLOCK, positions)
    starts = jnp.arange(0, positions, size)
    blocks = jnp.moveaxis(q.reshape(q.shape[:2] + (-1, size, d)), 2, 0)
    outs = jax.lax.map(lambda a: block(*a), (blocks, starts))
    out = jnp.moveaxis(outs, 0, 2).reshape(q.shape)
    return matmul("bhsd,hde->bse", out, p["wo"], operand)


def router_logits(g, w_router):
    """g W_r: float32 at `highest` whatever the operand."""
    return jnp.einsum("bse,en->bsn", g, w_router, precision=HIGHEST)


def route(g, w_router, k):
    """(w [.., k], experts [.., k]): softmax over all experts, the k
    largest, renormalised to sum to one."""
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

    # a loop over the experts held, as a scan: the gradient of the stacked
    # leaves is then written an expert at a time (a Python loop over
    # slices adds up sixteen zero-padded copies of each leaf, 4.8 GB)
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(g), (
        jnp.arange(p["w_up"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return out


def layer(x, w, i, kw, operand):
    h = rms_norm(x, w[f"b{i}_norm"]["scale"], kw["eps"])
    x = x + attention(
        h, w[f"b{i}_attn"], mask=kw["attention_mask"],
        block_length=kw["block_length"],
        shared_positions=kw["shared_positions"], theta=kw["rope_theta"],
        eps=kw["eps"], operand=operand)
    g = rms_norm(x, w[f"b{i}_post_norm"]["scale"], kw["eps"])
    return x + experts(g, w[f"b{i}_mixer"], k=kw["num_experts_per_tok"],
                       offset=kw["expert_offset"], operand=operand)


LAYER_LEAVES = ("norm", "attn", "post_norm", "mixer")


def hidden_states(w, ids, layers, kw, operand):
    """The residual stream [b, 2L, e] after the first `layers` layers."""
    x = w["embed_tokens"]["kernel"][ids]
    for i in range(layers):
        def run(x, leaves, i=i):
            return layer(x, leaves, i, kw, operand)
        x = jax.checkpoint(run)(
            x, {f"b{i}_{n}": w[f"b{i}_{n}"] for n in LAYER_LEAVES})
    return x


def forward(w, ids, *, operand="f32", **kw):
    """ids [b, 2L] int32 (noised copy, then clean) -> logits of the noised
    half [b, L, vocabulary held]."""
    x = hidden_states(w, ids, kw["num_hidden_layers"], kw, operand)
    x = rms_norm(x[:, :ids.shape[1] // 2], w["final_ln"]["scale"], kw["eps"])
    return matmul("bse,ev->bsv", x, w["lm_head"]["kernel"], operand)


def routed_experts(w, ids, i, **kw):
    """The experts [b, 2L, k] that layer `i` chooses."""
    x = hidden_states(w, ids, i, kw, "f32")
    h = rms_norm(x, w[f"b{i}_norm"]["scale"], kw["eps"])
    x = x + attention(
        h, w[f"b{i}_attn"], mask=kw["attention_mask"],
        block_length=kw["block_length"],
        shared_positions=kw["shared_positions"], theta=kw["rope_theta"],
        eps=kw["eps"], operand="f32")
    g = rms_norm(x, w[f"b{i}_post_norm"]["scale"], kw["eps"])
    return route(g, w[f"b{i}_mixer"]["w_router"],
                 kw["num_experts_per_tok"])[1]


def sample_losses(pred, y):
    """Per-sample sums of c_i * cross-entropy(z_i, x0_i) over the noised
    half; y [b, L, 2] holds (x0_i, c_i). The loss is their total over the
    number of positions."""
    logp = jax.nn.log_softmax(pred, axis=-1)
    ids = y[..., 0].astype(jnp.int32)
    tok = jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
    return -jnp.sum(y[..., 1] * tok, axis=-1)


def loss_denominator(y):
    return y.shape[0] * y.shape[1]
