"""Plain reference of the `bert_ae` family: forward, loss, gradients, Adam.

The block is the repo's BERT-proxy (`flexflow_tpu.models.create_transformer`
at its defaults, what ran on the chip in PR 21), written out here from its
arithmetic alone: pre-LN, multi-head attention over the layer's input, a
residual add, pre-LN, a ReLU dense to `ffn_mult` x hidden with bias, a
dense back to hidden with bias, a residual add; after the last layer
`dense(1)`; loss `MEAN_SQUARED_ERROR_AVG_REDUCE`.

It is NOT the block of the source the configuration names for its sizes
(flexflow/FlexFlow examples/cpp/Transformer/transformer.cc,
`create_attention_encoder`), which, as far as can be told without network
access, is `multihead_attention -> dense(hidden, ReLU, no bias) ->
dense(hidden, no bias)`: no residual add, no bias, no layer norm, and an
FFN of the hidden width. Every difference is listed under `departures` in
benchmarks/configs/bert_ae.json. Further:
- attention weights keep an explicit head axis (`wq/wk/wv [h, e, d]`,
  `wo [h, d, e]`, output bias `bo`, no q/k/v bias): the same linear maps
  as a packed projection.

Everything is float32 `jax.numpy` under matmul precision `highest`. It
shares no code with `flexflow_tpu`. `operand` rounds the operands of
every matrix multiplication first: `"f32"` not at all (the reference),
`"bf16"` to bfloat16 (the precision the configuration states), `"fp8"`
to float8_e4m3 with one scale per tensor (the control: the nearest
precision below the stated one).
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


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
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def attention(x, p, operand):
    """Bidirectional softmax attention."""
    q = matmul("bse,hed->bhsd", x, p["wq"], operand)
    k = matmul("bse,hed->bhsd", x, p["wk"], operand)
    v = matmul("bse,hed->bhsd", x, p["wv"], operand)
    scores = matmul("bhqd,bhkd->bhqk", q, k, operand) / jnp.sqrt(
        jnp.float32(q.shape[3]))
    probs = jax.nn.softmax(scores, axis=-1)
    o = matmul("bhqk,bhkd->bhqd", probs, v, operand)
    return matmul("bhsd,hde->bse", o, p["wo"], operand) + p["bo"]


def forward(w, x, *, num_layers, layer_norm_eps=None, operand="f32"):
    """x [b, s, hidden] float32 -> predictions [b, s, 1].
    `layer_norm_eps=None` leaves the two layer norms out."""
    def norm(h, p):
        return h if layer_norm_eps is None else layer_norm(
            h, p, layer_norm_eps)

    h = x
    for i in range(num_layers):
        h = h + attention(norm(h, w.get(f"ln1_{i}")), w[f"attn_{i}"],
                          operand)
        f = norm(h, w.get(f"ln2_{i}"))
        f = jax.nn.relu(matmul("bse,ef->bsf", f, w[f"ffn1_{i}"]["kernel"],
                               operand) + w[f"ffn1_{i}"]["bias"])
        f = matmul("bsf,fe->bse", f, w[f"ffn2_{i}"]["kernel"],
                   operand) + w[f"ffn2_{i}"]["bias"]
        h = h + f
    return matmul("bse,eo->bso", h, w["head"]["kernel"],
                  operand) + w["head"]["bias"]


def sample_losses(pred, y):
    """Per-sample sums of squared error; the loss is their total over the
    number of elements (MEAN_SQUARED_ERROR_AVG_REDUCE)."""
    return jnp.sum((pred - y) ** 2, axis=tuple(range(1, pred.ndim)))


def loss_denominator(y):
    return y.size
