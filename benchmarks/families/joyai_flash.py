"""Family `joyai_flash`: a DeepSeek-V3-style decoder (JoyAI-LLM-Flash:
latent attention with 128 + 64-wide query/key heads, 128-wide value heads
and one rotary key shared by all heads; a leading dense SwiGLU layer;
sigmoid top-8-of-256 experts with a score-correction bias and a gated
shared expert; the multi-token-prediction module as a second, weighted
loss over a second half of the logits), one chip's share of a stated
deployment, built through `flexflow_tpu.models.create_decoder` +
`FFModel.compile`. See `bert_ae.py` for what a family gives the harness.

`make_data` gives ids `[n, S]` and labels `[n, 2S, 2]` float32 (target,
weight): row i of the first half carries t_{i+1}, row i of the second
t_{i+2}; the weights turn the program's mean over all 2S rows of the
weighted cross-entropy into mean_{S-1}(main) + lambda * mean_{S-2}(mtp).
The reference takes the rows with a target and lambda from the objective
itself (`references/joyai_flash.py`), not from these weights.
`kernel_fallbacks` prints the program's counters after the window (the
`observed` line) and holds its `loss/target_positions` to the data's own
count. Beside that: `latent_flash_step_flops_and_bytes` for the kernel
roofline.
"""

import json
import math

import numpy as np

from benchmarks.families.nemotron_h import (  # noqa: F401  (the harness's)
    _attention_impls, install_weights, readback)
from benchmarks.references import joyai_flash as reference_module

# Limits of the output check; both readings of each in PERF.md ("The output
# check"), from `run.py` and `seeds_check.py` on the chip at the cell's own
# sizes (PR 39).
# (a) pred_nrmse: RMS error of both halves' logits on the first batch over
#     the standard deviation of the reference's. Program 0.00295-0.00298
#     over 11 seeds (the reference with bfloat16 operands reads the same,
#     0.00298: the error is the operands' rounding and hardly varies),
#     float8 control 0.0468 over 2. The limit stands 1.34 times over the
#     program's largest, far under the float8 control, and under what a
#     program that rotates the whole head reads (0.00513, 1.28 times the
#     limit): at the seeded initialisation attention is near uniform
#     (scores' standard deviation about 0.5), so what is done to the
#     scores moves the logits little, and a limit that tells that control
#     apart has to sit close to the program. A lower precision fails by
#     this limit alone; so does the module reading the unshifted embedding
#     (0.707).
# (b) loss0_rel: relative error of the step-0 loss, a guard on the loss,
#     the weight between its two terms and the label path (lambda 0 reads
#     0.231). The precision hardly moves it (program at most 1.3e-5 over 11
#     seeds, the float8 control 1.2e-5 and 5.8e-5), so the accepted
#     decoder cells' limit, 4.6 times the program's largest.
# (c) later_loss_rel: largest relative error of the losses of steps 1-2
#     against the reference's own Adam steps; program at most 1.7e-5 over
#     11 seeds, so likewise (3.6 times). Adam without bias correction reads
#     2.6e-4.
TOLERANCES = {"pred_nrmse": 4.0e-3, "loss0_rel": 6.0e-5,
              "later_loss_rel": 6.0e-5}

# what the program showed of itself after the window (see kernel_fallbacks)
observed = {}
# positions with a target in each batch of the data made last (make_data)
_targets_by_batch = []

SIZE_KEYS = (
    "num_hidden_layers", "first_k_dense_replace", "vocab_size", "hidden_size",
    "rms_norm_eps", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
    "intermediate_size", "n_routed_experts", "n_routed_experts_published",
    "expert_offset", "num_experts_per_tok", "n_shared_experts",
    "moe_intermediate_size", "routed_scaling_factor", "norm_topk_prob",
    "hidden_act", "slot_slack", "num_nextn_predict_layers",
    "mtp_loss_weight", "initializer_range", "embedding_std",
    "published_depth")


def sizes(config, traffic, overrides=None):
    # a program without latent attention (an older commit under these
    # files) ends here, at once, before any weight is made
    import dataclasses

    from flexflow_tpu.models import DecoderConfig
    if "kv_lora_rank" not in {f.name for f in
                              dataclasses.fields(DecoderConfig)}:
        raise SystemExit("family joyai_flash: this program's decoder has no "
                         "latent attention (flexflow_tpu PR 39)")
    s = {k: config[k] for k in SIZE_KEYS}
    s.update(seq=traffic["seq"], batch=traffic["batch"],
             steps_per_epoch=traffic["steps_per_epoch"])
    s.update(overrides or {})
    return s


def decoder_pattern(s):
    dense = s["first_k_dense_replace"]
    return "A" * dense + "X" * (s["num_hidden_layers"] - dense)


def layer_prefixes(s):
    """The layers that run, in order: the trunk's and the module's."""
    return [f"b{i}" for i in range(s["num_hidden_layers"])] + ["mtp"]


def expert_prefixes(s):
    return layer_prefixes(s)[s["first_k_dense_replace"]:]


def labels_of(ids, weight):
    """ids [n, S] -> [n, 2S, 2] float32: (t_{i+1}, 2S / (S-1)) for the
    S-1 rows of the first half that have a next token, (t_{i+2}, weight *
    2S / (S-2)) for the S-2 rows of the second that have a token after
    next, (0, 0) elsewhere."""
    n, seq = ids.shape
    labels = np.zeros((n, 2 * seq, 2), np.float32)
    labels[:, :seq - 1, 0] = ids[:, 1:]
    labels[:, :seq - 1, 1] = 2 * seq / (seq - 1)
    labels[:, seq:2 * seq - 2, 0] = ids[:, 2:]
    labels[:, seq:2 * seq - 2, 1] = weight * 2 * seq / (seq - 2)
    return labels


def make_data(s, seed):
    """One epoch of token ids uniform over the rows of the vocabulary that
    are held, and both heads' targets. `program_mtp_loss_weight` (a
    control) weighs the module's targets otherwise than the objective."""
    rng = np.random.default_rng(seed)
    n = s["batch"] * s["steps_per_epoch"]
    ids = rng.integers(0, s["vocab_size"], size=(n, s["seq"]),
                       dtype=np.int32)
    labels = labels_of(ids, s.get("program_mtp_loss_weight",
                                  s["mtp_loss_weight"]))
    per_sample = (labels[..., 1] > 0).sum(axis=1)
    _targets_by_batch[:] = [int(per_sample[i:i + s["batch"]].sum())
                            for i in range(0, n, s["batch"])]
    return [ids], labels


def weight_shapes(s):
    """name -> leaf -> (kind, shape); kinds: `normal` (std
    initializer_range), `out` (that over the square root of the published
    depth), `embed` (std embedding_std), `ones`, `zeros` (the routers'
    bias, which `balance_routers` then sets)."""
    e, v = s["hidden_size"], s["vocab_size"]
    h, d, r = (s["num_attention_heads"], s["qk_nope_head_dim"],
               s["qk_rope_head_dim"])
    rq, rkv = s["q_lora_rank"], s["kv_lora_rank"]
    held, f = s["n_routed_experts"], s["moe_intermediate_size"]
    fs, fd = s["n_shared_experts"] * f, s["intermediate_size"]
    n = s["n_routed_experts_published"]
    shapes = {"embed_tokens": {"kernel": ("embed", (v, e))}}
    for i, prefix in enumerate(layer_prefixes(s)):
        if prefix == "mtp":
            shapes["mtp_enorm"] = {"scale": ("ones", (e,))}
            shapes["mtp_hnorm"] = {"scale": ("ones", (e,))}
            shapes["mtp_eh_proj"] = {"kernel": ("normal", (2 * e, e))}
        shapes[f"{prefix}_norm"] = {"scale": ("ones", (e,))}
        shapes[f"{prefix}_attn"] = {
            "wq_a": ("normal", (e, rq)), "q_a_norm": ("ones", (rq,)),
            "wq_b_nope": ("normal", (h, rq, d)),
            "wq_b_rope": ("normal", (h, rq, r)),
            "wkv_a": ("normal", (e, rkv + r)), "kv_a_norm": ("ones", (rkv,)),
            "wkv_b_k": ("normal", (h, rkv, d)),
            "wkv_b_v": ("normal", (h, rkv, s["v_head_dim"])),
            "wo": ("out", (h, s["v_head_dim"], e))}
        shapes[f"{prefix}_post_norm"] = {"scale": ("ones", (e,))}
        if prefix != "mtp" and i < s["first_k_dense_replace"]:
            shapes[f"{prefix}_gate_up_proj"] = {
                "kernel": ("normal", (e, 2 * fd))}
            shapes[f"{prefix}_down_proj"] = {"kernel": ("out", (fd, e))}
        else:
            shapes[f"{prefix}_mixer"] = {
                "w_router": ("normal", (e, n)), "e_bias": ("zeros", (n,)),
                "w_gate": ("normal", (held, e, f)),
                "w_up": ("normal", (held, e, f)),
                "w_down": ("out", (held, f, e)),
                "ws_gate": ("normal", (e, fs)), "ws_up": ("normal", (e, fs)),
                "ws_down": ("out", (fs, e))}
    shapes["mtp_final_ln"] = {"scale": ("ones", (e,))}
    shapes["final_ln"] = {"scale": ("ones", (e,))}
    shapes["lm_head"] = {"kernel": ("normal", (e, v))}
    return shapes


def parameters(s):
    return sum(math.prod(shape) for leaves in weight_shapes(s).values()
               for _, shape in leaves.values())


def make_weights(s, seed):
    """All weights on the device in one jitted call from the seed, float32;
    the same tree goes to the program and to the reference."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(s)
    std = s["initializer_range"]
    scale = {"normal": std, "out": std / math.sqrt(s["published_depth"]),
             "embed": s["embedding_std"]}
    constant = {"ones": 1.0, "zeros": 0.0}

    def init(key, ids):
        out = {}
        for name, leaves in shapes.items():
            out[name] = {}
            for pname, (kind, shape) in leaves.items():
                key, sub = jax.random.split(key)
                out[name][pname] = (
                    jnp.full(shape, constant[kind], jnp.float32)
                    if kind in constant else
                    scale[kind] * jax.random.normal(sub, shape, jnp.float32))
        return balance_routers(out, ids, s)

    # the ids are an argument, not a constant of the program: every seed
    # then runs the one program the persistent cache holds
    ids = make_data(dict(s, steps_per_epoch=1), seed)[0][0]
    return jax.jit(init)(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                         jnp.asarray(ids))


def balance_routers(w, ids, s):
    """Set every router's score-correction bias `e_bias` to the balanced
    state on the seed's first batch, layer by layer, in the reference's
    float32 arithmetic, exactly as `nemotron_h.balance_routers` does and
    for its reason: b_e = -(the score of expert e that a share k / E of
    the batch's tokens exceeds). A trained model's routers are balanced,
    and a step's work should not depend on the seed. (Here the loads are
    near balance with the bias at zero too, 0.94-1.07 of the held share
    by the CPU census in the configuration's file: the router reads the
    norm of x + attention, in which a token's own embedding row
    dominates.) The module's router is balanced on what the module
    reads. The measured steps leave the bias as set here."""
    import jax.numpy as jnp

    ref, kw = reference_module, reference_kw(s)
    k, n = s["num_experts_per_tok"], s["n_routed_experts_published"]
    embedded = x = w["embed_tokens"]["kernel"][ids]
    for prefix in layer_prefixes(s):
        if prefix == "mtp":
            x = ref.mtp_input(w, embedded, x, kw, "f32")
        if f"{prefix}_mixer" in w:
            _, g = ref.attended(x, w, prefix, kw, "f32")
            scores = ref.router_scores(g, w[f"{prefix}_mixer"]["w_router"])
            mark = jnp.quantile(scores.reshape(-1, n), 1.0 - k / n, axis=0)
            w = dict(w, **{f"{prefix}_mixer": dict(
                w[f"{prefix}_mixer"],
                e_bias=w[f"{prefix}_mixer"]["e_bias"] - mark)})
        x = ref.layer(x, w, prefix, kw, "f32")
    return w


def build(config, s, chips, seed, machine_spec=None):
    import jax.numpy as jnp

    from flexflow_tpu import AdamOptimizer, FFConfig, LossType
    from flexflow_tpu.models import DecoderConfig, create_decoder

    # `program_*`: the controls of the mechanisms run the PROGRAM built
    # otherwise than the reference
    dc = DecoderConfig(
        hybrid_override_pattern=decoder_pattern(s),
        vocab_size=s["vocab_size"], hidden_size=s["hidden_size"],
        layer_norm_epsilon=s["rms_norm_eps"],
        num_attention_heads=s["num_attention_heads"],
        q_lora_rank=s["q_lora_rank"], kv_lora_rank=s["kv_lora_rank"],
        qk_nope_head_dim=s["qk_nope_head_dim"],
        qk_rope_head_dim=s["qk_rope_head_dim"], v_head_dim=s["v_head_dim"],
        rope_theta=float(s["rope_theta"]),
        rope_whole_head=s.get("program_rope_whole_head", False),
        intermediate_size=s["intermediate_size"],
        hidden_act=s["hidden_act"],
        n_routed_experts=s["n_routed_experts_published"],
        experts_held=s["n_routed_experts"],
        expert_offset=s["expert_offset"],
        num_experts_per_tok=s["num_experts_per_tok"],
        n_shared_experts=s["n_shared_experts"],
        moe_intermediate_size=s["moe_intermediate_size"],
        routed_scaling_factor=s["routed_scaling_factor"],
        norm_topk_prob=s["norm_topk_prob"], slot_slack=s["slot_slack"],
        num_nextn_predict_layers=s["num_nextn_predict_layers"],
        mtp_shift=s.get("program_mtp_shift", 1),
        batch_size=s["batch"], seq_length=s["seq"])
    cfg = FFConfig(batch_size=s["batch"], workers_per_node=chips,
                   seed=seed % (2 ** 31 - 1),
                   search_budget=config["search_budget"],
                   enable_parameter_parallel=chips > 1)
    ff = create_decoder(dc, cfg)
    adam = config["adam"]
    ff.compile(AdamOptimizer(alpha=adam["alpha"], beta1=adam["beta1"],
                             beta2=adam["beta2"], epsilon=adam["epsilon"],
                             weight_decay=adam["weight_decay"],
                             state_dtype=jnp.dtype(adam["state_dtype"])),
               LossType.WEIGHTED_SPARSE_CATEGORICAL_CROSSENTROPY, [],
               machine_spec=machine_spec)
    return ff


def reference_kw(s):
    return dict(num_hidden_layers=s["num_hidden_layers"],
                eps=s["rms_norm_eps"], rope_theta=float(s["rope_theta"]),
                num_experts_per_tok=s["num_experts_per_tok"],
                routed_scaling_factor=s["routed_scaling_factor"],
                expert_offset=s["expert_offset"])


def reference(s, traffic):
    """(module, keyword arguments of its forward, samples a chunk). The
    weight between the reference's two losses is the configuration's."""
    reference_module.LOSS_WEIGHT = s["mtp_loss_weight"]
    return reference_module, reference_kw(s), traffic.get("reference_chunk",
                                                          1)


# ---------------------------------------------------------------------------
# operations and bytes, counted for the work done HERE (the experts held,
# the vocabulary held)


def visible_pairs(s):
    """(query, key) pairs of one causal sequence, a head."""
    return s["seq"] * (s["seq"] + 1) // 2


def expected_held_slots(s):
    """(token, slot) pairs a step that land on a held expert, a layer, if
    routing is uniform: tokens * k * held / published."""
    return (s["batch"] * s["seq"] * s["num_experts_per_tok"]
            * s["n_routed_experts"] / s["n_routed_experts_published"])


def forward_flops_per_token(s):
    """Forward FLOPs a token by part (a multiply-add is 2): a latent
    attention's projections (both latents, the up-projections, the
    output's) and its causal scores over 192 + 128 lanes a head; the
    dense layer's MLP; an expert layer's feed-forward (router, shared
    expert, the expected held pairs); the module's projection; one head."""
    e, h = s["hidden_size"], s["num_attention_heads"]
    d, r, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    rq, rkv = s["q_lora_rank"], s["kv_lora_rank"]
    f = s["moe_intermediate_size"]
    share = s["n_routed_experts"] / s["n_routed_experts_published"]
    return {
        "projections": 2 * (e * rq + rq * h * (d + r) + e * (rkv + r)
                            + rkv * h * (d + dv) + h * dv * e),
        "scores": 2 * h * (d + r + dv) * visible_pairs(s) / s["seq"],
        "dense_mlp": 6 * e * s["intermediate_size"],
        "experts": (6 * e * f * s["num_experts_per_tok"] * share
                    + 6 * e * f * s["n_shared_experts"]
                    + 2 * e * s["n_routed_experts_published"]),
        "mtp_projection": 2 * 2 * e * e,
        "head": 2 * e * s["vocab_size"]}


def train_flops_per_sample(s):
    """FLOPs the forward and backward of one sample require (backward is
    twice the forward; no recomputation): S tokens through the trunk's
    layers, the module's layer and projection, and two heads."""
    per = forward_flops_per_token(s)
    dense = s["first_k_dense_replace"]
    mtp = s["num_nextn_predict_layers"]
    layers = s["num_hidden_layers"] + mtp
    total = (layers * (per["projections"] + per["scores"])
             + dense * per["dense_mlp"]
             + (layers - dense) * per["experts"]
             + mtp * per["mtp_projection"] + (1 + mtp) * per["head"])
    return 3 * s["seq"] * total


def latent_flash_step_flops_and_bytes(s):
    """What the flash kernels of the latent-attention ops need in one
    step, forward and backward, for the visible pairs counted exactly:
    forward 2 * (192 + 128) FLOPs a pair a head (Q K^T over the 192-wide
    query/key head, P V over the 128-wide value head), backward 2 * (128 +
    128 + 192 + 192) (dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q;
    the recomputed scores are not required work). Bytes in bfloat16, in
    the form the kernel is handed: the forward reads q (the not-rotated
    and the rotated parts), k (the heads' not-rotated parts and ONE
    rotated key a position), v and writes o; the backward reads those, o
    and dO and writes the gradients of q, k (the rotated key's a head at
    a time, 128 lanes each) and v."""
    ops = s["num_hidden_layers"] + s["num_nextn_predict_layers"]
    h = s["num_attention_heads"]
    d, r, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    pairs = s["batch"] * visible_pairs(s) * h
    flops = pairs * (2 * (d + r + dv) + 2 * (2 * dv + 2 * (d + r))) * ops
    q, k, v = h * (d + r), h * d + r, h * dv
    rows = 2 * s["batch"] * s["seq"]              # bytes of one lane's column
    forward = rows * (q + k + v + h * dv)
    backward = rows * (q + k + v + 2 * h * dv + q + h * d + h * 128 + v)
    return flops, (forward + backward) * ops


# ---------------------------------------------------------------------------
# checks, and what only the loaded program can tell


def extra_checks(ff, s, chips, on_tpu):
    out = []
    ops = s["num_hidden_layers"] + s["num_nextn_predict_layers"]
    latent = [n.op.name for n in ff.executor.nodes
              if getattr(n.op, "latent", None)]
    out.append(("attention_all_latent", len(latent) == ops, latent))
    if on_tpu and chips == 1:
        impls = _attention_impls(ff)
        out.append(("attention_all_flash",
                    len(impls) == ops and set(impls.values()) == {"flash"},
                    impls))
    return out


def kernel_fallbacks(ff):
    """What makes a run not correct beside the comparison: attention ops
    that fell back from the searched kernel, pairs that the expert
    layers' buffer could not hold, and a count of target positions (the
    program's `loss/target_positions` of its last epoch) that is neither
    one batch's of the data made last nor the whole epoch's. Also
    prints the counters (the cell's `observed` line). The readers of this
    cell's metrics take their scopes from the join table the program
    writes, so no step is lowered a second time here."""
    out = {n.op.name: n.op._kernel_fallback for n in ff.executor.nodes
           if getattr(n.op, "_kernel_fallback", None)}
    counters = dict(getattr(ff, "op_counters", None) or {})
    if counters.get("moe/overflow_slots"):
        out["moe/overflow_slots"] = counters["moe/overflow_slots"]
    targets = counters.get("loss/target_positions")
    if targets not in _targets_by_batch + [sum(_targets_by_batch)]:
        out["loss/target_positions"] = dict(program=targets,
                                            data=list(_targets_by_batch))
    observed.clear()
    observed["op_counters"] = counters
    print(json.dumps(dict(
        phase="observed", op_counters=counters,
        target_positions_by_batch=list(_targets_by_batch))), flush=True)
    return out
