"""Family `sdar`: a decoder trained by the block-diffusion objective
(SDAR-30B-A3B-Chat: a noised and a clean copy of a sample in one sequence
of 2L positions under the three-part block mask, shared rotary positions,
a 1/t-weighted loss on the masked positions of the noised half; QK-normed
8:1 GQA, softmax top-8-of-128 SwiGLU experts), one chip's share of a
stated deployment, built through `flexflow_tpu.models.create_decoder` +
`FFModel.compile`. See `bert_ae.py` for what a family gives the harness.

`seq` is the SAMPLE's length L; a step's sequence is 2L positions.
`make_data` noises the samples here, on the host, with its own copy of
the program's `dataloader.block_diffusion_batch`: inputs `[n, 2L]` ids
(noised copy, then clean), labels `[n, L, 2]` float32 (the clean token
and its weight). `kernel_fallbacks` fills `observed` after the window, as
`nemotron_h.py`'s does, from a step lowered with THIS family's shapes, and
holds the program's `loss/target_positions` to the data's own count.
Beside that: `block_diffusion_flash_step_flops_and_bytes` and
`grouped_matmul_step_flops_and_bytes` for the kernel rooflines.
"""

import json
import time

import numpy as np

from benchmarks.families.nemotron_h import (  # noqa: F401  (the harness's)
    _attention_impls, hlo_scopes, install_weights, readback)
from benchmarks.references import sdar as reference_module

# Limits of the output check; both readings of each in PERF.md ("The output
# check"), from `run.py` and `seeds_check.py` on the chip at the cell's own
# sizes (PR 34). They are looser than the other decoder cells' for a reason
# the model gives: at a masked position of the noised half, where the
# logits are read, the residual stream is what attention wrote (the mask
# token's own row is small), and attention in bfloat16 is a few percent
# from float32; elsewhere the stream is the token's exact embedding. The
# reference with bfloat16 operands reads what the program reads.
# (a) pred_nrmse: RMS error of the noised half's logits on the first batch
#     over the standard deviation of the reference's. Program 0.084-0.093
#     over 14 seeds (the reference with bfloat16 operands 0.087-0.094),
#     float8 control 0.591-0.597 over 3: the limit sits between, 2.7 times
#     the program's largest and 2.4 times under the control's smallest. A
#     lower precision fails by this limit alone.
# (b) loss0_rel: relative error of the step-0 loss (the weighted one), a
#     guard on the loss, the weights and the label path. A few targets
#     weigh 1/t up to a thousand, so the loss carries the logits' error at
#     a handful of positions: program 7e-6 to 1.25e-3 over 14 seeds, the
#     float8 control 6.0e-4 to 1.8e-3 (it does not separate precisions, as
#     in the other cells, whose limit of 6e-5 this cell cannot take; the
#     harness has no way to leave the number out: PERF.md section 7). 3.2
#     times the program's largest.
# (c) later_loss_rel: largest relative error of the losses of steps 1-2
#     against the reference's own Adam steps; program at most 1.06e-3 over
#     14 seeds, Adam without bias correction 4.3e-3 to 4.7e-3 over 3: 2.4
#     times the program's largest and 1.7 times under the control's
#     smallest (which is reference against reference, and steady).
TOLERANCES = {"pred_nrmse": 0.25, "loss0_rel": 4.0e-3,
              "later_loss_rel": 2.5e-3}

# what the program showed of itself after the window (see kernel_fallbacks)
observed = {}
# positions with a target in each batch of the data made last (make_data)
_targets_by_batch = []

SIZE_KEYS = (
    "num_hidden_layers", "vocab_size", "hidden_size", "rms_norm_eps",
    "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
    "num_experts", "num_experts_published", "expert_offset",
    "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
    "hidden_act", "slot_slack", "block_length", "attention_mask",
    "shared_positions", "noise_t_min", "initializer_range", "embedding_std",
    "mask_embedding_std", "qk_norm_scale")


def sizes(config, traffic, overrides=None):
    s = {k: config[k] for k in SIZE_KEYS}
    s.update(seq=traffic["seq"], batch=traffic["batch"],
             steps_per_epoch=traffic["steps_per_epoch"])
    s.update(overrides or {})
    return s


def mask_id(s):
    """The last row held stands for the mask token (its published id lies
    outside the slice of the vocabulary)."""
    return s["vocab_size"] - 1


def decoder_pattern(s):
    return "D" * s["num_hidden_layers"]


def pattern_of(s):
    """A letter a mixer, as the readers of the accepted per-layer metrics
    count them: every layer is its attention and then `E`."""
    return "DE" * s["num_hidden_layers"]


def noised(x0, s, rng):
    """x0 [n, L] -> (ids [n, 2L] int32, labels [n, L, 2] float32): one t a
    block of `block_length` tokens, uniform on [noise_t_min, 1]; a token is
    the mask token with probability t; its weight 1/t where masked, else
    0. The benchmark's own copy of `dataloader.block_diffusion_batch`."""
    n, length = x0.shape
    b = s["block_length"]
    t = np.repeat(rng.uniform(s["noise_t_min"], 1.0, size=(n, length // b)),
                  b, axis=1)
    masked = rng.random((n, length)) < t
    ids = np.concatenate([np.where(masked, mask_id(s), x0), x0], axis=1)
    labels = np.stack([x0.astype(np.float32),
                       np.where(masked, 1.0 / t, 0.0).astype(np.float32)],
                      axis=-1)
    return np.ascontiguousarray(ids, np.int32), labels


def make_data(s, seed):
    """One epoch of samples, token ids uniform over the rows held but the
    mask token's, noised from the seed."""
    rng = np.random.default_rng(seed)
    n = s["batch"] * s["steps_per_epoch"]
    x0 = rng.integers(0, mask_id(s), size=(n, s["seq"]), dtype=np.int32)
    ids, labels = noised(x0, s, rng)
    per_sample = (labels[..., 1] > 0).sum(axis=1)
    _targets_by_batch[:] = [int(per_sample[i:i + s["batch"]].sum())
                            for i in range(0, n, s["batch"])]
    return [ids], labels


def weight_shapes(s):
    """name -> leaf -> (kind, shape); kinds: `normal` (std
    initializer_range), `embed` (std embedding_std, the mask token's row
    mask_embedding_std), `ones`, `qk` (the constant qk_norm_scale)."""
    e, v = s["hidden_size"], s["vocab_size"]
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    held, f = s["num_experts"], s["moe_intermediate_size"]
    shapes = {"embed_tokens": {"kernel": ("embed", (v, e))}}
    for i in range(s["num_hidden_layers"]):
        shapes[f"b{i}_norm"] = {"scale": ("ones", (e,))}
        shapes[f"b{i}_attn"] = {
            "wq": ("normal", (heads, e, d)), "wk": ("normal", (kv, e, d)),
            "wv": ("normal", (kv, e, d)), "wo": ("normal", (heads, d, e)),
            "q_norm": ("qk", (d,)), "k_norm": ("qk", (d,))}
        shapes[f"b{i}_post_norm"] = {"scale": ("ones", (e,))}
        shapes[f"b{i}_mixer"] = {
            "w_router": ("normal", (e, s["num_experts_published"])),
            "w_gate": ("normal", (held, e, f)),
            "w_up": ("normal", (held, e, f)),
            "w_down": ("normal", (held, f, e))}
    shapes["final_ln"] = {"scale": ("ones", (e,))}
    shapes["lm_head"] = {"kernel": ("normal", (e, v))}
    return shapes


def make_weights(s, seed):
    """All weights on the device in one jitted call from the seed, float32;
    the same tree goes to the program and to the reference."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(s)
    scale = {"normal": s["initializer_range"], "embed": s["embedding_std"]}
    constant = {"ones": 1.0, "qk": s["qk_norm_scale"]}

    def init(key):
        out = {}
        for name, leaves in shapes.items():
            out[name] = {}
            for pname, (kind, shape) in leaves.items():
                key, sub = jax.random.split(key)
                out[name][pname] = (
                    jnp.full(shape, constant[kind], jnp.float32)
                    if kind in constant else
                    scale[kind] * jax.random.normal(sub, shape, jnp.float32))
        rows = out["embed_tokens"]["kernel"]
        out["embed_tokens"]["kernel"] = rows.at[mask_id(s)].multiply(
            s["mask_embedding_std"] / s["embedding_std"])
        return out

    return jax.jit(init)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def build(config, s, chips, seed, machine_spec=None):
    import jax.numpy as jnp

    from flexflow_tpu import AdamOptimizer, FFConfig, LossType
    from flexflow_tpu.models import DecoderConfig, create_decoder

    # `program_*`: the two controls of the mechanism run the PROGRAM with
    # another mask or other positions than the reference's
    dc = DecoderConfig(
        hybrid_override_pattern=decoder_pattern(s),
        vocab_size=s["vocab_size"], hidden_size=s["hidden_size"],
        layer_norm_epsilon=s["rms_norm_eps"],
        num_attention_heads=s["num_attention_heads"],
        num_key_value_heads=s["num_key_value_heads"],
        head_dim=s["head_dim"], rope_theta=float(s["rope_theta"]),
        attention_mask=s.get("program_attention_mask", s["attention_mask"]),
        block_length=s["block_length"],
        shared_positions=s.get("program_shared_positions",
                               s["shared_positions"]),
        qk_norm=True, hidden_act=s["hidden_act"],
        n_routed_experts=s["num_experts_published"],
        experts_held=s["num_experts"], expert_offset=s["expert_offset"],
        num_experts_per_tok=s["num_experts_per_tok"],
        moe_intermediate_size=s["moe_intermediate_size"],
        norm_topk_prob=s["norm_topk_prob"], slot_slack=s["slot_slack"],
        batch_size=s["batch"], seq_length=2 * s["seq"])
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
                attention_mask=s["attention_mask"],
                block_length=s["block_length"],
                shared_positions=bool(s["shared_positions"]),
                num_experts_per_tok=s["num_experts_per_tok"],
                expert_offset=s["expert_offset"])


def reference(s, traffic):
    """(module, keyword arguments of its forward, samples a chunk)."""
    return reference_module, reference_kw(s), traffic.get("reference_chunk",
                                                          1)


# ---------------------------------------------------------------------------
# operations and bytes, counted for the work done HERE (the experts held,
# the heads held, the vocabulary held)


def visible_pairs(s):
    """(query, key) pairs of one 2L-position sequence that the block mask
    leaves, a head: noised-noised n B^2 (a block sees itself),
    noised-clean n (n - 1) / 2 B^2 and clean-clean n (n + 1) / 2 B^2 for
    the n = L / B blocks."""
    b = s["block_length"]
    n = s["seq"] // b
    return b * b * (n + n * (n - 1) // 2 + n * (n + 1) // 2)


def expected_held_slots(s):
    """(position, slot) pairs a step that land on a held expert, a layer,
    if routing is uniform: positions * k * held / published."""
    return (s["batch"] * 2 * s["seq"] * s["num_experts_per_tok"]
            * s["num_experts"] / s["num_experts_published"])


def forward_flops_per_position(s):
    """Forward FLOPs a position of the 2L by part (a multiply-add is 2):
    the four projections of an attention; Q K^T and P V over the visible
    pairs; the router, and the expected held pairs through an expert's
    three matrices. The head is counted apart: the noised half alone
    goes through it."""
    e = s["hidden_size"]
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    share = s["num_experts"] / s["num_experts_published"]
    return {
        "projections": 2 * e * d * (2 * heads + 2 * kv),
        "scores": 4 * heads * d * visible_pairs(s) / (2 * s["seq"]),
        "experts": (6 * e * s["moe_intermediate_size"]
                    * s["num_experts_per_tok"] * share),
        "router": 2 * e * s["num_experts_published"]}


def train_flops_per_sample(s):
    """FLOPs the forward and backward of one sample require (backward is
    twice the forward; no recomputation): 2L positions through the
    layers, L through the head."""
    per = forward_flops_per_position(s)
    layers = s["num_hidden_layers"] * sum(per.values())
    head = 2 * s["hidden_size"] * s["vocab_size"]
    return 3 * s["seq"] * (2 * layers + head)


def block_diffusion_flash_step_flops_and_bytes(s):
    """What the flash kernels of the layers need in one step, forward and
    backward: 4 * pairs * heads * head_dim FLOPs forward (Q K^T, P V) and
    twice that backward, for the visible pairs counted exactly. Bytes in
    bfloat16: the forward reads q, k, v and writes o; the backward reads
    q, k, v, o, dO and writes dQ, dK, dV; k and v as the kernels take
    them, repeated to the query heads."""
    layers = s["num_hidden_layers"]
    width = s["num_attention_heads"] * s["head_dim"]
    flops = 12 * s["batch"] * visible_pairs(s) * width * layers
    nbytes = 12 * 2 * s["batch"] * 2 * s["seq"] * width * layers
    return flops, nbytes


def grouped_matmul_step_flops_and_bytes(s, slots=None):
    """What the three grouped products of every expert layer need in one
    step, forward and backward, for `slots` (position, slot) pairs a layer
    that landed on held experts (the expected number by default). FLOPs
    3 * 6 * slots * hidden * width a layer. Bytes in bfloat16: each of the
    nine products (three forward, three for the rows' gradients, three
    for the weights') reads or writes the held experts' matrix once and
    the rows' operands and result once."""
    slots = expected_held_slots(s) if slots is None else slots
    e, f = s["hidden_size"], s["moe_intermediate_size"]
    layers = s["num_hidden_layers"]
    flops = 3 * 6 * slots * e * f * layers
    weights = 2 * s["num_experts"] * e * f   # one matrix, bytes
    rows = 2 * slots * (e + f)
    return flops, 9 * (weights + rows) * layers


# ---------------------------------------------------------------------------
# checks, and what only the loaded program can tell


def extra_checks(ff, s, chips, on_tpu):
    out = []
    if on_tpu and chips == 1:
        impls = _attention_impls(ff)
        out.append(("attention_all_flash",
                    len(impls) == s["num_hidden_layers"]
                    and set(impls.values()) == {"flash"}, impls))
    return out


def scopes_of_compiled_step(ff):
    """HLO instruction name -> `op_name` of the compiled train step, from
    the step that is already compiled: lowering it again with arguments of
    the same shapes finds it in JAX's caches."""
    import jax

    batch, positions = ff.input_tensors[0].shape
    ids = np.zeros((batch, positions), np.int32)
    labels = np.zeros((batch, positions // 2, 2), np.float32)
    step = ff.executor.make_train_step()
    text = step.lower(ff.params, ff.opt_state, ff.state,
                      ff._stage_inputs([ids]), ff._shard_batch(labels),
                      jax.random.PRNGKey(0)).compile().as_text()
    return hlo_scopes(text)


def kernel_fallbacks(ff):
    """What makes a run not correct beside the comparison: attention ops
    that fell back from the searched kernel, pairs that the expert
    layers' buffer could not hold, and a count of target positions (the
    program's `loss/target_positions` of its last epoch) that is neither
    one batch's of the data made last nor the whole epoch's. Also
    records, for the readers of the per-layer metrics, what only the
    loaded program can tell."""
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
    t0 = time.perf_counter()
    try:
        observed["scopes"] = scopes_of_compiled_step(ff)
    except Exception as e:      # the readers then return nothing
        observed["scopes_error"] = repr(e)
    print(json.dumps(dict(
        phase="observed", op_counters=counters,
        target_positions_by_batch=list(_targets_by_batch),
        scoped_instructions=len(observed.get("scopes", ())),
        scopes_error=observed.get("scopes_error"),
        scopes_s=time.perf_counter() - t0)), flush=True)
    return out
