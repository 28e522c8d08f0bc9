"""Family `laguna`: a decoder whose layers differ in their number of
query heads (Laguna-XS.2: 48 on the full-attention layers, 64 on the
window-512 layers, 8 key/value heads on both, a per-head softplus gate on
the attention output, half-rotated YaRN heads on the full layers and
whole-head rotary on the window layers, one leading dense SwiGLU layer,
sigmoid top-8-of-256 experts with a gated shared expert), one chip's
share of a stated deployment, built through
`flexflow_tpu.models.create_decoder` + `FFModel.compile`.

What `families/laguna.py` answers (the contract `benchmarks/README.md`
states for every family; `harness.run_cell` and `seeds_check.py` call
these and nothing else):
    sizes(config, traffic, overrides)   the sizes as run; ends at once
                                        (SystemExit) on a program without
                                        per-layer head counts
    make_data(s, seed)                  ([ids [n, S]], labels [n, S]), the
                                        next token
    make_weights(s, seed)               every leaf, float32, on the device,
                                        the routers' bias balanced
    build(config, s, chips, seed, machine_spec)   the compiled FFModel
    install_weights(ff, weights), readback(ff, weights)
    extra_checks(ff, s, chips, on_tpu)  (name, ok, detail) rows
    kernel_fallbacks(ff)                what makes a run not correct
                                        beside the comparison; fills
                                        `observed` for the readers
    reference(s, traffic)               (module, keyword arguments, chunk)
    train_flops_per_sample(s)           for `device.mfu_pct`
    TOLERANCES                          the output check's limits
    narrow_window_flash_step_flops_and_bytes(s)   for the kernel roofline
The controls of the mechanisms go through `program_*` size overrides
(`seeds_check.check_seeds(cell, seeds, rehearsal=dict(sizes=...))` one
control a call, or `scripts/program_controls.py` all of them in one
process with one reference run), which build the PROGRAM otherwise and
leave the reference as the cell states it; each has to come out not
correct:
    program_gating=False                     the gate left out
    program_gate_activation="sigmoid"        sigmoid for softplus
    program_full_partial_rotary_factor=1.0   whole-head rotary on the
                                             full-attention layers
    program_full_rope_type="default"         plain theta-500,000 rotary:
                                             no YaRN table, no
                                             attention_factor
    program_sliding_window=4096              a window of 4096 for 512
"""

import json
import math

import numpy as np

from benchmarks.families.nemotron_h import (  # noqa: F401  (the harness's)
    _attention_impls, make_data, readback)
from benchmarks.references import laguna as reference_module

# Limits of the output check; both readings of each in PERF.md ("The output
# check"), from `run.py`, `seeds_check.py` and `scripts/program_controls.py`
# on the chip at the cell's own sizes (PR 41).
# (a) pred_nrmse: RMS error of the logits on the first batch over the
#     standard deviation of the reference's. Program 0.00248-0.00254 over
#     12 seeds (the reference with bfloat16 operands reads the same,
#     0.00248-0.00252: the error is the operands' rounding and hardly
#     varies), float8 control 0.0390 over 2, and the five mechanism
#     controls (the program built otherwise) 0.0131 (plain rotary for
#     YaRN's), 0.0167 (sigmoid for softplus), 0.0209 (no gate), 0.0226
#     (window 4096 for 512), 0.0267 (whole-head rotary on the full
#     layers). The limit stands 1.57 times over the program's largest,
#     3.3 times under the smallest mechanism control and 9.7 times under
#     the float8 control. A lower precision, and each mechanism left out
#     or changed, fails by this limit alone.
# (b) loss0_rel: relative error of the step-0 loss, a guard on the loss
#     and label path. The precision hardly moves it (program at most
#     1.3e-5, the float8 control 3.9e-6 and 1.7e-5; the mechanism controls
#     7e-6 to 3.3e-5: with seeded weights the loss is near ln V whatever
#     attention does), so the accepted decoder cells' limit, 4.8 times the
#     program's largest.
# (c) later_loss_rel: largest relative error of the losses of steps 1-2
#     against the reference's own Adam steps; program at most 1.4e-5, so
#     likewise (4.3 times). Adam without bias correction reads 2.8e-4.
TOLERANCES = {"pred_nrmse": 4.0e-3, "loss0_rel": 6.0e-5,
              "later_loss_rel": 6.0e-5}

# what the program showed of itself after the window (see kernel_fallbacks)
observed = {}

SIZE_KEYS = (
    "num_hidden_layers", "vocab_size", "hidden_size", "rms_norm_eps",
    "num_attention_heads", "num_attention_heads_per_layer",
    "num_key_value_heads", "head_dim", "layer_types", "mlp_layer_types",
    "rope_parameters", "sliding_window", "gating", "intermediate_size",
    "num_experts", "num_experts_published", "expert_offset",
    "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "moe_routed_scaling_factor",
    "norm_topk_prob", "hidden_act", "slot_slack", "initializer_range",
    "embedding_std", "published_depth")
ATTENTION_KINDS = ("full_attention", "sliding_attention")


def sizes(config, traffic, overrides=None):
    # a program without per-layer head counts (an older commit under these
    # files) ends here, at once, before any weight is made
    import dataclasses

    from flexflow_tpu.models import DecoderConfig
    if "num_attention_heads_per_layer" not in {
            f.name for f in dataclasses.fields(DecoderConfig)}:
        raise SystemExit("family laguna: this program's decoder has no "
                         "per-layer head counts, gate or partial rotary "
                         "(flexflow_tpu PR 41)")
    s = {k: config[k] for k in SIZE_KEYS}
    s.update(seq=traffic["seq"], batch=traffic["batch"],
             steps_per_epoch=traffic["steps_per_epoch"])
    s.update(overrides or {})
    # the published lists are kept whole; the layers that run read their
    # first entries
    n = s["num_hidden_layers"]
    for k in ("layer_types", "mlp_layer_types",
              "num_attention_heads_per_layer"):
        s[k] = list(s[k][:n])
    return s


def rope_of(s, kind):
    """One attention kind's rotary parameters, as the config groups them."""
    return dict(s["rope_parameters"][kind])


def weight_shapes(s):
    """name -> leaf -> (kind, shape); kinds: `normal` (std
    initializer_range), `out` (that over the square root of the published
    depth), `embed` (std embedding_std), `ones`, `zeros` (the routers'
    bias, which `balance_routers` then sets)."""
    e, v, d = s["hidden_size"], s["vocab_size"], s["head_dim"]
    kv = s["num_key_value_heads"]
    held, f = s["num_experts"], s["moe_intermediate_size"]
    fs, fd = s["shared_expert_intermediate_size"], s["intermediate_size"]
    n = s["num_experts_published"]
    shapes = {"embed_tokens": {"kernel": ("embed", (v, e))}}
    for i in range(s["num_hidden_layers"]):
        h = s["num_attention_heads_per_layer"][i]
        shapes[f"b{i}_norm"] = {"scale": ("ones", (e,))}
        shapes[f"b{i}_attn"] = {
            "wq": ("normal", (h, e, d)), "wk": ("normal", (kv, e, d)),
            "wv": ("normal", (kv, e, d)), "wo": ("out", (h, d, e))}
        if s["gating"]:
            shapes[f"b{i}_attn"]["w_gate"] = ("normal", (e, h))
        shapes[f"b{i}_post_norm"] = {"scale": ("ones", (e,))}
        if s["mlp_layer_types"][i] == "dense":
            shapes[f"b{i}_gate_up_proj"] = {
                "kernel": ("normal", (e, 2 * fd))}
            shapes[f"b{i}_down_proj"] = {"kernel": ("out", (fd, e))}
        else:
            shapes[f"b{i}_mixer"] = {
                "w_router": ("normal", (e, n)), "e_bias": ("zeros", (n,)),
                "w_gate": ("normal", (held, e, f)),
                "w_up": ("normal", (held, e, f)),
                "w_down": ("out", (held, f, e)),
                "ws_gate": ("normal", (e, fs)), "ws_up": ("normal", (e, fs)),
                "ws_down": ("out", (fs, e))}
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
    float32 arithmetic, exactly as `joyai_flash.balance_routers` and
    `nemotron_h.balance_routers` do and for their reason: b_e = -(the
    score of expert e that a share k / E of the batch's tokens exceeds).
    A trained model's routers are balanced, and a step's work should not
    depend on the seed. The measured steps leave the bias as set here."""
    import jax.numpy as jnp

    ref, kw = reference_module, reference_kw(s)
    k, n = s["num_experts_per_tok"], s["num_experts_published"]
    x = w["embed_tokens"]["kernel"][ids]
    for i in range(s["num_hidden_layers"]):
        if f"b{i}_mixer" in w:
            _, g = ref.attended(x, w, i, kw, "f32")
            scores = ref.router_scores(g, w[f"b{i}_mixer"]["w_router"])
            mark = jnp.quantile(scores.reshape(-1, n), 1.0 - k / n, axis=0)
            w = dict(w, **{f"b{i}_mixer": dict(
                w[f"b{i}_mixer"],
                e_bias=w[f"b{i}_mixer"]["e_bias"] - mark)})
        x = ref.layer(x, w, i, kw, "f32")
    return w


def program_rope_parameters(s):
    """The rotary parameters the PROGRAM is built with: the cell's, but
    for the two controls of the full-attention layers' rotary form."""
    rope = {kind: rope_of(s, kind) for kind in ATTENTION_KINDS}
    full = rope["full_attention"]
    if "program_full_partial_rotary_factor" in s:
        full["partial_rotary_factor"] = s[
            "program_full_partial_rotary_factor"]
    if "program_full_rope_type" in s:
        full["rope_type"] = s["program_full_rope_type"]
    return rope


def build(config, s, chips, seed, machine_spec=None):
    import jax.numpy as jnp

    from flexflow_tpu import AdamOptimizer, FFConfig, LossType
    from flexflow_tpu.models import DecoderConfig, create_decoder

    # `program_*`: the controls of the mechanisms run the PROGRAM built
    # otherwise than the reference (module docstring)
    dc = DecoderConfig(
        layer_types=s["layer_types"],
        mlp_layer_types=s["mlp_layer_types"],
        num_attention_heads_per_layer=s["num_attention_heads_per_layer"],
        rope_parameters=program_rope_parameters(s),
        gating=s.get("program_gating", s["gating"]),
        gate_activation=s.get("program_gate_activation", "softplus"),
        vocab_size=s["vocab_size"], hidden_size=s["hidden_size"],
        layer_norm_epsilon=s["rms_norm_eps"],
        num_attention_heads=s["num_attention_heads"],
        num_key_value_heads=s["num_key_value_heads"],
        head_dim=s["head_dim"],
        sliding_window_size=s.get("program_sliding_window",
                                  s["sliding_window"]),
        intermediate_size=s["intermediate_size"],
        hidden_act=s["hidden_act"],
        n_routed_experts=s["num_experts_published"],
        experts_held=s["num_experts"], expert_offset=s["expert_offset"],
        num_experts_per_tok=s["num_experts_per_tok"],
        moe_intermediate_size=s["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=s[
            "shared_expert_intermediate_size"],
        routed_scaling_factor=s["moe_routed_scaling_factor"],
        norm_topk_prob=s["norm_topk_prob"], slot_slack=s["slot_slack"],
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
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [],
               machine_spec=machine_spec)
    return ff


def install_weights(ff, weights):
    """Every leaf through `set_parameter`; a program built without the
    gate (the control `program_gating=False`) has no `w_gate` to take."""
    gated = {n.op.name for n in ff.executor.nodes
             if getattr(n.op, "gate", False)}
    for name, leaves in weights.items():
        for pname, value in leaves.items():
            if (pname == "w_gate" and name.endswith("_attn")
                    and name not in gated):
                continue
            ff.set_parameter(name, value, pname)


def reference_kw(s):
    """Keyword arguments of the reference's forward; every value can be
    hashed (`common.compiled` keeps one program a set of them)."""
    n = s["num_hidden_layers"]
    return dict(num_hidden_layers=n, eps=s["rms_norm_eps"],
                layer_types=tuple(s["layer_types"][:n]),
                rope_full=tuple(sorted(
                    rope_of(s, "full_attention").items())),
                rope_sliding=tuple(sorted(
                    rope_of(s, "sliding_attention").items())),
                sliding_window=s["sliding_window"],
                num_experts_per_tok=s["num_experts_per_tok"],
                routed_scaling_factor=s["moe_routed_scaling_factor"],
                expert_offset=s["expert_offset"])


def reference(s, traffic):
    """(module, keyword arguments of its forward, samples a chunk)."""
    return reference_module, reference_kw(s), traffic.get("reference_chunk",
                                                          1)


# ---------------------------------------------------------------------------
# operations and bytes, counted for the work done HERE (the experts held,
# the vocabulary held; attention whole)


def visible_pairs(seq, window=0):
    """(query, key) pairs of one sequence with key <= query and, under a
    window, query - key < window: counted exactly (S * W - W (W - 1) / 2)."""
    w = min(window, seq) if window else seq
    return w * (w + 1) // 2 + (seq - w) * w


def expected_held_slots(s):
    """(token, slot) pairs a step that land on a held expert, a layer, if
    routing is uniform: tokens * k * held / published."""
    return (s["batch"] * s["seq"] * s["num_experts_per_tok"]
            * s["num_experts"] / s["num_experts_published"])


def layer_heads(s, kind):
    """The query heads of the layers of one attention kind."""
    return [h for h, k in zip(s["num_attention_heads_per_layer"],
                              s["layer_types"]) if k == kind]


def forward_flops_per_token(s):
    """Forward FLOPs a token by part (a multiply-add is 2), added over
    the layers that run: the four projections and the gate's product of
    every attention op, with the op's own heads; Q K^T and P V over the
    visible pairs of the full and of the window layers; the dense layer's
    MLP; the expert layers' feed-forward (router, shared expert, the
    expected held pairs); the head."""
    e, d, seq = s["hidden_size"], s["head_dim"], s["seq"]
    kv = s["num_key_value_heads"]
    gate = 1 if s["gating"] else 0
    full = layer_heads(s, "full_attention")
    window = layer_heads(s, "sliding_attention")
    f = s["moe_intermediate_size"]
    share = s["num_experts"] / s["num_experts_published"]
    sparse = s["mlp_layer_types"].count("sparse")
    return {
        "projections": sum(2 * e * d * (2 * h + 2 * kv) + gate * 2 * e * h
                           for h in full + window),
        "full_scores": sum(4 * h * d * visible_pairs(seq) / seq
                           for h in full),
        "window_scores": sum(
            4 * h * d * visible_pairs(seq, s["sliding_window"]) / seq
            for h in window),
        "dense_mlp": (6 * e * s["intermediate_size"]
                      * s["mlp_layer_types"].count("dense")),
        "experts": sparse * (
            6 * e * f * s["num_experts_per_tok"] * share
            + 6 * e * s["shared_expert_intermediate_size"]
            + 2 * e * s["num_experts_published"]),
        "head": 2 * e * s["vocab_size"]}


def train_flops_per_sample(s):
    """FLOPs the forward and backward of one sample require (backward is
    twice the forward; no recomputation)."""
    return 3 * s["seq"] * sum(forward_flops_per_token(s).values())


def narrow_window_flash_step_flops_and_bytes(s):
    """What the flash kernels of the window layers need in one step,
    forward and backward, over the VISIBLE pairs counted exactly: forward
    4 * head_dim FLOPs a pair a head (Q K^T, P V), backward 8 * head_dim
    (dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q; the recomputed
    scores are not required work), so a key that is visited and hidden
    lowers the share and can never lift it. Bytes in bfloat16: the
    forward reads q, k, v and writes o; the backward reads q, k, v, o, dO
    and writes dQ, dK, dV; k and v as the kernels take them, repeated to
    the query heads."""
    pairs = s["batch"] * visible_pairs(s["seq"], s["sliding_window"])
    width = sum(layer_heads(s, "sliding_attention")) * s["head_dim"]
    return 12 * pairs * width, 12 * 2 * s["batch"] * s["seq"] * width


# ---------------------------------------------------------------------------
# checks, and what only the loaded program can tell


def extra_checks(ff, s, chips, on_tpu):
    out = []
    ops = {n.op.name: n.op for n in ff.executor.nodes
           if hasattr(n.op, "num_kv_heads")}
    heads = [ops[f"b{i}_attn"].num_heads
             for i in range(s["num_hidden_layers"])]
    out.append(("attention_heads_by_layer",
                heads == s["num_attention_heads_per_layer"], heads))
    if on_tpu and chips == 1:
        impls = _attention_impls(ff)
        out.append(("attention_all_flash",
                    len(impls) == s["num_hidden_layers"]
                    and set(impls.values()) == {"flash"}, impls))
    return out


def kernel_fallbacks(ff):
    """What makes a run not correct beside the comparison: attention ops
    that fell back from the searched kernel, and pairs that the expert
    layers' buffer could not hold. Also prints the counters (the cell's
    `observed` line) and keeps them for the reader of
    `kernels.window_keys_visited_ratio`. The readers of the device-trace
    metrics take their scopes from the join table the program writes, so
    no step is lowered a second time here."""
    out = {n.op.name: n.op._kernel_fallback for n in ff.executor.nodes
           if getattr(n.op, "_kernel_fallback", None)}
    counters = dict(getattr(ff, "op_counters", None) or {})
    if counters.get("moe/overflow_slots"):
        out["moe/overflow_slots"] = counters["moe/overflow_slots"]
    observed.clear()
    observed["op_counters"] = counters
    print(json.dumps(dict(phase="observed", op_counters=counters)),
          flush=True)
    return out
