"""Family `lfm2`: a decoder whose sequence mixer is a gated short
convolution in three layers of four and causal grouped-query attention
in the fourth (LFM2-8B-A1B: 3 taps; 32 query and 8 key/value heads of 64
with a norm of every query and key head ahead of whole-head rotary), a
SwiGLU MLP in the leading dense layers and sigmoid top-4-of-32 experts
with a score-correction bias and NO shared expert after them, and ONE
table for the embedding and the head, one chip's share of a stated
deployment, built through `flexflow_tpu.models.create_decoder` +
`FFModel.compile`.

What `families/lfm2.py` answers (the contract `benchmarks/README.md`
states for every family; `harness.run_cell` and `seeds_check.py` call
these and nothing else):
    sizes(config, traffic, overrides)   the sizes as run; ends at once
                                        (SystemExit) on a program without
                                        the convolution mixer
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
    gated_conv_step_flops_and_bytes(s)  for the kernel roofline
The layers that run are the configuration's `num_hidden_layers` from
its `first_layer` on (published layers 1-5); program and reference name
them b0-b4 in that order.
The controls of the mechanisms go through `program_*` size overrides
(`seeds_check.check_seeds(cell, seeds, rehearsal=dict(sizes=...))` one
control a call, or `scripts/program_controls.py` all of them in one
process with one reference run), which build the PROGRAM otherwise and
leave the reference as the cell states it; each has to come out not
correct:
    program_conv_L_cache=2           the convolution with two taps (the
                                     oldest, w_0, left out)
    program_conv_output_gate=False   the output gate C left out
    program_tie_word_embeddings=False   the head given a table of its own
                                     (its own seeded initialisation)
    program_qk_layernorm=False       the heads' norm left out
"""

import json
import math

import numpy as np

from benchmarks.families.nemotron_h import (  # noqa: F401  (the harness's)
    _attention_impls, make_data)
from benchmarks.references import lfm2 as reference_module

# Limits of the output check; both readings of each in PERF.md ("The output
# check"), from `run.py`, `seeds_check.py` and `scripts/program_controls.py`
# on the chip at the cell's own sizes (PR 45).
# pred_nrmse: the program reads 0.049-0.050 on every seed (the reference
#   with bfloat16 operands 0.046: the table is drawn at 0.02, so the stream
#   holds no exact component and every layer's rounding reaches the logits
#   at unit gain), the float8 control 0.373, the four mechanism controls
#   0.67-1.41: 0.12 is 2.4 times the first and 3.1 times under the second.
# loss0_rel, later_loss_rel: the mean over 16,384 positions of that logit
#   error is 4e-5 of a loss of 9.4, three times the other decoder cells',
#   whose 6e-5 one seed in eleven passed (7.3e-5; 6.5e-5 for the later
#   losses): 2.5e-4 is 3.4 times the largest reading and six times the
#   readings' root mean square (4e-5), 13 times under the wrong-Adam
#   control (3.3e-3) and under what labels moved by one position would
#   read (reckoned, not run: 0.9 / sqrt(16,384) of 9.4 = 7e-4).
TOLERANCES = {"pred_nrmse": 0.12, "loss0_rel": 2.5e-4,
              "later_loss_rel": 2.5e-4}

# what the program showed of itself after the window (see kernel_fallbacks)
observed = {}

SIZE_KEYS = (
    "num_hidden_layers", "first_layer", "num_dense_layers", "vocab_size",
    "hidden_size", "norm_eps", "num_attention_heads",
    "num_key_value_heads", "head_dim", "layer_types", "rope_theta",
    "conv_L_cache", "intermediate_size", "num_experts",
    "num_experts_published", "expert_offset", "num_experts_per_tok",
    "moe_intermediate_size", "routed_scaling_factor", "norm_topk_prob",
    "tie_word_embeddings", "hidden_act", "slot_slack",
    "initializer_range", "embedding_std", "qk_norm_scale",
    "published_depth")


def sizes(config, traffic, overrides=None):
    # a program without the convolution mixer (an older commit under
    # these files) ends here, at once, before any weight is made
    import dataclasses

    from flexflow_tpu.models import DecoderConfig
    if "conv_L_cache" not in {f.name for f in
                              dataclasses.fields(DecoderConfig)}:
        raise SystemExit("family lfm2: this program's decoder has no "
                         "gated short convolution or tied head "
                         "(flexflow_tpu PR 45)")
    s = {k: config[k] for k in SIZE_KEYS}
    s.update(seq=traffic["seq"], batch=traffic["batch"],
             steps_per_epoch=traffic["steps_per_epoch"])
    s.update(overrides or {})
    # the published list is kept whole; the layers that run are the
    # num_hidden_layers from first_layer on, and those of them below the
    # published num_dense_layers carry the dense MLP
    first, n = s["first_layer"], s["num_hidden_layers"]
    s["layer_types"] = list(s["layer_types"][first:first + n])
    s["dense_layers"] = max(0, min(n, s["num_dense_layers"] - first))
    return s


def is_dense(s, i):
    return i < s["dense_layers"]


def weight_shapes(s):
    """name -> leaf -> (kind, shape); kinds: `normal` (std
    initializer_range), `out` (that over the square root of the published
    depth), `embed` (std embedding_std), `taps` (uniform in +-1/sqrt(K)),
    `ones`, `qk` (the constant qk_norm_scale), `zeros` (the routers'
    bias, which `balance_routers` then sets). ONE table: there is no
    `lm_head`."""
    e, v, d = s["hidden_size"], s["vocab_size"], s["head_dim"]
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    held, f = s["num_experts"], s["moe_intermediate_size"]
    fd, n = s["intermediate_size"], s["num_experts_published"]
    shapes = {"embed_tokens": {"kernel": ("embed", (v, e))}}
    for i, kind in enumerate(s["layer_types"]):
        shapes[f"b{i}_norm"] = {"scale": ("ones", (e,))}
        if kind == "conv":
            shapes[f"b{i}_conv"] = {
                "w_in": ("normal", (e, 3 * e)),
                "conv_w": ("taps", (s["conv_L_cache"], e)),
                "w_out": ("out", (e, e))}
        else:
            shapes[f"b{i}_attn"] = {
                "wq": ("normal", (h, e, d)), "wk": ("normal", (kv, e, d)),
                "wv": ("normal", (kv, e, d)), "wo": ("out", (h, d, e)),
                "q_norm": ("qk", (d,)), "k_norm": ("qk", (d,))}
        shapes[f"b{i}_post_norm"] = {"scale": ("ones", (e,))}
        if is_dense(s, i):
            shapes[f"b{i}_gate_up_proj"] = {
                "kernel": ("normal", (e, 2 * fd))}
            shapes[f"b{i}_down_proj"] = {"kernel": ("out", (fd, e))}
        else:
            shapes[f"b{i}_mixer"] = {
                "w_router": ("normal", (e, n)), "e_bias": ("zeros", (n,)),
                "w_gate": ("normal", (held, e, f)),
                "w_up": ("normal", (held, e, f)),
                "w_down": ("out", (held, f, e))}
    shapes["final_ln"] = {"scale": ("ones", (e,))}
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
    constant = {"ones": 1.0, "zeros": 0.0, "qk": s["qk_norm_scale"]}

    def init(key, ids):
        out = {}
        for name, leaves in shapes.items():
            out[name] = {}
            for pname, (kind, shape) in leaves.items():
                key, sub = jax.random.split(key)
                if kind in constant:
                    leaf = jnp.full(shape, constant[kind], jnp.float32)
                elif kind == "taps":
                    bound = 1.0 / math.sqrt(shape[0])
                    leaf = jax.random.uniform(sub, shape, jnp.float32,
                                              -bound, bound)
                else:
                    leaf = scale[kind] * jax.random.normal(
                        sub, shape, jnp.float32)
                out[name][pname] = leaf
        return balance_routers(out, ids, s)

    # the ids are an argument, not a constant of the program: every seed
    # then runs the one program the persistent cache holds
    ids = make_data(dict(s, steps_per_epoch=1), seed)[0][0]
    return jax.jit(init)(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                         jnp.asarray(ids))


def balance_routers(w, ids, s):
    """Set every router's score-correction bias `e_bias` to the balanced
    state on the seed's first batch, layer by layer, in the reference's
    float32 arithmetic, exactly as `laguna.balance_routers` and
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
            _, g = ref.mixed(x, w, i, kw, "f32")
            scores = ref.router_scores(g, w[f"b{i}_mixer"]["w_router"])
            mark = jnp.quantile(scores.reshape(-1, n), 1.0 - k / n, axis=0)
            w = dict(w, **{f"b{i}_mixer": dict(
                w[f"b{i}_mixer"],
                e_bias=w[f"b{i}_mixer"]["e_bias"] - mark)})
        x = ref.layer(x, w, i, kw, "f32")
    return w


def build(config, s, chips, seed, machine_spec=None):
    import jax.numpy as jnp

    from flexflow_tpu import AdamOptimizer, FFConfig, LossType
    from flexflow_tpu.models import DecoderConfig, create_decoder

    # `program_*`: the controls of the mechanisms run the PROGRAM built
    # otherwise than the reference (module docstring)
    dc = DecoderConfig(
        layer_types=s["layer_types"],
        num_dense_layers=s["dense_layers"],
        conv_L_cache=s.get("program_conv_L_cache", s["conv_L_cache"]),
        conv_output_gate=s.get("program_conv_output_gate", True),
        tie_word_embeddings=s.get("program_tie_word_embeddings",
                                  s["tie_word_embeddings"]),
        qk_layernorm=s.get("program_qk_layernorm", True),
        vocab_size=s["vocab_size"], hidden_size=s["hidden_size"],
        layer_norm_epsilon=s["norm_eps"],
        num_attention_heads=s["num_attention_heads"],
        num_key_value_heads=s["num_key_value_heads"],
        head_dim=s["head_dim"], rope_theta=float(s["rope_theta"]),
        intermediate_size=s["intermediate_size"],
        hidden_act=s["hidden_act"],
        n_routed_experts=s["num_experts_published"],
        experts_held=s["num_experts"], expert_offset=s["expert_offset"],
        num_experts_per_tok=s["num_experts_per_tok"],
        moe_intermediate_size=s["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=0,
        routed_scaling_factor=s["routed_scaling_factor"],
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
    """Every leaf through `set_parameter`. A program built as a control
    takes what it has a place for: with fewer taps the newest ones (the
    oldest left out), without the heads' norm no `q_norm` / `k_norm`;
    with a table of its own the head keeps its own initialisation."""
    for name, leaves in weights.items():
        have = ff.params.get(name, {})
        for pname, value in leaves.items():
            if pname not in have:
                continue
            if pname == "conv_w":
                value = value[value.shape[0] - have[pname].shape[0]:]
            ff.set_parameter(name, value, pname)


def readback(ff, weights):
    return (np.asarray(ff.get_parameter("embed_tokens", "kernel")),
            np.asarray(weights["embed_tokens"]["kernel"]))


def reference_kw(s):
    """Keyword arguments of the reference's forward; every value can be
    hashed (`common.compiled` keeps one program a set of them)."""
    return dict(num_hidden_layers=s["num_hidden_layers"],
                eps=s["norm_eps"], layer_types=tuple(s["layer_types"]),
                rope_theta=float(s["rope_theta"]),
                num_experts_per_tok=s["num_experts_per_tok"],
                routed_scaling_factor=s["routed_scaling_factor"],
                expert_offset=s["expert_offset"])


def reference(s, traffic):
    """(module, keyword arguments of its forward, samples a chunk)."""
    return reference_module, reference_kw(s), traffic.get("reference_chunk",
                                                          1)


# ---------------------------------------------------------------------------
# operations and bytes, counted for the work done HERE (the experts held,
# the vocabulary held; the mixers whole)


def expected_held_slots(s):
    """(token, slot) pairs a step that land on a held expert, a layer, if
    routing is uniform: tokens * k * held / published."""
    return (s["batch"] * s["seq"] * s["num_experts_per_tok"]
            * s["num_experts"] / s["num_experts_published"])


def forward_flops_per_token(s):
    """Forward FLOPs a token by part (a multiply-add is 2), added over
    the layers that run: the convolution mixers' two products (the
    gate-convolution-gate between them is bytes: 2 K + 2 FLOPs a lane);
    the attention ops' four projections; Q K^T and P V over the causal
    pairs; the dense layers' MLP; the expert layers' feed-forward (the
    router and the expected held pairs; no shared expert); the head,
    through the one table."""
    e, d, seq = s["hidden_size"], s["head_dim"], s["seq"]
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    convs = s["layer_types"].count("conv")
    attns = len(s["layer_types"]) - convs
    dense = s["dense_layers"]
    sparse = s["num_hidden_layers"] - dense
    share = s["num_experts"] / s["num_experts_published"]
    return {
        "conv_products": convs * 2 * e * 4 * e,
        "gated_conv": convs * e * (2 * s["conv_L_cache"] + 2),
        "projections": attns * 2 * e * d * (2 * h + 2 * kv),
        "scores": attns * 4 * h * d * (seq + 1) / 2,
        "dense_mlp": dense * 6 * e * s["intermediate_size"],
        "experts": sparse * (
            6 * e * s["moe_intermediate_size"] * s["num_experts_per_tok"]
            * share + 2 * e * s["num_experts_published"]),
        "head": 2 * e * s["vocab_size"]}


def train_flops_per_sample(s):
    """FLOPs the forward and backward of one sample require (backward is
    twice the forward; no recomputation)."""
    return 3 * s["seq"] * sum(forward_flops_per_token(s).values())


def gated_conv_step_flops_and_bytes(s):
    """What a step's gate-convolution-gates need, forward and backward,
    over the convolution ops that run. The count is of the work and not
    of what implements it. Bytes in bfloat16, T = batch * seq positions
    of E lanes: the forward reads B, C, x and writes y (4 arrays of
    T * E), the backward reads dy, B, C, x and writes dB, dC, dx (7):
    22 * T * E bytes an op. FLOPs: forward 2 K + 2 an element (u, K
    multiply-adds, the gate), backward twice that for the inputs'
    gradients and 2 K for the taps'."""
    ops = s["layer_types"].count("conv")
    elements = s["batch"] * s["seq"] * s["hidden_size"]
    taps = s["conv_L_cache"]
    flops = ops * elements * (3 * (2 * taps + 2) + 2 * taps)
    return flops, ops * 11 * 2 * elements


# ---------------------------------------------------------------------------
# checks, and what only the loaded program can tell


def extra_checks(ff, s, chips, on_tpu):
    out = []
    kinds = ["conv" if n.op.op_type.name == "SHORT_CONV" else
             "full_attention" for n in ff.executor.nodes
             if n.op.op_type.name in ("SHORT_CONV", "MULTIHEAD_ATTENTION")]
    out.append(("mixers_by_layer", kinds == s["layer_types"], kinds))
    tables = [name for name, leaves in ff.params.items()
              for leaf in leaves.values()
              if sorted(leaf.shape) == sorted((s["vocab_size"],
                                               s["hidden_size"]))]
    # a control builds the head with a table of its own; the cell's
    # program has to hold ONE
    want = 1 if s.get("program_tie_word_embeddings",
                      s["tie_word_embeddings"]) else 2
    out.append(("one_table", len(tables) == want, tables))
    if on_tpu and chips == 1:
        impls = _attention_impls(ff)
        out.append(("attention_all_flash",
                    len(impls) == kinds.count("full_attention")
                    and set(impls.values()) == {"flash"}, impls))
    return out


def kernel_fallbacks(ff):
    """What makes a run not correct beside the comparison: attention ops
    that fell back from the searched kernel, and pairs that the expert
    layers' buffer could not hold. Also prints the counters (the cell's
    `observed` line) and keeps them. The readers of the device-trace
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
