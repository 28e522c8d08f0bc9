"""Family `ouro`: a LOOPED decoder (Ouro-2.6B): one stack of blocks with
sandwich norms (a norm on each branch's input and on its output; causal
16:16 attention at heads of 128 with whole-head rotary, a SwiGLU MLP)
applied `total_ut_steps` = T times with ONE set of leaves, a final norm
that closes every pass and feeds the next, an untied head and an exit
gate over all T passes' sequences, and the expected loss over the
passes under the exit distribution; one chip's share of a stated
deployment, built through `flexflow_tpu.models.create_decoder` +
`FFModel.compile`.

What `families/ouro.py` answers (the contract `benchmarks/README.md`
states for every family; `harness.run_cell` and `seeds_check.py` call
these and nothing else):
    sizes(config, traffic, overrides)   the sizes as run; ends at once
                                        (SystemExit) on a program whose
                                        decoder cannot loop
    make_data(s, seed)                  ([ids [n, S]], labels [n, S]), the
                                        next token
    make_weights(s, seed)               every leaf ONCE, float32, on the
                                        device
    build(config, s, chips, seed, machine_spec)   the compiled FFModel
    install_weights(ff, weights), readback(ff, weights)
    extra_checks(ff, s, chips, on_tpu)  (name, ok, detail) rows
    kernel_fallbacks(ff)                what makes a run not correct
                                        beside the comparison; fills
                                        `observed` for the readers
    reference(s, traffic)               (module, keyword arguments, chunk)
    train_flops_per_sample(s)           for `device.mfu_pct`: T passes
                                        and T heads
    TOLERANCES                          the output check's limits
    causal_flash_step_flops_and_bytes(s)   for the kernel roofline
The model's output, and the reference's, is [B, T * S, V + 1]: the T
passes' logits laid end to end, pass-major, the exit gate's logit the
last column; `pred_nrmse` is over all of it.

The controls of the mechanisms go through `program_*` size overrides
(`scripts/program_controls.py` all of them in one process with one
reference run), which build the PROGRAM otherwise and leave the
reference as the cell states it; each has to come out not correct:
    program_total_ut_steps=3         three passes (the fourth pass's rows
                                     of the output are the third's again,
                                     so that the shapes compare)
    program_share_leaves=false       every pass leaves of its own, equal
                                     at step 0: four Adam updates where
                                     one belongs; told by `extra_checks`
                                     (`parameters_held_once`,
                                     `shared_weight_ops`)
    program_sandwich_norm=false      no norm on the branches' outputs
    program_norm_between_passes=false   the final norm feeds the head
                                     alone, the next pass reads the raw
                                     stream
    program_exit_weights="uniform"   1 / T in the exit distribution's
                                     place in the loss
"""

import json
import math

import numpy as np

from benchmarks.families.nemotron_h import (  # noqa: F401  (the harness's)
    _attention_impls, make_data)
from benchmarks.references import ouro as reference_module

# Limits of the output check; both readings of each in PERF.md ("The output
# check"), from `run.py`, `seeds_check.py` and `scripts/program_controls.py`
# on the chip at the cell's own sizes (PR 48).
TOLERANCES = {"pred_nrmse": 0.03, "loss0_rel": 1e-4,
              "later_loss_rel": 1.5e-4}

# what the program showed of itself after the window (see kernel_fallbacks)
observed = {}

SIZE_KEYS = (
    "num_hidden_layers", "vocab_size", "hidden_size", "rms_norm_eps",
    "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
    "intermediate_size", "total_ut_steps", "exit_entropy_beta",
    "hidden_act", "tie_word_embeddings", "initializer_range",
    "embedding_std")
# leaf-holding ops of a layer: four norms, attention, the MLP's two products
LAYER_OPS = 7


def sizes(config, traffic, overrides=None):
    # a program whose decoder cannot loop (an older commit under these
    # files) ends here, at once, before any weight is made
    import dataclasses

    from flexflow_tpu.models import DecoderConfig
    if "total_ut_steps" not in {f.name for f in
                                dataclasses.fields(DecoderConfig)}:
        raise SystemExit("family ouro: this program's decoder applies a "
                         "layer once (no total_ut_steps; flexflow_tpu "
                         "PR 48)")
    s = {k: config[k] for k in SIZE_KEYS}
    s.update(seq=traffic["seq"], batch=traffic["batch"],
             steps_per_epoch=traffic["steps_per_epoch"])
    s.update(overrides or {})
    if s["exit_entropy_beta"] != reference_module.EXIT_ENTROPY_BETA:
        raise SystemExit("family ouro: the reference's loss is written at "
                         f"beta {reference_module.EXIT_ENTROPY_BETA}")
    return s


def weight_shapes(s):
    """name -> leaf -> (kind, shape), every leaf ONCE whatever
    `total_ut_steps` is; kinds: `normal` (std initializer_range, with NO
    depth scaling of the output projections: the norm on a branch's
    output sets what the branch adds), `embed` (std embedding_std),
    `ones`, `zeros`."""
    e, v, d = s["hidden_size"], s["vocab_size"], s["head_dim"]
    h, kv, f = (s["num_attention_heads"], s["num_key_value_heads"],
                s["intermediate_size"])
    shapes = {"embed_tokens": {"kernel": ("embed", (v, e))}}
    for i in range(s["num_hidden_layers"]):
        for norm in ("norm", "attn_out_norm", "post_norm", "mlp_out_norm"):
            shapes[f"b{i}_{norm}"] = {"scale": ("ones", (e,))}
        shapes[f"b{i}_attn"] = {
            "wq": ("normal", (h, e, d)), "wk": ("normal", (kv, e, d)),
            "wv": ("normal", (kv, e, d)), "wo": ("normal", (h, d, e))}
        shapes[f"b{i}_gate_up_proj"] = {"kernel": ("normal", (e, 2 * f))}
        shapes[f"b{i}_down_proj"] = {"kernel": ("normal", (f, e))}
    shapes["final_ln"] = {"scale": ("ones", (e,))}
    shapes["lm_head"] = {"kernel": ("normal", (e, v))}
    shapes["exit_gate"] = {"kernel": ("normal", (e, 1)),
                           "bias": ("zeros", (1,))}
    return shapes


def parameters(s):
    return sum(math.prod(shape) for leaves in weight_shapes(s).values()
               for _, shape in leaves.values())


def make_weights(s, seed):
    """All weights on the device in one jitted call from the seed, float32,
    each shared leaf drawn ONCE; the same tree goes to the program and to
    the reference."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(s)
    scale = {"normal": s["initializer_range"], "embed": s["embedding_std"]}
    constant = {"ones": 1.0, "zeros": 0.0}

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
        return out

    return jax.jit(init)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def program_passes(s):
    return s.get("program_total_ut_steps", s["total_ut_steps"])


def build(config, s, chips, seed, machine_spec=None):
    import jax.numpy as jnp

    from flexflow_tpu import AdamOptimizer, FFConfig, LossType
    from flexflow_tpu.models import DecoderConfig, create_decoder

    # `program_*`: the controls of the mechanisms run the PROGRAM built
    # otherwise than the reference (module docstring)
    dc = DecoderConfig(
        hybrid_override_pattern="U" * s["num_hidden_layers"],
        total_ut_steps=program_passes(s),
        share_ut_leaves=s.get("program_share_leaves", True),
        sandwich_norm=s.get("program_sandwich_norm", True),
        norm_between_passes=s.get("program_norm_between_passes", True),
        exit_entropy_beta=s["exit_entropy_beta"],
        tie_word_embeddings=s["tie_word_embeddings"],
        vocab_size=s["vocab_size"], hidden_size=s["hidden_size"],
        layer_norm_epsilon=s["rms_norm_eps"],
        num_attention_heads=s["num_attention_heads"],
        num_key_value_heads=s["num_key_value_heads"],
        head_dim=s["head_dim"], rope_theta=float(s["rope_theta"]),
        intermediate_size=s["intermediate_size"],
        hidden_act=s["hidden_act"], batch_size=s["batch"],
        seq_length=s["seq"])
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
               LossType.EXPECTED_EXIT_SPARSE_CATEGORICAL_CROSSENTROPY, [],
               machine_spec=machine_spec)
    ff.executor.exit_uniform = s.get("program_exit_weights",
                                     "gate") == "uniform"
    missing = s["total_ut_steps"] - program_passes(s)
    if missing > 0:
        # the control with fewer passes: the output's missing rows are the
        # last pass's again, so that the comparison has equal shapes
        predict, rows = ff.predict, s["seq"]

        def padded(xs):
            out = np.asarray(predict(xs))
            return np.concatenate([out] + [out[:, -rows:]] * missing, axis=1)

        ff.predict = padded
    return ff


def install_weights(ff, weights):
    """Every leaf through `set_parameter`, ONCE: the passes after the
    first read the first's. A program built as a control takes what it
    has a place for: without the output norms not their scales; with
    leaves of its own in every pass each pass's copy (`ut<t>_<name>`)."""
    for name, leaves in weights.items():
        for held in [name] + [f"ut{t}_{name}"
                              for t in range(1, len(ff.loss_parts))]:
            if held in ff.params:
                for pname, value in leaves.items():
                    ff.set_parameter(held, value, pname)


def readback(ff, weights):
    return (np.asarray(ff.get_parameter("embed_tokens", "kernel")),
            np.asarray(weights["embed_tokens"]["kernel"]))


def reference_kw(s):
    """Keyword arguments of the reference's forward; every value can be
    hashed (`common.compiled` keeps one program a set of them)."""
    return dict(num_hidden_layers=s["num_hidden_layers"],
                eps=s["rms_norm_eps"], rope_theta=float(s["rope_theta"]),
                total_ut_steps=s["total_ut_steps"])


def reference(s, traffic):
    """(module, keyword arguments of its forward, samples a chunk)."""
    return reference_module, reference_kw(s), traffic.get("reference_chunk",
                                                          1)


# ---------------------------------------------------------------------------
# operations and bytes


def causal_pairs(s):
    """(query, key) pairs a causal op sees, a sample."""
    return s["seq"] * (s["seq"] + 1) // 2


def forward_flops_per_token(s):
    """Forward FLOPs a token by part (a multiply-add is 2), added over
    the T applications of every layer and the T heads: the layers'
    products (q, k, v, o and the MLP's three); Q K^T and P V over the
    causal pairs; the head and the gate's column over every pass's
    sequence."""
    e, d, seq = s["hidden_size"], s["head_dim"], s["seq"]
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    applications = s["num_hidden_layers"] * s["total_ut_steps"]
    return {
        "layer_products": applications * (
            2 * e * d * (2 * h + 2 * kv) + 6 * e * s["intermediate_size"]),
        "scores": applications * 4 * h * d * (seq + 1) / 2,
        "heads": s["total_ut_steps"] * 2 * e * (s["vocab_size"] + 1)}


def train_flops_per_sample(s):
    """FLOPs the forward and backward of one sample require (backward is
    twice the forward; no recomputation): T passes and T heads."""
    return 3 * s["seq"] * sum(forward_flops_per_token(s).values())


def causal_flash_step_flops_and_bytes(s):
    """What a step's causal attention cores need, forward and backward,
    over the layers' T applications, for the VISIBLE pairs alone: Q K^T
    and P V forward (4 d a pair a head), twice that backward: 12 * pairs
    * heads * d an op. Bytes in bfloat16, S positions of heads * d lanes:
    the forward reads q, k, v and writes o (4 arrays), the backward reads
    q, k, v, o, do and writes dq, dk, dv (8): 24 * S * heads * d bytes an
    op. The count is of the work and not of what implements it."""
    ops = s["num_hidden_layers"] * s["total_ut_steps"]
    lanes = s["num_attention_heads"] * s["head_dim"]
    flops = ops * s["batch"] * 12 * causal_pairs(s) * lanes
    return flops, ops * s["batch"] * 12 * 2 * s["seq"] * lanes


# ---------------------------------------------------------------------------
# checks, and what only the loaded program can tell


def extra_checks(ff, s, chips, on_tpu):
    import jax

    out = []
    passes, layers = s["total_ut_steps"], s["num_hidden_layers"]
    held = sum(int(leaf.size) for leaf in jax.tree_util.tree_leaves(ff.params))
    out.append(("parameters_held_once", held == parameters(s), held))
    gauges = ff.executor.traced_gauges()
    readers = gauges.get("executor.shared_weight_ops")
    out.append(("shared_weight_ops",
                readers == (passes - 1) * (LAYER_OPS * layers + 1), readers))
    out.append(("layer_applications",
                gauges.get("executor.layer_applications") == passes * layers,
                gauges.get("executor.layer_applications")))
    if on_tpu and chips == 1:
        impls = _attention_impls(ff)
        out.append(("attention_all_flash",
                    len(impls) == passes * layers
                    and set(impls.values()) == {"flash"}, impls))
    return out


def kernel_fallbacks(ff):
    """What makes a run not correct beside the comparison: attention ops
    that fell back from the searched kernel. Also prints the counters
    (the cell's `observed` line) and keeps them. The readers of the
    device-trace metrics take their scopes from the join table the
    program writes, so no step is lowered a second time here."""
    out = {n.op.name: n.op._kernel_fallback for n in ff.executor.nodes
           if getattr(n.op, "_kernel_fallback", None)}
    counters = dict(getattr(ff, "op_counters", None) or {})
    observed.clear()
    observed["op_counters"] = counters
    print(json.dumps(dict(phase="observed", op_counters=counters)),
          flush=True)
    return out
