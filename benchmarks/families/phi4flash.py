"""Family `phi4flash`: a decoder-hybrid-decoder language model (SambaY;
Phi-4-mini-flash-reasoning) with differential attention: Mamba-1 mixers
and differential attention under a causal window in its first half, ONE
Mamba-1 layer whose scan output the later gated memory units read as
their memory, ONE full-attention layer whose projected keys and values
the later cross-attention layers read, LayerNorms with bias, a SwiGLU
MLP in every layer, ONE table for the embedding and the head; one
pipeline stage's share of a stated deployment, built through
`flexflow_tpu.models.create_decoder` + `FFModel.compile`.

What `families/phi4flash.py` answers (the contract `benchmarks/README.md`
states for every family; `harness.run_cell` and `seeds_check.py` call
these and nothing else):
    sizes(config, traffic, overrides)   the sizes as run; ends at once
                                        (SystemExit) on a program without
                                        the family
    make_data(s, seed)                  ([ids [n, S]], labels [n, S]), the
                                        next token
    make_weights(s, seed)               every leaf, float32, on the device
    build(config, s, chips, seed, machine_spec)   the compiled FFModel
    install_weights(ff, weights), readback(ff, weights)
    extra_checks(ff, s, chips, on_tpu)  (name, ok, detail) rows
    kernel_fallbacks(ff)                what makes a run not correct
                                        beside the comparison; fills
                                        `observed` for the readers
    reference(s, traffic)               (module, keyword arguments, chunk)
    train_flops_per_sample(s)           for `device.mfu_pct`
    TOLERANCES                          the output check's limits
    selective_scan_step_flops_and_bytes(s), diff_flash_step_flops_and_bytes(s)
                                        for the two kernel rooflines
The layers that run are the configuration's `num_hidden_layers` from its
`first_layer_index` on (published layers 16-19 of 32); program and
reference name them b0-b3 in that order and keep the published index for
the rule that names a layer's kind and for lambda_init.
The controls of the mechanisms go through `program_*` size overrides
(`seeds_check.check_seeds(cell, seeds, rehearsal=dict(sizes=...))`),
which build the PROGRAM otherwise and leave the reference as the cell
states it; each has to come out not correct:
    program_diff_lambda_scale=0.0   lambda = 0: plain attention, the
                                    second map weighs nothing
    program_memory_gated=True       the gated memory unit reads the
                                    scan's output AFTER its silu(z) gate
    program_cross_own_kv=True       the cross-attention layer projects
                                    keys and values of its own (its own
                                    seeded initialisation)
"""

import json
import math

import numpy as np

from benchmarks.families.nemotron_h import (  # noqa: F401  (the harness's)
    _attention_impls, make_data)
from benchmarks.references import phi4flash as reference_module

# Limits of the output check; both readings of each in PERF.md ("The output
# check"), from `run.py`, `seeds_check.py` and `scripts/program_controls.py`
# on the chip at the cell's own sizes (PR 52).
# pred_nrmse: the program reads 0.01442-0.01451 on every seed (the reference
#   with bfloat16 operands 0.0130: a table drawn at 0.02 and LayerNorms, so
#   the stream holds no exact component; four layers where lfm2's five read
#   0.05), the float8 control 0.2053-0.2061, the three mechanism controls
#   0.137 (keys and values of the cross layer's own), 0.195 (the memory
#   taken after the gate) and 0.264 (lambda = 0): 0.045 is 3.1 times the
#   first and 3.0 times under the smallest of the others.
# loss0_rel, later_loss_rel: 3.7e-5 / 4.0e-5 at the largest over twelve
#   seeds (root mean square 2.0e-5 / 2.1e-5; the first reading 1.2e-5 /
#   1.7e-5): the accepted decoder cells' 6e-5 would leave 1.5 times of room
#   over what fresh seeds read, so 1.2e-4, three times the largest and six
#   times the root mean square, 19 times under the wrong-Adam control
#   (2.28e-3); float8 reads 1.5e-4 to 4.7e-4 (the precision hardly moves a
#   mean over 8,192 positions; the logits' limit is what separates it).
TOLERANCES = {"pred_nrmse": 0.045, "loss0_rel": 1.2e-4,
              "later_loss_rel": 1.2e-4}

# what the program showed of itself after the window (see kernel_fallbacks)
observed = {}

SIZE_KEYS = (
    "num_hidden_layers", "first_layer_index", "published_num_hidden_layers",
    "vocab_size", "hidden_size", "intermediate_size", "layer_norm_eps",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "sliding_window", "mb_per_layer", "tie_word_embeddings",
    "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
    "time_step_min", "time_step_max", "initializer_range", "embedding_std",
    "lambda_std")


def sizes(config, traffic, overrides=None):
    # a program without the family (an older commit under these files)
    # ends here, at once, before any weight is made
    import dataclasses

    from flexflow_tpu.models import DecoderConfig
    if "mb_per_layer" not in {f.name for f in
                              dataclasses.fields(DecoderConfig)}:
        raise SystemExit("family phi4flash: this program's decoder has no "
                         "Mamba-1 mixer, differential attention or tensors "
                         "shared between layers (flexflow_tpu PR 52)")
    s = {k: config[k] for k in SIZE_KEYS}
    s.update(seq=traffic["seq"], batch=traffic["batch"],
             steps_per_epoch=traffic["steps_per_epoch"])
    s.update(overrides or {})
    s["kinds"] = [reference_module.layer_kind(
        s["first_layer_index"] + j, s["published_num_hidden_layers"])
        for j in range(s["num_hidden_layers"])]
    return s


def mamba_widths(s):
    """(d_inner, state N, taps K, rank R)."""
    e = s["hidden_size"]
    return (s["mamba_expand"] * e, s["mamba_d_state"], s["mamba_d_conv"],
            s["mamba_dt_rank"] or -(-e // 16))


def weight_shapes(s):
    """name -> leaf -> (kind, shape); kinds: `normal` (std
    initializer_range), `embed` (std embedding_std), `taps` (uniform in
    +-1/2), `dt_proj` (uniform in +-R^-1/2), `dt_bias` (the inverse
    softplus of dt log-uniform in [time_step_min, time_step_max]),
    `a_log` (log(n + 1)), `lambda` (normal, std lambda_std), `ones`,
    `zeros`. ONE table: there is no `lm_head`."""
    e, v, d = s["hidden_size"], s["vocab_size"], s["head_dim"]
    h, kv, f = (s["num_attention_heads"], s["num_key_value_heads"],
                s["intermediate_size"])
    c, n, k, r = mamba_widths(s)
    norm = {"scale": ("ones", (e,)), "bias": ("zeros", (e,))}
    shapes = {"embed_tokens": {"kernel": ("embed", (v, e))}}
    for j, kind in enumerate(s["kinds"]):
        shapes[f"b{j}_norm"] = dict(norm)
        if kind == "mamba":
            shapes[f"b{j}_mixer"] = {
                "w_in": ("normal", (e, 2 * c)), "conv_w": ("taps", (k, c)),
                "conv_b": ("zeros", (c,)),
                "w_x": ("normal", (c, r + 2 * n)),
                "w_dt": ("dt_proj", (r, c)), "dt_bias": ("dt_bias", (c,)),
                "a_log": ("a_log", (c, n)), "d": ("ones", (c,)),
                "w_out": ("normal", (c, e))}
        elif kind == "gated_memory":
            shapes[f"b{j}_memory_in_proj"] = {"kernel": ("normal", (e, c))}
            shapes[f"b{j}_memory_out_proj"] = {"kernel": ("normal", (c, e))}
        else:
            attn = {"wq": ("normal", (h, e, d)), "bq": ("zeros", (h, d)),
                    "wo": ("normal", (h, d, e)), "bo": ("zeros", (e,)),
                    "diff_norm": ("ones", (2 * d,))}
            attn.update({name: ("lambda", (d,)) for name in (
                "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")})
            if kind != "cross":
                attn.update(wk=("normal", (kv, e, d)), bk=("zeros", (kv, d)),
                            wv=("normal", (kv, e, d)), bv=("zeros", (kv, d)))
            shapes[f"b{j}_attn"] = attn
        shapes[f"b{j}_post_norm"] = dict(norm)
        shapes[f"b{j}_gate_up_proj"] = {"kernel": ("normal", (e, 2 * f))}
        shapes[f"b{j}_down_proj"] = {"kernel": ("normal", (f, e))}
    shapes["final_ln"] = dict(norm)
    return shapes


def parameters(s):
    return sum(math.prod(shape) for leaves in weight_shapes(s).values()
               for _, shape in leaves.values())


def make_weights(s, seed):
    """All weights on the device in one jitted call from the seed, float32;
    the same tree goes to the program and to the reference. The keys are
    split outside the program that draws the leaves."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(s)
    scale = {"normal": s["initializer_range"], "embed": s["embedding_std"],
             "lambda": s["lambda_std"]}
    constant = {"ones": 1.0, "zeros": 0.0}
    lo, hi = math.log(s["time_step_min"]), math.log(s["time_step_max"])
    names = [(name, pname) for name, leaves in shapes.items()
             for pname in leaves]
    keys = dict(zip(names, jax.random.split(
        jax.random.PRNGKey(seed % (2 ** 31 - 1)), len(names))))

    def init(keys):
        out = {}
        for name, pname in names:
            kind, shape = shapes[name][pname]
            key = keys[(name, pname)]
            if kind in constant:
                leaf = jnp.full(shape, constant[kind], jnp.float32)
            elif kind == "taps":
                leaf = jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
            elif kind == "dt_proj":
                bound = shape[0] ** -0.5
                leaf = jax.random.uniform(key, shape, jnp.float32, -bound,
                                          bound)
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                             * (hi - lo) + lo)
                leaf = dt + jnp.log(-jnp.expm1(-dt))
            elif kind == "a_log":
                leaf = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[1] + 1, dtype=jnp.float32)), shape)
            else:
                leaf = scale[kind] * jax.random.normal(key, shape,
                                                       jnp.float32)
            out.setdefault(name, {})[pname] = leaf
        return out

    return jax.jit(init)(keys)


def build(config, s, chips, seed, machine_spec=None):
    import jax.numpy as jnp

    from flexflow_tpu import AdamOptimizer, FFConfig, LossType
    from flexflow_tpu.models import DecoderConfig, create_decoder

    # `program_*`: the controls of the mechanisms run the PROGRAM built
    # otherwise than the reference (module docstring)
    dc = DecoderConfig(
        mb_per_layer=s["mb_per_layer"],
        num_hidden_layers=s["num_hidden_layers"],
        first_layer_index=s["first_layer_index"],
        published_num_hidden_layers=s["published_num_hidden_layers"],
        sliding_window=s["sliding_window"],
        vocab_size=s["vocab_size"], hidden_size=s["hidden_size"],
        intermediate_size=s["intermediate_size"],
        layer_norm_epsilon=s["layer_norm_eps"],
        num_attention_heads=s["num_attention_heads"],
        num_key_value_heads=s["num_key_value_heads"],
        head_dim=s["head_dim"],
        tie_word_embeddings=s["tie_word_embeddings"],
        mamba_d_state=s["mamba_d_state"], mamba_d_conv=s["mamba_d_conv"],
        mamba_expand=s["mamba_expand"], mamba_dt_rank=s["mamba_dt_rank"],
        time_step_min=s["time_step_min"], time_step_max=s["time_step_max"],
        diff_lambda_scale=s.get("program_diff_lambda_scale", 1.0),
        memory_gated=s.get("program_memory_gated", False),
        cross_own_kv=s.get("program_cross_own_kv", False),
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
    takes what it has a place for: a cross-attention layer with keys and
    values of its own keeps its own initialisation of them."""
    for name, leaves in weights.items():
        have = ff.params.get(name, {})
        for pname, value in leaves.items():
            if pname in have:
                ff.set_parameter(name, value, pname)


def readback(ff, weights):
    return (np.asarray(ff.get_parameter("embed_tokens", "kernel")),
            np.asarray(weights["embed_tokens"]["kernel"]))


def reference_kw(s):
    """Keyword arguments of the reference's forward; every value can be
    hashed (`common.compiled` keeps one program a set of them)."""
    return dict(num_hidden_layers=s["num_hidden_layers"],
                first_layer_index=s["first_layer_index"],
                published_num_hidden_layers=s["published_num_hidden_layers"],
                eps=s["layer_norm_eps"], sliding_window=s["sliding_window"])


def reference(s, traffic):
    """(module, keyword arguments of its forward, samples a chunk)."""
    return reference_module, reference_kw(s), traffic.get("reference_chunk",
                                                          1)


# ---------------------------------------------------------------------------
# operations and bytes, counted for the work done HERE (the layers and the
# vocabulary held; the mixers whole)


def visible_pairs(s, kind):
    """(query, key) pairs a head and sample that layer kind's mask leaves,
    counted exactly."""
    seq, w = s["seq"], s["sliding_window"]
    if kind == "window" and 0 < w < seq:
        return w * (w + 1) // 2 + (seq - w) * w
    return seq * (seq + 1) // 2


def forward_flops_per_token(s):
    """Forward FLOPs a token by part (a multiply-add is 2), added over
    the layers that run: the MLPs; the Mamba layers' four products (the
    convolution and the scan are element-wise: `selective_scan_step_
    flops_and_bytes`); the attention layers' projections (a
    cross-attention layer projects queries and outputs alone); both
    softmax maps over the visible pairs, a pair of heads' values twice a
    head wide: 2 H d for the scores and 2 H 2d for the values a pair;
    the gated memory units' two products; the head, through the one
    table."""
    e, d, seq = s["hidden_size"], s["head_dim"], s["seq"]
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    c, n, _, r = mamba_widths(s)
    kinds = s["kinds"]
    attention = [k for k in kinds if k in ("window", "full", "cross")]
    return {
        "mlp": len(kinds) * 6 * e * s["intermediate_size"],
        "mamba_products": kinds.count("mamba") * 2 * (
            e * 2 * c + c * (r + 2 * n) + r * c + c * e),
        "projections": sum(
            2 * e * d * (2 * h + (0 if k == "cross" else 2 * kv))
            for k in attention),
        "scores": sum(6 * h * d * visible_pairs(s, k) / seq
                      for k in attention),
        "gated_memory": kinds.count("gated_memory") * 4 * e * c,
        "head": 2 * e * s["vocab_size"]}


def train_flops_per_sample(s):
    """FLOPs the forward and backward of one sample require (backward is
    twice the forward; no recomputation)."""
    return 3 * s["seq"] * sum(forward_flops_per_token(s).values())


def selective_scan_step_flops_and_bytes(s):
    """What a step's selective scans need, forward and backward, over the
    Mamba layers that run. The count is of the work and not of what
    implements it. Bytes, T = batch * seq positions of C channels and N
    states: the forward reads x (bfloat16), dt (float32), B and C
    (float32, N a position) and writes y (float32): 10 T C + 8 T N; the
    backward reads x, dt, dy, B, C and writes dx (bfloat16), ddt, dB, dC:
    16 T C + 16 T N. FLOPs an element and state: the decay's argument and
    exponential, the state's update (3) and its read (2) forward, three
    times that backward (the state formed again, its cotangent's
    recurrence, five sums): 28 T C N. Neither binds a kernel that a
    vector unit's element-wise work bounds (PERF.md says what it can
    reach)."""
    ops = s["kinds"].count("mamba")
    c, n, _, _ = mamba_widths(s)
    t = s["batch"] * s["seq"]
    return ops * 28 * t * c * n, ops * (26 * t * c + 24 * t * n)


def diff_flash_step_flops_and_bytes(s):
    """What a step's differential attention cores need, forward and
    backward, over the VISIBLE pairs alone: 12 * pairs * H * d * 1.5
    FLOPs an op (two products forward and four backward of 2 FLOPs a
    multiply-add over H heads of d lanes, the values' products twice as
    wide as the scores': (d + 2d) / 2d = 1.5), the bfloat16 q, k, v, o of
    both maps and their gradients beside them."""
    h, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                s["head_dim"])
    t = s["batch"] * s["seq"]
    flops = nbytes = 0
    for kind in s["kinds"]:
        if kind in ("window", "full", "cross"):
            flops += 12 * s["batch"] * visible_pairs(s, kind) * h * d * 1.5
            # a map: q, o, dq, do at H/2 heads of 2d; k, v, dk, dv at Hk
            nbytes += 2 * 2 * t * d * (4 * h + 4 * kv)
    return flops, nbytes


# ---------------------------------------------------------------------------
# checks, and what only the loaded program can tell


def extra_checks(ff, s, chips, on_tpu):
    out = []
    kind_of = {"MAMBA_MIXER": "mamba", "MULTIHEAD_ATTENTION": "attention"}
    kinds = [kind_of[n.op.op_type.name] for n in ff.executor.nodes
             if n.op.op_type.name in kind_of]
    want = [k if k == "mamba" else "attention" for k in s["kinds"]
            if k != "gated_memory"]
    out.append(("mixers_by_layer", kinds == want, kinds))
    held = int(sum(leaf.size for leaves in ff.params.values()
                   for leaf in leaves.values()))
    if not s.get("program_cross_own_kv"):
        out.append(("parameters_as_counted", held == parameters(s), held))
    gauges = ff.executor.traced_gauges()
    readers = gauges.get("executor.shared_tensor_readers")
    # the memory's readers and the keys' and values' (two tensors a reader)
    expected = (s["kinds"].count("gated_memory")
                + 2 * s["kinds"].count("cross")
                * (not s.get("program_cross_own_kv")))
    out.append(("shared_tensor_readers", readers == expected, readers))
    if on_tpu and chips == 1:
        impls = _attention_impls(ff)
        out.append(("attention_all_flash",
                    len(impls) == kinds.count("attention")
                    and set(impls.values()) == {"flash"}, impls))
    return out


def kernel_fallbacks(ff):
    """What makes a run not correct beside the comparison: attention ops
    that fell back from the searched kernel, and on the chip a scan that
    ran outside its kernel. Also prints the counters
    (the cell's `observed` line) and keeps them. The readers of the
    device-trace metrics take their scopes from the join table the
    program writes, so no step is lowered a second time here."""
    out = {n.op.name: n.op._kernel_fallback for n in ff.executor.nodes
           if getattr(n.op, "_kernel_fallback", None)}
    counters = dict(getattr(ff, "op_counters", None) or {})
    if ff.executor.mesh.devices.flat[0].platform == "tpu" and counters.get(
            "ssm/selective_scan_kernel_ops") != counters.get(
                "ssm/selective_scan_ops"):
        out["ssm/selective_scan_kernel_ops"] = counters.get(
            "ssm/selective_scan_kernel_ops")
    observed.clear()
    observed["op_counters"] = counters
    print(json.dumps(dict(phase="observed", op_counters=counters)),
          flush=True)
    return out
