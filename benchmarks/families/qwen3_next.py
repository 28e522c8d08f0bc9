"""Family `qwen3_next`: a decoder whose sequence mixer is a GATED
DELTA-RULE linear-attention mixer in three layers of four and GATED
softmax attention in the fourth (Qwen3-Next-80B-A3B-Instruct: 16 key and
32 value heads of 128 with a causal convolution of 4 taps; 16 query and 2
key/value heads of 256, a sigmoid gate a lane out of the query
projection, rotary over 64 lanes of a head), softmax top-10-of-512
experts with a gated shared expert in every layer, zero-centred norms and
an untied head, one chip's share of a stated deployment, built through
`flexflow_tpu.models.create_decoder` + `FFModel.compile`. See
`bert_ae.py` / `lfm2.py` for what a family gives the harness
(`harness.run_cell` and `seeds_check.py` call these and nothing else).

Layer i of the published 48 is full attention where (i + 1) %
`full_attention_interval` == 0, else linear attention; the layers that
run are the configuration's first `num_hidden_layers`, b0-b3.

The controls of the mechanisms are of two kinds (`scripts/
program_controls.py` runs both in one process; each has to come out not
correct). `program_*` size overrides of published keys build the PROGRAM
otherwise and leave the reference as the cell states it:
    program_partial_rotary_factor=1.0   rotary over all 256 lanes
    program_rope_theta=1e4
    program_num_experts_per_tok=8
    program_norm_topk_prob=false        the chosen not renormalised
For what no published key switches, `reference_*` overrides alter the
REFERENCE (`reference_kw` passes them on) and the unaltered program must
then read not correct:
    reference_delta_correction=false    S <- S + k (x) beta v
    reference_decay=false               g = 0
    reference_attention_gate=false      the attention's gate left out
    reference_shared_gate=false         the shared expert's gate left out
Beside the contract: `delta_rule_step_flops_and_bytes` and
`flash_step_flops_and_bytes` for the two kernel rooflines.
"""

import json
import math

from benchmarks.families.nemotron_h import (  # noqa: F401  (the harness's)
    _attention_impls, install_weights, make_data, readback)
from benchmarks.references import qwen3_next as reference_module

# Limits of the output check; both readings of each in PERF.md (section 4,
# "The output check", and section 6, PR 58), from `seeds_check.py` (twelve
# seeds, the controls on three), `run.py` (six from the final tree's
# archive) and `scripts/program_controls.py`, on the chip at the cell's own
# sizes.
# (a) pred_nrmse: RMS error of the logits on the first batch over the
#     standard deviation of the reference's. Program 0.00276-0.00278 over
#     18 seeds (the reference with bfloat16 operands 0.00259: the
#     embedding's N(0, 1) rows dilute every layer's rounding), the float8
#     control 0.04128-0.04133 over three seeds. The limit is their
#     geometric mean: 4.0 times the program's largest and 3.7 under the
#     control's smallest. A lower precision fails by this limit alone;
#     the six mechanism controls that the logits see read 0.034-0.169.
# (b) loss0_rel, (c) later_loss_rel: the accepted decoder cells' 6e-5.
#     The precision hardly moves them (program at most 1.0e-5 and 1.4e-5
#     over 18 seeds: 5.9 and 4.3 times of room; float8's loss 1.1e-5 to
#     6.9e-5); Adam without bias correction reads 2.3e-3 at the file's
#     alpha 1e-6, 38 times the limit.
TOLERANCES = {"pred_nrmse": 0.011, "loss0_rel": 6.0e-5,
              "later_loss_rel": 6.0e-5}

# what the program showed of itself after the window (see kernel_fallbacks)
observed = {}
_sizes = {}

SIZE_KEYS = (
    "num_hidden_layers", "full_attention_interval", "vocab_size",
    "hidden_size", "rms_norm_eps", "num_attention_heads",
    "num_key_value_heads", "head_dim", "partial_rotary_factor",
    "rope_theta", "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "num_experts", "num_experts_published",
    "expert_offset", "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "norm_topk_prob", "hidden_act",
    "slot_slack", "initializer_range", "embedding_std", "qk_norm_scale",
    "published_depth", "delta_chunk_size", "dt_min", "dt_max")


def sizes(config, traffic, overrides=None):
    # a program without the delta-rule mixer (an older commit under these
    # files) ends here, at once, before any weight is made
    import dataclasses

    from flexflow_tpu.models import DecoderConfig
    if "linear_num_value_heads" not in {f.name for f in
                                        dataclasses.fields(DecoderConfig)}:
        raise SystemExit("family qwen3_next: this program's decoder has no "
                         "gated delta-rule mixer, no gate a lane and no "
                         "head of 256 (flexflow_tpu PR 58)")
    s = {k: config[k] for k in SIZE_KEYS}
    s.update(seq=traffic["seq"], batch=traffic["batch"],
             steps_per_epoch=traffic["steps_per_epoch"])
    s.update(overrides or {})
    every = s["full_attention_interval"]
    s["layer_types"] = ["full_attention" if (i + 1) % every == 0
                        else "linear_attention"
                        for i in range(s["num_hidden_layers"])]
    _sizes.clear()
    _sizes.update(s)
    return s


def widths(s):
    """(key lanes, value lanes) of a delta mixer: Hk Dk, Hv Dv."""
    return (s["linear_num_key_heads"] * s["linear_key_head_dim"],
            s["linear_num_value_heads"] * s["linear_value_head_dim"])


def weight_shapes(s):
    """name -> leaf -> (kind, shape); kinds: `normal` (std
    initializer_range), `out` (that over the square root of the published
    depth), `embed` (std embedding_std), `taps` (uniform in +-1/sqrt(K)),
    `a_log` (log of uniform (0, 16)), `dt` (the inverse softplus of a
    step log-uniform in [dt_min, dt_max]), `ones`, `zeros` (the zero-centred
    norms' leaves), `qk` (the constant qk_norm_scale - 1: the heads'
    zero-centred leaves)."""
    e, v, d = s["hidden_size"], s["vocab_size"], s["head_dim"]
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    held, f = s["num_experts"], s["moe_intermediate_size"]
    fs, n = s["shared_expert_intermediate_size"], s["num_experts_published"]
    kd, vd = widths(s)
    hv, taps = s["linear_num_value_heads"], s["linear_conv_kernel_dim"]
    shapes = {"embed_tokens": {"kernel": ("embed", (v, e))}}
    for i, kind in enumerate(s["layer_types"]):
        shapes[f"b{i}_norm"] = {"scale": ("zeros", (e,))}
        if kind == "linear_attention":
            shapes[f"b{i}_delta"] = {
                "w_qkvz": ("normal", (e, 2 * kd + 2 * vd)),
                "w_ba": ("normal", (e, 2 * hv)),
                "conv_w": ("taps", (taps, 2 * kd + vd)),
                "a_log": ("a_log", (hv,)), "dt_bias": ("dt", (hv,)),
                "norm_scale": ("ones", (s["linear_value_head_dim"],)),
                "w_out": ("out", (vd, e))}
        else:
            shapes[f"b{i}_attn"] = {
                "wq": ("normal", (h, e, 2 * d)),
                "wk": ("normal", (kv, e, d)), "wv": ("normal", (kv, e, d)),
                "wo": ("out", (h, d, e)),
                "q_norm": ("qk", (d,)), "k_norm": ("qk", (d,))}
        shapes[f"b{i}_post_norm"] = {"scale": ("zeros", (e,))}
        shapes[f"b{i}_mixer"] = {
            "w_router": ("normal", (e, n)),
            "w_gate": ("normal", (held, e, f)),
            "w_up": ("normal", (held, e, f)),
            "w_down": ("out", (held, f, e)),
            "ws_gate": ("normal", (e, fs)), "ws_up": ("normal", (e, fs)),
            "ws_down": ("out", (fs, e)),
            "w_shared_gate": ("normal", (e, 1))}
    shapes["final_ln"] = {"scale": ("zeros", (e,))}
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
    constant = {"ones": 1.0, "zeros": 0.0, "qk": s["qk_norm_scale"] - 1.0}

    def init(key):
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
                elif kind == "a_log":
                    leaf = jnp.log(jax.random.uniform(
                        sub, shape, jnp.float32, 1e-3, 16.0))
                elif kind == "dt":
                    lo, hi = math.log(s["dt_min"]), math.log(s["dt_max"])
                    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(
                        sub, shape, jnp.float32))
                    leaf = dt + jnp.log(-jnp.expm1(-dt))
                else:
                    leaf = scale[kind] * jax.random.normal(
                        sub, shape, jnp.float32)
                out[name][pname] = leaf
        return out

    return jax.jit(init)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def build(config, s, chips, seed, machine_spec=None):
    import jax.numpy as jnp

    from flexflow_tpu import AdamOptimizer, FFConfig, LossType
    from flexflow_tpu.models import DecoderConfig, create_decoder

    # `program_*`: the controls of the published keys run the PROGRAM
    # built otherwise than the reference (module docstring)
    dc = DecoderConfig(
        layer_types=s["layer_types"], num_dense_layers=0,
        vocab_size=s["vocab_size"], hidden_size=s["hidden_size"],
        layer_norm_epsilon=s["rms_norm_eps"],
        num_attention_heads=s["num_attention_heads"],
        num_key_value_heads=s["num_key_value_heads"],
        head_dim=s["head_dim"],
        rope_theta=float(s.get("program_rope_theta", s["rope_theta"])),
        partial_rotary_factor=float(s.get("program_partial_rotary_factor",
                                          s["partial_rotary_factor"])),
        qk_layernorm=True, attn_output_gate=True, zero_centered_norms=True,
        linear_num_key_heads=s["linear_num_key_heads"],
        linear_num_value_heads=s["linear_num_value_heads"],
        linear_key_head_dim=s["linear_key_head_dim"],
        linear_value_head_dim=s["linear_value_head_dim"],
        linear_conv_kernel_dim=s["linear_conv_kernel_dim"],
        delta_chunk_size=s["delta_chunk_size"],
        hidden_act=s["hidden_act"],
        n_routed_experts=s["num_experts_published"],
        experts_held=s["num_experts"], expert_offset=s["expert_offset"],
        num_experts_per_tok=s.get("program_num_experts_per_tok",
                                  s["num_experts_per_tok"]),
        moe_intermediate_size=s["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=s[
            "shared_expert_intermediate_size"],
        router_scoring="softmax", shared_expert_gate=True,
        norm_topk_prob=s.get("program_norm_topk_prob", s["norm_topk_prob"]),
        slot_slack=s["slot_slack"],
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


REFERENCE_CONTROLS = ("delta_correction", "decay", "attention_gate",
                      "shared_gate")


def reference_kw(s):
    """Keyword arguments of the reference's forward; every value can be
    hashed (`common.compiled` keeps one program a set of them). A
    `reference_<mechanism>` size alters the REFERENCE (module docstring)."""
    kw = dict(num_hidden_layers=s["num_hidden_layers"],
              eps=s["rms_norm_eps"], layer_types=tuple(s["layer_types"]),
              rope_theta=float(s["rope_theta"]),
              rotary_dim=int(s["head_dim"] * s["partial_rotary_factor"]),
              linear_num_key_heads=s["linear_num_key_heads"],
              num_experts_per_tok=s["num_experts_per_tok"],
              norm_topk_prob=s["norm_topk_prob"],
              expert_offset=s["expert_offset"])
    kw.update({name: bool(s["reference_" + name])
               for name in REFERENCE_CONTROLS if "reference_" + name in s})
    return kw


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


def delta_rule_flops_a_chunk(s):
    """Forward FLOPs of the chunked rule's matrix products, a VALUE head
    and chunk of C rows (a multiply-add is 2): K K^T and Q K^T, the
    inverse's 2 (log2 C - 1) products of C^3, T K and T V, and the walk's
    W S, Q S, P V' and K^T V'. The count is of the shipped chunk size."""
    c, dk, dv = (s["delta_chunk_size"], s["linear_key_head_dim"],
                 s["linear_value_head_dim"])
    doublings = 2 * max(0, (c - 1).bit_length() - 1)
    return 2 * c * (2 * c * dk + doublings * c * c + c * dk + c * dv
                    + 3 * dk * dv + c * dv)


def forward_flops_per_token(s):
    """Forward FLOPs a token by part (a multiply-add is 2), added over the
    layers that run: the delta mixers' three products; the chunked rule's
    products (`delta_rule_flops_a_chunk`); the attention op's projections
    (the query's carries the gate: H x 2 x D columns); Q K^T and P V over
    the causal pairs; the expert layers (router, shared expert with its
    gate, the expected held pairs); the head."""
    e, d, seq = s["hidden_size"], s["head_dim"], s["seq"]
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    kd, vd = widths(s)
    hv = s["linear_num_value_heads"]
    deltas = s["layer_types"].count("linear_attention")
    attns = len(s["layer_types"]) - deltas
    share = s["num_experts"] / s["num_experts_published"]
    return {
        "delta_products": deltas * 2 * e * (2 * kd + 3 * vd + 2 * hv),
        "delta_rule": deltas * hv * delta_rule_flops_a_chunk(s)
        / s["delta_chunk_size"],
        "projections": attns * 2 * e * d * (3 * h + 2 * kv),
        "scores": attns * 4 * h * d * (seq + 1) / 2,
        "experts": len(s["layer_types"]) * (
            6 * e * s["moe_intermediate_size"] * s["num_experts_per_tok"]
            * share + 2 * e * s["num_experts_published"]
            + 6 * e * s["shared_expert_intermediate_size"] + 2 * e),
        "head": 2 * e * s["vocab_size"]}


def train_flops_per_sample(s):
    """FLOPs the forward and backward of one sample require (backward is
    twice the forward; no recomputation)."""
    return 3 * s["seq"] * sum(forward_flops_per_token(s).values())


def delta_rule_step_flops_and_bytes(s):
    """What a step's chunked delta rules need, forward and backward, over
    the delta mixers that run. The count is of the work at the shipped
    chunk size and not of what implements it: FLOPs three times the
    forward's products; bytes in bfloat16, T = batch * seq positions: q
    and k at the key heads, v, and o at the value heads read or written
    once forward (2 Hk Dk + 2 Hv Dv lanes) and, backward, read again with
    dO and their gradients written (twice that and Hv Dv more), g and
    beta in float32 both ways."""
    ops = s["layer_types"].count("linear_attention")
    tokens = s["batch"] * s["seq"]
    kd, vd = widths(s)
    hv = s["linear_num_value_heads"]
    flops = 3 * ops * tokens * hv * delta_rule_flops_a_chunk(s) \
        / s["delta_chunk_size"]
    lanes = 2 * kd + 2 * vd
    return flops, ops * tokens * (2 * (3 * lanes + vd) + 4 * 4 * hv)


def flash_step_flops_and_bytes(s):
    """What a step's flash kernels need at a head of 256, forward and
    backward, over the VISIBLE causal pairs (`kernels.
    causal_flash_roofline`'s formula): forward Q K^T and P V, backward
    five products of the same size; bytes: q, k, v, o forward, and q, k,
    v, o, dO, dq, dk, dv backward, bfloat16, k and v at the KV heads."""
    ops = s["layer_types"].count("full_attention")
    h, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                s["head_dim"])
    pairs = s["batch"] * s["seq"] * (s["seq"] + 1) / 2
    tokens = s["batch"] * s["seq"]
    flops = ops * 7 * 2 * h * d * pairs
    return flops, ops * 2 * tokens * d * (5 * h + 6 * kv)


# ---------------------------------------------------------------------------
# checks, and what only the loaded program can tell


def expected_chunks(s):
    """`delta/chunks` of one step: layers x value heads x chunks."""
    return (s["layer_types"].count("linear_attention") * s["batch"]
            * s["linear_num_value_heads"]
            * -(-s["seq"] // s["delta_chunk_size"]))


def extra_checks(ff, s, chips, on_tpu):
    out = []
    held = int(sum(leaf.size for leaves in ff.params.values()
                   for leaf in leaves.values()))
    out.append(("parameters_as_counted", held == parameters(s), held))
    kinds = ["linear_attention" if n.op.op_type.name == "DELTA_MIXER" else
             "full_attention" for n in ff.executor.nodes
             if n.op.op_type.name in ("DELTA_MIXER", "MULTIHEAD_ATTENTION")]
    out.append(("mixers_by_layer", kinds == s["layer_types"], kinds))
    # the routers as the cell states them: this chip holds 16 of 512
    # experts, a token sends 0.31 of its 10 pairs here, and the logits
    # hardly see how many it chose or whether their weights sum to one
    # (the configuration's census), so the built ops say it
    routers = sorted({(n.op.n_experts, n.op.k, n.op.norm_topk, n.op.scoring)
                      for n in ff.executor.nodes
                      if n.op.op_type.name == "MOE_LAYER"})
    out.append(("routers_as_stated", routers == [(
        s["num_experts_published"], s["num_experts_per_tok"],
        s["norm_topk_prob"], "softmax")], routers))
    if on_tpu and chips == 1:
        impls = _attention_impls(ff)
        out.append(("attention_all_flash",
                    len(impls) == kinds.count("full_attention")
                    and set(impls.values()) == {"flash"}, impls))
    return out


def kernel_fallbacks(ff):
    """What makes a run not correct beside the comparison: attention ops
    that fell back from the searched kernel, pairs that the expert
    layers' buffer could not hold, a count of the delta rule's chunks
    (`delta/chunks` of the program's last epoch) that is not EXACTLY
    layers x heads x chunks a step, for one step or the whole epoch, held
    pairs further than 15% from their expectation, and on the chip a
    delta mixer whose walk ran outside its kernels or an attention op
    outside the wide-head kernels. Also prints the counters (the cell's
    `observed` line) and keeps them for the readers, which take their
    scopes from the join table the program writes."""
    out = {n.op.name: n.op._kernel_fallback for n in ff.executor.nodes
           if getattr(n.op, "_kernel_fallback", None)}
    counters = dict(getattr(ff, "op_counters", None) or {})
    if counters.get("moe/overflow_slots"):
        out["moe/overflow_slots"] = counters["moe/overflow_slots"]
    s = _sizes
    if s:
        a_step = expected_chunks(s)
        got = counters.get("delta/chunks")
        if got not in (a_step, a_step * s["steps_per_epoch"]):
            out["delta/chunks"] = dict(program=got, a_step=a_step)
        # the pairs that landed on the held experts against what the
        # stated top-k sends them if tokens chose independently (the
        # float32 reference routes 0.98-1.06 of it by layer at the seeded
        # weights; at the configuration's alpha the routers drift a few
        # hundredths more over a run: its file, under `departures`)
        held = counters.get("moe/slots_held", 0.0)
        want = len(s["layer_types"]) * expected_held_slots(s)
        # 15%, or four standard deviations of so few pairs (a rehearsal)
        if not any(abs(held / (want * steps) - 1.0)
                   <= max(0.15, 4.0 / math.sqrt(want * steps))
                   for steps in (1, s["steps_per_epoch"])):
            out["moe/slots_held"] = dict(program=held, a_step=want)
    if ff.executor.mesh.devices.flat[0].platform == "tpu":
        for gauge, ops in (("executor.delta_rule_kernel_ops",
                            "executor.delta_mixer_ops"),):
            if counters.get(gauge) != counters.get(ops):
                out[gauge] = counters.get(gauge)
        if not counters.get("executor.flash_wide_head_ops"):
            out["executor.flash_wide_head_ops"] = counters.get(
                "executor.flash_wide_head_ops")
    observed.clear()
    observed["op_counters"] = counters
    print(json.dumps(dict(phase="observed", op_counters=counters)),
          flush=True)
    return out
