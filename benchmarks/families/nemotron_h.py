"""Family `nemotron_h`: a hybrid Mamba-2 / mixture-of-experts / attention
decoder (NVIDIA-Nemotron-3-Nano-30B-A3B), one chip's share of a stated
deployment, built through `flexflow_tpu.models.create_decoder` +
`FFModel.compile`. See `bert_ae.py` for what a family gives the harness.
Beside that: `ssd_step_flops_and_bytes` and
`grouped_matmul_step_flops_and_bytes` for the two kernel rooflines, and
`observed`, which `kernel_fallbacks` fills after the window (the program is
still loaded then) for the readers under `layer_metrics/`: the compiled
step's scope of every HLO instruction, and the routing counts.
"""

import json
import math
import re
import time

import numpy as np

from benchmarks.references import nemotron_h as reference_module

# Limits of the output check; both readings of each in PERF.md ("The output
# check"), from `seeds_check.py` on the chip at the cell's own sizes (PR 27).
# (a) pred_nrmse: RMS error of the logits on the first batch over the
#     standard deviation of the reference's. Program 0.0041-0.0049 over 18
#     seeds (the reference with bfloat16 operands reads the same,
#     0.0043-0.0049), float8 control 0.0473-0.0476 over 6: the limit sits
#     between, three times the program's largest and a third of the
#     control's smallest. A lower precision fails by this limit alone.
# (b) loss0_rel: relative error of the step-0 loss, a guard on the loss
#     and label path. The precision hardly moves it (program at most
#     2.1e-5, the float8 control 2e-5 to 1.4e-4), so about three times the
#     program's largest reading.
# (c) later_loss_rel: largest relative error of the losses of steps 1-2
#     against the reference's own Adam steps; program at most 2.1e-5 over
#     18 seeds, so likewise. Adam without bias correction reads 9.0e-4.
TOLERANCES = {"pred_nrmse": 1.5e-2, "loss0_rel": 6.0e-5,
              "later_loss_rel": 6.0e-5}

# what the program showed of itself after the window (see kernel_fallbacks)
observed = {}

SIZE_KEYS = (
    "hybrid_override_pattern", "num_hidden_layers", "vocab_size", "hidden_size",
    "layer_norm_epsilon", "num_attention_heads", "num_key_value_heads",
    "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
    "ssm_state_size", "conv_kernel", "chunk_size", "time_step_min",
    "time_step_max", "time_step_floor", "n_routed_experts",
    "n_routed_experts_published", "expert_offset", "num_experts_per_tok",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "routed_scaling_factor", "norm_topk_prob", "slot_slack",
    "initializer_range", "embedding_std")


def sizes(config, traffic, overrides=None):
    s = {k: config[k] for k in SIZE_KEYS}
    s.update(seq=traffic["seq"], batch=traffic["batch"],
             steps_per_epoch=traffic["steps_per_epoch"])
    s.update(overrides or {})
    return s


def pattern_of(s):
    """The blocks that run: the model's first `num_hidden_layers`."""
    return s["hybrid_override_pattern"][:s["num_hidden_layers"]]


def make_data(s, seed):
    """One epoch of token ids uniform over the rows of the vocabulary that
    are held; the labels are the next token."""
    rng = np.random.default_rng(seed)
    n = s["batch"] * s["steps_per_epoch"]
    ids = rng.integers(0, s["vocab_size"], size=(n, s["seq"] + 1),
                       dtype=np.int32)
    return [np.ascontiguousarray(ids[:, :-1])], np.ascontiguousarray(
        ids[:, 1:])


def weight_shapes(s):
    """name -> leaf -> (kind, shape); kinds: `normal` (std
    initializer_range), `conv` (uniform +-k^-1/2), `dt`, `a_log`, `ones`,
    `zeros` (the routers' bias, which `balance_routers` then sets)."""
    e, v = s["hidden_size"], s["vocab_size"]
    h, p = s["mamba_num_heads"], s["mamba_head_dim"]
    gn = s["n_groups"] * s["ssm_state_size"]
    d_inner, conv_dim = h * p, h * p + 2 * gn
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    held, f, fs = (s["n_routed_experts"], s["moe_intermediate_size"],
                   s["moe_shared_expert_intermediate_size"])
    shapes = {"embed_tokens": {"kernel": ("embed", (v, e))}}
    for i, letter in enumerate(pattern_of(s)):
        shapes[f"b{i}_norm"] = {"scale": ("ones", (e,))}
        if letter == "M":
            shapes[f"b{i}_mixer"] = {
                "w_in": ("normal", (e, d_inner + conv_dim + h)),
                "conv_w": ("conv", (s["conv_kernel"], conv_dim)),
                "conv_b": ("conv", (conv_dim,)),
                "dt_bias": ("dt", (h,)), "a_log": ("a_log", (h,)),
                "d": ("ones", (h,)), "norm_scale": ("ones", (d_inner,)),
                "w_out": ("out", (d_inner, e))}
        elif letter == "E":
            shapes[f"b{i}_mixer"] = {
                "w_router": ("normal", (e, s["n_routed_experts_published"])),
                "e_bias": ("zeros", (s["n_routed_experts_published"],)),
                "w_up": ("normal", (held, e, f)),
                "w_down": ("out", (held, f, e)),
                "ws_up": ("normal", (e, fs)), "ws_down": ("out", (fs, e))}
        else:
            shapes[f"b{i}_mixer"] = {
                "wq": ("normal", (heads, e, d)), "wk": ("normal", (kv, e, d)),
                "wv": ("normal", (kv, e, d)), "wo": ("out", (heads, d, e))}
    shapes["final_ln"] = {"scale": ("ones", (e,))}
    shapes["lm_head"] = {"kernel": ("normal", (e, v))}
    return shapes


def make_weights(s, seed):
    """All weights on the device in one jitted call from the seed, float32;
    the same tree goes to the program and to the reference."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(s)
    std = s["initializer_range"]
    depth = len(s["hybrid_override_pattern"])     # the published model's
    lo, hi, floor = (s["time_step_min"], s["time_step_max"],
                     s["time_step_floor"])

    def leaf(key, kind, shape):
        if kind == "ones":
            return jnp.ones(shape, jnp.float32)
        if kind == "zeros":
            return jnp.zeros(shape, jnp.float32)
        if kind in ("normal", "out", "embed"):
            scale = {"normal": std, "out": std / math.sqrt(depth),
                     "embed": s["embedding_std"]}[kind]
            return scale * jax.random.normal(key, shape, jnp.float32)
        if kind == "conv":
            bound = 1.0 / math.sqrt(s["conv_kernel"])
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        if kind == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        # dt_bias: dt log-uniform in [lo, hi], floored; inverse softplus
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    def init(key):
        out = {}
        for name, leaves in shapes.items():
            out[name] = {}
            for pname, (kind, shape) in leaves.items():
                key, sub = jax.random.split(key)
                out[name][pname] = leaf(sub, kind, shape)
        return balance_routers(out, jnp.asarray(ids), s)

    ids = make_data(dict(s, steps_per_epoch=1), seed)[0][0]
    return jax.jit(init)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def balance_routers(w, ids, s):
    """Set every router's score-correction bias `e_bias` so that the
    experts' loads even out, on the seed's first batch, layer by layer, in
    the reference's float32 arithmetic: b_e = -(the score of expert e that
    a share k / E of the batch's tokens exceeds), so that every expert's
    corrected score passes a common mark for the same share of tokens.
    The published model gets there by training (loss-free balancing moves
    b_e up while expert e is under-loaded and down while it is
    over-loaded); one pass over the scores' quantiles gives the balanced
    state without stepping a rule to a fixed point, which would leave
    tokens exactly on the margin between two experts. Seeded random
    weights alone give every token nearly the same ranking of experts
    (the mixers' outputs share a large common component): a few experts
    take most pairs, and the eight held here anything from none to
    several times their share, seed to seed and layer to layer. A trained
    model's routers are balanced, and a step's work should not depend on
    the seed. The measured steps leave the bias as set here."""
    import jax.numpy as jnp

    kw = reference_kw(s)
    k, n = s["num_experts_per_tok"], s["n_routed_experts_published"]
    x = w["embed_tokens"]["kernel"][ids]
    for i, letter in enumerate(kw["pattern"]):
        p = w[f"b{i}_mixer"]
        h = reference_module.rms_norm(x, w[f"b{i}_norm"]["scale"], kw["eps"])
        if letter == "E":
            scores = reference_module.router_scores(h, p["w_router"])
            mark = jnp.quantile(scores.reshape(-1, n), 1.0 - k / n, axis=0)
            p = dict(p, e_bias=p["e_bias"] - mark)
            w = dict(w, **{f"b{i}_mixer": p})
        x = x + reference_module.mixer(letter, h, p, kw, "f32")
    return w


def build(config, s, chips, seed, machine_spec=None):
    import jax.numpy as jnp

    from flexflow_tpu import AdamOptimizer, FFConfig, LossType
    from flexflow_tpu.models import DecoderConfig, create_decoder

    dc = DecoderConfig(
        hybrid_override_pattern=pattern_of(s), vocab_size=s["vocab_size"],
        hidden_size=s["hidden_size"],
        layer_norm_epsilon=s["layer_norm_epsilon"],
        num_attention_heads=s["num_attention_heads"],
        num_key_value_heads=s["num_key_value_heads"], head_dim=s["head_dim"],
        mamba_num_heads=s["mamba_num_heads"],
        mamba_head_dim=s["mamba_head_dim"], n_groups=s["n_groups"],
        ssm_state_size=s["ssm_state_size"], conv_kernel=s["conv_kernel"],
        chunk_size=s["chunk_size"], time_step_min=s["time_step_min"],
        time_step_max=s["time_step_max"],
        time_step_floor=s["time_step_floor"],
        n_routed_experts=s["n_routed_experts_published"],
        experts_held=s["n_routed_experts"],
        expert_offset=s["expert_offset"],
        num_experts_per_tok=s["num_experts_per_tok"],
        moe_intermediate_size=s["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=s[
            "moe_shared_expert_intermediate_size"],
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
    for name, leaves in weights.items():
        for pname, value in leaves.items():
            ff.set_parameter(name, value, pname)


def readback(ff, weights):
    return (np.asarray(ff.get_parameter("lm_head", "kernel")),
            np.asarray(weights["lm_head"]["kernel"]))


def reference_kw(s):
    return dict(pattern=pattern_of(s), eps=s["layer_norm_epsilon"],
                mamba_num_heads=s["mamba_num_heads"],
                mamba_head_dim=s["mamba_head_dim"], n_groups=s["n_groups"],
                ssm_state_size=s["ssm_state_size"],
                num_experts_per_tok=s["num_experts_per_tok"],
                routed_scaling_factor=s["routed_scaling_factor"],
                expert_offset=s["expert_offset"])


def reference(s, traffic):
    """(module, keyword arguments of its forward, samples a chunk)."""
    return reference_module, reference_kw(s), traffic.get("reference_chunk",
                                                          1)


# ---------------------------------------------------------------------------
# operations and bytes, counted for the work done HERE (the experts held,
# the heads held, the vocabulary held)


def expected_held_slots(s):
    """(token, slot) pairs a step that land on a held expert, a layer, if
    routing is uniform: tokens * k * held / published."""
    return (s["batch"] * s["seq"] * s["num_experts_per_tok"]
            * s["n_routed_experts"] / s["n_routed_experts_published"])


def forward_flops_per_token(s):
    """Forward FLOPs a token by kind of layer (a multiply-add is 2):
    the four SSD products and the convolution with the mixer's two
    projections; causal attention at half the full scores; the router, the
    shared expert and the expected held slots; the head."""
    e, seq = s["hidden_size"], s["seq"]
    h, p, n = s["mamba_num_heads"], s["mamba_head_dim"], s["ssm_state_size"]
    g, q = s["n_groups"], s["chunk_size"]
    d_inner, conv_dim = h * p, h * p + 2 * g * n
    mamba = (2 * e * (d_inner + conv_dim + h) + 2 * conv_dim
             * s["conv_kernel"] + ssd_forward_flops_per_token(s)
             + 2 * d_inner * e)
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    attention = (2 * e * d * (2 * heads + 2 * kv)
                 + 4 * heads * d * seq / 2)           # causal: half
    share = s["n_routed_experts"] / s["n_routed_experts_published"]
    routed = (4 * e * s["moe_intermediate_size"]
              * s["num_experts_per_tok"] * share)
    shared = 4 * e * s["moe_shared_expert_intermediate_size"]
    router = 2 * e * s["n_routed_experts_published"]
    return {"M": mamba, "*": attention, "E": routed + shared + router,
            "head": 2 * e * s["vocab_size"]}


def ssd_forward_flops_per_token(s):
    h, p, n = s["mamba_num_heads"], s["mamba_head_dim"], s["ssm_state_size"]
    g, q = s["n_groups"], s["chunk_size"]
    # C B^T and (scores) x inside a chunk; the chunk's state and its read
    return 2 * (q * g * n + q * h * p + 2 * n * h * p)


def train_flops_per_sample(s):
    """FLOPs the forward and backward of one sample require (backward is
    twice the forward; no recomputation)."""
    per = forward_flops_per_token(s)
    layers = sum(per[letter] for letter in pattern_of(s))
    return 3 * s["seq"] * (layers + per["head"])


def ssd_step_flops_and_bytes(s):
    """What the chunked scans of one step need, forward and backward
    (three times the forward's FLOPs). Bytes in bfloat16: the forward
    reads x, B, C and writes y; the backward reads x, B, C, y's gradient
    and writes the gradients of x, B, C (dt and the decays, 1/64 of x,
    are left out)."""
    tokens = s["batch"] * s["seq"]
    layers = pattern_of(s).count("M")
    h, p = s["mamba_num_heads"], s["mamba_head_dim"]
    gn = s["n_groups"] * s["ssm_state_size"]
    flops = 3 * ssd_forward_flops_per_token(s) * tokens * layers
    x_bytes, bc_bytes = 2 * tokens * h * p, 2 * tokens * 2 * gn
    nbytes = ((2 * x_bytes + bc_bytes) + (3 * x_bytes + 2 * bc_bytes)) * layers
    return flops, nbytes


def grouped_matmul_step_flops_and_bytes(s, slots=None):
    """What the two grouped products of every expert layer need in one
    step, forward and backward, for `slots` (token, slot) pairs a layer
    that landed on held experts (the expected number by default). FLOPs
    3 * 4 * slots * hidden * width a layer. Bytes in bfloat16: each of the
    six products (two forward, two for the rows' gradients, two for the
    weights') reads or writes the held experts' two matrices once and the
    rows' operands and result once."""
    slots = expected_held_slots(s) if slots is None else slots
    e, f = s["hidden_size"], s["moe_intermediate_size"]
    layers = pattern_of(s).count("E")
    flops = 3 * 4 * slots * e * f * layers
    weights = 2 * s["n_routed_experts"] * e * f        # one matrix, bytes
    rows = 2 * slots * (e + f)
    return flops, 6 * (weights + rows) * layers


# ---------------------------------------------------------------------------
# checks


def _attention_impls(ff):
    axes = dict(zip(ff.mesh.axis_names,
                    (int(n) for n in ff.mesh.devices.shape)))
    return {n.op.name: n.op.selected_impl(axes, training=True)
            for n in ff.executor.nodes if hasattr(n.op, "selected_impl")}


def routing_flips(ff, s):
    """Share of (token, slot) pairs at the first expert layer whose chosen
    expert differs between the program (its own activations, bfloat16 on
    the chip) and the float32 reference, on ids made here: scores within
    rounding of the k-th."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.base import OpContext
    from flexflow_tpu.ops.moe import route_scores

    block = pattern_of(s).index("E")
    ex = ff.executor
    norm = next(n for n in ex.nodes if n.op.name == f"b{block}_norm")
    nodes = ex.nodes[:ex.nodes.index(norm) + 1]
    rng = np.random.default_rng(ff.config.seed)
    ids = rng.integers(0, s["vocab_size"], size=(s["batch"], s["seq"]),
                       dtype=np.int32)

    def program_choice(params, state, inputs):
        ctx = OpContext(training=False, compute_dtype=ex.compute_dtype,
                        mesh=ex.mesh)
        values = {}
        ex._run_nodes(nodes, params, state, inputs, values, {}, [], ctx)
        x = values[(norm.op.guid, 0)].astype(jnp.float32)
        w_r = params[f"b{block}_mixer"]["w_router"].astype(jnp.float32)
        scores = jax.nn.sigmoid(jnp.einsum(
            "bse,en->bsn", x, w_r, precision=jax.lax.Precision.HIGHEST))
        bias = params[f"b{block}_mixer"]["e_bias"].astype(jnp.float32)
        return route_scores(scores, bias, s["num_experts_per_tok"], True,
                            1.0)[1]

    ff._refresh_compute_params()
    from flexflow_tpu.executor import COMPUTE_PARAMS_KEY
    cparams = (ff.state[COMPUTE_PARAMS_KEY] if ex.use_master_copy
               else ff.params)
    got = np.asarray(jax.jit(program_choice)(
        cparams, ff.state, ff._stage_inputs([ids])))
    names = ["embed_tokens", f"b{block}_norm", f"b{block}_mixer"] + [
        f"b{i}_{part}" for i in range(block) for part in ("norm", "mixer")]
    shapes = weight_shapes(s)
    weights = {name: {p: ff.get_parameter(name, p) for p in shapes[name]}
               for name in names}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(
            lambda w, x: reference_module.routed_experts(
                w, x, block, **reference_kw(s)))(weights, jnp.asarray(ids)))
    k = got.shape[-1]
    same = (got[..., :, None] == want[..., None, :]).any(-1).sum()
    return 1.0 - float(same) / (got.size // k * k)


def extra_checks(ff, s, chips, on_tpu):
    out = []
    if on_tpu and chips == 1:
        impls = _attention_impls(ff)
        out.append(("attention_all_flash",
                    len(impls) == pattern_of(s).count("*")
                    and set(impls.values()) == {"flash"}, impls))
    flips = routing_flips(ff, s)
    # a report, not a limit: a flipped pair moves one expert's share of a
    # token's output, which the limit on the logits holds
    out.append(("routing_flip_share_first_expert_layer", True, flips))
    return out


def scopes_of_compiled_step(ff, s):
    """HLO instruction name -> `op_name` of the compiled train step (the
    program's named scopes are part of it), from the step that is already
    compiled: lowering it again with the same arguments finds it in JAX's
    caches."""
    import jax

    xs, y = make_data(dict(s, steps_per_epoch=1), 0)
    step = ff.executor.make_train_step()
    text = step.lower(ff.params, ff.opt_state, ff.state,
                      ff._stage_inputs(xs), ff._shard_batch(y),
                      jax.random.PRNGKey(0)).compile().as_text()
    return hlo_scopes(text)


INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?"
                         r"metadata=\{[^}]*?op_name=\"([^\"]*)\"", re.M)


def hlo_scopes(text):
    return dict(INSTRUCTION.findall(text))


def kernel_fallbacks(ff):
    """Attention ops that fell back from the searched kernel, and pairs
    that the expert layers' buffer could not hold: either makes the run
    not correct. Also records, for the readers of the per-layer metrics,
    what only the loaded program can tell."""
    out = {n.op.name: n.op._kernel_fallback for n in ff.executor.nodes
           if getattr(n.op, "_kernel_fallback", None)}
    counters = dict(getattr(ff, "op_counters", None) or {})
    if counters.get("moe/overflow_slots"):
        out["moe/overflow_slots"] = counters["moe/overflow_slots"]
    observed.clear()
    observed["op_counters"] = counters
    t0 = time.perf_counter()
    try:
        observed["scopes"] = scopes_of_compiled_step(ff, observed_sizes(ff))
    except Exception as e:      # the readers then return nothing
        observed["scopes_error"] = repr(e)
    print(json.dumps(dict(
        phase="observed", op_counters=counters,
        scoped_instructions=len(observed.get("scopes", ())),
        scopes_error=observed.get("scopes_error"),
        scopes_s=time.perf_counter() - t0)), flush=True)
    return out


def observed_sizes(ff):
    """What `make_data` needs, read off the loaded model."""
    batch, seq = ff.input_tensors[0].shape
    vocab = next(n.op.output_shapes[0][-1] for n in ff.executor.nodes
                 if n.op.name == "lm_head")
    return dict(batch=batch, seq=seq, vocab_size=vocab)
