"""The gated short convolution, a head that reads the embedding's table,
and grouped-query attention at heads that are not a lane block wide with
a norm of every head (PR 45; `benchmarks/references/lfm2.py` is the plain
float32 reference, which shares no code with `flexflow_tpu`): the
convolution op alone against the three-term sum, forward and backward;
batch 2, whose second sample reads nothing of the first; the model
against the reference for logits, three losses and every gradient leaf;
ONE table with the sum of both uses' gradients, one Adam state and a
checkpoint round trip; the share test that ties a chip's experts to the
uncut layer; the search's price of the new op; the four controls."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_model as fm
from benchmarks import harness as hs
from benchmarks.references import lfm2 as ref
from family_model import ROOT, OpContext, make_op, run_op
from flexflow_tpu.ffconst import OperatorType
from one_program import output_and_gradients

CELL = "lfm2_8b_a1b.s16384_b1.1chip"
# every width small, the structure whole: conv + MLP, attention + experts,
# conv + experts three times; 4 query heads a key/value head; 4 held
# experts of 16, none shared; ONE table
TINY = dict(num_hidden_layers=5, vocab_size=64, hidden_size=32,
            num_attention_heads=8, num_key_value_heads=2, head_dim=8,
            intermediate_size=48, num_experts=4, num_experts_published=16,
            num_experts_per_tok=3, moe_intermediate_size=24, slot_slack=3.0,
            initializer_range=0.2, embedding_std=0.2, seq=32, batch=2,
            steps_per_epoch=1)


# ---------------------------------------------------------------------------
# the convolution op alone


def three_term_sum(h, p, taps=3, gate=True):
    """y = (C * sum_j w_j (B * x)_{t - (K-1) + j}) W_out, as written."""
    b, c, x = jnp.split(h @ p["w_in"], 3, axis=-1)
    u = b * x
    out = jnp.zeros_like(u)
    for t in range(u.shape[1]):
        for j in range(taps):
            at = t - (taps - 1) + j
            if at >= 0:
                out = out.at[:, t].add(p["conv_w"][j] * u[:, at])
    return ((c * out) if gate else out) @ p["w_out"]


@pytest.mark.parametrize("taps,gate", [(3, True), (2, True), (4, True),
                                       (3, False)])
def test_short_conv_matches_the_three_term_sum(taps, gate):
    rs = np.random.RandomState(taps)
    h = jnp.asarray(rs.randn(2, 12, 16), jnp.float32)
    op = make_op(OperatorType.SHORT_CONV,
                 dict(kernel=taps, **({} if gate else {"output_gate": False})),
                 [h.shape])
    p = op.init_params(jax.random.PRNGKey(1))
    assert {k: v.shape for k, v in p.items()} == {
        "w_in": (16, 48), "conv_w": (taps, 16), "w_out": (16, 16)}
    assert op.params_elems() == 4 * 16 * 16 + taps * 16
    # the sum as written and its gradients, one program
    ctx = OpContext(training=True, compute_dtype=jnp.float32)
    weight = rs.randn(*h.shape).astype(np.float32)
    with fm.highest():
        want, want_grads = output_and_gradients(
            lambda p, h: three_term_sum(h, p, taps, gate), weight, p, h)
    np.testing.assert_allclose(run_op(op, p, [h]), want, rtol=1e-5,
                               atol=1e-6)
    # the reference writes the same sum as shifted products
    if gate:
        with fm.highest():
            np.testing.assert_allclose(
                jax.jit(lambda h, p: ref.short_conv(h, p, "f32"))(h, p),
                want, rtol=1e-5, atol=1e-6)
    # backward: the op's own (it keeps the projection alone) against
    # autodiff of the sum as written, for the input and every leaf
    with fm.highest():
        got = jax.jit(jax.grad(lambda p, h: jnp.sum(
            op.forward(p, [h], ctx)[0] * weight), argnums=(0, 1)))(p, h)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)
    assert op.traced_gauges() == {"executor.short_conv_ops": 1,
                                  "executor.gated_conv_kernel_ops": 0}


def test_the_second_sample_reads_nothing_of_the_first():
    """Batch 2: the zeros ahead of a sample's start are its own. Change
    the first sample alone and the second's output, position 0 and 1
    among them, stays bit for bit; in bfloat16 too, as the cell runs."""
    rs = np.random.RandomState(0)
    h = jnp.asarray(rs.randn(2, 10, 16), jnp.float32)
    other = h.at[0].set(jnp.asarray(rs.randn(10, 16), jnp.float32))
    op = make_op(OperatorType.SHORT_CONV, {}, [h.shape])
    p = op.init_params(jax.random.PRNGKey(3))
    for dtype in (jnp.float32, jnp.bfloat16):
        ctx = OpContext(training=False, compute_dtype=dtype)
        f = jax.jit(lambda p, x: op.forward(p, [x], ctx)[0])
        a, b = np.asarray(f(p, h)), np.asarray(f(p, other))
        assert np.array_equal(a[1], b[1])
        assert not np.array_equal(a[0], b[0])
    # position 0 sees its own lane alone: y_0 = (C_0 * w_2 * B_0 * x_0) W_out
    with fm.highest():
        b_, c_, x_ = jnp.split(h[1, 0] @ p["w_in"], 3)
        want = (c_ * p["conv_w"][2] * b_ * x_) @ p["w_out"]
    np.testing.assert_allclose(run_op(op, p, [h])[1, 0], want, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("batch,seq,width,taps,gate,dtype", [
    (2, 512, 256, 3, True, jnp.float32),     # two row blocks, two samples
    (1, 384, 128, 2, True, jnp.float32),     # three blocks of 128 rows
    (1, 256, 640, 4, False, jnp.float32),    # a 512-lane and a 128 chunk
    (1, 512, 256, 3, True, jnp.bfloat16)])   # as the cell stores it
def test_the_one_pass_kernel_matches_the_jax_numpy_form(
        batch, seq, width, taps, gate, dtype, monkeypatch):
    """`pallas_kernels.gated_conv_lanes`, interpreted: the value, d proj
    and the taps' gradient against `ops.short_conv.gated_conv`, whose
    backward the first test holds to autodiff. The rows a block takes
    from its neighbour (and the zeros at a sample's two ends) are what
    two blocks and two samples exercise."""
    from flexflow_tpu.ops import pallas_kernels as pk
    from flexflow_tpu.ops.short_conv import gated_conv
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert pk.gated_conv_shape_legal(seq, width, taps)
    assert not pk.gated_conv_shape_legal(seq + 8, width, taps)
    assert not pk.gated_conv_shape_legal(seq, width, 9)
    keys = jax.random.split(jax.random.PRNGKey(seq + taps), 3)
    proj = jax.random.normal(keys[0], (batch, seq, 3 * width)).astype(dtype)
    w = jax.random.normal(keys[1], (taps, width), jnp.float32)
    dy = jax.random.normal(keys[2], (batch, seq, width)).astype(dtype)

    def both(form):     # one program a form
        def run(proj, w, dy):
            y, vjp = jax.vjp(lambda p, w: form(p, w, gate), proj, w)
            return (y, *vjp(dy))
        return jax.jit(run)(proj, w, dy)

    got, want = both(pk.gated_conv_lanes), both(gated_conv)
    # one rounding of the stored dtype apart, where the sums' order differs
    atol = 1e-5 if dtype == jnp.float32 else 2 ** -7
    for g, v in zip(got, want):
        assert g.dtype == v.dtype and g.shape == v.shape
        scale = float(np.max(np.abs(np.asarray(v, np.float32))))
        np.testing.assert_allclose(np.asarray(g, np.float32) / scale,
                                   np.asarray(v, np.float32) / scale,
                                   atol=atol)


def test_the_op_takes_the_kernel_where_the_shape_allows(monkeypatch):
    """With Pallas on (here interpreted) an op over whole blocks runs the
    one-pass kernel and says so; off, or over a length no block divides,
    XLA's fusions; the two agree."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    rs = np.random.RandomState(5)
    h = jnp.asarray(rs.randn(1, 256, 128), jnp.float32)
    op = make_op(OperatorType.SHORT_CONV, {}, [h.shape])
    p = op.init_params(jax.random.PRNGKey(2))
    with_kernel = run_op(op, p, [h])
    assert op.traced_gauges() == {"executor.short_conv_ops": 1,
                                  "executor.gated_conv_kernel_ops": 1}
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    np.testing.assert_allclose(run_op(op, p, [h]), with_kernel, rtol=1e-5,
                               atol=1e-6)
    assert op.traced_gauges()["executor.gated_conv_kernel_ops"] == 0
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    odd = make_op(OperatorType.SHORT_CONV, {}, [(1, 200, 128)])
    run_op(odd, p, [h[:, :200]])
    assert odd.traced_gauges()["executor.gated_conv_kernel_ops"] == 0


def test_the_search_prices_the_convolution_op():
    from flexflow_tpu.search.unity import _node_attrs, _param_shapes
    op = make_op(OperatorType.SHORT_CONV, {}, [(1, 2048, 64)])
    assert _param_shapes(op) == {"w_in": [64, 192], "conv_w": [3, 64],
                                 "w_out": [64, 64]}
    # the two products and 8 FLOPs an element between them
    assert op.flops() == 2 * 2048 * 64 * 4 * 64 + 8 * 2048 * 64
    assert _node_attrs(op)["interior_bytes"] == 2048 * 4 * 64 * 4
    # a position reads the two before it: the sequence is not a SEQ dim
    from flexflow_tpu.ops.base import DimRole
    assert op.output_dim_roles() == [(DimRole.SAMPLE, DimRole.OTHER,
                                      DimRole.CHANNEL)]


# ---------------------------------------------------------------------------
# the model


@pytest.fixture(scope="module")
def tiny():
    return fm.build_tiny(CELL, TINY)


def test_create_decoder_builds_the_cut_from_the_public_keys(tiny):
    family, _, s, _, _, _, _, ff = tiny
    assert s["layer_types"] == ["conv", "full_attention"] + ["conv"] * 3
    ops = {n.op.name: n.op for n in ff.executor.nodes}
    assert [f"b{i}_conv" in ops for i in range(5)] == [
        True, False, True, True, True]
    attn = ops["b1_attn"]
    assert (attn.num_heads, attn.num_kv_heads, attn.head_dim) == (8, 2, 8)
    assert attn.qk_norm and attn.rope and attn.causal and not attn.gate
    assert attn.rope_theta == 1e6 and attn.qk_norm_eps == 1e-5
    assert all(ops[f"b{i}_conv"].kernel == 3 for i in (0, 2, 3, 4))
    assert "b0_gate_up_proj" in ops and "b0_mixer" not in ops
    assert all(ops[f"b{i}_mixer"].experts_held == 4
               and ops[f"b{i}_mixer"].scoring == "sigmoid"
               and ops[f"b{i}_mixer"].shared_width == 0
               for i in range(1, 5))
    assert ops["lm_head"].tied_params == {"kernel": ("embed_tokens",
                                                     "kernel")}
    assert ff.search_seconds is not None and ff.strategy
    # every op got a choice, the new one among them
    by_name = {n.op.name: ff.strategy[n.op.guid].choice
               for n in ff.executor.nodes}
    assert by_name["b0_conv"] and by_name["lm_head"]
    # the letters and the public config's kinds are one thing
    from flexflow_tpu.models import DecoderConfig, create_decoder
    by_letters = create_decoder(DecoderConfig(
        hybrid_override_pattern="CFC", num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, num_dense_layers=1))
    names = [layer.name for layer in by_letters.layers]
    assert {"b0_conv", "b0_gate_up_proj", "b1_attn", "b2_mixer"} <= set(names)
    with pytest.raises(ValueError, match="conv.*full_attention"):
        create_decoder(DecoderConfig(layer_types=["hyena"]))


def test_model_against_the_reference_logits_and_three_losses(tiny):
    family, config, s, traffic, xs, y, weights, ff = tiny
    system, _ = hs.system_side(ff, xs, y, s["batch"])
    want = hs.reference_side(family, weights, s, traffic, config, xs, y,
                             s["batch"])
    assert system["preds"].shape == (s["batch"], s["seq"], s["vocab_size"])
    np.testing.assert_allclose(system["preds"], want["preds"], rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(system["losses"], want["losses"], rtol=2e-5)
    assert want["losses"][2] < want["losses"][0] - 1e-3   # the steps moved it
    counters = ff.op_counters
    assert counters["moe/overflow_slots"] == 0 and \
        counters["moe/slots_held"] > 0
    assert counters["executor.short_conv_ops"] == 4
    assert counters["executor.tied_head_ops"] == 1
    # heads of 8 lanes on the CPU: the einsum core, the 4-D rotary, the
    # repeat (on the chip at heads of 64, two a 128-lane column: flash,
    # the heads' norm and rotary as the lane-dense pass and K and V at
    # the KV heads since PR 47: both counters 1)
    assert counters["executor.rotary_lane_dense_ops"] == 0
    assert counters["executor.flash_grouped_kv_ops"] == 0


@pytest.fixture(scope="module")
def gradients(tiny):
    return fm.gradients_of(tiny)


def test_every_gradient_leaf_matches_the_reference(gradients):
    _, got, want = gradients
    # the table; 4 conv layers of 2 norms + 3 leaves; the attention
    # layer's 2 + 6; the MLP's 2; 4 expert layers' 4; the final norm
    assert fm.assert_leaves_close(got, want, still=("e_bias",)) == (
        1 + 4 * 5 + 8 + 2 + 4 * 4 + 1)


def test_one_table_with_both_uses_gradients_and_one_adam_state(
        tiny, gradients, tmp_path):
    family, _, s, _, xs, y, weights, ff = tiny
    table = (s["vocab_size"], s["hidden_size"])
    held = [(name, leaf) for name, leaves in ff.params.items()
            for leaf, value in leaves.items()
            if sorted(value.shape) == sorted(table)]
    assert held == [("embed_tokens", "kernel")] and "lm_head" not in ff.params
    # one Adam state, and one compute copy, of that shape
    def tables(tree):
        return [x for x in jax.tree.leaves(tree)
                if sorted(getattr(x, "shape", ())) == sorted(table)]
    assert len(tables(ff.opt_state)) == 2          # m and v of the ONE leaf
    assert len(tables(ff.state)) <= 1              # the compute copy, if any
    # the step's arguments hold no second [V, E] array
    step = ff.executor.make_train_step()
    lowered = step.lower(ff.params, ff.opt_state, ff.state,
                         ff._stage_inputs([xs[0]]), ff._shard_batch(y),
                         jax.random.PRNGKey(0))
    args = jax.tree.leaves(lowered.args_info)
    assert sum(sorted(a.shape) == sorted(table) for a in args) == \
        1 + 2 + len(tables(ff.state))
    # its gradient is the head's dW plus the gather's scatter-add: take
    # the two uses apart in the reference by giving the head a copy
    params, got, _ = gradients

    def two_tables(table_in, table_out):
        w = dict(params, embed_tokens={"kernel": table_in})
        kw = family.reference_kw(s)
        x = ref.hidden_states(w, jnp.asarray(xs[0]), s["num_hidden_layers"],
                              kw, "f32")
        x = ref.rms_norm(x, w["final_ln"]["scale"], kw["eps"])
        logits = jnp.einsum("bse,ve->bsv", x, table_out)
        return jnp.sum(ref.sample_losses(logits, jnp.asarray(y))) / y.size

    e = params["embed_tokens"]["kernel"]
    with fm.highest():
        gather, head = jax.jit(jax.grad(two_tables, argnums=(0, 1)))(e, e)
    assert float(jnp.max(jnp.abs(gather))) > 0 < float(jnp.max(jnp.abs(head)))
    scale = float(jnp.max(jnp.abs(gather + head)))
    np.testing.assert_allclose(
        np.asarray(got["embed_tokens"]["kernel"]) / scale,
        np.asarray(gather + head) / scale, atol=2e-4)
    # a checkpoint holds the one leaf and brings it back
    path = str(tmp_path / "ckpt")
    ff.save_checkpoint(path)
    before = ff.get_parameter("embed_tokens", "kernel")
    ff.set_parameter("embed_tokens", np.zeros_like(before), "kernel")
    ff.load_checkpoint(path)
    assert np.array_equal(ff.get_parameter("embed_tokens", "kernel"), before)
    got_logits = np.asarray(ff.predict([xs[0][:s["batch"]]]))
    assert np.isfinite(got_logits).all() and got_logits.std() > 0


_CONTROLS_REFERENCE = []
CONTROLS = [dict(program_conv_L_cache=2),
            dict(program_conv_output_gate=False),
            dict(program_tie_word_embeddings=False),
            dict(program_qk_layernorm=False)]


@pytest.mark.parametrize("control", CONTROLS,
                         ids=[next(iter(c)) for c in CONTROLS])
def test_a_program_built_otherwise_is_not_correct(tiny, control):
    """The four mechanism controls: two taps, the output gate left out, a
    table of its own for the head, the heads' norm left out; the
    reference as the cell states it. On the model's first two layers
    (conv + MLP, attention + experts)."""
    family = tiny.family
    sizes = dict(TINY, num_hidden_layers=2)
    if not _CONTROLS_REFERENCE:
        # the cut's weights and reference, made once: the `program_*`
        # keys reach `family.build` alone
        s = family.sizes(tiny.config, tiny.traffic, sizes)
        cut = tiny._replace(s=s, weights=jax.device_get(
            family.make_weights(s, 11)))
        _CONTROLS_REFERENCE.extend(
            [cut, fm.reference_predictions(cut)["preds"]])
    cut, want = _CONTROLS_REFERENCE
    weights = cut.weights
    if "program_qk_layernorm" in control:
        # scales of one and heads of unit variance would hide the norm
        weights = dict(weights, b1_attn=dict(weights["b1_attn"], **{
            name: weights["b1_attn"][name] * 3.0
            for name in ("q_norm", "k_norm")}))
        want = fm.reference_predictions(cut, weights=weights)["preds"]
    ff, s = fm.control_model(cut, dict(sizes, **control), weights)
    nrmse = hs.prediction_errors(fm.predictions(ff, cut), want,
                                 False)["nrmse"]
    assert nrmse > family.TOLERANCES["pred_nrmse"]
    checks = dict((n, ok) for n, ok, _ in family.extra_checks(ff, s, 1,
                                                              False))
    assert checks["one_table"] and checks["mixers_by_layer"]


def test_the_step_names_the_new_scopes(tiny):
    from flexflow_tpu.obs import step_scopes
    text = fm.compiled_step_text(tiny)
    for scope in ("jvp(jit(op_short_conv))/jit(gated_conv)",
                  "transpose(jvp(jit(op_short_conv)))",
                  "jit(attention_full))/jit(rotary_whole)",
                  "jvp(jit(head))", "jit(moe_layer)"):
        assert scope in text, scope
    rows = step_scopes.table_of(text).values()
    assert {r["part"] for r in rows
            if "jit(gated_conv)" in r["op_name"]} == {"op_short_conv"}
    directions = {r["direction"] for r in rows
                  if "jit(gated_conv)" in r["op_name"]}
    assert {"forward", "backward"} <= directions
    # no reader's bare substring lies in the new names
    for taken in ("moe_layer", "ssm_mixer", "ssd_scan", "flash_",
                  "attention_", "moe_combine"):
        assert taken not in "op_short_conv gated_conv"


def test_four_shares_add_up_to_the_uncut_layer():
    """The share ties to the model: 4 chips hold 4 of 16 experts each;
    their routed parts, plus what every chip computes alike (the
    convolution mixer; there is no shared expert) counted ONCE, are the
    reference's uncut expert layer."""
    rs = np.random.RandomState(7)
    x = jnp.asarray(rs.randn(2, 24, 32), jnp.float32)
    kw = dict(n_experts=16, k=3, hidden_size=24, shared_width=0, gated=True,
              activation="silu", routed_scaling=1.0, slot_slack=15.0)
    conv = make_op(OperatorType.SHORT_CONV, {}, [x.shape])
    full = make_op(OperatorType.MOE_LAYER, kw, [x.shape])
    w = {"b0_norm": {"scale": jnp.asarray(rs.rand(32) + 0.5, jnp.float32)},
         "b0_post_norm": {"scale": jnp.asarray(rs.rand(32) + 0.5,
                                               jnp.float32)},
         "b0_conv": conv.init_params(jax.random.PRNGKey(8)),
         "b0_mixer": full.init_params(jax.random.PRNGKey(9))}
    w["b0_mixer"]["e_bias"] = jnp.asarray(rs.randn(16) * 0.1, jnp.float32)
    ref_kw = dict(eps=1e-5, layer_types=("conv",), rope_theta=1e6,
                  num_experts_per_tok=3, routed_scaling_factor=1.0,
                  expert_offset=0)
    with fm.highest():
        want, h = jax.jit(lambda x, w: (
            ref.layer(x, w, 0, ref_kw, "f32"),
            ref.rms_norm(x, w["b0_norm"]["scale"], 1e-5)))(x, w)
    mixed = np.asarray(x) + run_op(conv, w["b0_conv"], [h])
    with fm.highest():
        g = jax.jit(ref.rms_norm, static_argnums=2)(
            mixed, w["b0_post_norm"]["scale"], 1e-5)
    # the reference's own share is the same part (a pair the buffer
    # could not hold would show here)
    parts = fm.expert_shares(
        kw, w["b0_mixer"], [g], 4, 4,
        reference=lambda share, offset: ref.experts(
            g, share, k=3, scaling=1.0, offset=offset, operand="f32"))
    np.testing.assert_allclose(mixed + sum(parts), want, rtol=2e-4,
                               atol=2e-5)


def test_search_prices_and_places_the_new_op_and_the_one_table(tiny):
    """The serialized graph states the convolution op's FLOPs, leaves,
    roles and interior, and the tied head with NO leaf of its own (the
    table is the embedding's: one choice, one gradient sync, one
    optimizer state); the native search offers the op replicated,
    batch-parallel and as `_r` twins."""
    from flexflow_tpu.search import native
    from flexflow_tpu.search.unity import serialize_graph
    if not native.available():
        pytest.skip("native search unavailable")
    ff = tiny[-1]
    nodes = serialize_graph(ff.executor.nodes)
    by_name = {n["name"]: n for n in nodes}
    conv, head, table = (by_name["b0_conv"], by_name["lm_head"],
                         by_name["embed_tokens"])
    assert conv["type"] == "SHORT_CONV"
    assert conv["roles"] == [["sample", "other", "channel"]]
    assert set(conv["params"]) == {"w_in", "conv_w", "w_out"}
    assert conv["flops"] > 0 and conv["attrs"]["interior_bytes"] > 0
    assert head["params"] == {} and head["flops"] == 2 * 2 * 32 * 32 * 64
    assert table["params"] == {"kernel": [64, 32]}
    machine = {"num_devices": 4, "flops": 197e12, "hbm_bw": 0.82e12,
               "hbm_cap": 16e9, "ici_bw": 45e9, "ici_latency": 1e-6,
               "dcn_bw": 25e9, "dcn_latency": 1e-5, "num_slices": 1,
               "comm_bytes_factor": 0.5}
    resp = native.native_optimize(dict(
        nodes=nodes, machine=machine, measured={},
        config=dict(budget=2, training=True, enable_substitution=False,
                    enable_parameter_parallel=True, batch=TINY["batch"],
                    emit_search_trace=True)))
    ops = {o["name"]: o for o in resp["search_trace"]["ops"]}
    conv_choices = {c["choice"] for c in ops["b0_conv"]["candidates"]}
    assert {"rep", "dp"} <= {c.split("_")[0] for c in conv_choices}
    assert any(c.endswith("_r") for c in conv_choices), conv_choices
    # the head has no kernel to shard: its choices carry no model axis
    head_choices = {c["choice"] for c in ops["lm_head"]["candidates"]}
    assert not any("col" in c or "row" in c for c in head_choices)
    assert all(c["terms"]["fwd_s"] > 0 and c["memory"]["param_bytes"] > 0
               for c in ops["b0_conv"]["candidates"])
    assert all(c["memory"]["param_bytes"] == 0
               for c in ops["lm_head"]["candidates"])


def test_the_cache_refuses_a_convolution_layer(tiny):
    from flexflow_tpu.serve.kv_cache import init_kv_cache
    with pytest.raises(NotImplementedError, match="short convolution"):
        init_kv_cache(tiny[-1], max_len=TINY["seq"])


def test_fflint_knows_the_new_op_and_the_tied_head(tiny):
    from flexflow_tpu import lint_model
    report = lint_model(tiny[-1])
    assert not [d for d in report.diagnostics
                if d.severity.name == "ERROR"], report.diagnostics


def test_reference_counts_tie_to_the_configuration():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2_8b_a1b.json")) as f:
        config = json.load(f)
    from benchmarks.families import lfm2 as family
    s = family.sizes(config, dict(seq=16384, batch=1, steps_per_epoch=4))
    assert s["layer_types"] == ["conv", "full_attention"] + ["conv"] * 3
    assert s["dense_layers"] == 1
    assert family.parameters(s) == 491_043_072
    shapes = family.weight_shapes(s)
    assert "lm_head" not in shapes
    count = lambda name: sum(  # noqa: E731
        int(np.prod(shape)) for _, shape in shapes[name].values())
    assert count("b0_conv") == 16_783_360
    assert count("b1_attn") == 10_485_888
    assert count("b0_gate_up_proj") + count("b0_down_proj") == 44_040_192
    assert count("b1_mixer") == 88_080_384 + 65_536 + 32
    per = family.forward_flops_per_token(s)
    assert sum(per.values()) / 1e6 == pytest.approx(432.6, abs=0.1)
    assert per["conv_products"] / sum(per.values()) == pytest.approx(
        0.31, abs=0.005)
    assert family.train_flops_per_sample(s) / 1e12 == pytest.approx(21.26,
                                                                    abs=0.01)
    # the published counts decide the tie: one table, 8.34B / 1.56B
    conv, attn, mlp, expert, table = (16_783_360, 10_485_888, 44_040_192,
                                      11_010_048, 65_536 * 2048)
    rest = 18 * conv + 6 * attn + 2 * mlp + table
    assert (rest + 22 * 32 * expert) / 1e9 == pytest.approx(8.34, abs=0.01)
    assert (rest + 22 * 4 * expert) / 1e9 == pytest.approx(1.56, abs=0.01)
    assert (rest + table + 22 * 32 * expert) / 1e9 == pytest.approx(
        8.47, abs=0.01)
