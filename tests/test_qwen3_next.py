"""Gated delta-rule linear attention, gated attention whose gate comes a
lane out of the query projection, zero-centred norms and softmax
top-k-of-many experts with a gated shared expert (PR 58;
`benchmarks/references/qwen3_next.py` is the plain float32 reference,
which shares no code with `flexflow_tpu` and runs the delta rule one
position a step): the op alone against the reference's mixer; the gate a lane and the zero-centred norms against the
reference's attention; the model against the reference for logits, three
losses and every gradient leaf; a checkpoint round trip; the share test
that ties a chip's experts to the uncut layer; the search's price of the
new op and its refused remat twin; the controls of both kinds; the
refusals. The rule's forms alone (chunked against stepwise, the
triangular inverse) and the kernels are in
tests/test_delta_rule_kernels.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_model as fm
from benchmarks import harness as hs
from benchmarks.references import qwen3_next as ref
from family_model import make_op, run_op
from flexflow_tpu.ffconst import OperatorType

CELL = "qwen3_next_80b_a3b.s16384_b1.1chip"
# every width small, both kinds of layer (a period of two: a delta layer,
# then the attention layer); 2 key and 4 value heads of 8, chunks of 8;
# 4 : 2 attention heads of 16 of which 4 lanes rotate; 4 held experts of
# 16, top-3, a gated shared expert
TINY = dict(num_hidden_layers=2, full_attention_interval=2, vocab_size=64,
            hidden_size=32,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8,
            delta_chunk_size=8, num_experts=4, num_experts_published=16,
            num_experts_per_tok=3, moe_intermediate_size=24,
            shared_expert_intermediate_size=24, slot_slack=3.0,
            initializer_range=0.2, qk_norm_scale=4.0, seq=28, batch=2,
            steps_per_epoch=1)


# ---------------------------------------------------------------------------
# the ops alone


DELTA = dict(num_key_heads=2, num_value_heads=4, key_head_dim=8,
             value_head_dim=8, conv_kernel=4, chunk_size=8, eps=1e-6)


def test_delta_mixer_matches_the_references_mixer():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 19, 32))
    op = make_op(OperatorType.DELTA_MIXER, DELTA, [x.shape])
    p = op.init_params(jax.random.PRNGKey(5))
    assert {k: v.shape for k, v in p.items()} == {
        "w_qkvz": (32, 96), "w_ba": (32, 8), "conv_w": (4, 64),
        "a_log": (4,), "dt_bias": (4,), "norm_scale": (8,),
        "w_out": (32, 32)}
    assert op.params_elems() == sum(v.size for v in p.values())
    got = run_op(op, p, [x])
    with fm.highest():
        want = jax.jit(lambda x, p: ref.delta_mixer(
            x, p, key_heads=2, eps=1e-6, operand="f32"))(x, p)
        no_decay = jax.jit(lambda x, p: ref.delta_mixer(
            x, p, key_heads=2, eps=1e-6, operand="f32", decay=False))(x, p)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(no_decay - want))) > 1e-3
    # the second sample reads nothing of the first
    alone = run_op(make_op(OperatorType.DELTA_MIXER, DELTA, [(1, 19, 32)]),
                   p, [x[1:]])
    np.testing.assert_allclose(alone, got[1:], rtol=2e-4, atol=2e-5)
    assert op.traced_gauges() == {"executor.delta_mixer_ops": 1,
                                  "executor.delta_rule_kernel_ops": 0,
                                  "executor.delta_rule_heads_a_step": 0}
    kind, counted = op._counters["delta/chunks"]
    assert kind == "sum" and float(counted) == 2 * 4 * 3   # ceil(19 / 8)


ATTENTION = dict(embed_dim=32, num_heads=4, num_kv_heads=2, head_dim=16,
                 bias=False, causal=True, rope=True, rope_theta=1e7,
                 partial_rotary_factor=0.25, qk_norm=True, qk_norm_eps=1e-6,
                 qk_norm_zero_centered=True, lane_gate=True)


def test_gate_a_lane_and_zero_centred_head_norms_match_the_reference():
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 32))
    op = make_op(OperatorType.MULTIHEAD_ATTENTION, ATTENTION,
                 [x.shape] * 3)
    p = op.init_params(jax.random.PRNGKey(7))
    assert p["wq"].shape == (4, 32, 32) and p["wk"].shape == (2, 32, 16)
    # zero-centred leaves are drawn at zero: the scale is one
    assert not np.any(np.asarray(p["q_norm"]))
    p = dict(p, q_norm=p["q_norm"] + 1.5, k_norm=p["k_norm"] + 0.7)
    assert op.params_elems() == sum(v.size for v in p.values())
    got = run_op(op, p, [x, x, x])
    kw = dict(theta=1e7, rotary_dim=4, eps=1e-6, operand="f32")
    with fm.highest():
        want = jax.jit(lambda x, p: ref.attention(x, p, **kw))(x, p)
        bare = jax.jit(lambda x, p: ref.attention(x, p, gate=False, **kw))(
            x, p)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(bare - want))) > 1e-3
    with pytest.raises(ValueError, match="one output gate"):
        make_op(OperatorType.MULTIHEAD_ATTENTION,
                dict(ATTENTION, gate=True), [x.shape] * 3)
    with pytest.raises(NotImplementedError, match="gate a lane"):
        op.decode_forward(p, [x, x, x], fm.OpContext(), None, None, 0)


def test_zero_centred_norm_op():
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 5, 32))
    op = make_op(OperatorType.RMSNORM, dict(eps=1e-6, zero_centered=True),
                 [x.shape])
    p = op.init_params(jax.random.PRNGKey(0))
    assert not np.any(np.asarray(p["scale"]))
    p = {"scale": jnp.linspace(-0.5, 0.5, 32)}
    np.testing.assert_allclose(run_op(op, p, [x]),
                               ref.rms_norm(x, p["scale"], 1e-6), rtol=1e-5,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# the model


@pytest.fixture(scope="module")
def tiny():
    return fm.build_tiny(CELL, TINY)


def test_create_decoder_builds_the_cut_from_the_public_keys(tiny):
    family, _, s, _, _, _, _, ff = tiny
    assert s["layer_types"] == ["linear_attention", "full_attention"]
    ops = {n.op.name: n.op for n in ff.executor.nodes}
    assert "b0_delta" in ops and "b1_delta" not in ops
    attn = ops["b1_attn"]
    assert (attn.num_heads, attn.num_kv_heads, attn.head_dim) == (4, 2, 16)
    assert attn.lane_gate and attn.qk_norm and attn.qk_norm_offset == 1.0
    assert attn.rotary_dim == 4 and attn.rope_theta == 1e7
    delta = ops["b0_delta"]
    assert (delta.key_heads, delta.value_heads, delta.conv_kernel) == (
        2, 4, 4)
    assert all(ops[f"b{i}_mixer"].experts_held == 4
               and ops[f"b{i}_mixer"].scoring == "softmax"
               and ops[f"b{i}_mixer"].shared_gate
               and ops[f"b{i}_mixer"].shared_width == 24
               for i in range(2))
    assert all(ops[n].zero_centered for n in ("b0_norm", "b1_post_norm",
                                              "final_ln"))
    assert family.parameters(s) == sum(
        leaf.size for leaves in ff.params.values()
        for leaf in leaves.values())
    assert ff.search_seconds is not None and ff.strategy
    by_name = {n.op.name: ff.strategy[n.op.guid].choice
               for n in ff.executor.nodes}
    assert by_name["b0_delta"] and by_name["b1_attn"]
    # the letters and the public config's kinds are one thing
    from flexflow_tpu.models import DecoderConfig, create_decoder
    by_letters = create_decoder(DecoderConfig(
        hybrid_override_pattern="RF", num_attention_heads=4,
        num_key_value_heads=2, head_dim=16))
    names = [layer.name for layer in by_letters.layers]
    assert {"b0_delta", "b0_mixer", "b1_attn", "b1_mixer"} <= set(names)


def test_the_published_count_of_parameters():
    """424,340,544 at the cell's sizes, by the issue's table."""
    family, config, traffic = fm.load_cell(CELL)
    s = family.sizes(config, traffic)
    shapes = family.weight_shapes(s)

    def count(name):
        return sum(int(np.prod(shape)) for _, shape in shapes[name].values())

    assert count("b0_delta") == 33_718_464
    assert count("b3_attn") == 27_263_488
    assert count("b0_mixer") == 54_528_000
    assert family.parameters(s) == 424_340_544
    flops = family.forward_flops_per_token(s)
    assert family.train_flops_per_sample(s) == 3 * 16384 * sum(
        flops.values())
    assert family.expected_held_slots(s) == 5120
    assert family.expected_chunks(s) == 3 * 32 * 128


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
    assert counters["delta/chunks"] == 4 * 4 * 2    # ceil(28 / 8) = 4
    assert 0 < counters["delta/decay_min"] < counters["delta/decay_mean"] < 1
    assert counters["executor.delta_mixer_ops"] == 1
    assert counters["executor.delta_rule_kernel_ops"] == 0
    assert counters["executor.delta_rule_heads_a_step"] == 0


def test_the_heads_a_step_is_the_largest_ops_not_the_sum(tiny, monkeypatch):
    """Two mixers that each walk two heads a step publish 2, while the
    counts beside it add up (`executor.GAUGES_OF_THE_LARGEST_OP`)."""
    ff = tiny[-1]
    nodes = ff.executor.nodes
    mixer = next(n for n in nodes if n.op.op_type == OperatorType.DELTA_MIXER)
    monkeypatch.setattr(type(mixer.op), "traced_gauges", lambda self: {
        "executor.delta_rule_kernel_ops": 1,
        "executor.delta_rule_heads_a_step": 2})
    monkeypatch.setattr(ff.executor, "nodes", list(nodes) + [mixer])
    gauges = ff.executor.traced_gauges()
    assert gauges["executor.delta_rule_kernel_ops"] == 2
    assert gauges["executor.delta_rule_heads_a_step"] == 2


def test_every_gradient_leaf_matches_the_reference(tiny):
    _, got, want = fm.gradients_of(tiny)
    # the table and the head; the delta layer's 2 norms + 7 leaves; the
    # attention layer's 2 + 6; 2 expert layers' 8; the final norm
    assert fm.assert_leaves_close(got, want, atol=3e-4) == (
        2 + 9 + 8 + 2 * 8 + 1)


def test_checkpoint_round_trip(tiny, tmp_path):
    _, _, s, _, xs, _, _, ff = tiny
    path = str(tmp_path / "ckpt")
    ff.save_checkpoint(path)
    before = {name: np.asarray(ff.get_parameter("b0_delta", name))
              for name in ("a_log", "dt_bias", "conv_w", "w_qkvz")}
    logits = np.asarray(ff.predict([xs[0][:s["batch"]]]))
    for name, value in before.items():
        ff.set_parameter("b0_delta", np.zeros_like(value), name)
    ff.load_checkpoint(path)
    for name, value in before.items():
        assert np.array_equal(ff.get_parameter("b0_delta", name), value)
    np.testing.assert_array_equal(
        np.asarray(ff.predict([xs[0][:s["batch"]]])), logits)


_CONTROLS = {}
CONTROLS = [dict(program_partial_rotary_factor=1.0),
            dict(program_norm_topk_prob=False),
            dict(reference_delta_correction=False),
            dict(reference_decay=False),
            dict(reference_attention_gate=False),
            dict(reference_shared_gate=False)]


@pytest.mark.parametrize("control", CONTROLS,
                         ids=[next(iter(c)) for c in CONTROLS])
def test_a_control_of_either_kind_is_told(tiny, control):
    """`program_*`: the PROGRAM built otherwise against the reference as
    the cell states it; `reference_*`: the REFERENCE altered against the
    program as the cell states it. As stated the two agree to 1e-4 of
    the logits' spread; every control reads fifty times that or more
    (the cell's own limit is for bfloat16 on the chip, where
    `scripts/program_controls.py` runs these and the other two)."""
    if not _CONTROLS:
        # the steps above moved them
        tiny.family.install_weights(tiny.ff, tiny.weights)
        _CONTROLS.update(got=fm.predictions(tiny.ff, tiny),
                         want=fm.reference_predictions(tiny)["preds"])
        assert hs.prediction_errors(_CONTROLS["got"], _CONTROLS["want"],
                                    False)["nrmse"] < 1e-4
    got, want = _CONTROLS["got"], _CONTROLS["want"]
    sizes = dict(TINY, **control)
    if next(iter(control)).startswith("program_"):
        got = fm.predictions(fm.control_model(tiny, sizes)[0], tiny)
    else:
        want = fm.reference_predictions(tiny, tiny.family.sizes(
            tiny.config, tiny.traffic, sizes))["preds"]
    nrmse = hs.prediction_errors(got, want, False)["nrmse"]
    assert nrmse > 5e-3, nrmse


def test_the_step_names_the_new_scopes(tiny):
    from flexflow_tpu.obs import step_scopes
    text = fm.compiled_step_text(tiny)
    for scope in ("jvp(jit(delta_mixer))/jit(delta_rule)",
                  "transpose(jvp(jit(delta_mixer)))",
                  "jit(attention_full))/jit(attention_gate)",
                  "jit(attention_full))/jit(rotary_partial_yarn)",
                  "jvp(jit(head))", "jit(moe_layer)"):
        assert scope in text, scope
    rows = step_scopes.table_of(text).values()
    assert {r["part"] for r in rows
            if "jit(delta_rule)" in r["op_name"]} == {"delta_mixer"}
    assert {"forward", "backward"} <= {
        r["direction"] for r in rows if "jit(delta_rule)" in r["op_name"]}
    # no reader's bare substring lies in the new names
    for taken in ("moe_layer", "ssm_mixer", "ssd_scan", "flash_",
                  "attention_", "moe_combine", "mamba_mixer",
                  "selective_scan", "gated_conv"):
        assert taken not in "delta_mixer delta_rule"


def test_thirty_two_style_shares_add_up_to_the_uncut_layer():
    """The share ties to the model: 4 chips hold 2 of 8 experts each;
    their routed parts, plus what every chip computes alike (the delta
    mixer, the router, the shared expert with its gate) counted ONCE, are
    the reference's uncut expert layer."""
    rs = np.random.RandomState(7)
    x = jnp.asarray(rs.randn(2, 24, 32), jnp.float32)
    kw = dict(n_experts=8, k=3, hidden_size=24, shared_width=24, gated=True,
              activation="silu", scoring="softmax", shared_gate=True,
              slot_slack=15.0)
    delta = make_op(OperatorType.DELTA_MIXER, DELTA, [x.shape])
    full = make_op(OperatorType.MOE_LAYER, kw, [x.shape])
    w = {"b0_norm": {"scale": jnp.asarray(rs.rand(32) - 0.5, jnp.float32)},
         "b0_post_norm": {"scale": jnp.asarray(rs.rand(32) - 0.5,
                                               jnp.float32)},
         "b0_delta": delta.init_params(jax.random.PRNGKey(8)),
         "b0_mixer": full.init_params(jax.random.PRNGKey(9))}
    assert w["b0_mixer"]["w_shared_gate"].shape == (32, 1)
    ref_kw = dict(eps=1e-6, layer_types=("linear_attention",),
                  linear_num_key_heads=2, num_experts_per_tok=3,
                  norm_topk_prob=True, expert_offset=0)
    with fm.highest():
        want, h = jax.jit(lambda x, w: (
            ref.layer(x, w, 0, ref_kw, "f32"),
            ref.rms_norm(x, w["b0_norm"]["scale"], 1e-6)))(x, w)
    mixed = np.asarray(x) + run_op(delta, w["b0_delta"], [h])
    with fm.highest():
        g = jax.jit(ref.rms_norm, static_argnums=2)(
            mixed, w["b0_post_norm"]["scale"], 1e-6)
    p = w["b0_mixer"]
    routed_only = dict(kw, shared_width=0, shared_gate=False)
    shared_leaves = ("ws_gate", "ws_up", "ws_down", "w_shared_gate")
    routed_leaves = {n: v for n, v in p.items() if n not in shared_leaves}
    parts = fm.expert_shares(routed_only, routed_leaves, [g], 2, 4)
    # the shared expert with its gate, once: a chip's layer less its
    # routed part
    chip0 = dict(p, **{n: p[n][:2] for n in fm.EXPERT_LEAVES})
    with_shared = run_op(make_op(OperatorType.MOE_LAYER, dict(
        kw, experts_held=2, expert_offset=0), [x.shape]), chip0, [g])
    total = mixed + sum(parts) + (with_shared - parts[0])
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    # and a chip's layer is the reference's own share
    with fm.highest():
        np.testing.assert_allclose(
            with_shared, jax.jit(lambda g, p: ref.experts(
                g, p, k=3, norm_topk=True, offset=0, operand="f32"))(
                    g, chip0), rtol=2e-4, atol=2e-5)


def test_search_prices_the_new_op_and_refuses_its_remat_twin(tiny):
    from flexflow_tpu.search import native
    from flexflow_tpu.search.unity import serialize_graph
    if not native.available():
        pytest.skip("native search unavailable")
    ff = tiny[-1]
    nodes = serialize_graph(ff.executor.nodes)
    by_name = {n["name"]: n for n in nodes}
    delta, attn = by_name["b0_delta"], by_name["b1_attn"]
    assert delta["type"] == "DELTA_MIXER"
    assert delta["roles"] == [["sample", "other", "channel"]]
    assert set(delta["params"]) == {"w_qkvz", "w_ba", "conv_w", "a_log",
                                    "dt_bias", "norm_scale", "w_out"}
    assert delta["flops"] > 0 and delta["attrs"]["interior_bytes"] > 0
    assert delta["attrs"]["side_counters"] == 1
    assert attn["attrs"]["head_dim"] == 16
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
    choices = {c["choice"] for c in ops["b0_delta"]["candidates"]}
    assert {"rep", "dp"} <= {c.split("_")[0] for c in choices}
    # its counters leave the step beside its output: no `_r` twin
    assert not any(c.endswith("_r") for c in choices), choices
    assert all(c["terms"]["fwd_s"] > 0 and c["memory"]["param_bytes"] > 0
               for c in ops["b0_delta"]["candidates"])


def test_the_refusals(tiny):
    from flexflow_tpu.models import DecoderConfig, create_decoder
    from flexflow_tpu.serve.kv_cache import init_kv_cache
    with pytest.raises(NotImplementedError, match="delta-rule mixer"):
        init_kv_cache(tiny[-1], max_len=TINY["seq"])
    gated_only = create_decoder(DecoderConfig(
        hybrid_override_pattern="F", num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, attn_output_gate=True))
    from flexflow_tpu import AdamOptimizer, LossType
    gated_only.compile(AdamOptimizer(alpha=1e-3),
                       LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
    with pytest.raises(NotImplementedError, match="gates its output a lane"):
        init_kv_cache(gated_only, max_len=16)
    with pytest.raises(ValueError, match="multiple of num_key_heads"):
        make_op(OperatorType.DELTA_MIXER, dict(DELTA, num_value_heads=3),
                [(1, 8, 32)])
