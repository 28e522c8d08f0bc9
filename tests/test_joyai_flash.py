"""Latent attention, the gated shared expert and the multi-token-prediction
module (PR 39; `benchmarks/references/joyai_flash.py` is the plain float32
reference, which shares no code with `flexflow_tpu`): the flash kernels'
two-part score against the einsum core (interpret mode, at the published
128 + 64 / 128 head: the lane rule only shows there), the op against the
reference's layer, the model against the reference for both halves of the
logits, the loss and two Adam steps, the share test that ties a chip's
eight experts to the uncut layer, and what stays as it was."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_model as fm
from benchmarks import harness as hs
from benchmarks.references import common
from benchmarks.references import joyai_flash as ref
from family_model import ROOT, OpContext, make_op, run_op
from flexflow_tpu import losses
from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops.attention import rotary_interleaved
from one_program import output_and_gradients

CELL = "joyai_llm_flash.s4096_b1.1chip"
# tiny widths that keep the query/key head (16 + 8) wider than the value
# head (16)
TINY = dict(num_hidden_layers=2, vocab_size=64, hidden_size=32,
            num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=48, n_routed_experts=4,
            n_routed_experts_published=16, num_experts_per_tok=3,
            moe_intermediate_size=24, slot_slack=3.0, initializer_range=0.2,
            seq=32, batch=2, steps_per_epoch=1)


# ---------------------------------------------------------------------------
# the kernels


def assembled(q, k, v, qr, kr, heads):
    """The einsum core on a head's query and key assembled the obvious
    way: [not rotated ; rotated], the one rotated key for every head."""
    from flexflow_tpu.ops.attention import scaled_dot_product_attention
    b, s, _ = q.shape
    qh, kh, vh, qrh = (pk.split_heads(t, heads) for t in (q, k, v, qr))
    kk = jnp.concatenate([kh, jnp.broadcast_to(
        kr[:, None], (b, heads, s, kr.shape[-1]))], -1)
    with fm.highest():
        return pk.merge_heads(scaled_dot_product_attention(
            jnp.concatenate([qh, qrh], -1), kk, vh, causal=True))


@pytest.mark.parametrize("seq,heads", [(256, 2), (1024, 4), (2048, 2)])
def test_two_part_flash_matches_the_einsum_core(seq, heads, monkeypatch):
    """Forward, and every gradient: the rotated key's sums over the heads
    into the ONE vector a position (autodiff of the assembled form does
    the same through its broadcast)."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert pk.flash_attention_available(seq, 128, heads, 64)
    ks = jax.random.split(jax.random.PRNGKey(seq), 6)
    q, k, v, g = (jax.random.normal(ks[i], (1, seq, heads * 128))
                  for i in (0, 1, 2, 5))
    qr = jax.random.normal(ks[3], (1, seq, heads * 64))
    kr = jax.random.normal(ks[4], (1, seq, 64))

    def flash(q, k, v, qr, kr):
        return pk.flash_attention(q, k, v, heads, causal=True, rope=(qr, kr))

    o, got = output_and_gradients(flash, g, q, k, v, qr, kr)
    o_want, want = output_and_gradients(
        lambda *a: assembled(*a, heads), g, q, k, v, qr, kr)
    np.testing.assert_allclose(o, o_want, rtol=1e-4, atol=2e-5)
    for name, a, b in zip(("q", "k", "v", "q_rope", "k_rope"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4,
                                   err_msg=name)


@pytest.mark.parametrize("head_dim,rope_dim,heads,legal", [
    (128, 64, 32, True), (128, 64, 2, True), (128, 128, 3, True),
    # an odd head out among the rotated parts' blocks of two; a head that
    # is not one block of 128 lanes; rotated lanes that do not divide 128
    (128, 64, 3, False), (64, 64, 4, False), (128, 48, 4, False)])
def test_native_gate_and_flash_shape_legal_agree(head_dim, rope_dim, heads,
                                                 legal):
    from flexflow_tpu.search import native
    from flexflow_tpu.search.unity import _node_attrs, _param_shapes
    if not native.available():
        pytest.skip("native search unavailable")
    assert pk.flash_shape_legal(4096, head_dim, heads, rope_dim) == legal
    # a 192-wide head as ONE width is still refused
    assert not pk.flash_shape_legal(4096, 192, 32)
    e, seq = 64, 4096
    op = make_op(OperatorType.MULTIHEAD_ATTENTION, dict(
        embed_dim=e, num_heads=heads, head_dim=head_dim, bias=False,
        causal=True, rope=True, q_lora_rank=48, kv_lora_rank=32,
        qk_rope_head_dim=rope_dim), [(1, seq, e)] * 3)
    attrs = _node_attrs(op)
    assert attrs["rope_head_dim"] == rope_dim
    node = dict(guid=1, type="MULTIHEAD_ATTENTION", name="attn",
                inputs=[[-1, 0]] * 3, input_shapes=[[1, seq, e]] * 3,
                output_shapes=[[1, seq, e]],
                roles=[["sample", "seq", "channel"]],
                params=_param_shapes(op), flops=float(op.flops()),
                dtype_size=2, attrs=attrs)
    machine = {"num_devices": 1, "flops": 197e12, "hbm_bw": 0.82e12,
               "hbm_cap": 16e9, "ici_bw": 45e9, "ici_latency": 1e-6,
               "dcn_bw": 25e9, "dcn_latency": 1e-5, "num_slices": 1,
               "comm_bytes_factor": 0.5}
    resp = native.native_optimize(dict(
        nodes=[node], machine=machine, measured={},
        config=dict(budget=2, training=True, enable_substitution=False,
                    batch=1, emit_search_trace=True)))
    (traced,) = resp["search_trace"]["ops"]
    rej = {r["impl"]: r["reason"]
           for r in traced.get("kernel_rejections") or []}
    twins = any("_k:flash" in c["choice"] for c in traced["candidates"])
    assert twins == legal
    if not legal:
        assert rej["flash"] in ("latent_heads_do_not_tile_128_lanes",
                                "heads_do_not_tile_128_lanes")


# ---------------------------------------------------------------------------
# the ops


LATENT = dict(embed_dim=32, num_heads=2, head_dim=16, bias=False,
              causal=True, rope=True, rope_theta=3.2e7, q_lora_rank=24,
              kv_lora_rank=16, qk_rope_head_dim=8, latent_norm_eps=1e-6)


def test_interleaved_rotary_turns_adjacent_pairs():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 5, 3 * 8), jnp.float32)  # 3 heads side by side
    got = rotary_interleaved(x, theta=100.0, head_dim=8)
    want = jnp.moveaxis(ref.rotary(
        jnp.moveaxis(x.reshape(2, 5, 3, 8), 2, 1), 100.0), 1, 2)
    np.testing.assert_allclose(got, want.reshape(x.shape), rtol=1e-5,
                               atol=1e-6)


def test_latent_attention_matches_the_reference_and_counts_itself():
    op = make_op(OperatorType.MULTIHEAD_ATTENTION, LATENT, [(2, 24, 32)] * 3)
    params = op.init_params(jax.random.PRNGKey(3))
    assert {k: v.shape for k, v in params.items()} == {
        "wq_a": (32, 24), "q_a_norm": (24,), "wq_b_nope": (2, 24, 16),
        "wq_b_rope": (2, 24, 8), "wkv_a": (32, 24), "kv_a_norm": (16,),
        "wkv_b_k": (2, 16, 16), "wkv_b_v": (2, 16, 16), "wo": (2, 16, 32)}
    assert op.params_elems() == sum(int(np.prod(p.shape))
                                    for p in params.values())
    # both latents' and the output's products, and scores over 24 + 16
    # lanes a head for the whole square (as a plain causal op is priced)
    matrices = op.params_elems() - 24 - 16
    assert op.flops() == 2 * 2 * 24 * matrices + 2 * 2 * 2 * 24 * 24 * 40
    rs = np.random.RandomState(3)
    params["q_a_norm"] = jnp.asarray(rs.rand(24) + 0.5, jnp.float32)
    params["kv_a_norm"] = jnp.asarray(rs.rand(16) + 0.5, jnp.float32)
    x = jnp.asarray(rs.randn(2, 24, 32), jnp.float32)
    with fm.highest():
        want = jax.jit(lambda x, p: ref.latent_attention(
            x, p, theta=3.2e7, eps=1e-6, operand="f32"))(x, params)
    np.testing.assert_allclose(run_op(op, params, [x] * 3), want, rtol=1e-4,
                               atol=1e-5)
    # the control's program turns every lane of a head and is another model
    whole = make_op(OperatorType.MULTIHEAD_ATTENTION,
                    dict(LATENT, rope_whole_head=True), [(2, 24, 32)] * 3)
    assert not np.allclose(run_op(whole, params, [x] * 3), want, atol=1e-3)
    assert op.selected_impl({}, training=True) == "einsum"   # CPU, no Pallas


@pytest.mark.parametrize("bad", [
    dict(causal=False), dict(num_kv_heads=1), dict(window=8),
    dict(qk_norm=True), dict(bias=True), dict(seq_parallel="seq")])
def test_latent_attention_refuses_what_it_is_not(bad):
    with pytest.raises(ValueError, match="latent attention"):
        make_op(OperatorType.MULTIHEAD_ATTENTION, dict(LATENT, **bad),
                [(2, 24, 32)] * 3)


def test_decode_and_the_cache_refuse_latent_attention(tiny):
    op = make_op(OperatorType.MULTIHEAD_ATTENTION, LATENT, [(2, 24, 32)] * 3)
    with pytest.raises(NotImplementedError, match="latent"):
        op.decode_forward({}, [jnp.zeros((2, 1, 32))] * 3,
                          OpContext(compute_dtype=jnp.float32), None, None, 0)
    from flexflow_tpu.serve.kv_cache import init_kv_cache
    with pytest.raises(NotImplementedError, match="latent attention"):
        init_kv_cache(tiny[-1], max_len=TINY["seq"])


GATED = dict(n_experts=16, k=3, hidden_size=24, shared_width=24, gated=True,
             activation="silu", routed_scaling=2.5, slot_slack=15.0)


def test_the_shared_expert_takes_the_experts_own_form():
    rs = np.random.RandomState(5)
    g = jnp.asarray(rs.randn(2, 24, 32), jnp.float32)
    op = make_op(OperatorType.MOE_LAYER, GATED, [g.shape])
    params = op.init_params(jax.random.PRNGKey(1))
    assert set(params) == {"w_router", "e_bias", "w_gate", "w_up", "w_down",
                           "ws_gate", "ws_up", "ws_down"}
    assert op.params_elems() == sum(int(np.prod(p.shape))
                                    for p in params.values())
    params["e_bias"] = jnp.asarray(rs.randn(16) * 0.1, jnp.float32)
    with fm.highest():
        want = jax.jit(lambda g, p: ref.experts(
            g, p, k=3, scaling=2.5, offset=0, operand="f32"))(g, params)
    np.testing.assert_allclose(run_op(op, params, [g]), want, rtol=1e-4,
                               atol=1e-5)
    # the ungated layer's shared expert is the squared ReLU it was, with
    # the leaves and the counts it had
    plain = make_op(OperatorType.MOE_LAYER, dict(
        n_experts=16, k=3, hidden_size=24, shared_width=40), [g.shape])
    pp = plain.init_params(jax.random.PRNGKey(1))
    assert set(pp) == {"w_router", "e_bias", "w_up", "w_down", "ws_up",
                       "ws_down"}
    assert plain.flops() == int(2 * 48 * 32 * 16 + 4 * 48 * 3 * 32 * 24
                                + 4 * 48 * 32 * 40)
    zero = dict(pp, w_down=jnp.zeros_like(pp["w_down"]))
    with fm.highest():
        by_hand = jnp.square(jax.nn.relu(g @ pp["ws_up"])) @ pp["ws_down"]
    np.testing.assert_allclose(run_op(plain, zero, [g]), by_hand, rtol=1e-4,
                               atol=1e-5)


def test_thirty_two_shares_add_up_to_the_uncut_layer():
    """The share ties to the model: 32 chips hold 2 of 64 experts each;
    their routed parts, plus what every chip computes alike (latent
    attention, the shared expert) counted ONCE, are the reference's uncut
    expert layer."""
    rs = np.random.RandomState(7)
    x = jnp.asarray(rs.randn(2, 24, 32), jnp.float32)
    kw = dict(GATED, n_experts=64, k=8, slot_slack=63.0)
    attn = make_op(OperatorType.MULTIHEAD_ATTENTION, LATENT, [x.shape] * 3)
    full = make_op(OperatorType.MOE_LAYER, kw, [x.shape])
    w = {"b1_norm": {"scale": jnp.asarray(rs.rand(32) + 0.5, jnp.float32)},
         "b1_post_norm": {"scale": jnp.asarray(rs.rand(32) + 0.5,
                                               jnp.float32)},
         "b1_attn": attn.init_params(jax.random.PRNGKey(8)),
         "b1_mixer": full.init_params(jax.random.PRNGKey(9))}
    w["b1_mixer"]["e_bias"] = jnp.asarray(rs.randn(64) * 0.1, jnp.float32)
    ref_kw = dict(eps=1e-6, rope_theta=3.2e7, num_experts_per_tok=8,
                  routed_scaling_factor=2.5, expert_offset=0)
    with fm.highest():
        want, h = jax.jit(lambda x, w: (
            ref.layer(x, w, "b1", ref_kw, "f32"),
            ref.rms_norm(x, w["b1_norm"]["scale"], 1e-6)))(x, w)
    attended = np.asarray(x) + run_op(attn, w["b1_attn"], [h] * 3)
    p = w["b1_mixer"]
    with fm.highest():
        g, shared = jax.jit(lambda a, scale, p: (
            ref.rms_norm(a, scale, 1e-6),
            ref.swiglu(ref.rms_norm(a, scale, 1e-6), p["ws_gate"],
                       p["ws_up"], p["ws_down"], "f32")))(
                attended, w["b1_post_norm"]["scale"], p)
    shared = np.asarray(shared)
    parts = fm.expert_shares(kw, p, [g], 2, 32)
    total = attended + shared + sum(part - shared for part in parts)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_part_sums_of_the_weighted_loss():
    rs = np.random.RandomState(6)
    logits = jnp.asarray(rs.randn(2, 8, 11), jnp.float32)
    labels = np.zeros((2, 8, 2), np.float32)
    labels[..., 0] = rs.randint(0, 11, size=(2, 8))
    labels[:, :3, 1] = 8 / 3
    labels[:, 4:6, 1] = 0.3 * 8 / 2
    labels_j = jnp.asarray(labels)
    parts = losses.part_nll_sums(
        losses.target_log_probs(logits, losses.class_ids(logits, labels_j)),
        labels_j, ("main", "mtp"))
    logp = np.asarray(jax.nn.log_softmax(logits, -1))
    nll = -np.take_along_axis(
        logp, labels[..., 0].astype(int)[..., None], -1)[..., 0]
    assert float(parts["main"]) == pytest.approx(nll[:, :3].sum(), rel=1e-5)
    assert float(parts["mtp"]) == pytest.approx(nll[:, 4:6].sum(), rel=1e-5)
    # the weighted mean over all rows is mean(main) + 0.3 mean(mtp)
    loss = losses.weighted_sparse_categorical_crossentropy(
        logits, jnp.asarray(labels))
    assert float(loss) == pytest.approx(
        nll[:, :3].mean() + 0.3 * nll[:, 4:6].mean(), rel=1e-5)


# ---------------------------------------------------------------------------
# the model


@pytest.fixture(scope="module")
def tiny():
    return fm.build_tiny(CELL, TINY)


def test_model_against_the_reference_both_halves_loss_and_two_adam_steps(
        tiny):
    family, config, s, traffic, xs, y, weights, ff = tiny
    assert ff.loss_parts == ("main", "mtp")
    names = [n.op.name for n in ff.executor.nodes]
    assert {"b0_gate_up_proj", "b1_mixer", "mtp_eh_proj", "mtp_attn",
            "mtp_mixer", "mtp_final_ln", "main_and_mtp"} <= set(names)
    system, _ = hs.system_side(ff, xs, y, s["batch"])
    want = hs.reference_side(family, weights, s, traffic, config, xs, y,
                             s["batch"])
    seq = s["seq"]
    assert system["preds"].shape == (s["batch"], 2 * seq, s["vocab_size"])
    for half in (slice(0, seq), slice(seq, 2 * seq)):   # main, then mtp
        np.testing.assert_allclose(system["preds"][:, half],
                                   want["preds"][:, half], rtol=2e-4,
                                   atol=2e-5)
    np.testing.assert_allclose(system["losses"], want["losses"], rtol=2e-5)
    assert want["losses"][2] < want["losses"][0] - 1e-3   # the steps moved it
    # the two unweighted terms of the last step, and the targets' count
    counters = ff.op_counters
    assert counters["loss/target_positions"] == s["batch"] * (2 * seq - 3)
    assert counters["executor.latent_attention_ops"] == 3
    loss = (counters["loss/main_nll"] / (s["batch"] * (seq - 1))
            + s["mtp_loss_weight"] * counters["loss/mtp_nll"]
            / (s["batch"] * (seq - 2)))
    assert loss == pytest.approx(system["losses"][2], rel=1e-5)


@pytest.mark.parametrize("control,failing", [
    (dict(program_rope_whole_head=True), "pred_nrmse"),
    (dict(program_mtp_shift=0), "pred_nrmse"),
    (dict(program_mtp_loss_weight=0.0), "loss0_rel")])
def test_a_program_built_otherwise_is_not_correct(tiny, control, failing):
    """The mechanisms' controls: rotary over the whole head, the module
    reading the unshifted embedding, lambda 0; the reference as stated."""
    ff, s = fm.control_model(tiny, dict(TINY, **control))
    want = fm.reference_predictions(tiny, s)
    if failing == "pred_nrmse":     # judged by its logits: no step taken
        nrmse = hs.prediction_errors(fm.predictions(ff, tiny),
                                     want["preds"], False)["nrmse"]
        assert nrmse > tiny.family.TOLERANCES["pred_nrmse"]
        return
    xs, y = tiny.family.make_data(s, 11)    # the labels carry the weight
    system, _ = hs.system_side(ff, xs, y, s["batch"])
    rows = {r["name"]: r for r in hs.compare(system, want,
                                             tiny.family.TOLERANCES)}
    assert rows[failing]["ok"] is False


def test_the_step_names_the_new_scopes(tiny):
    from flexflow_tpu.obs import step_scopes
    text = fm.compiled_step_text(tiny)
    for scope in ("jvp(jit(attention_latent))",
                  "transpose(jvp(jit(attention_latent)))",
                  "jvp(jit(mtp))/jit(attention_latent)",
                  "jvp(jit(mtp))/jit(moe_layer)", "jvp(jit(mtp))/jit(op_"):
        assert scope in text, scope
    parts = {(r["part"], r["direction"])
             for r in step_scopes.table_of(text).values()}
    assert {("mtp", "forward"), ("mtp", "backward"),
            ("attention", "forward"), ("experts", "forward")} <= parts
    assert step_scopes.part_of(
        "jit(train_step)/jvp(jit(mtp))/jit(moe_layer)/dot_general") == "mtp"


def test_reference_counts_tie_to_the_configuration():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "joyai_llm_flash.json")) as f:
        config = json.load(f)
    from benchmarks.families import joyai_flash as family
    s = family.sizes(config, dict(seq=4096, batch=1, steps_per_epoch=4))
    assert family.parameters(s) == 491_697_408
    assert family.train_flops_per_sample(s) / 1e12 == pytest.approx(10.68,
                                                                    abs=0.01)
    flops, nbytes = family.latent_flash_step_flops_and_bytes(s)
    assert flops == 6 * 32 * 8_390_656 * 1920
    assert nbytes / 819e9 < flops / 197e12       # the FLOPs are the floor
    labels = family.labels_of(np.arange(8, dtype=np.int32)[None], 0.3)
    assert (labels[0, :, 0] == [1, 2, 3, 4, 5, 6, 7, 0,
                                2, 3, 4, 5, 6, 7, 0, 0]).all()
    assert labels[0, :, 1].sum() == pytest.approx(16 * 1.3)
    assert common.loss_of(ref, np.zeros((1, 16, 5), np.float32),
                          labels % 5) == pytest.approx(1.3 * np.log(5))
