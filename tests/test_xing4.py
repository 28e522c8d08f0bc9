"""Manifold-constrained hyper-connections, YaRN on the latent route and
the cell's tiny cut (PR 64; `benchmarks/references/xing4.py` is the plain
float32 reference, which shares no code with `flexflow_tpu`): the three
kernels against their `jax.numpy` forms (interpret mode), the two ops
against the reference's sublayer with every leaf's gradient, the mixing
matrix doubly stochastic and clamped, the plain residual where
``hc_mult`` is absent and reproduced by equal copies under identity
maps, the accepted cells' graphs node for node the parent's, the model
against the reference (logits, three losses, every gradient leaf), the
controls, and the shares that tie a chip's heads and experts to the
uncut layer."""

import hashlib
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_model as fm
from benchmarks import harness as hs
from benchmarks import manifest as mf
from benchmarks.references import xing4 as ref
from family_model import ROOT, make_op, run_op
from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops import hyper_connection as hc
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops.attention import (latent_yarn_factors,
                                        rotary_frequencies)
from one_program import output_and_gradients

CELL = "xing4_0_29b_a4b.s4096_b1.1chip"
YARN = dict(type="yarn", factor=64, original_max_position_embeddings=4096,
            beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
# tiny widths that keep the query/key head (16 + 8) wider than the value
# head (16); YaRN over an original length shorter than the sequence
TINY = dict(num_hidden_layers=2, vocab_size=64, hidden_size=32,
            num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=48, n_routed_experts=4,
            n_routed_experts_published=16, num_experts_per_tok=3,
            moe_intermediate_size=24, slot_slack=3.0, initializer_range=0.2,
            rope_scaling=dict(YARN, original_max_position_embeddings=8),
            seq=32, batch=2, steps_per_epoch=1)
CLAMP = (-30.0, 30.0)


# ---------------------------------------------------------------------------
# the kernels


def hc_operands(dtype, n=4, c=256, rows=256, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    k = hc.map_count(n)
    x = jax.random.normal(ks[0], (rows, n * c)).astype(dtype)
    y = jax.random.normal(ks[1], (rows, c)).astype(dtype)
    phi = 0.05 * jax.random.normal(ks[2], (n * c, k))
    a = jnp.concatenate([jnp.full((n,), 0.3), jnp.full((n,), 0.2),
                         jnp.full((n * n,), 0.1)])
    return x, y, phi, a, jax.random.normal(ks[3], (k,))


def sublayer_of(read, maps, write, n=4):
    """A whole sublayer out of the three passes, with a branch that reads
    h; the weight of the sum lies on the new stream."""
    def fn(x, y, phi, a, b):
        k = phi.shape[1]
        h, zr, x_out = read(x, phi, a, b, n, 1e-6)
        logits = a * zr[..., 127:] * zr[..., :k] + b
        out = write(x_out, (y.astype(jnp.float32)
                            + jnp.tanh(h.astype(jnp.float32))
                            ).astype(x.dtype), maps(logits), n)
        return out, h, zr
    return fn


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_the_three_kernels_match_their_plain_forms(dtype, tol, monkeypatch):
    """Forward (h, the products and the statistic, the new stream) and
    the gradient of every operand, the stream's through both of its uses;
    in bfloat16 the products still agree to float32's rounding (phi's
    three terms)."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert pk.hc_shape_legal(256, 4, 256)
    operands = hc_operands(dtype)
    k = hc.map_count(4)
    weight = jax.random.normal(jax.random.PRNGKey(9), (256, 4 * 256))
    plain = sublayer_of(hc.read_plain, lambda t: jnp.pad(
        hc.hc_maps(t, 4, 20, 1e-6, CLAMP), ((0, 0), (0, 128 - k))),
        hc.write_plain)
    kernels = sublayer_of(pk.hc_read_lanes, lambda t: pk.hc_maps_lanes(
        jnp.pad(t, ((0, 0), (0, 128 - k))), 4, 20, 1e-6, CLAMP),
        pk.hc_write_lanes)
    with fm.highest():
        (out, h, zr), got = output_and_gradients(kernels, weight, *operands)
        (out_w, h_w, zr_w), want = output_and_gradients(plain, weight,
                                                        *operands)
    np.testing.assert_allclose(zr, zr_w, rtol=1e-5, atol=1e-5)
    for name, a, b in (("h", h, h_w), ("out", out, out_w)) + tuple(zip(
            ("dx", "dy", "dphi", "da", "db"), got, want)):
        a, b = (np.asarray(t, np.float32) for t in (a, b))
        np.testing.assert_allclose(a / np.abs(b).max(), b / np.abs(b).max(),
                                   atol=tol, err_msg=name)


@pytest.mark.parametrize("rows,n,c,legal", [
    (4096, 4, 3584, True), (128, 2, 128, True), (128, 1, 128, True),
    # rows that are no whole block; lanes that are no whole column; maps
    # wider than a group of 32 lanes; a block over the lane budget
    (100, 4, 128, False), (128, 4, 96, False), (128, 5, 128, False),
    (128, 4, 16384, False)])
def test_hc_shape_legal(rows, n, c, legal, monkeypatch):
    assert pk.hc_shape_legal(rows, n, c) == legal
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert hc.by_kernel(None, (1, rows, n * c), n) == legal
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    assert not hc.by_kernel(None, (1, rows, n * c), n)


def test_phi_operand_holds_three_terms():
    phi = jax.random.normal(jax.random.PRNGKey(1), (256, 24))
    op = pk.hc_phi_operand(phi, jnp.bfloat16).astype(jnp.float32)
    assert op.shape == (256, 128)
    np.testing.assert_allclose(op[:, :24] + op[:, 32:56] + op[:, 64:88],
                               phi, rtol=0, atol=2e-7)
    assert not np.any(op[:, 24:32]) and not np.any(op[:, 88:])
    turned = pk.hc_phi_operand(phi, jnp.bfloat16, transposed=True)
    assert turned.shape == (128, 256)
    np.testing.assert_array_equal(turned[:24], turned[32:56])    # hi, hi
    np.testing.assert_array_equal(
        pk.hc_phi_operand(phi, jnp.float32)[:, :24], phi)


# ---------------------------------------------------------------------------
# the maps


def test_h_res_is_doubly_stochastic_and_the_clamp_is_active():
    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(50, 24), jnp.float32)
    with fm.highest():
        maps = np.asarray(jax.jit(lambda t: hc.hc_maps(
            t, 4, 20, 1e-6, CLAMP))(logits))
    res = maps[:, 8:].reshape(50, 4, 4)
    assert np.abs(res.sum(-1) - 1).max() < 1e-5       # rows: the last step
    assert np.abs(res.sum(-2) - 1).max() < 1e-5
    assert (res > 0).all()
    np.testing.assert_allclose(maps[:, :4], jax.nn.sigmoid(logits[:, :4]),
                               rtol=1e-6)
    np.testing.assert_allclose(maps[:, 4:8],
                               2 * jax.nn.sigmoid(logits[:, 4:8]), rtol=1e-6)
    # the reference's own steps, written out on [.., n, n]
    want = ref.sinkhorn(jnp.exp(logits[:, 8:].reshape(50, 4, 4)), 20, 1e-6)
    np.testing.assert_allclose(res, want, rtol=1e-5, atol=1e-7)
    # past the clamp a logit is the clamp: +-100 reads as +-30
    far = jnp.asarray(np.sign(rs.randn(5, 24)) * 100.0, jnp.float32)
    np.testing.assert_array_equal(
        hc.hc_maps(far, 4, 20, 1e-6, CLAMP)[:, 8:],
        hc.hc_maps(far * 0.3, 4, 20, 1e-6, CLAMP)[:, 8:])
    assert np.isfinite(np.asarray(hc.hc_maps(far, 4, 20, 1e-6, CLAMP))).all()
    # fewer steps are another matrix (the controls' lever)
    assert np.abs(np.asarray(hc.hc_maps(logits, 4, 1, 1e-6, CLAMP))[:, 8:]
                  .reshape(50, 4, 4).sum(-2) - 1).max() > 1e-2


# ---------------------------------------------------------------------------
# the ops


def hc_ops(b=2, s=6, n=4, c=32, **props):
    pre = make_op(OperatorType.HC_PRE, dict(streams=n, **props),
                  [(b, s, n * c)])
    post = make_op(OperatorType.HC_POST, dict(streams=n),
                   [(b, s, n * c), (b, s, c), (b, s, hc.MAP_LANES)])
    return pre, post


def test_a_sublayer_matches_the_reference_with_every_leafs_gradient():
    b, s, n, c = 2, 6, 4, 32
    pre, post = hc_ops(b, s, n, c)
    params = pre.init_params(jax.random.PRNGKey(3))
    assert {k: v.shape for k, v in params.items()} == {
        "phi_pre": (128, 4), "phi_post": (128, 4), "phi_res": (128, 16),
        "b_pre": (4,), "b_post": (4,), "b_res": (4, 4), "alpha": (3,)}
    assert pre.params_elems() == sum(int(np.prod(p.shape))
                                     for p in params.values()) == 3099
    assert pre.output_shapes == [(b, s, c), (b, s, 128), (b, s, n * c)]
    assert (pre.exports, pre.aliased_outputs, post.exports) == (2, 1, 0)
    params = dict(params, alpha=jnp.asarray([0.5, 0.4, 0.3]))   # maps that move
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(b, s, n * c), jnp.float32)
    w = jnp.asarray(rs.randn(c, c) * 0.3, jnp.float32)
    weight = jnp.asarray(rs.randn(b, s, n * c), jnp.float32)
    ctx = fm.OpContext(training=True, compute_dtype=jnp.float32)
    kw = dict(hc_eps=1e-6, hc_sinkhorn_iters=20, hc_clamp_min=-30.0,
              hc_clamp_max=30.0)

    def program(x, w, p):
        h, maps, stream = pre.forward(p, [x], ctx)
        pre._counters = None
        return post.forward({}, [stream, jnp.tanh(h) @ w, maps], ctx)[0]

    def reference(x, w, p):
        streams = x.reshape(b, s, n, c)
        h, maps = ref.hc_read(streams, p, kw)
        return ref.hc_write(streams, jnp.tanh(h) @ w, maps).reshape(x.shape)

    with fm.highest():
        out, got = output_and_gradients(program, weight, x, w, params)
        out_w, want = output_and_gradients(reference, weight, x, w, params)
    np.testing.assert_allclose(out, out_w, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)
    assert fm.assert_leaves_close(got[2], want[2]) == 7


def test_the_ops_refuse_what_they_are_not():
    with pytest.raises(ValueError, match="hc_pre"):
        make_op(OperatorType.HC_PRE, dict(streams=3), [(2, 6, 128)])
    with pytest.raises(ValueError, match="hc_post"):
        make_op(OperatorType.HC_POST, dict(streams=4),
                [(2, 6, 128), (2, 6, 64), (2, 6, 128)])


def test_the_search_sees_the_handed_through_stream_as_no_bytes():
    """The exported edges are pinned, no remat twin stands for the read
    half (its counters leave on the side channel), and the third output
    occupies and moves nothing."""
    from flexflow_tpu.search.unity import _node_attrs
    pre, post = hc_ops(1, 128, 4, 128)
    attrs = _node_attrs(pre)
    assert attrs["interior_bytes"] == 128 * 128 * 4
    assert pre.flops() > 2 * 128 * 512 * 24 and post.flops() == 2 * 128 * 512 * 5
    from flexflow_tpu.models import DecoderConfig, create_decoder
    from flexflow_tpu.search.unity import serialize_graph
    ff = create_decoder(DecoderConfig(hybrid_override_pattern="A", hc_mult=2,
                                      batch_size=2, seq_length=8))
    nodes = {n["name"]: n for n in serialize_graph(
        ff._materialize_nodes()[0])}
    read, write = nodes["b0_hc_attn"], nodes["b0_res1"]
    assert read["attrs"]["aliased_outputs"] == 1
    assert read["attrs"]["exports"] == 2 and read["attrs"]["pinned"] == 1
    assert read["attrs"]["side_counters"] == 1
    assert write["attrs"]["pinned"] == 1 and write["type"] == "HC_POST"
    assert [i[1] for i in write["inputs"]] == [2, 0, 1]


# ---------------------------------------------------------------------------
# YaRN on the latent route


def test_yarn_table_and_factors_on_the_latent_route():
    inv_freq, _ = rotary_frequencies(64, 10000.0, YARN)
    plain = 1.0 / (10000.0 ** (np.arange(0, 64, 2) / 64))
    # low 10, high 23: the fast lanes keep their frequency, the slow
    # ones are divided by the factor, a ramp between
    np.testing.assert_allclose(inv_freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[23:], plain[23:] / 64, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(inv_freq[11:23], plain[11:23] * (
        ramp / 64 + 1 - ramp), rtol=1e-5)
    want, of_tables, of_scores = ref.yarn(64, 10000.0, tuple(YARN.items()))
    np.testing.assert_allclose(inv_freq, want, rtol=1e-6)
    assert latent_yarn_factors(YARN) == pytest.approx((of_tables, of_scores))
    assert of_tables == 1.0
    assert of_scores * 192 ** -0.5 == pytest.approx(0.14468, abs=1e-5)
    # mscale_all_dim 0 (a control): the factor moves to cos and sin
    assert latent_yarn_factors(dict(YARN, mscale_all_dim=0)) == pytest.approx(
        (of_scores ** 0.5, 1.0))


LATENT = dict(embed_dim=32, num_heads=2, head_dim=16, bias=False,
              causal=True, rope=True, rope_theta=1e4, q_lora_rank=24,
              kv_lora_rank=16, qk_rope_head_dim=8, latent_norm_eps=1e-6,
              rope_scaling=dict(YARN, original_max_position_embeddings=8))


def test_latent_attention_under_yarn_matches_the_reference():
    op = make_op(OperatorType.MULTIHEAD_ATTENTION, LATENT, [(2, 24, 32)] * 3)
    params = op.init_params(jax.random.PRNGKey(3))
    x = jnp.asarray(np.random.RandomState(3).randn(2, 24, 32), jnp.float32)
    kw = dict(eps=1e-6, rope_theta=1e4,
              rope_scaling=tuple(LATENT["rope_scaling"].items()))
    with fm.highest():
        want = jax.jit(lambda x, p: ref.latent_attention(
            x, p, kw=kw, operand="f32"))(x, params)
        plain = jax.jit(lambda x, p: ref.latent_attention(
            x, p, kw=dict(kw, rope_scaling=None), operand="f32"))(x, params)
    np.testing.assert_allclose(run_op(op, params, [x] * 3), want, rtol=1e-4,
                               atol=1e-5)
    assert not np.allclose(plain, want, atol=1e-3)     # it is another model
    with pytest.raises(ValueError, match="rope_whole_head"):
        make_op(OperatorType.MULTIHEAD_ATTENTION,
                dict(LATENT, rope_whole_head=True), [(2, 24, 32)] * 3)


# ---------------------------------------------------------------------------
# the builder


def fingerprint(ff):
    rows = [[layer.op_type.name, layer.name,
             [[t.owner_layer.name if t.owner_layer else None,
               getattr(t, "owner_idx", 0), list(t.shape)]
              for t in layer.inputs],
             sorted((k, repr(v)) for k, v in layer.properties.items()),
             [list(t.shape) for t in layer.outputs]] for layer in ff.layers]
    return len(rows), hashlib.sha256(
        json.dumps(rows, sort_keys=True).encode()).hexdigest()[:16]


# (layers, digest of every layer's kind, name, inputs, properties and
# output shapes) of the cell's graph as the PARENT commit 99952bc builds
# it through the family's `build` at the cell's own sizes
# (`git archive 99952bc`, this function; PR 64)
PARENT_GRAPHS = {
    "joyai_llm_flash.s4096_b1.1chip": (53, "92432aa06d2c8671"),
    "laguna_xs2.s8192_b1.1chip": (39, "f280f7a56d3c2dae"),
    "lfm2_8b_a1b.s16384_b1.1chip": (39, "6f9758db3e394b15"),
    "ouro_2_6b.s4096_b1.1chip": (322, "78883aa5a8de8fcb"),
    "qwen3_next_80b_a3b.s16384_b1.1chip": (28, "cf536e1e05e62f6b"),
}


@pytest.mark.parametrize("cell", sorted(PARENT_GRAPHS))
def test_an_accepted_cells_graph_is_node_for_node_the_parents(
        cell, monkeypatch):
    """`_attention_ffn_block` is the function this PR edits and these
    five cells' blocks go through it: without ``hc_mult`` the residual
    rule is `add`, and the graph is what it was."""
    from flexflow_tpu.model import FFModel

    def no_compile(self, *a, **k):
        self.executor = types.SimpleNamespace()
    monkeypatch.setattr(FFModel, "compile", no_compile)
    _, config, traffic = mf.find_cell(mf.load_manifest(ROOT), cell, ROOT)
    family = hs.load_by_path("families", config["family"], ROOT)
    ff = family.build(config, family.sizes(config, traffic), 1, 5)
    assert fingerprint(ff) == PARENT_GRAPHS[cell]
    assert not any(layer.op_type in (OperatorType.HC_PRE,
                                     OperatorType.HC_POST)
                   for layer in ff.layers)


def test_the_builder_refuses_streams_where_no_block_carries_them():
    from flexflow_tpu.models import DecoderConfig, create_decoder
    with pytest.raises(ValueError, match="hc_mult"):
        create_decoder(DecoderConfig(hybrid_override_pattern="ME*",
                                     hc_mult=4))
    with pytest.raises(NotImplementedError, match="hyper-connections"):
        create_decoder(DecoderConfig(hybrid_override_pattern="U", hc_mult=2,
                                     total_ut_steps=2))
    ff = create_decoder(DecoderConfig(hybrid_override_pattern="AXX",
                                      hc_mult=4,
                                      batch_size=2, seq_length=8))
    kinds = [layer.op_type.name for layer in ff.layers]
    assert kinds.count("HC_PRE") == kinds.count("HC_POST") == 6
    names = [layer.name for layer in ff.layers]
    assert names[2:5] == ["hc_streams", "b0_hc_attn", "b0_norm"]
    assert {"b0_gate_up_proj", "b1_mixer", "b2_mixer", "hc_merge",
            "hc_merge_sum3"} <= set(names)


def test_the_witness_is_the_largest_over_an_epochs_steps():
    """A counter of kind `max` is the largest over the ops of a step AND
    over the steps of an epoch (a mean over the steps would hide one bad
    step among good ones): three batches in one epoch against the same
    three an epoch each, the weights held still."""
    from flexflow_tpu import FFConfig, LossType, SGDOptimizer
    from flexflow_tpu.models import DecoderConfig, create_decoder
    ff = create_decoder(DecoderConfig(
        hybrid_override_pattern="A", hc_mult=4, vocab_size=32,
        hidden_size=32, num_attention_heads=2, batch_size=1, seq_length=8),
        FFConfig(batch_size=1, seed=5))
    ff.compile(SGDOptimizer(lr=0.0),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 32, (3, 8)).astype(np.int32)
    labels = rs.randint(0, 32, (3, 8, 1)).astype(np.int32)
    key = "hc/res_col_sum_err_max"
    ff.fit([ids], labels, epochs=1, verbose=False)
    assert ff.executor.max_counters == {
        "hc/res_row_sum_err_max", "hc/res_col_sum_err_max"}
    epoch = ff.op_counters[key]
    steps = []
    for i in range(3):
        ff.fit([ids[i:i + 1]], labels[i:i + 1], epochs=1, verbose=False)
        steps.append(ff.op_counters[key])
    assert len(set(steps)) > 1 and epoch == max(steps) > np.mean(steps)


# ---------------------------------------------------------------------------
# the model


@pytest.fixture(scope="module")
def tiny():
    return fm.build_tiny(CELL, TINY)


def test_model_against_the_reference_both_halves_and_three_losses(tiny):
    family, config, s, traffic, xs, y, weights, ff = tiny
    assert ff.loss_parts == ("main", "mtp")
    names = [n.op.name for n in ff.executor.nodes]
    assert {"hc_streams", "b0_hc_attn", "b0_res1", "b0_hc_ffn", "b0_res2",
            "b1_mixer", "hc_merge", "mtp_hc_streams", "mtp_hc_attn",
            "mtp_res2", "mtp_hc_merge", "main_and_mtp"} <= set(names)
    system, _ = hs.system_side(ff, xs, y, s["batch"])
    want = hs.reference_side(family, weights, s, traffic, config, xs, y,
                             s["batch"])
    seq = s["seq"]
    for half in (slice(0, seq), slice(seq, 2 * seq)):   # main, then mtp
        np.testing.assert_allclose(system["preds"][:, half],
                                   want["preds"][:, half], rtol=2e-4,
                                   atol=2e-5)
    np.testing.assert_allclose(system["losses"], want["losses"], rtol=2e-5)
    assert want["losses"][2] < want["losses"][0] - 1e-3   # the steps moved it
    counters = ff.op_counters
    assert counters["loss/target_positions"] == s["batch"] * (2 * seq - 3)
    assert (counters["hc/streams"], counters["hc/sublayers"],
            counters["hc/sinkhorn_iters"]) == (4, 6, 20)
    assert counters["hc/kernel_fallbacks"] == 12      # the CPU: every op
    assert 0 <= counters["hc/res_row_sum_err_max"] < 1e-5
    assert 0 <= counters["hc/res_col_sum_err_max"] < 1e-3
    assert family.kernel_fallbacks(ff) == {}
    checks = {name: ok for name, ok, _ in family.extra_checks(ff, s, 1,
                                                              False)}
    assert checks == dict(parameters_as_counted=True,
                          attention_all_latent=True,
                          rope_scaling_as_stated=True,
                          routers_as_stated=True, streams_as_stated=True)


def test_every_gradient_leaf_matches_the_reference(tiny):
    """Every leaf of the model, the hyper-connections' 42 among them
    (seven a sublayer, six sublayers); the routers' bias moves no
    gradient."""
    from benchmarks.references import common
    module, kw, chunk = tiny.family.reference(tiny.s, tiny.traffic)
    params = fm.as_arrays(tiny.weights)
    want = common.loss_and_grads(module, params, tiny.xs[0], tiny.y, chunk,
                                 **kw)[1]
    with fm.highest():
        got = jax.jit(jax.grad(fm.program_loss_of(
            tiny.ff, tiny.xs, tiny.y)))(params)
    # the first sublayer of a stream reads n EQUAL copies: its h is
    # (sum_i H_pre[i]) e under a norm that does not see the factor, and
    # H_res's rows sum to one, so those maps' leaves move nothing
    # (rounding, 1e-10 where the others read 1e-3) on either side
    still, leaves = [], 0
    for name in want:
        for leaf, w in want[name].items():
            g, scale = np.asarray(got[name][leaf]), float(
                jnp.max(jnp.abs(w)))
            if leaf == "e_bias":
                assert not np.any(g)
            elif scale < 1e-8:
                assert np.abs(g).max() < 1e-8, (name, leaf)
                still.append((name, leaf))
            else:
                np.testing.assert_allclose(g / scale, np.asarray(w) / scale,
                                           atol=5e-4, err_msg=name + leaf)
                leaves += 1
    assert {(name, leaf) for name in ("b0_hc_attn", "mtp_hc_attn")
            for leaf in ("b_pre", "b_res", "phi_pre", "phi_res")} <= set(still)
    # elsewhere only the mixing map's leaves can fall under the noise: at
    # the seeded weights the streams are still near one another, and a
    # doubly stochastic matrix mixes near-equal streams into themselves
    assert {leaf for _, leaf in still} <= {"b_pre", "phi_pre", "b_res",
                                           "phi_res"}
    hc_leaves = [k for k in got if k.endswith(("hc_attn", "hc_ffn"))]
    assert len(hc_leaves) == 6 and leaves >= 42 - len(still) + 30


@pytest.mark.parametrize("control,check", [
    (dict(program_hc_sinkhorn_iters=0), "streams_as_stated"),
    pytest.param(dict(program_hc_sinkhorn_iters=1), "streams_as_stated",
                 marks=pytest.mark.slow),
    (dict(program_hc_mult=0), "streams_as_stated"),
    (dict(program_rope_scaling=None), "rope_scaling_as_stated"),
    pytest.param(dict(program_mscale_all_dim=0), "rope_scaling_as_stated",
                 marks=pytest.mark.slow),
    (dict(program_num_experts_per_tok=2), "routers_as_stated"),
    pytest.param(dict(program_routed_scaling_factor=1), "routers_as_stated",
                 marks=pytest.mark.slow)])
def test_a_program_built_otherwise_is_not_correct(tiny, control, check):
    """The mechanisms' controls, each a published key's other value: the
    logits leave the limit, and the row of `extra_checks` that reads the
    key off the built ops fails too."""
    ff, s = fm.control_model(tiny, dict(TINY, **control))
    rows = {name: ok for name, ok, _ in tiny.family.extra_checks(
        ff, s, 1, False)}
    assert rows[check] is False
    want = fm.reference_predictions(tiny, s)
    nrmse = hs.prediction_errors(fm.predictions(ff, tiny), want["preds"],
                                 False)["nrmse"]
    assert nrmse > tiny.family.TOLERANCES["pred_nrmse"]


def test_equal_copies_under_identity_maps_are_the_plain_residual(tiny):
    """With H_res = I, H_pre = 1 / n and H_post = 1 (alpha 0 and the
    biases that give them) the n streams stay n equal copies of the one
    stream a plain block carries, their sum n times it, and the norms
    that read the sum do not see the factor: the logits are the plain
    program's (the control `program_hc_mult=0`) on the same weights."""
    n = tiny.s["hc_mult"]
    weights = {name: dict(leaves) for name, leaves in tiny.weights.items()}
    for name, leaves in weights.items():
        if name.endswith(("hc_attn", "hc_ffn")):
            leaves.update(
                alpha=np.zeros(3, np.float32),
                b_pre=np.full(n, -np.log(n - 1.0), np.float32),
                b_post=np.zeros(n, np.float32),
                b_res=(60.0 * np.eye(n) - 30.0).astype(np.float32))
    streams, _ = fm.control_model(tiny, TINY, weights)
    plain, _ = fm.control_model(tiny, dict(TINY, program_hc_mult=0), weights)
    assert not any(node.op.op_type == OperatorType.HC_PRE
                   for node in plain.executor.nodes)
    np.testing.assert_allclose(fm.predictions(streams, tiny),
                               fm.predictions(plain, tiny), rtol=1e-4,
                               atol=1e-5)


def test_the_step_names_the_new_scopes(tiny):
    from flexflow_tpu.obs import step_scopes
    text = fm.compiled_step_text(tiny)
    for scope in ("jvp(jit(hyper_connection))/jit(hc_read)",
                  "jvp(jit(hyper_connection))/jit(hc_maps)",
                  "jvp(jit(hyper_connection))/jit(hc_write)",
                  "transpose(jvp(jit(hyper_connection)))/jit(hc_read)",
                  "transpose(jvp(jit(hyper_connection)))/jit(hc_write)",
                  "jvp(jit(mtp))/jit(hyper_connection)"):
        assert scope in text, scope
    assert "transpose(jvp(jit(hyper_connection)))/jit(hc_maps)" in text \
        or "jit(hc_maps)/transpose(jvp(" in text
    parts = {(r["part"], r["direction"])
             for r in step_scopes.table_of(text).values()}
    assert {("hyper_connection", "forward"), ("hyper_connection", "backward"),
            ("mtp", "forward"), ("attention", "forward")} <= parts
    assert step_scopes.part_of(
        "jit(train_step)/jvp(jit(hyper_connection))/jit(hc_read)/mul") \
        == "hyper_connection"
    assert tiny.ff.executor.part_of_node(next(
        n for n in tiny.ff.executor.nodes
        if n.op.name == "b0_hc_attn")) == "hyper_connection"


def test_the_cache_refuses_the_family(tiny):
    from flexflow_tpu.serve.kv_cache import init_kv_cache
    with pytest.raises(NotImplementedError, match="several residual streams"):
        init_kv_cache(tiny.ff, max_len=TINY["seq"])


# ---------------------------------------------------------------------------
# the shares


def test_eight_shares_add_up_to_the_uncut_layer():
    """The share ties to the model: 8 chips hold 2 of 16 heads and 2 of
    16 experts each. The heads' partial attention outputs, with the two
    latents' down-projections and norms on every chip, add up to the
    uncut reference's attention; the chips' routed parts, plus the shared
    expert counted ONCE, to its uncut expert layer."""
    rs = np.random.RandomState(7)
    x = jnp.asarray(rs.randn(2, 24, 32), jnp.float32)
    whole = dict(LATENT, num_heads=16)
    attn = make_op(OperatorType.MULTIHEAD_ATTENTION, whole, [x.shape] * 3)
    p = attn.init_params(jax.random.PRNGKey(8))
    kw = dict(eps=1e-6, rope_theta=1e4,
              rope_scaling=tuple(LATENT["rope_scaling"].items()))
    with fm.highest():
        want = jax.jit(lambda x, p: ref.latent_attention(
            x, p, kw=kw, operand="f32"))(x, p)
    share = make_op(OperatorType.MULTIHEAD_ATTENTION, LATENT, [x.shape] * 3)
    run = fm.op_program(share)
    per_head = ("wq_b_nope", "wq_b_rope", "wkv_b_k", "wkv_b_v", "wo")
    parts = [run(dict(p, **{leaf: p[leaf][2 * chip:2 * chip + 2]
                            for leaf in per_head}), [x] * 3)
             for chip in range(8)]
    np.testing.assert_allclose(sum(parts), want, rtol=2e-4, atol=2e-5)
    props = dict(n_experts=16, k=4, hidden_size=24, shared_width=24,
                 gated=True, activation="silu", routed_scaling=2.0,
                 slot_slack=15.0)
    full = make_op(OperatorType.MOE_LAYER, props, [x.shape])
    pm = full.init_params(jax.random.PRNGKey(9))
    pm["e_bias"] = jnp.asarray(rs.randn(16) * 0.1, jnp.float32)
    with fm.highest():
        want, shared = jax.jit(lambda g, p: (
            ref.experts(g, p, k=4, scaling=2.0, offset=0, operand="f32"),
            ref.swiglu(g, p["ws_gate"], p["ws_up"], p["ws_down"], "f32")))(
                x, pm)
    shared = np.asarray(shared)
    parts = fm.expert_shares(props, pm, [x], 2, 8)
    np.testing.assert_allclose(shared + sum(part - shared for part in parts),
                               want, rtol=2e-4, atol=2e-5)


def test_reference_counts_tie_to_the_configuration():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "xing4_0_29b_a4b.json")) as f:
        config = json.load(f)
    from benchmarks.families import xing4 as family
    s = family.sizes(config, dict(seq=4096, batch=1, steps_per_epoch=4))
    assert family.parameters(s) == 789_610_628
    shapes = family.weight_shapes(s)
    count = lambda name: sum(int(np.prod(shape))     # noqa: E731
                             for _, shape in shapes[name].values())
    assert count("b1_attn") == 7_767_296
    assert count("b1_mixer") == 99_319_872
    assert count("b0_gate_up_proj") + count("b0_down_proj") == 99_090_432
    assert count("b1_hc_attn") == count("mtp_hc_ffn") == 344_091
    assert family.sublayers(s) == 12
    assert family.train_flops_per_sample(s) / 1e12 == pytest.approx(9.67,
                                                                    abs=0.01)
    reader = hs.load_by_path("layer_metrics",
                             "kernels.hyper_connection_roofline")
    assert reader.step_bytes(s) == 2 * 4096 * 12 * 41 * 3584


def test_the_ops_take_the_kernels_where_the_shape_allows(monkeypatch):
    """Whole blocks (128 positions, streams of 128 lanes) in interpret
    mode: both ops run the kernels, give what the `jax.numpy` passes
    give, and the witness of the projection comes out of the maps' spare
    lanes either way."""
    pre, post = hc_ops(1, 128, 4, 128)
    params = dict(pre.init_params(jax.random.PRNGKey(2)),
                  alpha=jnp.asarray([0.5, 0.4, 0.3]))
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(1, 128, 512), jnp.float32)
    y = jnp.asarray(rs.randn(1, 128, 128), jnp.float32)
    ctx = fm.OpContext(training=True, compute_dtype=jnp.float32)

    def run():
        with fm.highest():
            h, maps, stream = pre.forward(params, [x], ctx)
            errs = {name: float(v) for name, (_, v) in pre._counters.items()}
            out = post.forward({}, [stream, y, maps], ctx)[0]
        fallbacks = (pre.traced_gauges()["hc/kernel_fallbacks"]
                     + post.traced_gauges()["hc/kernel_fallbacks"])
        return np.asarray(h), np.asarray(maps), np.asarray(out), errs, \
            fallbacks

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    h_w, maps_w, out_w, errs_w, fallbacks = run()
    assert fallbacks == 2
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    h, maps, out, errs, fallbacks = run()
    assert fallbacks == 0
    np.testing.assert_allclose(h, h_w, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(maps, maps_w, rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(out, out_w, rtol=1e-5, atol=1e-5)
    assert set(errs) == {"hc/res_row_sum_err_max", "hc/res_col_sum_err_max"}
    for name in errs:
        assert 0 <= errs[name] < 1e-3
        assert errs[name] == pytest.approx(errs_w[name], abs=2e-6)
    assert not np.any(maps[..., 32:])
