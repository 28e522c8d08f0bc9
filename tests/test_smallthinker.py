"""What the SmallThinker configuration forced (PR 31), at a small size on
the CPU with Pallas in interpret mode: the sliding window in the four
flash kernels and the einsum core, the K blocks the blocked kernels visit,
softmax-of-chosen routing, gated experts and the router's second input,
the decoder's `G` / `W` layers against the plain reference, the share test
that ties a chip's eight experts to the uncut layer, and the search's
price of a windowed attention op."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_model as fm
from benchmarks import harness as hs
from benchmarks.references import smallthinker as ref
from family_model import make_op, run_op
from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops import moe
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops.attention import scaled_dot_product_attention
from one_program import output_and_gradients

family = hs.load_by_path("families", "smallthinker")

# ---------------------------------------------------------------------------
# the window in the kernels

HEADS, KV, D = 7, 1, 128      # the cell's 7 Q : 1 KV heads of 128


def qkv(seq, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (1, seq, HEADS * D), jnp.float32)
    k, v = (jax.random.normal(key, (1, seq, KV * D), jnp.float32)
            for key in keys[1:])
    return q, k, v


def repeat_kv(x):
    b, s, _ = x.shape
    return jnp.repeat(x.reshape(b, s, KV, D), HEADS // KV, axis=2).reshape(
        b, s, HEADS * D)


def flash(q, k, v, window):
    return pk._flash(q, repeat_kv(k), repeat_kv(v), HEADS, True, True,
                     window)


def einsum_core(q, k, v, window):
    split = lambda x: pk.split_heads(x, HEADS)  # noqa: E731
    return pk.merge_heads(scaled_dot_product_attention(
        split(q), split(repeat_kv(k)), split(repeat_kv(v)), causal=True,
        window=window))


@pytest.mark.parametrize("window", [128, 512, 1 << 20])
@pytest.mark.parametrize("seq", [512, 2048])
def test_window_flash_matches_the_einsum_core(seq, window):
    """Whole-tile kernels (S 512) and blocked ones (S 2048), forward and
    the gradients of q, k, v (the key/value head's through the repeat)."""
    q, k, v = qkv(seq)
    weight = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)
    with fm.highest():
        o, got = output_and_gradients(
            lambda *a: flash(*a, window), weight, q, k, v)
        o_want, want = output_and_gradients(
            lambda *a: einsum_core(*a, window), weight, q, k, v)
    np.testing.assert_allclose(o, o_want, rtol=2e-4, atol=2e-5)
    for g, w in zip(got, want):
        scale = float(np.max(np.abs(w)))
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=2e-5)


@pytest.mark.parametrize("seq", [512, 2048])
def test_a_window_that_covers_the_sequence_is_causal_bit_for_bit(seq):
    q, k, v = qkv(seq, seed=1)

    def output_and_grads(window):
        """(q, k, v) -> the output and the gradients of sum(output^2)."""
        def loss(*a):
            o = flash(*a, window)
            return jnp.sum(o ** 2), o
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)

    # a window the sequence fits in is normalised away before a kernel is
    # built: ONE program, character for character, forward and backward
    causal = str(jax.make_jaxpr(output_and_grads(0))(q, k, v))
    for window in (seq, seq + 1, 4096):
        assert str(jax.make_jaxpr(output_and_grads(window))(q, k, v)) == causal
    (_, o), g = jax.jit(output_and_grads(0))(q, k, v)
    assert all(np.isfinite(np.asarray(a)).all() for a in (o, *g))
    # one key less is another function
    assert not np.array_equal(
        jax.jit(lambda *a: flash(*a, seq - 1))(q, k, v), o)


def tiles_with_a_visible_pair(seq, blk_q, blk_k, causal, window):
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = np.ones((seq, seq), bool)
    if causal:
        seen = np.asarray(pk.visible(i, j, window))
    return int(seen.reshape(seq // blk_q, blk_q, seq // blk_k, blk_k)
               .any(axis=(1, 3)).sum())


@pytest.mark.parametrize("seq,causal,window", [
    (2048, True, 0), (2048, True, 128), (2048, True, 512), (2048, False, 0),
    (4096, True, 1024), (1152, True, 256)])
def test_blocked_kernels_visit_exactly_the_blocks_with_a_visible_pair(
        seq, causal, window, monkeypatch):
    """The forward's K chunks and the backward's Q chunks, from the loop
    bounds the kernels use, against a count on the mask itself."""
    one = pk.one_span(seq, causal, window)
    assert (one is not None) == (0 < window <= 512)
    if one is not None:
        # PR 46: ONE tile a block where a block's reach fits it, and no
        # visible pair outside it (tests/test_laguna.py); the chunk
        # loop's count below is the two-part score's at these windows
        blk, span = one[0]
        assert pk.kv_blocks(seq, causal, window) == (
            seq // blk, (seq // blk) * -(-seq // span))
        monkeypatch.setattr(pk, "one_span", lambda *a, **k: None)
    # the chunk follows a window narrower than 1024 (PR 41)
    blk = pk._seq_block(seq, None, pk.normalized_window(seq, causal, window))
    blk_q = pk._q_block(seq)
    visited, total = pk.kv_blocks(seq, causal, window)
    assert total == (seq // blk_q) * (seq // blk)
    assert visited == tiles_with_a_visible_pair(seq, blk_q, blk, causal,
                                                window)
    back = sum(int(hi - lo) for lo, hi in (
        pk._q_chunks(k0, blk, blk, seq, causal, window)
        for k0 in range(0, seq, blk)))
    assert back == tiles_with_a_visible_pair(seq, blk, blk, causal, window)


def test_the_cells_layers_visit_three_tenths_of_the_square():
    """16,384 tokens, one full causal layer and three of window 4096: 310M
    visible pairs of 1,074M, a little more at block granularity."""
    full, total = pk.kv_blocks(16384, True, 0)
    window, _ = pk.kv_blocks(16384, True, 4096)
    pairs = family.visible_pairs(16384) + 3 * family.visible_pairs(16384,
                                                                   4096)
    assert pairs == 134_225_920 + 3 * 58_722_304
    share = (full + 3 * window) / (4 * total)
    assert pairs / (4 * 16384 ** 2) < share < 0.36
    assert pk.kv_blocks(512, True, 128) == (1, 1)   # whole tile: masks
    with pytest.raises(ValueError, match="causal"):
        pk.kv_blocks(2048, False, 128)


# ---------------------------------------------------------------------------
# routing, the gated form, the second input


def test_softmax_of_the_chosen_is_softmax_then_renormalise():
    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(50, 16) * 2, jnp.float32)
    weights, idx = moe.route_scores(logits, None, 6, True, 1.0, "softmax")
    dense = jax.nn.softmax(logits, axis=-1)
    _, want_idx = jax.lax.top_k(dense, 6)
    chosen = jnp.take_along_axis(dense, want_idx, axis=-1)
    assert np.array_equal(idx, want_idx)
    np.testing.assert_allclose(
        weights, chosen / jnp.sum(chosen, -1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(weights, -1), 1.0, rtol=1e-6)
    # without the renormalisation: the chosen entries of the softmax
    plain, _ = moe.route_scores(logits, None, 6, False, 1.0, "softmax")
    np.testing.assert_allclose(plain, chosen, rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        moe.route_scores(logits, None, 6, True, 1.0, "tanh")


@pytest.fixture(scope="module")
def hidden():
    rs = np.random.RandomState(5)
    return (jnp.asarray(rs.randn(2, 24, 32), jnp.float32),
            jnp.asarray(rs.randn(2, 24, 32), jnp.float32))


GATED = dict(n_experts=16, k=3, hidden_size=24, scoring="softmax",
             gated=True, slot_slack=15.0)


def test_gated_experts_and_the_routers_own_input_match_a_loop(hidden):
    """Token by token: the router reads the second input, the experts
    transform the first; three leaves an expert and no bias leaf."""
    g, h = hidden
    op = make_op(OperatorType.MOE_LAYER, GATED, [g.shape, h.shape])
    params = op.init_params(jax.random.PRNGKey(1))
    assert set(params) == {"w_router", "w_gate", "w_up", "w_down"}
    assert op.params_elems() == sum(int(np.prod(p.shape))
                                    for p in params.values())
    got = run_op(op, params, [g, h])
    p64 = {k: np.asarray(v, np.float64) for k, v in params.items()}
    want = np.zeros(g.shape, np.float64)
    for b in range(g.shape[0]):
        for t in range(g.shape[1]):
            x, r = np.asarray(g[b, t], np.float64), np.asarray(h[b, t],
                                                               np.float64)
            logits = r @ p64["w_router"]
            top = np.argsort(-logits)[:3]
            w = np.exp(logits[top] - logits[top].max())
            for j, wj in zip(top, w / w.sum()):
                want[b, t] += wj * ((np.maximum(x @ p64["w_gate"][j], 0)
                                     * (x @ p64["w_up"][j]))
                                    @ p64["w_down"][j])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # routed from its own input, the layer gives something else
    assert not np.allclose(run_op(op, params, [g, g]), got, atol=1e-3)
    # the sigmoid form is as it was: a bias leaf, two matrices
    plain = make_op(OperatorType.MOE_LAYER, dict(n_experts=16, k=3,
                                                 hidden_size=24), [g.shape])
    assert set(plain.init_params(jax.random.PRNGKey(1))) == {
        "w_router", "e_bias", "w_up", "w_down"}
    assert op.flops() - plain.flops() == 2 * 48 * 3 * 32 * 24


def test_eight_shares_of_eight_experts_add_up_to_the_uncut_layer(hidden):
    """8 chips with 8 of the 64 experts each (no shared expert to count
    once), against the reference's uncut layer (all 64 held)."""
    g, h = hidden
    kw = dict(GATED, n_experts=64, k=6, slot_slack=63.0)
    full = make_op(OperatorType.MOE_LAYER, kw, [g.shape, h.shape])
    params = full.init_params(jax.random.PRNGKey(2))
    with fm.highest():
        want = np.asarray(jax.jit(lambda g, h, p: ref.experts(
            g, h, p, k=6, offset=0, operand="f32"))(g, h, params))
    np.testing.assert_allclose(run_op(full, params, [g, h]), want,
                               rtol=1e-4, atol=1e-5)
    # the reference's share is the program's
    parts = fm.expert_shares(
        kw, params, [g, h], 8, 8, rtol=1e-4, atol=1e-5,
        reference=lambda share, offset: ref.experts(
            g, h, share, k=6, offset=offset, operand="f32"))
    np.testing.assert_allclose(sum(parts), want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the decoder's layers through compile / fit against the reference

TINY = dict(
    num_hidden_layers=4, vocab_size=64, hidden_size=32, rms_norm_eps=1e-6,
    num_attention_heads=2, num_key_value_heads=1, head_dim=16,
    rope_theta=1500000, rope_layout=[0, 1, 1, 1] * 2,
    sliding_window_layout=[0, 1, 1, 1] * 2, sliding_window_size=32,
    moe_num_primary_experts=4, moe_num_primary_experts_published=16,
    expert_offset=4, moe_num_active_primary_experts=3,
    moe_ffn_hidden_size=24, norm_topk_prob=True, slot_slack=3.0,
    initializer_range=0.2, embedding_std=1.0, seq=128, batch=2,
    steps_per_epoch=1)
CONFIG = dict(search_budget=2, adam=fm.ADAM)


@pytest.fixture(scope="module")
def model():
    # interpret mode: the attention ops run the flash kernels (whole tile
    # at this length), so the window is the kernels' and not the core's
    with fm.pallas("interpret"):
        ff, weights, (ids,), labels = fm.build_model(family, CONFIG, TINY, 3)
        with fm.highest():
            logits = np.asarray(ff.predict([ids]))
            losses = []
            for _ in range(3):
                ff.fit([ids], labels, epochs=1, verbose=False)
                losses.append(float(ff._last_loss))
    return ff, weights, ids, labels, logits, losses


def test_pattern_and_graph(model):
    ff = model[0]
    assert family.decoder_pattern(TINY) == "GWWW"
    assert family.pattern_of(TINY) == "GEWEWEWE"
    ops = {n.op.name: n.op for n in ff.executor.nodes}
    assert [ops[f"b{i}_attn"].window for i in range(4)] == [0, 32, 32, 32]
    assert [ops[f"b{i}_attn"].rope for i in range(4)] == [False, True, True,
                                                         True]
    mixer = ops["b1_mixer"]
    assert mixer.op_type == OperatorType.MOE_LAYER and mixer.gated
    assert mixer.scoring == "softmax" and len(mixer.input_shapes) == 2
    # the router's input is the attention's: the pre-attention norm
    node = next(n for n in ff.executor.nodes if n.op.name == "b1_mixer")
    by_guid = {n.op.guid: n.op.name for n in ff.executor.nodes}
    assert [by_guid[r[1]] for r in node.input_refs] == ["b1_post_norm",
                                                         "b1_norm"]
    with pytest.raises(ValueError, match="window and no rotary"):
        family.decoder_pattern(dict(TINY, rope_layout=[1, 1, 1, 1]))
    assert ff.search_seconds is not None and ff.strategy


def test_logits_and_three_losses_match_the_reference(model):
    from benchmarks.references import common
    ff, weights, ids, labels, logits, losses = model
    kw = family.reference_kw(TINY)
    with fm.highest():
        want = np.asarray(jax.jit(lambda w, ids: ref.forward(
            w, ids, **kw))(weights, ids))
    assert logits.shape == (2, 128, 64)
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-5)
    want_losses = common.train_losses(ref, weights, ids, labels, 1, 3,
                                      CONFIG["adam"], **kw)
    np.testing.assert_allclose(losses, want_losses, rtol=2e-5)
    assert losses[2] < losses[0]
    counters = ff.op_counters
    assert counters["moe/overflow_slots"] == 0
    assert counters["moe/slots_held"] > 0
    assert counters["executor.window_attention_ops"] == 3
    assert counters["executor.flash_lane_dense_ops"] == 4
    assert counters["attention/kv_blocks_visited"] == 4   # whole tiles


def test_a_windowed_layer_differs_from_a_full_one(model):
    _, weights, ids, _, logits, _ = model
    kw = dict(family.reference_kw(TINY), sliding_window_layout=(0, 0, 0, 0))
    with fm.highest():
        full = np.asarray(jax.jit(lambda w, ids: ref.forward(
            w, ids, **kw))(weights, ids))
    assert not np.allclose(full, logits, atol=1e-3)
    # up to the window's length no key is hidden
    np.testing.assert_allclose(full[:, :32], logits[:, :32], rtol=2e-4,
                               atol=2e-5)


def test_decode_and_ring_refuse_a_window(model):
    ff = model[0]
    op = next(n.op for n in ff.executor.nodes if n.op.name == "b1_attn")
    with pytest.raises(NotImplementedError, match="sliding window"):
        op.decode_forward({}, [jnp.zeros((2, 1, 32))], fm.OpContext(), None,
                          None, 0)
    with pytest.raises(ValueError, match="causal"):
        make_op(OperatorType.MULTIHEAD_ATTENTION,
                dict(embed_dim=32, num_heads=2, window=8), [(2, 16, 32)] * 3)


def test_scopes_reach_the_compiled_steps_op_names(model, monkeypatch):
    """The device trace's readers find the new scopes by these names,
    forward and backward."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    with fm.highest():   # as the fixture's steps ran: the step it compiled
        scopes = family.scopes_of_compiled_step(
            model[0], family.observed_sizes(model[0]))
    names = " ".join(scopes.values())
    for scope in ("jit(attention_window)", "jit(attention_full)",
                  "jit(flash_window)", "jit(flash_full)",
                  "jit(moe_layer)", "jit(moe_route)",
                  "jit(moe_grouped_matmul)"):
        assert scope in names, scope
        assert any(scope in n and "transpose(" in n
                   for n in scopes.values()), scope


def test_the_compiled_step_moves_expert_rows_by_gathers_only(
        model, monkeypatch):
    """No `scatter` under `jit(moe_layer)` in the compiled train step but
    the megablox kernels' own tile tables (a few int32 of group and tile
    ids under `jit(gmm)` / `jit(tgmm)`, here in interpret mode as on the
    chip), and the `moe_combine` scope in its `op_name`s both ways."""
    from flexflow_tpu.obs.inspect import scatters_in
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    ff = model[0]
    (ids,), labels = family.make_data(TINY, 0)
    with fm.highest():   # as the fixture's steps ran: the step it compiled
        text = ff.executor.make_train_step().lower(
            ff.params, ff.opt_state, ff.state, ff._stage_inputs([ids]),
            ff._shard_batch(labels),
            jax.random.PRNGKey(0)).compile().as_text()
    under = scatters_in(text, "jit(moe_layer)")
    assert under and all(("/jit(gmm)/" in name or "/jit(tgmm)/" in name)
                         and size < 16 for name, size in under), under
    for scope in ("/jvp(jit(moe_layer))/jit(moe_combine)/",
                  "/transpose(jvp(jit(moe_layer)))/jit(moe_combine)/"):
        assert scope in text, scope
    assert sum(n.op.op_type == OperatorType.MOE_LAYER
               for n in ff.executor.nodes) == 4
    assert model[0].op_counters["executor.moe_sum_rows_ops"] == 0


# ---------------------------------------------------------------------------
# the search


def attention_node(seq, window=0, heads=7, head_dim=128, batch=1):
    e = 2560
    props = dict(embed_dim=e, num_heads=heads, num_kv_heads=1,
                 head_dim=head_dim, bias=False, causal=True)
    if window:
        props["window"] = window
    op = make_op(OperatorType.MULTIHEAD_ATTENTION, props,
                 [(batch, seq, e)] * 3)
    from flexflow_tpu.search.unity import _node_attrs, _param_shapes
    return op, dict(
        guid=1, type="MULTIHEAD_ATTENTION", name="attn",
        inputs=[[-1, 0]] * 3, input_shapes=[[batch, seq, e]] * 3,
        output_shapes=[[batch, seq, e]],
        roles=[["sample", "seq", "channel"]], params=_param_shapes(op),
        flops=float(op.flops()), dtype_size=2, attrs=_node_attrs(op))


def test_search_prices_a_window_below_the_square_and_admits_flash():
    from flexflow_tpu.search import native
    if not native.available():
        pytest.skip("native search unavailable")
    seq = 16384
    machine = {"num_devices": 1, "flops": 197e12, "hbm_bw": 0.82e12,
               "hbm_cap": 16e9, "ici_bw": 45e9, "ici_latency": 1e-6,
               "dcn_bw": 25e9, "dcn_latency": 1e-5, "num_slices": 1,
               "comm_bytes_factor": 0.5}
    prices = {}
    for window in (0, 4096, seq):
        op, node = attention_node(seq, window)
        assert ("window" in node["attrs"]) == (0 < window < seq)
        resp = native.native_optimize(dict(
            nodes=[node], machine=machine, measured={},
            config=dict(budget=2, training=True, enable_substitution=False,
                        batch=1, emit_search_trace=True)))
        (traced,) = resp["search_trace"]["ops"]
        assert not traced.get("kernel_rejections"), traced
        cands = {c["choice"]: c["terms"]["total_s"]
                 for c in traced["candidates"]}
        assert "rep_k:flash" in cands
        prices[window] = (op.flops(), cands["rep"], cands["rep_k:flash"])
    core = lambda w: 4 * 7 * 128 * seq * w    # noqa: E731
    assert prices[0][0] - prices[4096][0] == core(seq) - core(4096)
    # S x W scores, not S^2: cheaper by either lowering
    assert prices[4096][1] < prices[0][1]
    assert prices[4096][2] < prices[0][2]
    # a window that hides nothing is the plain causal op to the search
    assert prices[seq] == prices[0]
    assert pk.flash_shape_legal(seq, 128, 7)
