"""What the SDAR configuration forced (PR 34), at a small size on the CPU
with Pallas in interpret mode: the block-diffusion mask in the four flash
kernels and the einsum core, the tiles the blocked kernels visit (two
ranges a tile), rotary positions that wrap, the per-head query/key norm,
SiLU-gated experts, the weighted loss and its counter, the host-side
noising, the decoder's `D` block against the plain reference (and a causal
program failing against it), the share test that ties a chip's sixteen
experts to the uncut layer, and the search's price of the masked op."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_model as fm
from benchmarks import harness as hs
from benchmarks.references import sdar as ref
from family_model import OpContext, make_op, run_op
from flexflow_tpu import losses
from flexflow_tpu.dataloader import block_diffusion_batch
from flexflow_tpu.ffconst import LossType, OperatorType
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops.attention import (rotary_embedding,
                                        scaled_dot_product_attention)
from one_program import output_and_gradients

family = hs.load_by_path("families", "sdar")

# ---------------------------------------------------------------------------
# the mask in the kernels

HEADS, KV, D = 8, 1, 128      # the cell's 8 Q : 1 KV heads of 128


def mask_by_hand(length, block):
    """visible(i, j) written out from half and blk, element by element."""
    seen = np.zeros((2 * length, 2 * length), bool)
    for i in range(2 * length):
        for j in range(2 * length):
            half_i, half_j = i // length, j // length
            blk_i, blk_j = (i % length) // block, (j % length) // block
            if half_i == 0 and half_j == 0:
                seen[i, j] = blk_j == blk_i
            elif half_i == 0 and half_j == 1:
                seen[i, j] = blk_j < blk_i
            elif half_i == 1 and half_j == 1:
                seen[i, j] = blk_j <= blk_i
    return seen


@pytest.mark.parametrize("length,block", [(64, 4), (96, 32), (60, 3),
                                          (8, 8)])
def test_visible_is_the_mask_written_out_from_half_and_blk(length, block):
    i = np.arange(2 * length)
    want = mask_by_hand(length, block)
    got = np.asarray(pk.visible(i[:, None], i[None, :], 0, (length, block)))
    assert np.array_equal(got, want)
    # the reference builds its own from the same equations
    assert np.array_equal(np.asarray(ref.block_mask(
        jnp.asarray(i)[:, None], jnp.asarray(i)[None, :], length, block)),
        want)
    n = length // block
    assert want.sum() == block * block * (n + n * n)
    # a [k, q] tile of the kernels' `_mask`, somewhere off the origin
    tile = np.asarray(pk._mask(jnp.zeros((16, 8)), length - 8, 8, 0,
                               (length, block)))
    assert np.array_equal(tile == 0, want[8:16, length - 8:length + 8].T)


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


def flash(q, k, v, causal=False, window=0, block_diffusion=None):
    return pk._flash(q, repeat_kv(k), repeat_kv(v), HEADS, causal, True,
                     window, block_diffusion)


def einsum_core(q, k, v, block_diffusion):
    split = lambda x: pk.split_heads(x, HEADS)  # noqa: E731
    return pk.merge_heads(scaled_dot_product_attention(
        split(q), split(repeat_kv(k)), split(repeat_kv(v)),
        block_diffusion=block_diffusion))


@pytest.mark.parametrize("block", [4, 32])
@pytest.mark.parametrize("seq", [512, 2048])
def test_block_mask_flash_matches_the_einsum_core(seq, block):
    """Whole-tile kernels (2L = 512) and blocked ones (2L = 2048), forward
    and the gradients of q, k, v (the key/value head's through the
    repeat)."""
    bd = (seq // 2, block)
    q, k, v = qkv(seq)
    weight = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)
    with fm.highest():
        o, got = output_and_gradients(
            lambda *a: flash(*a, block_diffusion=bd), weight, q, k, v)
        o_want, want = output_and_gradients(
            lambda *a: einsum_core(*a, bd), weight, q, k, v)
    np.testing.assert_allclose(o, o_want, rtol=2e-4, atol=2e-5)
    for g, w in zip(got, want):
        scale = float(np.max(np.abs(w)))
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=2e-5)


def test_the_mask_is_neither_causal_nor_none():
    q, k, v = qkv(512, seed=2)
    run = lambda **mask: jax.jit(lambda q, k, v: flash(  # noqa: E731
        q, k, v, **mask))(q, k, v)
    masked = run(block_diffusion=(256, 4))
    assert not np.allclose(masked, run(), atol=1e-3)
    assert not np.allclose(masked, run(causal=True), atol=1e-3)
    # the clean copy's first block sees itself alone, as under causal
    # attention a first block of one token would
    for bad in ((256, 4, True, 0), (200, 4, False, 0), (256, 5, False, 0)):
        with pytest.raises(ValueError, match="block-diffusion"):
            pk.checked_block_diffusion(512, bad[2], bad[3], bad[:2])


@pytest.mark.parametrize("seq", [512, 2048])
def test_causal_and_window_are_bitwise_what_they_were(seq):
    """The two kinds the kernels had take no notice of the third: the
    calls as every caller before PR 34 spelt them (seven arguments) and
    with the new argument left empty give the same bits, forward and
    backward, and the one-range tile bounds are PR 31's."""
    q, k, v = qkv(seq, seed=1)
    rk, rv = jax.jit(repeat_kv)(k), jax.jit(repeat_kv)(v)

    def output_and_grads(f):
        """f's output and the gradients of sum(output^2), the output
        that of the forward the gradients ran."""
        def loss(*a):
            o = f(*a)
            return jnp.sum(o ** 2), o
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)

    for causal, window in ((True, 0), (True, 128), (False, 0)):
        old = lambda q, k, v: pk._flash(q, k, v, HEADS, causal, True,  # noqa: E731,E501
                                        window)
        new = lambda q, k, v: pk._flash(q, k, v, HEADS, causal, True,  # noqa: E731,E501
                                        window, None)
        # ONE program, character for character, forward and backward:
        # the same bits, and it runs
        assert str(jax.make_jaxpr(output_and_grads(new))(q, rk, rv)) == str(
            jax.make_jaxpr(output_and_grads(old))(q, rk, rv))
        (_, o), g = jax.jit(output_and_grads(old))(q, rk, rv)
        assert all(np.isfinite(np.asarray(a)).all() for a in (o, *g))
    assert pk._k_ranges(1024, 256, 1024, 4096, True, 0) == ((0, 2),)
    assert pk._k_ranges(3072, 256, 512, 4096, True, 1024) == ((4, 7),)
    assert pk._q_ranges(1024, 1024, 1024, 4096, True, 0) == ((1, 4),)
    assert pk._q_ranges(0, 512, 512, 4096, True, 1024) == ((0, 3),)
    assert pk._k_ranges(512, 256, 1024, 4096, False, 0) == ((0, 4),)
    assert pk._k_chunks(1024, 256, 1024, 4096, True, 0) == (0, 2)
    assert pk.kv_blocks(16384, True, 0) == (544, 1024)
    assert pk.kv_blocks(16384, True, 4096) == (280, 1024)
    assert pk._seq_block(16384) == 1024 and pk._q_block(16384) == 256


def tiles_with_a_visible_pair(seq, blk_q, blk_k, bd):
    i = np.arange(seq)
    seen = np.asarray(pk.visible(i[:, None], i[None, :], 0, bd))
    return seen.reshape(seq // blk_q, blk_q, seq // blk_k, blk_k).any(
        axis=(1, 3))


@pytest.mark.parametrize("seq,block", [(2048, 4), (2048, 32), (4096, 4),
                                       (3072, 12), (2304, 128)])
def test_blocked_kernels_visit_exactly_the_tiles_with_a_visible_pair(
        seq, block):
    """The forward's K chunks and the backward's Q chunks, from the two
    ranges a tile that the kernels loop over, against a count on the mask
    itself; no tile lies across the halves."""
    bd = (seq // 2, block)
    blk, blk_q = pk._seq_block(seq, bd), pk._q_block(seq, bd)
    assert (seq // 2) % blk == 0 and (seq // 2) % blk_q == 0
    want = tiles_with_a_visible_pair(seq, blk_q, blk, bd)
    for n, q0 in enumerate(range(0, seq, blk_q)):
        got = np.zeros(seq // blk, bool)
        for lo, hi in pk._k_ranges(q0, blk_q, blk, seq, False, 0, bd):
            assert not got[lo:hi].any()       # no tile is visited twice
            got[lo:hi] = True
        assert np.array_equal(got, want[n]), q0
    visited, total = pk.kv_blocks(seq, False, 0, bd)
    assert (visited, total) == (want.sum(), want.size)
    back = tiles_with_a_visible_pair(seq, blk, blk, bd)
    for n, k0 in enumerate(range(0, seq, blk)):
        got = np.zeros(seq // blk, bool)
        for lo, hi in pk._q_ranges(k0, blk, blk, seq, False, 0, bd):
            assert not got[lo:hi].any()
            got[lo:hi] = True
        assert np.array_equal(got, back[:, n]), k0


def test_the_cells_layers_visit_under_a_third_of_the_square():
    """8,192-token samples, 16,384 positions, blocks of 4: 67,141,632
    visible pairs a head of 268,435,456 (0.250); by hand, with Q blocks of
    256 and K chunks of 1024 (PR 35; 512 until then): 32 noised Q blocks
    see their own noised tile and ceil((256 i + 252) / 1024) clean chunks,
    32 clean ones ceil((256 i + 256) / 1024): 32 + 144 + 144 of 64 x 16
    tiles."""
    s = dict(seq=8192, block_length=4)
    assert family.visible_pairs(s) == 67_141_632 == (
        33_570_816 + 33_538_048 + 32_768)
    visited, total = pk.kv_blocks(16384, False, 0, (8192, 4))
    assert (visited, total) == (32 + 144 + 144, 64 * 16)
    assert 0.25 < visited / total < 1 / 3
    assert pk._seq_block(16384, (8192, 4)) == 1024
    assert pk._seq_block(3072, (1536, 12)) == 512     # divides L
    assert pk.kv_blocks(512, False, 0, (256, 4)) == (1, 1)  # whole tile


# ---------------------------------------------------------------------------
# positions, the query/key norm, the experts' gate, the loss, the data


def test_wrapped_rotary_is_rotary_applied_to_each_half():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 3, 16))
    got = rotary_embedding(x, theta=1e6, seq_axis=1, wrap=12)
    halves = [rotary_embedding(x[:, :12], theta=1e6, seq_axis=1),
              rotary_embedding(x[:, 12:], theta=1e6, seq_axis=1)]
    assert np.array_equal(got, jnp.concatenate(halves, axis=1))
    assert not np.allclose(got, rotary_embedding(x, theta=1e6, seq_axis=1))
    # [B, H, S, D], and the reference's own rotary
    xt = x.transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        rotary_embedding(xt, theta=1e6, wrap=12),
        ref.rotary(xt, jnp.arange(24) % 12, 1e6), rtol=1e-5, atol=1e-6)


def test_qk_norm_matches_a_loop_over_heads():
    e, heads, kv, d, seq = 32, 4, 2, 16, 24
    props = dict(embed_dim=e, num_heads=heads, num_kv_heads=kv, head_dim=d,
                 bias=False, rope=True, rope_theta=1e6, rope_wrap=12,
                 block_diffusion=(12, 4), qk_norm=True, qk_norm_eps=1e-6)
    op = make_op(OperatorType.MULTIHEAD_ATTENTION, props, [(2, seq, e)] * 3)
    params = op.init_params(jax.random.PRNGKey(4))
    assert params["q_norm"].shape == params["k_norm"].shape == (d,)
    assert op.params_elems() == sum(int(np.prod(p.shape))
                                    for p in params.values())
    rs = np.random.RandomState(4)
    params["q_norm"] = jnp.asarray(rs.rand(d) + 0.5, jnp.float32)
    params["k_norm"] = jnp.asarray(rs.rand(d) + 0.5, jnp.float32)
    x = jnp.asarray(rs.randn(2, seq, e), jnp.float32)
    got = run_op(op, params, [x])
    # by hand: per head, norm over its 16 lanes, then rotary at i mod 12
    p64 = {k: np.asarray(v, np.float64) for k, v in params.items()}
    x64 = np.asarray(x, np.float64)
    inv = 1.0 / (1e6 ** (np.arange(0, d, 2) / d))
    ang = (np.arange(seq) % 12)[:, None] * inv[None, :]
    cos, sin = np.cos(ang), np.sin(ang)

    def head(w, scale):
        y = x64 @ w                                        # [2, seq, d]
        y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-6) * scale
        y1, y2 = y[..., :d // 2], y[..., d // 2:]
        return np.concatenate([y1 * cos - y2 * sin, y2 * cos + y1 * sin],
                              -1)

    seen = mask_by_hand(12, 4)
    want = np.zeros((2, seq, e))
    for h in range(heads):
        q = head(p64["wq"][h], p64["q_norm"])
        k = head(p64["wk"][h // (heads // kv)], p64["k_norm"])
        v = x64 @ p64["wv"][h // (heads // kv)]
        scores = np.where(seen, q @ k.transpose(0, 2, 1) / np.sqrt(d),
                          -np.inf)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        want += (probs @ v) @ p64["wo"][h]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    plain = make_op(OperatorType.MULTIHEAD_ATTENTION,
                    dict(props, qk_norm=False), [(2, seq, e)] * 3)
    assert "q_norm" not in plain.init_params(jax.random.PRNGKey(4))
    assert op.params_elems() - plain.params_elems() == 2 * d


def test_decode_and_ring_refuse_the_mask():
    e = 32
    op = make_op(OperatorType.MULTIHEAD_ATTENTION,
                 dict(embed_dim=e, num_heads=2, bias=False,
                      block_diffusion=(8, 4)), [(2, 16, e)] * 3)
    with pytest.raises(NotImplementedError, match="block-diffusion"):
        op.decode_forward({}, [jnp.zeros((2, 1, e))], OpContext(), None,
                          None, 0)
    normed = make_op(OperatorType.MULTIHEAD_ATTENTION,
                     dict(embed_dim=e, num_heads=2, bias=False, causal=True,
                          qk_norm=True), [(2, 16, e)] * 3)
    with pytest.raises(NotImplementedError, match="query/key norm"):
        normed.decode_forward({}, [jnp.zeros((2, 1, e))], OpContext(),
                              None, None, 0)
    for bad in (dict(causal=True), dict(window=4, causal=True)):
        with pytest.raises(ValueError, match="block-diffusion"):
            make_op(OperatorType.MULTIHEAD_ATTENTION,
                    dict(embed_dim=e, num_heads=2, block_diffusion=(8, 4),
                         **bad), [(2, 16, e)] * 3)
    with pytest.raises(ValueError, match="2L positions"):
        make_op(OperatorType.MULTIHEAD_ATTENTION,
                dict(embed_dim=e, num_heads=2, block_diffusion=(8, 4)),
                [(2, 24, e)] * 3)
    # ring attention: the op refuses before it would split the sequence
    from flexflow_tpu.machine import make_mesh
    ring = make_op(OperatorType.MULTIHEAD_ATTENTION,
                   dict(embed_dim=e, num_heads=2, bias=False,
                        block_diffusion=(8, 4), seq_parallel="seq"),
                   [(2, 16, e)] * 3)
    ctx = OpContext(compute_dtype=jnp.float32,
                    mesh=make_mesh(2, {"seq": 2}))
    with pytest.raises(NotImplementedError, match="ring attention"):
        ring.forward(ring.init_params(jax.random.PRNGKey(0)),
                     [jnp.zeros((2, 16, e))], ctx)


@pytest.fixture(scope="module")
def hidden():
    rs = np.random.RandomState(5)
    return jnp.asarray(rs.randn(2, 24, 32), jnp.float32)


GATED = dict(n_experts=16, k=3, hidden_size=24, scoring="softmax",
             gated=True, activation="silu", slot_slack=15.0)


def test_silu_gated_experts_match_a_loop_over_tokens(hidden):
    g = hidden
    op = make_op(OperatorType.MOE_LAYER, GATED, [g.shape])
    params = op.init_params(jax.random.PRNGKey(1))
    assert set(params) == {"w_router", "w_gate", "w_up", "w_down"}
    got = run_op(op, params, [g])
    p64 = {k: np.asarray(v, np.float64) for k, v in params.items()}
    want = np.zeros(g.shape, np.float64)
    for b in range(g.shape[0]):
        for t in range(g.shape[1]):
            x = np.asarray(g[b, t], np.float64)
            logits = x @ p64["w_router"]
            p = np.exp(logits - logits.max())
            p /= p.sum()                      # softmax over all 16
            top = np.argsort(-p)[:3]
            for j in top:
                gate = x @ p64["w_gate"][j]
                want[b, t] += p[j] / p[top].sum() * (
                    (gate / (1 + np.exp(-gate)) * (x @ p64["w_up"][j]))
                    @ p64["w_down"][j])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    relu = make_op(OperatorType.MOE_LAYER, dict(GATED, activation="relu"),
                   [g.shape])
    assert not np.allclose(run_op(relu, params, [g]), got, atol=1e-3)
    assert relu.flops() == op.flops()
    for bad in (dict(GATED, activation="gelu"),
                dict(GATED, gated=False)):
        with pytest.raises(ValueError, match="activation"):
            make_op(OperatorType.MOE_LAYER, bad, [g.shape])


def test_eight_shares_of_sixteen_experts_add_up_to_the_uncut_layer(hidden):
    """8 chips with 16 of the 128 experts each (no shared expert to count
    once), against the reference's uncut layer (all 128 held)."""
    g = hidden
    kw = dict(GATED, n_experts=128, k=8, slot_slack=127.0)
    full = make_op(OperatorType.MOE_LAYER, kw, [g.shape])
    params = full.init_params(jax.random.PRNGKey(2))
    with fm.highest():
        want = np.asarray(jax.jit(lambda g, p: ref.experts(
            g, p, k=8, offset=0, operand="f32"))(g, params))
    np.testing.assert_allclose(run_op(full, params, [g]), want,
                               rtol=1e-4, atol=1e-5)
    # the reference's share is the program's
    parts = fm.expert_shares(
        kw, params, [g], 16, 8, rtol=1e-4, atol=1e-5,
        reference=lambda share, offset: ref.experts(
            g, share, k=8, offset=offset, operand="f32"))
    np.testing.assert_allclose(sum(parts), want, rtol=1e-4, atol=1e-5)


def test_weighted_loss_and_its_gradient_match_a_loop():
    rs = np.random.RandomState(6)
    logits = jnp.asarray(rs.randn(2, 6, 11), jnp.float32)
    ids = rs.randint(0, 11, size=(2, 6))
    weight = np.where(rs.rand(2, 6) < 0.5, 1.0 / rs.uniform(0.05, 1, (2, 6)),
                      0.0)
    weight[0, 0], weight[1, 5] = 0.0, 7.5
    labels = jnp.asarray(np.stack([ids, weight], -1), jnp.float32)
    fn = losses.get_loss_fn(
        LossType.WEIGHTED_SPARSE_CATEGORICAL_CROSSENTROPY)
    got, grad = jax.value_and_grad(fn)(logits, labels)
    z = np.asarray(logits, np.float64)
    want, want_grad = 0.0, np.zeros_like(z)
    for b in range(2):
        for t in range(6):
            p = np.exp(z[b, t] - z[b, t].max())
            p /= p.sum()
            want -= weight[b, t] * np.log(p[ids[b, t]]) / 12
            onehot = np.eye(11)[ids[b, t]]
            want_grad[b, t] = weight[b, t] * (p - onehot) / 12
    np.testing.assert_allclose(float(got), want, rtol=1e-6)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-5, atol=1e-7)
    # weight 0: exactly zero, not small
    assert np.array_equal(np.asarray(grad)[weight == 0],
                          np.zeros_like(np.asarray(grad)[weight == 0]))
    assert float(losses.target_positions(labels)) == (weight > 0).sum()
    # the reference's own loss reads the same labels
    assert float(jnp.sum(ref.sample_losses(logits, labels))
                 / ref.loss_denominator(labels)) == pytest.approx(want,
                                                                 rel=1e-6)


def test_block_diffusion_batch_lays_the_noised_copy_before_the_clean():
    rng = np.random.default_rng(11)
    x0 = rng.integers(0, 99, size=(5, 64))
    ids, labels = block_diffusion_batch(x0, 4, 99, np.random.default_rng(3))
    assert ids.shape == (5, 128) and ids.dtype == np.int32
    assert labels.shape == (5, 64, 2) and labels.dtype == np.float32
    assert np.array_equal(ids[:, 64:], x0)                 # the clean half
    assert np.array_equal(labels[..., 0], x0)
    masked = ids[:, :64] == 99
    # the mask id only where weighted, the clean token everywhere else
    assert np.array_equal(masked, labels[..., 1] > 0)
    assert np.array_equal(ids[:, :64][~masked], x0[~masked])
    # one t a block: the weights of a block's masked positions are equal,
    # 1/t with t in [1e-3, 1]
    blocks = labels[..., 1].reshape(5, 16, 4)
    for row in blocks.reshape(-1, 4):
        assert len(set(row[row > 0])) <= 1
    assert blocks[blocks > 0].min() >= 1.0 and blocks.max() <= 1e3
    # the same draws, by hand
    r = np.random.default_rng(3)
    t = np.repeat(r.uniform(1e-3, 1.0, size=(5, 16)), 4, axis=1)
    again = r.random((5, 64)) < t
    assert np.array_equal(masked, again)
    np.testing.assert_allclose(labels[..., 1][masked], (1 / t)[masked],
                               rtol=1e-6)
    # masked about half of the time: t is uniform
    many, _ = block_diffusion_batch(np.zeros((64, 256), np.int64), 4, 1,
                                    np.random.default_rng(0))
    assert 0.45 < (many[:, :256] == 1).mean() < 0.55
    with pytest.raises(ValueError, match="whole blocks"):
        block_diffusion_batch(x0[:, :62], 4, 99, rng)
    # the benchmark's own copy makes the same batch from the same draws
    s = dict(block_length=4, noise_t_min=1e-3, vocab_size=100)
    mine, mine_labels = family.noised(x0, s, np.random.default_rng(3))
    assert np.array_equal(mine, ids) and np.array_equal(mine_labels, labels)


# ---------------------------------------------------------------------------
# the decoder's `D` block through compile / fit against the reference

TINY = dict(
    num_hidden_layers=2, vocab_size=64, hidden_size=32, rms_norm_eps=1e-6,
    num_attention_heads=4, num_key_value_heads=1, head_dim=16,
    rope_theta=1000000, num_experts=4, num_experts_published=16,
    expert_offset=4, num_experts_per_tok=3, moe_intermediate_size=24,
    norm_topk_prob=True, hidden_act="silu", slot_slack=3.0, block_length=4,
    attention_mask="block_diffusion", shared_positions=True,
    noise_t_min=1e-3, initializer_range=0.2, embedding_std=1.0,
    mask_embedding_std=0.2, qk_norm_scale=1.5, seq=128, batch=2,
    steps_per_epoch=1)
CONFIG = dict(search_budget=2, adam=fm.ADAM)


def run_program(sizes, weights=None, steps=3):
    """``weights``: the module's, for a control (the `program_*` keys
    reach `family.build` alone, so making them again gives the same);
    a control is judged by its logits and takes no step."""
    # interpret mode: the attention ops run the flash kernels (whole tile
    # at this length), so the mask is the kernels' and not the core's
    with fm.pallas("interpret"):
        ff, made, (ids,), labels = fm.build_model(family, CONFIG, sizes, 3)
        if weights is not None:
            family.install_weights(ff, weights)
        with fm.highest():
            logits = np.asarray(ff.predict([ids]))
            step_losses = []
            for _ in range(steps):
                ff.fit([ids], labels, epochs=1, verbose=False)
                step_losses.append(float(ff._last_loss))
    return ff, weights or made, ids, labels, logits, step_losses


@pytest.fixture(scope="module")
def model():
    return run_program(TINY)


@pytest.fixture(scope="module")
def reference(model):
    from benchmarks.references import common
    _, weights, ids, labels, _, _ = model
    kw = family.reference_kw(TINY)
    with fm.highest():
        logits = np.asarray(jax.jit(lambda w, ids: ref.forward(
            w, ids, **kw))(weights, ids))
    return logits, common.train_losses(ref, weights, ids, labels, 1, 3,
                                       CONFIG["adam"], **kw)


def test_pattern_and_graph(model):
    ff, _, ids, labels, _, _ = model
    assert family.decoder_pattern(TINY) == "DD"
    assert family.pattern_of(TINY) == "DEDE"
    assert ids.shape == (2, 256) and labels.shape == (2, 128, 2)
    ops = {n.op.name: n.op for n in ff.executor.nodes}
    attn = ops["b1_attn"]
    assert attn.block_diffusion == (128, 4) and not attn.causal
    assert attn.rope and attn.rope_wrap == 128 and attn.qk_norm
    assert attn.visible_pairs == family.visible_pairs(TINY)
    mixer = ops["b1_mixer"]
    assert mixer.op_type == OperatorType.MOE_LAYER and mixer.gated
    assert mixer.scoring == "softmax" and mixer.activation == "silu"
    # the router reads the experts' own input: the POST-attention norm
    node = next(n for n in ff.executor.nodes if n.op.name == "b1_mixer")
    by_guid = {n.op.guid: n.op.name for n in ff.executor.nodes}
    assert [by_guid[r[1]] for r in node.input_refs] == ["b1_post_norm"]
    # the noised half alone reaches the final norm and the head
    assert ops["noised_half"].output_shapes == [(2, 128, 32), (2, 128, 32)]
    assert ops["final_ln"].input_shapes == [(2, 128, 32)]
    assert ops["lm_head"].output_shapes == [(2, 128, 64)]
    assert ff.search_seconds is not None and ff.strategy


def test_logits_and_three_losses_match_the_reference(model, reference):
    ff, _, _, labels, logits, step_losses = model
    want, want_losses = reference
    assert logits.shape == (2, 128, 64)
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(step_losses, want_losses, rtol=2e-5)
    assert step_losses[2] < step_losses[0]
    counters = ff.op_counters
    assert counters["moe/overflow_slots"] == 0
    assert counters["moe/slots_held"] > 0
    assert counters["loss/target_positions"] == (labels[..., 1] > 0).sum()
    assert counters["executor.block_diffusion_attention_ops"] == 2
    assert counters["executor.window_attention_ops"] == 0
    assert counters["executor.flash_lane_dense_ops"] == 2
    assert counters["attention/kv_blocks_visited"] == 2   # whole tiles


@pytest.mark.parametrize("control", [
    dict(program_attention_mask="causal"),
    dict(program_shared_positions=False)])
def test_a_program_with_another_mask_or_other_positions_fails(
        control, model, reference):
    """The two controls of the mechanism: plain causal attention over the
    2L positions, and positions 0..2L-1 for the two copies, each against
    the reference of the objective: judged as the harness judges, with the
    cell's limits, and not correct."""
    logits = run_program(dict(TINY, **control), model[1], steps=0)[4]
    nrmse = hs.prediction_errors(logits, reference[0], False)["nrmse"]
    assert nrmse > family.TOLERANCES["pred_nrmse"], nrmse


def test_scopes_reach_the_compiled_steps_op_names(model, monkeypatch):
    """The device trace's readers find the new scopes by these names,
    forward and backward."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    with fm.highest():   # as the fixture's steps ran: the step it compiled
        scopes = family.scopes_of_compiled_step(model[0])
    for scope in ("jit(attention_block_diffusion)",
                  "jit(flash_block_diffusion)", "jit(moe_layer)",
                  "jit(moe_grouped_matmul)"):
        assert any(scope in n for n in scopes.values()), scope
        assert any(scope in n and "transpose(" in n
                   for n in scopes.values()), scope
    # the kernels sit inside the attention's scope
    assert any("jit(attention_block_diffusion)" in n
               and "jit(flash_block_diffusion)" in n
               for n in scopes.values())
    assert not any("attention_full" in n or "attention_window" in n
                   for n in scopes.values())


def test_kernel_fallbacks_holds_the_target_count_to_the_datas(model):
    ff = model[0]
    family.make_data(TINY, 3)         # the data of the model's last epoch
    with fm.highest():   # as the fixture's steps ran: the step it compiled
        assert family.kernel_fallbacks(ff) == {}
        assert family.observed["scopes"]
        family.make_data(TINY, 4)         # other data: another count
        assert "loss/target_positions" in family.kernel_fallbacks(ff)


# ---------------------------------------------------------------------------
# the search


def attention_node(seq, block_diffusion=None, causal=False):
    e = 2048
    props = dict(embed_dim=e, num_heads=8, num_kv_heads=1, head_dim=128,
                 bias=False, causal=causal, qk_norm=True)
    if block_diffusion:
        props["block_diffusion"] = block_diffusion
    op = make_op(OperatorType.MULTIHEAD_ATTENTION, props, [(1, seq, e)] * 3)
    from flexflow_tpu.search.unity import _node_attrs, _param_shapes
    return op, dict(
        guid=1, type="MULTIHEAD_ATTENTION", name="attn",
        inputs=[[-1, 0]] * 3, input_shapes=[[1, seq, e]] * 3,
        output_shapes=[[1, seq, e]],
        roles=[["sample", "seq", "channel"]], params=_param_shapes(op),
        flops=float(op.flops()), dtype_size=2, attrs=_node_attrs(op))


def test_search_prices_the_mask_at_its_visible_pairs_and_admits_flash():
    from flexflow_tpu.search import native
    if not native.available():
        pytest.skip("native search unavailable")
    seq = 16384
    machine = {"num_devices": 1, "flops": 197e12, "hbm_bw": 0.82e12,
               "hbm_cap": 16e9, "ici_bw": 45e9, "ici_latency": 1e-6,
               "dcn_bw": 25e9, "dcn_latency": 1e-5, "num_slices": 1,
               "comm_bytes_factor": 0.5}
    prices = {}
    for bd in (None, (seq // 2, 4)):
        op, node = attention_node(seq, bd, causal=bd is None)
        assert node["attrs"].get("keys_seen") == (4098 if bd else None)
        resp = native.native_optimize(dict(
            nodes=[node], machine=machine, measured={},
            config=dict(budget=2, training=True, enable_substitution=False,
                        batch=1, emit_search_trace=True)))
        (traced,) = resp["search_trace"]["ops"]
        assert not traced.get("kernel_rejections"), traced
        cands = {c["choice"]: c["terms"]["total_s"]
                 for c in traced["candidates"]}
        assert "rep_k:flash" in cands
        prices[bd] = (op.flops(), cands["rep"], cands["rep_k:flash"])
    masked, square = prices[(seq // 2, 4)], prices[None]
    # 12 * pairs * 1024 a layer forward and backward is 4 * pairs * 1024
    # forward: the pairs counted exactly, a quarter of the square and 2
    # keys a query more
    assert square[0] - masked[0] == 4 * 8 * 128 * (seq * seq - 67_141_632)
    assert masked[1] < square[1] and masked[2] < square[2]
    assert pk.flash_shape_legal(seq, 128, 8)
