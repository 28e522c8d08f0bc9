"""Head counts that differ by layer, the per-head output gate, partial and
YaRN rotary and a window narrower than a K chunk (PR 41;
`benchmarks/references/laguna.py` is the plain float32 reference, which
shares no code with `flexflow_tpu`): the window kernels against the
einsum core at 1, 6 and 8 query heads a key/value head, the frequency
table against its closed form, the gate's gradient by finite differences,
the model against the reference for logits, three losses and every
gradient leaf, the share test that ties a chip's experts to the uncut
layer, and what the new properties refuse."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_model as fm
from benchmarks import harness as hs
from benchmarks.references import laguna as ref
from family_model import ROOT, OpContext, make_op, run_op
from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops.attention import (rotary_embedding,
                                        rotary_frequencies, rotary_partial,
                                        scaled_dot_product_attention)
from one_program import output_and_gradients

CELL = "laguna_xs2.s8192_b1.1chip"
# every width small, the structure whole: both head counts (3 and 4 query
# heads a key/value head), both rotary forms, the gate, a window, the
# dense layer 0 and 4 held experts of 16 with the shared expert
TINY = dict(num_hidden_layers=5, vocab_size=64, hidden_size=32,
            num_attention_heads=6,
            num_attention_heads_per_layer=[6, 8, 8, 8, 6],
            num_key_value_heads=2, head_dim=16, sliding_window=8,
            intermediate_size=48, num_experts=4, num_experts_published=16,
            num_experts_per_tok=3, moe_intermediate_size=24,
            shared_expert_intermediate_size=24, slot_slack=3.0,
            initializer_range=0.2, seq=32, batch=2, steps_per_epoch=1)
YARN = dict(rope_type="yarn", factor=64, beta_fast=64, beta_slow=1,
            original_max_position_embeddings=4096,
            attention_factor=1.4158883083359672)


# ---------------------------------------------------------------------------
# the window kernels under a window narrower than a K chunk


def repeat_kv(x, kv, heads):
    b, s, w = x.shape
    return jnp.repeat(x.reshape(b, s, kv, w // kv), heads // kv, axis=2
                      ).reshape(b, s, heads * (w // kv))


def one_span_by_hand(seq, window):
    """What `pk.one_span` should answer under a causal window that
    hides something past the whole-tile kernels: the forward's Q block
    (256 rows where that divides S) and the backward's K block (128),
    each with the positions it reaches rounded up to 128, where the
    forward's fit one tile."""
    forward, backward = ((rows, -(-(window + rows - 1) // 128) * 128)
                         for rows in (pk._q_block(seq), 128))
    return (forward, backward) if forward[1] <= pk.MAX_SPAN else None


@pytest.mark.parametrize("seq,window,group", [
    (1536, 128, 1), (1536, 128, 6), (1536, 128, 8), (1536, 512, 8),
    # PR 46, the one-span form at its edges: windows that are no
    # multiple of 128 (spans of 512 and, exactly, 768), the widest window
    # the rule admits (769: 1024 keys) and the first it does not (770:
    # the chunk loop), Q blocks of 128 where 256 does not divide S, and a
    # span that is most of the sequence (held at 0 for three of the five
    # Q blocks, at S - span for two of the five K blocks)
    (1536, 200, 1), (1536, 513, 6), (1536, 769, 1), (1536, 770, 1),
    (1152, 200, 8), (1280, 769, 1)])
def test_narrow_window_flash_matches_the_einsum_core(seq, window, group):
    """The blocked kernels (S several chunks long), forward and the
    gradients of q, k, v, the key/value head's through the repeat to
    `group` query heads (heads of 16 lanes: one column block holds them
    all, and the interpreter's grid is short). Since PR 46 every case but
    the window of 770 takes the one-span form: a Q block against the
    keys it reaches and a K block against the queries that see it as ONE
    masked tile each, the span held at 0 at the sequence's start and at
    S - span at its end."""
    d, kv = 16, 1
    heads = kv * group
    if window in (128, 512):    # the chunk the loop would take (PR 41)
        assert pk._seq_block(seq, None, window) == max(window, 256) < 1024
    one = pk.one_span(seq, True, window)
    assert one == one_span_by_hand(seq, window)
    assert (one is None) == (window == 770)
    keys = jax.random.split(jax.random.PRNGKey(seq + group), 4)
    q, weight = (jax.random.normal(k, (1, seq, heads * d), jnp.float32)
                 for k in keys[:2])
    k, v = (jax.random.normal(key, (1, seq, kv * d), jnp.float32)
            for key in keys[2:])

    def flash(q, k, v):
        return pk._flash(q, repeat_kv(k, kv, heads), repeat_kv(v, kv, heads),
                         heads, True, True, window)

    def einsum_core(q, k, v):
        split = lambda x: pk.split_heads(x, heads)  # noqa: E731
        return pk.merge_heads(scaled_dot_product_attention(
            split(q), split(repeat_kv(k, kv, heads)),
            split(repeat_kv(v, kv, heads)), causal=True, window=window))

    with fm.highest():
        o, got = output_and_gradients(flash, weight, q, k, v)
        o_want, want = output_and_gradients(einsum_core, weight, q, k, v)
    np.testing.assert_allclose(o, o_want, rtol=2e-4, atol=2e-5)
    for g, w in zip(got, want):
        scale = float(np.max(np.abs(w)))
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=2e-5)


def test_the_widest_one_span_window_agrees_with_the_chunk_loop(monkeypatch):
    """A window of 769 is the widest whose reach from a Q block of 256
    (1,024 keys) is one tile, 770 the first that takes the chunk loop
    (both against the einsum core above). Here the two FORMS at one
    window: output, logsumexp, dQ, dK, dV of the one-span kernels against
    those of the chunk loop (`one_span` held to None), which differ only
    in the order of float32 roundings (the plain softmax against the
    online one), heads of 128 in bfloat16 as the cells run them."""
    seq, window, heads = 1536, 769, 2
    assert pk.one_span(seq, True, window) == ((256, pk.MAX_SPAN), (128, 896))
    assert pk.one_span(seq, True, window + 1) is None
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, do = (jax.random.normal(key, (1, seq, heads * 128),
                                     jnp.float32).astype(jnp.bfloat16)
                   for key in keys)

    def run():
        # a new function a call: each is traced under the rule in force
        def both(q, k, v, do):
            o, lse = pk._flash_fwd(q, k, v, heads, True, True,
                                   window=window)
            return (o, lse) + tuple(pk._flash_bwd(
                q, k, v, o, lse, do, heads, True, True, window=window))
        return jax.jit(both)(q, k, v, do)

    got = run()
    monkeypatch.setattr(pk, "one_span", lambda *a, **k: None)
    want = run()
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # a bf16 rounding (2^-8) of the largest entry at most, and far
        # less over the whole tensor
        assert np.abs(a - b).max() <= 2.0 ** -8 * np.abs(b).max(), name
        assert (np.sqrt(np.mean((a - b) ** 2))
                <= 1e-3 * np.sqrt(np.mean(b ** 2))), name


@pytest.mark.parametrize("group", [1, 6, 8])
def test_one_span_reads_grouped_keys_at_the_kv_head(group, monkeypatch):
    """`flash_attention(..., num_kv_heads=)` under a narrow window: ONE
    KV head of 128 lanes read by `group` query heads through the one-span
    kernels (the K / V BlockSpec's `j // rep`, dK and dV a group's
    float32 sums in the KV head's resident panel), against the einsum
    core on repeated keys, forward and the gradients of q, k, v."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    seq, window, d = 1280, 300, 128
    assert pk.one_span(seq, True, window) == ((256, 640), (128, 512))
    keys = jax.random.split(jax.random.PRNGKey(group), 4)
    q, weight = (jax.random.normal(key, (1, seq, group * d), jnp.float32)
                 for key in keys[:2])
    k, v = (jax.random.normal(key, (1, seq, d), jnp.float32)
            for key in keys[2:])

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, group, True, window=window,
                                  num_kv_heads=1)

    def einsum_core(q, k, v):
        split = lambda x: pk.split_heads(x, group)  # noqa: E731
        return pk.merge_heads(scaled_dot_product_attention(
            split(q), split(repeat_kv(k, 1, group)),
            split(repeat_kv(v, 1, group)), causal=True, window=window))

    with fm.highest():
        o, got = output_and_gradients(flash, weight, q, k, v)
        o_want, want = output_and_gradients(einsum_core, weight, q, k, v)
    np.testing.assert_allclose(o, o_want, rtol=2e-4, atol=2e-5)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        scale = float(np.max(np.abs(w)))
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=2e-5)


def test_the_rule_leaves_every_other_kind_alone(monkeypatch):
    """`one_span` answers None for whatever is not a causal window that
    hides something, narrower than a tile, past the whole-tile kernels:
    `flash_attention_lse` (the ring's primitive carries no window: its
    logsumexp's cotangent goes through the chunk loop as before), full
    causal, a window that hides nothing, smallthinker's 4096, block
    diffusion, the two-part score, and S <= MAX_BWD_SEQ."""
    assert pk.one_span(8192, True, 512) == ((256, 768), (128, 640))
    for kind in [(8192, True, 0), (8192, False, 0), (8192, True, 8192),
                 (16384, True, 4096), (8192, True, 1024), (1024, True, 128),
                 (512, True, 100), (4096, False, 0, (2048, 4)),
                 (8192, True, 512, None, 64)]:
        assert pk.one_span(*kind) is None, kind
    with pytest.raises(ValueError):
        pk.one_span(8192, False, 512)      # a window needs causal
    seq, heads = 1280, 2
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q, k, v, wo = (jax.random.normal(key, (1, seq, heads * 16), jnp.float32)
                   for key in keys[:4])
    wl = jax.random.normal(keys[4], (1, heads, seq), jnp.float32)

    def by_kernel(q, k, v):
        o, lse = pk.flash_attention_lse(q, k, v, heads, True, True)
        return jnp.sum(o * wo) + jnp.sum(lse * wl)

    def by_einsum(q, k, v):
        split = lambda x: pk.split_heads(x, heads).reshape(  # noqa: E731
            heads, seq, 16)
        o, lse = pk._xla_attention_lse(split(q), split(k), split(v), True)
        return (jnp.sum(pk.merge_heads(o[None]) * wo)
                + jnp.sum(lse[None] * wl))

    asked, rule = [], pk.one_span
    monkeypatch.setattr(pk, "one_span", lambda *a, **k: asked.append(
        rule(*a, **k)) or asked[-1])
    with fm.highest():
        got = jax.jit(jax.grad(by_kernel, argnums=(0, 1, 2)))(q, k, v)
        want = jax.jit(jax.grad(by_einsum, argnums=(0, 1, 2)))(q, k, v)
    # forward and backward both asked, and both took the chunk loop
    assert len(asked) >= 2 and all(one is None for one in asked)
    for g, w in zip(got, want):
        scale = float(np.max(np.abs(w)))
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=2e-5)


def one_span_squares(seq, forward_tile, backward_tile):
    """The pairs the one-span kernels visit, as a [query, key] boolean
    square a direction, from the kernels' own starts (`_k_span`,
    `_q_span`)."""
    forward = np.zeros((seq, seq), bool)
    backward = np.zeros((seq, seq), bool)
    blk, span = forward_tile
    for q0 in range(0, seq, blk):
        k0 = pk._k_span(q0, blk, span)
        assert k0 % 128 == 0 and 0 <= k0 <= seq - span
        forward[q0:q0 + blk, k0:k0 + span] = True
    blk, span = backward_tile
    for k0 in range(0, seq, blk):
        q0 = pk._q_span(k0, span, seq)
        assert q0 % 128 == 0 and 0 <= q0 <= seq - span
        backward[q0:q0 + span, k0:k0 + blk] = True
    return forward, backward


@pytest.mark.parametrize("seq,window", [
    (1536, 128), (1536, 200), (1536, 513), (1536, 769), (1152, 200),
    (1280, 769), (8192, 512)])
def test_every_visible_pair_lies_in_a_one_span_tile(seq, window):
    """The one-span form's tiles, forward ([Q block, span of keys]) and
    backward ([span of queries, K block]), hold every pair `visible`
    admits, and `visited_pairs` / `kv_blocks` count exactly those
    tiles."""
    one = pk.one_span(seq, True, window)
    (blk, span), (_, back_span) = one
    i = np.arange(seq)
    seen = np.asarray(pk.visible(i[:, None], i[None, :], window))
    forward, backward = one_span_squares(seq, *one)
    assert not (seen & ~forward).any() and not (seen & ~backward).any()
    assert pk.visited_pairs(seq, True, window) == int(
        forward.sum() + backward.sum()) == seq * (span + back_span)
    assert pk.kv_blocks(seq, True, window) == (
        seq // blk, (seq // blk) * -(-seq // span))
    assert pk.kv_blocks_masked(seq, True, window) == seq // blk


@pytest.mark.parametrize("seq,window,block", [
    (8192, 512, 512), (8192, 4096, 1024), (8192, 0, 1024), (8192, 128, 256),
    (16384, 4096, 1024), (8192, 1024, 1024), (3072, 512, 512)])
def test_the_chunk_follows_a_narrow_window_and_the_counts_follow_it(
        seq, window, block, monkeypatch):
    assert pk._seq_block(seq, None, pk.normalized_window(seq, True,
                                                         window)) == block
    visible = pk.visible_pairs(seq, True, window)
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    if seq <= 8192:
        assert visible == int(np.asarray(pk.visible(i, j, window)).sum())
    w = window or seq
    assert visible == seq * w - w * (w - 1) // 2
    # every visible pair lies in a visited tile, forward and backward
    visited = pk.visited_pairs(seq, True, window)
    assert visited >= 2 * visible
    if (seq, window) == (8192, 512):
        # PR 46: ONE tile a block, from the rule's own answer: a Q block
        # of 256 against the 768 keys it reaches, a K block of 128
        # against the 640 queries that see it: 1.5 and 1.25 times the
        # window's 512 keys a row (1.42 times the visible pairs)
        (blk, span), (back_blk, back_span) = pk.one_span(seq, True, window)
        assert (blk, span, back_blk, back_span) == (256, 768, 128, 640)
        assert visited == seq * (span + back_span) == 2.75 * seq * window
        assert 1.4 < visited / (2 * visible) < 1.42
        assert pk.kv_blocks(seq, True, window) == (
            seq // blk, (seq // blk) * -(-seq // span)) == (32, 32 * 11)
        assert pk.kv_blocks_masked(seq, True, window) == 32
        # the chunk loop of PR 41 (the rule held to None): a Q block of
        # 256 meets two chunks of 512, a K block of 512 two Q chunks of
        # 512 (but the first and the last): 2 and 2 times the window
        monkeypatch.setattr(pk, "one_span", lambda *a, **k: None)
        chunks = pk.visited_pairs(seq, True, window)
        whole = 8192 * 1024 - 256 * 512 * 2 + (8192 * 1024 - 512 * 512)
        assert whole > 1.4 * visited
        assert pk.kv_blocks(seq, True, window) == (2 * 32 - 2, 32 * 16)
        # since PR 51 a sub-tile takes the part of a chunk it can see: a
        # super-block of two Q blocks is one chunk of 512, and of its
        # diagonal chunk and of the far one (a whole window behind) three
        # of the four squares of 256 are met; the backward's K block of
        # two sub-blocks the same: (16 + 15) chunks x 3 squares each way,
        # within 6% of the one-span kernels' pairs (whose tiles have no
        # loop to carry sums through)
        assert pk.super_block(seq, window) == ((2, 2, True), 2)
        assert chunks == 2 * (16 + 15) * 3 * 256 * 256
        assert chunks == whole - 2 * (16 + 15) * 256 * 256
        assert 1.05 < chunks / visited < 1.06
        # at the chunks of 1024 that S alone gives
        monkeypatch.setattr(pk, "_seq_block", lambda s, bd=None, w=0: 1024)
        # 46 forward tiles of [256, 1024] (a Q block meets one chunk or
        # two; the window ends inside a chunk, so a step is one Q block
        # and its tiles are whole), 15 backward tiles of [1024, 1024],
        # of the 8 at a K block's own positions 10 of 16 squares: 2.1
        # times as many pairs (2.4 with those whole, until PR 51)
        assert pk.super_block(seq, window) == ((1, 1, False), 4)
        assert pk.visited_pairs(seq, True, window) == (
            46 * 256 * 1024 + 15 * 1024 * 1024 - 8 * 6 * 256 * 256
        ) > 2.1 * visited


# ---------------------------------------------------------------------------
# rotary: the table and the partial form


def test_yarn_table_against_the_closed_form_and_plain_rotary():
    theta, d = 500000.0, 64
    got, factor = rotary_frequencies(d, theta, YARN)
    assert factor == pytest.approx(0.1 * math.log(64) + 1)
    j = np.arange(d // 2)
    f = theta ** (-2.0 * j / d)
    low = math.floor(d * math.log(4096 / (64 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(d * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (5, 16)
    m = 1 - np.clip((j - low) / (high - low), 0, 1)
    np.testing.assert_allclose(got, f / 64 * (1 - m) + f * m, rtol=1e-5)
    # the fast lanes keep their frequency, the slow ones are divided by 64
    np.testing.assert_allclose(got[:6], f[:6], rtol=1e-5)
    np.testing.assert_allclose(got[16:], f[16:] / 64, rtol=1e-5)
    # the reference's table is the same numbers
    want, scale = ref.inverse_frequencies(d, dict(YARN, rope_theta=theta))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert scale == factor
    # at factor 1 the table is plain rotary's, and the factor 1
    plain, one = rotary_frequencies(d, theta, dict(YARN, factor=1,
                                                   attention_factor=None))
    np.testing.assert_allclose(plain, f, rtol=1e-5)
    np.testing.assert_allclose(plain, rotary_frequencies(d, theta)[0])
    assert one == 1.0
    with pytest.raises(ValueError, match="rope_type"):
        rotary_frequencies(d, theta, dict(rope_type="linear"))


@pytest.mark.parametrize("rotated,rope", [
    (16, dict(rope_theta=100.0)), (8, dict(rope_theta=100.0)),
    (8, dict(YARN, rope_theta=500000.0, original_max_position_embeddings=8,
             factor=4, beta_fast=2))])
def test_partial_rotary_against_the_reference_and_the_whole_form(rotated,
                                                                 rope):
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 40, 3, 16), jnp.float32)     # [B, S, H, D]
    scaling = {k: v for k, v in rope.items() if k != "rope_theta"} or None
    inv_freq, factor = rotary_frequencies(rotated, rope["rope_theta"],
                                          scaling)
    got = rotary_partial(x, inv_freq, rotary_dim=rotated,
                         attention_factor=factor)
    want = jnp.moveaxis(ref.rotary(jnp.moveaxis(x, 2, 1), dict(
        rope, partial_rotary_factor=rotated / 16)), 1, 2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the lanes past the rotated ones pass, bit for bit
    assert np.array_equal(got[..., rotated:], x[..., rotated:])
    if rotated == 16 and scaling is None:
        np.testing.assert_allclose(
            got, rotary_embedding(x, theta=100.0, seq_axis=1), rtol=1e-5,
            atol=1e-6)


# ---------------------------------------------------------------------------
# the op: gate, heads, refusals


GATED = dict(embed_dim=32, num_heads=6, num_kv_heads=2, head_dim=16,
             bias=False, causal=True, rope=True, rope_theta=500000.0,
             gate=True, partial_rotary_factor=0.5, rope_scaling=YARN)


def test_gated_attention_matches_the_reference_and_counts_itself():
    op = make_op(OperatorType.MULTIHEAD_ATTENTION, GATED, [(2, 24, 32)] * 3)
    params = op.init_params(jax.random.PRNGKey(3))
    assert {k: v.shape for k, v in params.items()} == {
        "wq": (6, 32, 16), "wk": (2, 32, 16), "wv": (2, 32, 16),
        "wo": (6, 16, 32), "w_gate": (32, 6)}
    assert op.full_precision_params == ("w_gate",)
    assert op.params_elems() == sum(int(np.prod(p.shape))
                                    for p in params.values())
    plain = make_op(OperatorType.MULTIHEAD_ATTENTION,
                    dict(GATED, gate=False), [(2, 24, 32)] * 3)
    # the gate's product and its multiply, priced with the op's own heads
    assert op.flops() - plain.flops() == (2 * 32 + 16) * 2 * 24 * 6
    assert op.params_elems() - plain.params_elems() == 32 * 6
    # the four leaves an op without the gate has are what they were
    for name, leaf in plain.init_params(jax.random.PRNGKey(3)).items():
        assert np.array_equal(leaf, params[name]), name
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(2, 24, 32), jnp.float32)
    rope = dict(YARN, rope_theta=500000.0, partial_rotary_factor=0.5)
    with fm.highest():
        want = jax.jit(lambda x, p: ref.attention(
            x, p, rope=rope, window=0, operand="f32"))(x, params)
    np.testing.assert_allclose(run_op(op, params, [x] * 3), want, rtol=1e-4,
                               atol=1e-5)
    # each control's program is another model
    for other in (dict(gate=False), dict(gate_activation="sigmoid"),
                  dict(partial_rotary_factor=1.0), dict(rope_scaling=None),
                  dict(window=8)):
        control = make_op(OperatorType.MULTIHEAD_ATTENTION,
                          dict(GATED, **other), [(2, 24, 32)] * 3)
        assert not np.allclose(run_op(control, params, [x] * 3), want,
                               atol=1e-3), other


def test_the_gates_gradient_by_finite_differences():
    """d loss / d w_gate (a 16-lane sum of dO * o a head and position,
    through the softplus) and d loss / d x through the gate, against
    central differences of the op's own forward in float64-free float32:
    a few entries, a step that the curvature allows."""
    op = make_op(OperatorType.MULTIHEAD_ATTENTION, GATED, [(1, 12, 32)] * 3)
    params = op.init_params(jax.random.PRNGKey(5))
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(1, 12, 32), jnp.float32)
    weight = jnp.asarray(rs.randn(1, 12, 32), jnp.float32)
    ctx = OpContext(training=True, compute_dtype=jnp.float32)

    def loss(w_gate):
        out = op.forward(dict(params, w_gate=w_gate), [x] * 3, ctx)[0]
        return jnp.sum(out * weight)

    with fm.highest():
        grad = np.asarray(jax.jit(jax.grad(loss))(params["w_gate"]))
        loss = jax.jit(loss)
        for at in ((0, 0), (7, 3), (31, 5), (16, 2)):
            step = np.zeros((32, 6), np.float32)
            step[at] = 1e-2
            numeric = (float(loss(params["w_gate"] + step))
                       - float(loss(params["w_gate"] - step))) / 2e-2
            assert grad[at] == pytest.approx(numeric, rel=2e-2, abs=1e-4), at
    assert np.abs(grad).min() > 0          # every head's gate is reached


@pytest.mark.parametrize("props,match", [
    (dict(partial_rotary_factor=0.03), "rotated lanes"),
    (dict(gate_activation="tanh"), "gate_activation"),
    (dict(q_lora_rank=24, kv_lora_rank=16, qk_rope_head_dim=8,
          num_kv_heads=6), "latent attention")])
def test_what_the_new_properties_refuse(props, match):
    with pytest.raises(ValueError, match=match):
        make_op(OperatorType.MULTIHEAD_ATTENTION, dict(GATED, **props),
                [(2, 24, 32)] * 3)


@pytest.mark.parametrize("props", [
    dict(gate=True), dict(partial_rotary_factor=0.5),
    dict(rope_scaling=YARN)])
def test_decode_and_the_cache_refuse_the_new_properties(props, tiny):
    base = {k: v for k, v in GATED.items()
            if k not in ("gate", "partial_rotary_factor", "rope_scaling")}
    op = make_op(OperatorType.MULTIHEAD_ATTENTION, dict(base, **props),
                 [(2, 24, 32)] * 3)
    with pytest.raises(NotImplementedError, match="partial_rotary_factor"):
        op.decode_forward({}, [jnp.zeros((2, 1, 32))] * 3,
                          OpContext(compute_dtype=jnp.float32), None, None, 0)
    from flexflow_tpu.serve.kv_cache import init_kv_cache
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        init_kv_cache(tiny[-1], max_len=TINY["seq"])


def test_the_search_prices_each_op_with_its_own_heads_window_and_gate():
    from flexflow_tpu.search.unity import _node_attrs, _param_shapes
    full = make_op(OperatorType.MULTIHEAD_ATTENTION, GATED,
                   [(1, 2048, 32)] * 3)
    window = make_op(OperatorType.MULTIHEAD_ATTENTION,
                     dict(GATED, num_heads=8, window=512),
                     [(1, 2048, 32)] * 3)
    assert _node_attrs(full)["num_heads"] == 6
    assert "window" not in _node_attrs(full)
    assert _node_attrs(window)["num_heads"] == 8
    assert _node_attrs(window)["window"] == 512     # the keys a query meets
    assert _param_shapes(full)["w_gate"] == [32, 6]
    assert _param_shapes(window)["w_gate"] == [32, 8]
    # scores over S x 512 pairs for 8 heads, over S x S for 6
    core = lambda op: op.flops() - 2 * 2048 * (  # noqa: E731
        op.params_elems() + 16 * op.num_heads // 2)
    assert core(window) == 4 * 8 * 16 * 2048 * 512
    assert core(full) == 4 * 6 * 16 * 2048 * 2048


# ---------------------------------------------------------------------------
# the model


@pytest.fixture(scope="module")
def tiny():
    return fm.build_tiny(CELL, TINY)


def test_create_decoder_builds_the_cut_from_the_per_layer_lists(tiny):
    family, _, s, _, _, _, _, ff = tiny
    ops = {n.op.name: n.op for n in ff.executor.nodes}
    assert [ops[f"b{i}_attn"].num_heads for i in range(5)] == [6, 8, 8, 8, 6]
    assert [ops[f"b{i}_attn"].window for i in range(5)] == [0, 8, 8, 8, 0]
    assert [ops[f"b{i}_attn"].rotary_dim for i in range(5)] == [
        8, 16, 16, 16, 8]
    assert [bool(ops[f"b{i}_attn"].rope_scaling) for i in range(5)] == [
        True, False, False, False, True]
    assert ops["b0_attn"].rope_theta == 500000 and \
        ops["b1_attn"].rope_theta == 10000
    assert all(ops[f"b{i}_attn"].gate and ops[f"b{i}_attn"].num_kv_heads == 2
               for i in range(5))
    assert "b0_gate_up_proj" in ops and "b0_mixer" not in ops
    assert all(ops[f"b{i}_mixer"].experts_held == 4
               and ops[f"b{i}_mixer"].scoring == "sigmoid"
               and ops[f"b{i}_mixer"].shared_width == 24
               for i in range(1, 5))
    assert ff.search_seconds is not None and ff.strategy
    # the letters and the public config's kinds are one thing
    from flexflow_tpu.models import DecoderConfig, create_decoder
    by_letters = create_decoder(DecoderConfig(
        hybrid_override_pattern="FSS", num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, sliding_window_size=8))
    names = [layer.name for layer in by_letters.layers]
    assert {"b0_attn", "b2_mixer"} <= set(names)
    with pytest.raises(ValueError, match="layer_types"):
        create_decoder(DecoderConfig(layer_types=["hyena"]))
    with pytest.raises(ValueError, match="mlp_layer_types"):
        create_decoder(DecoderConfig(layer_types=["full_attention"],
                                     mlp_layer_types=["conv"]))


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
    assert counters["executor.window_attention_ops"] == 3
    assert [counters[f"attention/heads_by_op/b{i}_attn"]
            for i in range(5)] == [6, 8, 8, 8, 6]
    # the einsum core visits no tile: the kernels' counts stay 0 here
    assert counters["attention/window_keys_visited"] == 0


def test_every_gradient_leaf_matches_the_reference(tiny):
    _, got, want = fm.gradients_of(tiny)
    # the routers' bias moves no gradient; the gates' are among the rest
    assert fm.assert_leaves_close(got, want, still=("e_bias",)) == (
        2 + 5 * 7 + 2 + 4 * 7 + 1)


_CONTROLS_REFERENCE = {}


def controls_reference(tiny, layers):
    """The model cut to ``layers`` layers as the cell states it, its
    weights and the reference's predictions, made once a cut: the
    `program_*` keys reach `family.build` alone, so every control of a
    cut is compared with the same reference on the same weights."""
    if layers not in _CONTROLS_REFERENCE:
        s = tiny.family.sizes(tiny.config, tiny.traffic,
                              dict(TINY, num_hidden_layers=layers))
        cut = tiny._replace(s=s, weights=jax.device_get(
            tiny.family.make_weights(s, 11)))
        _CONTROLS_REFERENCE[layers] = (
            cut, fm.reference_predictions(cut)["preds"])
    return _CONTROLS_REFERENCE[layers]


@pytest.mark.parametrize("layers,control", [
    (1, dict(program_gating=False)),
    (1, dict(program_gate_activation="sigmoid")),
    (1, dict(program_full_partial_rotary_factor=1.0)),
    (1, dict(program_full_rope_type="default")),
    (2, dict(program_sliding_window=16))])
def test_a_program_built_otherwise_is_not_correct(tiny, layers, control):
    """The five mechanism controls: the gate left out, sigmoid for
    softplus, whole-head rotary and plain frequencies on the full layers,
    a wider window; the reference as the cell states it. On the model's
    first layer (full attention, dense), and its first two for the
    window's."""
    cut, want = controls_reference(tiny, layers)
    ff, _ = fm.control_model(
        cut, dict(TINY, num_hidden_layers=layers, **control))
    nrmse = hs.prediction_errors(fm.predictions(ff, cut), want,
                                 False)["nrmse"]
    assert nrmse > tiny.family.TOLERANCES["pred_nrmse"]


def test_the_step_names_the_new_scopes(tiny):
    from flexflow_tpu.obs import step_scopes
    text = fm.compiled_step_text(tiny)
    for scope in ("jvp(jit(attention_full))/jit(attention_gate)",
                  "transpose(jvp(jit(attention_full)))/jit(attention_gate)",
                  "jvp(jit(attention_window))/jit(attention_gate)",
                  "jit(attention_full))/jit(rotary_partial_yarn)",
                  "jit(attention_window))/jit(rotary_whole)"):
        assert scope in text, scope
    assert "jit(attention_window))/jit(rotary_partial_yarn)" not in text
    rows = step_scopes.table_of(text).values()
    assert {r["part"] for r in rows
            if "jit(attention_gate)" in r["op_name"]} == {"attention"}
    assert step_scopes.part_of(
        "jit(train_step)/jvp(jit(attention_gate))/mul") == "attention"


def test_four_shares_add_up_to_the_uncut_layer():
    """The share ties to the model: 4 chips hold 4 of 16 experts each;
    their routed parts, plus what every chip computes alike (the gated
    attention, the shared expert) counted ONCE, are the reference's uncut
    expert layer."""
    rs = np.random.RandomState(7)
    x = jnp.asarray(rs.randn(2, 24, 32), jnp.float32)
    kw = dict(n_experts=16, k=3, hidden_size=24, shared_width=24, gated=True,
              activation="silu", routed_scaling=2.5, slot_slack=15.0)
    attn = make_op(OperatorType.MULTIHEAD_ATTENTION,
                   dict(GATED, num_heads=8, window=8, partial_rotary_factor=1,
                        rope_scaling=None, rope_theta=10000.0),
                   [x.shape] * 3)
    full = make_op(OperatorType.MOE_LAYER, kw, [x.shape])
    w = {"b1_norm": {"scale": jnp.asarray(rs.rand(32) + 0.5, jnp.float32)},
         "b1_post_norm": {"scale": jnp.asarray(rs.rand(32) + 0.5,
                                               jnp.float32)},
         "b1_attn": attn.init_params(jax.random.PRNGKey(8)),
         "b1_mixer": full.init_params(jax.random.PRNGKey(9))}
    w["b1_mixer"]["e_bias"] = jnp.asarray(rs.randn(16) * 0.1, jnp.float32)
    ref_kw = dict(
        eps=1e-6, layer_types=("full_attention", "sliding_attention"),
        rope_full=(), rope_sliding=(("rope_theta", 10000.0),),
        sliding_window=8, num_experts_per_tok=3, routed_scaling_factor=2.5,
        expert_offset=0)
    with fm.highest():
        want, h = jax.jit(lambda x, w: (
            ref.layer(x, w, 1, ref_kw, "f32"),
            ref.rms_norm(x, w["b1_norm"]["scale"], 1e-6)))(x, w)
    attended = np.asarray(x) + run_op(attn, w["b1_attn"], [h] * 3)
    p = w["b1_mixer"]
    with fm.highest():
        g, shared = jax.jit(lambda a, scale, p: (
            ref.rms_norm(a, scale, 1e-6),
            ref.shared_expert(ref.rms_norm(a, scale, 1e-6), p, "f32")))(
                attended, w["b1_post_norm"]["scale"], p)
    shared = np.asarray(shared)
    # the reference's own share is the same part less the shared expert
    # (a pair the buffer could not hold would show here)
    parts = fm.expert_shares(
        kw, p, [g], 4, 4,
        reference=lambda share, offset: shared + ref.routed_experts_part(
            g, share, k=3, scaling=2.5, offset=offset, operand="f32"))
    total = attended + shared + sum(part - shared for part in parts)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_reference_counts_tie_to_the_configuration():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "laguna_xs2.json")) as f:
        config = json.load(f)
    from benchmarks.families import laguna as family
    s = family.sizes(config, dict(seq=8192, batch=1, steps_per_epoch=4))
    assert s["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert s["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert s["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert family.parameters(s) == 490_298_368
    assert family.train_flops_per_sample(s) / 1e12 == pytest.approx(19.40,
                                                                    abs=0.01)
    flops, nbytes = family.narrow_window_flash_step_flops_and_bytes(s)
    assert flops == 12 * (8192 * 512 - 512 * 511 // 2) * 3 * 64 * 128
    # 6.08 ms of FLOPs at the peak, 5.90 ms of bytes: the FLOPs bind, just
    assert flops / 197e12 == pytest.approx(6.083e-3, rel=1e-3)
    assert nbytes / 819e9 == pytest.approx(5.900e-3, rel=1e-3)
