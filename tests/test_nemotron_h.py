"""The hybrid Mamba-2 / experts / attention decoder against its plain
reference, at a small size on the CPU (same letters, tiny widths): forward
logits, loss and every gradient leaf; the chunked scan against the
stepwise recurrence; the grouped product against a loop; and the share
tests that tie a chip's share of a layer to the uncut layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_model as fm
from benchmarks import harness as hs
from benchmarks.references import nemotron_h as ref
from family_model import OpContext, make_op, run_op
from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops import moe, pallas_kernels, ssm
from one_program import output_and_gradients

family = hs.load_by_path("families", "nemotron_h")

TINY = dict(
    hybrid_override_pattern="MEMEM*EME", num_hidden_layers=9, vocab_size=64,
    hidden_size=32, layer_norm_epsilon=1e-5, num_attention_heads=2,
    num_key_value_heads=1, head_dim=8, mamba_num_heads=2, mamba_head_dim=8,
    n_groups=1, ssm_state_size=16, conv_kernel=4, chunk_size=8,
    time_step_min=1e-3, time_step_max=1e-1, time_step_floor=1e-4,
    n_routed_experts=4, n_routed_experts_published=16, expert_offset=4,
    num_experts_per_tok=3, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, routed_scaling_factor=2.5,
    norm_topk_prob=True, slot_slack=3.0, initializer_range=0.2,
    embedding_std=1.0, seq=29,
    batch=2, steps_per_epoch=1)
CONFIG = dict(search_budget=2, adam=fm.ADAM)

# What a case costs is the programs it compiles (ROADMAP D10), and a
# `jnp` call outside `jax.jit` compiles one an operation: the tests below
# run what is jax under one `jax.jit` a value, with their operands and
# expectations in numpy. A routing is one program a (shape, cut),
# whichever test asks for it.
route_held_experts = jax.jit(moe.route_held_experts, static_argnums=(1, 2, 3))


@pytest.fixture(scope="module")
def model():
    ff, weights, (ids,), labels = fm.build_model(family, CONFIG, TINY, 3)
    return ff, weights, ids, labels


@pytest.fixture(scope="module")
def reference(model):
    """(the reference's loss on the module's batch, its gradient): ONE
    program, a sample a call, by the harness's own driver."""
    from benchmarks.references import common
    _, weights, ids, labels = model
    return common.loss_and_grads(ref, fm.as_arrays(weights), ids, labels, 1,
                                 **family.reference_kw(TINY))


def test_searched_like_any_other_graph(model):
    ff = model[0]
    assert ff.search_seconds is not None and ff.strategy
    types = {n.op.op_type for n in ff.executor.nodes}
    assert {OperatorType.SSM_MIXER, OperatorType.MOE_LAYER,
            OperatorType.MULTIHEAD_ATTENTION} <= types


def test_forward_logits_and_loss_match_the_reference(model, reference):
    ff, weights, ids, labels = model
    got = np.asarray(ff.predict([ids]))
    with fm.highest():
        want = np.asarray(jax.jit(lambda w, ids: ref.forward(
            w, ids, **family.reference_kw(TINY)))(weights, ids))
    want_loss = reference[0]
    assert got.shape == (TINY["batch"], TINY["seq"], TINY["vocab_size"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    ff.fit([ids], labels, epochs=1, verbose=False)
    assert float(ff._last_loss) == pytest.approx(want_loss, rel=1e-5)
    # the routing counts left the step with the metrics
    assert ff.op_counters["moe/overflow_slots"] == 0
    assert ff.op_counters["moe/slots_held"] > 0
    assert ff.op_counters["moe/load_max_over_mean"] >= 1.0


def test_every_gradient_leaf_matches_the_reference(model, reference):
    ff, weights, ids, labels = model
    with fm.highest():
        got = jax.jit(jax.grad(fm.program_loss_of(ff, [ids], labels)))(
            fm.as_arrays(weights))
    want = reference[1]
    # the routers' bias moves no gradient on either side
    compared = fm.assert_leaves_close(got, want, still=("e_bias",))
    assert compared == len(jax.tree.leaves(want)) - 4


# ---------------------------------------------------------------------------
# the chunked scan


def scan_inputs(length, b=2, h=4, p=8, g=2, n=16, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(b, length, h, p).astype(np.float32)
    dt = np.logaddexp(rs.randn(b, length, h).astype(np.float32),
                      np.float32(0))                         # softplus
    a = -np.exp(rs.randn(h).astype(np.float32))
    bm = rs.randn(b, length, g, n).astype(np.float32)
    cm = rs.randn(b, length, g, n).astype(np.float32)
    return x, dt, a, bm, cm


@pytest.mark.parametrize("length", [24, 29, 8, 5])
def test_chunked_scan_matches_the_stepwise_recurrence(length):
    """Three whole chunks, a length the chunk does not divide, exactly one
    chunk, and less than one; forward and every gradient."""
    args = scan_inputs(length)
    weight = np.random.RandomState(1).randn(*args[0].shape).astype(
        np.float32)

    with fm.highest():
        y, got = output_and_gradients(
            lambda *a: ssm.ssd_chunked(*a, chunk=8), weight, *args)
        y_want, want = output_and_gradients(ssm.ssd_stepwise, weight, *args)
    np.testing.assert_allclose(y, y_want, rtol=1e-4, atol=1e-4)
    for g, w in zip(got, want):
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# the grouped product and the routing


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_grouped_matmul_matches_a_loop(mode, monkeypatch):
    """`lax.ragged_dot` (any backend) and the megablox kernels (the TPU's
    path, here interpreted): values of the rows the groups cover, and both
    gradients; the sizes leave rows past the groups' sum."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
    rs = np.random.RandomState(0)
    m, k, n, g = 256, 40, 24, 3
    lhs = rs.randn(m, k).astype(np.float32)
    rhs = rs.randn(g, k, n).astype(np.float32)
    sizes = np.asarray([70, 0, 130], np.int32)
    rows = int(sizes.sum())
    group = np.repeat(np.arange(g), sizes)

    def loop(lhs, rhs):
        return jnp.einsum("mk,mkn->mn", lhs[:rows], rhs[group])

    def grouped(lhs, rhs):
        return moe.grouped_matmul(lhs, rhs, sizes)[:rows]

    def value_and_gradients(fn):
        def loss(a, b):
            y = fn(a, b)
            return jnp.sum(y ** 2), y
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(lhs, rhs)

    with fm.highest():
        (_, y), got = value_and_gradients(grouped)
        (_, y_want), want = value_and_gradients(loop)
    np.testing.assert_allclose(y, y_want, rtol=1e-4, atol=1e-4)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


def test_routing_sorts_held_pairs_and_counts_what_does_not_fit():
    experts = jnp.asarray([[5, 0, 9], [4, 5, 1], [7, 6, 5], [2, 3, 8]],
                          jnp.int32)
    r = route_held_experts(experts, 4, 4, 8)
    assert r["load"].tolist() == [1, 3, 1, 1]
    assert r["group_sizes"].tolist() == [1, 3, 1, 1]
    assert int(r["overflow"]) == 0 and int(r["valid"].sum()) == 6
    flat = np.asarray(experts).reshape(-1)
    assert flat[np.asarray(r["slot"])[:6]].tolist() == [4, 5, 5, 5, 6, 7]
    # a buffer of 4 rows holds 4 of the 6 pairs and counts the other 2
    small = route_held_experts(experts, 4, 4, 4)
    assert small["group_sizes"].tolist() == [1, 3, 0, 0]
    assert int(small["overflow"]) == 2


def hand_routing():
    """Token 0 holds no pair here, token 1 all three, token 3 one."""
    return jnp.asarray([[0, 9, 3], [4, 5, 7], [7, 6, 2], [2, 3, 4]],
                       jnp.int32)


def random_routing(tokens, k, n_experts, seed):
    """k distinct experts a token, as `top_k` gives them."""
    rs = np.random.RandomState(seed)
    return jnp.asarray(np.stack([rs.permutation(n_experts)[:k]
                                 for _ in range(tokens)]), jnp.int32)


def tiles_routing():
    """Three tiles of 128 tokens: every pair of the first is held (a run
    of 128 * k rows), none of the second (an empty tile), of the third
    what falls to the two held experts; in it token 300 holds no pair and
    token 301 both."""
    experts = np.array(random_routing(384, 2, 16, 5))
    experts[:128] = [0, 1]
    experts[128:256] = [7, 9]
    experts[300], experts[301] = [5, 6], [1, 0]
    return jnp.asarray(experts)


def mask_token_routing(tokens, k, n_experts, seed):
    """A quarter of the tokens choose the same k experts, one of them
    held (the first), as the mask token of a block-diffusion sample."""
    rs = np.random.RandomState(seed)
    experts = np.array(random_routing(tokens, k, n_experts, seed))
    experts[rs.rand(tokens) < 0.25] = [0] + list(range(n_experts - k + 1,
                                                       n_experts))
    return jnp.asarray(experts)


# name -> (experts [T, k], held, offset, rows); the `kernel_` ones at
# shapes `moe_sum_rows` takes (whole tiles of tokens, whole blocks of
# rows), which under FLEXFLOW_TPU_PALLAS=interpret run it
ROUTINGS = {
    "by_hand": (hand_routing(), 4, 4, 8),
    "by_hand_buffer_too_small": (hand_routing(), 4, 4, 3),
    "random_an_eighth_held": (random_routing(96, 6, 64, 0), 8, 16, 128),
    "random_buffer_too_small": (random_routing(96, 6, 64, 1), 8, 0, 40),
    "all_experts_held": (random_routing(40, 3, 16, 2), 16, 0, 128),
    "buffer_past_the_pairs": (random_routing(8, 2, 4, 3), 2, 1, 128),
    "kernel_a_sixteenth_held": (random_routing(256, 4, 32, 4), 2, 8, 128),
    "kernel_full_empty_and_mixed_tiles": (tiles_routing(), 2, 0, 384),
    "kernel_a_quarter_on_the_same_experts": (
        mask_token_routing(512, 4, 32, 6), 4, 0, 512),
    "kernel_buffer_too_small": (random_routing(256, 4, 16, 7), 4, 4, 128),
    "a_row_a_pair_keeps_the_gathers": (random_routing(128, 2, 4, 8), 4, 0,
                                       256),
}


def sums_by_kernel(name, mode):
    return name.startswith("kernel_") and mode == "interpret"


@pytest.mark.parametrize("name", list(ROUTINGS))
def test_row_of_pair_is_the_inverse_of_slot(name):
    """A valid row's pair points back at the row; the pairs with a row
    are the held ones below the cut, and the others are counted."""
    experts, held, offset, rows = ROUTINGS[name]
    r = jax.tree.map(np.asarray,
                     route_held_experts(experts, held, offset, rows))
    flat = np.asarray(experts).reshape(-1)
    here = (flat >= offset) & (flat < offset + held)
    n_rows = int(r["valid"].sum())
    assert n_rows == min(int(here.sum()), rows) == r["group_sizes"].sum()
    assert int(r["overflow"]) == int(here.sum()) - n_rows
    assert ("too_small" in name) == (int(r["overflow"]) > 0)
    row_of_pair = r["row_of_pair"].reshape(-1)
    pair_valid = r["pair_valid"].reshape(-1)
    assert r["row_of_pair"].shape == r["pair_valid"].shape == experts.shape
    assert int(pair_valid.sum()) == n_rows and not pair_valid[~here].any()
    held_slots = r["slot"][:n_rows]
    assert pair_valid[held_slots].all()
    assert row_of_pair[held_slots].tolist() == list(range(n_rows))
    assert not row_of_pair[~pair_valid].any()
    # rows are sorted by expert, and within an expert by pair
    assert (np.diff(flat[held_slots]) >= 0).all()
    if name == "by_hand":
        assert r["pair_valid"].sum(axis=1).tolist() == [0, 3, 2, 1]
    if name == "all_experts_held":
        assert pair_valid.all() and n_rows == flat.size


@pytest.mark.parametrize("name", list(ROUTINGS))
def test_rows_in_token_order_is_the_stable_sort_of_slot(name):
    """The order `tokens_from_rows`' kernel reads the rows in (PR 37):
    the valid rows by the pair they hold, the others last and in place;
    each tile of tokens a run of it, and the kernel's items, tile by
    tile, the blocks of the order that the run touches."""
    SUM_ROWS, SUM_TOKENS = pallas_kernels.SUM_ROWS, pallas_kernels.SUM_TOKENS
    experts, held, offset, rows = ROUTINGS[name]
    tokens, k = experts.shape
    r = jax.tree.map(np.asarray,
                     route_held_experts(experts, held, offset, rows))
    order = r["in_token_order"]
    key = np.where(r["valid"], r["slot"], tokens * k)
    assert order["row"].tolist() == np.argsort(key, kind="stable").tolist()
    n_rows = int(r["valid"].sum())
    assert order["pair"][:n_rows].tolist() == sorted(r["slot"][:n_rows])
    assert (order["pair"] < tokens * k).all()
    assert order["token"].tolist() == (np.sort(key) // k).tolist()
    tiles = -(-tokens // SUM_TOKENS)
    start = order["tile_start"]
    assert start.tolist() == np.searchsorted(
        order["token"],
        np.minimum(np.arange(tiles + 1) * SUM_TOKENS, tokens)).tolist()
    assert start[0] == 0 and start[-1] == n_rows
    if name == "kernel_full_empty_and_mixed_tiles":
        assert start[:3].tolist() == [0, 256, 256]
        assert r["pair_valid"][300:302].sum(axis=1).tolist() == [0, 2]
    items = order["items"]
    blocks = -(-rows // SUM_ROWS)
    count = int(items["count"][0])
    assert items["tile"].shape == items["block"].shape == (tiles + blocks,)
    assert tiles <= count <= tiles + blocks
    # past the real items: the last one again, so that nothing is fetched
    assert (items["tile"][count:] == items["tile"][count - 1]).all()
    assert (items["block"][count:] == items["block"][count - 1]).all()
    assert (np.diff(items["tile"]) >= 0).all()
    for tile in range(tiles):
        mine = items["block"][:count][items["tile"][:count] == tile]
        assert len(mine) >= 1 and (np.diff(mine) == 1).all()
        assert 0 <= mine[0] and mine[-1] < blocks
        if start[tile + 1] > start[tile]:
            assert mine[0] * SUM_ROWS <= start[tile]
            assert start[tile + 1] <= (mine[-1] + 1) * SUM_ROWS


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "unweighted"])
@pytest.mark.parametrize("name", list(ROUTINGS))
def test_tokens_from_rows_is_the_scatter_add_of_the_rows(name, weighted,
                                                         dtype, mode,
                                                         monkeypatch):
    """`tokens_from_rows` against the form it replaced, `.at[token].add`
    of the valid rows, to float32 rounding: the k gathers, and under
    `interpret` at the shapes it takes the kernel `moe_sum_rows` (PR 37),
    whose products and sums are float32's too, whatever the buffer
    holds."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
    experts, held, offset, rows = ROUTINGS[name]
    tokens, k = experts.shape
    # wide enough for the kernel where tokens and rows are whole tiles
    width = 256 if pallas_kernels.moe_sum_rows_shape_legal(
        rows, 256, tokens) else 20
    assert moe.sums_rows_by_kernel(rows, width, tokens, k) == sums_by_kernel(
        name, mode)
    r = route_held_experts(experts, held, offset, rows)
    rs = np.random.RandomState(7)
    buf = rs.randn(rows, width).astype(np.float32).astype(dtype)
    weights = rs.rand(tokens, k).astype(np.float32)
    slot, valid = np.asarray(r["slot"]), np.asarray(r["valid"])
    w_row = np.where(valid, weights.reshape(-1)[slot] if weighted else 1.0,
                     0.0).astype(np.float32)
    want = np.zeros((tokens, width), np.float32)
    np.add.at(want, slot // k, buf.astype(np.float32) * w_row[:, None])

    def run(out_dtype):     # traced under the case's mode
        return jax.jit(lambda buf, r, weights: moe.tokens_from_rows(
            buf, r, weights, out_dtype))(
                buf, r, weights if weighted else None)

    got = run(jnp.float32)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert got.dtype == jnp.float32       # rounded once, to what is asked
    same = run(None)
    assert same.dtype == dtype
    np.testing.assert_array_equal(same, np.asarray(got).astype(dtype))


KERNEL_ROUTINGS = [name for name in ROUTINGS if name.startswith("kernel_")]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", KERNEL_ROUTINGS)
def test_moe_spread_rows_is_the_gather_of_the_tokens_gradient(name, dtype):
    """`moe_spread_rows` (PR 49), interpreted, against the plain form
    with the rows in token order: d x = w * dY[token], float32's product
    rounded once, to the bit; d weights[token, slot] = <dY[token], x>.
    The routings hold an empty tile and a run of two blocks
    (`kernel_full_empty_and_mixed_tiles`), blocks that two tiles share
    and a block no run reaches (`kernel_a_quarter_on_the_same_experts`),
    and rows that hold no pair, which come out 0."""
    experts, held, offset, rows = ROUTINGS[name]
    tokens, k = experts.shape
    order = route_held_experts(experts, held, offset,
                                   rows)["in_token_order"]
    token, slot = np.asarray(order["token"]), np.asarray(order["pair"]) % k
    rs = np.random.RandomState(11)
    x = rs.randn(rows, 256).astype(np.float32).astype(dtype)
    w = rs.rand(rows).astype(np.float32)
    d_y = rs.randn(tokens, 256).astype(np.float32)
    d_x, d_w = jax.jit(lambda *a: pallas_kernels.moe_spread_rows(
        *a, k, True))(d_y, x, order["token"], slot, w, order["items"])
    held_rows = token < tokens
    assert 0 < held_rows.sum() <= rows
    own = np.where(held_rows[:, None],
                   np.asarray(d_y)[np.minimum(token, tokens - 1)], 0.0)
    assert d_x.dtype == dtype and d_w.shape == (tokens, k)
    np.testing.assert_array_equal(d_x, (w[:, None] * own).astype(dtype))
    want = np.zeros((tokens, k), np.float32)
    want[token[held_rows], slot[held_rows]] = np.sum(
        own * x.astype(np.float32), axis=1)[held_rows]
    np.testing.assert_allclose(d_w, want, rtol=1e-5, atol=1e-5)
    assert (np.asarray(d_w)[want == 0] == 0).all()
    if name == "kernel_a_quarter_on_the_same_experts":
        items = jax.tree.map(np.asarray, order["items"])
        count = int(items["count"][0])
        assert (np.diff(items["block"][:count]) == 0).any()     # shared
        assert items["block"].max() < rows // 128 - 1           # unreached
        assert items["every_block"][:count].tolist() == (
            items["block"][:count].tolist())
        assert items["every_block"][count:].tolist() == np.minimum(
            items["block"][count - 1] + 1 + np.arange(len(items["block"])
                                                      - count),
            rows // 128 - 1).tolist()


@pytest.mark.parametrize("name,dtype", [
    (name, jnp.bfloat16) for name in KERNEL_ROUTINGS] + [
    ("kernel_full_empty_and_mixed_tiles", jnp.float32),
    ("a_row_a_pair_keeps_the_gathers", jnp.bfloat16)])
def test_combine_rows_gradients_are_the_same_both_ways(name, dtype,
                                                       monkeypatch):
    """`jax.grad` through `combine_rows` where its backward is the kernel
    `moe_spread_rows` over the rows in token order (PR 49: the `kernel_`
    routings, interpreted) equals the backward that gathers dY's rows:
    `d o` to the bit after its one rounding, `d weights` to float32's
    rounding. The kernel's backward says so to its caller, the other,
    and every backward of a shape the kernel does not take, does not."""
    experts, held, offset, rows = ROUTINGS[name]
    tokens, k = experts.shape
    width = 256 if pallas_kernels.moe_sum_rows_shape_legal(
        rows, 256, tokens) else 20
    rs = np.random.RandomState(13)
    o = rs.randn(rows, width).astype(np.float32).astype(dtype)
    weights = rs.rand(tokens, k).astype(np.float32)
    d_y = rs.randn(tokens, width).astype(np.float32)
    got = {}
    for mode in ("off", "interpret"):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
        r = route_held_experts(experts, held, offset, rows)
        said = []
        got[mode] = jax.jit(jax.grad(
            lambda o, w: jnp.sum(moe.combine_rows(
                o, w, r, lambda: said.append(mode)) * d_y), (0, 1)))(
                    o, weights)
        assert bool(said) == sums_by_kernel(name, mode)
    (d_o, d_w), (d_o_kernel, d_w_kernel) = got["off"], got["interpret"]
    assert d_o_kernel.dtype == dtype and d_w_kernel.dtype == jnp.float32
    np.testing.assert_array_equal(d_o_kernel, d_o)
    np.testing.assert_allclose(d_w_kernel, d_w, rtol=1e-5, atol=1e-5)
    assert (np.asarray(d_w_kernel)[~np.asarray(r["pair_valid"])] == 0).all()


def scatter_add_layer(op, params, inputs):
    """`MoELayer.forward` as it was until PR 32, kept here as the
    reference of the gradients: rows go out by `xt[token]`, come back by
    `.at[token].add`, and autodiff transposes both."""
    x, x_router = inputs[0], inputs[-1]
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    scores = jnp.dot(x_router.reshape(b * s, d), params["w_router"],
                     precision=jax.lax.Precision.HIGHEST)
    bias = None
    if op.scoring == "sigmoid":
        scores, bias = jax.nn.sigmoid(scores), params["e_bias"]
    choose = scores if bias is None else scores + bias
    _, experts = jax.lax.top_k(choose, op.k)
    top = jnp.take_along_axis(scores, experts, axis=-1)
    if op.scoring == "softmax":
        weights = jax.nn.softmax(top, axis=-1)
    else:
        weights = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    weights = weights * op.routed_scaling
    r = moe.route_held_experts(experts.astype(jnp.int32), op.experts_held,
                               op.expert_offset, op.buffer_rows)
    token = r["slot"] // op.k
    w_row = jnp.where(r["valid"], weights.reshape(-1)[r["slot"]], 0.0)
    x_buf = xt[token]
    h = moe.grouped_matmul(x_buf, params["w_up"], r["group_sizes"])
    if op.gated:
        h = jax.nn.relu(moe.grouped_matmul(x_buf, params["w_gate"],
                                           r["group_sizes"])) * h
    else:
        h = jnp.square(jax.nn.relu(h))
    o = moe.grouped_matmul(h, params["w_down"], r["group_sizes"])
    y = jnp.zeros((b * s, d), jnp.float32).at[token].add(o * w_row[:, None])
    if op.shared_width:
        y = y + jnp.square(jax.nn.relu(xt @ params["ws_up"])) \
            @ params["ws_down"]
    return y.reshape(b, s, d)


# what the two models' layers state, and a buffer that overflows (512
# tokens: a buffer is at least 128 rows); name -> (properties, inputs,
# tokens a sample)
LAYERS = {
    "softmax_gated_second_router_input": (dict(
        n_experts=16, k=3, hidden_size=24, scoring="softmax", gated=True,
        experts_held=4, expert_offset=8, slot_slack=15.0), 2, 24),
    "sigmoid_bias_shared_expert": (dict(
        n_experts=16, k=3, hidden_size=24, shared_width=48,
        routed_scaling=2.5, experts_held=4, expert_offset=4,
        slot_slack=15.0), 1, 24),
    "all_held": (dict(n_experts=8, k=2, hidden_size=24, shared_width=16),
                 1, 24),
    "buffer_too_small": (dict(
        n_experts=16, k=3, hidden_size=24, scoring="softmax", gated=True,
        experts_held=8, slot_slack=-0.5), 2, 256),
    # 256 tokens 128 wide, an eighth of the experts held: under
    # `interpret` the rows are summed by the kernel, forward (weighted)
    # and in the dispatch's backward (unweighted)
    "kernel_an_eighth_held": (dict(
        n_experts=16, k=2, hidden_size=24, scoring="softmax", gated=True,
        experts_held=2, expert_offset=6), 2, 128, 128),
    "kernel_shared_expert_buffer_too_small": (dict(
        n_experts=16, k=4, hidden_size=24, shared_width=48,
        routed_scaling=2.5, experts_held=4, slot_slack=-0.6), 1, 128, 128),
}


_SCATTER_ADD = {}


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("name", list(LAYERS))
def test_layer_gradients_match_the_scatter_add_form(name, mode, monkeypatch):
    """Output and `jax.grad` of a whole `MoELayer`, every leaf and every
    input, against the scatter-add form above, with the Pallas kernels
    off and interpreted (the grouped products' and, in the `kernel_`
    layers, `moe_sum_rows` and the combine's backward `moe_spread_rows`,
    which the op's gauges then say)."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
    props, n_inputs, seq, *width = LAYERS[name]
    width = width[0] if width else 32
    rs = np.random.RandomState(11)
    inputs = [rs.randn(2, seq, width).astype(np.float32)
              for _ in range(n_inputs)]
    probe = rs.randn(2, seq, width).astype(np.float32)
    layer = fm.Layer(OperatorType.MOE_LAYER, "op", [])
    layer.properties.update(props)
    op = fm.OpRegistry.create(layer, [x.shape for x in inputs])
    params = jax.jit(op.init_params)(jax.random.PRNGKey(4))
    if "e_bias" in params:
        params["e_bias"] = (0.1 * rs.randn(props["n_experts"])).astype(
            np.float32)
    ctx = OpContext(training=True, compute_dtype=jnp.float32)

    def program(params, inputs):
        y = op.forward(params, inputs, ctx)[0]
        overflow = op._counters["moe/overflow_slots"][1]
        op._counters = None
        return y, overflow

    with fm.highest():
        (y, overflow), got = output_and_gradients(program, probe, params,
                                                  inputs)
        if name not in _SCATTER_ADD:
            # once a layer, for both modes: the form's grouped products
            # by `ragged_dot` (the seeds give both cases the same
            # operands and leaves)
            with fm.pallas("off"):
                _SCATTER_ADD[name] = output_and_gradients(
                    lambda p, xs: scatter_add_layer(op, p, xs), probe,
                    params, inputs)
        y_want, want = _SCATTER_ADD[name]
    np.testing.assert_allclose(y, y_want, rtol=1e-5, atol=1e-5)
    overflow = float(overflow)
    assert (overflow > 0) == ("buffer_too_small" in name)
    # small groups: the narrowest row tile, and every `gmm` product of the
    # layer contracts in one tile (PR 53); nothing of it by `ragged_dot`
    walk = (128, 2 * op.matrices) if mode == "interpret" else (0, 0)
    assert op.traced_gauges() == {
        "executor.moe_sum_rows_ops": int(sums_by_kernel(name, mode)),
        "executor.moe_spread_rows_ops": int(sums_by_kernel(name, mode)),
        "executor.moe_row_tile": walk[0],
        "executor.moe_resident_weight_products": walk[1]}
    flat_got, tree = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree == tree_want and len(flat_got) == len(params) + n_inputs
    for path, a, b in zip(jax.tree_util.tree_leaves_with_path(got),
                          flat_got, flat_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                   err_msg=str(path[0]))
    assert any(np.abs(g).max() > 0 for g in got[1])
    if "e_bias" in params:   # enters the choice only
        assert not np.asarray(got[0]["e_bias"]).any()


# ---------------------------------------------------------------------------
# the share tests: a chip's share of a layer, summed over the chips,
# is the uncut layer


@pytest.fixture(scope="module")
def hidden():
    return jnp.asarray(np.random.RandomState(5).randn(2, 24, 32), jnp.float32)


def test_sixteen_expert_shares_add_up_to_the_uncut_layer(hidden):
    """16 chips with one expert each, the shared expert counted once,
    against the reference's uncut layer (all 16 experts held)."""
    kw = dict(n_experts=16, k=3, hidden_size=24, shared_width=48,
              routed_scaling=2.5, slot_slack=15.0)
    full = make_op(OperatorType.MOE_LAYER, kw, [hidden.shape])
    params = full.init_params(jax.random.PRNGKey(1))
    with fm.highest():
        want, shared = jax.jit(lambda x, p: (
            ref.experts(x, p, k=3, scaling=2.5, offset=0, operand="f32"),
            ref.relu2_mlp(x, p["ws_up"], p["ws_down"], "f32")))(
                hidden, params)
    np.testing.assert_allclose(run_op(full, params, [hidden]), want,
                               rtol=1e-4, atol=1e-4)
    parts = fm.expert_shares(kw, params, [hidden], 1, 16,
                             leaves=("w_up", "w_down"))
    total = sum(part - np.asarray(shared) for part in parts)
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-4)


def test_eight_head_shares_of_a_mamba_mixer_add_up(hidden):
    """8 chips with one head and one group each: the convolution is
    depthwise, dt, A, D are per head, a head reads its group's B and C,
    the gated norm's group is the share, W_out is linear."""
    h, p, g, n = 8, 4, 8, 8
    kw = dict(num_heads=h, head_dim=p, n_groups=g, state_size=n,
              chunk_size=8)
    full = make_op(OperatorType.SSM_MIXER, kw, [hidden.shape])
    params = full.init_params(jax.random.PRNGKey(2))
    with fm.highest():
        want = np.asarray(jax.jit(lambda x, w: ref.mamba2(
            x, w, heads=h, head_dim=p, groups=g, state=n, eps=1e-5,
            operand="f32"))(hidden, params))
    np.testing.assert_allclose(run_op(full, params, [hidden]), want,
                               rtol=1e-4, atol=1e-4)
    d_inner, gn = h * p, g * n
    cols = np.arange(2 * d_inner + 2 * gn + h)
    z, xs, bs, cs, dts = np.split(cols, np.cumsum(
        [d_inner, d_inner, gn, gn]))
    total = np.zeros_like(want)
    a_share = fm.op_program(make_op(
        OperatorType.SSM_MIXER, dict(kw, num_heads=1, n_groups=1),
        [hidden.shape]))
    for chip in range(8):
        head = slice(chip * p, (chip + 1) * p)
        grp = slice(chip * n, (chip + 1) * n)
        pick = np.concatenate([z[head], xs[head], bs[grp], cs[grp],
                               dts[chip:chip + 1]])
        conv = pick[p:-1] - d_inner
        share = dict(
            w_in=params["w_in"][:, pick], conv_w=params["conv_w"][:, conv],
            conv_b=params["conv_b"][conv],
            dt_bias=params["dt_bias"][chip:chip + 1],
            a_log=params["a_log"][chip:chip + 1], d=params["d"][chip:chip + 1],
            norm_scale=params["norm_scale"][head],
            w_out=params["w_out"][head])
        total += a_share(share, [hidden])
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_eight_head_shares_of_attention_add_up(hidden):
    """8 chips with 2 of 16 query heads and 1 of 8 key/value heads each,
    heads of 8 on a model width of 32 that they do not divide."""
    kw = dict(embed_dim=32, num_heads=16, num_kv_heads=8, head_dim=8,
              bias=False, causal=True)
    full = make_op(OperatorType.MULTIHEAD_ATTENTION, kw, [hidden.shape])
    assert full.head_dim == 8
    params = full.init_params(jax.random.PRNGKey(3))
    with fm.highest():
        want = np.asarray(jax.jit(lambda x, w: ref.attention(x, w, "f32"))(
            hidden, params))
    np.testing.assert_allclose(run_op(full, params, [hidden]), want,
                               rtol=1e-4, atol=1e-4)
    total = np.zeros_like(want)
    a_share = fm.op_program(make_op(
        OperatorType.MULTIHEAD_ATTENTION,
        dict(kw, num_heads=2, num_kv_heads=1), [hidden.shape]))
    for chip in range(8):
        q = slice(2 * chip, 2 * chip + 2)
        share = dict(wq=params["wq"][q], wo=params["wo"][q],
                     wk=params["wk"][chip:chip + 1],
                     wv=params["wv"][chip:chip + 1])
        total += a_share(share, [hidden])
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_a_sliced_vocabulary_gives_the_slice_of_the_logits(model):
    """Rows 0-15 of the head and of the embedding: the logits over the
    slice are the slice of the full head's logits (ids drawn from it)."""
    _, weights, ids, _ = model
    ids = jnp.asarray(ids) % 16
    kw = family.reference_kw(TINY)
    sliced = dict(weights,
                  embed_tokens={"kernel": weights["embed_tokens"]["kernel"][:16]},
                  lm_head={"kernel": weights["lm_head"]["kernel"][:, :16]})
    with fm.highest():
        forward = jax.jit(lambda w, ids: ref.forward(w, ids, **kw))
        full = forward(weights, ids)
        part = forward(sliced, ids)
    np.testing.assert_allclose(part, full[..., :16], rtol=1e-5, atol=1e-6)


def test_attention_head_dim_defaults_to_the_split_of_the_width(hidden):
    op = make_op(OperatorType.MULTIHEAD_ATTENTION,
                 dict(embed_dim=32, num_heads=4), [hidden.shape])
    assert op.head_dim == 8 and "head_dim" not in op.layer.properties


# ---------------------------------------------------------------------------
# the search sees the new ops like any other


def test_search_prices_and_places_the_new_ops(model):
    """The serialized graph states the ops' FLOPs, parameters, roles and
    interior; the native search offers replicated, batch-parallel and,
    for the scan, `_r` twins, and refuses `_r` for the expert layer
    (its counters leave the step beside its output)."""
    from flexflow_tpu.search import native
    from flexflow_tpu.search.unity import serialize_graph
    if not native.available():
        pytest.skip("native search unavailable")
    ff = model[0]
    nodes = serialize_graph(ff.executor.nodes)
    by_type = {}
    for n in nodes:
        by_type.setdefault(n["type"], n)
    scan, experts, attn = (by_type["SSM_MIXER"], by_type["MOE_LAYER"],
                           by_type["MULTIHEAD_ATTENTION"])
    assert scan["roles"] == [["sample", "other", "channel"]]
    assert scan["flops"] > 0 and scan["attrs"]["interior_bytes"] > 0
    assert set(scan["params"]) == {"w_in", "conv_w", "conv_b", "dt_bias",
                                   "a_log", "d", "norm_scale", "w_out"}
    assert experts["attrs"]["n_experts"] == 16
    assert experts["params"]["w_up"][0] == 4       # the experts held
    assert attn["attrs"]["head_dim"] == 8          # not 32 // 2
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
    scan_choices = {c["choice"] for c in ops["b0_mixer"]["candidates"]}
    assert {"rep", "dp"} <= {c.split("_")[0] for c in scan_choices}
    assert any(c.endswith("_r") for c in scan_choices), scan_choices
    moe_choices = {c["choice"] for c in ops["b1_mixer"]["candidates"]}
    assert not any(c.endswith("_r") for c in moe_choices)
    assert "counter_side_channel" in [
        r["reason"] for r in ops["b1_mixer"].get("remat_rejections") or []]


def test_router_and_scan_rates_stay_float32_in_the_compute_copy(model):
    ff = model[0]
    ex = ff.executor
    keep = ex._full_precision_leaves
    assert ("b1_mixer", "e_bias") in keep and ("b0_mixer", "a_log") in keep
    ex.compute_dtype, was = jnp.bfloat16, ex.compute_dtype
    try:
        copy = ex._cast_tree(ff.params)
    finally:
        ex.compute_dtype = was
    assert copy["b1_mixer"]["e_bias"].dtype == jnp.float32
    assert copy["b1_mixer"]["w_router"].dtype == jnp.float32
    assert copy["b1_mixer"]["w_up"].dtype == jnp.bfloat16
    assert copy["b0_mixer"]["dt_bias"].dtype == jnp.float32
    assert copy["b0_mixer"]["w_in"].dtype == jnp.bfloat16
    assert copy["lm_head"]["kernel"].dtype == jnp.bfloat16


def test_scopes_reach_the_compiled_steps_op_names(model):
    """The device trace's readers find the new ops by these names."""
    ff = model[0]
    scopes = family.scopes_of_compiled_step(ff, family.observed_sizes(ff))
    names = " ".join(scopes.values())
    for scope in ("jit(ssm_mixer)", "jit(ssd_scan)", "jit(moe_layer)",
                  "jit(moe_route)", "jit(moe_grouped_matmul)",
                  "jit(moe_shared)"):
        assert scope in names, scope


def test_the_compiled_step_moves_expert_rows_by_gathers_only(model):
    """No `scatter` under `jit(moe_layer)` in the compiled train step
    (rows go out and come back by gathers, forward and backward), and the
    `moe_combine` scope is in its `op_name`s, in both directions."""
    from flexflow_tpu.obs.inspect import scatters_in
    ff = model[0]
    (ids,), labels = family.make_data(TINY, 0)
    text = ff.executor.make_train_step().lower(
        ff.params, ff.opt_state, ff.state, ff._stage_inputs([ids]),
        ff._shard_batch(labels), jax.random.PRNGKey(0)).compile().as_text()
    assert scatters_in(text, "jit(moe_layer)") == []
    assert scatters_in(text)          # the embedding's backward is one
    for scope in ("/jvp(jit(moe_layer))/jit(moe_combine)/",
                  "/transpose(jvp(jit(moe_layer)))/jit(moe_combine)/"):
        assert scope in text, scope
