"""The kernels of learned sparse attention (PR 54) in interpret mode
against their `jax.numpy` forms (`flexflow_tpu/ops/sparse_index.py`), at
1,152 positions: no multiple of the selection's 512-key chunk, of the
loss kernel's 256-key tile or of a 1,024-row flash block, so every
kernel runs blocks of 128 and ragged loop counts. `index_select` (the
exact selection: `lax.top_k`'s set, rows with fewer keys than `topk`,
forced ties), the chunk-loop flash kernels with a mask operand (forward,
backward, a tile their summary lets them skip), `index_kl` (the loss and
its three gradients), and the attention op's two routes against each
other."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops import sparse_index as si

S, B, HI, TOPK, H, HK = 1152, 1, 4, 300, 2, 1
HIGHEST = jax.lax.Precision.HIGHEST


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")


@pytest.fixture(scope="module")
def operands():
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    qi = jax.random.normal(ks[0], (B, S, HI * 64))
    ki = jax.random.normal(ks[1], (B, S, 64))
    w = jax.random.normal(ks[2], (B, S, HI)) * 0.1
    q = jax.random.normal(ks[3], (B, S, H * 128))
    k = jax.random.normal(ks[4], (B, S, HK * 128))
    v = jax.random.normal(ks[5], (B, S, HK * 128))
    return qi, ki, w, q, k, v


@pytest.mark.parametrize("precision", [HIGHEST, pk.BF16_3X],
                         ids=["highest", "bf16_3x"])
@pytest.mark.parametrize("case", ["seeded", "ties", "quantised"])
def test_index_select_keeps_top_ks_set(operands, case, precision):
    qi, ki, w, *_ = operands
    if case == "ties":          # every score 0: a row's FIRST keys
        w = jnp.zeros_like(w)
    if case == "quantised":     # a few distinct scores, many ties each
        qi, ki = jnp.round(qi), jnp.round(ki)
        w = jnp.round(w * 10) / 8
    mask, lse, counts = jax.jit(lambda q, k, w: pk.index_select(
        q, k, w, TOPK, precision))(qi, ki, w)

    @jax.jit    # the plain form, one program: scores, the set, its lse
    def plain(qi, ki, w):
        scores = si.index_scores(qi, ki, w)
        want = si.select(scores, TOPK)
        return want, si.kept_lse(scores, want)

    want, want_lse = plain(qi, ki, w)
    if case == "seeded" and precision == pk.BF16_3X:
        # three bfloat16 products: 2^-16 of a product; a key at a row's
        # threshold may change sides
        assert (np.asarray(mask) != np.asarray(want)).sum() <= 4
    else:
        np.testing.assert_array_equal(np.asarray(mask), np.asarray(want))
    # the pairs a tile keeps, counted by the kernel
    r, k = pk.index_blocks(S)
    np.testing.assert_array_equal(counts, np.asarray(mask).reshape(
        B, S // r, r, S // k, k).sum((2, 4)))
    rows = np.asarray(mask).sum(-1)[0]
    np.testing.assert_array_equal(rows, np.minimum(np.arange(S) + 1, TOPK))
    np.testing.assert_allclose(lse, want_lse, atol=1e-4)
    if case == "ties":
        assert np.asarray(mask)[0, -1, :TOPK].all()


def _scores_in_a_kernel(q, kk, w, precision):
    """`_index_scores` of one tile, run as a kernel in interpret mode."""
    def kernel(q_ref, kk_ref, w_ref, out_ref):
        out_ref[...] = pk._index_scores(q_ref[...], kk_ref[...], w_ref[...],
                                        w.shape[1], precision)

    return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
        (q.shape[0], kk.shape[0]), jnp.float32), interpret=True)(q, kk, w)


def test_index_scores_two_passes_hold_the_three_products():
    """``BF16_3X`` (PR 55): a head's low part rides in the other head's
    lanes of the high . high pass. At a tile of two lane blocks (four
    heads, both parities), scores of size 35 and more: the three
    bfloat16 products formed apart and summed in float32 to float32's
    own rounding, `highest` to 2^-15."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (128, 4 * 64))
    k = jax.random.normal(ks[1], (256, 64))
    w = jax.random.uniform(ks[2], (128, 4), minval=-1.5, maxval=1.5)
    kk = jnp.concatenate([k, k], axis=-1)
    got = _scores_in_a_kernel(q, kk, w, pk.BF16_3X)

    def dot(a, b):
        return jnp.einsum("thd,sd->hts", a.reshape(128, 4, 64), b,
                          preferred_element_type=jnp.float32)

    (qh, ql), (kh, kl) = pk._split(q), pk._split(k)
    apart = dot(qh, kh) + dot(qh, kl) + dot(ql, kh)
    want = jnp.einsum("hts,th->ts", jnp.maximum(apart, 0.0), w,
                      precision=HIGHEST)
    top = float(jnp.max(jnp.abs(want)))
    assert top > 35
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-6 * top
    exact = _scores_in_a_kernel(q, kk, w, HIGHEST)
    assert float(jnp.max(jnp.abs(got - exact))) <= 2.0 ** -15 * top
    assert float(jnp.max(jnp.abs(got - exact))) > 0     # not `highest`


def _inside(eqn, name: str) -> int:
    """The equations of primitive ``name`` in the jaxprs ``eqn`` holds
    (a kernel's body, a loop's), at any depth."""
    total = 0
    for value in eqn.params.values():
        for sub in value if isinstance(value, (list, tuple)) else [value]:
            sub = getattr(sub, "jaxpr", sub)
            for inner in getattr(sub, "eqns", ()):
                total += (inner.primitive.name == name) + _inside(inner, name)
    return total


@pytest.mark.parametrize("precision,dtype,passes", [
    (pk.BF16_3X, jnp.float32, 2), (HIGHEST, jnp.float32, 1),
    (None, jnp.bfloat16, 1)], ids=["bf16_3x", "highest", "bf16"])
def test_index_select_issues_two_products_a_head_for_float32(
        operands, precision, dtype, passes):
    """The kernel's one chunk loop with products holds 2 x heads MXU
    products under ``BF16_3X`` (three would be the form before PR 55)
    and heads in one pass; the left-hand tiles' lane work (a roll a lane
    block, the selects that keep a head's lanes) is outside every loop."""
    qi, ki, w, *_ = operands
    jaxpr = jax.make_jaxpr(lambda q, k, w: pk.index_select(
        q, k, w, TOPK, precision))(qi.astype(dtype), ki.astype(dtype), w)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert _inside(call, "dot_general") == passes * HI
    (scores,) = [e for e in call.params["jaxpr"].eqns
                 if e.primitive.name in ("while", "scan")
                 and _inside(e, "dot_general")]
    assert _inside(scores, "dot_general") == passes * HI
    assert _inside(scores, "roll") == 0
    assert _inside(call, "roll") == (HI // 2 if passes == 2 else 0)
    # the score loop selects for the causal rule alone (the sortable
    # form, the stored key, the running top), never a head's lanes
    assert _inside(scores, "select_n") <= 4


def test_index_select_in_bfloat16_operands_agrees_with_its_own_form(
        operands):
    qi, ki, w, *_ = (t.astype(jnp.bfloat16) for t in operands)
    mask, _, _ = pk.index_select(qi, ki, w.astype(jnp.float32), TOPK,
                                   None)
    want = si.select(si.index_scores(qi, ki, w.astype(jnp.float32), None),
                     TOPK)
    # the products accumulate in another order: a key at a row's
    # threshold may change sides
    assert (np.asarray(mask) != np.asarray(want)).sum() < 1e-4 * want.sum()


@pytest.fixture(scope="module")
def mask(operands):
    qi, ki, w, *_ = operands
    kept = si.select(si.index_scores(qi, ki, w), TOPK)
    # a tile no query of which keeps a key: the kernels skip it
    return kept.at[:, 512:640, 128:256].set(0)


def test_masked_flash_forward_and_backward(operands, mask, monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    _, _, _, q, k, v = operands
    (rows, keys), _ = pk.masked_tiles(S)
    assert int((pk.mask_tiles_any(mask, rows, keys)[0, 4, 1])) == 0

    def loss(core):
        def fn(q, k, v):
            o, lse = core(q, k, v)
            return jnp.sum(o * jnp.cos(o)), lse
        return jax.value_and_grad(fn, argnums=(0, 1, 2), has_aux=True)

    (got, lse_k), g_k = jax.jit(loss(
        lambda q, k, v: pk.flash_attention_masked(q, k, v, mask, H, HK)))(
            q, k, v)
    (want, lse_j), g_j = jax.jit(loss(
        lambda q, k, v: si.masked_attention(q, k, v, mask, H, HK)))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(lse_k[:, :, 0, :].transpose(0, 2, 1), lse_j,
                               atol=1e-5)
    for a, b in zip(g_k, g_j):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_index_kl_value_and_gradients(operands, mask, monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    qi, ki, w, q, k, v = operands

    @jax.jit
    def by_kernel(qi, ki, w, q, k, v, mask):
        scores = si.index_scores(qi, ki, w)
        lse_i = si.kept_lse(scores, mask)
        _, lse = si.masked_attention(q, k, v, mask, H, HK)
        return lse, pk.index_kl(qi, ki, w, lse_i, mask, q, k, lse, H,
                                1.0 / (B * S))

    lse, (kl, dq, dw, dk) = by_kernel(qi, ki, w, q, k, v, mask)

    def loss(qi, ki, w):
        return si.index_kl(si.index_scores(qi, ki, w), mask,
                           si.head_sum(q, k, lse, mask, H, HK))

    want, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        qi, ki, w)
    np.testing.assert_allclose(np.sum(kl) / (B * S), want, rtol=1e-5)
    for got, g in zip((dq, dk, dw), grads):
        scale = float(np.max(np.abs(g)))
        np.testing.assert_allclose(got / scale, g / scale, atol=2e-5)


def _one_op():
    """(op, params, input) of one attention op with an indexer."""
    from flexflow_tpu import FFConfig, FFModel

    ff = FFModel(FFConfig(batch_size=B))
    x = ff.create_tensor((B, S, 64), name="x")
    ff.multihead_attention(x, x, x, 64, 2, bias=False, causal=True,
                           num_kv_heads=1, rope=True, rope_theta=1e7,
                           head_dim=128, qk_norm=True,
                           sparse_index=(2, 64, 200),
                           mrope_section=(16, 24, 24), name="attn")
    nodes, _, _ = ff._materialize_nodes()
    op = nodes[-1].op
    params = op.init_params(jax.random.PRNGKey(0))
    params = jax.tree.map(lambda t: 4.0 * t, params)
    return op, params, jax.random.normal(jax.random.PRNGKey(1), (B, S, 64))


def _op_terms(op, params, xs):
    """d [the output's term, the indexer's loss] / d (leaves, input),
    with (y, the loss, the kept pairs counted)."""
    from flexflow_tpu.ops.base import OpContext

    def fn(params, xs):
        ctx = OpContext(training=True, compute_dtype=jnp.float32)
        (y,) = op.forward(params, [xs, xs, xs], ctx)
        aux, counters = op._aux_loss, op._counters
        op._aux_loss = op._counters = None
        return jnp.stack([jnp.sum(y * jnp.sin(y)), aux]), (
            y, aux, counters["attention/selected_pairs"][1])

    # one program a call (`fn` is new each time: the mode in force)
    return jax.jit(jax.jacrev(fn, argnums=(0, 1), has_aux=True))(params, xs)


@pytest.fixture(scope="module")
def on_the_kernels():
    """(op, params, input, `_op_terms` on the kernels' route,
    interpreted), computed once for the two tests below."""
    op, params, xs = _one_op()
    before = os.environ.get("FLEXFLOW_TPU_PALLAS")
    os.environ["FLEXFLOW_TPU_PALLAS"] = "interpret"
    try:
        terms = _op_terms(op, params, xs)
    finally:
        if before is None:
            del os.environ["FLEXFLOW_TPU_PALLAS"]
        else:
            os.environ["FLEXFLOW_TPU_PALLAS"] = before
    assert op._route.core == "flash" and op._route.sparse_kernels
    return op, params, xs, terms


def test_the_op_runs_its_kernels_where_pallas_is_on(on_the_kernels,
                                                    monkeypatch):
    """One op, both routes, forward and the gradients of output and
    loss: the kernels (interpreted) against `ops/sparse_index.py`."""
    op, params, xs, (g_k, (y_k, aux_k, n_k)) = on_the_kernels
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    g_j, (y_j, aux_j, n_j) = _op_terms(op, params, xs)
    assert op._route.core == "einsum"
    assert int(n_k) == int(n_j) == 200 * 201 // 2 + (S - 200) * 200
    np.testing.assert_allclose(y_k, y_j, atol=2e-4)
    np.testing.assert_allclose(aux_k, aux_j, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(g_k), jax.tree.leaves(g_j)):
        a, b = a[0] + a[1], b[0] + b[1]     # of output + loss
        scale = max(float(np.max(np.abs(b))), 1e-6)
        np.testing.assert_allclose(a / scale, b / scale, atol=5e-4)


def test_which_leaves_learn_from_which_loss_on_the_kernel_route(
        on_the_kernels):
    """On the kernels' route as on the other (`tests/test_keye.py`): the
    indexer's loss reaches the indexer's leaves and nothing else, not
    the op's input either (the indexer reads a DETACHED copy), and the
    op's output reaches every other leaf and none of the indexer's."""
    from flexflow_tpu.ops.attention import INDEXER_LEAVES
    (g_params, g_x), _ = on_the_kernels[3]
    assert float(jnp.max(jnp.abs(g_x[1]))) == 0.0 < float(
        jnp.max(jnp.abs(g_x[0])))
    for leaf, g in g_params.items():
        out, index = (float(jnp.max(jnp.abs(g[i]))) for i in (0, 1))
        if leaf in INDEXER_LEAVES:
            assert out == 0.0 < index, (leaf, out, index)
        else:
            assert index == 0.0 < out, (leaf, out, index)


def test_the_unmasked_kernels_take_no_mask_operand():
    """Without a mask the chunk-loop kernels are called as before: three
    operands forward, seven backward, no byte of a mask, no summary."""
    q = jnp.zeros((1, 2048, 128), jnp.bfloat16)

    def fwd_bwd(q):
        return jax.grad(lambda q: pk._flash(
            q, q, q, 1, True, True).astype(jnp.float32).sum())(q)

    text = str(jax.make_jaxpr(fwd_bwd)(q))
    assert "pallas_call" in text
    assert "int8" not in text and "i8[" not in text and "smem" not in text
