"""The learned-sparse-attention family (PR 54; `benchmarks/references/
keye.py` is the plain float32 reference, which shares no code with
`flexflow_tpu`): the model against the reference for logits, the
language model's loss, every layer's indexer loss and every gradient
leaf, with text positions and with three position streams that differ;
the kept sets against `lax.top_k`'s, rows with fewer keys than `topk`
and forced ties among them; which leaves learn from which loss; the
share test (the ranks' partial outputs and head sums add up to the uncut
layer's, every rank keeps the same keys); the refusals; remat; a
checkpoint round trip; the controls; the family's own check of the
kept keys; an epoch's counts past int32.

Tolerances: float32 on the CPU under matmul precision `highest`; the
program and the reference order their sums differently, so a logit
agrees to a few float32 units of its size, a loss to 2e-5 and a gradient
leaf to 2e-4 of its largest entry."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_model as fm
from benchmarks import harness as hs
from benchmarks.references import keye as ref
from family_model import OpContext, as_arrays
from flexflow_tpu.ops.attention import INDEXER_LEAVES

CELL = "keye_vl2_30b_a3b.s16384_b1.1chip"
SEQ = 48
TINY = dict(num_hidden_layers=2, vocab_size=64, hidden_size=32,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=4, num_local_experts=16, num_experts_per_tok=3,
            moe_intermediate_size=24, slot_slack=3.0, initializer_range=0.2,
            qk_norm_scale=4.0,
            sa_config=dict(indexer_num_heads=4, indexer_head_dim=16,
                           indexer_num_kv_heads=1, topk=12,
                           q_chunk_size=512, kv_chunk_size=512),
            rope_scaling=dict(mrope_section=[2, 3, 3]),
            seq=SEQ, batch=2, steps_per_epoch=1)
# an image-shaped grid of positions: 8 text tokens, a 5 x 6 grid whose
# temporal stream stands still while height and width walk the grid,
# then text again from the grid's largest position on
_GRID = [(8, 8 + i // 6, 8 + i % 6) for i in range(30)]
_TAIL = [(14 + i,) * 3 for i in range(SEQ - 38)]
GRID = tuple(zip(*([(i,) * 3 for i in range(8)] + _GRID + _TAIL)))
AFTER_ANOTHER = "text_built_after_another_family"
SIZES = {"text": TINY, "grid": dict(TINY, mrope_positions=GRID),
         AFTER_ANOTHER: TINY}


@pytest.fixture(scope="module")
def cell():
    return fm.load_cell(CELL, adam=None)


@pytest.fixture(scope="module")
def tinies(cell):
    """name -> the `fm.Tiny` of `SIZES[name]`, each built once."""
    return fm.built_by_name(cell, SIZES)


@pytest.fixture(scope="module")
def built(tinies):
    """name -> (s, xs, y, weights, ff) of `tinies(name)`."""
    def get(name):
        tiny = tinies(name)
        return tiny.s, tiny.xs, tiny.y, tiny.weights, tiny.ff
    return get


def program_losses_of(ff, xs, y):
    """p -> (language model's loss, [indexer loss a layer], logits)."""
    ex = ff.executor
    inputs = ff._stage_inputs([xs[0]])
    labels = ff._shard_batch(y)

    def losses(p):
        ctx = OpContext(training=True, rng=jax.random.PRNGKey(0),
                        compute_dtype=ex.compute_dtype, mesh=ex.mesh)
        values, _, aux = ex.run_graph(p, {}, inputs, ctx, counters={})
        logits = values[ex.final_ref]
        return ex._loss_value(logits, labels), list(aux), logits

    return losses


def reference_losses_of(family, s):
    def losses(w, ids, labels):
        logits, kl, _ = ref.forward_and_index_kl(w, ids,
                                                 **family.reference_kw(s))
        n = ids.shape[0] * ids.shape[1]
        logp = jax.nn.log_softmax(logits, -1)
        tok = jnp.take_along_axis(
            logp, labels[..., 0].astype(jnp.int32)[..., None], -1)[..., 0]
        return -jnp.sum(tok) / n, list(jnp.sum(kl, axis=(1, 2)) / n), logits

    return losses


def assert_leaves_close(got, want, atol=2e-4):
    assert set(got) == set(want)
    for name in want:
        assert set(got[name]) == set(want[name]), name
        for leaf, w in want[name].items():
            g = np.asarray(got[name][leaf], np.float32).reshape(w.shape)
            scale = max(float(np.max(np.abs(w))), 1e-6)
            np.testing.assert_allclose(g / scale, np.asarray(w) / scale,
                                       atol=atol, err_msg=f"{name}.{leaf}")


@pytest.fixture(scope="module")
def compared(built, cell):
    """name -> ((program's lm loss, index losses, logits, gradients of
    the step's loss), the reference's), computed once."""
    family = cell[0]
    cache = {}

    def total(fn):
        def step_loss(*args):
            lm, index, logits = fn(*args)
            return lm + sum(index), (lm, index, logits)
        return jax.jit(jax.value_and_grad(step_loss, has_aux=True))

    def get(name):
        if name not in cache:
            if name == AFTER_ANOTHER:
                # the SAME cut, built right after another family's module
                # has built its own through the helper
                import test_qwen3_next as another
                guid = fm.Layer._next_guid[0]
                fm.build_tiny(another.CELL, another.TINY)
                assert fm.Layer._next_guid[0] > guid
            s, xs, y, weights, ff = built(name)
            with fm.highest():
                (_, got), g_got = total(program_losses_of(ff, xs, y))(
                    ff.params)
                if name == AFTER_ANOTHER:   # the first build's reference
                    want = get("text")[1]
                else:
                    (_, want), g_want = total(reference_losses_of(family, s))(
                        as_arrays(weights), jnp.asarray(xs[0]),
                        jnp.asarray(y))
                    want += (g_want,)
            cache[name] = (got + (g_got,), want)
        return cache[name]

    return get


@pytest.mark.parametrize("name", ["text", "grid"])
def test_model_against_the_reference_logits_and_every_loss(name, compared):
    got, want = compared(name)
    np.testing.assert_allclose(got[2], want[2], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    assert len(got[1]) == len(want[1]) == 2
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=2e-5)
    assert min(float(v) for v in want[1]) > 1e-3     # the loss is there


@pytest.mark.parametrize("name", ["text", "grid", AFTER_ANOTHER])
def test_every_gradient_leaf_matches_the_reference(name, compared):
    """The third case (ROADMAP D0's order-dependent faults): two family
    modules built one after the other through `family_model` do not see
    each other's state. The process-wide `Layer._next_guid` has moved on
    and the leaves still pair by name; no "highest" is left behind by a
    context entered inside itself; the second build's step is the first
    build's to the bit."""
    got, want = compared(name)
    assert_leaves_close(got[3], jax.device_get(want[3]))
    assert jax.config.jax_default_matmul_precision is None
    if name == AFTER_ANOTHER:
        first = compared("text")[0]
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(first)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_three_streams_that_differ_change_the_result(compared):
    text, grid = compared("text")[0], compared("grid")[0]
    assert float(jnp.max(jnp.abs(text[2] - grid[2]))) > 1e-2


def test_which_leaves_learn_from_which_loss(built):
    """The indexer's leaves get their gradient from the indexers' loss
    alone, and no other leaf gets any from it."""
    _, xs, y, _, ff = built("text")
    program = program_losses_of(ff, xs, y)
    with fm.highest():
        from_lm = jax.jit(jax.grad(lambda p: program(p)[0]))(ff.params)
        from_index = jax.jit(jax.grad(lambda p: sum(program(p)[1])))(
            ff.params)
    seen = 0
    for name, leaves in from_lm.items():
        for leaf in leaves:
            lm = float(jnp.max(jnp.abs(from_lm[name][leaf])))
            index = float(jnp.max(jnp.abs(from_index[name][leaf])))
            if leaf in INDEXER_LEAVES:
                seen += 1
                assert lm == 0.0 and index > 0.0, (name, leaf, lm, index)
            else:
                assert index == 0.0, (name, leaf, index)
    assert seen == 2 * len(INDEXER_LEAVES)


_KEPT = {}


def _op_mask(ff, weights, x, layer=0):
    """The pairs the PROGRAM's op of `layer` keeps for the layer's
    input x [b, s, e] (the op's own indexer and selection)."""
    if (id(ff), layer) not in _KEPT:     # one program a model and layer
        op = next(n.op for n in ff.executor.nodes
                  if n.op.name == f"b{layer}_attn")
        ctx = OpContext(training=False, compute_dtype=jnp.float32)
        _KEPT[id(ff), layer] = jax.jit(
            lambda p, x: op._kept_keys(p, x, ctx, False)[3])
    params = {k: jnp.asarray(v) for k, v in
              weights[f"b{layer}_attn"].items()}
    return np.asarray(_KEPT[id(ff), layer](params, x)) != 0


@functools.lru_cache(maxsize=None)
def _reference_kept(kw_items, eps):
    """(w, ids) -> (the reference's kept pairs of layer 0, the layer's
    normed input), one program for both cases below."""
    kw = dict(kw_items)
    return jax.jit(lambda w, ids: (
        ref.kept_pairs(w, ids, 0, **kw)[0],
        ref.rms_norm(w["embed_tokens"]["kernel"][ids],
                     w["b0_norm"]["scale"], eps)))


@pytest.mark.parametrize("ties", [False, True], ids=["seeded", "ties"])
def test_the_kept_sets_are_the_references(built, cell, ties):
    """Exactly `lax.top_k`'s sets: rows with t + 1 < topk keep every
    causal key; with an indexer whose every score ties (head weights
    zero) a row keeps its FIRST topk keys."""
    family = cell[0]
    s, xs, _, weights, ff = built("text")
    if ties:
        weights = jax.tree.map(np.array, weights)
        weights["b0_attn"]["w_iw"][:] = 0.0
    w = as_arrays(weights)
    with fm.highest():
        want, x = _reference_kept(
            tuple(sorted(family.reference_kw(s).items())),
            s["rms_norm_eps"])(w, jnp.asarray(xs[0]))
        got = _op_mask(ff, weights, x)
    want = np.asarray(want)
    np.testing.assert_array_equal(got, want)
    topk = s["sa_config"]["topk"]
    rows = np.arange(SEQ)
    np.testing.assert_array_equal(got.sum(-1)[0], np.minimum(rows + 1, topk))
    np.testing.assert_array_equal(got[0, topk - 2, :topk - 1], True)
    if ties:
        np.testing.assert_array_equal(got[0, -1, :topk], True)
        assert not got[0, -1, topk:].any()


def test_the_share_test(built, cell):
    """One layer of the UNCUT model (4 query heads on 2 key/value heads,
    8 experts) against its ranks: two head ranks (a key/value head and
    its query heads each, the indexer whole) and two expert ranks. The
    ranks' partial attention outputs add up to the uncut layer's, every
    rank keeps the same keys, the ranks' un-normalised head sums add up
    to the whole model's, and the expert ranks' outputs to the uncut
    expert layer's."""
    family = cell[0]
    s, xs, _, weights, _ = built("text")
    w = as_arrays(weights)
    kw = family.reference_kw(s)
    akw = dict(ref.attention_kw(kw, SEQ), operand="f32", head_sums=True)
    def shares(w, ids):
        h = ref.rms_norm(w["embed_tokens"]["kernel"][ids],
                         w["b0_norm"]["scale"], s["rms_norm_eps"])
        p = w["b0_attn"]
        whole = ref.attention(h, p, **akw)
        ranks = []
        for r in range(2):
            heads, kv = slice(2 * r, 2 * r + 2), slice(r, r + 1)
            ranks.append(ref.attention(h, dict(
                p, wq=p["wq"][heads], wo=p["wo"][heads], wk=p["wk"][kv],
                wv=p["wv"][kv]), **akw))
        # the indexer's target is the normalised sum: a rank alone reads
        # its own heads' (what one chip does), the deployment the sum's
        g = ref.rms_norm(h, w["b0_post_norm"]["scale"], s["rms_norm_eps"])
        m = w["b0_mixer"]
        stacked = {k: jnp.concatenate([m[k], m[k][::-1]]) for k in
                   ("w_gate", "w_up", "w_down")}
        uncut = ref.experts(g, dict(m, **stacked), k=3, offset=0,
                            operand="f32")
        parts = [ref.experts(g, dict(m, **{
            k: stacked[k][4 * e:4 * e + 4] for k in stacked}), k=3,
            offset=4 * e, operand="f32") for e in range(2)]
        return whole, ranks, uncut, parts

    with fm.highest():      # one program
        whole, ranks, uncut, parts = jax.jit(shares)(w, jnp.asarray(xs[0]))
    np.testing.assert_allclose(sum(r[0] for r in ranks), whole[0],
                               rtol=1e-4, atol=1e-5)
    for r in ranks:
        np.testing.assert_array_equal(r[4], whole[4])
    np.testing.assert_allclose(sum(r[3] for r in ranks), whole[3],
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(sum(parts), uncut, rtol=1e-4, atol=1e-5)


def test_the_attention_op_refuses_what_it_has_not():
    from flexflow_tpu import FFConfig, FFModel
    for extra in (dict(window=4), dict(block_diffusion=(8, 4), causal=False),
                  dict(differential=True), dict(gate=True),
                  dict(causal=False)):
        ff = FFModel(FFConfig(batch_size=2))
        x = ff.create_tensor((2, 16, 32), name="x")
        kw = dict(dict(causal=True, bias=False, sparse_index=(4, 8, 4)),
                  **extra)
        with pytest.raises(ValueError, match="sparse_index"):
            ff.multihead_attention(x, x, x, 32, 4, **kw)
            ff._materialize_nodes()


def test_serving_refuses_the_family(built):
    from flexflow_tpu.serve import kv_cache
    _, _, _, _, ff = built("text")
    with pytest.raises(NotImplementedError, match="learned sparse"):
        kv_cache.init_kv_cache(ff)


def test_the_search_prices_the_op_and_takes_no_remat_twin(built):
    """The op's price holds the indexer's products and the mask as a
    saved activation; its side channel (the loss, the counts) keeps a
    remat twin away."""
    from flexflow_tpu.search.unity import serialize_graph
    _, _, _, _, ff = built("text")
    nodes = {n["name"]: n for n in serialize_graph(ff.executor.nodes)}
    op = next(n.op for n in ff.executor.nodes if n.op.name == "b0_attn")
    attrs = nodes["b0_attn"]["attrs"]
    assert attrs["side_counters"] == 1
    assert attrs["interior_bytes"] == op.sparse_saved_bytes() >= 2 * SEQ ** 2
    plain = type(op).flops.__wrapped__(op) if hasattr(
        type(op).flops, "__wrapped__") else None
    hi, di, _ = op.sparse_index
    pairs = 2 * SEQ * (SEQ + 1) // 2
    assert nodes["b0_attn"]["flops"] > 2 * hi * di * pairs * 4
    assert plain is None
    assert not [name for name, st in ff.strategy.items()
                if str(getattr(st, "choice", "")).endswith("_r")
                and "attn" in str(name)]


def test_fflint_knows_the_family(built):
    from flexflow_tpu import lint_model
    _, _, _, _, ff = built("text")
    report = lint_model(ff)
    assert not [d for d in report.diagnostics
                if d.severity.name == "ERROR"], report.diagnostics


def test_a_checkpoint_round_trip_keeps_the_families_leaves(built, tmp_path):
    s, xs, _, _, ff = built("grid")
    before = np.asarray(ff.predict([xs[0][:s["batch"]]]))
    path = str(tmp_path / "ckpt")
    ff.save_checkpoint(path)
    kept = ff.get_parameter("b0_attn", "w_iq").copy()
    ff.set_parameter("b0_attn", -kept, "w_iq")
    assert not np.array_equal(
        np.asarray(ff.predict([xs[0][:s["batch"]]])), before)
    ff.load_checkpoint(path)
    np.testing.assert_array_equal(ff.get_parameter("b0_attn", "w_iq"), kept)
    np.testing.assert_array_equal(
        np.asarray(ff.predict([xs[0][:s["batch"]]])), before)


# ---------------------------------------------------------------------------
# the controls, each built through a `program_*` override: another result

def _control(tinies, **control):
    tiny = tinies("text")
    return (fm.control_model(tiny, dict(TINY, **control))[0], tiny.xs,
            tiny.y)


def test_every_key_kept_is_another_model(tinies, compared):
    ff, xs, _ = _control(tinies, program_topk=SEQ)
    got = np.asarray(ff.predict([xs[0]]), np.float32)
    want = np.asarray(compared("text")[1][2])
    assert hs.prediction_errors(got, want, False)["nrmse"] > 0.05


def test_without_the_indexers_loss_the_step_loss_is_another(tinies,
                                                            compared):
    ff, xs, y = _control(tinies, program_index_loss=False)
    lm, index, _ = jax.jit(program_losses_of(ff, xs, y))(ff.params)
    assert index == []
    want = compared("text")[1]
    np.testing.assert_allclose(lm, want[0], rtol=2e-5)
    assert float(sum(want[1])) > 1e-3 * float(lm)


def test_the_family_holds_the_indexer_to_the_references_keys(cell, built,
                                                             tinies):
    """`kept_pairs_that_differ`, the number behind the family's
    `extra_checks` row: none as the cell states the program; an indexer
    whose products read bfloat16 operands keeps other keys."""
    family = cell[0]
    s, _, _, _, ff = built("text")
    assert family.kept_pairs_that_differ(ff, s) == 0
    rows = dict((name, ok) for name, ok, _ in family.extra_checks(
        ff, s, 1, False))
    assert rows["indexer_keeps_the_references_keys"]
    low, _, _ = _control(tinies, program_indexer_dtype="bfloat16")
    assert family.kept_pairs_that_differ(low, s) > 0


def test_an_epochs_counts_of_pairs_pass_what_int32_holds(monkeypatch):
    """The ops' integer counts leave a step as they are, one element an
    op, and an epoch's are added up on the host: four steps of two ops
    at the cell's 272,629,760 visited pairs an op are 2,181,038,080, past
    2^31 - 1, and read exactly; a float counter beside them adds up as
    ever."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.ops.linear import Linear
    forward = Linear.forward
    an_op = 272_629_760

    def counted(self, params, inputs, ctx):
        out = forward(self, params, inputs, ctx)
        self._counters = {
            "attention/visited_pairs": ("sum", jnp.int32(an_op)),
            "attention/a_float": ("sum", jnp.float32(0.5))}
        return out

    monkeypatch.setattr(Linear, "forward", counted)
    ff = FFModel(FFConfig(batch_size=2))
    t = ff.dense(ff.create_tensor((2, 8)), 8)
    ff.softmax(ff.dense(t, 4))
    ff.compile(SGDOptimizer(lr=0.1), LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               [])
    ff.fit(np.zeros((8, 8), np.float32), np.zeros((8, 1), np.int32),
           epochs=1, verbose=False)
    assert ff.op_counters["attention/visited_pairs"] == 4 * 2 * an_op > 2 ** 31
    assert ff.op_counters["attention/a_float"] == 4 * 2 * 0.5
