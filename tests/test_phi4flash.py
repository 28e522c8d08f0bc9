"""The decoder-hybrid-decoder family (PR 52; `benchmarks/references/
phi4flash.py` is the plain float32 reference, which shares no code with
`flexflow_tpu`): the Mamba-1 mixer and its selective-scan kernel, tensors
one op makes for other layers to read (a scan's memory, an attention op's
projected keys and values), differential attention, the gated memory
unit; the model against the reference for logits, three losses and every
gradient leaf, whole (all five kinds of layer by the published rule) and
as a stage that keeps its published indices; the producers' gradients as
the sum over their readers; what float32 is stated for; the refusals;
the search's view of the exported tensors; the three controls.

Tolerances: float32 on the CPU under matmul precision `highest`; the
program and the reference order their sums differently (heads side by
side against heads first, two softmax maps at heads of twice the width
with half the lanes zero), so a logit agrees to a few float32 units of
its size (rtol 2e-4, atol 2e-5), a loss to 2e-5 and a gradient leaf to
2e-4 of its largest entry. The scan's state or lambda's vectors in
bfloat16 (7e-3 a rounding) miss the first by ten times and more
(`test_what_is_stated_float32_is_float32`)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_model as fm
from benchmarks import harness as hs
from benchmarks.references import phi4flash as ref
from family_model import OpContext, as_arrays
from flexflow_tpu import FFConfig
from flexflow_tpu.models import DecoderConfig, create_decoder
from flexflow_tpu.models.decoder import sambay_pattern
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops import ssm
from flexflow_tpu.ops.base import exported_reads
from one_program import output_and_gradients

CELL = "phi4_mini_flash.s8192_b1.1chip"
# every width small, the structure whole
WIDTHS = dict(vocab_size=64, hidden_size=32, num_attention_heads=8,
              num_key_value_heads=4, head_dim=8, intermediate_size=48,
              sliding_window=8, mamba_d_state=4, mamba_dt_rank=4,
              initializer_range=0.2, embedding_std=0.2, seq=32, batch=2,
              steps_per_epoch=1)
# all five kinds by the published rule: M S M S M' F G C
WHOLE = dict(WIDTHS, num_hidden_layers=8, first_layer_index=0,
             published_num_hidden_layers=8)
# the cell's form: published layers 16-19 of 32
STAGE = dict(WIDTHS, num_hidden_layers=4, first_layer_index=16,
             published_num_hidden_layers=32)
# two cross layers and two units behind their producers
DEEP = dict(WIDTHS, num_hidden_layers=12, first_layer_index=0,
            published_num_hidden_layers=12)
SIZES = {"whole": WHOLE, "stage": STAGE, "deep": DEEP}


@pytest.fixture(scope="module")
def cell():
    # the cell's own rate (1e-7: the three steps barely move the leaves)
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


def program_loss_of(ff, xs, y, gated=()):
    """The program's loss as a function of its parameters and, with
    ``gated`` (names of ops that read an exported tensor), of one gate a
    name, 0 or 1: at 0 that reader takes the tensor under
    `stop_gradient`, so no cotangent flows back through its edge (the
    forward's values are the same: g v + (1 - g) v)."""
    ex = ff.executor
    inputs = ff._stage_inputs([xs[0]])
    labels = ff._shard_batch(y)
    exported, _ = exported_reads(ex.nodes)

    def loss(p, gates=None):
        ctx = OpContext(training=True, rng=jax.random.PRNGKey(0),
                        compute_dtype=ex.compute_dtype, mesh=ex.mesh)
        values = {}
        for node in ex.nodes:
            held = {}
            if node.op.name in gated:
                g = gates[gated.index(node.op.name)]
                for ref_ in node.input_refs:
                    key = tuple(ref_[1:3])
                    if ref_[0] == "op" and key in exported:
                        held[key] = v = values[key]
                        values[key] = g * v + (1 - g) * \
                            jax.lax.stop_gradient(v)
            ex._run_nodes([node], p, {}, inputs, values, {}, [], ctx)
            values.update(held)
        return ex._loss_value(values[ex.final_ref], labels)

    return loss


def assert_leaves_close(got, want, atol=2e-4):
    """Leaf by leaf, to ``atol`` of the leaf's largest entry. The keys'
    bias is the exception: it adds q . b_k to every score of a query,
    which a softmax does not see, so its gradient is zero but for
    rounding, on both sides."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bk']"):
            values = float(jnp.max(jnp.abs(want[path[0].key]["bv"])))
            assert float(jnp.max(jnp.abs(g))) < 1e-4 * values, name
            assert float(jnp.max(jnp.abs(w))) < 1e-4 * values, name
            continue
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=atol,
                                   err_msg=name)


# ---------------------------------------------------------------------------
# the rule, the builder, the refusals


def test_the_published_rule_names_the_layers():
    def pattern(**kw):
        return sambay_pattern(DecoderConfig(mb_per_layer=2, **kw))

    assert pattern(num_hidden_layers=8) == "mwmwyfgc"
    assert pattern(num_hidden_layers=32) == "mw" * 8 + "yf" + "gc" * 7
    assert pattern(num_hidden_layers=4, first_layer_index=16,
                   published_num_hidden_layers=32) == "yfgc"
    assert pattern(num_hidden_layers=4, first_layer_index=0,
                   published_num_hidden_layers=32) == "mwmw"
    assert [ref.layer_kind(i, 32) for i in (0, 1, 15, 16, 17, 18, 19, 31)] \
        == ["mamba", "window", "window", "mamba", "full", "gated_memory",
            "cross", "cross"]
    for bad in (dict(num_hidden_layers=6),              # L / 2 odd
                dict(num_hidden_layers=8, first_layer_index=28,
                     published_num_hidden_layers=32),   # past the end
                dict(num_hidden_layers=0)):
        with pytest.raises(ValueError, match="published depth"):
            pattern(**bad)
    with pytest.raises(ValueError, match="written for 2"):
        sambay_pattern(DecoderConfig(mb_per_layer=3, num_hidden_layers=8))


@pytest.mark.parametrize("first,reads,maker", [
    (18, "the memory", 16),                    # a unit without layer 16
    (19, "the shared keys and values", 17)])   # a cross layer without 17
def test_a_stage_that_reads_what_no_layer_in_it_makes_is_refused(
        first, reads, maker):
    with pytest.raises(ValueError) as e:
        create_decoder(DecoderConfig(
            mb_per_layer=2, num_hidden_layers=2, first_layer_index=first,
            published_num_hidden_layers=32, num_attention_heads=4,
            num_key_value_heads=2, tie_word_embeddings=True))
    said = str(e.value)
    assert f"layer {first} reads {reads}" in said
    assert f"of layer {maker}, which this stage (layers {first}.." \
        f"{first + 1} of 32) does not hold" in said
    assert "exported tensors do not cross stages" in said


def test_create_decoder_builds_producers_and_readers(built, cell):
    family, _, _ = cell
    s, _, _, weights, ff = built("whole")
    assert s["kinds"] == ["mamba", "window", "mamba", "window", "mamba",
                          "full", "gated_memory", "cross"]
    ops = {n.op.name: n.op for n in ff.executor.nodes}
    # the leaves are the reference's tree, name for name
    assert {k: {p: tuple(v.shape) for p, v in leaves.items()}
            for k, leaves in ff.params.items()} == {
        k: {p: tuple(v.shape) for p, v in leaves.items()}
        for k, leaves in weights.items()}
    assert family.parameters(s) == sum(
        int(x.size) for x in jax.tree.leaves(ff.params))
    assert all(ops[n].params_elems() == sum(
        int(v.size) for v in weights[n].values()) for n in (
            "b4_mixer", "b5_attn", "b7_attn", "b1_attn"))
    # ONE producer of each tensor, second (and third) outputs
    assert [ops[f"b{i}_mixer"].exports for i in (0, 2, 4)] == [0, 0, 1]
    assert [ops[f"b{i}_attn"].exports for i in (1, 3, 5, 7)] == [0, 0, 2, 0]
    assert ops["b4_mixer"].output_shapes == [(2, 32, 32), (2, 32, 64)]
    assert ops["b5_attn"].output_shapes == [(2, 32, 32)] + [(2, 32, 32)] * 2
    by_guid = {n.guid: n for n in ff.executor.nodes}
    refs = {n.op.name: [(by_guid[r[1]].op.name, r[2]) for r in n.input_refs
                        if r[0] == "op"] for n in ff.executor.nodes}
    assert refs["b6_memory_gated"][1] == ("b4_mixer", 1)
    assert refs["b7_attn"][1:] == [("b5_attn", 1), ("b5_attn", 2)]
    cross, full, window = ops["b7_attn"], ops["b5_attn"], ops["b1_attn"]
    assert cross.kv_given and not full.kv_given and full.export_kv
    assert all(op.differential and op.causal and op.use_bias
               and op.qkv_bias and not op.rope
               for op in (cross, full, window))
    assert (window.window, full.window, cross.window) == (8, 0, 0)
    assert "wk" not in ff.params["b7_attn"] and "bk" not in \
        ff.params["b7_attn"]
    assert [ops[f"b{i}_attn"].lambda_init for i in (1, 5)] == [
        pytest.approx(0.8 - 0.6 * math.exp(-0.3 * i)) for i in (1, 5)]
    assert ops["b6_memory_in_proj"].layer.properties["scope"] == \
        "gated_memory"
    assert ff.executor.part_of_node(
        ff.executor._by_name["b6_memory_gated"]) == "gated_memory"
    assert ff.executor.part_of_node(
        ff.executor._by_name["b4_mixer"]) == "mamba"
    assert ff.executor.part_of_node(
        ff.executor._by_name["b7_attn"]) == "attention"
    assert type(ops["final_ln"]).__name__ == "LayerNorm"
    assert ops["lm_head"].tied_params == {"kernel": ("embed_tokens",
                                                     "kernel")}
    # searched, every op with a choice
    assert ff.search_seconds is not None and ff.strategy
    assert all(ff.strategy[n.op.guid].choice for n in ff.executor.nodes)
    gauges = ff.executor.traced_gauges()
    assert gauges["executor.shared_tensors"] == 3
    assert gauges["executor.shared_tensor_readers"] == 3
    assert gauges["ssm/selective_scan_ops"] == 3
    assert gauges["executor.layer_applications"] == 7


# ---------------------------------------------------------------------------
# against the reference


@pytest.mark.parametrize("name", ["whole", "stage"])
def test_model_against_the_reference_output_and_three_losses(
        name, built, cell):
    family, config, traffic = cell
    s, xs, y, weights, ff = built(name)
    system, _ = hs.system_side(ff, xs, y, s["batch"])
    want = hs.reference_side(family, weights, s, traffic, config, xs, y,
                             s["batch"])
    assert system["preds"].shape == (s["batch"], s["seq"], s["vocab_size"])
    np.testing.assert_allclose(system["preds"], want["preds"], rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(system["losses"], want["losses"], rtol=2e-5)
    counters = ff.op_counters
    assert counters["executor.loss_own_vjp"] == 1
    attention = [j for j, k in enumerate(s["kinds"])
                 if k in ("window", "full", "cross")]
    for j in attention:
        # lambda as the reference's arithmetic gives it, at the
        # PUBLISHED index (the steps' alpha is 1e-7: the leaves barely
        # moved)
        p, depth = weights[f"b{j}_attn"], s["first_layer_index"] + j
        lam = (math.exp(float(np.sum(p["lambda_q1"] * p["lambda_k1"])))
               - math.exp(float(np.sum(p["lambda_q2"] * p["lambda_k2"])))
               + 0.8 - 0.6 * math.exp(-0.3 * depth))
        assert counters[f"attention/diff_lambda_b{j}"] == pytest.approx(
            lam, rel=1e-4)
    if name == "stage":
        assert [ref.lambda_init(i) for i in (17, 19)] == [
            pytest.approx(0.79634, abs=1e-5),
            pytest.approx(0.79799, abs=1e-5)]
        assert counters["executor.shared_tensor_readers"] == 3
        assert counters["executor.shared_tensors"] == 3


@pytest.fixture(scope="module")
def gradients(tinies):
    """name -> (the weights as arrays, the program's gradient of its
    loss, the reference's of its own) on the whole epoch's batch."""
    cache = {}

    def get(name):
        if name not in cache:
            tiny = tinies(name)
            params = as_arrays(tiny.weights)
            with fm.highest():
                got = jax.jit(jax.grad(program_loss_of(
                    tiny.ff, tiny.xs, tiny.y)))(params)
            cache[name] = (params, got, fm.reference_gradient(tiny))
        return cache[name]

    return get


@pytest.mark.parametrize("name", ["whole", "stage"])
def test_every_gradient_leaf_matches_the_reference(name, gradients):
    _, got, want = gradients(name)
    assert_leaves_close(got, want)
    assert len(jax.tree.leaves(got)) == {
        # the table, the final norm's two; a layer's two norms (4) and
        # MLP (2); a Mamba mixer 9, window / full attention 13, a unit
        # 2, cross-attention 9
        "whole": 3 + 8 * 6 + 3 * 9 + 3 * 13 + 2 + 9,
        "stage": 3 + 4 * 6 + 9 + 13 + 2 + 9}[name]


def test_a_producers_gradient_is_the_sum_over_its_readers(built, tinies):
    """Depth 12: layer 6's scan output is read by the units of layers 8
    and 10, layer 7's keys and values by the cross layers 9 and 11. With
    the cotangent of an exported tensor let through ONE reader at a time
    (the others read it under `stop_gradient`: the same forward), what
    the producers' leaves receive through each reader, beside what they
    receive with none, adds up to their gradient, which is the
    reference's; no part alone is."""
    _, xs, y, weights, ff = built("deep")
    params = as_arrays(weights)
    gauges = ff.executor.traced_gauges()
    assert gauges["executor.shared_tensors"] == 3
    assert gauges["executor.shared_tensor_readers"] == 2 + 2 * 2
    readers = {"b6_mixer": ["b8_memory_gated", "b10_memory_gated"],
               "b7_attn": ["b9_attn", "b11_attn"]}
    names = tuple(n for group in readers.values() for n in group)
    with fm.highest():
        grad = jax.jit(jax.grad(program_loss_of(ff, xs, y, names)))
        whole = grad(params, jnp.ones(4))
        none = grad(params, jnp.zeros(4))
        parts = {name: grad(params, jnp.zeros(4).at[k].set(1.0))
                 for k, name in enumerate(names)}
    want = fm.reference_gradient(tinies("deep"))
    for producer, leaves in (
            ("b6_mixer", ("a_log", "w_x", "w_dt", "dt_bias", "conv_w",
                          "w_in")),
            ("b7_attn", ("wk", "wv", "bv"))):
        for leaf in leaves:
            total = whole[producer][leaf]
            scale = float(jnp.max(jnp.abs(total)))
            np.testing.assert_allclose(
                np.asarray(total) / scale,
                np.asarray(want[producer][leaf]) / scale, atol=2e-4,
                err_msg=f"{producer}.{leaf} against the reference")
            own = none[producer][leaf]
            # every edge: layer 6's leaves reach the loss through layer
            # 7's keys and values too, which the cross layers read
            added = own + sum(p[producer][leaf] - own
                              for p in parts.values())
            np.testing.assert_allclose(np.asarray(added) / scale,
                                       np.asarray(total) / scale, atol=2e-4,
                                       err_msg=f"{producer}.{leaf}")
            for part in [own] + [p[producer][leaf] for p in parts.values()]:
                assert float(jnp.max(jnp.abs(part - total))) > 1e-3 * scale, \
                    (producer, leaf)
            # its own readers' edges each carry something
            for name in readers[producer]:
                assert float(jnp.max(jnp.abs(
                    parts[name][producer][leaf] - own))) > 1e-3 * scale, \
                    (producer, leaf, name)
    # a unit's edge carries nothing into layer 7's leaves: the memory
    # was made before them
    for name in readers["b6_mixer"]:
        np.testing.assert_allclose(parts[name]["b7_attn"]["wk"],
                                   none["b7_attn"]["wk"], atol=1e-7)


def test_what_is_stated_float32_is_float32(built, cell, monkeypatch):
    """The scan's state rounded to bfloat16 a step, or lambda's four
    vectors held in bfloat16, miss the logits' tolerance by ten times."""
    family, config, traffic = cell
    s, xs, y, weights, _ = built("stage")
    want = hs.reference_side(family, weights, s, traffic, config, xs, y,
                             s["batch"], steps=1)["preds"]

    def worst(weights, ff=None):
        ff = ff or family.build(config, s, 1, 11)
        family.install_weights(ff, weights)
        got = np.asarray(ff.predict([xs[0][:s["batch"]]]), np.float32)
        return float(np.max(np.abs(got - want) / (2e-5 + 2e-4
                                                  * np.abs(want))))

    # its own model (the module's has trained), which the rounded
    # weights run too: the same program
    ff = family.build(config, s, 1, 11)
    assert worst(weights, ff) < 1.0
    rounded = dict(weights, b1_attn=dict(weights["b1_attn"], **{
        k: np.asarray(jnp.asarray(weights["b1_attn"][k], jnp.bfloat16),
                      np.float32)
        for k in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")}))
    assert worst(rounded, ff) > 10.0

    def bf16_state(x, dt, bm, cm, a, d):
        f32 = jnp.float32

        def step(state, inp):
            x_t, dt_t, b_t, c_t = inp
            state = (jnp.exp(dt_t[..., None] * a) * state
                     + (dt_t * x_t)[..., None] * b_t[:, None, :]
                     ).astype(jnp.bfloat16).astype(f32)
            return state, jnp.einsum("bcn,bn->bc", state, c_t) + d * x_t

        seq = tuple(jnp.moveaxis(t.astype(f32), 1, 0)
                    for t in (x, dt, bm, cm))
        _, ys = jax.lax.scan(step, jnp.zeros((x.shape[0],) + a.shape, f32),
                             seq)
        return jnp.moveaxis(ys, 0, 1)

    monkeypatch.setattr(ssm, "selective_scan_stepwise", bf16_state)
    assert worst(weights) > 10.0


# ---------------------------------------------------------------------------
# the selective scan: the kernel against the recurrence as written


@pytest.mark.parametrize("batch,seq,channels,states", [
    (2, 100, 200, 16),      # two chunks, the second of 36; one vreg of
                            # channels, 824 of them padding; the cell's N
    (1, 64, 1100, 3)])      # one whole chunk, two vregs, an odd N
def test_the_scan_kernel_matches_the_stepwise_form(batch, seq, channels,
                                                   states, monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert seq % pk.SCAN_CHUNK or seq == pk.SCAN_CHUNK
    rs = np.random.RandomState(seq)
    f32 = lambda *shape: rs.randn(*shape).astype(np.float32)  # noqa: E731
    x = f32(batch, seq, channels)
    dt = np.logaddexp(f32(batch, seq, channels) - np.float32(2.0),
                      np.float32(0))                        # softplus
    bm = f32(batch, seq, states)
    cm = f32(batch, seq, states)
    a = -np.exp(np.float32(0.5) * f32(channels, states))
    d = f32(channels)
    weight = f32(batch, seq, channels)
    args = (x, dt, bm, cm, a, d)
    with fm.highest():
        (got, want), grads = zip(*(
            output_and_gradients(f, weight, *args)
            for f in (pk.selective_scan, ssm.selective_scan_stepwise)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the recurrence by hand at the first two positions
    h0 = (dt[:, 0] * x[:, 0])[..., None] * bm[:, 0][:, None, :]
    h1 = (np.exp(dt[:, 1][..., None] * a) * h0
          + (dt[:, 1] * x[:, 1])[..., None] * bm[:, 1][:, None, :])
    np.testing.assert_allclose(
        want[:, 1], np.einsum("bcn,bn->bc", h1, cm[:, 1]) + d * x[:, 1],
        rtol=1e-5, atol=1e-5)
    for name, g, w in zip(("x", "dt", "B", "C", "A", "D"), *grads):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        scale = float(np.max(np.abs(w)))
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=2e-5,
                                   err_msg=name)


def test_the_mixer_op_runs_the_kernel_where_pallas_is_on(monkeypatch):
    """The op alone, the kernel interpreted: the same outputs and leaf
    gradients as with the stepwise form, the memory the scan's output
    BEFORE the gate, the gauges saying which ran."""
    from flexflow_tpu import FFModel
    ff = FFModel(FFConfig(batch_size=2))
    x = ff.create_tensor((2, 70, 16))
    ff.mamba_mixer(x, state_size=4, export_memory=True, name="mix")
    from flexflow_tpu.ops.base import OpRegistry
    layer = ff._layer_named["mix"]
    op = OpRegistry.create(layer, [(2, 70, 16)])
    params = op.init_params(jax.random.PRNGKey(0))
    assert {k: v.shape for k, v in params.items()} == {
        "w_in": (16, 64), "conv_w": (4, 32), "conv_b": (32,),
        "w_x": (32, 9), "w_dt": (1, 32), "dt_bias": (32,),
        "a_log": (32, 4), "d": (32,), "w_out": (32, 16)}
    np.testing.assert_allclose(np.exp(params["a_log"][5]), [1, 2, 3, 4],
                               rtol=1e-6)
    assert op.params_elems() == sum(int(v.size) for v in params.values())
    rs = np.random.RandomState(0)
    h = jnp.asarray(rs.randn(2, 70, 16), jnp.float32)
    ctx = OpContext(training=True, compute_dtype=jnp.float32)

    def outputs_and_grads():
        # a new function a call: traced under the mode in force
        def loss(p, h):
            outs = op.forward(p, [h], ctx)
            return sum(jnp.sum(o * o) for o in outs), outs
        with fm.highest():
            (_, outs), grads = jax.jit(jax.value_and_grad(
                loss, has_aux=True))(params, h)
        return outs, grads

    off = outputs_and_grads()
    assert op.traced_gauges() == {"ssm/selective_scan_ops": 1,
                                  "ssm/selective_scan_kernel_ops": 0}
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    on = outputs_and_grads()
    assert op.traced_gauges()["ssm/selective_scan_kernel_ops"] == 1
    for a, b in zip(jax.tree.leaves(on), jax.tree.leaves(off)):
        scale = float(np.max(np.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=2e-5)
    out, memory = off[0]
    assert out.shape == (2, 70, 16) and memory.shape == (2, 70, 32)
    # the reference's mixer gives the same pair
    with fm.highest():
        y, want = jax.jit(lambda h, p: ref.mamba(h, p, "f32"))(h, params)
    np.testing.assert_allclose(memory, y, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# differential attention on the flash route


def test_differential_attention_takes_the_flash_route(monkeypatch):
    """At a length the kernels take (interpret mode) the two maps run as
    flash calls at heads of twice the width, self-attention and
    cross-attention over given keys and values alike, and agree with the
    einsum core and with the reference's blocks of queries."""
    from flexflow_tpu import FFModel
    from flexflow_tpu.ops.base import OpRegistry
    ff = FFModel(FFConfig(batch_size=1))
    x = ff.create_tensor((1, 256, 64))
    kw = dict(bias=True, qkv_bias=True, causal=True, num_kv_heads=2,
              head_dim=64, differential=True, lambda_init=0.7)
    _, k, v = ff.multihead_attention(x, x, x, 64, 4, export_kv=True,
                                     name="full", **kw)
    ff.multihead_attention(x, k, v, 64, 4, kv_given=True, name="cross", **kw)
    ff.multihead_attention(x, x, x, 64, 4, window=128, name="window", **kw)
    rs = np.random.RandomState(1)
    h = jnp.asarray(rs.randn(1, 256, 64), jnp.float32)
    ctx = OpContext(training=True, compute_dtype=jnp.float32)
    given = None
    for name in ("full", "cross", "window"):
        layer = ff._layer_named[name]
        op = OpRegistry.create(layer, [t.shape for t in layer.inputs])
        params = op.init_params(jax.random.PRNGKey(2))
        params = {k_: (0.3 * jnp.asarray(rs.randn(*v_.shape), jnp.float32)
                       if k_.startswith("b") else v_)
                  for k_, v_ in params.items()}
        inputs = [h] + (list(given) if name == "cross" else [h, h])

        def run():
            with fm.highest():   # a new function a call: the mode in force
                return jax.jit(lambda p, xs: op.forward(p, xs, ctx))(
                    params, inputs)

        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
        plain = run()
        assert op._route.core == "einsum"
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
        flash = run()
        route = op._route
        assert (route.core, route.blocked) == ("flash", None)
        assert route.scope == "diff_" + name
        # two query pair-heads of 128 over one key/value pair: grouped
        assert op.core_heads == (2, 1, 128) and route.grouped_kv
        assert op.traced_gauges()["executor.flash_diff_ops"] == 1
        for a, b in zip(flash, plain):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
        # the reference: heads first, the maps apart, blocks of queries
        p = {k_: np.asarray(v_) for k_, v_ in params.items()}
        with fm.highest():
            kv = (tuple(jnp.moveaxis(t.reshape(1, 256, 2, 64), 2, 1)
                        for t in given) if name == "cross"
                  else ref.projected_kv(h, p, "f32"))
            monkeypatch.setattr(ref, "lambda_init", lambda i: 0.7)
            want = ref.differential_attention(
                h, p, kv, depth=0, window=128 if name == "window" else 0,
                eps=1e-5, operand="f32")
        np.testing.assert_allclose(flash[0], want, rtol=2e-4, atol=2e-5)
        if name == "full":
            given = flash[1:]
            assert [t.shape for t in given] == [(1, 256, 128)] * 2
            np.testing.assert_allclose(
                given[0], jnp.moveaxis(kv[0], 1, 2).reshape(1, 256, 128),
                rtol=2e-4, atol=2e-5)
    # at different lengths the flash kernels still have no lowering
    layer = ff._layer_named["cross"]
    op = OpRegistry.create(layer, [t.shape for t in layer.inputs])
    assert op.route({}, True, sq=128, sk=256).blocked == "cross_attention"


def test_the_attention_op_refuses_what_it_has_not(cell):
    from flexflow_tpu import FFModel
    ff = FFModel(FFConfig(batch_size=1))
    x = ff.create_tensor((1, 16, 32))
    k = ff.create_tensor((1, 16, 16))
    with pytest.raises(ValueError, match="even numbers of query"):
        ff.multihead_attention(x, x, x, 32, 3, differential=True,
                               causal=True)
    with pytest.raises(ValueError, match="no latent, rotary"):
        ff.multihead_attention(x, x, x, 32, 4, differential=True, rope=True,
                               causal=True)
    with pytest.raises(ValueError, match="kv_given takes"):
        ff.multihead_attention(x, x, x, 32, 4, num_kv_heads=2, head_dim=8,
                               kv_given=True, causal=True)
    out = ff.multihead_attention(x, k, k, 32, 4, num_kv_heads=2, head_dim=8,
                                 kv_given=True, causal=True, name="plain")
    assert out.shape == (1, 16, 32)
    from flexflow_tpu.ops.base import OpRegistry
    layer = ff._layer_named["plain"]
    op = OpRegistry.create(layer, [t.shape for t in layer.inputs])
    assert set(op.init_params(jax.random.PRNGKey(0))) == {"wq", "wo", "bo"}
    assert op.route({}, True).scope == "cross"
    with pytest.raises(NotImplementedError, match="shared between layers"):
        op.decode_forward({}, [], None, None, None, 0)


def test_serving_refuses_the_family(built):
    from flexflow_tpu.serve.kv_cache import init_kv_cache
    _, _, _, _, ff = built("stage")
    with pytest.raises(NotImplementedError, match="ONE cache entry"):
        init_kv_cache(ff, batch=1, max_len=32)


# ---------------------------------------------------------------------------
# remat, the search, fflint


def test_a_rematted_reader_does_not_recompute_its_producer(built):
    """The readers of the memory and of the keys and values under
    `jax.checkpoint` (the searched `_r` choice): the step holds as many
    scans and as many key/value projections as without; the producer
    under it holds one more scan (the recomputation)."""
    _, xs, y, weights, ff = built("stage")
    ex = ff.executor
    params = as_arrays(weights)

    def scans(remat):
        kept, ex.remat_ops = ex.remat_ops, remat
        try:
            text = str(jax.make_jaxpr(jax.grad(
                program_loss_of(ff, xs, y)))(params))
        finally:
            ex.remat_ops = kept
        return text.count("name=selective_scan")

    plain = scans(None)
    assert plain >= 1
    readers = {"b2_memory_gated", "b2_memory_in_proj", "b2_memory_out_proj",
               "b3_attn"}
    assert scans(readers) == plain
    assert scans({"b0_mixer"}) > plain
    with fm.highest():
        want = jax.jit(jax.grad(program_loss_of(ff, xs, y)))(params)
        kept, ex.remat_ops = ex.remat_ops, readers - {"b3_attn"}
        try:
            got = jax.jit(jax.grad(program_loss_of(ff, xs, y)))(params)
        finally:
            ex.remat_ops = kept
    assert_leaves_close(got, want, atol=1e-5)


def test_the_search_keeps_both_ends_of_an_exported_tensor(built):
    """The producers and the readers are pinned (no rewrite re-forms
    them), the producers and the differential ops spawn no remat twin,
    and the memory estimate counts the exported outputs once, from the
    producer on."""
    from flexflow_tpu.search import native
    from flexflow_tpu.search.unity import serialize_graph
    if not native.available():
        pytest.skip("native search unavailable")
    _, _, _, _, ff = built("stage")
    graph = serialize_graph(ff.executor.nodes)
    by_name = {n["name"]: n for n in graph}
    for name in ("b0_mixer", "b1_attn", "b2_memory_gated", "b3_attn"):
        assert by_name[name]["attrs"]["pinned"] == 1, name
    assert by_name["b0_mixer"]["attrs"]["exports"] == 1
    assert by_name["b1_attn"]["attrs"]["exports"] == 2
    assert by_name["b0_mixer"]["type"] == "MAMBA_MIXER"
    assert by_name["b0_mixer"]["output_shapes"] == [[2, 32, 32],
                                                    [2, 32, 64]]
    assert by_name["b3_attn"]["attrs"]["side_counters"] == 1
    assert "pinned" not in by_name["b2_memory_in_proj"]["attrs"]
    assert "exports" not in by_name["b2_memory_gated"]["attrs"]
    machine = {"num_devices": 1, "flops": 197e12, "hbm_bw": 0.82e12,
               "hbm_cap": 16e9, "ici_bw": 45e9, "ici_latency": 1e-6,
               "dcn_bw": 25e9, "dcn_latency": 1e-5, "num_slices": 1,
               "comm_bytes_factor": 0.5}
    resp = native.native_optimize(dict(
        nodes=graph, machine=machine, measured={},
        config=dict(budget=2, training=True, enable_substitution=False,
                    batch=2, opt_state_factor=2.0, enable_remat=True,
                    emit_search_trace=True)))
    ops = {o["name"]: o for o in resp["search_trace"]["ops"]}
    rep = {name: next(c for c in o["candidates"] if c["choice"] == "rep")
           for name, o in ops.items()}
    # both outputs of the Mamba op, all three of the attention op
    assert rep["b0_mixer"]["memory"]["act_bytes"] >= 4 * 2 * 32 * (32 + 64)
    assert rep["b1_attn"]["memory"]["act_bytes"] >= 4 * 2 * 32 * 3 * 32
    assert rep["b3_attn"]["memory"]["act_bytes"] < \
        rep["b1_attn"]["memory"]["act_bytes"]
    for name in ("b0_mixer", "b1_attn", "b3_attn"):
        assert not [c for c in ops[name]["candidates"]
                    if c["choice"].endswith("_r")], name


def test_the_cells_model_is_priced_feasible_on_one_v5e(cell):
    """The cell's own model at its published widths, no array made: the
    native search finds a strategy for one described v5e chip whose
    predicted bytes fit the chip's 16 GB."""
    from flexflow_tpu.machine import MachineSpec
    from flexflow_tpu.search import native
    from flexflow_tpu.search.unity import graph_optimize
    if not native.available():
        pytest.skip("native search unavailable")
    family, config, traffic = cell
    s = family.sizes(config, traffic)
    dc = DecoderConfig(**{f.name: s[f.name] for f in
                          dataclasses.fields(DecoderConfig) if f.name in s},
                       layer_norm_epsilon=s["layer_norm_eps"],
                       batch_size=1, seq_length=s["seq"])
    cfg = FFConfig(batch_size=1, search_budget=config["search_budget"])
    ff = create_decoder(dc, cfg)
    nodes, _, _ = ff._materialize_nodes()
    assert sum(n.op.params_elems() for n in nodes) == 478_876_928
    _, strategy, info = graph_optimize(
        nodes, MachineSpec("tpu-v5e", chips_per_slice=1), cfg, 1, batch=1,
        final_ref=(nodes[-1].guid, 0))
    assert strategy and info["predicted_memory"] < 16e9, info
    # 4.79 GB of leaves and moments at the least
    assert info["predicted_memory"] > 4.7e9


def test_fflint_knows_the_family(built):
    from flexflow_tpu import lint_model
    for name in ("stage", "whole"):
        _, _, _, _, ff = built(name)
        report = lint_model(ff)
        assert not [d for d in report.diagnostics
                    if d.severity.name == "ERROR"], report.diagnostics


def test_a_checkpoint_round_trip_keeps_the_families_leaves(built, tmp_path):
    s, xs, _, _, ff = built("stage")
    before = np.asarray(ff.predict([xs[0][:s["batch"]]]))
    path = str(tmp_path / "ckpt")
    ff.save_checkpoint(path)
    kept = ff.get_parameter("b0_mixer", "a_log").copy()
    ff.set_parameter("b0_mixer", np.zeros_like(kept), "a_log")
    assert not np.array_equal(
        np.asarray(ff.predict([xs[0][:s["batch"]]])), before)
    ff.load_checkpoint(path)
    np.testing.assert_array_equal(ff.get_parameter("b0_mixer", "a_log"),
                                  kept)
    np.testing.assert_array_equal(
        np.asarray(ff.predict([xs[0][:s["batch"]]])), before)


# ---------------------------------------------------------------------------
# the three controls: each built through a `program_*` override, each NOT
# correct against the reference as the cell states it

_STATED = []
CONTROLS = [dict(program_diff_lambda_scale=0.0),
            dict(program_memory_gated=True),
            dict(program_cross_own_kv=True)]


@pytest.mark.parametrize("control", CONTROLS,
                         ids=[next(iter(c)) for c in CONTROLS])
def test_a_program_built_otherwise_is_not_correct(cell, built, control):
    family, config, traffic = cell
    stated, xs, y, weights, _ = built("stage")
    s = family.sizes(config, traffic, dict(STAGE, **control))
    ff = family.build(config, s, 1, 11)
    family.install_weights(ff, weights)
    x0 = xs[0][:s["batch"]]
    got = np.asarray(ff.predict([x0]), np.float32)
    if not _STATED:     # the reference as the cell states it: one a module
        with fm.highest():
            _STATED.append(np.asarray(jax.jit(lambda w, x: ref.forward(
                w, x, **family.reference_kw(stated)))(as_arrays(weights),
                                                      jnp.asarray(x0))))
    error = hs.prediction_errors(got, _STATED[0], False)["nrmse"]
    assert error > 2 * family.TOLERANCES["pred_nrmse"], error
