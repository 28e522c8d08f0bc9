"""A looped model: one stack applied `total_ut_steps` times with ONE set
of leaves (PR 48; `benchmarks/references/ouro.py` is the plain float32
reference, which shares no code with `flexflow_tpu`): the builder's
owners and readers; the model against the reference for its output, three
losses and every gradient leaf; a shared leaf's gradient against the SUM
of what unshared copies receive; one pass without the output norms
against the `L` block model; one Adam state a shared leaf and the
reference's update; a checkpoint that holds a shared leaf once; `compile`
refusing a reader that does not fit its owner; the search's memory terms
(a shared leaf once, every application's activations); fflint and
explain; the controls."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_model as fm
from benchmarks import harness as hs
from benchmarks.references import common
from benchmarks.references import ouro as ref
from family_model import ROOT, program_loss_of
from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
from flexflow_tpu.models import DecoderConfig, create_decoder

CELL = "ouro_2_6b.s4096_b1.1chip"
# every width small, the structure whole: two blocks with sandwich norms,
# three passes, as many key/value heads as query heads, an untied head
TINY = dict(num_hidden_layers=2, vocab_size=96, hidden_size=64,
            num_attention_heads=4, num_key_value_heads=4, head_dim=16,
            intermediate_size=96, total_ut_steps=3, initializer_range=0.2,
            seq=32, batch=2, steps_per_epoch=1)
LAYER_OPS = ("norm", "attn", "attn_out_norm", "post_norm", "gate_up_proj",
             "down_proj", "mlp_out_norm")


@pytest.fixture(scope="module")
def cell():
    # ten rounds of the search (at two layers and three passes thirty
    # take half a minute)
    return fm.load_cell(CELL, search_budget=10)


@pytest.fixture(scope="module")
def tiny(cell):
    return fm.build_tiny(cell, TINY)


def test_create_decoder_builds_owners_and_readers(tiny):
    family, _, s, _, _, _, weights, ff = tiny
    ops = {n.op.name: n.op for n in ff.executor.nodes}
    # pass 1 owns, passes 2 and 3 read ALL the leaves of their layer
    for i in range(2):
        for kind in LAYER_OPS:
            owner = ops[f"b{i}_{kind}"]
            assert owner.tied_params == {} and owner.params_elems() > 0
            for ut in (1, 2):
                reader = ops[f"ut{ut}_b{i}_{kind}"]
                assert set(reader.tied_params.values()) == {
                    (owner.name, leaf) for leaf in weights[owner.name]}
                assert reader.params_elems() == 0
                assert reader.init_params(jax.random.PRNGKey(0)) == {}
                assert reader.layer.properties["scope"] == f"ut{ut}"
                assert reader.param_key() == owner.param_key()
    assert ops["ut2_final_ln"].tied_params == {"scale": ("final_ln",
                                                         "scale")}
    # an op without leaves shares nothing
    assert "shared_op" not in ops["ut1_b0_res1"].layer.properties
    attn = ops["ut1_b1_attn"]
    assert (attn.num_heads, attn.num_kv_heads, attn.head_dim) == (4, 4, 16)
    assert attn.rope and attn.causal and attn.rope_theta == 1e6
    # ONE set of leaves, the reference's tree name for name
    assert {k: {p: tuple(v.shape) for p, v in leaves.items()}
            for k, leaves in ff.params.items()} == {
        k: {p: tuple(v.shape) for p, v in leaves.items()}
        for k, leaves in weights.items()}
    assert family.parameters(s) == sum(
        int(x.size) for x in jax.tree.leaves(ff.params))
    # the head and the gate stay two ops with their own names: no rewrite
    # re-forms a full-precision product
    assert ops["exit_gate"].full_precision and "lm_head" in ops
    assert ff.loss_parts == ("ut0", "ut1", "ut2")
    assert ff.executor.exit_entropy_beta == 0.1
    assert ff.search_seconds is not None and ff.strategy
    assert all(ff.strategy[n.op.guid].choice for n in ff.executor.nodes)
    with pytest.raises(NotImplementedError, match="looped"):
        create_decoder(DecoderConfig(hybrid_override_pattern="UU",
                                     total_ut_steps=2,
                                     tie_word_embeddings=True))


def test_model_against_the_reference_output_and_three_losses(tiny):
    family, config, s, traffic, xs, y, weights, ff = tiny
    system, _ = hs.system_side(ff, xs, y, s["batch"])
    want = hs.reference_side(family, weights, s, traffic, config, xs, y,
                             s["batch"])
    assert system["preds"].shape == (s["batch"], 3 * s["seq"],
                                     s["vocab_size"] + 1)
    np.testing.assert_allclose(system["preds"], want["preds"], rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(system["losses"], want["losses"], rtol=2e-5)
    assert want["losses"][2] < want["losses"][0] - 1e-3   # the steps moved it
    counters = ff.op_counters
    assert counters["executor.shared_weight_ops"] == 2 * (7 * 2 + 1)
    assert counters["executor.shared_leaves"] == 2 * 10 + 1
    assert counters["executor.layer_applications"] == 6
    assert counters["executor.loss_own_vjp"] == 1
    # what the loss counted, against the reference's arithmetic on the
    # weights as they were before the last step... the masses alone hold
    # whatever the weights: they are a distribution at every position
    positions = s["batch"] * s["seq"]
    assert counters["loss/target_positions"] == positions
    assert sum(counters[f"loss/exit_mass_ut{t}"] for t in range(3)) == \
        pytest.approx(positions, rel=1e-5)


def test_the_loss_and_its_counters_against_the_reference(tiny):
    """`losses.expected_exit_loss` on a made-up output: the loss, the
    passes' cross-entropies, the masses and the entropy are the
    reference's; at gates of zero the masses are 1/2, 1/4, 1/4."""
    from flexflow_tpu import losses
    # (one program a call: eagerly every `jnp` operation of the loss and
    # of the reference is a program of its own, ROADMAP D10)
    expected_exit_loss = jax.jit(losses.expected_exit_loss,
                                 static_argnums=2,
                                 static_argnames=("beta", "uniform"))
    rs = np.random.RandomState(3)
    out = rs.randn(2, 3 * 8, 13).astype(np.float32)
    y = rs.randint(0, 12, (2, 8)).astype(np.int32)
    loss, counted = expected_exit_loss(out, y, 3, beta=0.1)
    per_position, p, ce = map(np.asarray, jax.jit(ref.position_losses)(
        out, y))
    np.testing.assert_allclose(loss, np.mean(per_position), rtol=1e-6)
    np.testing.assert_allclose(counted["loss/exit_nll"],
                               np.sum(ce, axis=(0, 2)), rtol=1e-6)
    np.testing.assert_allclose(counted["loss/exit_mass"],
                               np.sum(p, axis=(0, 2)), rtol=1e-6)
    entropy = -np.sum(p * np.log(p), axis=1)
    np.testing.assert_allclose(counted["loss/exit_entropy"],
                               np.sum(entropy), rtol=1e-6)
    even = out.copy()
    even[..., -1] = 0.0
    _, counted = expected_exit_loss(even, y, 3)
    np.testing.assert_allclose(counted["loss/exit_mass"],
                               [8.0, 4.0, 4.0], rtol=1e-6)
    # the last pass's gate is not read: it gets no gradient
    gate_grad = np.asarray(jax.jit(jax.grad(
        lambda o: losses.expected_exit_loss(o, y, 3, 0.1)[0]))(out))[
            ..., -1].reshape(2, 3, 8)
    assert not np.any(gate_grad[:, 2])
    assert np.all(gate_grad[:, :2] != 0)
    # one pass: the plain cross-entropy, no entropy
    one, counted = expected_exit_loss(out[:, :8], y, 1, beta=0.1)
    np.testing.assert_allclose(one, np.mean(ce[:, 0]), rtol=1e-6)
    assert float(counted["loss/exit_entropy"]) == 0.0
    uniform, _ = expected_exit_loss(out, y, 3, beta=0.1, uniform=True)
    np.testing.assert_allclose(
        uniform, np.mean(np.mean(ce, axis=1)) - 0.1 * np.log(3.0),
        rtol=1e-6)


@pytest.fixture(scope="module")
def gradients(tiny):
    return fm.gradients_of(tiny)


def test_every_gradient_leaf_matches_the_reference(gradients):
    _, got, want = gradients
    # the table; two layers of 4 norms, 4 attention leaves and the MLP's
    # 2; the final norm; the head; the gate's weight and bias
    assert fm.assert_leaves_close(got, want) == 1 + 2 * 10 + 1 + 1 + 2


def test_a_shared_leafs_gradient_is_the_sum_over_unshared_copies(
        cell, tiny, gradients):
    """The same model with leaves of its own in every pass, equal to the
    shared ones: what the T copies of a leaf receive adds up to what the
    ONE shared leaf receives, leaf by leaf, and no copy's alone does."""
    family, config, traffic = cell
    _, _, s, _, xs, y, weights, _ = tiny
    params, shared, _ = gradients
    apart = family.build(config, dict(s, program_share_leaves=False), 1, 11)
    ops = {n.op.name: n.op for n in apart.executor.nodes}
    assert not any(op.tied_params for op in ops.values())
    assert "ut2_b1_attn" in apart.params and "ut1_final_ln" in apart.params
    copies = dict(params)
    for name in weights:
        for ut in (1, 2):
            if f"ut{ut}_{name}" in apart.params:
                copies[f"ut{ut}_{name}"] = params[name]
    assert jax.tree.structure(copies) == jax.tree.structure(apart.params)
    with fm.highest():
        got = jax.jit(jax.grad(program_loss_of(apart, xs, y)))(copies)
    summed = 0
    for name, leaves in shared.items():
        for leaf, want in leaves.items():
            parts = [got[name][leaf]] + [
                got[f"ut{ut}_{name}"][leaf] for ut in (1, 2)
                if f"ut{ut}_{name}" in got]
            scale = float(jnp.max(jnp.abs(want)))
            np.testing.assert_allclose(np.asarray(sum(parts)) / scale,
                                       np.asarray(want) / scale, atol=2e-4,
                                       err_msg=f"{name}.{leaf}")
            if len(parts) == 3:
                summed += 1
                assert float(jnp.max(jnp.abs(parts[0] - want))) > \
                    1e-3 * scale, (name, leaf)
    assert summed == 2 * 10 + 1
    checks = {n: ok for n, ok, _ in family.extra_checks(
        apart, dict(s, program_share_leaves=False), 1, False)}
    assert not checks["parameters_held_once"]
    assert not checks["shared_weight_ops"] and checks["layer_applications"]


def test_one_pass_without_output_norms_is_the_llama_block_model(tiny):
    """`total_ut_steps` 1 and `sandwich_norm` off: the `L` block model's
    logits and its cross-entropy (p_1 = 1, H = 0), and the reference's
    at one pass once the output norms' scales... the reference norms its
    branches' outputs, so it is held to the program WITH them."""
    _, _, s, _, xs, y, weights, _ = tiny
    shared = dict(vocab_size=s["vocab_size"], hidden_size=s["hidden_size"],
                  num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                  intermediate_size=s["intermediate_size"],
                  layer_norm_epsilon=s["rms_norm_eps"], rope_theta=1e6,
                  batch_size=s["batch"], seq_length=s["seq"])

    def build(**kw):
        ff = create_decoder(DecoderConfig(**shared, **kw),
                            FFConfig(batch_size=s["batch"], seed=5,
                                     workers_per_node=1, search_budget=0))
        ff.compile(AdamOptimizer(alpha=1e-3),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
        return ff

    def install(ff, rename):
        for name, leaves in weights.items():
            for leaf, value in leaves.items():
                for held, part in rename(name, leaf, value):
                    if held in ff.params:
                        ff.set_parameter(held, part, leaf)

    plain = build(hybrid_override_pattern="UU", sandwich_norm=False)
    assert not any("out_norm" in name for name in plain.params)
    assert "exit_gate" not in plain.params
    assert getattr(plain, "loss_parts", None) is None
    install(plain, lambda name, leaf, value: [(name, value)])

    def llama_names(name, leaf, value):
        if not name.startswith("b"):
            return [(name, value)]
        i, kind = name[1], name[3:]
        if kind == "gate_up_proj":
            gate, up = np.split(value, 2, axis=1)
            return [(f"l{i}_gate_proj", gate), (f"l{i}_up_proj", up)]
        return [({"norm": f"l{i}_input_ln", "post_norm": f"l{i}_post_ln",
                  "attn": f"l{i}_attn",
                  "down_proj": f"l{i}_down_proj"}.get(kind, name), value)]

    llama = build(hybrid_override_pattern="LL")
    install(llama, llama_names)
    x0, y0 = [xs[0][:s["batch"]]], y[:s["batch"]]
    got, want = np.asarray(plain.predict(x0)), np.asarray(llama.predict(x0))
    assert got.shape == (s["batch"], s["seq"], s["vocab_size"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for ff in (plain, llama):
        ff.fit(x0, y0, epochs=1, verbose=False)
    np.testing.assert_allclose(float(plain._last_loss),
                               float(llama._last_loss), rtol=2e-5)
    # with the output norms, one pass is the reference at T = 1: its
    # output's logits, and its loss the plain cross-entropy
    normed = build(hybrid_override_pattern="UU")
    install(normed, lambda name, leaf, value: [(name, value)])
    kw = dict(num_hidden_layers=2, eps=s["rms_norm_eps"], rope_theta=1e6,
              total_ut_steps=1)
    with fm.highest():
        out = jax.jit(lambda w, ids: ref.forward(w, ids, **kw))(
            weights, x0[0])
    np.testing.assert_allclose(np.asarray(normed.predict(x0)),
                               np.asarray(out[..., :-1]), rtol=2e-4,
                               atol=2e-5)
    normed.fit(x0, y0, epochs=1, verbose=False)
    np.testing.assert_allclose(
        float(normed._last_loss),
        float(jnp.sum(ref.sample_losses(out, jnp.asarray(y0)))) / y0.size,
        rtol=2e-5)


def test_one_adam_state_and_the_references_update(cell, tiny, tmp_path):
    """A shared leaf has ONE optimizer state and ONE compute copy, the
    step's arguments hold it once, and a step moves it as the
    reference's Adam moves it on the summed gradient; a checkpoint holds
    it once and every reader sees what is restored."""
    family, config, traffic = cell
    _, _, s, _, xs, y, weights, _ = tiny
    ff = family.build(config, s, 1, 11)
    family.install_weights(ff, weights)
    wq = tuple(weights["b0_attn"]["wq"].shape)
    wo = tuple(weights["b0_attn"]["wo"].shape)

    def of_shape(tree, shape):
        return [x for x in jax.tree.leaves(tree)
                if tuple(getattr(x, "shape", ())) == shape]

    # wq, wk, wv of two layers: six leaves, six m and six v
    assert len(of_shape(ff.params, wq)) == 6
    assert len(of_shape(ff.opt_state, wq)) == 12
    assert len(of_shape(ff.opt_state, wo)) == 4
    step = ff.executor.make_train_step()
    x0, y0 = [xs[0][:s["batch"]]], y[:s["batch"]]
    lowered = step.lower(ff.params, ff.opt_state, ff.state,
                         ff._stage_inputs(x0), ff._shard_batch(y0),
                         jax.random.PRNGKey(0))
    args = jax.tree.leaves(lowered.args_info)
    assert sum(tuple(a.shape) == wo for a in args) == \
        2 * 3 + len(of_shape(ff.state, wo))
    # one step against the reference's
    ff.fit(x0, y0, epochs=1, verbose=False)
    kw = family.reference_kw(s)
    _, grads = common.loss_and_grads(ref, weights, x0[0], y0, 1, **kw)
    adam = config["adam"]
    zeros = jax.tree.map(jnp.zeros_like, weights)
    want, _, _ = common._adam(
        weights, grads, zeros, zeros, jnp.int32(1),
        jnp.float32(adam["alpha"]), jnp.float32(adam["beta1"]),
        jnp.float32(adam["beta2"]), jnp.float32(adam["epsilon"]),
        jnp.bool_(True))
    for name in ("b0_attn", "b1_down_proj", "final_ln", "lm_head"):
        for leaf, moved in want[name].items():
            g = np.asarray(grads[name][leaf])
            # where the gradient is not nothing the first step is alpha
            # times its sign: the sum over the passes decides the sign
            sure = np.abs(g) > 1e-3 * np.abs(g).max()
            assert sure.mean() > 0.9
            np.testing.assert_allclose(
                np.asarray(ff.get_parameter(name, leaf))[sure],
                np.asarray(moved)[sure], atol=2e-2 * adam["alpha"],
                err_msg=f"{name}.{leaf}")
    # a checkpoint holds each shared leaf once
    path = str(tmp_path / "ckpt")
    ff.save_checkpoint(path)
    with open(path + ".manifest.json") as f:
        saved = json.load(f)["array_keys"]
    assert any("b0_attn" in k for k in saved)
    assert not any("ut1_" in k or "ut2_" in k for k in saved)
    before = np.asarray(ff.predict(x0))
    held = ff.get_parameter("b0_attn", "wo")
    ff.set_parameter("b0_attn", np.zeros_like(held), "wo")
    zeroed = np.asarray(ff.predict(x0))
    # every pass read the zeroed leaf: all three passes' rows moved
    rows = s["seq"]
    for ut in range(3):
        assert np.abs(zeroed - before)[:, ut * rows:(ut + 1) * rows].max() \
            > 1e-3
    ff.load_checkpoint(path)
    assert np.array_equal(ff.get_parameter("b0_attn", "wo"), held)
    np.testing.assert_allclose(np.asarray(ff.predict(x0)), before,
                               rtol=1e-6, atol=1e-6)


def shared_pair(second):
    """A model whose second dense reads the first's leaves."""
    ff = FFModel(FFConfig(batch_size=4, workers_per_node=1, search_budget=0))
    x = ff.create_tensor((4, 8), name="x")
    first = ff.dense(x, 8, name="first")
    second(ff, first)
    return ff


CANNOT_SHARE = {
    "shapes": (lambda ff, first: ff.dense(first, 6, shared_op=first,
                                          name="second"),
               r"'second'.*leaves.*\(8, 6\)"),
    "bias": (lambda ff, first: ff.dense(first, 8, use_bias=False,
                                        shared_op=first, name="second"),
             r"'second'.*reads its leaves out of 'first'"),
    "kind": (lambda ff, first: ff._finish(ff._add_layer(
        __import__("flexflow_tpu").ffconst.OperatorType.RMSNORM, [first],
        dict(eps=1e-6), "second", shared_op=first)),
             r"'second'.*a LINEAR.*needs a RMSNORM"),
    "chain": (lambda ff, first: ff.dense(
        ff.dense(first, 8, shared_op=first, name="second"), 8,
        shared_op=ff.layers[-1].outputs[0], name="third"),
              r"'third'.*holds none of its own"),
}


@pytest.mark.parametrize("why", sorted(CANNOT_SHARE))
def test_compile_refuses_a_reader_that_does_not_fit_its_owner(why):
    build, message = CANNOT_SHARE[why]
    ff = shared_pair(build)
    with pytest.raises(ValueError, match=message):
        ff.compile(AdamOptimizer(alpha=1e-3),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])


def test_a_dense_reads_another_denses_leaves():
    """Upstream's `shared_op` on `dense`: two applications of one kernel
    and bias, one leaf each, the gradient the sum of both uses."""
    ff = shared_pair(lambda ff, first: ff.dense(first, 8, shared_op=first,
                                                name="second"))
    ff.compile(AdamOptimizer(alpha=1e-3),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
    assert set(ff.params) == {"first"}
    assert set(ff.params["first"]) == {"kernel", "bias"}
    rs = np.random.RandomState(0)
    x = rs.randn(4, 8).astype(np.float32)
    w, b = (ff.get_parameter("first", k) for k in ("kernel", "bias"))
    np.testing.assert_allclose(np.asarray(ff.predict([x])),
                               (x @ w + b) @ w + b, rtol=2e-2, atol=2e-2)
    gauges = ff.executor.traced_gauges()
    assert gauges["executor.shared_weight_ops"] == 1
    assert gauges["executor.shared_leaves"] == 2


def test_the_search_counts_a_shared_leaf_once_and_every_activation():
    """One pass against four at a small size: the weights', gradients'
    and moments' bytes of the stack are equal (a reader serialises no
    leaf, so it has no gradient sync and no update either), the
    activations the stack saves are four times as many, and no reader
    may be re-formed by a rewrite."""
    from flexflow_tpu.search import native
    from flexflow_tpu.search.unity import serialize_graph
    if not native.available():
        pytest.skip("native search unavailable")
    machine = {"num_devices": 1, "flops": 197e12, "hbm_bw": 0.82e12,
               "hbm_cap": 16e9, "ici_bw": 45e9, "ici_latency": 1e-6,
               "dcn_bw": 25e9, "dcn_latency": 1e-5, "num_slices": 1,
               "comm_bytes_factor": 0.5}

    def terms(passes):
        ff = create_decoder(DecoderConfig(
            hybrid_override_pattern="UU", total_ut_steps=passes,
            num_attention_heads=4, num_key_value_heads=4, batch_size=2,
            seq_length=16))
        nodes, _, _ = ff._materialize_nodes()
        graph = serialize_graph(nodes)
        resp = native.native_optimize(dict(
            nodes=graph, machine=machine, measured={},
            config=dict(budget=2, training=True, enable_substitution=False,
                        batch=2, opt_state_factor=2.0,
                        emit_search_trace=True)))
        stack = [o for o in resp["search_trace"]["ops"]
                 if "_b0_" in "_" + o["name"] or "_b1_" in "_" + o["name"]]
        first = [next(c for c in o["candidates"] if c["choice"] == "rep")
                 for o in stack]
        weights = sum(c["memory"]["param_bytes"]
                      + c["memory"]["opt_state_bytes"] for c in first)
        sync = sum(c["terms"].get("gradsync_s", 0.0) for c in first)
        acts = sum(c["memory"]["act_bytes"] for c in first)
        return graph, weights, sync, acts, resp["predicted_memory"]

    one, w1, s1, a1, m1 = terms(1)
    four, w4, s4, a4, m4 = terms(4)
    assert w1 == w4 > 0 and a4 == 4 * a1 > 0
    assert s4 == pytest.approx(s1)
    assert m4 > m1 and m4 - m1 < 5 * a1 + 4 * 2 * 64 * 257 * 4
    by_name = {n["name"]: n for n in four}
    assert by_name["ut3_b1_attn"]["params"] == {}
    assert by_name["ut3_b1_attn"]["flops"] == by_name["b1_attn"]["flops"]
    assert by_name["b1_attn"]["params"] and \
        by_name["b1_attn"]["attrs"]["pinned"] == 1
    assert by_name["ut1_b0_down_proj"]["attrs"]["pinned"] == 1
    assert by_name["exit_gate"]["attrs"]["pinned"] == 1
    assert "pinned" not in by_name["lm_head"]["attrs"]
    assert not any("pinned" in n["attrs"] for n in one)


def test_the_search_counts_saved_activations_at_the_compute_dtype():
    """Under mixed precision an op's forward leaves bfloat16 for its
    backward pass: `serialize_graph(act_dtype_size=2)` halves every
    activation term of the native memory estimate and leaves the leaves'
    (float32 master, moments) as they were; `graph_optimize` asks for it
    on every machine but the CPU's."""
    from flexflow_tpu.search import native
    from flexflow_tpu.search.unity import serialize_graph
    if not native.available():
        pytest.skip("native search unavailable")
    ff = create_decoder(DecoderConfig(
        hybrid_override_pattern="U", total_ut_steps=2,
        num_attention_heads=4, num_key_value_heads=4, batch_size=2,
        seq_length=16))
    nodes, _, _ = ff._materialize_nodes()

    def memory(**kw):
        graph = serialize_graph(nodes, **kw)
        resp = native.native_simulate(dict(
            nodes=graph, machine={"num_devices": 1, "hbm_cap": 16e9},
            config=dict(training=True, opt_state_factor=2.0),
            mesh=dict(data=1, model=1, seq=1, expert=1),
            assignment={str(n["guid"]): "rep" for n in graph},
            measured={}))
        leaves = sum(4 * 3 * int(np.prod(shape)) for n in graph
                     for shape in n["params"].values())
        return graph, resp["memory"], leaves

    full, m4, leaves = memory()
    half, m2, _ = memory(act_dtype_size=2)
    assert {n["act_dtype_size"] for n in full} == {4}
    assert {n["act_dtype_size"] for n in half} == {2}
    assert [n["dtype_size"] for n in half] == [n["dtype_size"] for n in full]
    assert m4 > leaves > 0
    assert m2 - leaves == pytest.approx((m4 - leaves) / 2)


def test_fflint_and_explain_know_a_shared_op(tiny):
    from flexflow_tpu import lint_model
    ff = tiny[-1]
    report = lint_model(ff)
    assert not [d for d in report.diagnostics
                if d.severity.name == "ERROR"], report.diagnostics
    # a reader whose owner is not in the graph, and one whose
    # leaf-shaping property differs, are flagged
    from flexflow_tpu.analysis.passes import hygiene
    ops = {n.op.name: n.op for n in ff.executor.nodes}
    reader = ops["ut1_b0_down_proj"]
    kept = dict(reader.layer.properties)
    try:
        reader.layer.properties["shared_op"] = "no_such_layer"
        del reader.__dict__["_tied_params"]
        found = [d for d in lint_model(ff).diagnostics
                 if d.rule == hygiene.SHARED_LEAVES]
        assert len(found) == 1 and "no_such_layer" in found[0].message
        assert found[0].severity.name == "ERROR"
    finally:
        reader.layer.properties.clear()
        reader.layer.properties.update(kept)
        del reader.__dict__["_tied_params"]
    assert not [d for d in lint_model(ff).diagnostics
                if d.rule == hygiene.SHARED_LEAVES]
    # explain prints every owner with its leaves and its readers
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import explain
    rows = {owner: (leaves, names)
            for owner, leaves, names in explain.shared_leaves_rows(ff)}
    assert len(rows) == 2 * 7 + 1
    assert rows["b1_attn"] == (["wk", "wo", "wq", "wv"],
                               ["ut1_b1_attn", "ut2_b1_attn"])
    assert rows["final_ln"] == (["scale"], ["ut1_final_ln", "ut2_final_ln"])


_STATED = []
CONTROLS = [dict(program_total_ut_steps=2),
            dict(program_sandwich_norm=False),
            dict(program_norm_between_passes=False),
            dict(program_exit_weights="uniform")]


@pytest.mark.parametrize("control", CONTROLS,
                         ids=[next(iter(c)) for c in CONTROLS])
def test_a_program_built_otherwise_is_not_correct(cell, tiny, control):
    """Four of the five mechanism controls (the fifth, leaves of its own
    in every pass, is the shared-gradient test's): fewer passes, no
    output norms, no norm between passes, uniform exit weights; the
    reference as the cell states it."""
    ff, s = fm.control_model(tiny, dict(TINY, **control))
    if not _STATED:     # the reference as the cell states it: one a module
        _STATED.append(fm.reference_predictions(tiny))
    if "program_exit_weights" not in control:
        # judged by its output: no step taken
        nrmse = hs.prediction_errors(fm.predictions(ff, tiny),
                                     _STATED[0]["preds"], False)["nrmse"]
        assert nrmse > tiny.family.TOLERANCES["pred_nrmse"], nrmse
        return
    system, _ = hs.system_side(ff, tiny.xs, tiny.y, s["batch"])
    rows = {r["name"]: r for r in hs.compare(system, _STATED[0],
                                             tiny.family.TOLERANCES)}
    # the output is the stated model's; the loss is not
    assert {n for n, r in rows.items() if not r["ok"]} == {"loss0_rel"}, rows
