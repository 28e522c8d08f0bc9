"""Kernel-implementation choice as a searched dimension (ISSUE 15).

The ``_k:<impl>`` suffix-lattice twins: native enumeration + per-impl
pricing (flash attention HBM-traffic model, fused one-dispatch
optimizer update, train-time Conv+BN fusion), legality gates with named
rejection reasons in the search trace, the ``FFS_NO_KERNEL_SEARCH`` /
``--kernel-search off`` opt-out, executor parity (fused triad bitwise;
flash within the 2e-5 class), suffix-lattice decode/replay composing
with ``_wus``/``_ovl``, per-impl corpus classes, the fflint
FFL208/FFL209 priced-vs-executed rules, and serve provenance.

Runs on the conftest 8-device virtual CPU mesh.
"""

import copy
import json
import os

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.ffconst import LossType
from flexflow_tpu.machine import make_mesh
from flexflow_tpu.model import FFModel
from flexflow_tpu.optimizers import AdamOptimizer, SGDOptimizer

BATCH = 16


# ---- native mini-graph harness (test_overlap's pattern) -------------------

_MACHINE = {"num_devices": 8, "flops": 197e12, "hbm_bw": 0.82e12,
            "hbm_cap": 16e9, "ici_bw": 45e9, "ici_latency": 1e-6,
            "dcn_bw": 25e9, "dcn_latency": 1e-5, "num_slices": 1,
            "mxu_efficiency": 0.55, "conv_efficiency": 0.35,
            "min_op_time": 5e-7, "collective_launch_overhead": 2e-6,
            "comm_bytes_factor": 0.5}


def _attn_linear_nodes(seq=512, heads=8, head_dim=16):
    """One self-attention (flash-legal at seq=512, 128|seq; 8 heads of
    16 are one column block of 128 lanes) + one Linear — the minimal
    graph every kernel dimension shows up on."""
    e = heads * head_dim
    return [
        dict(guid=1, type="MULTIHEAD_ATTENTION", name="attn",
             inputs=[[-1, 0], [-1, 0], [-1, 0]],
             input_shapes=[[8, seq, e]] * 3,
             output_shapes=[[8, seq, e]],
             roles=[["sample", "seq", "channel"]],
             params={"wq": [heads, e, head_dim], "wk": [heads, e, head_dim],
                     "wv": [heads, e, head_dim], "wo": [heads, head_dim, e]},
             flops=1e9, dtype_size=4, attrs={"num_heads": heads}),
        dict(guid=2, type="LINEAR", name="fc", inputs=[[1, 0]],
             input_shapes=[[8, seq, e]], output_shapes=[[8, seq, e]],
             roles=[["sample", "seq", "channel"]],
             params={"kernel": [e, e], "bias": [e]},
             flops=1e9, dtype_size=4, attrs={}),
    ]


def _req(nodes, **cfg):
    base = dict(budget=2, training=True, enable_parameter_parallel=True,
                enable_substitution=False, batch=8,
                emit_search_trace=True)
    base.update(cfg)
    return dict(nodes=nodes, machine=dict(_MACHINE), measured={},
                config=base)


def _native():
    from flexflow_tpu.search import native
    if not native.available():
        pytest.skip("native search unavailable")
    return native


class TestNativeEnumeration:
    def test_twins_spawn_and_compose_with_suffix_lattice(self):
        native = _native()
        resp = native.native_optimize(_req(_attn_linear_nodes()))
        ops = {o["name"]: o for o in resp["search_trace"]["ops"]}
        names = [c["choice"] for c in ops["attn"]["candidates"]]
        # the kernel suffix composes with the whole "_wus"/"_ovl" lattice
        assert any(n.endswith("_k:flash") and "_wus" in n and "_ovl" in n
                   for n in names), names
        fc = [c["choice"] for c in ops["fc"]["candidates"]]
        assert any(n.endswith("_k:fused") and "_wus" in n for n in fc), fc
        # fused twins only exist on wus parents (the chain they collapse)
        assert all("_wus" in n for n in fc if "_k:fused" in n)

    def test_priced_distinctly_with_impl_column(self):
        native = _native()
        resp = native.native_optimize(_req(_attn_linear_nodes()))
        ops = {o["name"]: o for o in resp["search_trace"]["ops"]}

        def total(opn, choice):
            c = next(c for c in ops[opn]["candidates"]
                     if c["choice"] == choice)
            return c["terms"]["total_s"], c.get("impl"), c["cost_source"]

        t_e, i_e, src = total("attn", "dp")
        t_f, i_f, _ = total("attn", "dp_k:flash")
        assert i_e == "einsum" and i_f == "flash" and src == "analytic"
        assert t_f < t_e  # the HBM-traffic model prices flash cheaper
        t_t, i_t, _ = total("fc", "dp_wus")
        t_u, i_u, _ = total("fc", "dp_wus_k:fused")
        assert i_t == "triad" and i_u == "fused"
        assert t_u < t_t  # one round trip + two launches cheaper

    @pytest.mark.parametrize("seq,reason", [
        (64, "seq_not_divisible_by_flash_tile_128"),
        # one Q block past the kernels' VMEM budget (MAX_FLASH_SEQ)
        (16384 + 128, "seq_exceeds_flash_vmem_budget_16384"),
    ])
    def test_illegal_flash_rejected_with_named_reason(self, seq, reason):
        from flexflow_tpu.ops.pallas_kernels import flash_shape_legal
        native = _native()
        resp = native.native_optimize(_req(_attn_linear_nodes(seq=seq)))
        ops = {o["name"]: o for o in resp["search_trace"]["ops"]}
        rej = {r["impl"]: r["reason"]
               for r in ops["attn"].get("kernel_rejections") or []}
        assert rej.get("flash") == reason
        assert not any("_k:flash" in c["choice"]
                       for c in ops["attn"]["candidates"])
        # the Python gate refuses what the native gate refuses
        assert not flash_shape_legal(seq, 16, 8)
        assert flash_shape_legal(16384, 16, 8)

    @pytest.mark.parametrize("heads,head_dim,legal", [
        # both benchmark shapes; a whole row as one block
        (16, 64, True), (4, 128, True), (1, 64, True), (4, 8, True),
        # an odd head out; lanes the heads of a block do not fill
        (3, 64, False), (4, 96, False), (24, 8, False),
    ])
    def test_heads_that_do_not_tile_the_lanes_are_rejected(self, heads,
                                                           head_dim, legal):
        """The kernels take [B, S, H*D] in column blocks of 128 lanes:
        the native gate and `flash_shape_legal` admit the same set."""
        from flexflow_tpu.ops.pallas_kernels import flash_shape_legal
        native = _native()
        resp = native.native_optimize(_req(_attn_linear_nodes(
            heads=heads, head_dim=head_dim)))
        ops = {o["name"]: o for o in resp["search_trace"]["ops"]}
        rej = {r["impl"]: r["reason"]
               for r in ops["attn"].get("kernel_rejections") or []}
        twins = any("_k:flash" in c["choice"]
                    for c in ops["attn"]["candidates"])
        assert flash_shape_legal(512, head_dim, heads) == legal
        assert twins == legal
        assert rej.get("flash") == (
            None if legal else "heads_do_not_tile_128_lanes")

    @pytest.mark.parametrize("heads,kv_heads,head_dim,reason", [
        # a head of two lane blocks (PR 58), the qwen3_next cell's 16 : 2
        (16, 2, 256, None), (1, 1, 256, None),
        # past one lane block a head is 256 exactly
        (4, 4, 192, "head_dim_exceeds_flash_vmem_budget_128"),
        (4, 4, 384, "head_dim_exceeds_flash_vmem_budget_128")])
    def test_a_head_of_two_lane_blocks_passes_both_gates(self, heads,
                                                         kv_heads, head_dim,
                                                         reason):
        """`flash_shape_legal`, the native `kernel_gate` and the grouped
        K / V rule say the same shapes at heads wider than 128 lanes."""
        from flexflow_tpu.ops.pallas_kernels import (flash_shape_legal,
                                                     grouped_kv_shape_legal)
        native = _native()
        resp = native.native_optimize(_req(_attn_linear_nodes(
            heads=heads, head_dim=head_dim)))
        ops = {o["name"]: o for o in resp["search_trace"]["ops"]}
        rej = {r["impl"]: r["reason"]
               for r in ops["attn"].get("kernel_rejections") or []}
        twins = any("_k:flash" in c["choice"]
                    for c in ops["attn"]["candidates"])
        assert flash_shape_legal(16384, head_dim, heads) == (reason is None)
        assert twins == (reason is None)
        assert rej.get("flash") == reason
        if reason is None and kv_heads < heads:
            assert grouped_kv_shape_legal(heads, kv_heads, head_dim)

    def test_dropout_attention_rejects_flash(self):
        """Attention-prob dropout has no flash lowering: the training
        gate rejects the twin with a named reason instead of pricing a
        kernel the executor's forward can never take (review finding)."""
        native = _native()
        nodes = _attn_linear_nodes()
        nodes[0]["attrs"]["dropout"] = 0.1
        resp = native.native_optimize(_req(nodes))
        ops = {o["name"]: o for o in resp["search_trace"]["ops"]}
        rej = {r["impl"]: r["reason"]
               for r in ops["attn"].get("kernel_rejections") or []}
        assert rej.get("flash") == "attention_prob_dropout_unsupported"
        assert not any("_k:flash" in c["choice"]
                       for c in ops["attn"]["candidates"])

    def test_opt_out_removes_dimension(self):
        native = _native()
        on = native.native_optimize(_req(_attn_linear_nodes()))
        off = native.native_optimize(
            _req(_attn_linear_nodes(), kernel_search="off"))
        names_off = [c["choice"] for o in off["search_trace"]["ops"]
                     for c in o["candidates"]]
        assert not any("_k:" in n for n in names_off)
        # deterministic: two off-runs agree bit-for-bit (the pre-PR
        # search space — twins absent, pricing of every remaining
        # candidate untouched)
        off2 = native.native_optimize(
            _req(_attn_linear_nodes(), kernel_search="off"))
        assert json.dumps(off, sort_keys=True) == \
            json.dumps(off2, sort_keys=True)
        # the on-search saw strictly more candidates
        names_on = [c["choice"] for o in on["search_trace"]["ops"]
                    for c in o["candidates"]]
        assert set(names_off) < set(names_on)

    def test_replay_tolerates_and_falls_back_k_suffix(self):
        native = _native()
        nodes = _attn_linear_nodes()
        base = dict(nodes=nodes, machine=dict(_MACHINE), measured={},
                    config=dict(training=True,
                                enable_parameter_parallel=True),
                    mesh={"data": 4, "model": 2, "seq": 1, "expert": 1,
                          "pipe": 1},
                    assignment={"1": "dp_head_k:flash",
                                "2": "dp_wus_k:fused"})
        r = native.native_simulate(base)
        assert r["iteration_time"] > 0
        # kernel search off: the "_k:" request falls back along the
        # suffix lattice to the default lowering instead of erroring
        off = copy.deepcopy(base)
        off["config"]["kernel_search"] = "off"
        r2 = native.native_simulate(off)
        assert r2["iteration_time"] > 0
        # the fused/flash lowerings price cheaper than the fallback
        assert r["iteration_time"] <= r2["iteration_time"]

    def test_acceptance_v4_32_bert_family_picks_fused_kernel(self):
        """Simulated v4-32 BERT-family search prices `_k:flash` and
        `_k:fused` distinctly from their baselines and commits to at
        least one fused kernel."""
        from flexflow_tpu.machine import MachineSpec
        from flexflow_tpu.models.transformer import (TransformerConfig,
                                                     create_transformer)
        from flexflow_tpu.search.unity import (machine_to_json,
                                               serialize_graph)
        native = _native()
        n_chips = 32
        mcfg = TransformerConfig(num_layers=2, hidden_size=1024,
                                 num_heads=16, seq_length=512,
                                 batch_size=n_chips)
        ff = create_transformer(
            mcfg, FFConfig(batch_size=mcfg.batch_size,
                           only_data_parallel=True, workers_per_node=1))
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
        resp = native.native_optimize(dict(
            nodes=serialize_graph(ff.executor.nodes),
            machine=machine_to_json(
                MachineSpec(chip="tpu-v4", chips_per_slice=n_chips),
                n_chips, comm_bytes_factor=0.5),
            measured={},
            config=dict(budget=4, alpha=0.05, training=True, overlap=True,
                        batch=mcfg.batch_size, opt_state_factor=2.0,
                        seed=42, rules=[], enable_parameter_parallel=True,
                        enable_substitution=False,
                        enable_pipeline_parallel=False,
                        emit_search_trace=True)))
        choices = {v["choice"] for v in resp["ops"].values()}
        assert any("_k:" in c for c in choices), choices
        # distinct pricing of both kernel families on the winning mesh
        ops = resp["search_trace"]["ops"]
        saw_flash = saw_fused = False
        for oj in ops:
            by = {}
            for c in oj["candidates"]:
                impl = c.get("impl")
                if impl:
                    by.setdefault(impl, set()).add(
                        round(c["terms"]["total_s"], 12))
            if "flash" in by and "einsum" in by and by["flash"] != by["einsum"]:
                saw_flash = True
            if "fused" in by and "triad" in by and by["fused"] != by["triad"]:
                saw_fused = True
        assert saw_flash and saw_fused


class TestFlagPlumbing:
    def test_flag_parsing(self):
        cfg = FFConfig()
        assert cfg.parse_args(["--kernel-search", "off"]) == []
        assert cfg.kernel_search == "off"
        assert FFConfig().kernel_search == "auto"
        with pytest.raises(ValueError):
            FFConfig().parse_args(["--kernel-search", "sometimes"])

    def test_env_opt_out_strips_choices(self, monkeypatch):
        monkeypatch.setenv("FFS_NO_KERNEL_SEARCH", "1")
        ff = _searched_mlp()
        assert ff.kernel_choices is None
        assert not any(
            "_k:" in (getattr(s, "choice", None) or "")
            for s in ff.strategy.values())

    def test_searched_kernel_choices_reach_executor(self):
        ff = _searched_mlp()
        assert ff.kernel_choices is not None
        fused = {n for n, i in ff.kernel_choices.items() if i == "fused"}
        assert fused == ff.executor.fused_update_ops
        assert fused  # the wus MLP takes the fused update


def _searched_mlp(seed=42):
    cfg = FFConfig(batch_size=BATCH, seed=seed)
    cfg.search_budget = 2
    cfg.enable_parameter_parallel = True
    ff = FFModel(cfg)
    x = ff.create_tensor((BATCH, 64), name="x")
    t = ff.dense(x, 512, name="d0")
    t = ff.relu(t)
    t = ff.dense(t, 64, name="d1")
    ff.compile(AdamOptimizer(alpha=1e-2),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
    return ff


def _plain_mlp(optimizer, fused_ops=None):
    """Heuristic (non-searched) MLP on the 8-way data mesh; the fused
    update is forced per-op so both runs share ONE strategy."""
    cfg = FFConfig(batch_size=BATCH, seed=42)
    cfg.weight_update_sharding = "on"
    ff = FFModel(cfg)
    x = ff.create_tensor((BATCH, 64), name="x")
    t = ff.dense(x, 512, name="d0")
    t = ff.relu(t)
    t = ff.dense(t, 64, name="d1")
    ff.compile(optimizer, LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [],
               mesh=make_mesh(8, {"data": 8}))
    if fused_ops:
        ff.executor.kernel_choices = {n: "fused" for n in fused_ops}
        ff.executor.fused_update_ops = set(fused_ops)
    return ff


class TestExecutorParity:
    def _train(self, ff, steps=3):
        import jax
        rs = np.random.RandomState(0)
        x = rs.randn(BATCH, 64).astype(np.float32)
        y = rs.randn(BATCH, 64).astype(np.float32)
        for _ in range(steps):
            ff.fit([x], y, epochs=1, verbose=False)
        return [np.asarray(l) for l in jax.tree_util.tree_leaves(
            (ff.params, ff.opt_state))]

    @pytest.mark.parametrize("opt", ["adam", "adam_pallas", "sgd",
                                     "sgd_momentum"])
    def test_fused_update_bitwise_on_8way_mesh(self, opt, monkeypatch):
        """The `_k:fused` one-dispatch update is bit-for-bit with the
        reference triad over a 3-step seeded run on the 8-way mesh.
        `adam_pallas` takes the Pallas kernel (interpret mode), which on
        a mesh runs per WUS shard under shard_map."""
        if opt == "adam_pallas":
            monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
        mk = {"adam": lambda: AdamOptimizer(alpha=1e-2),
              "adam_pallas": lambda: AdamOptimizer(alpha=1e-2),
              "sgd": lambda: SGDOptimizer(lr=0.01),
              "sgd_momentum": lambda: SGDOptimizer(lr=0.01, momentum=0.9)}
        ref = self._train(_plain_mlp(mk[opt]()))
        fus = self._train(_plain_mlp(mk[opt](), fused_ops={"d0", "d1"}))
        for a, b in zip(ref, fus):
            assert np.array_equal(a, b)

    def test_fused_adam_pallas_interpret_bitwise(self, monkeypatch):
        """The Pallas fused-update kernel (interpret mode) computes the
        EXACT reference expression."""
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
        import jax.numpy as jnp
        from flexflow_tpu.ops.fused_update import (_adam_math,
                                                   fused_adam_leaf)
        rs = np.random.RandomState(1)
        p = jnp.asarray(rs.randn(16, 128), jnp.float32)  # lane-aligned
        g = jnp.asarray(rs.randn(16, 128), jnp.bfloat16)
        m = jnp.zeros_like(p)
        v = jnp.zeros_like(p)
        kw = dict(beta1=0.9, beta2=0.999, eps=1e-8, wd=1e-4)
        a1 = _adam_math(p, g, m, v, jnp.float32(1e-2), **kw)
        a2 = fused_adam_leaf(p, g, m, v, jnp.float32(1e-2), **kw)
        for x, y in zip(a1, a2):
            assert np.array_equal(np.asarray(x), np.asarray(y))

    def test_conv_bn_fused_train_step_bitwise(self):
        """`_k:conv_bn_fused` (train-time fused region, batch-stats BN
        with preserved intermediate constraint) is bit-for-bit with the
        unfused pair — params AND BN running stats."""
        import jax

        def build(fused):
            cfg = FFConfig(batch_size=8, seed=42)
            ff = FFModel(cfg)
            x = ff.create_tensor((8, 3, 16, 16), name="x")
            t = ff.conv2d(x, 8, 3, 3, 1, 1, 1, 1, name="c1",
                          use_bias=False)
            t = ff.batch_norm(t, relu=True)
            t = ff.flat(t)
            t = ff.dense(t, 10, name="fc")
            ff.compile(SGDOptimizer(lr=0.01),
                       LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
            if fused:
                ff.executor.kernel_choices = {"c1": "conv_bn_fused"}
            return ff

        rs = np.random.RandomState(0)
        x = rs.randn(8, 3, 16, 16).astype(np.float32)
        y = rs.randint(0, 10, (8, 1)).astype(np.int32)
        states = []
        for fused in (False, True):
            ff = build(fused)
            if fused:
                fused_names = [n.op.name for n in
                               ff.executor._training_nodes()]
                assert any("+" in n for n in fused_names), fused_names
            for _ in range(3):
                ff.fit([x], y, epochs=1, verbose=False)
            states.append([np.asarray(l) for l in
                           jax.tree_util.tree_leaves(
                               (ff.params, ff.state))])
        for a, b in zip(*states):
            assert np.array_equal(a, b)

    def test_flash_vs_einsum_within_tolerance(self, monkeypatch):
        """Forced flash vs pinned einsum attention agree within the
        documented 2e-5 class over a training step (interpret mode)."""
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
        import jax

        def build(impl):
            cfg = FFConfig(batch_size=4, seed=42)
            ff = FFModel(cfg)
            x = ff.create_tensor((4, 128, 32), name="x")
            t = ff.multihead_attention(x, x, x, 32, 4, name="attn")
            t = ff.dense(t, 32, name="fc")
            ff.compile(SGDOptimizer(lr=0.01),
                       LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
            for n in ff.executor.nodes:
                if n.op.name == "attn":
                    n.op.kernel_impl = impl
                    assert n.op.selected_impl() == impl
            return ff

        rs = np.random.RandomState(0)
        x = rs.randn(4, 128, 32).astype(np.float32)
        y = rs.randn(4, 128, 32).astype(np.float32)
        leaves = {}
        for impl in ("einsum", "flash"):
            ff = build(impl)
            ff.fit([x], y, epochs=1, verbose=False)
            leaves[impl] = [np.asarray(l) for l in
                            jax.tree_util.tree_leaves(ff.params)]
        diffs = [float(np.max(np.abs(a.astype(np.float64)
                                     - b.astype(np.float64))))
                 for a, b in zip(leaves["einsum"], leaves["flash"])]
        assert max(diffs) < 2e-5, diffs

    def test_forced_flash_falls_back_with_recorded_reason(self,
                                                          monkeypatch):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
        cfg = FFConfig(batch_size=4, seed=42)
        ff = FFModel(cfg)
        x = ff.create_tensor((4, 128, 32), name="x")
        t = ff.multihead_attention(x, x, x, 32, 4, name="attn")
        t = ff.dense(t, 32, name="fc")
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
        attn = next(n.op for n in ff.executor.nodes
                    if n.op.name == "attn")
        attn.kernel_impl = "flash"
        rs = np.random.RandomState(0)
        ff.fit([rs.randn(4, 128, 32).astype(np.float32)],
               rs.randn(4, 128, 32).astype(np.float32),
               epochs=1, verbose=False)
        assert attn._kernel_fallback  # FFL209's runtime signal


class TestDecodeAndReplay:
    def test_strategy_file_roundtrip_with_k_suffix(self, tmp_path):
        ff = _searched_mlp()
        assert any("_k:" in (getattr(s, "choice", "") or "")
                   for s in ff.strategy.values())
        path = str(tmp_path / "s.json")
        from flexflow_tpu.search import unity
        axes = dict(zip(ff.mesh.axis_names, ff.mesh.devices.shape))
        unity.export_strategy_file(path, axes, ff.strategy,
                                   ff.executor.nodes)
        _, imported = unity.import_strategy_file(path, ff.executor.nodes)
        assert {getattr(s, "choice", None) for s in imported.values()} \
            == {getattr(s, "choice", None) for s in ff.strategy.values()}

    def test_simulate_strategy_replays_executed_kernels(self):
        from flexflow_tpu.search.validate import simulate_strategy
        ff = _searched_mlp()
        resp = simulate_strategy(ff)
        assert resp["iteration_time"] > 0
        assert "cost_sources" in resp

    def test_kernel_choice_of(self):
        from flexflow_tpu.parallel.choice import Choice
        assert Choice.parse("dp_wus_ovl_k:fused").kernel == "fused"
        assert Choice.parse("dp_head_k:flash").kernel == "flash"
        assert Choice.parse("dp_wus").kernel is None
        assert Choice.parse(None).kernel is None


class TestCorpusImpl:
    def test_simtrace_rows_carry_impl(self):
        from flexflow_tpu.obs.simtrace import (CORPUS_SCHEMA_VERSION,
                                               corpus_rows)
        from flexflow_tpu.search.validate import simulate_strategy
        assert CORPUS_SCHEMA_VERSION >= 3
        ff = _searched_mlp()
        rows = corpus_rows(ff, simulate_strategy(ff))
        by_name = {r["name"]: r for r in rows}
        fused = [n for n, i in (ff.kernel_choices or {}).items()
                 if i == "fused"]
        assert fused and all(by_name[n]["impl"] == "fused" for n in fused)

    def test_row_class_per_impl(self):
        from flexflow_tpu.costmodel.corpus import row_class, row_impl
        flash_row = dict(type="MULTIHEAD_ATTENTION",
                         choice="dp_head_k:flash")
        assert row_impl(flash_row) == "flash"
        assert row_class(flash_row) == "MULTIHEAD_ATTENTION:flash"
        # v2 row without impl: derived from the choice suffix
        ring_row = dict(type="MULTIHEAD_ATTENTION", choice="dp_ring")
        assert row_impl(ring_row) == "ring"
        assert row_class(ring_row) == "MULTIHEAD_ATTENTION"  # base class
        fused_row = dict(type="LINEAR", choice="dp_wus_k:fused",
                         impl="fused")
        assert row_class(fused_row) == "LINEAR"  # update impl: base
        conv_row = dict(type="CONV2D", choice="dp_k:conv_bn_fused")
        assert row_class(conv_row) == "CONV2D:conv_bn_fused"

    def test_v2_fixture_rows_stay_trainable(self):
        from flexflow_tpu.costmodel.corpus import build_corpus
        corpus = build_corpus([os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tests", "fixtures", "costmodel")])
        assert len(corpus["rows"]) > 50  # the committed v2 corpus loads


class TestFflintKernelRules:
    @pytest.mark.analysis
    @pytest.mark.parametrize("seq,props", [
        (96, dict(num_heads=4)),        # 96 % 128 != 0
        # a latent op whose rotated width the kernels refuse (128 lanes
        # do not divide by 48): legal by seq, head_dim and heads alone,
        # which is all the parent's FFL208 / FFL209 and the plan's
        # `_flash_was_enumerable` asked, so an imported `_k:flash` passed
        # both rules while forward ran einsum (PR 44)
        (128, dict(num_heads=2, head_dim=128, causal=True, bias=False,
                   q_lora_rank=16, kv_lora_rank=16, qk_rope_head_dim=48)),
    ], ids=["seq_96", "latent_rope_48"])
    def test_ffl208_illegal_flash_shape(self, seq, props, monkeypatch):
        from flexflow_tpu.analysis import lint_model
        from flexflow_tpu.ffconst import CompMode
        from flexflow_tpu.parallel.choice import _flash_was_enumerable
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
        cfg = FFConfig(batch_size=4, seed=42)
        ff = FFModel(cfg)
        x = ff.create_tensor((4, seq, 32), name="x")
        t = ff.multihead_attention(x, x, x, 32, name="attn", **props)
        t = ff.dense(t, 32, name="fc")
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
        attn = next(n.op for n in ff.executor.nodes if n.op.name == "attn")
        ff.strategy[attn.guid].choice = "dp_k:flash"  # stale/corrupt
        report = lint_model(ff)
        d208 = [d for d in report.diagnostics if d.rule == "FFL208"]
        assert d208 and "illegal at this shape" in d208[0].message, \
            [(d.rule, d.message) for d in report.diagnostics]
        # the plan and the forward agree with the rule
        assert not _flash_was_enumerable(attn, CompMode.TRAINING)
        assert attn.route({}, True).blocked == "shape"
        assert attn.selected_impl(training=True) == "einsum"

    @pytest.mark.analysis
    def test_ffl209_platform_fallback_is_info(self, monkeypatch):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
        from flexflow_tpu.analysis import lint_model
        cfg = FFConfig(batch_size=4, seed=42)
        ff = FFModel(cfg)
        x = ff.create_tensor((4, 128, 32), name="x")  # shape-legal
        t = ff.multihead_attention(x, x, x, 32, 4, name="attn")
        t = ff.dense(t, 32, name="fc")
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
        attn_guid = next(n.op.guid for n in ff.executor.nodes
                         if n.op.name == "attn")
        ff.strategy[attn_guid].choice = "dp_k:flash"
        report = lint_model(ff)
        d209 = [d for d in report.diagnostics if d.rule == "FFL209"]
        assert d209 and all(d.severity.name == "INFO" for d in d209)
        assert not any(d.rule == "FFL208" for d in report.diagnostics)


class TestServeProvenance:
    def test_bucket_report_records_kernel_choices(self):
        ff = _searched_mlp()
        eng = ff.serve(batch_buckets=[4], search_budget=0)
        try:
            rep = eng.bucket_report()
        finally:
            eng.stop()
        for b, e in rep.items():
            assert "kernel_choices" in e

    def test_decode_session_records_cached_einsum(self):
        from flexflow_tpu.serve.kv_cache import DecodeSession
        cfg = FFConfig(batch_size=2, seed=42)
        ff = FFModel(cfg)
        x = ff.create_tensor((2, 16, 32), name="x")
        t = ff.multihead_attention(x, x, x, 32, 4, name="attn",
                                   causal=True)
        t = ff.dense(t, 32, name="fc")
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
        sess = DecodeSession(ff, batch=2, max_len=16)
        rep = sess.report()
        # recorded at build, never re-derived: the decode path can only
        # ever run the cached einsum, whatever flash availability says
        assert rep["kernel_choices"] == {"attn": "cached_einsum"}
