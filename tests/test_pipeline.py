"""Pipeline parallelism (SPMD GPipe/circular pipelines over a 'pipe'
mesh axis).

Exceeds the reference, where pipeline parallelism is an enum with no
runtime (ffconst.h:153 OP_PIPELINE). Numerics and gradients are checked
against the plain sequential execution of the same stages; the circular
schedule and the sharded microbatch queue are additionally checked
bit-for-bit against the GPipe/replicated-queue baseline.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.machine import make_mesh
from flexflow_tpu.parallel.pipeline import (circular_block_order,
                                            pipeline_spmd, shard_stacked,
                                            stack_stage_params)

S, D = 4, 16


def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def make_params(seed):
    rs = np.random.RandomState(seed)
    return [{"w": jnp.asarray(rs.randn(D, D).astype(np.float32) * 0.3),
             "b": jnp.asarray(rs.randn(D).astype(np.float32) * 0.1)}
            for _ in range(S)]


def sequential(per_stage, x):
    for p in per_stage:
        x = stage_fn(p, x)
    return x


class TestPipeline:
    def test_matches_sequential(self):
        mesh = make_mesh(8, {"pipe": S, "data": 2})
        per_stage = make_params(0)
        stacked = shard_stacked(stack_stage_params(per_stage), mesh)
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.randn(16, D).astype(np.float32))
        want = sequential(per_stage, x)
        got = jax.jit(lambda p, x: pipeline_spmd(
            stage_fn, p, x, mesh, num_microbatches=4))(stacked, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("microbatches", [1, 2, 8])
    def test_microbatch_counts(self, microbatches):
        mesh = make_mesh(8, {"pipe": S, "data": 2})
        per_stage = make_params(2)
        stacked = shard_stacked(stack_stage_params(per_stage), mesh)
        x = jnp.asarray(np.random.RandomState(3).randn(16, D)
                        .astype(np.float32))
        want = sequential(per_stage, x)
        got = pipeline_spmd(stage_fn, stacked, x, mesh,
                            num_microbatches=microbatches)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_gradients_flow_through_pipeline(self):
        # GPipe backward = autodiff through shard_map + ppermute: grads of
        # every stage's params must match the sequential model's
        mesh = make_mesh(8, {"pipe": S, "data": 2})
        per_stage = make_params(4)
        stacked = stack_stage_params(per_stage)
        stacked_dev = shard_stacked(stacked, mesh)
        rs = np.random.RandomState(5)
        x = jnp.asarray(rs.randn(8, D).astype(np.float32))
        y = jnp.asarray(rs.randn(8, D).astype(np.float32))

        def loss_pipe(p):
            out = pipeline_spmd(stage_fn, p, x, mesh, num_microbatches=2)
            return jnp.mean((out - y) ** 2)

        def loss_seq(stages):
            return jnp.mean((sequential(stages, x) - y) ** 2)

        g_pipe = jax.jit(jax.grad(loss_pipe))(stacked_dev)
        g_seq = jax.grad(loss_seq)(per_stage)
        for i in range(S):
            for k in ("w", "b"):
                np.testing.assert_allclose(
                    np.asarray(g_pipe[k][i]), np.asarray(g_seq[i][k]),
                    rtol=5e-4, atol=5e-6)

    def test_trains_end_to_end(self):
        mesh = make_mesh(8, {"pipe": S, "data": 2})
        per_stage = make_params(6)
        params = shard_stacked(stack_stage_params(per_stage), mesh)
        rs = np.random.RandomState(7)
        x = jnp.asarray(rs.randn(16, D).astype(np.float32))
        y = jnp.asarray((rs.randn(16, D) * 0.1).astype(np.float32))

        @jax.jit
        def step(p):
            def loss(p):
                out = pipeline_spmd(stage_fn, p, x, mesh,
                                    num_microbatches=4)
                return jnp.mean((out - y) ** 2)

            l, g = jax.value_and_grad(loss)(p)
            return jax.tree.map(lambda w, gw: w - 0.3 * gw, p, g), l

        l0 = None
        for i in range(30):
            params, l = step(params)
            l0 = l0 if l0 is not None else float(l)
        assert float(l) < l0 * 0.5, (l0, float(l))

    def test_stage_count_mismatch_rejected(self):
        mesh = make_mesh(8, {"pipe": S, "data": 2})
        eight = make_params(8) + make_params(9)  # 8 stages vs pipe=4
        stacked = stack_stage_params(eight)
        x = jnp.ones((8, D), jnp.float32)
        with pytest.raises(ValueError, match="drop stages"):
            pipeline_spmd(stage_fn, stacked, x, mesh, num_microbatches=2)

    def test_composes_with_data_axis(self):
        # the data axis shards each microbatch (review finding: previously
        # both data replicas redundantly computed the full batch)
        mesh = make_mesh(8, {"pipe": S, "data": 2})
        per_stage = make_params(10)
        stacked = shard_stacked(stack_stage_params(per_stage), mesh)
        x = jnp.asarray(np.random.RandomState(11).randn(16, D)
                        .astype(np.float32))
        want = sequential(per_stage, x)
        got = pipeline_spmd(stage_fn, stacked, x, mesh, num_microbatches=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)
        # pipe-only mesh (no data axis) still works
        mesh1 = make_mesh(4, {"pipe": S})
        stacked1 = shard_stacked(stack_stage_params(per_stage), mesh1)
        got1 = pipeline_spmd(stage_fn, stacked1, x, mesh1,
                             num_microbatches=2)
        np.testing.assert_allclose(np.asarray(got1), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)


class TestPipelinedTransformer:
    """The pipeline carrying the framework's real ops: S pre-norm
    transformer blocks (MultiHeadAttention / LayerNorm / Linear) as the
    repeated stage."""

    def test_pipelined_transformer_matches_sequential(self):
        from flexflow_tpu.parallel.pipeline import transformer_block_stage

        S_, b, s, e = 4, 4, 8, 32
        mesh = make_mesh(8, {"pipe": S_, "data": 2})
        init_fn, stage = transformer_block_stage(
            embed_dim=e, num_heads=4, seq_length=s,
            batch_per_microbatch=b // 2, ffn_mult=2)
        rngs = jax.random.split(jax.random.PRNGKey(0), S_)
        per_stage = [init_fn(k) for k in rngs]
        stacked = shard_stacked(stack_stage_params(per_stage), mesh)
        x = jnp.asarray(np.random.RandomState(0).randn(b, s, e)
                        .astype(np.float32) * 0.3)
        want = x
        for p in per_stage:
            want = stage(p, want)
        got = jax.jit(lambda pp, xx: pipeline_spmd(
            stage, pp, xx, mesh, num_microbatches=2))(stacked, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_pipelined_transformer_trains(self):
        from flexflow_tpu.parallel.pipeline import transformer_block_stage

        S_, b, s, e = 4, 4, 8, 16
        mesh = make_mesh(8, {"pipe": S_, "data": 2})
        init_fn, stage = transformer_block_stage(
            embed_dim=e, num_heads=2, seq_length=s,
            batch_per_microbatch=b // 2, ffn_mult=2)
        per_stage = [init_fn(k) for k in
                     jax.random.split(jax.random.PRNGKey(1), S_)]
        params = shard_stacked(stack_stage_params(per_stage), mesh)
        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.randn(b, s, e).astype(np.float32) * 0.3)
        y = jnp.asarray((rs.randn(b, s, e) * 0.1).astype(np.float32))

        @jax.jit
        def step(p):
            def loss(p):
                out = pipeline_spmd(stage, p, x, mesh, num_microbatches=2)
                return jnp.mean((out - y) ** 2)

            l, g = jax.value_and_grad(loss)(p)
            return jax.tree.map(lambda w, gw: w - 0.05 * gw, p, g), l

        l0 = None
        for _ in range(20):
            params, l = step(params)
            l0 = l0 if l0 is not None else float(l)
        assert float(l) < l0 * 0.8, (l0, float(l))


def _build_transformer(cfg_kwargs, ff_kwargs=None, mesh=None, lr=0.001,
                       microbatches=0, **compile_kw):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 create_transformer)
    from flexflow_tpu.optimizers import SGDOptimizer

    cfg = TransformerConfig(**cfg_kwargs)
    c = FFConfig(batch_size=cfg.batch_size, seed=7, **(ff_kwargs or {}))
    c.pipeline_microbatches = microbatches
    ff = create_transformer(cfg, c)
    ff.compile(SGDOptimizer(lr=lr), LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [], mesh=mesh, **compile_kw)
    return ff


_DEEP_NARROW = dict(num_layers=8, hidden_size=64, num_heads=4,
                    seq_length=32, batch_size=16)


class TestPipelineDetection:
    def test_transformer_blocks(self):
        # detection only walks the node graph — half the _DEEP_NARROW
        # depth keeps the compile cheap without changing what is tested
        ff = _build_transformer(dict(_DEEP_NARROW, num_layers=4),
                                mesh=make_mesh(1, {"data": 1}))
        from flexflow_tpu.parallel.pipeline_detect import (
            detect_repeated_blocks)
        pb = detect_repeated_blocks(ff.executor.nodes)
        assert pb is not None
        assert pb.num_blocks == 4
        assert pb.body_in == ("input", "input")
        # tail = the classification head dense
        assert [ff.executor.nodes[i].op.name for i in pb.tail] == ["head"]

    def test_non_repeated_graph_returns_none(self):
        from flexflow_tpu import FFConfig, FFModel, LossType
        from flexflow_tpu.parallel.pipeline_detect import (
            detect_repeated_blocks)

        ff = FFModel(FFConfig(batch_size=8))
        t = ff.create_tensor((8, 16))
        t = ff.dense(t, 32)
        t = ff.dense(t, 4)  # different shapes: not repeated blocks
        ff.compile(loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE)
        assert detect_repeated_blocks(ff.executor.nodes) is None


class TestPipelineLowering:
    """FFModel.compile lowers a 'pipe' mesh onto PipelineGraphExecutor
    (VERDICT r3 Next #1: pipeline as a framework capability, not a
    library demo)."""

    @pytest.mark.slow
    def test_explicit_pipe_mesh_matches_single_device(self):
        from flexflow_tpu.parallel.pipeline_exec import (
            BODY_KEY, PipelineGraphExecutor)

        rs = np.random.RandomState(0)
        x = rs.randn(16, 32, 64).astype(np.float32)
        y = rs.randn(16, 32, 1).astype(np.float32)
        ff_pipe = _build_transformer(
            _DEEP_NARROW, mesh=make_mesh(8, {"pipe": 4, "data": 2}),
            microbatches=4)
        assert isinstance(ff_pipe.executor, PipelineGraphExecutor)
        # body params stacked [R, ...] and sharded over the pipe axis
        leaf = ff_pipe.params[BODY_KEY]["op4"]["kernel"]
        assert leaf.shape[0] == 8
        assert "pipe" in jax.tree.leaves(leaf.sharding.spec)[0:1][0] or \
            leaf.sharding.spec[0] == "pipe"
        ff_ref = _build_transformer(_DEEP_NARROW,
                                    mesh=make_mesh(1, {"data": 1}))
        for lname, sub in ff_ref.params.items():
            for pname in sub:
                ff_pipe.set_parameter(lname,
                                      ff_ref.get_parameter(lname, pname),
                                      pname)
        np.testing.assert_allclose(ff_pipe.predict(x), ff_ref.predict(x),
                                   rtol=1e-5, atol=1e-5)
        for ff in (ff_pipe, ff_ref):
            ff.fit(x, y, epochs=3, verbose=False)
        np.testing.assert_allclose(ff_pipe.get_parameter("ffn1_2"),
                                   ff_ref.get_parameter("ffn1_2"),
                                   rtol=2e-3, atol=2e-4)

    @pytest.mark.slow
    def test_search_picks_pipe_and_executes(self):
        """Deep-narrow transformer on the 8-device mesh: the search must
        DISCOVER a pipe>1 mesh and the compiled model must train."""
        rs = np.random.RandomState(0)
        x = rs.randn(16, 32, 64).astype(np.float32)
        y = rs.randn(16, 32, 1).astype(np.float32)
        # lr 1e-3 diverges on this random-data fixture (pre-existing:
        # also at the PR-4 seed) — 3e-4 trains monotonically
        ff = _build_transformer(
            _DEEP_NARROW, lr=3e-4,
            ff_kwargs=dict(search_budget=4, enable_parameter_parallel=True))
        axes = dict(zip(ff.mesh.axis_names, ff.mesh.devices.shape))
        assert axes.get("pipe", 1) > 1, f"search chose {axes}"
        # the searched pipeline records its microbatch count + schedule
        pinfo = (ff.search_info or {}).get("pipeline") or {}
        assert pinfo.get("microbatches", 0) >= 1
        assert pinfo.get("schedule") in ("gpipe", "circular")
        assert ff.executor.schedule == pinfo["schedule"]
        assert ff.executor.microbatches == pinfo["microbatches"]
        from flexflow_tpu.parallel.pipeline_exec import PipelineGraphExecutor
        assert isinstance(ff.executor, PipelineGraphExecutor)
        l0 = ff.evaluate(x, y)["loss"]
        ff.fit(x, y, epochs=3, verbose=False)
        l1 = ff.evaluate(x, y)["loss"]
        assert np.isfinite(l1) and l1 < l0

    @pytest.mark.slow
    def test_checkpoint_roundtrip_with_stacked_body(self, tmp_path):
        rs = np.random.RandomState(0)
        x = rs.randn(16, 32, 64).astype(np.float32)
        y = rs.randn(16, 32, 1).astype(np.float32)
        ff = _build_transformer(
            _DEEP_NARROW, mesh=make_mesh(8, {"pipe": 2, "data": 4}),
            microbatches=4)
        ff.fit(x, y, epochs=1, verbose=False)
        w0 = ff.get_parameter("ffn1_3")
        path = str(tmp_path / "pipe_ck")
        ff.save_checkpoint(path)
        ff.fit(x, y, epochs=1, verbose=False)
        assert ff.load_checkpoint(path) == 1
        np.testing.assert_allclose(ff.get_parameter("ffn1_3"), w0,
                                   rtol=1e-6, atol=1e-7)


class TestPipelineSearchCostModel:
    """Native GPipe cost model (simulated v4-32, deviceless)."""

    @pytest.mark.slow
    def test_pipe_beats_dp_tp_on_deep_narrow(self):
        from flexflow_tpu.machine import MachineSpec
        from flexflow_tpu.search.native import available, native_optimize
        from flexflow_tpu.search.unity import (machine_to_json,
                                               serialize_graph)
        from flexflow_tpu.parallel.pipeline_detect import (
            detect_repeated_blocks, pipeline_meta_json)

        if not available():
            pytest.skip("native search unavailable")
        ff = _build_transformer(
            dict(num_layers=32, hidden_size=256, num_heads=8,
                 seq_length=128, batch_size=32),
            ff_kwargs=dict(only_data_parallel=True, workers_per_node=1),
            mesh=None)
        nodes = ff.executor.nodes
        pb = detect_repeated_blocks(nodes)
        assert pb is not None and pb.num_blocks == 32
        machine = machine_to_json(
            MachineSpec(chip="tpu-v4", chips_per_slice=32), 32)
        base = dict(budget=4, alpha=0.05, training=True, overlap=True,
                    batch=32, opt_state_factor=0.0, seed=42, rules=[])
        req = dict(nodes=serialize_graph(nodes), machine=machine,
                   measured={},
                   config=dict(base, enable_parameter_parallel=True),
                   pipeline=pipeline_meta_json(nodes, pb))
        r = native_optimize(req)
        assert r["mesh"].get("pipe", 1) > 1, r["mesh"]
        assert r.get("pipeline", {}).get("microbatches", 0) >= 1
        # the schedule is searched alongside M (gpipe vs circular priced)
        assert r["pipeline"].get("schedule") in ("gpipe", "circular")
        # must beat the best strategy the search finds WITHOUT pipe
        r2 = native_optimize(dict(
            req, config=dict(base, enable_parameter_parallel=True,
                             enable_pipeline_parallel=False)))
        assert r["predicted_time"] < r2["predicted_time"]

    def test_disable_flag_respected(self):
        rs = np.random.RandomState(0)
        # the flag gate is depth-independent — 4 layers compile ~2x
        # faster than the full _DEEP_NARROW and still offer pipe splits
        ff = _build_transformer(
            dict(_DEEP_NARROW, num_layers=4),
            ff_kwargs=dict(search_budget=4, enable_parameter_parallel=True,
                           enable_pipeline_parallel=False))
        axes = dict(zip(ff.mesh.axis_names, ff.mesh.devices.shape))
        assert axes.get("pipe", 1) == 1


# ---------------------------------------------------------------------------
# circular schedule + sharded microbatch queue (pipeline overhaul, ISSUE 5)


R8 = 2 * S  # 8 blocks over 4 stages: k = 2 rounds per microbatch


def _make_blocks(seed, n=R8):
    rs = np.random.RandomState(seed)
    return [{"w": jnp.asarray(rs.randn(D, D).astype(np.float32) * 0.3),
             "b": jnp.asarray(rs.randn(D).astype(np.float32) * 0.1)}
            for _ in range(n)]


def _block_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _seq_blocks(blocks, x):
    for p in blocks:
        x = _block_fn(p, x)
    return x


class TestCircularSchedule:
    """stage s holds blocks s, s+S, ... and runs one block per tick; a
    microbatch circulates the ring k times (bubble (S-1)/(kM+S-1))."""

    def _stacked(self, blocks, mesh):
        order = circular_block_order(len(blocks), S)
        return shard_stacked(stack_stage_params(blocks, order=order), mesh)

    @pytest.mark.parametrize("shard_queue", [False, True])
    @pytest.mark.parametrize("microbatches", [4, 8])
    def test_matches_sequential_bitwise(self, shard_queue, microbatches):
        mesh = make_mesh(8, {"pipe": S, "data": 2})
        blocks = _make_blocks(0)
        stacked = self._stacked(blocks, mesh)
        x = jnp.asarray(np.random.RandomState(1).randn(16, D)
                        .astype(np.float32))
        # the sequential model on the rows ONE device holds of a
        # microbatch (16 rows / microbatches / data axis of 2). At eight
        # microbatches that is one row, and XLA:CPU sums a [1, D] @ [D, D]
        # product (a matrix-vector one) in another order than a product
        # of two rows or more: the sequential model alone, run a row at a
        # time, differs from itself on all 16 rows in the same 193 of 256
        # elements (by 4.8e-7 after the first product, 6.8e-7 after eight
        # blocks) in which the schedule did. Against the rows it really
        # multiplies the schedule is exact at either count.
        rows = 16 // microbatches // 2
        want = np.concatenate([np.asarray(_seq_blocks(blocks, x[i:i + rows]))
                               for i in range(0, 16, rows)])
        got = pipeline_spmd(_block_fn, stacked, x, mesh,
                            num_microbatches=microbatches,
                            stage_leading_dim=True, schedule="circular",
                            shard_queue=shard_queue)
        # same per-microbatch computation graph, scheduled differently:
        # f32 results are bit-identical, not merely close
        np.testing.assert_array_equal(np.asarray(got), want)
        if rows > 1:    # and a product of several rows is the whole batch's
            np.testing.assert_array_equal(
                want, np.asarray(_seq_blocks(blocks, x)))

    def test_gradients_match_sequential(self):
        mesh = make_mesh(8, {"pipe": S, "data": 2})
        blocks = _make_blocks(2)
        order = circular_block_order(R8, S)
        stacked = self._stacked(blocks, mesh)
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(16, D).astype(np.float32))
        y = jnp.asarray(rs.randn(16, D).astype(np.float32))

        def loss_pipe(p):
            out = pipeline_spmd(_block_fn, p, x, mesh, num_microbatches=4,
                                stage_leading_dim=True, schedule="circular",
                                shard_queue=True)
            return jnp.mean((out - y) ** 2)

        def loss_seq(bl):
            return jnp.mean((_seq_blocks(bl, x) - y) ** 2)

        g_pipe = jax.jit(jax.grad(loss_pipe))(stacked)
        g_seq = jax.grad(loss_seq)(blocks)
        for row, b in enumerate(order):
            for k in ("w", "b"):
                np.testing.assert_allclose(
                    np.asarray(g_pipe[k][row]), np.asarray(g_seq[b][k]),
                    rtol=5e-4, atol=5e-6)

    def test_rejects_too_few_microbatches(self):
        # a returning microbatch would overtake the recirculation buffer
        mesh = make_mesh(8, {"pipe": S, "data": 2})
        stacked = self._stacked(_make_blocks(4), mesh)
        x = jnp.ones((16, D), jnp.float32)
        with pytest.raises(ValueError, match="microbatches >= stages"):
            pipeline_spmd(_block_fn, stacked, x, mesh, num_microbatches=2,
                          stage_leading_dim=True, schedule="circular")


class TestShardedQueue:
    """queue + output buffer sharded over the pipe axis; results must be
    bit-identical to the replicated-queue lowering."""

    def test_bitwise_matches_replicated(self):
        mesh = make_mesh(8, {"pipe": S, "data": 2})
        per_stage = _make_blocks(5, n=S)
        stacked = shard_stacked(stack_stage_params(per_stage), mesh)
        x = jnp.asarray(np.random.RandomState(6).randn(16, D)
                        .astype(np.float32))
        outs = {}
        for sq in (False, True):
            outs[sq] = np.asarray(pipeline_spmd(
                _block_fn, stacked, x, mesh, num_microbatches=8,
                shard_queue=sq))
        np.testing.assert_array_equal(outs[False], outs[True])

    def test_indivisible_microbatches_fall_back(self):
        # M=2 does not divide over 4 stages: the replicated queue runs
        mesh = make_mesh(8, {"pipe": S, "data": 2})
        per_stage = _make_blocks(7, n=S)
        stacked = shard_stacked(stack_stage_params(per_stage), mesh)
        x = jnp.asarray(np.random.RandomState(8).randn(16, D)
                        .astype(np.float32))
        want = _seq_blocks(per_stage, x)
        got = pipeline_spmd(_block_fn, stacked, x, mesh, num_microbatches=2,
                            shard_queue=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)


_PIPE_TINY = dict(num_layers=4, hidden_size=32, num_heads=2,
                  seq_length=8, batch_size=16)

_parity_cache = {}


def _pipe_variant(tag):
    """Compiled tiny transformer (Adam) + its 3-step seeded f32 loss
    trajectory, cached per variant (several tests share the builds)."""
    if tag in _parity_cache:
        return _parity_cache[tag]
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 create_transformer)
    from flexflow_tpu.optimizers import AdamOptimizer
    variants = {
        "single": dict(mesh_axes={"data": 1}),
        "gpipe_repl": dict(mesh_axes={"pipe": 2, "data": 2},
                           ff_kwargs=dict(pipeline_schedule="gpipe",
                                          pipeline_shard_queue=False)),
        "circ_shard": dict(mesh_axes={"pipe": 2, "data": 2},
                           ff_kwargs=dict(pipeline_schedule="circular")),
        "circ_wus": dict(mesh_axes={"pipe": 2, "data": 2},
                         ff_kwargs=dict(pipeline_schedule="circular",
                                        weight_update_sharding="on")),
    }
    kw = variants[tag]
    mesh_axes = kw["mesh_axes"]
    cfg = TransformerConfig(**_PIPE_TINY)
    c = FFConfig(batch_size=cfg.batch_size, seed=7, **(kw.get("ff_kwargs")
                                                       or {}))
    if "pipe" in mesh_axes:
        c.pipeline_microbatches = 4
    ff = create_transformer(cfg, c)
    ff.compile(AdamOptimizer(alpha=1e-2),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [],
               mesh=make_mesh(int(np.prod(list(mesh_axes.values()))),
                              mesh_axes))
    # no weight copying between variants: every executor draws the init
    # keys in graph order, so one seed gives all of them the same weights
    rs = np.random.RandomState(0)
    x = rs.randn(16, 8, 32).astype(np.float32)
    y = rs.randn(16, 8, 1).astype(np.float32)
    losses = []
    for _ in range(3):
        ff.set_batch(x, y)
        ff.forward(); ff.backward(); ff.update()
        losses.append(np.float32(ff._last_loss))
    _parity_cache[tag] = (ff, losses)
    return _parity_cache[tag]


class TestPipelineSchedulesEndToEnd:
    """FFModel-level seeded f32 training parity on the pp=2 host-device
    mesh (acceptance: circular + sharded-queue == GPipe baseline)."""

    # only tier-1 user of the gpipe_repl build (~18s of the 37s leg);
    # TestCircularSchedule asserts circular+sharded bitwise parity at
    # the functional layer, pp_x_dp keeps the FFModel-level leg cheap
    @pytest.mark.slow
    def test_circular_sharded_matches_gpipe_replicated(self):
        _, base = _pipe_variant("gpipe_repl")
        ff, circ = _pipe_variant("circ_shard")
        from flexflow_tpu.parallel.pipeline_exec import PipelineGraphExecutor
        assert isinstance(ff.executor, PipelineGraphExecutor)
        assert ff.executor.schedule == "circular"
        assert ff.executor.shard_queue
        for a, b in zip(base, circ):
            # bit-for-bit: same per-microbatch math, different schedule
            assert a.tobytes() == b.tobytes(), (base, circ)

    @pytest.mark.slow
    def test_pp_x_dp_matches_single_device(self):
        """pp=2 x dp=2 *training* composition vs single-device f32 (the
        previously-untested leg: forward parity and pp-only training were
        covered, pp x dp training was not). Slow tier (t1 budget,
        with test_loss_parity_vs_plain_sync — together they retire the
        circ_shard build from tier-1): functional-layer bitwise parity
        (TestCircularSchedule) and the circ_wus trajectory checks keep
        the pp x dp path covered."""
        _, single = _pipe_variant("single")
        _, pipe = _pipe_variant("circ_shard")
        assert all(np.isfinite(v) for v in pipe)
        np.testing.assert_allclose(np.asarray(pipe), np.asarray(single),
                                   rtol=1e-5, atol=1e-6)


class TestPipelineWUS:
    """Weight-update sharding at pp > 1 (previously the lowering kept
    plain sync): reduce-scatter body-grad sync composing with the
    pipe-stacked leading dim, sharded f32 master + moments, all-gather
    inside the optimizer fusion — the tests/test_wus.py invariants."""

    def test_master_and_moments_shard_pipe_x_data(self):
        from flexflow_tpu.parallel.pipeline_exec import BODY_KEY
        ff, losses = _pipe_variant("circ_wus")
        assert ff.executor.weight_update_sharding
        assert all(np.isfinite(v) for v in losses)
        sharded = 0
        for key, sub in ff.opt_state["m"][BODY_KEY].items():
            for pname, arr in sub.items():
                spec = arr.sharding.spec
                assert spec and spec[0] == "pipe", (key, pname, spec)
                if "data" in tuple(spec):
                    sharded += 1
        assert sharded > 0  # data axis actually landed on the moments

    @pytest.mark.slow
    def test_loss_parity_vs_plain_sync(self):
        # slow tier (t1 budget): retires the circ_shard build from
        # tier-1; WUS-vs-sync bitwise parity stays tier-1 in
        # tests/test_wus.py on the 8-way data mesh
        _, plain = _pipe_variant("circ_shard")
        _, wus = _pipe_variant("circ_wus")
        np.testing.assert_allclose(np.asarray(wus), np.asarray(plain),
                                   rtol=1e-6)

    def test_wus_specs_pass_fflint(self):
        from flexflow_tpu.analysis import LintContext, run_passes
        from flexflow_tpu.analysis.passes.sharding import (
            ShardingLegalityPass)
        ff, _ = _pipe_variant("circ_wus")
        specs = ff.executor.wus_param_specs()
        assert specs, "WUS sharded no body leaves"
        ctx = LintContext(nodes=ff.executor.nodes, mesh=ff.mesh,
                          strategy=ff.strategy, ff=ff)
        rep = run_passes(ctx, [ShardingLegalityPass()])
        assert not rep.errors, [d.format() for d in rep.errors]


class TestPipelineFflintClean:
    """Acceptance: the pipelined (WUS) strategy's collective census is
    priced — the collective-inference pass replays pipe strategies
    through simulate_pipeline and reports no FFL2xx errors."""

    def test_pipelined_wus_census_is_priced(self):
        from flexflow_tpu.analysis import LintContext, run_passes
        from flexflow_tpu.analysis.passes.collectives import (
            CollectiveInferencePass, infer_strategy_collectives)
        from flexflow_tpu.search.native import available
        ff, _ = _pipe_variant("circ_wus")
        ctx = LintContext(nodes=ff.executor.nodes, mesh=ff.mesh,
                          strategy=ff.strategy, ff=ff)
        inferred = infer_strategy_collectives(ctx)
        assert "ppermute" in inferred, inferred  # the pipeline hop
        if ff.executor.weight_update_sharding:
            assert "allgather" in inferred, inferred  # the WUS gather
        if not available():
            pytest.skip("native search unavailable")
        rep = run_passes(ctx, [CollectiveInferencePass()])
        assert rep.passes["collective-inference"] == "ok", rep.passes
        bad = [d for d in rep.errors if d.rule.startswith("FFL2")]
        assert not bad, "\n".join(d.format() for d in bad)


@pytest.mark.slow
class TestShardedQueueMemory:
    """Acceptance: compiled HBM peak (XLA memory_analysis) of the
    pipelined transformer fixture drops >= 25% with the sharded
    microbatch queue at pp=4 vs the replicated-queue baseline. Measured
    on the forward executable — the queue/output buffers are the
    pipeline's persistent activation memory; the training peak is
    dominated by saved-for-backward residuals the queue layout does not
    touch (it still must not regress). Marked slow (two full compiles);
    the tier-1 proxy is the native memory model's sharded-vs-replicated
    assertion plus the bench hbm_peak_bytes ratchet."""

    @staticmethod
    def _build(shard_queue):
        from flexflow_tpu.config import FFConfig
        from flexflow_tpu.ffconst import LossType
        from flexflow_tpu.models.transformer import (TransformerConfig,
                                                     create_transformer)
        from flexflow_tpu.optimizers import AdamOptimizer
        cfg = TransformerConfig(num_layers=4, hidden_size=64, num_heads=2,
                                seq_length=32, batch_size=128)
        c = FFConfig(batch_size=128, seed=7)
        c.pipeline_shard_queue = shard_queue
        c.pipeline_microbatches = 8
        ff = create_transformer(cfg, c)
        ff.compile(AdamOptimizer(alpha=1e-3),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [],
                   mesh=make_mesh(4, {"pipe": 4}))
        return ff

    def test_forward_hbm_peak_drops_25pct_at_pp4(self):
        peaks = {}
        for sq in (False, True):
            ff = self._build(sq)
            rs = np.random.RandomState(0)
            x = ff._stage_inputs([rs.randn(128, 32, 64).astype(np.float32)])
            fwd = ff.executor.make_forward(training=False)
            ma = fwd.lower(ff.params, ff.state, x,
                           jax.random.PRNGKey(0)).compile().memory_analysis()
            peaks[sq] = (ma.argument_size_in_bytes
                         + ma.temp_size_in_bytes)
        assert peaks[True] <= 0.75 * peaks[False], peaks


class TestPipelineNativePricing:
    """Acceptance: ffs_simulate prices gpipe vs circular and M=2S vs
    larger M distinctly, and the `_wus` choice twins exist at pp > 1."""

    B, DIM = 128, 512

    def _chain(self):
        nodes = []
        for i in range(1, 5):
            nodes.append({
                "guid": i, "type": "LINEAR", "name": f"l{i}",
                "inputs": [[i - 1 if i > 1 else -1, 0]],
                "input_shapes": [[self.B, self.DIM]],
                "output_shapes": [[self.B, self.DIM]],
                "roles": [["sample", "channel"]],
                "params": {"kernel": [self.DIM, self.DIM],
                           "bias": [self.DIM]},
                "flops": 2.0 * self.B * self.DIM * self.DIM,
                "dtype_size": 4, "attrs": {},
            })
        return nodes

    def _simulate(self, choice, M, schedule, shard_queue=True):
        from flexflow_tpu.search.native import native_simulate
        machine = {"num_devices": 4, "flops": 197e12, "hbm_bw": 0.82e12,
                   "hbm_cap": 16e9, "ici_bw": 45e9, "ici_latency": 1e-6,
                   "dcn_bw": 25e9, "dcn_latency": 1e-5, "num_slices": 1}
        meta = dict(num_blocks=4, body=[1, 2, 3, 4], head=[], tail=[],
                    block_out_bytes=self.B * self.DIM * 4.0, batch=self.B,
                    microbatches=M, schedule=schedule,
                    shard_queue=shard_queue)
        return native_simulate({
            "nodes": self._chain(), "machine": machine, "measured": {},
            "config": {"training": True, "overlap": True,
                       "opt_state_factor": 2.0},
            "mesh": {"data": 2, "model": 1, "seq": 1, "expert": 1,
                     "pipe": 2},
            "pipeline": meta,
            "assignment": {str(i): choice for i in range(1, 5)}})

    def test_schedule_and_microbatches_priced_distinctly(self):
        from flexflow_tpu.search.native import available
        if not available():
            pytest.skip("native search unavailable")
        times = {}
        for sched in ("gpipe", "circular"):
            for M in (4, 8, 16):
                times[(sched, M)] = \
                    self._simulate("dp", M, sched)["iteration_time"]
        assert len(set(times.values())) == len(times), times
        # the bubble term: more microbatches shrink the gpipe bubble's
        # share, and circular runs kM+S-1 ticks of 1/k-sized stages
        assert times[("gpipe", 4)] != times[("circular", 4)]

    def test_wus_twins_enumerated_and_priced_at_pp(self):
        from flexflow_tpu.search.native import available
        if not available():
            pytest.skip("native search unavailable")
        r_dp = self._simulate("dp", 4, "gpipe")
        r_wus = self._simulate("dp_wus", 4, "gpipe")
        kinds = {t["collective"] for t in r_wus["tasks"]
                 if t.get("collective")}
        assert {"allreduce", "allgather", "ppermute"} <= kinds, kinds
        kinds_dp = {t["collective"] for t in r_dp["tasks"]
                    if t.get("collective")}
        assert "allgather" not in kinds_dp
        # sharded optimizer state: the twin's memory is strictly lower
        assert r_wus["memory"] < r_dp["memory"]

    def test_sharded_vs_replicated_queue_memory(self):
        from flexflow_tpu.search.native import available
        if not available():
            pytest.skip("native search unavailable")
        shard = self._simulate("dp", 4, "gpipe", shard_queue=True)
        repl = self._simulate("dp", 4, "gpipe", shard_queue=False)
        assert shard["memory"] < repl["memory"]

    def test_circular_recirc_window_hbm_drop(self):
        """Acceptance: the k>1 circular schedule's stage-0
        recirculation buffer is windowed to the M-S+1 in-flight slots
        in BOTH queue lowerings (a value banked at global step u is
        consumed exactly M ticks later, so only M-S+1 slots are ever
        live) — the replicated-queue fallback no longer pays the
        full-M-slot ring (ISSUE 20 satellite: the last pipeline memory
        gap). The circular-over-gpipe memory premium is therefore
        exactly block_out/dp * (M-S+1)/M regardless of queue sharding —
        a drop of block_out/dp * (S-1)/M on the replicated path vs the
        unwindowed model."""
        from flexflow_tpu.search.native import available
        if not available():
            pytest.skip("native search unavailable")
        M, dp, pp = 8, 2, 2  # num_blocks=4 -> k=2 rounds: recirc live
        mems = {(sched, sq): self._simulate(
                    "dp", M, sched, shard_queue=sq)["memory"]
                for sched in ("gpipe", "circular") for sq in (True, False)}
        recirc = self.B * self.DIM * 4.0 / dp * (M - pp + 1) / M
        for sq in (True, False):
            premium = mems[("circular", sq)] - mems[("gpipe", sq)]
            assert premium == pytest.approx(recirc, rel=1e-9), mems
        # queue sharding still saves the same bytes under either
        # schedule (the recirc window itself is schedule-only now)
        circ_gap = mems[("circular", False)] - mems[("circular", True)]
        gpipe_gap = mems[("gpipe", False)] - mems[("gpipe", True)]
        assert circ_gap == pytest.approx(gpipe_gap, rel=1e-9)
        assert gpipe_gap > 0.0, mems

    def test_searched_pipe_strategy_picks_wus_twins(self):
        """Acceptance: the searched pipeline strategy at pp > 1
        enumerates the `_wus` twins — a memory-capped search on a deep
        param-heavy chain lands on a pipe x data mesh with every body
        op's choice the reduce-scatter twin, plus a searched microbatch
        count and schedule."""
        from flexflow_tpu.search.native import available, native_optimize
        if not available():
            pytest.skip("native search unavailable")
        b, d, R = 4096, 2048, 4
        nodes = []
        for i in range(1, R + 1):
            nodes.append({
                "guid": i, "type": "LINEAR", "name": f"l{i}",
                "inputs": [[i - 1 if i > 1 else -1, 0]],
                "input_shapes": [[b, d]], "output_shapes": [[b, d]],
                "roles": [["sample", "channel"]],
                "params": {"kernel": [d, d], "bias": [d]},
                "flops": 2.0 * b * d * d, "dtype_size": 4, "attrs": {},
            })
        machine = {"num_devices": 8, "flops": 197e12, "hbm_bw": 0.82e12,
                   "hbm_cap": 9e7,  # dp=8 (even with WUS) does not fit
                   "ici_bw": 45e9, "ici_latency": 1e-6,
                   "dcn_bw": 25e9, "dcn_latency": 1e-5, "num_slices": 1}
        meta = dict(num_blocks=R, body=list(range(1, R + 1)), head=[],
                    tail=[], block_out_bytes=b * d * 4.0, batch=b)
        r = native_optimize(dict(
            nodes=nodes, machine=machine, measured={},
            config=dict(budget=2, alpha=0.05, training=True, overlap=True,
                        batch=b, opt_state_factor=2.0, seed=42, rules=[],
                        enable_parameter_parallel=False,
                        enable_substitution=False),
            pipeline=meta))
        mesh = r["mesh"]
        assert mesh.get("pipe", 1) > 1 and mesh.get("data", 1) > 1, mesh
        choices = {v["choice"] for v in r["ops"].values()}
        # the memory-capped search must keep picking the WUS dimension;
        # since ISSUE 9 the latency-hiding "_ovl" twin of a "_wus" choice
        # (dp_wus_ovl) also satisfies it — suffix order is base[_wus][_ovl]
        assert all("_wus" in c for c in choices), choices
        pj = r.get("pipeline") or {}
        assert pj.get("microbatches", 0) >= 2 * mesh["pipe"]
        assert pj.get("schedule") in ("gpipe", "circular"), pj
