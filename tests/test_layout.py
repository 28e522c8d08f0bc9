"""Conv-family layout overhaul tests (ISSUE 2).

NHWC execution-layout parity vs the NCHW reference path (fwd + bwd, on
CPU), the layout-propagation pass's once-per-chain transpose guarantee,
execution-time Conv+BN(+ReLU) folding parity, the census byte-volume
ratchet, and the _declared_seq multi-extent fix.
"""

import numpy as np
import pytest

import jax

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.ffconst import ActiMode, OperatorType, PoolType

RS = np.random.RandomState(0)
B = 4


def build_conv_chain(layout, fold=True, batch=B):
    """conv -> bn(relu) -> pool -> conv(relu) -> groupnorm -> flat -> dense:
    one conv chain exercising every NHWC-capable op plus pass-through."""
    ff = FFModel(FFConfig(batch_size=batch, only_data_parallel=True,
                          conv_compute_layout=layout, fold_conv_bn=fold))
    t = ff.create_tensor((batch, 3, 16, 16))
    x = ff.conv2d(t, 8, 3, 3, 1, 1, 1, 1)
    x = ff.batch_norm(x, relu=True)
    x = ff.pool2d(x, 2, 2, 2, 2, 0, 0, pool_type=PoolType.POOL_AVG)
    x = ff.conv2d(x, 8, 3, 3, 1, 1, 1, 1, activation=ActiMode.AC_MODE_RELU)
    x = ff.group_norm(x, 4)
    x = ff.flat(x)
    out = ff.dense(x, 10)
    ff.compile(SGDOptimizer(lr=0.05),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [], outputs=out)
    return ff


def build_branchy(layout):
    """Inception-style diamond: one producer feeds parallel conv branches
    that concat on the channel axis — the case where per-op transposes
    would multiply but per-chain placement must not."""
    ff = FFModel(FFConfig(batch_size=B, only_data_parallel=True,
                          conv_compute_layout=layout))
    t = ff.create_tensor((B, 4, 12, 12))
    s = ff.conv2d(t, 8, 3, 3, 1, 1, 1, 1, activation=ActiMode.AC_MODE_RELU)
    b1 = ff.conv2d(s, 8, 1, 1, 1, 1, 0, 0, activation=ActiMode.AC_MODE_RELU)
    b2 = ff.conv2d(s, 8, 3, 3, 1, 1, 1, 1, activation=ActiMode.AC_MODE_RELU)
    b3 = ff.pool2d(s, 3, 3, 1, 1, 1, 1, pool_type=PoolType.POOL_AVG)
    x = ff.concat([b1, b2, b3], axis=1)
    x = ff.flat(x)
    out = ff.dense(x, 5)
    ff.compile(SGDOptimizer(lr=0.05),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [], outputs=out)
    return ff


def _built(name):
    """A layer's name ends in the process's layer counter: order by it,
    not by the name (`conv2d_99` sorts after `conv2d_101` as a string,
    and two models built one after the other then pair different
    leaves)."""
    stem, _, count = str(name).rpartition("_")
    return (int(count), stem) if count.isdigit() else (-1, str(name))


def leaves(tree):
    if isinstance(tree, dict):
        return [leaf for key in sorted(tree, key=_built)
                for leaf in leaves(tree[key])]
    return [np.asarray(v) for v in jax.tree.leaves(tree)]


def max_leaf_diff(a, b):
    return max(float(np.abs(x - y).max()) for x, y in zip(leaves(a),
                                                          leaves(b)))


X = RS.randn(8, 3, 16, 16).astype(np.float32)
Y = RS.randint(0, 10, (8, 1)).astype(np.int32)


class TestNHWCParity:
    """NHWC and NCHW execution must agree numerically fwd AND bwd — the
    gradient check runs a full SGD epoch and compares every updated
    parameter and BN running stat."""

    def test_forward_parity(self):
        pa = build_conv_chain("nchw").predict(X[:B])
        pb = build_conv_chain("nhwc").predict(X[:B])
        np.testing.assert_allclose(pa, pb, rtol=1e-4, atol=1e-5)

    def test_backward_parity_via_sgd_epoch(self):
        ffa, ffb = build_conv_chain("nchw"), build_conv_chain("nhwc")
        for ff in (ffa, ffb):
            ff.fit(X, Y, batch_size=B, epochs=1, verbose=False)
        assert max_leaf_diff(ffa.params, ffb.params) < 1e-5
        sa = {k: v for k, v in ffa.state.items() if not k.startswith("__")}
        sb = {k: v for k, v in ffb.state.items() if not k.startswith("__")}
        assert max_leaf_diff(sa, sb) < 1e-5

    def test_branchy_parity(self):
        x = RS.randn(B, 4, 12, 12).astype(np.float32)
        pa = build_branchy("nchw").predict(x)
        pb = build_branchy("nhwc").predict(x)
        np.testing.assert_allclose(pa, pb, rtol=1e-4, atol=1e-5)

    def test_auto_is_nchw_on_cpu(self):
        ff = build_conv_chain("auto")
        assert ff.layout_info["enabled"] is False


class TestLayoutPass:
    def test_one_transpose_pair_per_chain(self):
        ff = build_conv_chain("nhwc")
        info = ff.layout_info
        assert info["enabled"] is True
        # every NHWC-capable op converted, and exactly ONE boundary pair:
        # input->NHWC at the first conv, NHWC->NCHW before flat
        assert info["nhwc_ops"] == 5
        assert info["transposes"] == 2

    def test_branchy_still_one_pair(self):
        ff = build_branchy("nhwc")
        info = ff.layout_info
        # 3 branch heads + concat + stem conv compute NHWC, but the
        # branches share the stem's NHWC value: still one pair total
        assert info["nhwc_ops"] == 5
        assert info["transposes"] == 2

    def test_exec_layout_set_on_ops(self):
        ff = build_conv_chain("nhwc")
        by_type = {}
        for n in ff.executor.nodes:
            by_type.setdefault(n.op.op_type, n.op)
        for t in (OperatorType.CONV2D, OperatorType.POOL2D,
                  OperatorType.BATCHNORM, OperatorType.GROUPNORM):
            assert getattr(by_type[t], "exec_layout", "NCHW") == "NHWC"
        # flat/dense stay on the boundary layout
        assert getattr(by_type[OperatorType.FLAT], "exec_layout",
                       "NCHW") == "NCHW"


class TestConvBNFold:
    def _trained_pair(self, layout="nchw"):
        """Same weights, fold on vs off, after a training epoch (so BN
        running stats are non-trivial)."""
        ffa = build_conv_chain(layout, fold=True)
        ffb = build_conv_chain(layout, fold=False)
        # align initial params by GRAPH order (param dicts come back
        # key-sorted from jit, and guid-suffixed names don't sort stably
        # across builds); copy through host — the jitted step donates its
        # param buffers, so aliasing them between models would leave the
        # second model holding deleted arrays
        import jax.numpy as jnp
        names_a = [n.op.name for n in ffa.executor.nodes
                   if n.op.name in ffa.params]
        names_b = [n.op.name for n in ffb.executor.nodes
                   if n.op.name in ffb.params]
        for ka, kb in zip(names_a, names_b):
            for pn in ffa.params[ka]:
                ffb.params[kb][pn] = jnp.asarray(np.asarray(ffa.params[ka][pn]))
        ffb._compute_params_dirty = True
        ffa.fit(X, Y, batch_size=B, epochs=1, verbose=False)
        ffb.fit(X, Y, batch_size=B, epochs=1, verbose=False)
        return ffa, ffb

    def test_fold_applied_to_inference_nodes_only(self):
        ff = build_conv_chain("nchw", fold=True)
        full = ff.executor.nodes
        folded = ff.executor._inference_nodes()
        assert len(folded) == len(full) - 1  # conv+bn pair collapsed
        names = [n.op.name for n in folded]
        assert any("+" in n for n in names)
        # training step untouched
        assert len(ff.executor.nodes) == len(full)

    def test_fold_parity_eval_and_predict(self):
        ffa, ffb = self._trained_pair()
        pa, pb = ffa.predict(X[:B]), ffb.predict(X[:B])
        np.testing.assert_allclose(pa, pb, rtol=1e-4, atol=1e-5)
        ea = ffa.evaluate(X, Y, batch_size=B)
        eb = ffb.evaluate(X, Y, batch_size=B)
        assert abs(ea["loss"] - eb["loss"]) < 1e-4

    def test_fold_parity_nhwc(self):
        ffa, ffb = self._trained_pair("nhwc")
        np.testing.assert_allclose(ffa.predict(X[:B]), ffb.predict(X[:B]),
                                   rtol=1e-4, atol=1e-5)

    def test_conv_with_activation_not_folded(self):
        """A conv that owns an activation cannot fold into the BN."""
        ff = FFModel(FFConfig(batch_size=B, only_data_parallel=True))
        t = ff.create_tensor((B, 3, 8, 8))
        x = ff.conv2d(t, 4, 3, 3, 1, 1, 1, 1,
                      activation=ActiMode.AC_MODE_RELU)
        x = ff.batch_norm(x, relu=False)
        x = ff.flat(x)
        out = ff.dense(x, 3)
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [],
                   outputs=out)
        assert len(ff.executor._inference_nodes()) == len(ff.executor.nodes)


class TestBf16ConvCoverage:
    def test_convs_compute_bf16_under_master_weights(self):
        """The master-weight regime's bf16 compute must actually COVER
        the conv family: every convolution in the compiled train step
        runs on bf16 operands (the BN statistics deliberately stay f32 —
        conv.py). Compiling against a TPU machine spec selects bf16 even
        on the CPU backend, so the emitted HLO is checkable here."""
        from flexflow_tpu.machine import MachineSpec
        from flexflow_tpu.search.validate import train_step_hlo

        ff = FFModel(FFConfig(batch_size=B, only_data_parallel=True,
                              conv_compute_layout="nhwc"))
        t = ff.create_tensor((B, 3, 8, 8))
        x = ff.conv2d(t, 4, 3, 3, 1, 1, 1, 1)
        x = ff.batch_norm(x, relu=True)
        x = ff.flat(x)
        out = ff.dense(x, 3)
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [],
                   machine_spec=MachineSpec(chip="tpu-v5e"), outputs=out)
        import jax.numpy as jnp
        assert ff.executor.compute_dtype == jnp.bfloat16
        hlo = train_step_hlo(ff)
        conv_lines = [l for l in hlo.splitlines() if "convolution(" in l]
        assert conv_lines, "no convolution in the compiled step"
        f32_convs = [l for l in conv_lines if "f32[" in l.split(" = ")[0]
                     and "bf16" not in l]
        assert not f32_convs, f"f32 convolutions leaked: {f32_convs[:2]}"


class TestNHWCOpMeasurable:
    def test_profile_measures_nhwc_conv_standalone(self):
        """The roofline/calibration channel must be able to time NHWC
        ops: example inputs follow the execution layout."""
        from flexflow_tpu.search.profile import measure_op, op_cost_key

        ff = build_conv_chain("nhwc", batch=2)
        conv = next(n.op for n in ff.executor.nodes
                    if n.op.op_type == OperatorType.CONV2D)
        assert conv.exec_layout == "NHWC"
        fwd, bwd = measure_op(conv, repeats=1, warmup=0)
        assert fwd > 0 and bwd > 0
        # layout is part of the measurement identity
        nchw = build_conv_chain("nchw", batch=2)
        conv2 = next(n.op for n in nchw.executor.nodes
                     if n.op.op_type == OperatorType.CONV2D)
        assert op_cost_key(conv) != op_cost_key(conv2)


class TestCensusByteRatchet:
    def _bench(self):
        import importlib
        import bench
        return importlib.reload(bench)

    def test_first_run_records_baseline(self):
        bench = self._bench()
        hist = {}
        reg, base = bench.census_ratchet(hist, "fam:cpu", 1024.0)
        assert reg is False and base is None
        assert hist["fam:cpu"]["collective_bytes"] == 1024.0

    def test_regression_flagged_and_baseline_kept(self):
        bench = self._bench()
        hist = {"fam:cpu": {"collective_bytes": 1000.0,
                            "samples_per_s": 5.0}}
        reg, base = bench.census_ratchet(hist, "fam:cpu", 1200.0)
        assert reg is True and base == 1000.0
        assert hist["fam:cpu"]["collective_bytes"] == 1000.0

    def test_lower_bytes_ratchet_down(self):
        bench = self._bench()
        hist = {"fam:cpu": {"collective_bytes": 1000.0}}
        reg, _ = bench.census_ratchet(hist, "fam:cpu", 900.0)
        assert reg is False
        assert hist["fam:cpu"]["collective_bytes"] == 900.0

    def test_throughput_ratchet_preserves_byte_baseline(self):
        bench = self._bench()
        hist = {"fam:cpu": {"samples_per_s": 5.0,
                            "collective_bytes": 1000.0}}
        bench.ratchet(hist, "fam:cpu", 6.0, {"bs": 8}, "best1x5")
        assert hist["fam:cpu"]["collective_bytes"] == 1000.0
        assert hist["fam:cpu"]["samples_per_s"] == 6.0

    def test_equal_volume_green(self):
        bench = self._bench()
        hist = {"fam:cpu": {"collective_bytes": 1000.0}}
        reg, _ = bench.census_ratchet(hist, "fam:cpu", 1000.0)
        assert reg is False


class TestDeclaredSeqMultiExtent:
    def test_disagreeing_seq_extents_disable_bucketing(self):
        """Two rank-3 paths with different position extents: no single
        bucketable sequence — _declared_seq must return None (full-length
        path) instead of whichever op iterated last (ADVICE r5)."""
        ff = FFModel(FFConfig(batch_size=B, only_data_parallel=True))
        a = ff.create_tensor((B, 12, 8))
        b = ff.create_tensor((B, 20, 8))
        xa = ff.relu(ff.dense(a, 8))
        xb = ff.relu(ff.dense(b, 8))
        x = ff.concat([xa, xb], axis=1)
        x = ff.flat(x)
        out = ff.dense(x, 4)
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [],
                   outputs=out)
        assert ff._declared_seq() is None
        # and the iteration protocol quietly runs full-length
        xs = [RS.randn(B, 12, 8).astype(np.float32),
              RS.randn(B, 20, 8).astype(np.float32)]
        y = RS.randint(0, 4, (B, 1)).astype(np.int32)
        ff.set_batch(xs, y)
        ff.forward(seq_length=10)
        ff.backward()
        ff.update()
        assert np.isfinite(float(ff._last_loss))

    def test_single_extent_still_found(self):
        ff = FFModel(FFConfig(batch_size=B, only_data_parallel=True))
        a = ff.create_tensor((B, 16, 8))
        x = ff.relu(ff.dense(a, 8))
        x = ff.flat(x)
        out = ff.dense(x, 4)
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [],
                   outputs=out)
        assert ff._declared_seq() == 16


class TestAllgatherValue:
    def test_single_process_identity(self):
        from flexflow_tpu import distributed as dist
        assert dist.allgather_value(7) == [7]


class TestRooflineReport:
    def test_report_and_markdown(self):
        from flexflow_tpu.machine import MachineSpec
        from flexflow_tpu.obs.roofline import (finish_aggregates,
                                               format_markdown,
                                               roofline_report)
        ff = build_conv_chain("nchw", batch=2)
        spec = MachineSpec(chip="cpu-sim")
        rep = roofline_report(ff.executor.nodes, spec, repeats=1,
                              include_bwd=False)
        rows = [r for r in rep["rows"] if "fwd_s" in r]
        assert rows, "no op measured"
        for r in rows:
            assert r["bound"] in ("compute", "bandwidth")
            assert r["fwd_s"] > 0
        assert "conv" in rep["classes"]
        finish_aggregates(rep["classes"],
                          rep["machine"]["peak_flops"])
        assert rep["classes"]["conv"]["efficiency"] is not None
        md = format_markdown(rep)
        assert "Per-class aggregates" in md
        assert "| conv |" in md
