"""Learned TPU cost model (ISSUE 14 tentpole): measure -> learn -> search.

Covers the four layers of flexflow_tpu/costmodel:

- corpus: fixture-trace ingestion, dedup round-trip, schema-drift
  loudness (the CI stage's contract), v1-row skip;
- model: train/predict parity through the COSTMODEL.json round-trip,
  coverage gate, hull-confidence behavior, synthetic-law recovery;
- native integration: per-candidate ``cost_source`` provenance in the
  search trace, measured > learned > analytic priority, out-of-hull
  fallback to analytic pricing, FFS_NO_LEARNED_COSTS bit-identical
  searches on the zoo (the acceptance row);
- validation surfaces: simtrace analytic-vs-learned side-by-side,
  obs_report accuracy block, fflint FFL704 staleness INFO.
"""

from __future__ import annotations

import json
import math
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "costmodel")

pytestmark = pytest.mark.costmodel


# ---------------------------------------------------------------------------
# shared fixtures


@pytest.fixture(scope="module")
def fixture_corpus():
    from flexflow_tpu.costmodel import build_corpus
    return build_corpus([FIXTURES])


@pytest.fixture(scope="module")
def trained(fixture_corpus, tmp_path_factory):
    """(model, path): trained on the committed fixture corpus and
    round-tripped through COSTMODEL.json."""
    from flexflow_tpu.costmodel import CostModel, train_model
    model = train_model(fixture_corpus)
    path = str(tmp_path_factory.mktemp("costmodel") / "COSTMODEL.json")
    model.save(path)
    return CostModel.load(path), path


def small_mlp(budget=1):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.models.mlp import create_mlp
    from flexflow_tpu.optimizers import SGDOptimizer
    cfg = FFConfig(batch_size=16)
    cfg.search_budget = budget
    cfg.enable_parameter_parallel = True
    ff = create_mlp(batch_size=16, in_dim=64, hidden_dims=(128, 128),
                    out_dim=10, ff_config=cfg)
    ff.compile(SGDOptimizer(lr=0.01),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
    return ff


def strategy_fingerprint(ff):
    """Order-stable (mesh, per-op choice+specs) identity of a searched
    strategy — the bit-identical comparison coordinate. Keyed by node
    POSITION, not name: auto-names carry the process-global guid
    counter, which differs between two models built in one process
    while the strategies themselves are identical."""
    mesh_axes = dict(zip(ff.mesh.axis_names,
                         (int(d) for d in ff.mesh.devices.shape)))
    ops = []
    for node in ff.executor.nodes:
        st = (ff.strategy or {}).get(node.op.guid)
        ops.append(dict(
            type=node.op.op_type.name,
            choice=getattr(st, "choice", None),
            outputs=[list(s) if s is not None else None
                     for s in (st.output_specs if st else [])],
            params={k: list(v)
                    for k, v in (st.param_specs if st else {}).items()},
        ))
    return json.dumps(dict(mesh=mesh_axes, ops=ops), sort_keys=True)


# ---------------------------------------------------------------------------
# corpus


class TestCorpus:
    def test_fixture_corpus_loads(self, fixture_corpus):
        rows = fixture_corpus["rows"]
        assert len(rows) >= 50
        classes = fixture_corpus["classes"]
        for cname in ("LINEAR", "CONV2D", "MULTIHEAD_ATTENTION"):
            assert classes.get(cname, 0) >= 8, classes
        for r in rows:
            assert r["schema"] == 2
            assert r["measured"]["source"] == "measured"
            assert r["io_bytes"] > 0
            assert r["flops"] >= 0

    def test_featurize_matches_native_transforms(self, fixture_corpus):
        from flexflow_tpu.costmodel import FEATURE_NAMES, featurize
        r = fixture_corpus["rows"][0]
        f = featurize(r)
        assert f.shape == (len(FEATURE_NAMES),)
        div = max(1.0, float(r["work_div"]))
        assert f[0] == pytest.approx(math.log1p(r["flops"] / div))
        assert f[1] == pytest.approx(math.log1p(r["io_bytes"] / div))
        assert f[2] == pytest.approx(math.log1p(r["param_bytes"]))
        assert f[3] == pytest.approx(math.log(div))

    def test_corpus_roundtrip(self, fixture_corpus, tmp_path):
        from flexflow_tpu.costmodel import load_corpus, save_corpus
        p = str(tmp_path / "COSTMODEL_CORPUS.json")
        save_corpus(p, fixture_corpus)
        back = load_corpus(p)
        assert back["corpus_schema"] == fixture_corpus["corpus_schema"]
        assert back["rows"] == fixture_corpus["rows"]

    def test_dedup_across_dirs(self, fixture_corpus, tmp_path):
        """The same dir ingested twice must not double-count rows."""
        from flexflow_tpu.costmodel import build_corpus
        double = build_corpus([FIXTURES, FIXTURES])
        assert len(double["rows"]) == len(fixture_corpus["rows"])
        assert double["stats"]["duplicates"] >= len(fixture_corpus["rows"])

    def test_schema_drift_fails_loudly(self, tmp_path):
        from flexflow_tpu.costmodel import (CORPUS_SCHEMA_VERSION,
                                            CorpusSchemaError,
                                            load_corpus, load_trace_dir)
        src = os.path.join(FIXTURES, "mlp_b16_r00_host00.simtrace.json")
        payload = json.load(open(src))
        payload["corpus_schema"] = CORPUS_SCHEMA_VERSION + 1
        drifted = tmp_path / "drift_r00_host00.simtrace.json"
        drifted.write_text(json.dumps(payload))
        with pytest.raises(CorpusSchemaError):
            load_trace_dir(str(tmp_path))
        # row-level drift too, and through load_corpus
        corpus = dict(schema_version=1,
                      corpus_schema=CORPUS_SCHEMA_VERSION,
                      rows=[dict(schema=CORPUS_SCHEMA_VERSION + 1,
                                 type="LINEAR")])
        cp = tmp_path / "corpus.json"
        cp.write_text(json.dumps(corpus))
        with pytest.raises(CorpusSchemaError):
            load_corpus(str(cp))

    def test_v1_rows_skipped_not_fatal(self):
        """The pre-featurization demo fixture (schema v1 rows) loads as
        zero trainable rows, counted as skipped — not an error."""
        from flexflow_tpu.costmodel import load_trace_dir
        rows, stats = load_trace_dir(
            os.path.join(REPO, "tests", "fixtures", "obs_report_dir"))
        assert rows == []
        assert stats["skipped"] >= 1

    def test_roofline_rows_ingest(self):
        """A committed ``scripts/roofline.py`` report (Inception-v3,
        NCHW, a CPU run) is corpus rows too (the conv-class coverage
        channel)."""
        from flexflow_tpu.costmodel import load_trace_dir
        rows, stats = load_trace_dir(
            os.path.join(REPO, "tests", "fixtures", "roofline"))
        assert stats["roofline_files"] >= 1
        assert any(r["type"] == "CONV2D" for r in rows)
        assert all(r["measured"]["source"] == "measured" for r in rows)


# ---------------------------------------------------------------------------
# model


class TestModel:
    def test_coverage_gate_and_heldout_error(self, trained):
        model, _ = trained
        for cname in ("LINEAR", "CONV2D", "MULTIHEAD_ATTENTION"):
            assert cname in model.classes
        # classes under MIN_CLASS_ROWS fixture rows stay analytic
        assert "FLAT" not in model.classes
        for cm in model.classes.values():
            assert cm.n_train >= 2
            assert cm.err_fwd >= 0.0
            assert cm.err_factor >= 1.0

    def test_train_predict_parity_roundtrip(self, fixture_corpus,
                                            trained):
        from flexflow_tpu.costmodel import train_model
        fresh = train_model(fixture_corpus)
        loaded, _ = trained
        for r in fixture_corpus["rows"][:20]:
            t1, c1 = fresh.predict(r)
            t2, c2 = loaded.predict(r)
            if t1 is None:
                assert t2 is None
                continue
            # round-trip through JSON (8-decimal coefs) stays within
            # float noise of the in-memory model
            assert t2 == pytest.approx(t1, rel=1e-4)
            assert c2 == pytest.approx(c1, rel=1e-4)

    def test_prediction_tracks_measured(self, fixture_corpus, trained):
        """On in-corpus LINEAR rows the learned prediction lands within
        ~3x of the measurement (CPU microbench noise) — versus the
        analytic roofline which misses by orders of magnitude here."""
        model, _ = trained
        ratios = []
        for r in fixture_corpus["rows"]:
            if r["type"] != "LINEAR":
                continue
            t, conf = model.predict(r)
            if t is None or conf < 0.3:
                continue
            true = float(r["measured"]["fwd_s"]) / max(
                1.0, float(r["work_div"]))
            ratios.append(t / true)
        assert len(ratios) >= 10
        med = sorted(abs(math.log(x)) for x in ratios)[len(ratios) // 2]
        assert math.exp(med) < 3.0

    def test_low_confidence_outside_hull(self, fixture_corpus, trained):
        model, _ = trained
        r = next(r for r in fixture_corpus["rows"]
                 if r["type"] == "LINEAR")
        t_in, c_in = model.predict(r)
        far = dict(r, flops=r["flops"] * 1e9, io_bytes=r["io_bytes"] * 1e9)
        t_out, c_out = model.predict(far)
        assert c_in > 0.5
        assert c_out < 0.05 * max(c_in, 1e-9) or c_out < 1e-3
        assert model.in_hull(r) and not model.in_hull(far)

    def test_unknown_class_none(self, trained):
        model, _ = trained
        t, c = model.predict(dict(type="NO_SUCH_OP", flops=1e6,
                                  io_bytes=1e5, param_bytes=0,
                                  work_div=1))
        assert t is None and c == 0.0

    def test_synthetic_law_recovery(self):
        """A corpus generated from a pure power law is recovered to
        within a few percent — the regression itself is sound."""
        from flexflow_tpu.costmodel import train_model
        rows = []
        rs = np.random.RandomState(7)
        for i in range(64):
            flops = float(10 ** rs.uniform(5, 9))
            io = float(10 ** rs.uniform(4, 8))
            t = 3e-4 * (flops / 1e8) ** 0.8 * (io / 1e6) ** 0.1
            rows.append(dict(
                schema=2, type="LINEAR", out_shape=[i], choice="dp",
                work_div=1, flops=flops, io_bytes=io, param_bytes=io / 3,
                dtype_size=4, mesh_axes={}, platform="cpu",
                measured=dict(fwd_s=t, bwd_s=2 * t, source="measured")))
        model = train_model(dict(rows=rows))
        errs = []
        for r in rows:
            t, _ = model.predict(r)
            errs.append(abs(math.log(t / r["measured"]["fwd_s"])))
        assert math.exp(float(np.median(errs))) < 1.05

    def test_platform_gate(self, trained, tmp_path, monkeypatch):
        """A model trained on another platform's corpus never engages
        (load_native_table returns None), same discipline as the
        collective_corrections platform buckets."""
        from flexflow_tpu.costmodel import CostModel, load_native_table
        model, path = trained
        assert load_native_table(path, platform="cpu") is not None
        assert load_native_table(path, platform="tpu") is None
        monkeypatch.setenv("FFS_NO_LEARNED_COSTS", "1")
        assert load_native_table(path, platform="cpu") is None


# ---------------------------------------------------------------------------
# native integration


def _tiny_nodes():
    roles = [["sample", "channel"]]
    return [
        dict(guid=1, type="INPUT", name="x", inputs=[], input_shapes=[],
             output_shapes=[[32, 64]], roles=roles, params={},
             flops=0.0, dtype_size=4, attrs={}),
        dict(guid=2, type="LINEAR", name="dense1", inputs=[[1, 0]],
             input_shapes=[[32, 64]], output_shapes=[[32, 128]],
             roles=roles, params={"kernel": [64, 128], "bias": [128]},
             flops=32 * 64 * 128 * 2.0, dtype_size=4, attrs={}),
        dict(guid=3, type="LINEAR", name="dense2", inputs=[[2, 0]],
             input_shapes=[[32, 128]], output_shapes=[[32, 10]],
             roles=roles, params={"kernel": [128, 10], "bias": [10]},
             flops=32 * 128 * 10 * 2.0, dtype_size=4, attrs={}),
    ]


def _machine(**kw):
    m = dict(num_devices=8, flops=1e12, hbm_bw=1e11, hbm_cap=16e9,
             ici_bw=1e10, ici_latency=1e-6, dcn_bw=1e9, dcn_latency=1e-5,
             num_slices=1, mxu_efficiency=0.55, conv_efficiency=0.35,
             min_op_time=5e-7, comm_bytes_factor=1.0, torus=[])
    m.update(kw)
    return m


def _wide_table(trained_model):
    """The trained native table with the hull opened wide so the tiny
    test graph's features land inside it."""
    tab = trained_model.native_table()
    for c in tab["classes"].values():
        c["fmin"] = [-100.0] * 4
        c["fmax"] = [100.0] * 4
    return tab


class TestNativeIntegration:
    def _simulate(self, machine, measured=None):
        from flexflow_tpu.search.native import native_simulate
        return native_simulate(dict(
            nodes=_tiny_nodes(), machine=machine,
            config=dict(training=True, overlap=True,
                        opt_state_factor=0.0),
            mesh=dict(data=8, model=1, seq=1, expert=1, pipe=1),
            assignment={"1": "rep", "2": "dp", "3": "dp"},
            measured=measured or {}))

    def test_search_trace_records_cost_source(self, trained):
        from flexflow_tpu.search.native import native_optimize
        model, _ = trained
        resp = native_optimize(dict(
            nodes=_tiny_nodes(),
            machine=_machine(learned=_wide_table(model)),
            config=dict(budget=1, training=True, batch=32,
                        enable_substitution=False,
                        emit_search_trace=True),
            measured={}))
        cands = [c for op in resp["search_trace"]["ops"]
                 for c in op["candidates"]]
        assert all(c["cost_source"] in ("learned", "analytic", "measured")
                   for c in cands)
        learned_cands = [c for c in cands if c["cost_source"] == "learned"]
        assert learned_cands, "no candidate was priced by the learned model"
        # the side-by-side columns explain.py's disagreement table reads
        for c in learned_cands:
            assert "compute_analytic_s" in c["terms"]
            assert "compute_learned_s" in c["terms"]

    def test_trace_all_analytic_without_table(self):
        from flexflow_tpu.search.native import native_optimize
        resp = native_optimize(dict(
            nodes=_tiny_nodes(), machine=_machine(),
            config=dict(budget=1, training=True, batch=32,
                        enable_substitution=False,
                        emit_search_trace=True),
            measured={}))
        cands = [c for op in resp["search_trace"]["ops"]
                 for c in op["candidates"]]
        assert {c["cost_source"] for c in cands} == {"analytic"}
        assert all("compute_learned_s" not in c["terms"] for c in cands)

    def test_out_of_hull_falls_back_to_analytic(self, trained):
        model, _ = trained
        tab = _wide_table(model)
        plain = self._simulate(_machine())
        priced = self._simulate(_machine(learned=tab))
        assert priced["cost_sources"]["2"] == "learned"
        far = dict(tab, classes={
            k: dict(v, fmin=[90.0] * 4, fmax=[100.0] * 4)
            for k, v in tab["classes"].items()})
        fell_back = self._simulate(_machine(learned=far))
        assert all(v in ("analytic",)
                   for v in fell_back["cost_sources"].values())
        assert fell_back["iteration_time"] == plain["iteration_time"]

    def test_measured_overrides_learned(self, trained):
        model, _ = trained
        resp = self._simulate(_machine(learned=_wide_table(model)),
                              measured={"2:fwd": 1e-3})
        assert resp["cost_sources"]["2"] == "measured"
        assert resp["cost_sources"]["3"] == "learned"


# ---------------------------------------------------------------------------
# end-to-end: search wiring + opt-out parity (acceptance rows)


class TestSearchWiring:
    def test_no_learned_costs_bit_identical(self, trained, monkeypatch):
        """With FFS_NO_LEARNED_COSTS=1 a searched zoo strategy is
        bit-identical to the no-model search, even with a trained
        COSTMODEL.json present."""
        _, path = trained
        monkeypatch.delenv("FFS_COSTMODEL_FILE", raising=False)
        monkeypatch.delenv("FFS_NO_LEARNED_COSTS", raising=False)
        base = small_mlp()
        assert base.search_info.get("cost_model") == "analytic"
        fp_base = strategy_fingerprint(base)
        monkeypatch.setenv("FFS_COSTMODEL_FILE", path)
        monkeypatch.setenv("FFS_NO_LEARNED_COSTS", "1")
        opted_out = small_mlp()
        assert opted_out.search_info.get("cost_model") == "analytic"
        assert strategy_fingerprint(opted_out) == fp_base

    def test_learned_model_engages_in_search(self, trained, monkeypatch):
        _, path = trained
        monkeypatch.setenv("FFS_COSTMODEL_FILE", path)
        monkeypatch.delenv("FFS_NO_LEARNED_COSTS", raising=False)
        ff = small_mlp()
        info = ff.search_info
        assert info.get("cost_model") == "learned"
        assert "LINEAR" in info.get("learned_cost_classes", [])

    def test_simtrace_side_by_side(self, trained, monkeypatch):
        """simulate_strategy(learned=False) is the control arm; the
        simtrace report carries cost_sources and the analytic twin."""
        from flexflow_tpu.obs.simtrace import simtrace_report
        from flexflow_tpu.search.validate import simulate_strategy
        _, path = trained
        monkeypatch.setenv("FFS_COSTMODEL_FILE", path)
        monkeypatch.delenv("FFS_NO_LEARNED_COSTS", raising=False)
        ff = small_mlp()
        resp = simulate_strategy(ff)
        srcs = set((resp.get("cost_sources") or {}).values())
        assert "learned" in srcs
        resp_an = simulate_strategy(ff, learned=False)
        assert set(resp_an["cost_sources"].values()) == {"analytic"}
        report = simtrace_report(ff, resp, resp_analytic=resp_an)
        assert report["corpus_schema"] == 3
        assert report["cost_sources"].get("learned", 0) >= 1
        assert report["predicted_analytic"]["step_s"] == \
            resp_an["iteration_time"]
        for row in report["per_op"]:
            assert row["priced"]["source"] in ("learned", "analytic",
                                               "measured")


# ---------------------------------------------------------------------------
# validation surfaces


class TestValidationSurfaces:
    def test_obs_report_accuracy_block(self, tmp_path):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "_obs_report", os.path.join(REPO, "scripts", "obs_report.py"))
        obs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(obs)
        sim = dict(
            corpus_schema=2,
            predicted=dict(step_s=0.010),
            predicted_analytic=dict(step_s=0.002),
            cost_sources=dict(learned=3, analytic=2),
            mesh_axes={"data": 8}, tasks=5, per_op=[],
            header=dict(run_name="demo", platform="cpu", host_id=0))
        counters = dict(
            observations={"demo/step_time_s": dict(p50=0.012, p99=0.02)},
            gauges={}, header=dict(run_name="demo", platform="cpu"))
        (tmp_path / "demo_r00_host00.simtrace.json").write_text(
            json.dumps(sim))
        (tmp_path / "demo_r00_host00.counters.json").write_text(
            json.dumps(counters))
        report = obs.build_report(str(tmp_path))
        row = report["runs"][0]
        s = row["sim"]
        assert s["predicted_vs_measured"] == pytest.approx(0.01 / 0.012,
                                                           abs=1e-3)
        assert s["predicted_analytic_step_s"] == pytest.approx(0.002)
        assert s["predicted_vs_measured_analytic"] == pytest.approx(
            0.002 / 0.012, abs=1e-3)
        assert s["cost_sources"] == dict(learned=3, analytic=2)
        md = obs.to_markdown(report)
        assert "Simulator accuracy" in md
        assert "learned:3" in md

    def test_costmodel_cli_train_and_report(self, tmp_path):
        """The CI stage's contract: train on the committed fixtures
        produces COSTMODEL.json; report renders the accuracy block."""
        import subprocess
        out = tmp_path / "COSTMODEL.json"
        corpus = tmp_path / "COSTMODEL_CORPUS.json"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "costmodel.py"),
             "train", "--trace-dir", FIXTURES, "--corpus", str(corpus),
             "--out", str(out)],
            capture_output=True, text=True, env=env, timeout=120)
        assert r.returncode == 0, r.stderr
        assert out.exists() and corpus.exists()
        model = json.load(open(out))
        assert model["schema_version"] == 1
        assert "LINEAR" in model["classes"]
        r2 = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "costmodel.py"),
             "report", "--model", str(out), "--corpus", str(corpus)],
            capture_output=True, text=True, env=env, timeout=120)
        assert r2.returncode == 0, r2.stderr
        assert "Simulator accuracy on the corpus" in r2.stdout
        assert "analytic" in r2.stdout

    def test_fflint_ffl704_stale_model(self, trained, tmp_path,
                                       monkeypatch):
        """INFO diagnostic when the search was priced by a learned
        model whose held-out error exceeds the calibration tolerance."""
        from flexflow_tpu.analysis import run_passes
        from flexflow_tpu.analysis.passes.calibration import CalibrationPass
        from flexflow_tpu.costmodel import CostModel
        model, _ = trained
        # inflate every class's held-out error past tolerance
        stale = json.loads(json.dumps(model.to_json()))
        for c in stale["classes"].values():
            c["err_fwd"] = 1.0  # e^1 ~ 2.7x >> 1.25x tolerance
        stale_path = tmp_path / "COSTMODEL.json"
        stale_path.write_text(json.dumps(stale))
        monkeypatch.setenv("FFS_COSTMODEL_FILE", str(stale_path))
        monkeypatch.delenv("FFS_NO_LEARNED_COSTS", raising=False)
        from flexflow_tpu.analysis import LintContext

        def ctx_of(ff):
            ctx = LintContext(
                nodes=ff.executor.nodes, mesh=ff.mesh,
                strategy=ff.strategy, machine_spec=ff.machine_spec,
                config=ff.config, final_ref=ff.executor.final_ref, ff=ff)
            ctx.searched = True
            return ctx

        ff = small_mlp()
        assert ff.search_info.get("cost_model") == "learned"
        diags = run_passes(ctx_of(ff), [CalibrationPass()]).diagnostics
        hits = [d for d in diags if d.rule == "FFL704"]
        assert hits and "LINEAR" in "".join(d.message for d in hits)
        # healthy model (fixture-trained errors are modest but may
        # exceed tolerance for noisy classes) — with the opt-out set,
        # no FFL704 regardless
        monkeypatch.setenv("FFS_NO_LEARNED_COSTS", "1")
        ff2 = small_mlp()
        assert ff2.search_info.get("cost_model") == "analytic"
        diags2 = run_passes(ctx_of(ff2), [CalibrationPass()]).diagnostics
        assert not [d for d in diags2 if d.rule == "FFL704"]
