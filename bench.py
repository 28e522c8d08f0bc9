#!/usr/bin/env python
"""Benchmark zoo: training throughput on the chip for 5 workload families.

Headline metric follows the reference's OSDI'22 AE BERT benchmark
(scripts/osdi22ae/bert.sh + examples/cpp/Transformer/transformer.cc:79-84):
12 layers, hidden 1024, 16 heads, seq 512, batch 8 per chip; metric is
training samples/s (fwd+bwd+update, jitted). Three more mirror the
rest of the AE protocol on one chip (scripts/osdi22ae/{inception,dlrm}.sh
+ examples/cpp/mixture_of_experts): a conv family, an embedding-heavy
recsys model, and a MoE; the fifth is a pipelined transformer on a
pipe x data mesh (PipelineGraphExecutor — on CPU via 8 virtual host
devices) — so executor changes can't regress a family unnoticed
(VERDICT r4 Missing #2). Prints ONE JSON line.

vs_baseline: ratio against the recorded best from previous rounds
(bench_history.json, keyed per workload), 1.0 on first run — the
reference repo publishes no absolute numbers (BASELINE.md).
"""

import dataclasses
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))


def ensure_virtual_host_devices(n: int = 8) -> None:
    """Give the CPU backend ``n`` virtual host devices BEFORE jax
    initializes (harmless on TPU — the flag only affects the host
    platform). The ONE bootstrap shared by bench main/serve and
    scripts/serve_bench.py; call before the first ``import jax``."""
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}")


def single_device_mesh_on_cpu(on_cpu):
    """Explicit 1-device mesh for the legacy workload families on CPU:
    main() forces 8 virtual host devices so the pipeline workload has a
    pipe x data mesh, but the single-device CPU protocol (census = 0 B,
    unsharded HBM peak) is what their ratchet history records — the
    virtual devices must not silently turn them data-parallel. On TPU
    (None) they keep using every visible chip as before."""
    if not on_cpu:
        return None
    from flexflow_tpu.machine import make_mesh
    return make_mesh(1, {"data": 1})


def time_train(ff, xs, y, iters, windows, tracer=None, capture=None):
    """Steady-state training samples/s: jitted fwd+bwd+update loop.

    Plain per-step dispatch, NOT lax.scan — measured r3 (30 iters, v5e):
    async dispatch pipelines better than the fused scan (160.35 vs
    156.46 samples/s), so the plain loop is both the honest protocol and
    the faster one. float(loss) is the window's fence: the loss depends
    on the whole step chain, and its value is wanted anyway. Best-of-N
    windows is the protocol bench_history.json was recorded with; the
    chip's host shares its CPU cores, so single windows vary.

    ``tracer`` (an active obs StepTracer) wraps each step in a span
    WITHOUT per-step fencing — the protocol's async pipelining is the
    thing being measured, so spans record dispatch cadence, and the
    window's host fetch is the only sync. None (the default) leaves the
    loop untouched.

    ``capture`` (an obs DeviceTraceCapture) wraps the WARMUP steps only
    — the windowed profiler session runs on post-compile warmup steps
    (window "1:3"), so the device-time attribution (exposed_comms_frac,
    the overlap direction's coordinate) is measured without perturbing
    the throughput windows.

    Returns ``(samples_per_s, step_samples)`` where ``step_samples`` are
    the per-step dispatch intervals (perf_counter deltas) of every
    measured window — in the steady state the async pipeline backs up on
    the device queue, so their distribution tracks device step time;
    main() reports their p50/p99 next to the throughput number
    (informational, no ratchet).
    """
    import jax
    import jax.random as jrandom

    train_step = ff.executor.make_train_step()
    inputs = ff._stage_inputs(xs)
    labels = ff._shard_batch(y)

    def step(params, opt_state, state, rng):
        rng, sub = jrandom.split(rng)
        params, opt_state, state, loss, _ = train_step(
            params, opt_state, state, inputs, labels, sub)
        return params, opt_state, state, rng, loss

    if tracer is not None and tracer.active:
        _raw_step = step

        def step(params, opt_state, state, rng):
            with tracer.step():
                with tracer.phase("dispatch"):
                    return _raw_step(params, opt_state, state, rng)

    params, opt_state, state = ff.params, ff.opt_state, ff.state
    rng = jrandom.PRNGKey(0)
    # warmup (compile; a second round catches the donation-aliased recompile)
    for i in range(3):
        if capture is not None:
            with capture.step(i):
                params, opt_state, state, rng, loss = step(
                    params, opt_state, state, rng)
                jax.block_until_ready(loss)  # device spans inside window
        else:
            params, opt_state, state, rng, loss = step(params, opt_state,
                                                       state, rng)
    float(loss)
    bs = ff.input_tensors[0].shape[0]
    best_dt = None
    final_loss = None
    step_samples = []
    for _ in range(windows):
        t0 = time.perf_counter()
        prev = t0
        for _ in range(iters):
            params, opt_state, state, rng, loss = step(params, opt_state,
                                                       state, rng)
            now = time.perf_counter()
            step_samples.append(now - prev)
            prev = now
        final_loss = float(loss)  # sync: depends on the whole step chain
        dt = time.perf_counter() - t0
        best_dt = dt if best_dt is None else min(best_dt, dt)
    assert np.isfinite(final_loss), f"training diverged: loss={final_loss}"
    return bs * iters / best_dt, step_samples


# ---------------------------------------------------------------------------
# workload builders: name -> (ff, xs, y, config_dict)


def build_bert_proxy(on_cpu):
    import jax.numpy as jnp

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType, MetricsType
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 create_transformer)
    from flexflow_tpu.optimizers import AdamOptimizer

    cfg = (TransformerConfig(num_layers=2, hidden_size=128, num_heads=4,
                             seq_length=64, batch_size=8)
           if on_cpu else TransformerConfig())  # reference config on TPU
    # TPU-native optimizer configuration: bf16 m/v storage (update math is
    # f32 — optimizers.py). The update phase is HBM-bound (measured r4,
    # scripts/measure_bw.py: ~620 GB/s marginal, so bytes are the lever);
    # bf16 state cuts its traffic 29%. Convergence parity with f32 state is
    # asserted by tests/test_model_training.py::test_adam_bf16_state.
    ff = create_transformer(cfg, FFConfig(batch_size=cfg.batch_size))
    ff.compile(AdamOptimizer(alpha=1e-4, state_dtype=jnp.bfloat16),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [MetricsType.MEAN_SQUARED_ERROR],
               mesh=single_device_mesh_on_cpu(on_cpu))
    rs = np.random.RandomState(0)
    x = rs.randn(cfg.batch_size, cfg.seq_length,
                 cfg.hidden_size).astype(np.float32)
    y = rs.randn(cfg.batch_size, cfg.seq_length, 1).astype(np.float32)
    return ff, [x], y, dataclasses.asdict(cfg)


def build_inception_proxy(on_cpu):
    import jax.numpy as jnp

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.models.inception import (InceptionConfig,
                                               create_inception_v3)
    from flexflow_tpu.optimizers import AdamOptimizer

    # reference AE: batch 64 across 4 GPUs (scripts/osdi22ae/inception.sh);
    # one-chip proxy keeps the full v3 topology at batch 16
    cfg = (InceptionConfig(batch_size=2, image_size=75, num_classes=10,
                           reduced=True)
           if on_cpu else
           InceptionConfig(batch_size=16, image_size=299, num_classes=1000))
    ff = create_inception_v3(cfg, FFConfig(batch_size=cfg.batch_size))
    ff.compile(AdamOptimizer(alpha=1e-4, state_dtype=jnp.bfloat16),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [],
               mesh=single_device_mesh_on_cpu(on_cpu))
    rs = np.random.RandomState(0)
    x = rs.randn(cfg.batch_size, 3, cfg.image_size,
                 cfg.image_size).astype(np.float32)
    y = rs.randint(0, cfg.num_classes,
                   (cfg.batch_size, 1)).astype(np.int32)
    return ff, [x], y, dataclasses.asdict(cfg)


def build_dlrm(on_cpu):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.models.dlrm import DLRMConfig, create_dlrm
    from flexflow_tpu.optimizers import SGDOptimizer

    # reference AE config family (examples/cpp/DLRM/dlrm.cc defaults,
    # run_random.sh: sparse-feature-size 64, embedding-bag-size 1):
    # embedding-table traffic dominates — the parameter-parallel showcase
    cfg = (DLRMConfig(batch_size=32, num_sparse_features=4,
                      vocab_size=1000, embedding_dim=16)
           if on_cpu else
           DLRMConfig(batch_size=2048, num_sparse_features=8,
                      vocab_size=1000000, embedding_dim=64))
    ff = create_dlrm(cfg, FFConfig(batch_size=cfg.batch_size))
    ff.compile(SGDOptimizer(lr=0.01),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [],
               mesh=single_device_mesh_on_cpu(on_cpu))
    rs = np.random.RandomState(0)
    xs = []
    for name in ff.executor.input_names:
        if name.startswith("sparse"):
            xs.append(rs.randint(0, cfg.vocab_size,
                                 (cfg.batch_size,
                                  cfg.indices_per_feature)).astype(np.int32))
        else:
            xs.append(rs.randn(cfg.batch_size,
                               cfg.dense_dim).astype(np.float32))
    y = rs.randint(0, 2, (cfg.batch_size, 1)).astype(np.float32)
    return ff, xs, y, dataclasses.asdict(cfg)


def build_moe(on_cpu):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.models.moe_model import MoEConfig, create_moe
    from flexflow_tpu.optimizers import SGDOptimizer

    # reference moe.cc defaults scaled to saturate one chip: top-2 of 16
    # experts over a 1024-wide hidden
    cfg = (MoEConfig(batch_size=32, input_dim=64, num_exp=4, num_select=2,
                     hidden_size=32)
           if on_cpu else
           MoEConfig(batch_size=1024, input_dim=1024, num_exp=16,
                     num_select=2, hidden_size=1024, num_classes=1000))
    ff = create_moe(cfg, FFConfig(batch_size=cfg.batch_size))
    ff.compile(SGDOptimizer(lr=0.01),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [],
               mesh=single_device_mesh_on_cpu(on_cpu))
    rs = np.random.RandomState(0)
    x = rs.randn(cfg.batch_size, cfg.input_dim).astype(np.float32)
    y = rs.randint(0, cfg.num_classes, (cfg.batch_size, 1)).astype(np.int32)
    return ff, [x], y, dataclasses.asdict(cfg)


def build_pipeline_transformer(on_cpu):
    """Pipelined transformer (pp >= 2): the only workload exercising
    PipelineGraphExecutor, so the hbm_peak_bytes / collective_bytes
    ratchets cover the pipeline path (sharded microbatch queue, circular
    schedule, WUS at pp > 1). On CPU the 8 virtual host devices (main()
    sets --xla_force_host_platform_device_count before jax initializes)
    provide the pipe x data mesh; on a real slice the physical chips do."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.machine import make_mesh
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 create_transformer)
    from flexflow_tpu.optimizers import AdamOptimizer

    ndev = len(jax.devices())
    if ndev < 2:
        raise RuntimeError(
            f"pipeline workload needs >= 2 devices, have {ndev}")
    pp = 4 if ndev >= 8 else 2
    dp = 2 if ndev >= 2 * pp else 1
    mesh = make_mesh(pp * dp, {"pipe": pp, "data": dp})
    cfg = (TransformerConfig(num_layers=2 * pp, hidden_size=64, num_heads=4,
                             seq_length=32, batch_size=8 * dp * pp)
           if on_cpu else
           TransformerConfig(num_layers=4 * pp, hidden_size=1024,
                             num_heads=16, seq_length=512,
                             batch_size=8 * dp * pp))
    c = FFConfig(batch_size=cfg.batch_size)
    c.pipeline_microbatches = 2 * pp
    ff = create_transformer(cfg, c)
    ff.compile(AdamOptimizer(alpha=1e-4, state_dtype=jnp.bfloat16),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [], mesh=mesh)
    # block-level rematerialization (ISSUE 20): the searched pipeline
    # 'remat' bit, engaged here so the family's hbm_peak_bytes ratchet
    # records the remat footprint (measured 36% of the remat-less peak
    # on the CPU config — the backward holds ONE block interior instead
    # of every in-flight microbatch's). Step values stay in the last-ulp
    # parity class of the remat-less step (XLA re-fuses the recomputed
    # interior; tests/test_remat.py::test_pipeline_body_remat_parity_-
    # at_pp2 bounds the drift); FFS_NO_REMAT opts out bit-identically,
    # mirroring the search-side switch.
    if not os.environ.get("FFS_NO_REMAT"):
        ff.executor.body_remat = True
    rs = np.random.RandomState(0)
    x = rs.randn(cfg.batch_size, cfg.seq_length,
                 cfg.hidden_size).astype(np.float32)
    y = rs.randn(cfg.batch_size, cfg.seq_length, 1).astype(np.float32)
    out_cfg = dataclasses.asdict(cfg)
    out_cfg.update(pipe=pp, data=dp, microbatches=c.pipeline_microbatches,
                   schedule=ff.executor.schedule,
                   body_remat=ff.executor.body_remat)
    return ff, [x], y, out_cfg


def build_longcontext_transformer(on_cpu):
    """Long-context attention at seq 2048 (ISSUE 20), DEVICELESS: the
    workload is never timed — its coordinates are the compile-determined
    ratchets (hbm_peak_bytes, dispatch_count, collective_bytes) from
    XLA's memory analysis, so it runs in seconds even though an
    interpret-mode flash step would take minutes on CPU. It pins the
    winning remat x kernel composition for long contexts, the lattice
    point ``_k:flash_r``: flash never materializes the O(seq^2) score
    interior, and remat then frees the boundary activations too —
    remat of the EINSUM attention alone cannot cut the peak (the
    recompute re-materializes the same interior at backward time;
    tests/test_remat.py::test_long_context_attention_hbm_peak_at_seq_2k
    asserts the same composition). FFS_NO_REMAT leaves the flash
    lowering but drops the checkpoint, exactly like the executor's
    opt-out."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.model import FFModel
    from flexflow_tpu.optimizers import SGDOptimizer

    if on_cpu:
        # the pallas flash kernel needs the interpreter off-TPU; on a
        # real chip the compiled kernel runs as-is
        os.environ.setdefault("FLEXFLOW_TPU_PALLAS", "interpret")
    seq, hidden, layers = 2048, 32, 2
    cfg = FFConfig(batch_size=2, seed=42)
    ff = FFModel(cfg)
    x = ff.create_tensor((2, seq, hidden), name="x")
    t = x
    for i in range(layers):
        t = ff.multihead_attention(t, t, t, hidden, 2, name=f"attn{i}")
    ff.dense(t, hidden, name="fc")
    ff.compile(SGDOptimizer(lr=0.01),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [],
               mesh=single_device_mesh_on_cpu(on_cpu))
    attn = {f"attn{i}" for i in range(layers)}
    for n in ff.executor.nodes:
        if n.op.name in attn:
            n.op.kernel_impl = "flash"
    if not os.environ.get("FFS_NO_REMAT"):
        ff.executor.remat_ops = attn
    rs = np.random.RandomState(0)
    xv = rs.randn(2, seq, hidden).astype(np.float32)
    y = rs.randn(2, seq, hidden).astype(np.float32)
    cfg_dict = dict(seq_length=seq, hidden_size=hidden, num_layers=layers,
                    batch_size=2, kernel="flash",
                    remat=not os.environ.get("FFS_NO_REMAT"))
    return ff, [xv], y, cfg_dict


def build_multislice_transformer(on_cpu):
    """Multi-slice transformer (2 slices x 4 chips), deviceless on CPU:
    the 8 virtual host devices stand in for two DCN-connected slices.
    ``--slices 2`` splits the flat data mesh into ('slice', 'data') in
    model.compile, so the gradient sync crosses the slice boundary and
    the fabric-split census (collectives_by_fabric) attributes its bytes
    to DCN — the ``dcn_bytes`` coordinate this workload records. On a
    real multi-slice deployment the physical DCN carries the same
    collectives; here the numbers are compile-determined, not timed."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.machine import make_mesh
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 create_transformer)
    from flexflow_tpu.optimizers import AdamOptimizer

    ndev = len(jax.devices())
    if ndev < 8:
        raise RuntimeError(
            f"multislice workload needs >= 8 devices, have {ndev}")
    cfg = (TransformerConfig(num_layers=2, hidden_size=128, num_heads=4,
                             seq_length=64, batch_size=32)
           if on_cpu else
           TransformerConfig(num_layers=8, hidden_size=1024, num_heads=16,
                             seq_length=512, batch_size=64))
    c = FFConfig(batch_size=cfg.batch_size)
    c.slices = 2
    ff = create_transformer(cfg, c)
    ff.compile(AdamOptimizer(alpha=1e-4, state_dtype=jnp.bfloat16),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [],
               mesh=make_mesh(8, {"data": 8}))
    assert "slice" in ff.mesh.axis_names, ff.mesh.axis_names
    rs = np.random.RandomState(0)
    x = rs.randn(cfg.batch_size, cfg.seq_length,
                 cfg.hidden_size).astype(np.float32)
    y = rs.randn(cfg.batch_size, cfg.seq_length, 1).astype(np.float32)
    out_cfg = dataclasses.asdict(cfg)
    out_cfg.update(slices=2, mesh=dict(zip(ff.mesh.axis_names,
                                           ff.mesh.devices.shape)))
    return ff, [x], y, out_cfg


WORKLOADS = [
    ("bert_proxy", build_bert_proxy, 30),
    ("inception_proxy", build_inception_proxy, 10),
    ("dlrm", build_dlrm, 30),
    ("moe", build_moe, 30),
    ("pipeline_transformer", build_pipeline_transformer, 10),
    ("multislice_transformer", build_multislice_transformer, 10),
    # iters=0 marks a DEVICELESS family: never timed, only the
    # compile-determined ratchets engage (hbm_peak_bytes,
    # dispatch_count, collective_bytes)
    ("longcontext_transformer", build_longcontext_transformer, 0),
]


def load_history():
    path = os.path.join(REPO, "bench_history.json")
    hist = {}
    if os.path.exists(path):
        try:
            hist = json.load(open(path))
        except Exception:
            hist = {}
    if "samples_per_s" in hist:
        # migrate the r1/r2 flat format; those rounds were recorded on the
        # TPU by the driver, so the number belongs to the tpu key
        hist = {"bert_proxy:tpu": {"samples_per_s": hist["samples_per_s"]}}
    return path, hist


def save_history(path, hist):
    """Atomic write-temp-then-rename: a bench crash mid-dump must never
    truncate the ratchet history every later round compares against."""
    from flexflow_tpu.obs.artifacts import atomic_write_text
    atomic_write_text(path, json.dumps(hist))


def ratchet(hist, key, samples_per_s, config, protocol):
    """Best-ever per workload key. The key is protocol name + platform
    ONLY — never the config dict (a schema change must not reset the
    ratchet; r2 lesson). `protocol` records the actual windows x iters
    measured (e.g. "best3x30") so a drifted protocol is flagged, not
    silently compared. Returns (vs_baseline, best_ever,
    old_protocol_or_None) — best_ever is reported beside each run's
    number because the recorded rounds swung up to ~2.3x run-to-run
    (BENCH_NOTES.md), so a sub-1 vs_baseline on one run proves little.
    A best-ever ratchet is not a measurement protocol; ROADMAP S0
    replaces it with medians of repeated runs."""
    entry = hist.get(key)
    if not isinstance(entry, dict):
        # first run of a new workload family (key absent), or a legacy /
        # hand-edited bare-number entry: both must ratchet cleanly
        entry = ({"samples_per_s": float(entry)}
                 if isinstance(entry, (int, float)) else {})
    baseline = entry.get("samples_per_s")
    vs = samples_per_s / baseline if baseline else 1.0
    old = entry.get("protocol", protocol) if entry else protocol
    if samples_per_s >= (baseline or 0.0):
        # merge over the old entry: sibling ratchets (collective_bytes,
        # census_ratchet below) live in the same dict and must survive a
        # new throughput best
        hist[key] = dict(entry, samples_per_s=samples_per_s,
                         protocol=protocol, config=config)
    # else: keep the stored best AND its provenance untouched
    return vs, max(samples_per_s, baseline or 0.0), \
        (old if old != protocol else None)


def _low_water_ratchet(hist, key, field, value, tol, abs_tol=0.0,
                       skip=False, max_drop=None):
    """Shared downward ratchet (census bytes, HBM peak, exposed-comms
    fraction): lower is better; a new low updates ``field`` in the
    workload's history entry, anything more than ``tol`` relative plus
    ``abs_tol`` absolute above the recorded best is a regression.
    ``skip`` suppresses the flag (the low-water value still records).
    For MEASURED metrics ``max_drop`` bounds how far one run can tighten
    the baseline (e.g. 0.5 = at most halve it per round): a single
    outlier-low capture window must not set a floor typical runs can
    never meet again, while sustained genuine improvement still
    converges geometrically. Returns (regression, baseline)."""
    entry = hist.get(key)
    if not isinstance(entry, dict):
        # legacy bare-number entry: preserve it as the samples/s baseline
        # (exactly as ratchet() does) instead of clobbering the record
        entry = ({"samples_per_s": float(entry)}
                 if isinstance(entry, (int, float)) else {})
        hist[key] = entry
    baseline = entry.get(field)
    regression = (not skip and baseline is not None
                  and value > baseline * (1.0 + tol) + abs_tol)
    if baseline is None:
        entry[field] = float(value)
    elif value < baseline:
        floor = baseline * max_drop if max_drop else 0.0
        entry[field] = float(max(value, floor))
    return regression, baseline


def census_ratchet(hist, key, total_bytes, tol=0.01):
    """Collective BYTE-VOLUME ratchet per workload family (ROADMAP
    trace-regression gate): unlike samples/s the census is a property of
    the compiled program — chip weather cannot hide a strategy
    regression that adds comms. Best (lowest) per-device bytes per step
    live under ``collective_bytes`` in the same history entry the
    throughput ratchet uses."""
    return _low_water_ratchet(hist, key, "collective_bytes", total_bytes,
                              tol)


def emit_obs_artifacts(name, ff, tracer):
    """Per-workload observability emission (only when --trace-dir is
    set): export the step trace, write the compiled-step summary
    artifact, and print ONE census line — to stderr, because the driver
    parses stdout as the single bench JSON line. Returns the summary
    (reused by the census byte ratchet) or None."""
    import traceback

    try:
        from flexflow_tpu.obs import export_step_summary
        tracer.export()
        summary = export_step_summary(ff, tracer)
        census = summary.get("collectives") or {}
        total = summary.get("collectives_total") or {}
        print(f"[obs] {name} collectives: "
              + json.dumps(dict(per_kind=census, total=total)),
              file=sys.stderr)
        return summary
    except Exception:
        print(f"[obs] {name}: artifact emission failed:\n"
              + traceback.format_exc(), file=sys.stderr)
        return None


def step_summary_for(name, ff, summary):
    """The compiled-step summary (collective census + XLA memory
    analysis), computed at most once per workload. Reuses a summary
    already computed for --trace-dir; otherwise pays one AOT
    lower+compile of the train step. FFS_SKIP_CENSUS=1 opts out (e.g. a
    time-boxed chip call). Returns None when unavailable — the byte and
    HBM ratchets then simply don't engage."""
    if summary is None and not os.environ.get("FFS_SKIP_CENSUS"):
        try:
            from flexflow_tpu.obs import inspect_model_step
            summary = inspect_model_step(ff)
        except Exception as e:
            print(f"[obs] {name}: census inspection failed: {e!r}",
                  file=sys.stderr)
            return None
    return summary


def census_bytes_of(summary):
    """Per-device collective bytes the compiled step moves (census
    total), or None."""
    total = (summary or {}).get("collectives_total") or {}
    b = total.get("bytes")
    return float(b) if b is not None else None


def dcn_bytes_of(summary):
    """Per-device CROSS-SLICE collective bytes the compiled step moves
    (the fabric-split census's DCN bucket — only present on a
    ('slice', ...) mesh), or None. Informational this round: recorded
    per workload alongside collective_bytes, not yet ratcheted —
    BENCH_NOTES documents the attribution methodology; the ratchet
    lands once a chip-validated multi-slice baseline exists."""
    fab = (summary or {}).get("collectives_by_fabric") or {}
    dcn = fab.get("dcn") or {}
    b = dcn.get("bytes")
    return float(b) if b is not None else None


def hbm_peak_of(summary):
    """Per-device HBM peak the compiled step needs (XLA compiled memory
    analysis: live arguments + temp), or None."""
    mem = (summary or {}).get("memory") or {}
    b = mem.get("peak_bytes")
    return float(b) if b else None


def dispatch_count_of(summary):
    """Kernel launches per compiled step (HLO fusion census: fusions +
    custom calls + collectives), or None. The dispatch-bound hot path's
    coordinate — the one the searched kernel dimension (ISSUE 15)
    moves."""
    f = (summary or {}).get("fusions") or {}
    d = f.get("dispatches")
    return int(d) if d else None


def dispatch_ratchet(hist, key, dispatches, tol=0.05):
    """Downward ratchet on the per-step dispatch count, alongside
    ``collective_bytes``/``hbm_peak_bytes``: a change that un-fuses the
    hot path (more kernel launches) fails the bench even when wall
    clock hides it. Compile-determined but XLA-version-sensitive, so a
    slightly wider tolerance than the byte ratchets plus 2 launches of
    absolute slack. FFS_SKIP_CENSUS=1 opts out upstream (no summary ->
    no engagement)."""
    return _low_water_ratchet(hist, key, "dispatch_count",
                              float(dispatches), tol, abs_tol=2.0)


def step_time_stats(step_samples, iters):
    """p50/p99 of the steady-state per-step dispatch intervals: the
    first window (index < iters) still fills the async pipeline, so it
    is dropped whenever a later window exists. Returns (p50, p99) or
    (None, None)."""
    from flexflow_tpu.obs.registry import percentile
    s = step_samples[iters:] if len(step_samples) > iters else step_samples
    if not s:
        return None, None
    s = sorted(s)
    return percentile(s, 0.5), percentile(s, 0.99)


def mfu_of(ff, step_s):
    """Model-FLOPs utilization at the measured step time: analytic
    fwd+bwd FLOPs per step / chips / step seconds / chip peak
    (obs.devtrace.train_step_flops — same convention as the traced-run
    MFU gauge). None when unavailable."""
    try:
        from flexflow_tpu.obs.devtrace import train_step_flops
        spec = ff.machine_spec
        if not (spec and step_s):
            return None
        n_chips = int(ff.mesh.devices.size)
        return train_step_flops(ff) / n_chips / step_s / float(spec.flops)
    except Exception:
        return None


def sim_accuracy_of(name, ff, p50, sps, cfg_dict):
    """Predicted/measured step-time ratio for one workload: the native
    simulator's replay of the compiled strategy (learned cost table
    engaged per the usual discovery — FFS_NO_LEARNED_COSTS opts out)
    over the measured steady-state step. Measured = the dispatch p50
    when the window captured one, else batch/samples-per-s. None when
    either side is unavailable; never raises (a simulator failure must
    not cost a bench round)."""
    try:
        from flexflow_tpu.search.validate import simulate_strategy
        pred_s = simulate_strategy(ff).get("iteration_time")
        meas_s = p50
        if not meas_s and sps:
            bs = cfg_dict.get("batch_size")
            meas_s = float(bs) / sps if bs else None
        if not (pred_s and meas_s):
            return None
        return round(float(pred_s) / float(meas_s), 4)
    except Exception as e:
        print(f"[obs] {name}: sim-accuracy replay failed: {e!r}",
              file=sys.stderr)
        return None


def exposed_ratchet(hist, key, frac, tol=0.25, abs_tol=0.01):
    """Downward ratchet on the measured exposed-comms fraction (ISSUE 9:
    promoted from informational — overlap wins must not silently
    regress). The fraction comes from the warmup-window device capture,
    which is noisier than the compile-determined ratchets, so the guard
    allows ``tol`` relative plus ``abs_tol`` absolute slack (a
    zero-comms single-device family must not flag on measurement dust)
    and a new low can tighten the baseline by at most half per round
    (one lucky capture window must not set an unreachable floor).
    Mirrors the census ratchet's opt-out: FFS_SKIP_EXPOSED=1 skips the
    guard (the low-water value still records). Returns
    (regression, baseline)."""
    return _low_water_ratchet(
        hist, key, "exposed_comms_frac", frac, tol, abs_tol=abs_tol,
        skip=bool(os.environ.get("FFS_SKIP_EXPOSED")), max_drop=0.5)


def hbm_ratchet(hist, key, peak_bytes, tol=0.02):
    """HBM-peak ratchet per workload family, the memory sibling of
    ``census_ratchet``: XLA's compiled memory analysis is also a
    property of the program, so a regression that bloats optimizer
    state or loses buffer donation fails the bench even when chip
    weather hides the samples/s cost. Best peak lives under
    ``hbm_peak_bytes``."""
    return _low_water_ratchet(hist, key, "hbm_peak_bytes", peak_bytes, tol)


def latency_ratchet(hist, key, field, value_s, tol=0.5, max_drop=0.5):
    """Downward ratchet on a measured request-latency percentile
    (BENCH_NOTES r14): lower is better; generous relative tolerance
    because closed-loop request latency is far noisier than the
    compile-determined ratchets, and one outlier-fast round may tighten
    the baseline by at most half. FFS_SKIP_LATENCY=1 opts out (the
    low-water value still records)."""
    return _low_water_ratchet(
        hist, key, field, value_s, tol, abs_tol=0.001,
        skip=bool(os.environ.get("FFS_SKIP_LATENCY")), max_drop=max_drop)


def serve_main(argv):
    """`bench.py serve`: closed-loop inference-serving latency bench —
    the latency sibling of the training-throughput families. Drives the
    flexflow_tpu/serve engine (continuous batching + latency-searched
    bucket executors) with the BENCH_NOTES r14 protocol (per-bucket
    warmup excluded, closed-loop clients) and ratchets p50/p99 request
    latency downward in the same bench_history.json the throughput
    ratchets live in. Prints ONE JSON line."""
    ensure_virtual_host_devices()
    import jax

    sys.path.insert(0, REPO)
    from flexflow_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    on_cpu = jax.devices()[0].platform == "cpu"
    platform = "cpu" if on_cpu else "tpu"
    hist_path, hist = load_history()
    models = [a for a in argv if not a.startswith("-")] or ["transformer"]
    trace_dir = os.environ.get("FFS_TRACE_DIR") or None

    from flexflow_tpu.serve.loadgen import (build_serve_model,
                                            run_serve_workload)

    result = {"metric": "serve_request_latency", "unit": "s",
              "workloads": {}}
    regressions = []
    for name in models:
        try:
            # fresh registry per workload: the serve/* series (latency
            # reservoir, occupancy) are process-global — without a reset
            # the second model's report would blend in the first's
            from flexflow_tpu.obs.registry import get_registry
            get_registry().reset()
            ff, make_request, cfg_dict = build_serve_model(name, on_cpu)
            report = run_serve_workload(
                ff, make_request,
                num_requests=(24 if on_cpu else 200),
                concurrency=4, search_budget=4, trace_dir=trace_dir)
        except Exception as e:
            result["workloads"][name] = {
                "error": f"{type(e).__name__}: {e}"}
            continue
        loop = report["closed_loop"]
        key = f"serve_{name}:{platform}"
        wl = dict(
            p50_s=round(loop.get("p50_s", 0.0), 6),
            p99_s=round(loop.get("p99_s", 0.0), 6),
            throughput_rps=round(loop.get("throughput_rps", 0.0), 2),
            num_measured=loop.get("num_measured"),
            buckets={b: dict(objective=e["objective"],
                             differs=e["strategy_differs_from_training"])
                     for b, e in report["buckets"].items()},
        )
        occ = report.get("registry", {}).get("occupancy_mean")
        if occ is not None:
            wl["occupancy_mean"] = round(occ, 4)
        fields = ("request_latency_p50_s", "request_latency_p99_s")
        prev = dict(hist.get(key) or {}) if isinstance(hist.get(key),
                                                       dict) else {}
        for field, v in zip(fields, (loop.get("p50_s"),
                                     loop.get("p99_s"))):
            if v is None:
                continue
            reg, base = latency_ratchet(hist, key, field, v)
            if reg:
                regressions.append(
                    f"{name}: {field} {v:.6f}s vs recorded best "
                    f"{base:.6f}s")
        ent = hist.get(key)
        if isinstance(ent, dict):
            # provenance follows the RECORDED BEST, not the latest run
            # (the ratchet() discipline): protocol/config update only
            # when this run actually lowered a baseline
            improved = any(ent.get(f) != prev.get(f) for f in fields)
            if improved or "protocol" not in ent:
                ent.update(
                    protocol="closed4x" + str(loop.get("num_measured")),
                    config=cfg_dict,
                    throughput_rps=wl["throughput_rps"])
        result["workloads"][name] = wl
        del ff
    try:
        save_history(hist_path, hist)
    except Exception:
        pass
    if regressions:
        result["latency_regressions"] = regressions
    print(json.dumps(result))


def main():
    # the pipeline workload needs a pipe x data mesh
    ensure_virtual_host_devices()
    import jax

    sys.path.insert(0, REPO)
    from flexflow_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    on_cpu = jax.devices()[0].platform == "cpu"
    platform = "cpu" if on_cpu else "tpu"
    hist_path, hist = load_history()
    trace_dir = os.environ.get("FFS_TRACE_DIR") or None
    if "--trace-dir" in sys.argv:
        i = sys.argv.index("--trace-dir")
        if i + 1 >= len(sys.argv) or sys.argv[i + 1].startswith("-"):
            print("bench.py: --trace-dir requires a directory argument",
                  file=sys.stderr)
            sys.exit(2)
        trace_dir = sys.argv[i + 1]

    result = {}
    workloads_out = {}
    protocol_notes = []
    census_regressions = []
    memory_regressions = []
    exposed_regressions = []
    for name, build, iters in WORKLOADS:
        compile_only = iters == 0
        iters = iters if compile_only else (5 if on_cpu else iters)
        windows = 1 if on_cpu else 3
        protocol = ("compile_only" if compile_only
                    else f"best{windows}x{iters}")
        ff = None
        tracer = None
        try:
            ff, xs, y, cfg_dict = build(on_cpu)
            capture = None
            devrep = None
            summary = None
            if compile_only:
                # deviceless family: no training loop — every recorded
                # coordinate is a property of the compiled program
                sps, step_samples = None, []
            else:
                if trace_dir:
                    from flexflow_tpu.obs import make_capture, make_tracer
                    tracer = make_tracer(trace_dir, run_name=name)
                    # windowed device capture over the post-compile
                    # warmup steps: exposed_comms_frac (the overlap
                    # direction's ratchet coordinate) without perturbing
                    # the measurement
                    if tracer.active:
                        capture = make_capture(tracer, "1:3")
                sps, step_samples = time_train(ff, xs, y, iters=iters,
                                               windows=windows,
                                               tracer=tracer,
                                               capture=capture)
                if capture is not None and capture.active:
                    try:
                        devrep = capture.finalize(ff, tracer)
                    except Exception as e:
                        print(f"[obs] {name}: devtrace attribution "
                              f"failed: {e!r}", file=sys.stderr)
                if tracer is not None and tracer.active:
                    summary = emit_obs_artifacts(name, ff, tracer)
            summary = step_summary_for(name, ff, summary)
            cbytes = census_bytes_of(summary)
            hbm_peak = hbm_peak_of(summary)
        except Exception as e:
            if name == "bert_proxy":
                raise  # the headline metric must never be silently absent
            # a broken secondary family is a visible per-workload error,
            # not a lost bench run (the driver parses the ONE JSON line);
            # drop the failed model so its HBM frees before the next build
            ff = None
            workloads_out[name] = {"error": f"{type(e).__name__}: {e}"}
            continue
        key = f"{name}:{platform}"
        if compile_only:
            # no throughput to ratchet; record provenance so the entry
            # still says what was compiled
            vs = best = old_protocol = None
            ent = hist.get(key)
            if not isinstance(ent, dict):
                ent = {}
                hist[key] = ent
            ent.update(protocol=protocol, config=cfg_dict)
        else:
            vs, best, old_protocol = ratchet(hist, key, sps, cfg_dict,
                                             protocol)
        wl = {}
        if cbytes is not None:
            # the trace-regression gate (ROADMAP): a strategy change that
            # adds comms fails LOUDLY here even when chip weather hides
            # the samples/s slowdown — the census is compile-determined
            reg, byte_base = census_ratchet(hist, key, cbytes)
            wl["collective_bytes"] = round(cbytes, 1)
            if reg:
                census_regressions.append(
                    f"{name}: {cbytes:.0f} B/step vs recorded best "
                    f"{byte_base:.0f}")
        dcn = dcn_bytes_of(summary)
        if dcn is not None:
            # fabric attribution (multi-slice meshes only): cross-slice
            # byte volume per step — informational this round, the DCN
            # ratchet follows once a chip-validated baseline exists
            wl["dcn_bytes"] = round(dcn, 1)
        if hbm_peak is not None:
            # memory sibling of the census gate: per-device HBM peak from
            # XLA's compiled memory analysis (the metric weight-update
            # sharding moves) ratchets alongside throughput
            mreg, peak_base = hbm_ratchet(hist, key, hbm_peak)
            wl["hbm_peak_bytes"] = round(hbm_peak, 1)
            if mreg:
                memory_regressions.append(
                    f"{name}: {hbm_peak:.0f} B peak vs recorded best "
                    f"{peak_base:.0f}")
        dispatches = dispatch_count_of(summary)
        if dispatches is not None:
            # dispatch-count sibling (ISSUE 15): the kernel-search
            # dimension's coordinate — un-fusing the hot path (more
            # launches per step) fails loudly even when wall clock
            # doesn't move on this round's hardware
            dreg, dbase = dispatch_ratchet(hist, key, dispatches)
            wl["dispatch_count"] = dispatches
            if dreg:
                census_regressions.append(
                    f"{name}: {dispatches} dispatches/step vs recorded "
                    f"best {dbase:.0f}")
        # per-op kernel choices (provenance, informational): which impls
        # this round's strategy executed — seeded into the history entry
        # so cross-round diffs show kernel-choice flips. Searched models
        # record the "_k:" choices; heuristic workloads record the
        # attention dispatch (selected_impl) so the column never goes
        # silently absent.
        kc = dict(getattr(ff, "kernel_choices", None) or {})
        if not kc:
            from flexflow_tpu.search.unity import executed_kernel_choices
            axes = dict(zip(ff.mesh.axis_names, ff.mesh.devices.shape))
            kc = executed_kernel_choices(ff.executor.nodes, ff.strategy,
                                         axes, training=True)
        if kc:
            wl["kernel_choices"] = dict(sorted(kc.items()))
        # informational observability fields (ISSUE 6): step-time
        # distribution + MFU next to the ratchets — recorded into the
        # history entry for cross-round comparison, but NOT gated (chip
        # weather swings dispatch cadence far more than compiled bytes)
        p50, p99 = step_time_stats(step_samples, iters)
        mfu = mfu_of(ff, p50)
        if p50 is not None:
            wl["step_time_p50"] = round(p50, 6)
            wl["step_time_p99"] = round(p99, 6)
        if mfu is not None:
            wl["mfu"] = round(mfu, 8)
        # simulator accuracy as a tracked metric (ISSUE 14 / SCALE-Sim
        # methodology): replay the compiled strategy through the native
        # simulator — learned cost table engaged exactly as the search
        # had it — and record predicted/measured step time next to
        # throughput. Informational (no ratchet: the simulator predicts
        # chip behavior, so a CPU round's ratio is a smoke value, and
        # the recorded chip rounds swung too widely to ratchet on).
        sim_ratio = (None if compile_only
                     else sim_accuracy_of(name, ff, p50, sps, cfg_dict))
        if sim_ratio is not None:
            wl["sim_accuracy_ratio"] = sim_ratio
        # measured exposed-comms fraction from the warmup-window device
        # capture: since ISSUE 9 a downward-ratcheting GUARD (the
        # overlap direction's coordinate — a strategy/executor change
        # that re-exposes hidden comms fails the bench even when chip
        # weather hides the samples/s cost). FFS_SKIP_EXPOSED=1 opts
        # out, mirroring the census ratchet.
        tot = (devrep or {}).get("totals") or {}
        if tot.get("wall_s"):
            frac = round(tot.get("exposed_comms_s", 0.0) / tot["wall_s"], 4)
            wl["exposed_comms_frac"] = frac
            ereg, ebase = exposed_ratchet(hist, key, frac)
            if ereg:
                exposed_regressions.append(
                    f"{name}: exposed_comms_frac {frac:.4f} vs recorded "
                    f"best {ebase:.4f}")
        ent = hist.get(key)
        if isinstance(ent, dict):
            ent.update({k: wl[k] for k in
                        ("step_time_p50", "step_time_p99", "mfu",
                         "sim_accuracy_ratio", "kernel_choices")
                        if k in wl})
            if "sim_accuracy_ratio" not in wl:
                # a failed replay must not leave a PREVIOUS round's
                # ratio sitting next to this round's step times
                ent.pop("sim_accuracy_ratio", None)
            if "kernel_choices" not in wl:
                # same stale-field discipline: a round that records no
                # kernel choices must not inherit a previous round's
                ent.pop("kernel_choices", None)
        if name == "bert_proxy":
            result.update({
                "metric": "bert_proxy_train_throughput",
                "value": round(sps, 3),
                "unit": "samples/s",
                "vs_baseline": round(vs, 4),
                "best_recorded": round(best, 3),
            })
            result.update(wl)
        elif compile_only:
            workloads_out[name] = dict({"compile_only": True}, **wl)
        else:
            workloads_out[name] = dict(
                {"value": round(sps, 3),
                 "vs_baseline": round(vs, 4),
                 "best_recorded": round(best, 3)}, **wl)
        if old_protocol:
            protocol_notes.append(f"{name}: {old_protocol} -> {protocol}")
        del ff
    try:
        save_history(hist_path, hist)
    except Exception:
        pass
    result["workloads"] = workloads_out
    if census_regressions:
        result["census_regressions"] = census_regressions
    if memory_regressions:
        result["memory_regressions"] = memory_regressions
    if exposed_regressions:
        result["exposed_regressions"] = exposed_regressions
    if protocol_notes:
        result["protocol_change"] = ("vs_baseline spans protocols — " +
                                     "; ".join(protocol_notes))
    ratio = searched_vs_dp_ratio(on_cpu)
    if ratio is not None:
        # BASELINE.md north star: predicted searched/DP throughput on a
        # simulated v4-32 (the OSDI'22 AE protocol's headline comparison)
        result.update(ratio)
    print(json.dumps(result))


def searched_vs_dp_ratio(on_cpu):
    """Unity-search vs --only-data-parallel predicted iteration time for
    BERT-large (24 layers, hidden 1024, 16 heads, seq 512 — the
    BASELINE.md north-star model) on a simulated TPU v4-32.

    Protocol mirrors the reference's OSDI'22 AE comparison
    (scripts/osdi22ae/bert.sh: global batch 8 on 4 GPUs — *strong*
    scaling, ~1-2 samples per device, plain SGD): global batch = n_chips,
    where DP's per-parameter gradient sync cannot amortize and a hybrid
    strategy wins. At large per-chip batch DP is genuinely near-optimal
    on TPU (sync hides under backward) and the honest ratio approaches 1.
    Collectives are priced at the protocol's f32 payload
    (comm_bytes_factor 1.0, matching the reference's f32 training);
    r1-r4 measured the 12-layer proxy here — the r5 history in
    BENCH_NOTES.md tracks the change.
    """
    try:
        from flexflow_tpu.config import FFConfig
        from flexflow_tpu.ffconst import LossType
        from flexflow_tpu.machine import MachineSpec
        from flexflow_tpu.models.transformer import (TransformerConfig,
                                                     create_transformer)
        from flexflow_tpu.optimizers import SGDOptimizer
        from flexflow_tpu.search.native import available, native_optimize
        from flexflow_tpu.search.unity import machine_to_json, serialize_graph

        if not available():
            return None
        n_chips = 32
        mcfg = (TransformerConfig(num_layers=2, hidden_size=128, num_heads=4,
                                  seq_length=64, batch_size=n_chips)
                if on_cpu else
                TransformerConfig(num_layers=24, batch_size=n_chips))
        ff = create_transformer(
            mcfg, FFConfig(batch_size=mcfg.batch_size,
                           only_data_parallel=True, workers_per_node=1))
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
        nodes = serialize_graph(ff.executor.nodes,
                                final_guid=ff.executor.final_ref[0])
        machine = machine_to_json(
            MachineSpec(chip="tpu-v4", chips_per_slice=n_chips), n_chips)
        base_cfg = dict(budget=8, alpha=0.05, training=True, overlap=True,
                        batch=mcfg.batch_size, opt_state_factor=0.0,
                        seed=42, rules=[])
        # the searched arm gets the full strategy space, including the r4
        # GPipe pipeline meshes (repeated-block metadata)
        search_req = dict(
            nodes=nodes, machine=machine, measured={},
            config=dict(base_cfg, enable_parameter_parallel=True))
        from flexflow_tpu.parallel.pipeline_detect import (
            detect_repeated_blocks, pipeline_meta_json)
        pb = detect_repeated_blocks(ff.executor.nodes)
        if pb is not None:
            search_req["pipeline"] = pipeline_meta_json(ff.executor.nodes, pb)
        searched = native_optimize(search_req)
        dp = native_optimize(dict(
            nodes=nodes, machine=machine, measured={},
            config=dict(base_cfg, only_data_parallel=True)))
        r = dp["predicted_time"] / searched["predicted_time"]
        mesh = {k: v for k, v in searched["mesh"].items() if v > 1}
        # the searched strategy's kernel choices (ISSUE 15): which
        # "_k:" impls the simulated v4-32 search committed to, keyed by
        # op — the per-workload kernel_choices record for strategies
        # that actually SEARCH (the CPU proxy workloads run heuristic
        # single-device strategies and record none)
        from flexflow_tpu.parallel.choice import Choice
        by_guid = {n.op.guid: n.op.name for n in ff.executor.nodes}
        kchoices = {}
        for guid, oj in (searched.get("ops") or {}).items():
            impl = Choice.parse(oj.get("choice")).kernel
            if impl is not None:
                kchoices[by_guid.get(int(guid), guid)] = impl
        out = {
            "searched_vs_dp_v4_32": round(r, 3),
            "searched_mesh_v4_32": mesh or {"data": 1},
            "north_star_model": ("transformer_tiny" if on_cpu
                                 else "bert_large_24L"),
        }
        if kchoices:
            out["searched_kernel_choices_v4_32"] = dict(sorted(
                kchoices.items()))
        if searched.get("pipeline"):
            out["searched_microbatches_v4_32"] = \
                searched["pipeline"]["microbatches"]
        return out
    except Exception:
        return None


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "serve":
        serve_main(sys.argv[2:])
    else:
        main()
