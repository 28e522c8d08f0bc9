"""One run of one cell: set-up, the output check's system side, the
measured window, the traced extras (`--trace 1`) or the traced tail
(`--trace 2`), the reference, the result line.

`run.py` is the only entry on the chip. `rehearsal` (a dict of size
overrides) exists for the tests under tests/chipbench, which run a cell
at a tiny size on the CPU; nothing in `run.py` can set it.
"""

import collections
import gc
import glob
import importlib.util
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

from benchmarks import manifest as mf
from benchmarks.session_reduce import FENCED, OUT_DIR, SESSION

ROOT = mf.ROOT
DISCARD = "session_discard"   # beside SESSION and FENCED under a cell's output


def emit(**kw):
    print(json.dumps(kw, default=_jsonable), flush=True)


def _jsonable(o):
    try:
        return float(o)
    except (TypeError, ValueError):
        return str(o)


def load_by_path(kind, name, root=ROOT):
    """A family, reference or per-layer reader, by the name a data file
    gives it: benchmarks/<kind>/<name>.py."""
    path = os.path.join(root, "benchmarks", kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"{kind[:-1]} {name!r} has no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_native(root=ROOT):
    """`make -C native` before JAX starts a backend: no child process is
    started once this process holds the chip."""
    r = subprocess.run(["make", "-C", os.path.join(root, "native")],
                       capture_output=True, text=True)
    if r.returncode != 0:
        sys.stderr.write(r.stdout + r.stderr)
        raise SystemExit("benchmark: `make -C native` failed")


class CompileEvents:
    """Counts what JAX compiles: backend-compile durations (one per
    program compiled or fetched from the persistent cache) and the
    persistent cache's hits and misses."""

    def __init__(self):
        import jax
        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.counts[event.rsplit("/", 1)[1]] += 1

    def _on_duration(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.counts["backend_compiles"] += 1
            self.counts["backend_compile_s"] += duration

    def snapshot(self):
        return dict(self.counts)


def mesh_axes_of(ff):
    return dict(zip(ff.mesh.axis_names,
                    (int(n) for n in ff.mesh.devices.shape)))


def placement_checks(ff, x0, chips, on_tpu):
    """(name, ok, detail): the machine, dtype and devices the model ended
    up on are the ones the cell asks for."""
    import jax
    import jax.numpy as jnp

    out = []
    devs = set(jax.devices()[:chips])
    out.append(("mesh_has_the_cells_chips",
                int(ff.mesh.devices.size) == chips, mesh_axes_of(ff)))
    held = set()
    for leaf in jax.tree_util.tree_leaves(ff.params):
        held |= {s.device for s in leaf.addressable_shards}
    out.append(("parameters_on_every_chip", held == devs, len(held)))
    batch = ff._stage_inputs([x0])[ff.executor.input_names[0]]
    on = {s.device for s in batch.addressable_shards}
    out.append(("batch_on_every_chip", on == devs, len(on)))
    if on_tpu:
        out.append(("machine_spec_is_v5e",
                    ff.machine_spec.chip == "tpu-v5e", ff.machine_spec.chip))
        out.append(("compute_dtype_bf16",
                    ff.executor.compute_dtype == jnp.bfloat16,
                    str(ff.executor.compute_dtype)))
    return out


def release(ff):
    import jax
    ff.params = ff.opt_state = ff.state = ff.executor = None
    jax.clear_caches()
    gc.collect()


def memory_reading(chips):
    """The allocator's counters on the fullest of the cell's devices.
    `peak_bytes` is `peak_bytes_in_use` plus `peak_bytes_reserved`: the TPU
    runtime keeps the scratch memory of a loaded XLA program in a reserved
    region that `bytes_in_use` leaves out (read on the v5e: 7.81 GB
    reserved where the compiler's memory analysis of Inception's step says
    7.86 GB of temporaries, and 0.48 GB in use where it says 0.40 GB of
    arguments). Both parts stand beside the sum, with the readings of the
    moment, so that a run shows in which phase each peak was reached."""
    import jax
    best = None
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        row = {k: int(stats.get(k, 0)) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved")}
        row["peak_bytes"] = (row["peak_bytes_in_use"]
                             + row["peak_bytes_reserved"])
        if best is None or row["peak_bytes"] > best["peak_bytes"]:
            best = row
    return best


def p95(values):
    """95th percentile, linear interpolation between order statistics."""
    v = sorted(values)
    pos = 0.95 * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


# ---------------------------------------------------------------------------
# the output check


def system_side(ff, xs, y, batch):
    """Before any profiler or tracer: predictions on the first seeded
    batch, then the losses of three one-step `fit` calls on it (they
    double as warm-up). Returns the record and the first step's wall."""
    import numpy as np
    x0 = [x[:batch] for x in xs]
    y0 = y[:batch]
    preds = np.asarray(ff.predict(x0)).astype(np.float32)
    losses, first_step_s = [], None
    for _ in range(3):
        t0 = time.perf_counter()
        ff.fit(x0, y0, epochs=1, verbose=False)
        losses.append(float(ff._last_loss))
        if first_step_s is None:
            first_step_s = time.perf_counter() - t0
    return dict(preds=preds, losses=losses), first_step_s


def reference_side(family, weights, s, traffic, config, xs, y, batch,
                   operand="f32", adam=None, steps=3):
    """The plain reference on the same weights and batch, on one device:
    predictions and the losses of `steps` Adam steps (with `steps=1`, as
    the control of `seeds_check.py` asks, only the loss of the
    predictions, and no gradient is taken)."""
    from benchmarks.references import common
    ref, kw, chunk = family.reference(s, traffic)
    if operand != "f32":
        kw = dict(kw, operand=operand)
    x0, y0 = xs[0][:batch], y[:batch]
    preds = common.predict(ref, weights, x0, chunk, **kw)
    if steps > 1:
        losses = common.train_losses(ref, weights, x0, y0, chunk, steps,
                                     adam or config["adam"], **kw)
    else:
        losses = [common.loss_of(ref, preds, y0)]
    return dict(preds=preds, losses=losses)


def prediction_errors(got, want, log_space):
    """Candidate statistics of the predictions' error. `nrmse` is the RMS
    error over the standard deviation of the reference's predictions
    (their spread, not their size: an offset common to all predictions
    carries no rounding error and would dilute a relative L2 error). For
    class probabilities (`log_space`) it is taken on log-probabilities,
    where every class weighs the same."""
    import numpy as np
    g = got.astype(np.float64).ravel()
    w = want.astype(np.float64).ravel()
    out = {"rel_l2": float(np.linalg.norm(g - w) / np.linalg.norm(w)),
           "nrmse": float(np.sqrt(np.mean((g - w) ** 2)) / np.std(w)),
           "ref_std": float(np.std(w)), "ref_rms": float(
               np.sqrt(np.mean(w ** 2)))}
    if log_space:
        lg = np.log(np.maximum(g, 1e-30))
        lw = np.log(np.maximum(w, 1e-30))
        out["log_nrmse"] = float(np.sqrt(np.mean((lg - lw) ** 2))
                                 / np.std(lw))
        out["ref_log_std"] = float(np.std(lw))
    return out


def compare(got, want, tolerances, log_space=False):
    """Every number compared beside its limit; `ok` is all of them."""
    import numpy as np
    rows = []
    errs = prediction_errors(got["preds"], want["preds"], log_space)
    key = "log_nrmse" if log_space else "nrmse"
    rows.append(("pred_" + key, errs[key], tolerances["pred_" + key]))
    b = abs(got["losses"][0] - want["losses"][0]) / abs(want["losses"][0])
    rows.append(("loss0_rel", b, tolerances["loss0_rel"]))
    if len(want["losses"]) > 1:
        c = max(abs(g - w) / abs(w) for g, w in
                zip(got["losses"][1:], want["losses"][1:]))
        rows.append(("later_loss_rel", c, tolerances["later_loss_rel"]))
    finite = all(np.isfinite(got["losses"])) and bool(
        np.all(np.isfinite(got["preds"])))
    rows.append(("nonfinite_values", 0.0 if finite else 1.0, 0.0))
    return [dict(name=n, value=v, limit=lim, ok=bool(v <= lim))
            for n, v, lim in rows]


# ---------------------------------------------------------------------------
# the traced extras (only with --trace 1, after the window)


def profiled_epoch(ff, xs, y, out_dir):
    """Two back-to-back part-A epochs under `jax.profiler`, reduced here;
    only the reduction is kept."""
    import jax

    from benchmarks import trace_reduce as tr
    prof_dir = os.path.join(out_dir, "profile")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # device lanes only: less host drag
    jax.profiler.start_trace(prof_dir, profiler_options=options)
    try:
        for _ in range(2):     # two, so that the gap between epochs shows
            ff.fit(xs, y, epochs=1, verbose=False)
        jax.block_until_ready(ff.params)
    finally:
        jax.profiler.stop_trace()
    devices = tr.load_xplane(tr.newest_xplane(prof_dir))
    shutil.rmtree(prof_dir, ignore_errors=True)
    return devices


def program_dispatch_ms(ff, xs, y, out_dir):
    """The program's own `dispatch` phase over one epoch of fenced steps
    (`fit(trace_dir=...)` fences every step): host milliseconds inside
    each train-step call."""
    trace_dir = os.path.join(out_dir, "fit_trace")
    ff.fit(xs, y, epochs=1, verbose=False, trace_dir=trace_dir)
    files = sorted(glob.glob(os.path.join(trace_dir, "*.events.jsonl")))
    if not files:
        return []
    out = []
    with open(files[-1]) as f:
        for line in f:
            ev = json.loads(line)
            if ev.get("name") == "dispatch":
                out.append(ev["dur"] / 1e3)
    return out


# ---------------------------------------------------------------------------
# the traced tail (only with --trace 2, after the window has closed)

TAIL_SECONDS = 3.0   # of part-A traffic under the session; at least 2 epochs


def traced_tail(ff, xs, y, s, out_dir):
    """What `--trace 2` adds once every end-to-end number is taken, through
    the program's own control (`flexflow_tpu.obs.start_trace/stop_trace`):
    a session that is started and stopped at once and thrown away, as the
    form of a `--trace 2` run asks, so that the profiler's first start in
    the process falls into no number (0.3 s; PERF.md section 6 has the tail
    without it); (a) a session with the profiler over about TAIL_SECONDS of
    back-to-back part-A epochs, nothing fenced; (b) a session without the
    profiler over one epoch of part-B steps, each fenced here, whose
    `dispatch` spans are `executor.dispatch_ms`. The spans stay under `out_dir` for the readers
    (`session_reduce.find`); the profile is reduced here and deleted.
    Returns (devices, part A's session, dispatch milliseconds, a record for
    the `trace` line)."""
    import jax

    from benchmarks import session_reduce as sr
    from benchmarks import trace_reduce as tr
    from flexflow_tpu import obs

    t_tail = time.perf_counter()
    dirs = {k: os.path.join(out_dir, k) for k in (SESSION, FENCED, DISCARD)}
    obs.start_trace(dirs[DISCARD], device=True)
    obs.stop_trace()
    shutil.rmtree(dirs[DISCARD], ignore_errors=True)
    first_start_s = time.perf_counter() - t_tail

    t0 = time.perf_counter()
    obs.start_trace(dirs[SESSION], device=True)
    start_s = time.perf_counter() - t0
    try:
        epochs, t0 = 0, time.perf_counter()
        while epochs < 2 or time.perf_counter() - t0 < TAIL_SECONDS:
            ff.fit(xs, y, epochs=1, verbose=False)
            epochs += 1
        jax.block_until_ready(ff.params)
        traced_s = time.perf_counter() - t0
    finally:
        t0 = time.perf_counter()
        paths = obs.stop_trace()
        stop_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    devices, xplane_bytes = [], 0
    if paths["xplane"]:
        xplane_bytes = os.path.getsize(paths["xplane"])
        devices = tr.load_xplane(paths["xplane"])
    for d in glob.glob(os.path.join(dirs[SESSION], "*.jaxprof")):
        shutil.rmtree(d, ignore_errors=True)
    session = sr.load(dirs[SESSION])
    reduce_s = time.perf_counter() - t0

    batch = s["batch"]
    obs.start_trace(dirs[FENCED], device=False)
    try:
        for k in range(s["steps_per_epoch"]):
            sl = slice(k * batch, (k + 1) * batch)
            ff.fit([x[sl] for x in xs], y[sl], epochs=1, verbose=False)
            jax.block_until_ready(ff.params)
    finally:
        obs.stop_trace()
    dispatch_ms = sr.durations_ms(sr.load(dirs[FENCED]), "dispatch")

    # per traced step, the device program's start less its dispatch span's
    leads = [v for d in devices
             for v in sr.dispatch_leads_s(d, session.spans) or ()]
    record = dict(
        tail_s=time.perf_counter() - t_tail, first_start_s=first_start_s,
        start_s=start_s, traced_s=traced_s, traced_epochs=epochs, stop_s=stop_s,
        reduce_s=reduce_s, xplane_bytes=xplane_bytes, spans=len(session.spans),
        dispatch_lead_us_min=1e6 * min(leads) if leads else None,
        dispatch_lead_us_max=1e6 * max(leads) if leads else None,
        **{k: session.header.get(k) for k in (
            "clock_shift_us", "clock_tie_spread_us", "clock_tie_markers",
            "compile_phases", "set_parameter_s")})
    return devices, session, dispatch_ms, record


# ---------------------------------------------------------------------------


def run_cell(name, seed, seconds, trace, *, t_start, root=ROOT,
             rehearsal=None):
    """`trace`: 0 the window alone; 1 the window, then the traced extras,
    and a line of per-layer metrics only; 2 exactly what 0 does until the
    window has closed, then the traced tail, and a line with both kinds."""
    trace = int(trace)
    manifest = mf.load_manifest(root)
    cell, config, traffic = mf.find_cell(manifest, name, root)
    chips = cell["chips"]
    family = load_by_path("families", config["family"], root)
    build_native(root)

    import jax
    import jaxlib
    import numpy as np

    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    if rehearsal is None and not on_tpu:
        raise SystemExit(f"benchmark: needs a TPU, JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: cell {name} needs {chips} chip(s), "
                         f"JAX found {len(devs)}")
    cache_dir = None
    if rehearsal is None:
        from flexflow_tpu.utils.compile_cache import configure_compile_cache
        cache_dir = configure_compile_cache()
    events = CompileEvents()
    kind = devs[0].device_kind
    peaks = mf.peaks_for(kind, root) if on_tpu else None
    emit(phase="start", cell=name, seed=seed, seconds=seconds,
         trace=trace if trace == 2 else bool(trace),
         jax=jax.__version__, jaxlib=jaxlib.__version__,
         devices=[str(d) for d in devs], device_kind=kind, chips=chips,
         cache_dir=cache_dir, rehearsal=rehearsal is not None)

    s = family.sizes(config, traffic, (rehearsal or {}).get("sizes"))
    batch, n = s["batch"], s["batch"] * s["steps_per_epoch"]
    t0 = time.perf_counter()
    xs, y = family.make_data(s, seed)
    data_s = time.perf_counter() - t0

    # weights from the seed, by the benchmark: one jitted call on the
    # device, copied to the host once, before the model exists, so that the
    # copy on the device is gone before the program allocates anything and
    # the allocator's peak is the program's own. The host copy is the
    # reference's and is what set_parameter gets: a device array would cost
    # one small compile per distinct leaf shape to re-place (27 s for
    # Inception's 94)
    t0 = time.perf_counter()
    weights = jax.device_get(family.make_weights(s, seed))
    weights_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ff = family.build(config, s, chips, seed,
                      machine_spec=(rehearsal or {}).get("machine_spec"))
    ff_compile_s = time.perf_counter() - t0
    search_s = ff.search_seconds or 0.0
    choices = collections.Counter(
        getattr(st, "choice", None) for st in ff.strategy.values())
    emit(phase="compile", mesh=mesh_axes_of(ff), search_s=search_s,
         ff_compile_s=ff_compile_s, data_s=data_s, choices=choices,
         executor=type(ff.executor).__name__,
         wus=getattr(ff, "wus_enabled", None),
         overlap=getattr(ff, "overlap_enabled", None),
         cache_events=events.snapshot(), memory=memory_reading(chips))

    t0 = time.perf_counter()
    family.install_weights(ff, weights)
    got_leaf, want_leaf = family.readback(ff, weights)
    weights_s += time.perf_counter() - t0
    memory_weights = memory_reading(chips)   # the train step is not loaded
    checks = placement_checks(ff, xs[0][:batch], chips, on_tpu)
    checks.append(("weights_installed",
                   bool(np.array_equal(got_leaf, want_leaf)), None))
    checks += family.extra_checks(ff, s, chips, on_tpu)

    # the output check's system side; no profiler, no tracer
    system, first_step_s = system_side(ff, xs, y, batch)
    memory_check = memory_reading(chips)    # the train step is loaded now
    # warm-up of the window's own calls: one epoch, one fenced step
    t0 = time.perf_counter()
    ff.fit(xs, y, epochs=1, verbose=False)
    ff.fit([x[:batch] for x in xs], y[:batch], epochs=1, verbose=False)
    jax.block_until_ready(ff.params)
    warm_s = time.perf_counter() - t0
    before = events.snapshot()
    setup_s = time.perf_counter() - t_start
    emit(phase="setup", setup_s=setup_s, weights_s=weights_s,
         first_step_s=first_step_s, warm_epoch_s=warm_s,
         system_losses=system["losses"], cache_events=before,
         memory_after_weights=memory_weights,
         memory_after_check=memory_check, memory=memory_reading(chips))

    # ---- the window: part A, back-to-back epochs as users run them ----
    split = traffic.get("part_a_share", 0.5)
    a_seconds, b_seconds = seconds * split, seconds * (1 - split)
    epochs, ta, epoch_ends = 0, time.perf_counter(), []
    while True:
        ff.fit(xs, y, epochs=1, verbose=False)
        epochs += 1
        a_elapsed = time.perf_counter() - ta
        epoch_ends.append(a_elapsed)
        if a_elapsed >= a_seconds:
            break
    throughput = epochs * n / a_elapsed
    # ---- part B: one fenced step per call ----
    step_ms, tb, k = [], time.perf_counter(), 0
    while time.perf_counter() - tb < b_seconds or len(step_ms) < 2:
        sl = slice((k % s["steps_per_epoch"]) * batch,
                   (k % s["steps_per_epoch"] + 1) * batch)
        t0 = time.perf_counter()
        ff.fit([x[sl] for x in xs], y[sl], epochs=1, verbose=False)
        jax.block_until_ready(ff.params)
        step_ms.append((time.perf_counter() - t0) * 1e3)
        k += 1
    after = events.snapshot()
    window_compiles = (after.get("backend_compiles", 0)
                       - before.get("backend_compiles", 0))
    memory = memory_reading(chips)
    peak = memory["peak_bytes"]
    # the two peaks in the sum coincide if the one in use was reached with
    # the train step loaded (the reserved region is at its peak from then)
    peaks_coincide = (memory["peak_bytes_in_use"]
                      > memory_weights["peak_bytes_in_use"])
    fallbacks = family.kernel_fallbacks(ff)
    checks.append(("no_kernel_fallback", not fallbacks, fallbacks))
    checks.append(("no_compile_in_window", window_compiles == 0,
                   window_compiles))
    step_p95 = p95(step_ms)
    emit(phase="window", part_a_s=a_elapsed, epochs=epochs,
         samples=epochs * n, throughput=throughput, epoch_ends_s=epoch_ends,
         part_b_s=time.perf_counter() - tb, steps=len(step_ms),
         step_ms_median=statistics.median(step_ms), step_ms_p95=step_p95,
         steps_beyond_p95=sum(t > step_p95 for t in step_ms),
         step_ms_max=max(step_ms), window_compiles=window_compiles,
         peak_bytes=peak, memory=memory, peaks_coincide=peaks_coincide)

    # ---- traced extras ----
    counters = dict(search_s=search_s, ff_compile_s=ff_compile_s,
                    first_step_s=first_step_s,
                    window_compiles=window_compiles, throughput=throughput,
                    chips=chips, batch=batch, sizes=s,
                    train_flops_per_sample=family.train_flops_per_sample(s),
                    peaks=peaks, dispatch_ms=[])
    devices, session, run_peak = [], None, peak
    if trace:
        from benchmarks import trace_reduce as tr
        out_dir = os.path.join(root, OUT_DIR, name)
        os.makedirs(out_dir, exist_ok=True)
        # the readers of the session's metrics look here on their own: what
        # an earlier run left must not be read as this run's
        for k in (SESSION, FENCED, DISCARD):
            shutil.rmtree(os.path.join(out_dir, k), ignore_errors=True)
    if trace == 1:
        devices = profiled_epoch(ff, xs, y, out_dir)
        emit(phase="trace", **tr.describe(devices))
        counters["dispatch_ms"] = program_dispatch_ms(ff, xs, y, out_dir)
    elif trace == 2:
        devices, session, counters["dispatch_ms"], record = traced_tail(
            ff, xs, y, s, out_dir)
        # the line's `device.memory_peak_bytes` is the whole run's, tail
        # included, as the form of a `--trace 2` line asks; every value
        # under `metrics` stays the window's own
        memory_tail = memory_reading(chips)
        emit(phase="trace", **tr.describe(devices), **record,
             memory=memory_tail)
        run_peak = max(peak, memory_tail["peak_bytes"])

    # ---- the reference, with the device to itself ----
    release(ff)
    t0 = time.perf_counter()
    want = reference_side(family, weights, s, traffic, config, xs, y, batch)
    rows = compare(system, want, family.TOLERANCES,
                   getattr(family, "PREDICTIONS_ARE_PROBABILITIES", False))
    for r in rows:
        emit(phase="check", **r)
    for cname, ok, detail in checks:
        emit(phase="check", name=cname, ok=bool(ok), detail=detail)
    correct = all(r["ok"] for r in rows) and all(ok for _, ok, _ in checks)
    emit(phase="reference", reference_s=time.perf_counter() - t0,
         reference_losses=want["losses"], system_losses=system["losses"],
         correct=correct)

    # what a run can report end to end; BENCHMARK.json says which of them
    # a cell does (`peak_hbm_gb` is listed by none today: PERF.md, section 2)
    values = {"throughput": throughput, "step_ms_p95": step_p95,
              "peak_hbm_gb": peak / 1e9, "setup_s": setup_s}
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": run_peak}
    result = {"correct": bool(correct), "attempted": epochs + len(step_ms),
              "failed": 0, "metrics": {}, "device": device}
    if trace != 1:
        for m in mf.metrics_of(manifest, "end_to_end", name):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    if trace:
        ctx = dict(devices=devices, counters=counters, cell=cell,
                   config=config, traffic=traffic, family=family)
        for m in mf.metrics_of(manifest, "per_layer", name):
            value = load_by_path("layer_metrics", m["name"], root).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        bw = tr.mean_over_devices(devices, tr.busy_and_window)
        if bw is not None:
            device["busy_s"], device["window_s"] = bw
        result["breakdown"] = {"device_ops": tr.top_device_ops(devices)}
        if session is None:
            result["breakdown"]["idle_gaps"] = tr.top_idle_gaps(devices)
        else:
            # the tail's gaps carry the name of the program span that was
            # open on the host, on the session's shared clock
            from benchmarks import session_reduce as sr
            shares = sr.idle_shares_pct(devices, session.spans) or {}
            result["breakdown"].update(
                idle_gaps=sr.labelled_idle_gaps(devices, session.spans),
                idle_shares_pct=sorted(shares.items(),
                                       key=lambda kv: -kv[1]))
        reduction = os.path.join(root, OUT_DIR, name, f"trace_{seed}.json")
        with open(reduction, "w") as f:
            json.dump(dict(result=result, dispatch_ms=counters["dispatch_ms"]),
                      f, default=_jsonable)
    print(json.dumps(result, default=_jsonable), flush=True)
    return result
