"""Device-trace attribution: where a step's time goes ON THE DEVICE.

The StepTracer records host-side wall time; this module closes the gap
ROADMAP item (d) names — a windowed ``jax.profiler`` capture around a
range of training steps, plus a stdlib-only parser that classifies the
emitted Chrome-trace device spans into compute / collective / host-stall
buckets and runs interval arithmetic per step:

- ``compute_time``        union of device compute spans inside the step
- ``comms_time``          union of collective spans (all-reduce,
                          all-gather, reduce-scatter, collective-permute,
                          all-to-all)
- ``overlapped_comms``    comms time hidden under compute
- ``exposed_comms``       comms the step actually waits on — the number
                          the comms-compute-overlap direction ratchets

Capture is windowed (``fit(profile_steps="A:B")`` / ``--profile-steps``)
because a whole-run profile of a long job is gigabytes; a 2-4 step
window is the steady-state sample. The CPU backend emits the same
Chrome-trace JSON (``plugins/profile/*/*.trace.json.gz``) with per-op
``args.hlo_op`` spans, so the whole pipeline runs devicelessly in
tier-1. Steps are located inside the profile via
``jax.profiler.StepTraceAnnotation`` markers the capture wraps around
each step, which also give the host-clock correlation used to rebase
device lanes onto the StepTracer timeline for the merged Perfetto view.

Cf. "A Learned Performance Model for TPUs" (PAPERS.md 2008.01040): the
per-collective measured times this produces are exactly the calibration
signal the analytic simulator lacks — ``obs/drift.py`` joins them
against the census-priced predictions and ``scripts/calibrate.py
--ingest-drift`` folds the ratios into CALIBRATION.json.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

from flexflow_tpu.obs.inspect import COLLECTIVE_KINDS

# the step marker the capture wraps around each training step; the
# parser finds these annotations inside the profile to window device
# spans per step (args.step_num carries the global step index)
STEP_ANNOTATION = "ff_step"

# HLO op-name prefixes bucketed as host stalls: device time spent
# waiting on the host feed or cross-program transfers, not computing
HOST_OP_PREFIXES = ("infeed", "outfeed", "send", "recv", "host-call")

_KIND_RE = re.compile(
    r"^(" + "|".join(COLLECTIVE_KINDS) + r"|collective-broadcast)"
    r"(-start|-done)?(\.\d+)?$")

# Perfetto lane tids for device events injected into the StepTracer
# trace (tid 0 is the host train_loop): one lane per bucket, shared by
# all local devices (the accounting below reduces each device on its
# own; the lanes only draw them together).
TID_COMPUTE, TID_COMMS, TID_HOST, TID_ASYNC = 64, 65, 66, 67
LANE_THREADS = {TID_COMPUTE: "device:compute", TID_COMMS: "device:comms",
                TID_HOST: "device:host", TID_ASYNC: "device:async"}


def parse_profile_steps(spec: Optional[str]) -> Optional[Tuple[int, int]]:
    """``"A:B"`` -> capture steps A..B-1 (half-open, python-slice
    convention); bare ``"N"`` -> just step N. None/"" -> no capture."""
    if not spec:
        return None
    s = str(spec).strip()
    try:
        if ":" in s:
            a, b = s.split(":", 1)
            start, stop = int(a), int(b)
        else:
            start, stop = int(s), int(s) + 1
    except ValueError:
        raise ValueError(
            f"--profile-steps expects 'A:B' or 'N', got {spec!r}")
    if start < 0 or stop <= start:
        raise ValueError(
            f"--profile-steps window must satisfy 0 <= A < B, got {spec!r}")
    return start, stop


# ---------------------------------------------------------------------------
# classification + interval arithmetic (stdlib only)


def classify_hlo_op(name: str) -> Tuple[str, Optional[str]]:
    """Bucket one device HLO op-name: ``("collective", kind)``,
    ``("host", None)``, or ``("compute", None)``."""
    m = _KIND_RE.match(name)
    if m:
        return "collective", m.group(1)
    for p in HOST_OP_PREFIXES:
        if name.startswith(p):
            return "host", None
    return "compute", None


def merge_intervals(iv: List[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    """Union of half-open intervals, sorted and coalesced."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def interval_total(merged: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def intersect_total(a: List[Tuple[float, float]],
                    b: List[Tuple[float, float]]) -> float:
    """Total overlap between two MERGED interval lists (two-pointer)."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


# ---------------------------------------------------------------------------
# Chrome-trace parsing


def load_chrome_trace(path: str) -> Dict[str, Any]:
    """Load a Chrome-trace JSON, gzipped (``*.trace.json.gz``, what
    ``jax.profiler`` emits) or plain."""
    if path.endswith(".gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    with open(path) as f:
        return json.load(f)


def locate_profile_traces(profile_dir: str) -> List[str]:
    """The Chrome-trace files a ``jax.profiler`` session left under its
    log dir (``plugins/profile/<session>/<host>.trace.json.gz``). When
    repeated sessions share the dir, only the NEWEST session's files are
    returned."""
    sessions = sorted(glob.glob(os.path.join(profile_dir, "plugins",
                                             "profile", "*")))
    if not sessions:
        return []
    return sorted(glob.glob(os.path.join(sessions[-1], "*.trace.json*")))


# the threads of a TPU's ``/device:`` process that carry device work:
# "XLA Ops" has one span per executed HLO op, "Async XLA Ops" the
# asynchronous ops from start to done (copies, slices, collectives),
# which overlap the first. Their siblings are roll-ups of the same time
# — "XLA Modules" (one span per program run) and "Steps" (step
# groupings that include the gaps between programs) — and counting them
# would report the whole step as device compute.
DEVICE_OP_THREAD = "XLA Ops"
DEVICE_ASYNC_THREAD = "Async XLA Ops"


def extract_device_events(trace: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Device op spans from a profiler Chrome trace.

    An event is a device op when its args carry ``hlo_op``/``hlo_module``
    (the CPU thunk executor stamps these) or when it sits on the
    ``XLA Ops`` or ``Async XLA Ops`` thread of a ``/device:`` process
    (real TPU lanes). Python-tracer frames (``$``-prefixed) and runtime
    bookkeeping spans carry neither and are dropped. Returns rows
    ``{name, ts, dur, bucket, kind, device}`` (µs). ``device`` is the
    ``/device:`` process the span ran on (None for CPU thunk spans, which
    all share the host process); an asynchronous span that is not a
    collective gets the bucket ``"async"``: it keeps the device busy but
    is neither compute nor communication."""
    device_pids = set()
    lanes: Dict[Any, str] = {}
    for e in trace.get("traceEvents", []):
        if e.get("ph") != "M":
            continue
        label = str((e.get("args") or {}).get("name", ""))
        if e.get("name") == "process_name" and label.startswith("/device:"):
            device_pids.add(e.get("pid"))
        elif e.get("name") == "thread_name" and label in (
                DEVICE_OP_THREAD, DEVICE_ASYNC_THREAD):
            lanes[(e.get("pid"), e.get("tid"))] = label
    out: List[Dict[str, Any]] = []
    for e in trace.get("traceEvents", []):
        if e.get("ph") != "X":
            continue
        name = e.get("name") or ""
        args = e.get("args") or {}
        pid = e.get("pid")
        lane = lanes.get((pid, e.get("tid"))) if pid in device_pids else None
        if not (args.get("hlo_op") or args.get("hlo_module") or lane):
            continue
        if name.startswith("$"):
            continue
        bucket, kind = classify_hlo_op(name)
        if lane == DEVICE_ASYNC_THREAD and bucket != "collective":
            bucket = "async"
        out.append(dict(name=name, ts=float(e.get("ts", 0.0)),
                        dur=float(e.get("dur", 0.0)),
                        bucket=bucket, kind=kind,
                        device=pid if lane else None))
    return out


def extract_step_windows(trace: Dict[str, Any],
                         annotation: str = STEP_ANNOTATION
                         ) -> Dict[int, Tuple[float, float]]:
    """``{step_index: (ts, end)}`` (µs, profiler timebase) from the
    StepTraceAnnotation markers the capture wrapped around each step."""
    out: Dict[int, Tuple[float, float]] = {}
    for e in trace.get("traceEvents", []):
        if e.get("ph") != "X" or e.get("name") != annotation:
            continue
        args = e.get("args") or {}
        try:
            step = int(args.get("step_num"))
        except (TypeError, ValueError):
            continue
        t0 = float(e.get("ts", 0.0))
        t1 = t0 + float(e.get("dur", 0.0))
        if step in out:  # same step re-entered: span the union
            t0 = min(t0, out[step][0])
            t1 = max(t1, out[step][1])
        out[step] = (t0, t1)
    return out


def attribute_window(device_events: List[Dict[str, Any]],
                     t0: float, t1: float) -> Dict[str, Any]:
    """Interval accounting of ONE device's spans inside ``[t0, t1]`` (µs):
    ``compute_s`` is the time during which the device computes,
    ``overlapped_comms_s`` is collective time hidden under that compute,
    ``exposed_comms_s = comms_s - overlapped_comms_s`` is what the step
    waits on, ``busy_s`` the union of every span (asynchronous copies
    included). Times in seconds."""
    by_bucket: Dict[str, List[Tuple[float, float]]] = {}
    kind_iv: Dict[str, List[Tuple[float, float]]] = {}
    kind_count: Dict[str, int] = {}
    for ev in device_events:
        s = max(ev["ts"], t0)
        e = min(ev["ts"] + ev["dur"], t1)
        if e <= s:
            continue
        by_bucket.setdefault(ev["bucket"], []).append((s, e))
        if ev["bucket"] == "collective":
            kind_iv.setdefault(ev["kind"], []).append((s, e))
            kind_count[ev["kind"]] = kind_count.get(ev["kind"], 0) + 1
    compute_u = merge_intervals(by_bucket.get("compute", []))
    comms_u = merge_intervals(by_bucket.get("collective", []))
    compute_s = interval_total(compute_u) / 1e6
    comms_s = interval_total(comms_u) / 1e6
    overlapped_s = intersect_total(comms_u, compute_u) / 1e6
    busy_s = interval_total(merge_intervals(
        [iv for ivs in by_bucket.values() for iv in ivs])) / 1e6
    wall_s = (t1 - t0) / 1e6
    return dict(
        wall_s=wall_s,
        compute_s=compute_s,
        comms_s=comms_s,
        overlapped_comms_s=overlapped_s,
        exposed_comms_s=comms_s - overlapped_s,
        host_s=interval_total(
            merge_intervals(by_bucket.get("host", []))) / 1e6,
        busy_s=busy_s,
        idle_s=max(wall_s - busy_s, 0.0),
        # per-kind hidden/exposed split (ISSUE 9): a kind's hidden
        # seconds are its intervals under the compute union — the
        # measured counterpart of the simulator's per-choice hidden
        # term, so the merged report can show WHERE overlap lands
        per_kind={k: _kind_entry(v, kind_count[k], compute_u)
                  for k, v in kind_iv.items()},
    )


def _mean_rows(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Key-wise mean of per-device rows (nested ``per_kind`` included; a
    kind a device did not run counts as zero there)."""
    n = len(rows)
    out: Dict[str, Any] = {}
    for key in rows[0]:
        if key == "per_kind":
            kinds = sorted({k for r in rows for k in r["per_kind"]})
            zero = dict(time_s=0.0, count=0, overlapped_s=0.0,
                        exposed_s=0.0)
            out[key] = {k: _mean_rows([r["per_kind"].get(k, zero)
                                       for r in rows]) for k in kinds}
        else:
            mean = sum(r[key] for r in rows) / n
            out[key] = int(mean) if key == "count" and mean == int(mean) \
                else mean
    return out


def attribute_steps(device_events: List[Dict[str, Any]],
                    step_windows: Dict[int, Tuple[float, float]]
                    ) -> List[Dict[str, Any]]:
    """Per-step interval accounting over the device spans: every device
    is reduced on its own timeline (``attribute_window``), then the
    devices are averaged — as ``benchmarks/trace_reduce.py`` does. A
    union over devices would hide one chip's idle time under another's
    work. CPU thunk spans carry no device and form one group."""
    by_device: Dict[Any, List[Dict[str, Any]]] = {}
    for ev in device_events:
        by_device.setdefault(ev.get("device"), []).append(ev)
    rows: List[Dict[str, Any]] = []
    for step in sorted(step_windows):
        t0, t1 = step_windows[step]
        per_device = [attribute_window(evs, t0, t1)
                      for evs in by_device.values()] or [
                          attribute_window([], t0, t1)]
        rows.append(dict(step=step, **_mean_rows(per_device)))
    return rows


def _kind_entry(iv, count, compute_u):
    u = merge_intervals(iv)
    t = interval_total(u) / 1e6
    hidden = intersect_total(u, compute_u) / 1e6
    return dict(time_s=t, count=count, overlapped_s=hidden,
                exposed_s=t - hidden)


def aggregate_attribution(per_step: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Roll per-step attribution rows up into run totals plus a
    per-collective-kind summary (``{kind: {time_s, count, per_step_s}}``
    — the measured half of the measured-vs-priced drift join)."""
    n = len(per_step)
    totals = dict(compute_s=0.0, comms_s=0.0, overlapped_comms_s=0.0,
                  exposed_comms_s=0.0, host_s=0.0, busy_s=0.0, idle_s=0.0,
                  wall_s=0.0)
    coll: Dict[str, Dict[str, float]] = {}
    for row in per_step:
        for k in totals:
            totals[k] += row[k]
        for kind, e in row["per_kind"].items():
            c = coll.setdefault(kind, dict(time_s=0.0, count=0,
                                           overlapped_s=0.0, exposed_s=0.0))
            c["time_s"] += e["time_s"]
            c["count"] += e["count"]
            c["overlapped_s"] += e.get("overlapped_s", 0.0)
            c["exposed_s"] += e.get("exposed_s", e["time_s"])
    for c in coll.values():
        c["per_step_s"] = c["time_s"] / n if n else 0.0
        c["exposed_per_step_s"] = c["exposed_s"] / n if n else 0.0
        c["overlapped_per_step_s"] = c["overlapped_s"] / n if n else 0.0
    return dict(steps=n, totals=totals, collectives=coll)


def _parse_traces(trace_paths: List[str],
                  annotation: str = STEP_ANNOTATION):
    """(device_events, step_windows) pooled over a capture's
    Chrome-trace files (unreadable files are skipped — a half-written
    profile must not kill the report)."""
    events: List[Dict[str, Any]] = []
    windows: Dict[int, Tuple[float, float]] = {}
    for p in trace_paths:
        try:
            trace = load_chrome_trace(p)
        except (OSError, ValueError):
            continue
        events += extract_device_events(trace)
        windows.update(extract_step_windows(trace, annotation))
    return events, windows


def attribution_report(trace_paths: List[str],
                       annotation: str = STEP_ANNOTATION) -> Dict[str, Any]:
    """Parse + attribute one capture's Chrome-trace files.

    Returns ``{per_step, steps, totals, collectives, device_events}``."""
    events, windows = _parse_traces(trace_paths, annotation)
    per_step = attribute_steps(events, windows)
    return dict(per_step=per_step, device_events=len(events),
                **aggregate_attribution(per_step))


# ---------------------------------------------------------------------------
# capture


class NullCapture:
    """Inert capture: the no-profile-window fast path."""

    active = False
    captured = False
    _NULL = contextlib.nullcontext()

    def step(self, step_index: int):
        return self._NULL

    def finalize(self, ff, tracer):
        return None


NULL_CAPTURE = NullCapture()


class _CaptureStep:
    """Per-step context: starts the profiler session when the window
    opens, wraps the step in a StepTraceAnnotation while capturing, and
    stops the session when the window closes — recording the host
    perf_counter bracket of every annotated step for the clock
    correlation the Perfetto lane merge needs."""

    __slots__ = ("cap", "idx", "_ann", "_bracket")

    def __init__(self, cap, idx):
        self.cap = cap
        self.idx = idx
        self._ann = None

    def __enter__(self):
        cap = self.cap
        if cap.state == "idle" and self.idx >= cap.window[0]:
            cap._start()
        if cap.state == "capturing":
            try:
                import jax
                self._ann = jax.profiler.StepTraceAnnotation(
                    STEP_ANNOTATION, step_num=self.idx)
                p0 = time.perf_counter()
                self._ann.__enter__()
                self._bracket = (p0, time.perf_counter())
            except Exception:
                self._ann = None
        return self

    def __exit__(self, *exc):
        cap = self.cap
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:
                pass
            cap.host_steps[self.idx] = self._bracket
        if cap.state == "capturing" and self.idx + 1 >= cap.window[1]:
            cap._stop()
        return False


class DeviceTraceCapture:
    """One windowed ``jax.profiler`` session around a step range.

    Wrap each training step in ``capture.step(i)``; the session starts
    when step ``window[0]`` begins and stops after step ``window[1]-1``
    completes. ``finalize`` parses the emitted trace, writes the
    ``.devtrace.json`` attribution artifact, feeds the counter registry,
    and injects rebased device lanes + per-step attribution counter
    tracks into the StepTracer's Perfetto output. Every profiler
    interaction degrades to a warning — observability must never kill
    the run it watches."""

    active = True

    def __init__(self, tracer, window: Tuple[int, int]):
        self.tracer = tracer
        self.window = window
        self.profile_dir = os.path.join(tracer.trace_dir,
                                        tracer.file_stem + ".jaxprof")
        self.state = "idle"  # -> capturing -> done | failed
        self.host_steps: Dict[int, Tuple[float, float]] = {}
        self.trace_paths: List[str] = []

    @property
    def captured(self) -> bool:
        return self.state == "done" and bool(self.trace_paths)

    def step(self, step_index: int):
        if self.state in ("done", "failed"):
            return NullCapture._NULL
        return _CaptureStep(self, step_index)

    def _start(self) -> None:
        try:
            from flexflow_tpu.obs.session import start_profiler
            start_profiler(self.profile_dir)
            self.state = "capturing"
        except Exception as e:
            import sys
            print(f"[obs] device-trace capture failed to start ({e!r}); "
                  "profiling disabled for this run", file=sys.stderr)
            self.state = "failed"

    def _stop(self) -> None:
        try:
            from flexflow_tpu.obs.session import stop_profiler
            stop_profiler()
            self.state = "done"
            self.trace_paths = locate_profile_traces(self.profile_dir)
            if not self.trace_paths:
                import sys
                print(f"[obs] profiler session left no Chrome trace under "
                      f"{self.profile_dir}", file=sys.stderr)
        except Exception as e:
            import sys
            print(f"[obs] device-trace capture failed to stop ({e!r})",
                  file=sys.stderr)
            self.state = "failed"

    # ---- post-run ----------------------------------------------------------
    def _clock_shift_us(self, step_windows) -> float:
        """Profiler-timebase -> tracer-timeline shift: the session's
        clock tie (obs/session.clock_shift_us), with each step's
        annotation as a marker — the host perf_counter bracket recorded
        around its start vs the start the profile gives it."""
        from flexflow_tpu.obs.session import clock_shift_us
        origin = getattr(self.tracer, "_origin", None)
        seen = [idx for idx in self.host_steps if idx in step_windows]
        if origin is None or not seen:
            return 0.0
        shift, _ = clock_shift_us(
            origin, [self.host_steps[idx] for idx in seen],
            [step_windows[idx][0] for idx in seen])
        return shift

    def finalize(self, ff, tracer) -> Optional[Dict[str, Any]]:
        """Parse + attribute, emit the artifact, merge Perfetto lanes.
        Returns the attribution report (None when nothing was captured).
        Must run BEFORE ``tracer.export()`` so the device lanes land in
        the exported trace."""
        if self.state == "capturing":  # run ended inside the window
            self._stop()
        if not self.captured:
            return None
        events, windows = _parse_traces(self.trace_paths)
        per_step = attribute_steps(events, windows)
        report = dict(
            window=list(self.window),
            profile_dir=self.profile_dir,
            trace_files=[os.path.relpath(p, tracer.trace_dir)
                         for p in self.trace_paths],
            per_step=per_step,
            device_events=len(events),
            **aggregate_attribution(per_step),
        )
        # registry: the exposed-comms distribution survives into the
        # counters snapshot (bounded reservoir, registry.observe)
        from flexflow_tpu.obs.registry import get_registry
        reg = get_registry()
        for row in per_step:
            reg.observe(f"{tracer.run_name}/devtrace_exposed_comms_s",
                        row["exposed_comms_s"])
        # Perfetto lanes: device spans + per-step attribution counters,
        # rebased from the profiler timebase onto the tracer timeline
        shift = self._clock_shift_us(windows)
        lane_events: List[Dict[str, Any]] = []
        tid_of = {"compute": TID_COMPUTE, "collective": TID_COMMS,
                  "host": TID_HOST, "async": TID_ASYNC}
        for ev in events:
            ce = dict(name=ev["name"], ph="X", tid=tid_of[ev["bucket"]],
                      ts=round(ev["ts"] + shift, 3),
                      dur=round(ev["dur"], 3), cat="devtrace")
            if ev["kind"]:
                ce["args"] = dict(kind=ev["kind"])
            lane_events.append(ce)
        for row in per_step:
            t0 = windows[row["step"]][0] + shift
            lane_events.append(dict(
                name="step_attribution", ph="C", tid=0,
                ts=round(t0, 3), cat="devtrace",
                args=dict(compute_ms=round(row["compute_s"] * 1e3, 4),
                          overlapped_comms_ms=round(
                              row["overlapped_comms_s"] * 1e3, 4),
                          exposed_comms_ms=round(
                              row["exposed_comms_s"] * 1e3, 4))))
        tracer.add_trace_events(lane_events, dict(LANE_THREADS))
        from flexflow_tpu.obs.artifacts import write_artifact
        stem = os.path.join(tracer.trace_dir, tracer.file_stem)
        write_artifact(stem + ".devtrace.json", report,
                       host_id=tracer.host_id, kind="devtrace",
                       header_extra=dict(run_name=tracer.run_name,
                                         run_seq=tracer.run_seq))
        return report


def make_capture(tracer, profile_steps: Optional[str]):
    """A DeviceTraceCapture over the parsed window, or the shared no-op.

    Needs an ACTIVE tracer (the artifacts land in its trace dir and the
    lanes merge into its Perfetto output): a profile window without a
    trace dir warns and degrades rather than raising mid-fit."""
    window = parse_profile_steps(profile_steps)
    if window is None:
        return NULL_CAPTURE
    if not getattr(tracer, "active", False):
        import sys
        print("[obs] --profile-steps needs --trace-dir (device-trace "
              "artifacts land in the trace dir); profiling skipped",
              file=sys.stderr)
        return NULL_CAPTURE
    return DeviceTraceCapture(tracer, window)


# ---------------------------------------------------------------------------
# goodput / MFU step metrics (registry + drift report surface)


def train_step_flops(ff) -> float:
    """Model FLOPs of one training step: analytic per-op forward FLOPs
    (the roofline machinery's ``op.flops()``) x3 for fwd+bwd — the same
    fwd:bwd convention the drift predictor uses. Global (whole-batch)
    FLOPs; divide by chip count for per-chip."""
    return 3.0 * sum(float(n.op.flops()) for n in ff.executor.nodes)


def record_step_metrics(ff, tracer, registry=None) -> Dict[str, Any]:
    """Step-time histogram + goodput + MFU into the counter registry.

    - ``<run>/step_time_s`` observations (p50/p99 survive into the
      counters snapshot via the registry's bounded reservoir)
    - ``<run>/goodput`` gauge: productive-step time / run wall time —
      what fraction of the traced run the device spent inside steps
    - ``<run>/mfu`` gauge: model FLOPs per step / chips / median step
      time / chip peak FLOPs (meaningful on TPU; on cpu-sim it is
      relative to the synthetic 1 TFLOP/s peak)
    Returns the same numbers as a dict for the drift report."""
    from flexflow_tpu.obs.registry import get_registry, percentile
    if registry is None:
        registry = get_registry()
    run = tracer.run_name
    ds = tracer.step_durations_s()
    # step 0 carries the jit compile: record it SEPARATELY and never let
    # it into the percentile reservoir — a single-step run used to
    # observe its compile step, which is how OBS_REPORT once showed a
    # 17 s p99 against an 18 ms p50 (ISSUE 8 satellite). With one step
    # there is no steady-state sample, so nothing is observed.
    steady = ds[1:]
    out: Dict[str, Any] = dict(steps=len(ds))
    if ds:
        out["compile_time_s"] = ds[0]
        registry.gauge(f"{run}/compile_time_s", ds[0])
    for d in steady:
        registry.observe(f"{run}/step_time_s", d)
    if steady:
        s = sorted(steady)
        out["step_time_p50"] = percentile(s, 0.50)
        out["step_time_p99"] = percentile(s, 0.99)
    wall = tracer.run_wall_s()
    if wall and ds:
        out["goodput"] = min(sum(ds) / wall, 1.0)
        registry.gauge(f"{run}/goodput", out["goodput"])
    spec = getattr(ff, "machine_spec", None)
    step_s = out.get("step_time_p50")
    if spec is not None and step_s:
        n_chips = int(ff.mesh.devices.size)
        flops = train_step_flops(ff)
        out["model_flops_per_step"] = flops
        out["mfu"] = flops / n_chips / step_s / float(spec.flops)
        registry.gauge(f"{run}/mfu", out["mfu"])
    return out
