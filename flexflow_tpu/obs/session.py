"""The process-wide trace session: ``start_trace`` / ``stop_trace``.

One control for the program's tracing, usable from outside a ``fit``
call and in the process that holds the chip:

    from flexflow_tpu import obs
    obs.start_trace("/tmp/run1", device=True)
    ff.fit(x, y, epochs=1); ff.fit(x, y, epochs=1)
    paths = obs.stop_trace()   # trace, events, counters, xplane

While a session is open every ``fit`` / ``fit_loader`` / ``evaluate``
call records its spans into the session's one ``StepTracer`` instead of
making its own, so the stretch between two calls lies on the same
timeline as the calls. Nothing is fenced: the spans are the host's own
time inside the program an untraced run executes. ``device=True`` also
runs ``jax.profiler`` for the length of the session (this module holds
the program's only ``jax.profiler.start_trace`` call; the windowed
``--profile-steps`` capture goes through it) and ties the tracer's
clock to the profiler's: short marker annotations whose
``perf_counter`` brackets are known are looked up in the profile, and
the shift lands in the artifact's header as ``clock_shift_us`` (add it
to a profiler timestamp in microseconds to get a span's ``ts``).

``stop_trace`` writes the spans and a counters snapshot and returns;
the header holds the allocator's peak over the local devices
(``device_peak_bytes``). The summary, drift and simulator reports stay
with the per-call ``fit(trace_dir=...)`` form. With ``device=True`` it
also writes the join table of the train step the session's ``fit``
calls ran (``<stem>.step_scopes.json``, obs/step_scopes.py): once the
profiler has stopped, the step is lowered and compiled once more from
the shapes and shardings its first call had, and every instruction's
part and direction are read from the text; beside the table, where a
search compiled the model, stand the native simulator's ``prices`` of
the strategy that ran, by the table's parts and directions (one
replay, obs/simtrace.py). Without ``device=True`` nothing is lowered,
compiled or replayed.
"""

from __future__ import annotations

import functools
import glob
import os
import statistics
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.obs.registry import get_registry
from flexflow_tpu.obs.step_scopes import StepScopes
from flexflow_tpu.obs.tracer import StepTracer

# the marker the clock tie looks up in the profile
TIE_ANNOTATION = "ff_clock_tie"
TIE_MARKERS = 5   # at each end of the session

# the open session; `FFModel._make_tracer` reads this one global
_SESSION: Optional["TraceSession"] = None


def session_tracer() -> Optional[StepTracer]:
    """The open session's tracer, or None."""
    return _SESSION.tracer if _SESSION is not None else None


def step_keeper(ff):
    """``keep(step, args)`` if a session with ``device=True`` is open
    and holds no train step of ``ff``'s executor yet, else None:
    ``fit``'s first dispatch hands it the jitted step and the call's
    arguments, of which the shapes and shardings are kept for the join
    table, and the model, whose executed strategy is priced beside it."""
    scopes = _SESSION.step_scopes if _SESSION is not None else None
    if scopes is None or not scopes.wants(ff.executor):
        return None
    return functools.partial(scopes.keep, ff)


# ---------------------------------------------------------------------------
# the profiler (the program's only call site)


def start_profiler(profile_dir: str) -> None:
    """Start ``jax.profiler`` with the Python tracer off: device lanes
    and TraceMe annotations only, the least host drag."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(profile_dir, profiler_options=options)


def stop_profiler() -> None:
    import jax
    jax.profiler.stop_trace()


def newest_xplane(profile_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


# ---------------------------------------------------------------------------
# the clock tie


def tie_marker(name: str = TIE_ANNOTATION) -> Tuple[float, float]:
    """Emit one marker annotation; returns the ``perf_counter`` bracket
    around the instant the profiler stamped as its start."""
    import jax
    ann = jax.profiler.TraceAnnotation(name)
    p0 = time.perf_counter()
    ann.__enter__()
    p1 = time.perf_counter()
    ann.__exit__(None, None, None)
    return p0, p1


def clock_shift_us(origin: float,
                   brackets: Sequence[Tuple[float, float]],
                   profiler_starts_us: Sequence[float]
                   ) -> Tuple[Optional[float], Optional[float]]:
    """(shift, spread) in microseconds: ``profiler_us + shift`` is the
    tracer's ``ts`` (microseconds since the tracer's ``origin``).
    ``brackets[i]`` is the ``perf_counter`` pair around the host event
    whose start the profiler stamped as ``profiler_starts_us[i]``; the
    event's host time is taken as the bracket's middle. The shift is the
    median over the pairs, the spread the distance between the largest
    and the smallest single-pair shift."""
    shifts = [((p0 + p1) / 2 - origin) * 1e6 - ts
              for (p0, p1), ts in zip(brackets, profiler_starts_us)]
    if not shifts:
        return None, None
    return statistics.median(shifts), max(shifts) - min(shifts)


def annotation_starts_us(xplane_path: str, name: str = TIE_ANNOTATION
                         ) -> List[float]:
    """Start times (profiler microseconds) of the host annotations
    called ``name`` in an ``.xplane.pb``, in order."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [ev.start_ns / 1e3 for ev in line.events
                    if ev.name == name]
    return sorted(out)


def device_peaks() -> Dict[str, Optional[int]]:
    """The allocator's peak on the fullest local device, for a session's
    header: ``device_peak_bytes`` is ``peak_bytes_in_use`` plus
    ``peak_bytes_reserved`` of ``memory_stats()`` (the TPU runtime keeps
    a loaded program's scratch memory in the reserved region, which
    ``bytes_in_use`` leaves out), ``device_peak_bytes_in_use`` the first
    part alone. What the search's predicted memory is held against: did
    the step fit, and by how much was it misjudged. None each on a
    backend without the counters (the CPU)."""
    import jax
    best = None
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            continue
        in_use = int(stats["peak_bytes_in_use"])
        peak = in_use + int(stats.get("peak_bytes_reserved", 0))
        if best is None or peak > best[0]:
            best = (peak, in_use)
    return dict(device_peak_bytes=best and best[0],
                device_peak_bytes_in_use=best and best[1])


# ---------------------------------------------------------------------------


class TraceSession:
    """One open session; made by ``start_trace``."""

    def __init__(self, trace_dir: str, device: bool):
        self.tracer = StepTracer(trace_dir, run_name="session", fence=False)
        self.device = device
        self.profile_dir = os.path.join(
            trace_dir, self.tracer.file_stem + ".jaxprof")
        self._brackets: List[Tuple[float, float]] = []
        # the train steps dispatched under the profiler, for their tables
        self.step_scopes = StepScopes() if device else None
        if device:
            start_profiler(self.profile_dir)
            self._mark()

    def _mark(self) -> None:
        self._brackets += [tie_marker() for _ in range(TIE_MARKERS)]

    def _tie(self, xplane: str) -> Dict[str, Any]:
        """The header's clock tie, from the markers found in the profile
        (no shift if the profile does not hold every one of them)."""
        starts = annotation_starts_us(xplane)
        if len(starts) != len(self._brackets):
            return dict(clock_tie_markers=len(starts))
        shift, spread = clock_shift_us(self.tracer._origin, self._brackets,
                                       starts)
        return dict(clock_shift_us=shift, clock_tie_spread_us=spread,
                    clock_tie_markers=len(starts))

    def stop(self) -> Dict[str, Optional[str]]:
        tracer = self.tracer
        xplane = step_scopes = None
        if self.device:
            self._mark()
            stop_profiler()
            xplane = newest_xplane(self.profile_dir)
            if xplane is not None:
                tracer.set_meta(
                    xplane=os.path.relpath(xplane, tracer.trace_dir),
                    **self._tie(xplane))
            # the profiler has stopped: what lowering and compiling the
            # step again costs falls into no traced second
            meta = self.step_scopes.write(
                tracer.trace_dir, tracer.file_stem, host_id=tracer.host_id)
            tracer.set_meta(**meta)
            if "step_scopes" in meta:
                step_scopes = os.path.join(tracer.trace_dir,
                                           meta["step_scopes"])
        tracer.set_meta(**device_peaks())
        paths: Dict[str, Optional[str]] = dict(tracer.export())
        paths["counters"] = get_registry().export(
            os.path.join(tracer.trace_dir,
                         tracer.file_stem + ".counters.json"),
            host_id=tracer.host_id)
        paths["xplane"] = xplane
        paths["step_scopes"] = step_scopes
        return paths


def start_trace(trace_dir: str, device: bool = True) -> TraceSession:
    """Open the process's trace session (one at a time)."""
    global _SESSION
    if _SESSION is not None:
        raise RuntimeError("a trace session is already open; call "
                           "flexflow_tpu.obs.stop_trace() first")
    _SESSION = TraceSession(trace_dir, device)
    return _SESSION


def stop_trace() -> Dict[str, Optional[str]]:
    """Close the session: stop the profiler, write the spans
    (``*.trace.json``, ``*.events.jsonl``), the counters snapshot and,
    with ``device=True``, the train step's join table
    (``*.step_scopes.json``). Returns their paths and the ``.xplane.pb``
    path (both None without ``device=True``)."""
    global _SESSION
    session, _SESSION = _SESSION, None
    if session is None:
        raise RuntimeError("no trace session is open")
    return session.stop()
