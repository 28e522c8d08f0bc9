"""Real-chip op microbenchmarks feeding the search's measured-cost channel.

Analog of the reference's microbenchmark calibration: its simulator times
each operator's forward/backward on the actual device and caches the
result by parameter hash (``measure_operator_cost``,
/root/reference/src/runtime/model.cu:38-74;
``hash_to_operator_cost``, /root/reference/include/flexflow/simulator.h:750-752),
so the search optimizes real costs instead of an analytic model. Here each
materialized Op's ``forward`` (and its JAX-derived backward) is jitted and
timed standalone on the current default device; results are keyed by the
op's structural ``param_key`` hash + platform so repeated compiles and
repeated runs hit the cache.

The native search consumes the table through ``measured`` entries
``"<guid>:fwd"`` / ``"<guid>:bwd"`` (native/ffs_strategy.hpp node_cost):
measured seconds for the *unsharded* op, which the cost model divides by
the sharding's work_div — mirroring how the reference scales its measured
per-op cost by the machine view's degree.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops.base import OpContext

# process-wide cache: param-key hash -> (fwd_s, bwd_s)
_CACHE: Dict[str, Tuple[float, float]] = {}


def op_cost_key(op) -> str:
    """Structural identity of an op config on this platform — two ops with
    identical type/shapes/properties share one measurement (the analog of
    the reference's *Params hash). The execution layout is part of the
    identity: an NHWC conv and an NCHW conv are different programs with
    very different costs (flexflow_tpu/layout.py), so their measurements
    must never alias."""
    platform = jax.devices()[0].platform
    device = getattr(jax.devices()[0], "device_kind", platform)
    raw = repr((op.param_key(), getattr(op, "exec_layout", "NCHW"),
                platform, device))
    return hashlib.sha1(raw.encode()).hexdigest()[:16]


def op_io_bytes(op, dtype_size: float = 4.0) -> float:
    """HBM bytes one forward pass of the op must move: inputs + outputs +
    parameters, at ``dtype_size`` bytes/element. The denominator of the
    op's arithmetic intensity in the roofline report
    (flexflow_tpu/obs/roofline.py) — a lower bound (reads each operand
    once), matching the roofline model's convention."""
    elems = sum(float(np.prod(s)) for s in op.input_shapes)
    elems += sum(float(np.prod(s)) for s in op.output_shapes)
    elems += float(op.params_elems())
    return dtype_size * elems


def _example_inputs(op, rs: np.random.RandomState) -> List[jax.Array]:
    """Random inputs honoring the few ops with integral-domain inputs.

    Ops assigned the NHWC execution layout (flexflow_tpu/layout.py)
    consume physically channels-last values — their example inputs must
    be NHWC-shaped or the standalone forward rejects the channel count."""
    nhwc = getattr(op, "exec_layout", "NCHW") == "NHWC"
    arrs = []
    for i, shp in enumerate(op.input_shapes):
        if nhwc and len(shp) == 4:
            shp = tuple(shp[d] for d in (0, 2, 3, 1))  # NCHW -> NHWC
        if op.op_type == OperatorType.EMBEDDING:
            vocab = getattr(op, "num_entries", None) or 2
            a = rs.randint(0, max(1, int(vocab)), size=shp).astype(np.float32)
        else:
            a = rs.uniform(0.05, 1.0, size=shp).astype(np.float32)
        arrs.append(jnp.asarray(a))
    return arrs


def _fence_time(fn, args, repeats: int, warmup: int) -> float:
    """Median wall time of a jitted scalar-returning fn, fenced by fetching
    the scalar to host (as good a fence as ``block_until_ready``, and the
    value is already there). The dispatch and the fetch are a fixed cost
    per call; callers cancel it via slope timing."""
    for _ in range(warmup):
        float(fn(*args))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


# slope timing: per-iteration time = (T(long) - T(short)) / (long - short),
# cancelling the per-call cost (dispatch, launch, result fetch), which for
# a microsecond-scale op is larger than the op. The long run grows
# adaptively until its extra wall time dominates that cost and the host
# clock's jitter (a too-short long run measures nothing). Loops take
# their length dynamically (fori_loop), so growing costs no recompile.
_SHORT_ITERS = 4
_LONG_ITERS = 36
_MAX_ITERS = 1 << 15
_MIN_DELTA_S = 0.15


def _perturb(xs, acc):
    """Inject a loop-carried O(1) data dependence into the first float
    input so XLA cannot hoist the op out of the timing loop."""
    out, touched = [], False
    for x in xs:
        if not touched and jnp.issubdtype(x.dtype, jnp.floating):
            idx = (0,) * x.ndim
            x = x.at[idx].add(acc.astype(x.dtype) * 1e-12)
            touched = True
        out.append(x)
    return out


_VMEM_BYTES = 128 * 1024 * 1024  # v5e on-chip vector memory


def _param_rotation(params):
    """K stacked copies of every float param, K sized so the set exceeds
    VMEM: the timing loop indexes copy i%K each iteration, forcing the op
    to stream its weights from HBM like the real training step does.
    Without this XLA parks loop-invariant weights in VMEM and a
    bandwidth-bound op (fat Linear, small batch) measures flop-bound."""
    pbytes = float(sum(4.0 * np.prod(w.shape)
                       for w in jax.tree.leaves(params)))
    if pbytes <= 0:
        return None, 1
    k = int(min(8, max(2, np.ceil(2.0 * _VMEM_BYTES / pbytes))))
    stacked = jax.tree.map(
        lambda w: jnp.stack([w] * k)
        if jnp.issubdtype(w.dtype, jnp.floating) else w, params)
    return stacked, k


def _param_copy(stacked, params, i, k):
    if stacked is None:
        return params
    return jax.tree.map(
        lambda s, w: jax.lax.dynamic_index_in_dim(s, i % k, 0,
                                                  keepdims=False)
        if jnp.issubdtype(w.dtype, jnp.floating) else w, stacked, params)


def _artifact_bytes(op, params) -> Tuple[float, float]:
    """HBM bytes the timing loop touches that the real fused step would
    not: (fwd loop, bwd-minus-fwd loop). Forward: one extra write for the
    perturbed first input plus one read of the outputs by the keep-alive
    sum (the param-rotation read IS the op's realistic weight read, not an
    artifact). Backward delta: the keep-alive read of all gradients."""
    in0 = 4.0 * np.prod(op.input_shapes[0]) if op.input_shapes else 0.0
    pbytes = float(sum(4.0 * np.prod(w.shape)
                       for w in jax.tree.leaves(params)))
    obytes = float(sum(4.0 * np.prod(s) for s in op.output_shapes))
    fwd = in0 + obytes
    bwd_delta = pbytes + in0
    return fwd, bwd_delta


def _alive(outs):
    """Scalar depending on every output, so none is dead-code-eliminated.
    Costs one read of the outputs per iteration — small next to the ops
    being calibrated (matmul/conv/attention)."""
    dep = jnp.float32(0)
    for o in outs:
        dep = dep + jnp.sum(o).astype(jnp.float32)
    return dep


def _slope_time(loop_fn, args, repeats: int, warmup: int) -> float:
    """Per-iteration time via two loop lengths: cancels the constant
    per-call (dispatch + fetch) term exactly. ``loop_fn(*args, n)``
    must run its body ``n`` times (dynamic length, one compile)."""
    t_short = _fence_time(loop_fn, args + (_SHORT_ITERS,), repeats, warmup)
    n_long = _LONG_ITERS
    while True:
        t_long = _fence_time(loop_fn, args + (n_long,), repeats, 0)
        if t_long - t_short >= _MIN_DELTA_S or n_long >= _MAX_ITERS:
            break
        n_long *= 4
    return max((t_long - t_short) / (n_long - _SHORT_ITERS), 1e-9)


def measure_op(op, repeats: int = 3, warmup: int = 1,
               hbm_bw: float = 0.82e12,
               include_bwd: bool = True) -> Tuple[float, float]:
    """Time one op's forward and backward compute on the default device.

    Returns (fwd_seconds, bwd_seconds). The op runs inside a jitted
    ``lax.scan`` with a loop-carried dependence; timing two loop lengths
    and taking the slope cancels the per-call dispatch and fetch cost,
    which does not exist inside the fused training
    step the prediction is compared against — the analog of the reference
    timing kernel execution with CUDA events rather than wall-clocking
    launches (model.cu:54-66). Backward is (fwd+bwd slope) - (fwd slope)
    of a value_and_grad over float params/inputs, not assumed 2x forward.
    Raises on ops whose forward cannot run standalone (caller skips them).
    ``include_bwd=False`` skips the (expensive) backward slope timing
    entirely and returns the 2x-forward estimate for bwd; fwd-only
    measurements cache under a distinct key so they never masquerade as
    measured backward costs.
    """
    key = op_cost_key(op) + ("" if include_bwd else ":fwdonly")
    if key in _CACHE:
        return _CACHE[key]
    # a full measurement already covers the fwd-only request
    if not include_bwd and op_cost_key(op) in _CACHE:
        return _CACHE[op_cost_key(op)]
    rs = np.random.RandomState(0)
    params = op.init_params(jax.random.PRNGKey(0))
    # a leaf the op reads out of another op's parameters: alone, its own
    params.update({name: jnp.zeros(shape, jnp.float32)
                   for name, shape in op.tied_param_shapes().items()})
    inputs = _example_inputs(op, rs)
    rng = jax.random.PRNGKey(1)

    def fwd_once(p, xs, k):
        ctx = OpContext(training=True, rng=k, compute_dtype=jnp.float32)
        return op.forward(p, list(xs), ctx)

    stacked, kcopies = _param_rotation(params)

    @jax.jit
    def fwd_loop(st, xs, k, n):
        def body(i, carry):
            acc, kk = carry
            kk, sub = jax.random.split(kk)
            p_i = _param_copy(st, params, i, kcopies)
            out = fwd_once(p_i, _perturb(xs, acc), sub)
            return (_alive(out), kk)

        acc, _ = jax.lax.fori_loop(0, n, body, (jnp.float32(0), k))
        return acc

    art_fwd, art_bwd = _artifact_bytes(op, params)
    raw_fwd = _slope_time(fwd_loop, (stacked, inputs, rng), repeats, warmup)
    t_fwd = max(raw_fwd - art_fwd / hbm_bw, 0.25 * raw_fwd)

    def loss(p, xs, k):
        return _alive([o for o in fwd_once(p, xs, k)
                       if jnp.issubdtype(o.dtype, jnp.floating)])

    t_bwd = 2.0 * t_fwd
    has_grad_inputs = any(
        jnp.issubdtype(x.dtype, jnp.floating) for x in inputs)
    if include_bwd and (params or has_grad_inputs):
        argnums = (0, 1) if params and has_grad_inputs else (
            (0,) if params else (1,))
        vag = jax.value_and_grad(loss, argnums=argnums)

        @jax.jit
        def both_loop(st, xs, k, n):
            def body(i, carry):
                acc, kk = carry
                kk, sub = jax.random.split(kk)
                p_i = _param_copy(st, params, i, kcopies)
                v, grads = vag(p_i, _perturb(xs, acc), sub)
                return (v + _alive(jax.tree.leaves(grads)), kk)

            acc, _ = jax.lax.fori_loop(0, n, body, (jnp.float32(0), k))
            return acc

        try:
            raw_both = _slope_time(both_loop, (stacked, inputs, rng),
                                   repeats, warmup)
            t_bwd = max(raw_both - raw_fwd - art_bwd / hbm_bw, 0.1 * t_fwd)
        except Exception:
            pass  # non-differentiable op: keep the 2x-forward estimate
    _CACHE[key] = (t_fwd, t_bwd)
    return _CACHE[key]


def measure_runtime_constants() -> Dict[str, float]:
    """Per-step runtime constants the per-op sum cannot see:

    - ``__step_overhead__``: wall cost of dispatching one jitted step
      (program launch + host runtime), measured as the slope of a trivial
      jitted call chain.
    - ``__update_bw__``: effective HBM bytes/s of an optimizer-update
      triad (p - lr*g, donated), typically well below the datasheet rate.

    The native simulator reads both keys from the measured table (the
    analog of the reference measuring per-device memory/runtime constants
    alongside per-op costs).
    """
    key = "__runtime__" + jax.devices()[0].platform
    if key in _CACHE:
        oh, bw = _CACHE[key]
        return {"__step_overhead__": oh, "__update_bw__": bw}

    x0 = jnp.ones((8, 8))
    tiny = jax.jit(lambda x: x + 1.0)
    holder = [x0]

    def chain():
        holder[0] = tiny(holder[0])
        return holder[0]

    def chain_time(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = chain()
        float(out.ravel()[0])
        return time.perf_counter() - t0

    chain_time(4)
    n_small, n_big = 4, 64
    t_small = chain_time(n_small)
    while True:
        t_big = chain_time(n_big)
        if t_big - t_small >= _MIN_DELTA_S or n_big >= _MAX_ITERS:
            break
        n_big *= 4
    overhead = max((t_big - t_small) / (n_big - n_small), 1e-7)

    n_elems = 16 << 20  # 64 MB leaves
    p = jnp.zeros((n_elems,))
    g = jnp.ones((n_elems,))
    triad = jax.jit(lambda p, g: p - 0.01 * g, donate_argnums=(0,))
    pref = [p]

    def triad_step():
        pref[0] = triad(pref[0], g)
        return pref[0]

    def triad_time(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = triad_step()
        float(out[0])
        return time.perf_counter() - t0

    triad_time(2)
    t1 = triad_time(4)
    n2 = 32
    while True:
        t2 = triad_time(n2)
        if t2 - t1 >= _MIN_DELTA_S or n2 >= 4096:
            break
        n2 *= 4
    per_call = max((t2 - t1) / (n2 - 4), 1e-9)
    per_call = max(per_call - overhead, 1e-9)
    bw = 3.0 * 4.0 * n_elems / per_call  # read p + read g + write p

    _CACHE[key] = (overhead, bw)
    return {"__step_overhead__": overhead, "__update_bw__": bw}


def load_op_corrections(path: Optional[str] = None,
                        platform: Optional[str] = None
                        ) -> Dict[str, Dict[str, float]]:
    """Drift-derived per-op-type correction factors from CALIBRATION.json
    (written by ``scripts/calibrate.py --ingest-drift``). The file keys
    them platform-first ({platform: {op type: {"factor": ..}}}); this
    returns the bucket for ``platform`` (default: the current JAX
    platform) — a CPU-derived correction must never scale TPU
    measurements. Returns {} when no calibration exists.
    ``FFS_CALIBRATION_FILE`` overrides the path (tests)."""
    path = path or os.environ.get("FFS_CALIBRATION_FILE") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "CALIBRATION.json")
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    corr = data.get("op_corrections", {})
    if not isinstance(corr, dict):
        return {}
    if platform is None:
        platform = jax.devices()[0].platform
    bucket = corr.get(platform, {})
    return bucket if isinstance(bucket, dict) else {}


def apply_drift_corrections(measured: Dict[str, float], nodes,
                            corrections: Optional[Dict] = None
                            ) -> Dict[str, float]:
    """Scale each op's measured fwd/bwd seconds by its op type's
    drift-correction factor — the write-back half of the recalibration
    loop (observed runtime drift, ingested by ``calibrate.py
    --ingest-drift``, flows into every future measured table the search
    consumes). ``corrections`` defaults to the current platform's
    bucket from CALIBRATION.json."""
    if corrections is None:
        corrections = load_op_corrections()
    if not corrections:
        return measured
    out = dict(measured)
    for node in nodes:
        entry = corrections.get(node.op.op_type.name)
        if not entry:
            continue
        factor = float(entry.get("factor", 1.0))
        if factor <= 0:
            continue
        for leg in ("fwd", "bwd"):
            key = f"{node.op.guid}:{leg}"
            if key in out:
                out[key] *= factor
    return out


def microbenchmark(nodes, repeats: int = 3, warmup: int = 1,
                   cache_file: Optional[str] = None,
                   hbm_bw: float = 0.82e12,
                   verbose: bool = False,
                   drift_corrections: bool = True) -> Dict[str, float]:
    """Measure every op in an OpNode list; returns the native search's
    measured table {"<guid>:fwd": s, "<guid>:bwd": s}.

    Ops whose standalone forward fails (e.g. ones needing cross-op state)
    are skipped — the search keeps its analytic estimate for those.
    ``cache_file`` persists measurements across processes, keyed by the
    op-config hash, so a re-run on an unchanged model costs nothing.
    ``drift_corrections`` (default on; ``FFS_NO_DRIFT_CORRECTIONS=1``
    disables) scales the table by the per-op-type factors ingested from
    runtime drift reports — raw measurements stay in the cache, the
    correction applies on the way out.
    """
    disk: Dict[str, List[float]] = {}
    if cache_file and os.path.exists(cache_file):
        try:
            with open(cache_file) as f:
                disk = json.load(f)
        except (OSError, ValueError):
            disk = {}
    for k, v in disk.items():
        if k not in _CACHE and isinstance(v, list) and len(v) == 2:
            _CACHE[k] = (float(v[0]), float(v[1]))

    measured: Dict[str, float] = {}
    dirty = False
    for node in nodes:
        op = node.op
        key = op_cost_key(op)
        if key not in _CACHE:
            try:
                measure_op(op, repeats=repeats, warmup=warmup, hbm_bw=hbm_bw)
                dirty = True
            except Exception as e:
                if verbose:
                    print(f"[profile] skip {op.name}: {e!r}")
                continue
        fwd_s, bwd_s = _CACHE[key]
        measured[f"{op.guid}:fwd"] = fwd_s
        measured[f"{op.guid}:bwd"] = bwd_s
        if verbose:
            print(f"[profile] {op.name}: fwd {fwd_s * 1e6:.1f}us "
                  f"bwd {bwd_s * 1e6:.1f}us")
    measured.update(measure_runtime_constants())
    if cache_file and dirty:
        try:
            with open(cache_file, "w") as f:
                json.dump({k: list(v) for k, v in _CACHE.items()}, f)
        except OSError:
            pass
    if drift_corrections and not os.environ.get("FFS_NO_DRIFT_CORRECTIONS"):
        measured = apply_drift_corrections(measured, nodes)
    return measured
