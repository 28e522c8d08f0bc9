"""ServingEngine: latency-searched per-bucket executors + continuous batching.

The serving analog of ``FFModel.compile``'s seq-length buckets, applied
to the BATCH dim: the layer graph re-materializes at each batch bucket
(1, 2, 4, ... up to the declared batch), and — when the native search is
available — each bucket runs ``graph_optimize`` in INFERENCE mode, so
the DP minimizes simulated per-batch *latency* for that bucket's shapes:
forward cost only, no gradient-sync/``_wus``/``_ovl``/opt-state terms,
activation-memory-dominated pricing (``config.training=False`` →
``ffs_sim``'s forward-only schedule). A batch of 2 on 8 chips prices
model-parallel sharding where the training objective would have priced
data parallelism; the searched objective is recorded per bucket and in
the strategy/search-trace artifacts.

The engine then runs the ``serve/batching`` scheduler over the bucket
executors: requests queue, close on size-or-deadline, pad into the
smallest bucket that fits, and per-request rows come back out. p50/p99
request latency, queue depth, and batch occupancy flow through the obs
registry (``serve/*`` series).
"""

from __future__ import annotations

import dataclasses
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.ffconst import CompMode, OperatorType
from flexflow_tpu.obs.registry import get_registry
from flexflow_tpu.serve.batching import (BatchScheduler, Request,
                                         RequestQueue, pad_to_bucket,
                                         pick_bucket)


def default_buckets(max_batch: int) -> Tuple[int, ...]:
    """Powers of two up to (and including) the declared batch size."""
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(int(max_batch))
    return tuple(sorted(set(out)))


def _sanitize_output_specs(nodes, mesh) -> None:
    """Null out spec entries whose mesh-axis degree doesn't divide the
    bucket-materialized dim — a training strategy's P('data', ...) on
    the batch dim is illegal at bucket sizes below the data degree
    (with_sharding_constraint requires divisibility); the dim stays
    replicated for that bucket instead."""
    import math

    from jax.sharding import PartitionSpec as P

    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for node in nodes:
        specs = []
        for i, spec in enumerate(node.output_specs):
            if spec is None:
                specs.append(None)
                continue
            shp = node.op.output_shapes[i]
            entries = (list(spec) + [None] * len(shp))[:len(shp)]
            for d, e in enumerate(entries):
                if e is None:
                    continue
                names = e if isinstance(e, tuple) else (e,)
                deg = math.prod(axes.get(a, 1) for a in names)
                if deg <= 1 or shp[d] % deg != 0:
                    entries[d] = None
            specs.append(P(*entries) if any(entries) else None)
        node.output_specs = specs


def _filter_specs_to_mesh(strategy, mesh) -> None:
    """Drop spec entries naming axes the live mesh doesn't carry (the
    ``import_strategy_file`` discipline) — a bucket searched onto a
    {data:4, seq:2} factorization still applies on a {data:8} mesh."""
    from jax.sharding import PartitionSpec as P

    valid = set(mesh.axis_names)

    def keep(e):
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a in valid)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return e if e in valid else None

    for st in strategy.values():
        st.output_specs = [
            (P(*(keep(e) for e in s)) if s is not None else None)
            for s in st.output_specs
        ]
        st.param_specs = {k: P(*(keep(e) for e in v))
                          for k, v in st.param_specs.items()}


@dataclasses.dataclass
class BucketExecutor:
    """One batch bucket's compiled forward path + its search provenance."""

    bucket: int
    executor: Any  # GraphExecutor (comp_mode INFERENCE)
    objective: str  # e.g. "latency@batch4" / "reused-training-strategy"
    mesh_axes: Dict[str, int]
    predicted_latency_s: Optional[float] = None
    strategy_differs: bool = False  # vs the model's training strategy
    # per-op kernel implementations THIS bucket executes ({op name ->
    # impl}): "_k:" choices from the bucket's searched strategy plus
    # each attention op's statically-derived dispatch (selected_impl) —
    # RECORDED at build time, never re-derived at report time, so serve
    # observability and training provenance agree (ISSUE 15 defect fix)
    kernel_choices: Dict[str, str] = dataclasses.field(default_factory=dict)
    _fwd: Any = None

    def forward(self):
        if self._fwd is None:
            self._fwd = self.executor.make_forward(training=False)
        return self._fwd


class ServingEngine:
    """Continuous-batching inference server over latency-searched
    bucket executors. Build via ``FFModel.serve()``.

    Synchronous use: ``submit()`` requests then ``step()`` (or
    ``pump()``) on the caller's thread. Background use: ``start()``
    spins the serving thread; ``submit(...).wait()`` from any number of
    client threads; ``stop()`` drains and joins.
    """

    def __init__(self, ff, batch_buckets: Optional[Sequence[int]] = None,
                 max_wait_ms: float = 5.0,
                 search_budget: Optional[int] = None,
                 verbose: bool = False):
        self.ff = ff
        max_batch = int(ff.input_tensors[0].shape[0])
        buckets = tuple(sorted({int(b) for b in
                                (batch_buckets or default_buckets(max_batch))
                                if 0 < int(b) <= max_batch}))
        if not buckets:
            raise ValueError(f"no usable batch buckets <= {max_batch}")
        self.queue = RequestQueue()
        self.scheduler = BatchScheduler(buckets, max_wait_s=max_wait_ms / 1e3)
        self.verbose = verbose
        # False keeps served requests out of the registry latency
        # reservoir (loadgen toggles it off during warmup)
        self.record_latency = True
        # engine-local rng for the forward signature: the inference
        # forward never consumes it (dropout is off), and the serving
        # thread must NOT advance the model's rng stream — that would
        # race concurrent predict/fit splits and break the checkpoint
        # subsystem's bit-identical-resume guarantee
        self._rng = None
        budget = (search_budget if search_budget is not None
                  else getattr(ff.config, "search_budget", 0))
        self.buckets: Dict[int, BucketExecutor] = {}
        for b in buckets:
            self.buckets[b] = self._build_bucket(b, budget)
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # ---- bucket construction ----------------------------------------------
    def _training_signature(self):
        return self._signature(self.ff.strategy or {})

    @staticmethod
    def _signature(strategy):
        return {g: (s.choice,
                    tuple(tuple(sp) if sp is not None else None
                          for sp in s.output_specs),
                    tuple(sorted((k, tuple(v))
                                 for k, v in s.param_specs.items())))
                for g, s in strategy.items()}

    def _build_bucket(self, bucket: int, budget: int) -> BucketExecutor:
        from flexflow_tpu.executor import GraphExecutor
        from flexflow_tpu.parallel.choice import ExecPlan
        from flexflow_tpu.parallel.strategy import apply_strategy

        ff = self.ff
        # batch-only overrides: dim 0 of every INPUT becomes the bucket
        overrides = {}
        for layer in ff.layers:
            if layer.op_type != OperatorType.INPUT:
                continue
            shp = list(layer.outputs[0].shape)
            if shp and shp[0] != bucket:
                shp[0] = bucket
                overrides[layer.name] = tuple(shp)
        nodes, input_names, tensor_ref = ff._materialize_nodes(overrides)
        final_ref = ff._select_final_ref(nodes, tensor_ref)

        n_live = int(ff.mesh.devices.size)
        mesh = ff.mesh
        strategy = None
        objective = "reused-training-strategy"
        predicted = None
        info = None
        if budget and budget > 0:
            try:
                strategy, mesh, objective, predicted, info = \
                    self._search_bucket(nodes, bucket, budget, n_live,
                                        final_ref)
            except Exception as e:
                print(f"[serve] bucket {bucket}: latency search failed "
                      f"({e!r}) — reusing the training strategy",
                      file=sys.stderr)
                strategy, mesh = None, ff.mesh
        if strategy is None:
            # reuse the model's strategy (specs are axis names — they
            # apply at any batch the axes still divide; apply_strategy
            # guards divisibility per dim)
            import copy
            strategy = {g: copy.deepcopy(s)
                        for g, s in (ff.strategy or {}).items()}
        differs = self._signature(strategy) != self._training_signature()
        apply_strategy(nodes, strategy, mesh)
        _sanitize_output_specs(nodes, mesh)
        from flexflow_tpu.layout import propagate_layouts
        propagate_layouts(nodes, **getattr(
            ff, "_layout_args", dict(mode="nchw", on_tpu=False)))
        full = ff.executor
        axes_now = dict(zip(mesh.axis_names, mesh.devices.shape))
        # only data axes whose degree divides the bucket stage the batch
        # sharded; a bucket below the data degree stages replicated
        data_axes = tuple(
            a for a in mesh.axis_names if a in ("data", "replica")
            and axes_now.get(a, 1) > 1 and bucket % axes_now[a] == 0)
        ex = GraphExecutor(
            nodes, input_names, final_ref, mesh, ff.loss_type, ff.metrics,
            full.optimizer, compute_dtype=full.compute_dtype,
            data_axes=data_axes,
            final_is_softmax=ff._final_is_softmax,
            fold_conv_bn=full.fold_conv_bn,
            # a forward-only bucket runs no gradient sync, no update and
            # no backward: none of the plan's dimensions engages, and its
            # attention impls are on the ops (apply_strategy above)
            plan=ExecPlan())
        ex.comp_mode = CompMode.INFERENCE
        # record the kernel each op will RUN in this bucket: explicit
        # "_k:" searched choices, plus attention ops' static dispatch
        # (apply_strategy already pinned kernel_impl from the choice) —
        # the impl is decided here, at build time, with the bucket's
        # shapes; the report only replays the record
        from flexflow_tpu.search.unity import executed_kernel_choices
        kernel_choices = executed_kernel_choices(nodes, strategy, axes_now)
        be = BucketExecutor(bucket=bucket, executor=ex, objective=objective,
                            mesh_axes=axes_now,
                            predicted_latency_s=predicted,
                            strategy_differs=differs,
                            kernel_choices=kernel_choices)
        reg = get_registry()
        if predicted is not None:
            reg.gauge(f"serve/bucket{bucket}/predicted_latency_s", predicted)
        if self.verbose:
            print(f"[serve] bucket {bucket}: objective={objective} "
                  f"mesh={axes_now} differs_from_training={differs}",
                  file=sys.stderr)
        return be

    def _search_bucket(self, nodes, bucket: int, budget: int, n_live: int,
                       final_ref):
        """Latency-objective search for one bucket: INFERENCE-mode
        ``graph_optimize`` (forward-only cost model, opt_state_factor
        0) at this bucket's batch. Rewrites and pipeline meshes are
        disabled — the serving executors must keep the live model's
        parameter tree and run a plain graph."""
        import math

        from flexflow_tpu.machine import make_mesh
        from flexflow_tpu.search import unity as _unity

        ff = self.ff
        cfg = dataclasses.replace(
            ff.config, computation_mode=CompMode.INFERENCE,
            search_budget=int(budget), enable_parameter_parallel=True,
            enable_pipeline_parallel=False, enable_substitution=False,
            only_data_parallel=False, weight_update_sharding="off",
            overlap_bucket_mb="off")
        cfg.opt_state_factor = 0.0
        mesh_axes, strategy, info = _unity.graph_optimize(
            nodes, ff.machine_spec, cfg, n_live, batch=bucket,
            final_ref=final_ref)
        need = math.prod(mesh_axes.values())
        if need == n_live:
            mesh = make_mesh(n_live, mesh_axes)
        else:
            # searched factorization uses fewer devices than the params
            # live on — keep the live mesh, drop foreign axes from specs
            mesh = ff.mesh
            _filter_specs_to_mesh(strategy, mesh)
        objective = f"{info.get('objective', 'latency')}@batch{bucket}"
        return (strategy, mesh, objective, info.get("predicted_time"),
                info)

    # ---- request path ------------------------------------------------------
    def submit(self, inputs) -> Request:
        """Enqueue one request. ``inputs``: one array per model input,
        WITHOUT the batch dim (a single sample)."""
        return self.queue.submit(
            inputs if isinstance(inputs, (list, tuple)) else [inputs])

    def _stage(self, be: BucketExecutor, arrays: List[np.ndarray]):
        import jax
        import jax.numpy as jnp

        ex = be.executor
        staged = {}
        for name, arr in zip(ex.input_names, arrays):
            a = jnp.asarray(arr)
            if jnp.issubdtype(a.dtype, jnp.floating):
                a = a.astype(ex.compute_dtype)
            staged[name] = jax.device_put(a, ex.batch_sharding())
        return staged

    def _serve_batch(self, batch: List[Request]) -> None:
        import jax

        t0 = time.perf_counter()
        bucket = pick_bucket(len(batch), self.scheduler.buckets)
        be = self.buckets[bucket]
        try:
            arrays = pad_to_bucket(batch, bucket)
            inputs = self._stage(be, arrays)
            fwd = be.forward()
            if self._rng is None:
                self._rng = jax.random.PRNGKey(0)
            out, _ = fwd(self.ff.params, self.ff.state, inputs, self._rng)
            out = np.asarray(jax.device_get(out))
            for i, req in enumerate(batch):
                req.finish(result=out[i], record=self.record_latency)
        except BaseException as e:
            for req in batch:
                if not req.done:
                    req.finish(error=e)
            raise
        finally:
            reg = get_registry()
            reg.observe(f"serve/bucket{bucket}/batch_latency_s",
                        time.perf_counter() - t0)

    def step(self, flush: bool = False) -> int:
        """Close and serve at most one batch; returns requests served."""
        batch = self.scheduler.poll(self.queue, flush=flush)
        if not batch:
            return 0
        self._serve_batch(batch)
        return len(batch)

    def pump(self, flush: bool = True) -> int:
        """Serve until the queue drains; returns requests served."""
        total = 0
        while True:
            n = self.step(flush=flush)
            if n == 0 and self.queue.depth() == 0:
                return total
            total += n

    # ---- background serving loop ------------------------------------------
    def start(self) -> "ServingEngine":
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                try:
                    served = self.step()
                except Exception as e:
                    # the failed batch's requests already carry the error
                    # (_serve_batch finishes them before re-raising); the
                    # serving thread itself must survive — a malformed
                    # request or transient device error killing the loop
                    # would hang every future request forever
                    print(f"[serve] batch failed: {e!r} — serving "
                          f"continues", file=sys.stderr)
                    get_registry().inc("serve/batch_errors")
                    continue
                if served == 0:
                    # nothing closed: nap until a request arrives or the
                    # oldest hits its deadline
                    self.queue.wait_nonempty(self.scheduler.max_wait_s)
                    if self.queue.depth() and not self._stop.is_set():
                        time.sleep(min(self.scheduler.max_wait_s, 0.001))
            # drain on shutdown so no submitted request hangs forever
            while True:
                try:
                    if not self.step(flush=True):
                        break
                except Exception:
                    continue  # drained requests carry their errors

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="serve-engine")
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join()
        self._thread = None
        # close the submit-vs-shutdown race: a request enqueued after
        # the serving thread's final drain poll would otherwise sit
        # unserved with no thread, hanging its wait() forever. Any
        # submit that happened-before stop() returns is served here;
        # submits strictly after stop() are manual-mode (caller pumps).
        while True:
            try:
                if not self.step(flush=True):
                    break
            except Exception:
                continue  # the batch's requests carry the error

    # ---- introspection -----------------------------------------------------
    def bucket_report(self) -> Dict[str, Any]:
        """Per-bucket search provenance (the serve artifact payload)."""
        return {
            str(b): dict(objective=be.objective, mesh=be.mesh_axes,
                         predicted_latency_s=be.predicted_latency_s,
                         strategy_differs_from_training=be.strategy_differs,
                         # recorded at bucket build (never re-derived):
                         # the kernel each op executes in this bucket —
                         # training provenance (strategy "_k:" choices)
                         # and serve observability agree by construction
                         kernel_choices=dict(be.kernel_choices))
            for b, be in self.buckets.items()
        }
