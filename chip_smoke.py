#!/usr/bin/env python
"""Smoke test on the chip: the BERT-proxy trainer through the entry points
a user calls (create_transformer + FFConfig + FFModel.compile + fit, as
examples/transformer.py), at the reference size, on a TPU v5e.

    python chip_smoke.py            # one chip: search, compile, train, trace
    python chip_smoke.py --chips 4  # four chips: the multi-chip arms only

It is the quickest proof that the system still starts on the chip, not a
benchmark: the step seconds it prints are a sanity reading of a handful
of steps. Every line of standard output is one JSON object; the last is
`{"ok": true, "device": {...}}` and is printed only if every phase
passed. Any failure raises and the exit code is non-zero. It refuses to
run without a TPU and has no flag that lets it pass on the CPU.

One process holds the chip: the native search library is rebuilt before
JAX starts a backend, and no child process is started after that.
"""

import argparse
import collections
import gc
import glob
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

STEPS = 6          # per arm, after one warm-up step
SEED = 0
# Adam's step. At the 1e-4 of bench.py and examples/transformer.py the
# first update takes this model's loss from 8.5 to 8.5e3 — on the chip and
# on the CPU alike (no warm-up, no final norm before the head, and Adam's
# first steps move every weight by the full step); 1e-6 is where the
# first update lowers the loss, so "the loss falls" can be asserted.
ALPHA = 1e-6
# relative tolerance of an arm's per-step losses against its data-parallel
# arm (same seed, same data): what bf16 compute with a different reduction
# order gives on the chip; the f32 CPU dry runs agree to six digits
LOSS_RTOL = 2e-2


def emit(**kw):
    print(json.dumps(kw), flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def rebuild_native():
    """From the committed sources, so no leftover library is ever used."""
    r = subprocess.run(["make", "-C", os.path.join(REPO, "native"),
                        "clean", "all"], capture_output=True, text=True)
    if r.returncode != 0:
        sys.stderr.write(r.stdout + r.stderr)
        raise SystemExit("chip_smoke: `make -C native clean all` failed")


# ---------------------------------------------------------------------------
# model, data and the checks every arm shares


def build(batch, chips, mesh_axes=None, seq_parallel=None, **cfg_kw):
    import jax.numpy as jnp

    from flexflow_tpu import AdamOptimizer, FFConfig, LossType, MetricsType
    from flexflow_tpu.machine import make_mesh
    from flexflow_tpu.models import TransformerConfig, create_transformer

    # the reference size (12 layers, hidden 1024, 16 heads, seq 512)
    tc = TransformerConfig(batch_size=batch, seq_parallel=seq_parallel)
    cfg = FFConfig(batch_size=batch, workers_per_node=chips, seed=SEED,
                   **cfg_kw)
    ff = create_transformer(tc, cfg)
    mesh = make_mesh(chips, mesh_axes) if mesh_axes else None
    t0 = time.perf_counter()
    ff.compile(AdamOptimizer(alpha=ALPHA, state_dtype=jnp.bfloat16),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [MetricsType.MEAN_SQUARED_ERROR], mesh=mesh)
    return ff, tc, time.perf_counter() - t0


def make_data(tc):
    import numpy as np
    rs = np.random.RandomState(SEED)
    x = rs.randn(tc.batch_size, tc.seq_length,
                 tc.hidden_size).astype(np.float32)
    y = rs.randn(tc.batch_size, tc.seq_length, 1).astype(np.float32)
    return x, y


def mesh_axes_of(ff):
    return dict(zip(ff.mesh.axis_names,
                    (int(s) for s in ff.mesh.devices.shape)))


def check_placement(ff, x, chips):
    """The machine, dtype and devices the model ended up on are the ones
    asked for — nothing fell back to a synthetic chip, f32 or fewer
    devices."""
    import jax
    import jax.numpy as jnp

    check(ff.machine_spec.chip == "tpu-v5e",
          f"machine spec is {ff.machine_spec.chip!r}, not tpu-v5e")
    check(ff.executor.compute_dtype == jnp.bfloat16,
          f"compute dtype is {ff.executor.compute_dtype}, not bfloat16")
    check(int(ff.mesh.devices.size) == chips,
          f"asked for {chips} chips, mesh is {mesh_axes_of(ff)}")
    tpus = set(jax.devices()[:chips])
    check(all(d.platform == "tpu" for d in tpus), "non-TPU devices")
    leaves = jax.tree_util.tree_leaves(ff.params)
    held = set()
    for leaf in leaves:
        devs = {s.device for s in leaf.addressable_shards}
        check(devs <= tpus, f"a parameter lives on {devs - tpus}")
        held |= devs
    check(held == tpus, f"parameters cover {len(held)} of {chips} chips")
    batch = ff._stage_inputs([x])[ff.executor.input_names[0]]
    on = {s.device for s in batch.addressable_shards}
    check(on == tpus, f"the batch covers {len(on)} of {chips} chips")
    for d in tpus:
        check(d.memory_stats()["bytes_in_use"] > 0, f"{d} holds nothing")


def peak_bytes(device):
    return device.memory_stats()["peak_bytes_in_use"]


def attention_impls(ff):
    axes = mesh_axes_of(ff)
    return {n.op.name: n.op.selected_impl(axes, training=True)
            for n in ff.executor.nodes if hasattr(n.op, "selected_impl")}


def train(ff, x, y, steps=STEPS):
    """One warm-up step, then `steps` steps; each `fit` call is one step
    over the same batch and ends on a host read of the loss."""
    import jax
    import numpy as np

    ff.fit([x], y, epochs=1, verbose=False)
    losses, secs = [float(ff._last_loss)], []
    for _ in range(steps):
        t0 = time.perf_counter()
        ff.fit([x], y, epochs=1, verbose=False)
        jax.block_until_ready(ff.params)
        secs.append(time.perf_counter() - t0)
        losses.append(float(ff._last_loss))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return losses, secs


def release(ff):
    import jax
    ff.params = ff.opt_state = ff.state = ff.executor = None
    jax.clear_caches()
    gc.collect()


# ---------------------------------------------------------------------------
# one chip


def check_kernels():
    """The Pallas kernels against the paths they replace, on a small
    input: flash attention, forward and both backward kernels, against
    the einsum attention the op runs otherwise; the fused Adam update
    against the reference optimizer expression."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.attention import scaled_dot_product_attention
    from flexflow_tpu.ops.fused_update import _adam_math, fused_adam_leaf
    from flexflow_tpu.ops.pallas_kernels import (
        MAX_BWD_SEQ, MAX_FLASH_HEAD_DIM, MAX_FLASH_SEQ, flash_attention,
        merge_heads, split_heads)

    def rel_err(got, want):
        """Per leaf: largest difference over largest reference value."""
        return [float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                      - w.astype(jnp.float32)))
                      / jnp.max(jnp.abs(w.astype(jnp.float32))))
                for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))]

    def value_and_grads(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    flash_err, einsum_err = {}, {}
    # seq 512 takes the single-block backward, 2048 the K-blocked one; the
    # last shape is the longest and widest the flash gate admits
    for shape in ((2, 2, 512, 64), (2, 2, 2 * MAX_BWD_SEQ, 64),
                  (1, 1, MAX_FLASH_SEQ, MAX_FLASH_HEAD_DIM)):
        seq = shape[2]
        q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
                   for key in jax.random.split(jax.random.PRNGKey(SEED), 3))
        # the kernels take [B, S, H*D]; this check holds [B, H, S, D]
        flash = value_and_grads(lambda q, k, v: split_heads(
            flash_attention(merge_heads(q), merge_heads(k), merge_heads(v),
                            q.shape[1], causal=True), q.shape[1]))(q, k, v)
        einsum = value_and_grads(
            lambda q, k, v: scaled_dot_product_attention(
                q, k, v, causal=True, compute_dtype=jnp.bfloat16))(q, k, v)
        with jax.default_matmul_precision("highest"):
            exact = value_and_grads(
                lambda q, k, v: scaled_dot_product_attention(
                    q, k, v, causal=True, compute_dtype=jnp.float32))(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32))
        flash_err[seq] = max(rel_err(flash, exact))
        einsum_err[seq] = max(rel_err(einsum, exact))
        # the kernel may not be less accurate than the path it replaces:
        # twice that path's own error against full-precision f32, plus
        # one step of bf16, in which both return their results
        check(flash_err[seq] <= 2 * einsum_err[seq] + 2 ** -8,
              f"flash attention at seq {seq} is {flash_err[seq]:.3g} off "
              f"the f32 result (of its largest value); the einsum path is "
              f"{einsum_err[seq]:.3g} off")

    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), 4)
    p = jax.random.normal(keys[0], (256, 1024), jnp.float32)
    g, m = (jax.random.normal(key, p.shape, jnp.bfloat16) for key in keys[1:3])
    v = jnp.abs(jax.random.normal(keys[3], p.shape, jnp.bfloat16))
    kw = dict(beta1=0.9, beta2=0.999, eps=1e-8, wd=1e-4)
    alpha = jnp.float32(1e-3)
    got = jax.jit(lambda *a: fused_adam_leaf(*a, alpha, **kw))(p, g, m, v)
    want = jax.jit(lambda *a: _adam_math(*a, alpha, **kw))(p, g, m, v)
    p_err, m_err, v_err = rel_err(got, want)
    # one expression through Mosaic and through XLA: f32 parameters equal
    # up to the compilers' rounding, bf16 moments up to one bf16 step
    check(p_err < 1e-5 and max(m_err, v_err) < 1e-2,
          f"fused Adam is off the reference: p {p_err:.3g}, m {m_err:.3g}, "
          f"v {v_err:.3g}")
    emit(phase="kernels", flash_max_err_vs_f32=flash_err,
         einsum_max_err_vs_f32=einsum_err,
         fused_adam_max_err_vs_reference=dict(p=p_err, m=m_err, v=v_err),
         fused_adam_bitwise=all(
             bool(jnp.array_equal(a, b)) for a, b in zip(got, want)))


class CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax
        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.counts[event.rsplit("/", 1)[1]] += 1


def run_one_chip(cache_dir):
    import jax

    from flexflow_tpu.obs.inspect import pallas_kernel_count
    from flexflow_tpu.search.validate import compiled_train_step

    events = CacheEvents()
    ff, tc, compile_s = build(8, 1, search_budget=30)
    x, y = make_data(tc)
    check(ff.search_info is not None, "the search did not run")
    check_placement(ff, x, 1)
    choices = collections.Counter(
        s.choice for s in ff.strategy.values())
    emit(phase="search", mesh=mesh_axes_of(ff),
         search_s=ff.search_seconds, ff_compile_s=compile_s,
         choices=choices, executor=type(ff.executor).__name__)
    impls = attention_impls(ff)
    check(len(impls) == tc.num_layers and set(impls.values()) == {"flash"},
          f"attention impls: {impls}")

    # the train step compiled twice, the second time with JAX's in-memory
    # caches dropped: that one is served by the persistent cache. The
    # first is cold unless the machine came with a warm cache.
    t0 = time.perf_counter()
    compiled = compiled_train_step(ff)
    first_s = time.perf_counter() - t0
    first = dict(events.counts)
    jax.clear_caches()
    t0 = time.perf_counter()
    compiled_train_step(ff)
    again_s = time.perf_counter() - t0
    check(events.counts["cache_hits"] > first.get("cache_hits", 0),
          f"recompile after clear_caches missed the cache in {cache_dir}")
    check(os.listdir(cache_dir), f"nothing was written to {cache_dir}")
    kernels = pallas_kernel_count(compiled.as_text())
    ma = compiled.memory_analysis()
    emit(phase="compile", cache_dir=cache_dir, compile_first_s=first_s,
         cache_events_after_first=first, compile_again_s=again_s,
         cache_events_after_again=events.counts,
         tpu_custom_calls=kernels,
         argument_bytes=ma.argument_size_in_bytes,
         temp_bytes=ma.temp_size_in_bytes)
    # a flash forward and a flash backward per layer
    check(kernels == 2 * tc.num_layers,
          f"{kernels} tpu_custom_calls in the step, expected "
          f"{2 * tc.num_layers}")

    losses, secs = train(ff, x, y)
    fallbacks = {n.op.name: n.op._kernel_fallback for n in ff.executor.nodes
                 if getattr(n.op, "_kernel_fallback", None)}
    check(not fallbacks, f"kernel fallbacks: {fallbacks}")
    emit(phase="train", note="smoke, not a benchmark", losses=losses,
         step_s=secs,
         peak_bytes_in_use=peak_bytes(jax.devices()[0]))

    # a short traced fit: _finalize_trace swallows attribution errors, so
    # the report it leaves behind is what gets checked
    import numpy as np
    trace_dir = os.path.join(REPO, "chiprun_out", "chip_smoke_trace")
    ff.fit([np.concatenate([x] * 4)], np.concatenate([y] * 4), epochs=1,
           verbose=False, trace_dir=trace_dir, profile_steps="1:3")
    reports = sorted(glob.glob(os.path.join(trace_dir, "*.devtrace.json")))
    check(reports, f"no device-trace attribution report in {trace_dir}")
    with open(reports[-1]) as f:
        rep = json.load(f)
    check(rep["steps"] == 2 and rep["device_events"] > 0
          and rep["totals"]["compute_s"] > 0,
          f"empty device-trace attribution: steps={rep['steps']} "
          f"device_events={rep['device_events']} totals={rep['totals']}")
    emit(phase="trace", report=os.path.relpath(reports[-1], REPO),
         steps=rep["steps"], device_events=rep["device_events"],
         totals=rep["totals"])
    # last, and with the model gone: the einsum reference at the longest
    # sequence needs gigabytes, which must not count as the trainer's peak
    release(ff)
    check_kernels()


# ---------------------------------------------------------------------------
# four chips


def run_arm(name, batch, baseline=None, **build_kw):
    import jax
    import numpy as np

    from flexflow_tpu.obs.inspect import (collective_census,
                                          pallas_kernel_count)
    from flexflow_tpu.search.validate import compiled_train_step

    ff, tc, compile_s = build(batch, 4, **build_kw)
    x, y = make_data(tc)
    check_placement(ff, x, 4)
    choices = collections.Counter(
        s.choice for s in ff.strategy.values())
    hlo = compiled_train_step(ff).as_text()
    fused = sorted(getattr(ff.executor, "fused_update_ops", ()))
    executed = {s.choice for s in ff.strategy.values()
                if s.parsed.kernel == "fused"}
    check(bool(fused) == bool(executed),
          f"search chose {executed} but the executor fuses {fused}")
    losses, secs = train(ff, x, y)
    err = None
    if baseline is not None:
        err = float(np.max(np.abs(np.array(losses) / np.array(baseline) - 1)))
    emit(arm=name, note="smoke, not a benchmark", batch=batch,
         mesh=mesh_axes_of(ff), executor=type(ff.executor).__name__,
         wus=ff.wus_enabled, overlap=ff.overlap_enabled,
         search_s=ff.search_seconds, ff_compile_s=compile_s,
         choices=choices, fused_update_ops=len(fused),
         attention=collections.Counter(attention_impls(ff).values()),
         tpu_custom_calls=pallas_kernel_count(hlo),
         collectives=collective_census(hlo), losses=losses,
         max_rel_loss_err_vs_dp=err, step_s=secs,
         peak_bytes_in_use=[peak_bytes(d) for d in jax.devices()])
    release(ff)
    return losses, err


def run_four_chips():
    """Every arm runs and prints its line; the loss comparison is judged
    at the end, so one run shows all of them."""
    errs = {}
    dp, _ = run_arm("dp", 32, only_data_parallel=True)
    _, errs["searched"] = run_arm(
        "searched", 32, baseline=dp, search_budget=30,
        enable_parameter_parallel=True)
    _, errs["hybrid"] = run_arm(
        "hybrid", 32, baseline=dp, mesh_axes={"data": 2, "model": 2},
        enable_parameter_parallel=True)
    _, errs["ring"] = run_arm(
        "ring", 32, baseline=dp, mesh_axes={"data": 2, "seq": 2},
        seq_parallel="seq")
    # the AE protocol's global batch (scripts/osdi22ae/bert.sh: -b 8)
    dp8, _ = run_arm("dp_b8", 8, only_data_parallel=True)
    _, errs["searched_b8"] = run_arm(
        "searched_b8", 8, baseline=dp8, search_budget=30,
        enable_parameter_parallel=True)
    off = {arm: e for arm, e in errs.items() if e > LOSS_RTOL}
    check(not off, f"losses differ from the data-parallel arm's by more "
                   f"than {LOSS_RTOL} (max relative error): {off}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    if "FLEXFLOW_TPU_PALLAS" in os.environ:
        raise SystemExit("chip_smoke: unset FLEXFLOW_TPU_PALLAS — the "
                         "kernels must take their TPU path")
    rebuild_native()

    import jax
    import jaxlib

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devs)} device(s)")
    from importlib import metadata

    from flexflow_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    emit(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=metadata.version("libtpu"),
         devices=[str(d) for d in devs], device_kind=devs[0].device_kind,
         chips=args.chips, cache_dir=cache_dir)
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip(cache_dir)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
