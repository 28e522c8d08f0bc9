"""Multi-host (2-process) SPMD dryrun: gradient-sync parity.

Validates the multi-controller execution path without real multi-host
hardware: spawn N processes, each with `devices_per_proc` virtual CPU
devices, rendezvous through `jax.distributed` (gloo collectives), train a
tiny transformer data-parallel over the global mesh with each process
feeding only its local batch rows — then assert the synced parameters
match a single-process run on the same global batch.

Analog of the reference's multinode CI harness
(tests/multinode_helpers/mpi_wrapper1.sh: mpirun -np 2 with per-rank
GPU masks), re-expressed for JAX multi-controller SPMD.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import tempfile
from typing import Dict, Optional

import numpy as np

_STEPS = 2


def _model_config(total_devices: int):
    from flexflow_tpu.models.transformer import TransformerConfig

    return TransformerConfig(num_layers=1, hidden_size=32, num_heads=2,
                             seq_length=8, batch_size=2 * total_devices)


def _global_batch(cfg):
    rs = np.random.RandomState(0)
    x = rs.randn(cfg.batch_size, cfg.seq_length,
                 cfg.hidden_size).astype(np.float32)
    y = rs.randn(cfg.batch_size, cfg.seq_length, 1).astype(np.float32)
    return x, y


def _multi_axis_legs_possible(total_devices: int) -> bool:
    """Gates the tp AND ring legs plus the checkpoint roundtrip: their
    {model|seq: 2, data: N/2} meshes need an even device count >= 4."""
    return total_devices >= 4 and total_devices % 2 == 0


def _build(total_devices: int, leg: str = "dp"):
    """Compile the dryrun model (no training).

    Legs: "dp" — pure data parallel; "tp" — a {model: 2, data: N/2} mesh
    whose model axis SPANS hosts; "ring" — a {seq: 2, data: N/2} mesh
    whose seq axis spans hosts, so ring attention's K/V ppermute hops
    cross processes (long-context parallelism over the cross-host
    fabric, the brief's first-class requirement)."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.machine import make_mesh
    from flexflow_tpu.models.transformer import create_transformer
    from flexflow_tpu.optimizers import SGDOptimizer

    cfg = _model_config(total_devices)
    if leg == "ring":
        import dataclasses
        cfg = dataclasses.replace(cfg, seq_parallel="seq")
    ff = create_transformer(
        cfg, FFConfig(batch_size=cfg.batch_size,
                      enable_parameter_parallel=(leg == "tp")))
    if leg == "tp":
        # model axis FIRST (outermost): its stride equals half the device
        # list, so each model-ring pairs devices from DIFFERENT processes
        # — the leg exercises cross-host psum/all-gather, not an
        # intra-host copy of them. The data axis then lives within hosts
        # and each host feeds the FULL batch (its devices hold every
        # batch shard), which local_batch_rows resolves below.
        mesh = make_mesh(total_devices,
                         {"model": 2, "data": total_devices // 2})
    elif leg == "ring":
        # seq axis outermost for the same reason: every K/V rotation hop
        # crosses processes
        mesh = make_mesh(total_devices,
                         {"seq": 2, "data": total_devices // 2})
    else:
        mesh = make_mesh(total_devices, {"data": total_devices})
    ff.compile(SGDOptimizer(lr=0.05),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [], mesh=mesh)
    return ff


def _build_and_train(total_devices: int, leg: str = "dp",
                     trace_dir: Optional[str] = None,
                     profile_steps: Optional[str] = None):
    """Compile + train the dryrun model for _STEPS steps on this
    process's rows of the fixed global batch. Returns
    (FFModel, local_x, local_y) — the local slice is derived ONCE here
    and reused by callers (evaluate/predict legs). ``trace_dir``
    activates the obs step tracer; each process writes artifacts keyed
    by its host id (jax.process_index). ``profile_steps`` adds the
    windowed jax.profiler device-trace capture, so each host's merged
    Perfetto lanes include its own device compute/comms rows."""
    import jax

    ff = _build(total_devices, leg)
    cfg = _model_config(total_devices)
    x, y = _global_batch(cfg)
    if jax.process_count() > 1:
        from flexflow_tpu import distributed
        rows, lo = distributed.local_batch_rows(
            ff.executor.batch_sharding(), x.shape[0])
    else:
        rows, lo = x.shape[0], 0
    lx, ly = x[lo:lo + rows], y[lo:lo + rows]
    if leg == "dp":
        # DP leg drives the DataLoader path (SingleDataLoader's
        # multi-host staging), the other legs drive fit() — both per-host
        # feeding mechanisms get parity coverage
        from flexflow_tpu.dataloader import create_data_loaders
        loaders = create_data_loaders(ff, lx, ly)
        ff.fit_loader(loaders, epochs=_STEPS, verbose=False,
                      trace_dir=trace_dir, profile_steps=profile_steps)
    else:
        ff.fit(lx, ly, epochs=_STEPS, verbose=False, trace_dir=trace_dir,
               profile_steps=profile_steps)
    return ff, lx, ly


def _params_to_numpy(ff) -> Dict[str, np.ndarray]:
    from flexflow_tpu import distributed

    flat: Dict[str, np.ndarray] = {}

    def rec(prefix, tree):
        for k in sorted(tree):
            v = tree[k]
            if isinstance(v, dict):
                rec(f"{prefix}{k}/", v)
            else:
                # model-sharded params may not be fully addressable on one
                # host — gather (no-op single-process / replicated)
                flat[f"{prefix}{k}"] = distributed.all_gather_host(v)

    rec("", ff.params)
    return flat


def worker_main(process_id: int, num_processes: int, port: int,
                devices_per_proc: int, out_path: str) -> None:
    """One rendezvous participant (subprocess entry point)."""
    os.environ.pop("JAX_PLATFORMS", None)
    # per-process virtual device count via XLA_FLAGS: must land in the
    # environment BEFORE jax initializes its backend (the jax_num_cpu_devices
    # config knob is unsupported by the pinned JAX — ROADMAP item)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices_per_proc}")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from flexflow_tpu import distributed

    distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=num_processes,
                           process_id=process_id)
    total = jax.device_count()
    assert total == num_processes * devices_per_proc, (
        f"expected {num_processes * devices_per_proc} global devices, "
        f"got {total}")
    # per-host step tracing (FFS_TRACE_DIR, set by run_dryrun): each
    # worker's fit writes *_hostNN artifacts the parent merges by host
    # id; FFS_PROFILE_STEPS adds the per-host device-trace capture
    trace_dir = os.environ.get("FFS_TRACE_DIR") or None
    profile_steps = os.environ.get("FFS_PROFILE_STEPS") or None
    ff, lx, ly = _build_and_train(total, trace_dir=trace_dir,
                                  profile_steps=profile_steps)
    if trace_dir:
        # per-host optimized-HLO dump for the fflint multihost-order pass
        # (FFL501/502 static deadlock detector): every process writes the
        # text of ITS compiled train step; the parent feeds the set
        # through lint_model(ff, hlo_per_host=[...]) after the run
        from flexflow_tpu.search.validate import train_step_hlo
        hlo_path = os.path.join(trace_dir,
                                f"train_step_host{process_id}.hlo.txt")
        with open(hlo_path, "w") as f:
            f.write(train_step_hlo(ff))
    out = {"loss": np.float64(ff._last_loss)}
    out.update({f"dp/{k}": v for k, v in _params_to_numpy(ff).items()})
    # evaluate + predict on the multi-host path: evaluate consumes local
    # rows; predict gathers the GLOBAL output back to every host
    out["eval_loss"] = np.float64(ff.evaluate(lx, ly)["loss"])
    out["predict"] = ff.predict(lx)
    if _multi_axis_legs_possible(total):
        # leg 2: tensor parallelism whose model axis spans the two hosts
        ff_tp, _, _ = _build_and_train(total, leg="tp")
        out["tp_loss"] = np.float64(ff_tp._last_loss)
        tp_params = _params_to_numpy(ff_tp)
        out.update({f"tp/{k}": v for k, v in tp_params.items()})
        # leg 3: ring attention whose seq axis spans the two hosts —
        # every K/V rotation hop is a cross-process ppermute
        ff_ring, _, _ = _build_and_train(total, leg="ring")
        out["ring_loss"] = np.float64(ff_ring._last_loss)
        out.update({f"ring/{k}": v
                    for k, v in _params_to_numpy(ff_ring).items()})
        # leg 4: cross-host checkpoint roundtrip of the model-sharded
        # state — rank 0 writes (after an all-host gather), every host
        # loads back onto the cross-host shardings
        ckpt = os.path.join(os.path.dirname(out_path), "ckpt_tp")
        ff_tp.save_checkpoint(ckpt)  # barriers internally: durable on return
        ff_rt = _build(total, leg="tp")
        ff_rt.load_checkpoint(ckpt)
        rt_params = _params_to_numpy(ff_rt)
        for key, want in tp_params.items():
            got = rt_params[key]
            # bf16 leaves round-trip through an f32 container
            if not np.allclose(got, want, rtol=1e-5, atol=1e-6):
                raise AssertionError(
                    f"checkpoint roundtrip diverged at {key}: max diff "
                    f"{float(np.max(np.abs(got - want)))}")
        out["ckpt_roundtrip_ok"] = np.float64(1.0)
    np.savez(out_path, **out)


def _lint_per_host_hlo(trace_dir: str, num_processes: int, ff) -> None:
    """Feed the workers' per-host optimized-HLO dumps through fflint's
    multihost-order pass (FFL501/502 static deadlock detector). Raises
    when the per-host collective sequences diverge — the failure class
    that on a real pod only shows as a rendezvous timeout."""
    texts = []
    for p in range(num_processes):
        path = os.path.join(trace_dir, f"train_step_host{p}.hlo.txt")
        if not os.path.exists(path):
            raise AssertionError(
                f"multihost dryrun: worker {p} did not dump its train-step "
                f"HLO ({path}) — per-host collection is broken")
        with open(path) as f:
            texts.append(f.read())
    from flexflow_tpu.analysis import lint_model
    rep = lint_model(ff, hlo_per_host=texts)
    order = [d for d in rep.diagnostics if d.rule in ("FFL501", "FFL502")]
    if order:
        raise AssertionError(
            "multihost dryrun: per-host collective sequences diverge:\n"
            + "\n".join(d.format() for d in order))
    status = rep.passes.get("multihost-order")
    if status != "ok":
        raise AssertionError(
            f"multihost dryrun: multihost-order pass did not run: {status}")
    print(f"multihost dryrun: fflint multihost-order pass ok over "
          f"{len(texts)} per-host HLO programs")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# elastic fault-tolerance legs (ISSUE 10): kill a host mid-epoch via the
# FFS_FAULT harness, then resume from the last complete checkpoint on
# (a) the same mesh — bit-identical loss continuity — and (b) a smaller
# mesh through a re-searched strategy (resume is a strategy decision).


def _worker_env(trace_dir: Optional[str] = None) -> Dict[str, str]:
    env = dict(os.environ)
    env["FFS_MP_CHILD"] = "1"
    env.pop("JAX_PLATFORMS", None)
    # the per-process backend is configured inside the worker via jax
    # config, not through the parent's environment
    env.pop("XLA_FLAGS", None)
    env.pop("FFS_FAULT", None)
    if trace_dir:
        env["FFS_TRACE_DIR"] = trace_dir
    else:
        env.pop("FFS_TRACE_DIR", None)
    return env


def _spawn(entry: str, num_processes: int, devices_per_proc: int,
           outs, extra_args, env, timeout: int, tolerate_failures: bool,
           kill_grace: float = 30.0):
    """Spawn the rendezvous participants for one leg and wait.

    ``tolerate_failures`` is the fault-injection mode: the first worker
    to die does NOT fail the leg; its peers get ``kill_grace`` seconds
    to exit (they are mid-collective with a dead peer — gloo may error
    out or hang) and are then killed. Returns the exit-code list."""
    import time as _time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = _free_port()
    procs = []
    try:
        for p in range(num_processes):
            code = (
                "import sys; sys.path.insert(0, %r); "
                "from flexflow_tpu.multihost_dryrun import %s; "
                "%s(%d, %d, %d, %d, %s)"
                % (repo, entry, entry, p, num_processes, port,
                   devices_per_proc,
                   ", ".join(repr(a) for a in [outs[p]] + list(extra_args)))
            )
            procs.append(subprocess.Popen([sys.executable, "-c", code],
                                          cwd=repo, env=env))
        if not tolerate_failures:
            return [proc.wait(timeout=timeout) for proc in procs]
        deadline = _time.monotonic() + timeout
        first_death = None
        while _time.monotonic() < deadline:
            codes = [proc.poll() for proc in procs]
            if all(c is not None for c in codes):
                return codes
            if any(c is not None for c in codes):
                if first_death is None:
                    first_death = _time.monotonic()
                elif _time.monotonic() - first_death > kill_grace:
                    break  # survivors are wedged on the dead peer
            _time.sleep(0.1)
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        return [proc.poll() for proc in procs]
    finally:
        # a worker that died pre-rendezvous leaves its peer blocked in
        # jax.distributed.initialize — never orphan it
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()


def _elastic_train_loop(ff, lx, ly, start: int, steps: int, mgr=None,
                        health=None):
    """The manual iteration protocol with the checkpoint-manager, fault
    and supervision seams fit() uses, returning the per-step losses —
    the loss series the continuity assertions compare bitwise.
    ``health`` (a runtime_health.RuntimeHealth) is fed after every
    step, exactly like fit's epoch loop: a pending preemption raises
    ``Preempted`` out of here AFTER the in-flight step."""
    from flexflow_tpu.ckpt import faults

    losses = []
    ff.set_batch(lx, ly)
    for step in range(start, steps):
        ff.forward()
        ff.backward()
        ff.update()
        losses.append(float(ff._last_loss))
        faults.step_hook(step)
        if health is not None:
            health.step_done(step)
        if mgr is not None:
            if mgr.should_save(ff._iter):
                mgr.save(ff._iter)
            else:
                mgr.note_step(ff._iter)
    return losses


def elastic_worker_main(process_id: int, num_processes: int, port: int,
                        devices_per_proc: int, out_path: str,
                        ckpt_dir: str, steps: int, every: int,
                        resume: int) -> None:
    """One participant of an elastic-training leg: train the dryrun
    model step by step with per-shard async checkpointing, honoring the
    FFS_FAULT plan the parent set (kill_host mid-epoch), optionally
    resuming from the newest complete checkpoint first."""
    os.environ.pop("JAX_PLATFORMS", None)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices_per_proc}")
    import jax

    jax.config.update("jax_platforms", "cpu")
    from flexflow_tpu import distributed

    distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=num_processes,
                           process_id=process_id)
    total = jax.device_count()
    ff = _build(total)
    cfg = _model_config(total)
    x, y = _global_batch(cfg)
    rows, lo = distributed.local_batch_rows(
        ff.executor.batch_sharding(), x.shape[0])
    lx, ly = x[lo:lo + rows], y[lo:lo + rows]

    mgr = None
    start = 0
    if ckpt_dir:
        from flexflow_tpu.ckpt import CheckpointManager
        mgr = CheckpointManager(ff, ckpt_dir, every=every, retain=3,
                                async_write=True, run_name="dryrun",
                                fs_timeout=60.0)
        if resume:
            start = mgr.resume(require=True)
    losses = _elastic_train_loop(ff, lx, ly, start, steps, mgr)
    if mgr is not None:
        mgr.finalize(elapsed_s=None, steps=None)
    np.savez(out_path, losses=np.asarray(losses, np.float64),
             start=np.int64(start))


def failfast_worker_main(process_id: int, num_processes: int, port: int,
                         devices_per_proc: int, out_path: str,
                         base_dir: str) -> None:
    """Regression worker for the ADVICE r5 hang: every rank points at a
    RANK-PRIVATE checkpoint path (simulating a non-shared filesystem
    where only rank 0 can see the files rank 0 wrote). Both the v1 and
    the v2 load must raise the same actionable error on EVERY rank —
    promptly — instead of FileNotFoundError on some ranks and a
    collective deadlock on the rest."""
    os.environ.pop("JAX_PLATFORMS", None)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices_per_proc}")
    import jax

    jax.config.update("jax_platforms", "cpu")
    from flexflow_tpu import distributed

    distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=num_processes,
                           process_id=process_id)
    total = jax.device_count()
    ff = _build(total)
    my_dir = os.path.join(base_dir, f"rank{process_id}")
    os.makedirs(my_dir, exist_ok=True)
    # v1: a collective save whose files land only under rank 0's view
    v1_stem = os.path.join(my_dir, "ckpt_v1")
    ff.save_checkpoint(os.path.join(base_dir, "rank0", "ckpt_v1")
                       if process_id == 0 else v1_stem + "_unwritten")
    results = {}
    try:
        ff.load_checkpoint(v1_stem)
        results["v1"] = "no error"
    except FileNotFoundError as e:
        results["v1"] = f"FileNotFoundError: {e}"
    # v2: rank 0 sees a real checkpoint, rank 1 an empty directory
    from flexflow_tpu.ckpt import load_sharded, save_sharded
    shared = os.path.join(base_dir, "shared_v2")
    save_sharded(shared, ff)  # all ranks participate; genuinely shared
    probe = shared if process_id == 0 else my_dir
    try:
        load_sharded(probe, ff)
        results["v2"] = "no error"
    except FileNotFoundError as e:
        results["v2"] = f"FileNotFoundError: {e}"
    np.savez(out_path, **{k: np.str_(v) for k, v in results.items()})


def run_ckpt_failfast_dryrun(num_processes: int = 2,
                             devices_per_proc: int = 1,
                             timeout: int = 240) -> None:
    """Assert the non-shared-filesystem load fails fast on every rank
    (ADVICE r5 regression): both format loaders must raise
    FileNotFoundError naming the invisible ranks, and the whole leg
    must finish well inside the timeout (the old behavior was an
    unbounded hang)."""
    with tempfile.TemporaryDirectory() as td:
        outs = [os.path.join(td, f"ff{p}.npz") for p in range(num_processes)]
        rcs = _spawn("failfast_worker_main", num_processes,
                     devices_per_proc, outs, [os.path.join(td, "ckpts")],
                     _worker_env(), timeout, tolerate_failures=False)
        if any(rc != 0 for rc in rcs):
            raise RuntimeError(
                f"ckpt fail-fast dryrun: worker exit codes {rcs}")
        for p, out in enumerate(outs):
            got = {k: str(v) for k, v in np.load(out).items()}
            for fmt in ("v1", "v2"):
                if not got[fmt].startswith("FileNotFoundError"):
                    raise AssertionError(
                        f"worker {p} {fmt} load did not fail fast: "
                        f"{got[fmt]!r}")
                if "shared" not in got[fmt]:
                    raise AssertionError(
                        f"worker {p} {fmt} error is not actionable "
                        f"(no shared-filesystem hint): {got[fmt]!r}")
    print(f"ckpt fail-fast dryrun ok: {num_processes} ranks, both "
          f"formats raise actionable FileNotFoundError, no hang")


def run_elastic_dryrun(num_processes: int = 2, devices_per_proc: int = 1,
                       steps: int = 6, every: int = 2, kill_step: int = 4,
                       timeout: int = 240) -> dict:
    """Kill-and-resume end to end.

    Phase A: an uninterrupted N-process run records the reference loss
    series. Phase B: the same run with ``FFS_FAULT=kill_host:<last
    rank>@step:<kill_step>`` and per-shard async checkpointing — the
    killed host exits hard mid-epoch, the survivors are reaped, and the
    directory must hold a complete (manifest-committed) checkpoint and
    nothing readable beyond it. ``kill_step`` must leave at least one
    save() call strictly between the first checkpointed iteration and
    the kill: save() joins the PREVIOUS async writer on the training
    thread, so that earlier checkpoint is deterministically committed
    before the kill can fire — the leg never depends on a writer
    thread racing the (millisecond) training steps. Phase C: resume on
    the SAME mesh — the
    continued loss series must be bit-identical to the reference from
    the restored step on. Phase D (in-process): resume on a SMALLER
    mesh (half the devices) — ``plan_resume`` says "research", the
    native search (when available) picks a strategy for the surviving
    topology, and the reassembled state trains on with losses matching
    the reference to reduction-order tolerance. Returns a summary dict.
    """
    import jax

    total = num_processes * devices_per_proc
    kill_rank = num_processes - 1
    summary = {}
    with tempfile.TemporaryDirectory() as td:
        ckpt_dir = os.path.join(td, "ckpts")

        # ---- phase A: uninterrupted reference ---------------------------
        outs = [os.path.join(td, f"ref{p}.npz") for p in range(num_processes)]
        rcs = _spawn("elastic_worker_main", num_processes, devices_per_proc,
                     outs, ["", steps, every, 0], _worker_env(), timeout,
                     tolerate_failures=False)
        if any(rc != 0 for rc in rcs):
            raise RuntimeError(f"elastic dryrun reference: exit codes {rcs}")
        ref = np.load(outs[0])["losses"]
        if len(ref) != steps or not np.all(np.isfinite(ref)):
            raise AssertionError(f"reference losses malformed: {ref}")

        # ---- phase B: kill a host mid-epoch -----------------------------
        from flexflow_tpu.ckpt.faults import KILL_EXIT
        env = _worker_env()
        env["FFS_FAULT"] = f"kill_host:{kill_rank}@step:{kill_step}"
        outs_b = [os.path.join(td, f"fault{p}.npz")
                  for p in range(num_processes)]
        rcs = _spawn("elastic_worker_main", num_processes, devices_per_proc,
                     outs_b, [ckpt_dir, steps, every, 0], env, timeout,
                     tolerate_failures=True)
        if rcs[kill_rank] != KILL_EXIT:
            raise AssertionError(
                f"fault leg: rank {kill_rank} was meant to die with exit "
                f"{KILL_EXIT} at step {kill_step}, got exit codes {rcs}")
        from flexflow_tpu.ckpt import latest_complete, verify_step_dir
        latest = latest_complete(ckpt_dir)
        if latest is None:
            raise AssertionError(
                "fault leg left no complete checkpoint — the pre-kill "
                "saves never committed")
        resume_step, step_dir = latest
        if resume_step > kill_step + 1:
            raise AssertionError(
                f"complete checkpoint at iteration {resume_step} claims "
                f"steps after the kill at step {kill_step}")
        rep = verify_step_dir(step_dir)
        if not rep["complete"]:
            raise AssertionError(
                f"latest checkpoint fails deep verification: "
                f"{rep['errors']}")
        summary["resume_step"] = resume_step

        # ---- phase C: resume on the SAME mesh — bit-identical -----------
        outs_c = [os.path.join(td, f"res{p}.npz")
                  for p in range(num_processes)]
        rcs = _spawn("elastic_worker_main", num_processes, devices_per_proc,
                     outs_c, [ckpt_dir, steps, every, 1], _worker_env(),
                     timeout, tolerate_failures=False)
        if any(rc != 0 for rc in rcs):
            raise RuntimeError(f"elastic dryrun resume: exit codes {rcs}")
        for p, out in enumerate(outs_c):
            got = np.load(out)
            start = int(got["start"])
            if start != resume_step:
                raise AssertionError(
                    f"worker {p} resumed at {start}, expected "
                    f"{resume_step}")
            cont = got["losses"]
            want = ref[start:]
            if not np.array_equal(cont, want):
                raise AssertionError(
                    f"worker {p}: resumed losses diverge from the "
                    f"uninterrupted run on the same mesh — not "
                    f"bit-identical\n  resumed {cont}\n  expected {want}")
        summary["same_mesh_bitwise"] = True

        # ---- phase D: resume on a SMALLER mesh (re-searched) ------------
        n_small = max(1, total // 2)
        if len(jax.devices()) < n_small:
            raise RuntimeError(
                f"elastic dryrun needs {n_small} local devices for the "
                f"smaller-mesh leg, have {len(jax.devices())}")
        # phase C's resumed run has since committed newer checkpoints
        # into the same directory — phase D must restart from the same
        # post-kill state, so it targets the surviving step dir directly
        from flexflow_tpu.ckpt import load_manifest, plan_resume
        plan = plan_resume(load_manifest(step_dir), n_small)
        if plan["action"] != "research":
            raise AssertionError(
                f"plan_resume on {n_small}/{plan['saved_devices']} devices "
                f"should demand a re-search, got {plan}")
        from flexflow_tpu.config import FFConfig
        from flexflow_tpu.ffconst import LossType
        from flexflow_tpu.machine import make_mesh
        from flexflow_tpu.models.transformer import create_transformer
        from flexflow_tpu.optimizers import SGDOptimizer
        from flexflow_tpu.search.native import available as _native_ok
        cfg = _model_config(total)
        budget = 6 if _native_ok() else 0
        ff_small = create_transformer(
            cfg, FFConfig(batch_size=cfg.batch_size,
                          workers_per_node=n_small,
                          search_budget=budget,
                          enable_parameter_parallel=n_small > 1))
        ff_small.compile(SGDOptimizer(lr=0.05),
                         LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [],
                         mesh=None if budget else make_mesh(
                             n_small, {"data": n_small}))
        mesh_small = dict(zip(ff_small.mesh.axis_names,
                              ff_small.mesh.devices.shape))
        it = ff_small.load_checkpoint(step_dir)
        if it != resume_step:
            raise AssertionError(
                f"smaller-mesh load restored iteration {it}, expected "
                f"{resume_step}")
        x, y = _global_batch(cfg)
        cont = _elastic_train_loop(ff_small, x, y, resume_step, steps)
        if not np.all(np.isfinite(cont)):
            raise AssertionError(
                f"smaller-mesh resume produced non-finite losses: {cont}")
        if not np.allclose(cont, ref[resume_step:], rtol=1e-3, atol=1e-5):
            raise AssertionError(
                f"smaller-mesh resumed losses diverged beyond reduction-"
                f"order tolerance\n  resumed {cont}\n  "
                f"expected {ref[resume_step:]}")
        summary["smaller_mesh"] = mesh_small
        summary["researched"] = bool(budget)
    print(f"elastic dryrun ok: {num_processes}x{devices_per_proc} killed "
          f"rank {kill_rank} at step {kill_step}, resumed from iteration "
          f"{summary['resume_step']}: same-mesh continuation bit-identical"
          f"; smaller mesh {summary['smaller_mesh']} "
          f"({'re-searched strategy' if summary['researched'] else 'heuristic strategy'}) "
          f"converges within tolerance")
    return summary


# ---------------------------------------------------------------------------
# preemption-aware supervision legs (ISSUE 12): SIGTERM mid-epoch must
# yield a complete grace-window checkpoint and a bit-identical resume;
# a hung step loop must be reaped by the watchdog and auto-restarted by
# the supervisor; transient checkpoint-write failures must be absorbed
# by retry-with-backoff.


def preempted_worker_main(process_id: int, num_processes: int, port: int,
                          devices_per_proc: int, out_path: str,
                          ckpt_dir: str, steps: int, every: int,
                          resume: int, grace: float) -> None:
    """Elastic worker + RuntimeHealth: honors ``FFS_FAULT`` sigterm
    specs, converts the signal into a grace-window final checkpoint,
    and exits ``PREEMPTED_EXIT`` — the multi-host half of the graceful
    preemption contract (every rank must still reach the commit
    barrier inside the grace window)."""
    os.environ.pop("JAX_PLATFORMS", None)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices_per_proc}")
    import jax

    jax.config.update("jax_platforms", "cpu")
    from flexflow_tpu import distributed
    from flexflow_tpu.runtime_health import (Preempted, PREEMPTED_EXIT,
                                             RuntimeHealth)

    distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=num_processes,
                           process_id=process_id)
    total = jax.device_count()
    ff = _build(total)
    cfg = _model_config(total)
    x, y = _global_batch(cfg)
    rows, lo = distributed.local_batch_rows(
        ff.executor.batch_sharding(), x.shape[0])
    lx, ly = x[lo:lo + rows], y[lo:lo + rows]

    from flexflow_tpu.ckpt import CheckpointManager
    health = RuntimeHealth(grace_window_s=grace, run_name="dryrun")
    mgr = CheckpointManager(ff, ckpt_dir, every=every, retain=3,
                            async_write=True, run_name="dryrun",
                            fs_timeout=60.0, heartbeat=health.heartbeat)
    start = mgr.resume(require=True) if resume else 0
    health.install()
    try:
        losses = _elastic_train_loop(ff, lx, ly, start, steps, mgr,
                                     health=health)
    except Preempted:
        # the grace path: final checkpoint through the manager (every
        # rank participates in the commit barrier), then the distinct
        # exit code the supervisor classifies as "preempted"
        mgr.finalize(elapsed_s=None, steps=None)
        np.savez(out_path, losses=np.asarray([], np.float64),
                 start=np.int64(start), preempted=np.int64(1))
        health.close()
        sys.exit(PREEMPTED_EXIT)
    mgr.finalize(elapsed_s=None, steps=None)
    health.close()
    np.savez(out_path, losses=np.asarray(losses, np.float64),
             start=np.int64(start), preempted=np.int64(0))


def run_preemption_dryrun(num_processes: int = 2,
                          devices_per_proc: int = 1, steps: int = 6,
                          sigterm_step: int = 3,
                          timeout: int = 240) -> dict:
    """SIGTERM mid-epoch → grace-window checkpoint → bit-identical
    auto-resume, across processes.

    Phase A records the uninterrupted reference loss series. Phase B
    delivers ``FFS_FAULT sigterm`` to EVERY rank at ``sigterm_step``
    (the whole-slice preemption shape a platform maintenance event
    takes): each rank finishes the in-flight step, the grace path cuts
    a final checkpoint through the normal commit barrier, and every
    rank exits ``PREEMPTED_EXIT``. Phase C resumes on the same mesh and
    must continue bit-identically to the reference from the restored
    iteration on."""
    from flexflow_tpu.runtime_health import PREEMPTED_EXIT

    summary = {}
    with tempfile.TemporaryDirectory() as td:
        ckpt_dir = os.path.join(td, "ckpts")

        # ---- phase A: uninterrupted reference ---------------------------
        outs = [os.path.join(td, f"ref{p}.npz") for p in range(num_processes)]
        rcs = _spawn("elastic_worker_main", num_processes, devices_per_proc,
                     outs, ["", steps, 0, 0], _worker_env(), timeout,
                     tolerate_failures=False)
        if any(rc != 0 for rc in rcs):
            raise RuntimeError(
                f"preemption dryrun reference: exit codes {rcs}")
        ref = np.load(outs[0])["losses"]
        if len(ref) != steps or not np.all(np.isfinite(ref)):
            raise AssertionError(f"reference losses malformed: {ref}")

        # ---- phase B: SIGTERM every rank mid-epoch ----------------------
        env = _worker_env()
        env["FFS_FAULT"] = ",".join(
            f"sigterm:{r}@step:{sigterm_step}" for r in range(num_processes))
        outs_b = [os.path.join(td, f"pre{p}.npz")
                  for p in range(num_processes)]
        rcs = _spawn("preempted_worker_main", num_processes,
                     devices_per_proc, outs_b,
                     [ckpt_dir, steps, 0, 0, 60.0], env, timeout,
                     tolerate_failures=False)
        if rcs != [PREEMPTED_EXIT] * num_processes:
            raise AssertionError(
                f"preemption leg: every rank must exit PREEMPTED_EXIT "
                f"({PREEMPTED_EXIT}), got {rcs}")
        from flexflow_tpu.ckpt import latest_complete, verify_step_dir
        latest = latest_complete(ckpt_dir)
        if latest is None:
            raise AssertionError(
                "preemption leg left no complete checkpoint — the grace "
                "window did not produce a committed save")
        resume_step, step_dir = latest
        if resume_step != sigterm_step + 1:
            raise AssertionError(
                f"grace checkpoint at iteration {resume_step}, expected "
                f"{sigterm_step + 1} (the post-in-flight-step state)")
        rep = verify_step_dir(step_dir)
        if not rep["complete"]:
            raise AssertionError(
                f"grace checkpoint fails deep verification: "
                f"{rep['errors']}")
        summary["resume_step"] = resume_step

        # ---- phase C: auto-resume, bit-identical ------------------------
        outs_c = [os.path.join(td, f"res{p}.npz")
                  for p in range(num_processes)]
        rcs = _spawn("preempted_worker_main", num_processes,
                     devices_per_proc, outs_c,
                     [ckpt_dir, steps, 0, 1, 60.0], _worker_env(),
                     timeout, tolerate_failures=False)
        if any(rc != 0 for rc in rcs):
            raise RuntimeError(f"preemption dryrun resume: exit codes {rcs}")
        for p, out in enumerate(outs_c):
            got = np.load(out)
            start = int(got["start"])
            if start != resume_step:
                raise AssertionError(
                    f"worker {p} resumed at {start}, expected "
                    f"{resume_step}")
            cont = got["losses"]
            want = ref[start:]
            if not np.array_equal(cont, want):
                raise AssertionError(
                    f"worker {p}: post-preemption losses diverge from "
                    f"the uninterrupted run — not bit-identical\n  "
                    f"resumed {cont}\n  expected {want}")
        summary["bitwise"] = True
    print(f"preemption dryrun ok: {num_processes}x{devices_per_proc} "
          f"SIGTERM at step {sigterm_step} -> complete grace checkpoint "
          f"at iteration {summary['resume_step']}, resumed continuation "
          f"bit-identical")
    return summary


_SUPERVISED_CHILD = """
import sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.ffconst import ActiMode
cfg = FFConfig(batch_size=64)
rest = cfg.parse_args(sys.argv[1:])
assert not rest, f"unparsed flags: {{rest}}"
ff = FFModel(cfg)
t = ff.create_tensor((64, 16))
h = ff.dense(t, 32, activation=ActiMode.AC_MODE_RELU, name="h1")
out = ff.dense(h, 4, name="out")
ff.softmax(out)
ff.compile(AdamOptimizer(alpha=0.01))
rs = np.random.RandomState(0)
x = rs.randn(256, 16).astype(np.float32)
y = rs.randint(0, 4, 256).astype(np.int32).reshape(-1, 1)
ff.fit(x, y, epochs=2, verbose=False)
print("supervised child done: loss", float(ff._last_loss), flush=True)
"""


def run_supervised_dryrun(watchdog_timeout: float = 10.0) -> dict:
    """Self-healing auto-resume, end to end, single process per
    attempt: the Supervisor runs a real training subprocess through
    the real ``fit`` wiring (``--watchdog-timeout``/``--grace-window``
    flags), classifies the exit, and restarts with ``--resume``.

    Leg 1 (hang): ``FFS_FAULT hang`` wedges the step loop — the
    watchdog dumps stacks and exits ``HUNG_EXIT``; the supervised
    restart (fault cleared: an injected fault models a one-time event)
    resumes from the last complete checkpoint and finishes clean.
    Leg 2 (kill): ``FFS_FAULT kill_host`` hard-kills mid-epoch; same
    supervised recovery. Leg 3 (io_error, in-process): transient
    checkpoint-write failures are absorbed by retry-with-backoff with
    the retry count visible in obs counters."""
    from flexflow_tpu.ckpt import latest_complete, verify_step_dir
    from flexflow_tpu.ckpt import manifest as mf
    from flexflow_tpu.runtime_health import Supervisor

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child_src = _SUPERVISED_CHILD.format(repo=repo)
    summary = {}

    def _run_leg(name, fault, ckpt_dir):
        cmd = [sys.executable, "-c", child_src,
               "--checkpoint-dir", ckpt_dir, "--checkpoint-every", "2",
               "--watchdog-timeout", str(watchdog_timeout),
               "--grace-window", "60"]
        env = _worker_env()
        env["FFS_FAULT"] = fault
        sup = Supervisor(cmd, max_restarts=2, backoff_base_s=0.2,
                         backoff_max_s=2.0, env=env,
                         state_path=os.path.join(ckpt_dir,
                                                 mf.SUPERVISOR_NAME))
        res = sup.run()
        outcomes = [h["outcome"] for h in res["history"]]
        if res["final_outcome"] != "clean":
            raise AssertionError(
                f"{name} leg: supervised run did not converge to clean "
                f"(history {outcomes}, final code {res['final_code']})")
        latest = latest_complete(ckpt_dir)
        if latest is None or not verify_step_dir(latest[1])["complete"]:
            raise AssertionError(
                f"{name} leg: no complete checkpoint after supervised "
                f"recovery")
        sup_state = mf.read_supervisor(ckpt_dir)
        if not sup_state or sup_state.get("restarts", 0) < 1:
            raise AssertionError(
                f"{name} leg: SUPERVISOR.json missing or records no "
                f"restart: {sup_state}")
        return outcomes

    with tempfile.TemporaryDirectory() as td:
        # ---- leg 1: hang -> watchdog HUNG_EXIT -> supervised restart ----
        outcomes = _run_leg("hang", "hang:0@step:3",
                            os.path.join(td, "hang"))
        if outcomes[0] != "hung":
            raise AssertionError(
                f"hang leg: first attempt should be classified 'hung' "
                f"(watchdog exit), got {outcomes}")
        summary["hang"] = outcomes

        # ---- leg 2: kill -> supervised auto-resume ----------------------
        outcomes = _run_leg("kill", "kill_host:0@step:4",
                            os.path.join(td, "kill"))
        if outcomes[0] != "kill":
            raise AssertionError(
                f"kill leg: first attempt should be classified 'kill', "
                f"got {outcomes}")
        summary["kill"] = outcomes

        # ---- leg 3: transient io_error -> retried save completes --------
        from flexflow_tpu.ckpt import save_sharded
        from flexflow_tpu.obs.registry import get_registry
        ff = _build(1)
        cfg = _model_config(1)
        x, y = _global_batch(cfg)
        ff.fit(x, y, epochs=1, verbose=False)
        io_dir = os.path.join(td, "io")
        reg = get_registry()
        before = reg.get("ckpt/io_retries")
        old = os.environ.get("FFS_FAULT")
        from flexflow_tpu.ckpt import faults as _faults
        # the parse cache memoizes FaultPlan per spec string and the
        # io_error budget is mutable on the cached object — a stale
        # (depleted) plan would inject nothing
        _faults._CACHE.pop("io_error:shards_host:2", None)
        os.environ["FFS_FAULT"] = "io_error:shards_host:2"
        try:
            save_sharded(io_dir, ff)
        finally:
            if old is None:
                os.environ.pop("FFS_FAULT", None)
            else:
                os.environ["FFS_FAULT"] = old
        retries = reg.get("ckpt/io_retries") - before
        latest = latest_complete(io_dir)
        if latest is None or not verify_step_dir(latest[1])["complete"]:
            raise AssertionError(
                "io_error leg: retried save did not produce a complete "
                "checkpoint")
        if retries != 2:
            raise AssertionError(
                f"io_error leg: expected 2 visible retries in obs "
                f"counters, got {retries}")
        summary["io_retries"] = int(retries)
    print(f"supervised dryrun ok: hang {summary['hang']}, kill "
          f"{summary['kill']} (auto-resumed to clean under the "
          f"supervisor), io_error absorbed with {summary['io_retries']} "
          f"retries")
    return summary


# ---------------------------------------------------------------------------
# multi-slice legs (ISSUE 16): N process sets stand in for N
# DCN-connected slices — the ('slice', 'data') runtime mesh crosses the
# set boundary exactly where a real deployment crosses the DCN. The
# lint pass checks per-slice collective order (FFL501/502 with slice
# attribution) plus the cross-slice leader agreement (FFL503), and the
# kill-one-slice leg exercises plan_resume's slice_loss topology class.


def _build_multislice(total_devices: int, num_slices: int):
    """Compile the dryrun model over a ('slice', 'data') mesh:
    ``--slices`` splits the flat data mesh in model.compile, so the
    gradient sync's cross-slice leg rides the outer axis."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.machine import make_mesh
    from flexflow_tpu.models.transformer import create_transformer
    from flexflow_tpu.optimizers import SGDOptimizer

    cfg = _model_config(total_devices)
    c = FFConfig(batch_size=cfg.batch_size)
    c.slices = num_slices
    ff = create_transformer(cfg, c)
    ff.compile(SGDOptimizer(lr=0.05),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [],
               mesh=make_mesh(total_devices, {"data": total_devices}))
    assert "slice" in ff.mesh.axis_names, ff.mesh.axis_names
    return ff


def multislice_worker_main(process_id: int, num_processes: int, port: int,
                           devices_per_proc: int, out_path: str,
                           ckpt_dir: str, num_slices: int, steps: int,
                           every: int) -> None:
    """One participant of a multi-slice leg: processes form
    ``num_slices`` contiguous sets (slice-major, matching the
    ('slice', ...) mesh's device order), train over the cross-slice
    data axis with per-shard checkpointing, honor FFS_FAULT, and dump
    the per-host optimized HLO for the hierarchical lint pass."""
    os.environ.pop("JAX_PLATFORMS", None)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices_per_proc}")
    import jax

    jax.config.update("jax_platforms", "cpu")
    from flexflow_tpu import distributed
    from flexflow_tpu.multislice import slice_of_process

    distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=num_processes,
                           process_id=process_id)
    total = jax.device_count()
    my_slice = slice_of_process(process_id, num_processes, num_slices)
    ff = _build_multislice(total, num_slices)
    cfg = _model_config(total)
    x, y = _global_batch(cfg)
    rows, lo = distributed.local_batch_rows(
        ff.executor.batch_sharding(), x.shape[0])
    lx, ly = x[lo:lo + rows], y[lo:lo + rows]
    trace_dir = os.environ.get("FFS_TRACE_DIR") or None
    if trace_dir:
        from flexflow_tpu.search.validate import train_step_hlo
        hlo_path = os.path.join(trace_dir,
                                f"train_step_host{process_id}.hlo.txt")
        with open(hlo_path, "w") as f:
            f.write(train_step_hlo(ff))
    mgr = None
    if ckpt_dir:
        from flexflow_tpu.ckpt import CheckpointManager
        mgr = CheckpointManager(ff, ckpt_dir, every=every, retain=3,
                                async_write=True, run_name="msdryrun",
                                fs_timeout=60.0)
    losses = _elastic_train_loop(ff, lx, ly, 0, steps, mgr)
    if mgr is not None:
        mgr.finalize(elapsed_s=None, steps=None)
    np.savez(out_path, losses=np.asarray(losses, np.float64),
             slice_id=np.int64(my_slice),
             mesh_axes=np.asarray(
                 [f"{a}={s}" for a, s in zip(ff.mesh.axis_names,
                                             ff.mesh.devices.shape)]))


def _lint_per_slice_hlo(trace_dir: str, num_processes: int,
                        num_slices: int, ff) -> None:
    """Feed the workers' per-host HLO dumps through fflint's
    hierarchical multihost-order pass: within-slice FFL501/502 with
    slice attribution plus the FFL503 cross-slice leader comparison.
    Raises on any order diagnostic."""
    from flexflow_tpu.multislice import slice_of_process
    texts = []
    for p in range(num_processes):
        path = os.path.join(trace_dir, f"train_step_host{p}.hlo.txt")
        if not os.path.exists(path):
            raise AssertionError(
                f"multislice dryrun: worker {p} did not dump its "
                f"train-step HLO ({path})")
        with open(path) as f:
            texts.append(f.read())
    slice_of = [slice_of_process(p, num_processes, num_slices)
                for p in range(num_processes)]
    from flexflow_tpu.analysis import lint_model
    rep = lint_model(ff, hlo_per_host=texts, slice_of_host=slice_of)
    order = [d for d in rep.diagnostics
             if d.rule in ("FFL501", "FFL502", "FFL503")]
    if order:
        raise AssertionError(
            "multislice dryrun: per-slice collective sequences diverge:\n"
            + "\n".join(d.format() for d in order))
    if rep.passes.get("multihost-order") != "ok":
        raise AssertionError(
            f"multislice dryrun: multihost-order pass did not run: "
            f"{rep.passes.get('multihost-order')}")
    print(f"multislice dryrun: fflint multihost-order ok over "
          f"{num_slices} slices x {num_processes // num_slices} "
          f"processes (FFL501/502/503 clean)")


def run_multislice_dryrun(num_slices: int = 2, procs_per_slice: int = 2,
                          devices_per_proc: int = 1, steps: int = 6,
                          every: int = 2, kill_step: int = 4,
                          timeout: int = 300) -> dict:
    """Multi-slice training end to end, devicelessly.

    Phase A: ``num_slices x procs_per_slice`` processes train over a
    ('slice', 'data') mesh whose slice axis crosses the process-set
    boundary; every process dumps its optimized HLO and the
    hierarchical fflint pass must come back FFL501/502/503-clean.
    Phase B: the same run with per-shard checkpointing and
    ``FFS_FAULT`` killing a rank in the LAST slice mid-epoch — losing
    a host loses its slice; the directory must hold a complete
    manifest-committed checkpoint whose mesh records the slice axis.
    Phase C (in-process): ``plan_resume`` on the surviving slice's
    device count must classify the change as ``slice_loss`` (1 of
    ``num_slices`` slices lost, resume ``--slices`` = survivors), the
    survivors compile WITHOUT a slice axis (single surviving slice) —
    re-searched when the native search is available — and the
    continued losses match the reference within reduction-order
    tolerance. Returns a summary dict."""
    import jax

    num_processes = num_slices * procs_per_slice
    total = num_processes * devices_per_proc
    kill_rank = num_processes - 1  # a host of the last slice
    summary = {}
    with tempfile.TemporaryDirectory() as td:
        ckpt_dir = os.path.join(td, "ckpts")
        trace_dir = os.path.join(td, "trace")
        os.makedirs(trace_dir)

        # ---- phase A: reference run + hierarchical lint -----------------
        outs = [os.path.join(td, f"ref{p}.npz") for p in range(num_processes)]
        rcs = _spawn("multislice_worker_main", num_processes,
                     devices_per_proc, outs,
                     ["", num_slices, steps, every],
                     _worker_env(trace_dir=trace_dir), timeout,
                     tolerate_failures=False)
        if any(rc != 0 for rc in rcs):
            raise RuntimeError(
                f"multislice dryrun reference: exit codes {rcs}")
        ref = np.load(outs[0])["losses"]
        if len(ref) != steps or not np.all(np.isfinite(ref)):
            raise AssertionError(f"reference losses malformed: {ref}")
        for p, out in enumerate(outs):
            got = np.load(out)
            want_slice = p // procs_per_slice
            if int(got["slice_id"]) != want_slice:
                raise AssertionError(
                    f"worker {p} mapped to slice {int(got['slice_id'])}, "
                    f"expected {want_slice}")
            if not np.array_equal(got["losses"], ref):
                raise AssertionError(
                    f"worker {p} loss series diverges from rank 0 — the "
                    f"cross-slice sync is broken")
        if len(jax.devices()) < total:
            raise RuntimeError(
                f"multislice dryrun needs {total} local devices for the "
                f"lint-context leg, have {len(jax.devices())}")
        ff_lint = _build_multislice(total, num_slices)
        _lint_per_slice_hlo(trace_dir, num_processes, num_slices, ff_lint)
        summary["lint"] = "ok"

        # ---- phase B: kill one slice mid-epoch --------------------------
        from flexflow_tpu.ckpt.faults import KILL_EXIT
        env = _worker_env(trace_dir=None)
        env["FFS_FAULT"] = f"kill_host:{kill_rank}@step:{kill_step}"
        outs_b = [os.path.join(td, f"fault{p}.npz")
                  for p in range(num_processes)]
        rcs = _spawn("multislice_worker_main", num_processes,
                     devices_per_proc, outs_b,
                     [ckpt_dir, num_slices, steps, every], env, timeout,
                     tolerate_failures=True)
        if rcs[kill_rank] != KILL_EXIT:
            raise AssertionError(
                f"fault leg: rank {kill_rank} was meant to die with exit "
                f"{KILL_EXIT} at step {kill_step}, got exit codes {rcs}")
        from flexflow_tpu.ckpt import latest_complete, verify_step_dir
        latest = latest_complete(ckpt_dir)
        if latest is None:
            raise AssertionError(
                "fault leg left no complete checkpoint")
        resume_step, step_dir = latest
        rep = verify_step_dir(step_dir)
        if not rep["complete"]:
            raise AssertionError(
                f"latest checkpoint fails deep verification: "
                f"{rep['errors']}")
        summary["resume_step"] = resume_step

        # ---- phase C: slice-loss resume on the survivors ----------------
        from flexflow_tpu.ckpt import load_manifest, plan_resume
        manifest = load_manifest(step_dir)
        if int(manifest.get("mesh", {}).get("slice", 0)) != num_slices:
            raise AssertionError(
                f"checkpoint manifest does not record the slice axis: "
                f"{manifest.get('mesh')}")
        n_survive = total - total // num_slices
        plan = plan_resume(manifest, n_survive)
        if plan.get("topology") != "slice_loss":
            raise AssertionError(
                f"plan_resume did not classify losing a slice "
                f"({n_survive}/{total} devices): {plan}")
        if (plan["lost_slices"] != 1
                or plan["surviving_slices"] != num_slices - 1
                or plan["slices"] != num_slices - 1):
            raise AssertionError(f"slice_loss plan malformed: {plan}")
        if len(jax.devices()) < n_survive:
            raise RuntimeError(
                f"multislice dryrun needs {n_survive} local devices for "
                f"the resume leg, have {len(jax.devices())}")
        from flexflow_tpu.config import FFConfig
        from flexflow_tpu.ffconst import LossType
        from flexflow_tpu.machine import make_mesh
        from flexflow_tpu.models.transformer import create_transformer
        from flexflow_tpu.optimizers import SGDOptimizer
        from flexflow_tpu.search.native import available as _native_ok
        cfg = _model_config(total)
        budget = 6 if _native_ok() else 0
        c_small = FFConfig(batch_size=cfg.batch_size,
                           workers_per_node=n_survive,
                           search_budget=budget)
        c_small.slices = plan["slices"] if plan["slices"] > 1 else 1
        ff_small = create_transformer(cfg, c_small)
        ff_small.compile(SGDOptimizer(lr=0.05),
                         LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [],
                         mesh=None if budget else make_mesh(
                             n_survive, {"data": n_survive}))
        it = ff_small.load_checkpoint(step_dir)
        if it != resume_step:
            raise AssertionError(
                f"slice-loss load restored iteration {it}, expected "
                f"{resume_step}")
        x, y = _global_batch(cfg)
        cont = _elastic_train_loop(ff_small, x, y, resume_step, steps)
        if not np.all(np.isfinite(cont)):
            raise AssertionError(
                f"slice-loss resume produced non-finite losses: {cont}")
        if not np.allclose(cont, ref[resume_step:], rtol=1e-3, atol=1e-5):
            raise AssertionError(
                f"slice-loss resumed losses diverged beyond reduction-"
                f"order tolerance\n  resumed {cont}\n  "
                f"expected {ref[resume_step:]}")
        summary["surviving_mesh"] = dict(zip(ff_small.mesh.axis_names,
                                             ff_small.mesh.devices.shape))
        summary["researched"] = bool(budget)
    print(f"multislice dryrun ok: {num_slices} slices x "
          f"{procs_per_slice} processes, lint FFL501/502/503 clean; "
          f"killed slice {num_slices - 1} at step {kill_step}, "
          f"plan_resume classified slice_loss, survivors "
          f"{summary['surviving_mesh']} "
          f"({'re-searched' if summary['researched'] else 'heuristic'} "
          f"strategy) resumed from iteration {summary['resume_step']} "
          f"within tolerance")
    return summary


def run_dryrun(num_processes: int = 2, devices_per_proc: int = 2,
               timeout: int = 600,
               trace_dir: Optional[str] = None,
               profile_steps: Optional[str] = None) -> None:
    """Spawn the workers, train, and assert parity with a single-process
    run on the same global batch. Raises on any mismatch.

    The calling process must have >= num_processes * devices_per_proc
    JAX devices for the single-process reference leg. ``trace_dir``
    turns on per-host step tracing in every worker; after the workers
    exit their per-host Chrome traces are merged into one
    ``merged.trace.json`` keyed by host id (pid = host in Perfetto).
    ``profile_steps`` (with ``trace_dir``) additionally captures each
    worker's device trace over that step window, so the merged timeline
    shows every host's device compute/comms lanes on the shared
    wall-clock epoch."""
    import jax

    total = num_processes * devices_per_proc
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = _free_port()
    with tempfile.TemporaryDirectory() as td:
        outs = [os.path.join(td, f"worker{p}.npz")
                for p in range(num_processes)]
        procs = []
        env = dict(os.environ)
        env["FFS_MP_CHILD"] = "1"
        env.pop("JAX_PLATFORMS", None)
        if trace_dir:
            env["FFS_TRACE_DIR"] = trace_dir
        else:
            env.pop("FFS_TRACE_DIR", None)
        if trace_dir and profile_steps:
            env["FFS_PROFILE_STEPS"] = profile_steps
        else:
            env.pop("FFS_PROFILE_STEPS", None)
        # the per-process backend is configured inside worker_main via
        # jax config, not through the parent's environment
        env.pop("XLA_FLAGS", None)
        try:
            for p in range(num_processes):
                code = (
                    "import sys; sys.path.insert(0, %r); "
                    "from flexflow_tpu.multihost_dryrun import worker_main; "
                    "worker_main(%d, %d, %d, %d, %r)"
                    % (repo, p, num_processes, port, devices_per_proc,
                       outs[p])
                )
                procs.append(subprocess.Popen([sys.executable, "-c", code],
                                              cwd=repo, env=env))
            rcs = [proc.wait(timeout=timeout) for proc in procs]
        finally:
            # a worker that died pre-rendezvous leaves its peer blocked in
            # jax.distributed.initialize — never orphan it
            for proc in procs:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
        if any(rc != 0 for rc in rcs):
            raise RuntimeError(
                f"multihost dryrun: worker exit codes {rcs}")
        worker_results = [dict(np.load(o)) for o in outs]

    if trace_dir:
        from flexflow_tpu.obs import merge_host_traces
        merged = merge_host_traces(trace_dir)
        if merged:
            print(f"multihost dryrun: merged per-host traces -> {merged}")

    # single-process references on the same global batch
    if len(jax.devices()) < total:
        raise RuntimeError(
            f"multihost dryrun needs {total} local devices for the "
            f"reference leg, have {len(jax.devices())}")
    legs = ["dp"] + (["tp", "ring"] if _multi_axis_legs_possible(total) else [])
    refs = {}
    dp_extra = {}
    dp_model = None
    for leg in legs:
        ref, rx, ry = _build_and_train(total, leg=leg)
        if leg == "dp":
            dp_model = ref
            dp_extra["eval_loss"] = float(ref.evaluate(rx, ry)["loss"])
            dp_extra["predict"] = ref.predict(rx)
        refs[leg] = (_params_to_numpy(ref), float(ref._last_loss))

    if trace_dir:
        # the fflint FFL501/502 static deadlock pass, end-to-end: compare
        # the per-host optimized-HLO collective sequences every worker
        # dumped. A host-dependent divergence here is the bug class that
        # otherwise only shows as a wall-clock timeout on a real pod.
        _lint_per_host_hlo(trace_dir, num_processes, dp_model)

    loss_keys = {"dp": "loss", "tp": "tp_loss", "ring": "ring_loss"}
    for p, got in enumerate(worker_results):
        for leg in legs:
            loss_key = loss_keys[leg]
            ref_params, ref_loss = refs[leg]
            got_loss = float(got.pop(loss_key))
            if not np.isfinite(got_loss) or abs(got_loss - ref_loss) > \
                    1e-4 * (1.0 + abs(ref_loss)):
                raise AssertionError(
                    f"worker {p} {leg} loss {got_loss} != reference "
                    f"{ref_loss}")
            leg_params = {k[len(leg) + 1:]: v for k, v in got.items()
                          if k.startswith(f"{leg}/")}
            missing = set(ref_params) - set(leg_params)
            if missing:
                raise AssertionError(
                    f"worker {p} {leg} missing params: {missing}")
            for k, rv in ref_params.items():
                if not np.allclose(leg_params[k], rv, rtol=1e-4,
                                   atol=1e-5):
                    diff = float(np.max(np.abs(leg_params[k] - rv)))
                    raise AssertionError(
                        f"worker {p} {leg} param {k} diverged from "
                        f"single-process reference (max abs diff {diff})")
        if "tp" in refs and "ckpt_roundtrip_ok" not in got:
            raise AssertionError(
                f"worker {p} skipped the cross-host checkpoint roundtrip")
        # evaluate/predict parity vs the single-process reference
        if abs(float(got["eval_loss"]) - dp_extra["eval_loss"]) > 1e-4 * (
                1.0 + abs(dp_extra["eval_loss"])):
            raise AssertionError(
                f"worker {p} evaluate loss {float(got['eval_loss'])} != "
                f"reference {dp_extra['eval_loss']}")
        if not np.allclose(got["predict"], dp_extra["predict"], rtol=1e-4,
                           atol=1e-5):
            raise AssertionError(f"worker {p} predict diverged")
    names = {"dp": "data-parallel", "tp": "cross-host tensor-parallel",
             "ring": "cross-host ring attention"}
    legs_txt = " + ".join(names[leg] for leg in refs)
    if "tp" in refs:
        legs_txt += " + checkpoint roundtrip"
    losses = ", ".join(f"{leg} loss {refs[leg][1]:.6f}" for leg in refs)
    print(f"multihost dryrun ok: {num_processes} processes x "
          f"{devices_per_proc} devices; {legs_txt} "
          f"match single-process ({losses})")
