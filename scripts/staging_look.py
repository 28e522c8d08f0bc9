#!/usr/bin/env python3
"""The hand look behind `FFModel._shard_batch`'s transfer form (PR 26).

On the chip, for a batch shape, each candidate form of handing a float32
numpy batch to the runtime is timed from the call to the staged array
being ready with nothing else in flight, then traced once: what the
runtime's host threads do before the DMA (`XlaLinearize`, `Transpose`,
...) on the `/host:CPU` plane, and what the device runs to produce the
shaped bf16 array. Prints one JSON line a form and writes them to
`chiprun_out/staging_look_<shape>.json`. Nothing here is a benchmark metric.

    python scripts/staging_look.py --shape 256,3,299,299
"""

import argparse
import collections
import json
import os
import statistics
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def forms(shape, sharding):
    """name -> stage(x_np) -> staged bf16 array of `shape` on `sharding`."""
    import types
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from flexflow_tpu import model as ffmodel

    flat = NamedSharding(sharding.mesh, P(*sharding.spec[:1]))

    def unpack(raw):
        return raw.reshape(shape).astype(jnp.bfloat16)

    unpack = jax.jit(unpack, out_shardings=sharding, donate_argnums=0)
    concat = jax.jit(lambda *rows: jnp.concatenate(rows),
                     out_shardings=sharding)

    def parent(x):      # `_shard_batch` before PR 26
        return jax.device_put(jnp.asarray(x).astype(jnp.bfloat16), sharding)

    def flat1d(x):
        return unpack(jax.device_put(x.reshape(-1), flat))

    def rows2d(x):
        return unpack(jax.device_put(x.reshape(x.shape[0], -1), sharding))

    def shaped_x16(x):  # the shaped array in row chunks: is 1-D needed?
        rows = x.shape[0] // 16
        return concat(*(jax.device_put(x[i * rows:(i + 1) * rows], sharding)
                        .astype(jnp.bfloat16) for i in range(16)))

    class Stager(ffmodel.FFModel):
        """`FFModel`'s own staging, without a graph."""

        def __init__(self):
            self.executor = types.SimpleNamespace(
                batch_sharding=lambda: sharding,
                compute_dtype=jnp.bfloat16)
            self._unpackers = {}

    def model(piece_mib):
        stager = Stager()

        def stage(x):
            ffmodel._RAW_PIECE_BYTES = piece_mib << 20  # read at first use
            return stager._shard_batch(x, cast=True, inputs=True)
        return stage

    out = dict(parent=parent, flat1d=flat1d, rows2d=rows2d)
    if len(sharding.mesh.devices.flat) == 1:
        out["shaped_x16"] = shaped_x16
    for mib in (64, 32, 16, 8, 4):
        out[f"model_{mib}MiB_pieces"] = model(mib)
    return out


def host_events(xplane):
    """Per event name on the `/host:CPU` plane: total ms, count, threads,
    longest ms; and the device's programs with their ms."""
    from jax.profiler import ProfileData
    host = collections.defaultdict(lambda: [0.0, 0, set(), 0.0])
    programs = collections.defaultdict(list)
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    h = host[ev.name]
                    ms = ev.duration_ns * 1e-6
                    h[0] += ms
                    h[1] += 1
                    h[2].add(line.name)
                    h[3] = max(h[3], ms)
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        programs[ev.name.split("(")[0]].append(
                            round(ev.duration_ns * 1e-6, 3))
    top = sorted(host.items(), key=lambda kv: -kv[1][0])[:14]
    return ({n: dict(total_ms=round(h[0], 2), n=h[1], threads=len(h[2]),
                     longest_ms=round(h[3], 2)) for n, h in top},
            dict(programs))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="256,3,299,299")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    shape = tuple(int(s) for s in args.shape.split(","))

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from flexflow_tpu.machine import make_mesh
    from flexflow_tpu.obs.session import (newest_xplane, start_profiler,
                                          stop_profiler)

    dev = jax.devices()
    if dev[0].platform != "tpu":
        sys.exit("staging_look.py reads a TPU's runtime; no TPU here")
    mesh = make_mesh(len(dev), {"data": len(dev)})
    sharding = NamedSharding(mesh, P(("data",)))
    rng = np.random.default_rng(26)
    data = rng.standard_normal((4 * shape[0],) + shape[1:], dtype=np.float32)
    batches = [data[i * shape[0]:(i + 1) * shape[0]] for i in range(4)]
    want = None
    lines = []
    for name, stage in forms(shape, sharding).items():
        if args.only and name not in args.only.split(","):
            continue
        got = jax.block_until_ready(stage(batches[0]))      # compiles
        bits = np.asarray(got).view(np.uint16)
        if want is None:
            want = bits
        enqueue, ready, first_of_4, all_4 = [], [], [], []
        for r in range(args.reps):
            time.sleep(0.05)
            t0 = time.perf_counter()
            got = stage(batches[r % 4])
            t1 = time.perf_counter()
            jax.block_until_ready(got)
            ready.append((time.perf_counter() - t0) * 1e3)
            enqueue.append((t1 - t0) * 1e3)
        for r in range(max(args.reps // 2, 2)):     # as `fit` runs ahead
            time.sleep(0.05)
            t0 = time.perf_counter()
            staged = [stage(b) for b in batches]
            jax.block_until_ready(staged[0])
            first_of_4.append((time.perf_counter() - t0) * 1e3)
            jax.block_until_ready(staged)
            all_4.append((time.perf_counter() - t0) * 1e3)
            del staged
        with tempfile.TemporaryDirectory() as d:
            start_profiler(d)       # Python tracer off
            for r in range(3):
                jax.block_until_ready(stage(batches[r]))
                time.sleep(0.05)
            stop_profiler()
            host, programs = host_events(newest_xplane(d))
        line = dict(
            form=name, shape=shape, device=dev[0].device_kind, chips=len(dev),
            bitwise_equal_parent=bool(np.array_equal(bits, want)),
            layout=str(getattr(got, "format", "")),
            enqueue_ms=[round(v, 2) for v in enqueue],
            ready_ms=[round(v, 2) for v in ready],
            ready_ms_median=round(statistics.median(ready), 2),
            first_of_4_ms=[round(v, 2) for v in first_of_4],
            all_4_ms=[round(v, 2) for v in all_4],
            traced_3_stagings=dict(host=host, device_programs_ms=programs))
        print(json.dumps(line), flush=True)
        lines.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/staging_look_{args.shape.replace(',', 'x')}.json",
              "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
