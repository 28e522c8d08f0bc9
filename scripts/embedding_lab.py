#!/usr/bin/env python3
"""The hand look behind how `Embedding` forms its table's gradient (PR 57).

On the chip, at the nine decoder cells' `(lookups, V, E)`, each piece
alone, 20 calls after a warm-up, wall time a call (`<piece>_ms`), and
then under the profiler the device's own time of the piece's program
(`<piece>_device_ms`, with its ops by stem), for a cotangent in bfloat16
(what a cell's step hands the op: the compute copy of the table is
bfloat16) and in float32:

- `scatter_add`: the transpose autodiff picks for `jnp.take`, what the
  op ran until PR 57;
- `by_kernel`: what ships (`flexflow_tpu/ops/embedding.py`
  `table_gradient`): the ids sorted with their positions, one gather of
  the cotangent's rows, the kernel `embedding_sum_rows`; and its parts
  alone (`by_kernel.sort`, `.gather`, `.kernel`);
- `tied.scatter_add` / `tied.by_kernel`: either one added to a `[V, E]`
  array given, as the step adds it to the head's dW where the table is
  also the head's (`tie_word_embeddings`).

Ids are uniform over the table's rows, as the cells draw them; with
`mask_share`, that share of the lookups read ONE row (the mask token of
a block-diffusion sample), so one table row's run spans many blocks.
`max_abs_diff` is the kernel's float32 result against the scatter-add's
on the same arrays (another order of the same float32 sums), over the
whole table, its last rows (the ragged tile where V % 128 != 0) among
them; `rows_no_id_reaches_all_zero` that the rows no id reads are exact
zeros.

Prints one JSON line a shape with `byte_floor_ms` (the cotangent read
once and the table written once in bfloat16, at the HBM's peak) and
writes them to `chiprun_out/embedding_lab.json`. Nothing here is a
benchmark metric.

    python scripts/embedding_lab.py [--only by_kernel] [--cells phi4]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from moe_combine_lab import HBM_BYTES_PER_S, device_ms, timed_ms  # noqa: E402

# lookups a step (batch x positions), table rows, width; `tied`: the
# table is also the head's
SHAPES = {
    "nemotron3_nano_30b_a3b.s8192_b1": dict(N=8192, V=16384, E=2688),
    "smallthinker_21b_a3b.s16384_b1": dict(N=16384, V=18992, E=2560),
    "sdar_30b_a3b.s8192_b1": dict(N=16384, V=18992, E=2048,
                                  mask_share=0.25),
    "joyai_llm_flash.s4096_b1": dict(N=4096, V=16160, E=2048),
    "laguna_xs2.s8192_b1": dict(N=8192, V=12544, E=2048),
    "lfm2_8b_a1b.s16384_b1": dict(N=16384, V=8192, E=2048, tied=True),
    "ouro_2_6b.s4096_b1": dict(N=4096, V=6144, E=2048),
    "phi4_mini_flash.s8192_b1": dict(N=8192, V=25008, E=2560, tied=True),
    "keye_vl2_30b_a3b.s16384_b1": dict(N=16384, V=18992, E=2048),
}


def pieces(s, dtype):
    """name -> (function, argument names): every piece takes arrays only."""
    import jax
    import jax.numpy as jnp
    from flexflow_tpu.ops import embedding

    N, V = s["N"], s["V"]

    def scatter_add(ids, g):
        return jax.vjp(lambda w: jnp.take(w, ids, axis=0),
                       jnp.zeros((V, s["E"]), dtype))[1](g)[0]

    def by_kernel(ids, g):
        return embedding.table_gradient(ids, g, V, dtype)

    def tied_scatter_add(d_w, ids, g):
        return d_w + scatter_add(ids, g)

    def tied_by_kernel(d_w, ids, g):
        return d_w + by_kernel(ids, g)

    def sort(ids):
        return embedding.ids_in_order(ids, V)

    def gather(g, at):
        return g.reshape(N, -1).at[at].get(mode="promise_in_bounds")

    def kernel(ordered, entry):
        return embedding.sum_in_id_order(ordered, entry, V, dtype)

    out = {
        "scatter_add": (scatter_add, ("ids", "g")),
        "by_kernel": (by_kernel, ("ids", "g")),
        "by_kernel.sort": (sort, ("ids",)),
        "by_kernel.gather": (gather, ("g", "at")),
        "by_kernel.kernel": (kernel, ("ordered", "entry")),
    }
    if s.get("tied"):
        out["tied.scatter_add"] = (tied_scatter_add, ("d_w", "ids", "g"))
        out["tied.by_kernel"] = (tied_by_kernel, ("d_w", "ids", "g"))
    return out


def make_arguments(s, dtype):
    import jax
    import jax.numpy as jnp
    from flexflow_tpu.ops import embedding

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    N, V, E = s["N"], s["V"], s["E"]
    ids = jax.random.randint(ks[0], (1, N), 0, V, jnp.int32)
    if s.get("mask_share"):
        ids = jnp.where(jax.random.uniform(ks[1], (1, N)) < s["mask_share"],
                        V - 1, ids)
    g = jax.random.normal(ks[2], (1, N, E), dtype)
    entry, at = embedding.ids_in_order(ids, V)
    return dict(ids=ids, g=g, entry=entry, at=at,
                ordered=g.reshape(N, E)[at],
                d_w=jax.random.normal(ks[3], (V, E), dtype))


def agreement(s, arrays):
    """The kernel's float32 result against the scatter-add's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fns = pieces(s, jnp.float32)
    got, want = (np.asarray(jax.jit(fns[name][0])(arrays["ids"], arrays["g"]))
                 for name in ("by_kernel", "scatter_add"))
    unread = np.setdiff1d(np.arange(s["V"]), np.asarray(arrays["ids"]))
    return dict(max_abs_diff=float(np.abs(got - want).max()),
                max_abs=float(np.abs(want).max()),
                last_rows_max_abs_diff=float(
                    np.abs(got[-128:] - want[-128:]).max()),
                rows_no_id_reaches=int(unread.size),
                rows_no_id_reaches_all_zero=bool(
                    (got[unread] == 0).all()))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="", help="pieces whose name holds this")
    ap.add_argument("--cells", default="", help="cells whose name holds this")
    opts = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import pallas_kernels

    if pallas_kernels.pallas_mode() == "off":
        sys.exit("embedding_lab: no TPU here")
    out = {}
    for cell, s in SHAPES.items():
        if opts.cells not in cell:
            continue
        line = dict(cell=cell, device=jax.devices()[0].device_kind, **s,
                    byte_floor_ms=round(2 * (s["N"] + s["V"]) * s["E"]
                                        / HBM_BYTES_PER_S * 1e3, 3))
        for dtype in (jnp.bfloat16, jnp.float32):
            arrays = make_arguments(s, dtype)
            if dtype == jnp.float32:
                line.update(agreement(s, arrays))
            jitted = {}
            for name, (fn, names) in pieces(s, dtype).items():
                if opts.only not in name:
                    continue
                key = f"{name}@{jnp.dtype(dtype).name}"
                jitted[key] = (jax.jit(fn), [arrays[n] for n in names])
                line[key + "_ms"] = round(timed_ms(*jitted[key]), 3)
            # (two dtypes' programs of one piece share a name: a call of
            # `device_ms` a dtype)
            for key, (ms, ops) in device_ms(jitted).items():
                line[key + "_device_ms"] = round(ms, 3)
                line[key + "_device_ops"] = ops
        print(json.dumps(line), flush=True)
        out[cell] = line
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/embedding_lab.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
