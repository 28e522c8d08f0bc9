#!/usr/bin/env python3
"""The hand look behind how the expert layers' grouped products walk their
operands (PR 53).

On the chip, at the shapes of the six cells that run `MoELayer`, with
routed group sizes (`moe_combine_lab.draw_experts`: sdar's with a quarter
of the pairs on the mask token's experts), each of a layer's six products
alone (`up`: rows x [d, f], which a gated layer runs twice; `down`: rows x
[f, d]; their two `d lhs`, the same matrices transposed; the two `tgmm`,
the weights' gradients), 5 calls under the profiler, the device's own
time of the kernel (`ms`) and of its program (`program_ms`: the walk's
small XLA ops with it):

- `parent`: the megablox kernels that ship with JAX under the tiling the
  layer used until PR 53 (`parent_tiling`: the row tile 128 in every
  cell, the contraction in 1024s), over the buffer of then;
- `chosen`: what `flexflow_tpu.ops.moe.grouped_matmul` runs now
  (`moe._gmm_tiling` over `experts.buffer_rows`);
- the candidates, over the buffer rounded up to 512 rows: the same stock
  kernels with the contraction whole at row tiles of 128 / 256 / 512 and
  two output tiles (`stock.*`), and kernels of our own, kept HERE
  (`own.*`, `own_gmm` / `own_tgmm` below: the contraction always whole and
  no accumulator, masks on a group's boundary tiles alone, 100 MiB of
  VMEM). They were measured and not taken: within 1% of the stock
  kernels at the same tiles in five cells and slower in nemotron's.

Beside each: grid steps (visits x n tiles x k tiles, the visits counted
from the group sizes: a tile in which a group starts is visited once
more), bytes a grid step, and the share of the peak (the FLOPs of the
rows that hold a pair over `ms` at 197 TFLOP/s). The first table sums the
parent's products a step, to be held against the ledger's `gmm` / `tgmm`
before any candidate is believed. A variant the compiler refuses (VMEM)
is listed as refused. One JSON line a cell; all of it in
`chiprun_out/gmm_lab.json`, the tables in `chiprun_out/gmm_lab.md`.
With `--deviceless` nothing runs: each variant is compiled for a
described v5e. Nothing here is a benchmark metric.

    python scripts/gmm_lab.py [--deviceless] [--cell lfm2] [--only own]
"""

import argparse
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# tokens, k, experts, held, hidden d, expert width f, expert layers;
# `gmm` / `tgmm`: the ledger's ms a step (PR 52's lines)
SHAPES = {
    "lfm2_8b_a1b.s16384_b1": dict(
        T=16384, k=4, E=32, held=8, d=2048, f=1792, gated=True, layers=4,
        gmm=35.39, tgmm=11.35),
    "sdar_30b_a3b.s8192_b1": dict(
        T=16384, k=8, E=128, held=16, d=2048, f=768, gated=True, layers=4,
        mask_share=0.25, gmm=12.77, tgmm=5.55),
    "smallthinker_21b_a3b.s16384_b1": dict(
        T=16384, k=6, E=64, held=8, d=2560, f=768, gated=True, layers=4,
        gmm=11.08, tgmm=5.51),
    "nemotron3_nano_30b_a3b.s8192_b1": dict(
        T=8192, k=6, E=128, held=8, d=2688, f=1856, gated=False, layers=4,
        gmm=6.60, tgmm=3.15),
    "laguna_xs2.s8192_b1": dict(
        T=8192, k=8, E=256, held=16, d=2048, f=512, gated=True, layers=4,
        gmm=2.52, tgmm=1.61),
    "joyai_llm_flash.s4096_b1": dict(
        T=4096, k=8, E=256, held=8, d=2048, f=768, gated=True, layers=5,
        gmm=1.24, tgmm=0.84),
}
PEAK_FLOPS = 197e12       # v5e, bf16, as benchmarks/peaks.json has it


def parent_tiling(m, k, n):
    """`_gmm_tiling` as it stood until PR 53."""
    def tile(dim):
        if dim <= 1024:
            return dim
        return next((t for t in range(1024, 127, -128) if dim % t == 0),
                    1024)

    return next(t for t in (512, 256, 128) if m % t == 0), tile(k), tile(n)


def products(s):
    """name -> (kind, times a layer runs it, contraction, output width):
    `gmm` kinds are rows [m, K] x [g, K, N]; with `t` the matrix lies
    [g, N, K]; `tgmm` is [m, K]^T [m, N] a group."""
    d, f = s["d"], s["f"]
    twice = 2 if s["gated"] else 1
    return {
        "up": ("gmm", twice, d, f), "down": ("gmm", 1, f, d),
        "up.dlhs": ("gmm.t", twice, f, d), "down.dlhs": ("gmm.t", 1, d, f),
        "up.tgmm": ("tgmm", twice, d, f), "down.tgmm": ("tgmm", 1, f, d),
    }


# ---------------------------------------------------------------------------
# tried against the stock kernels: kernels of our own (measured, not taken)

_NT = (((1,), (1,)), ((), ()))  # a[m, c] . b[n, c] -> [m, n]
_NN = (((1,), (0,)), ((), ()))  # a[m, c] . b[c, n] -> [m, n]


def _product(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


_OWN_COMPILER_PARAMS = dict(vmem_limit_bytes=100 << 20)


def grouped_visits(group_sizes, m: int, tm: int, visit_empty: bool):
    """The walk of a grouped product over [m, ..] rows in tiles of `tm`:
    group_sizes [g] int32 (sum <= m) -> dict of `bounds` [g + 1] (the
    row a group starts at; the last: where the groups end), `group` and
    `tile` [m / tm + g - 1] int32 (the group and the row tile of each
    visit: a group's tiles in order, group after group, so that what is
    indexed by the group stays where it is over the group's visits and
    a row tile's visits follow each other) and `count` [] int32, the
    visits that are real (the kernels' grid runs that far and no
    further: the rows past the groups' sum are not visited). A group
    with no row has no visit unless `visit_empty` (it then gets one, of
    some tile: `tgmm` has to write its zeros)."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    count = jnp.where(group_sizes > 0, -(-ends // tm) - first,
                      int(visit_empty))
    last = jnp.cumsum(count)
    visit = jnp.arange(m // tm + g - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(last, visit, side="right", method="compare_all"),
        g - 1)
    tile = first[group] + jnp.clip(visit - (last - count)[group], 0,
                                   jnp.maximum(count[group] - 1, 0))
    return dict(bounds=jnp.concatenate([starts[:1] * 0, ends]).astype(
                    jnp.int32),
                group=group.astype(jnp.int32),
                tile=jnp.minimum(tile, m // tm - 1).astype(jnp.int32),
                count=last[-1].astype(jnp.int32))


def _rows_of_group(bounds_ref, group_ref, tile_ref, visit, shape):
    """(whether every row of this visit's tile is its group's, whether
    none is, the [tm, width] mask of those that are)."""
    group = group_ref[visit]
    start, end = bounds_ref[group], bounds_ref[group + 1]
    row0 = tile_ref[visit] * shape[0]

    def mine():
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        return (row >= start) & (row < end)

    return (start <= row0) & (row0 + shape[0] <= end), end == start, mine


def _gmm_kernel(bounds_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
                *, transpose_rhs: bool):
    """One visit: the tile's rows times the group's [k, tn] (or, with
    `transpose_rhs`, [tn, k]) panel, the contraction whole: no
    accumulator. A tile that is all one group's is stored as it comes;
    in one a group shares, the group's rows alone are written over what
    the tile's earlier visits left."""
    whole, _, mine = _rows_of_group(bounds_ref, group_ref, tile_ref,
                                    pl.program_id(1), out_ref.shape)

    def product():
        return _product(lhs_ref[...], rhs_ref[...], _NT if transpose_rhs else _NN)

    @pl.when(whole)
    def _():
        out_ref[...] = product().astype(out_ref.dtype)

    @pl.when(~whole)
    def _():
        out_ref[...] = jnp.where(
            mine(), product(), out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


def own_gmm(lhs, rhs, group_sizes, tm: int, tn: int, transpose_rhs: bool,
        interpret: bool):
    """lhs [m, k] rows sorted by group, rhs [g, k, n] (with
    `transpose_rhs` [g, n, k]), group_sizes [g] int32 (sum <= m) ->
    [m, n] in lhs' dtype: rows of group i times rhs[i], float32
    products, rounded once. Rows past the groups' sum are NOT written.
    m a multiple of `tm`; `tn` n or a multiple of 128 (a ragged last
    tile is fine).

    Grid (n tiles, visits of `grouped_visits`), the contraction whole:
    the group's [k, tn] panel has ONE block index over the group's
    visits, so it is fetched from HBM once a (group, n tile) and not
    once a row tile; the rows are read once an n tile."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    walk = grouped_visits(group_sizes, m, tm, visit_empty=False)
    panel = (None, tn, k) if transpose_rhs else (None, k, tn)

    def of_group(j, v, bounds, group, tile):
        return (group[v], j, 0) if transpose_rhs else (group[v], 0, j)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        name="gmm",
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), walk["count"]),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, v, bounds, group, tile: (tile[v], 0)),
                pl.BlockSpec(panel, of_group)],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, bounds, group, tile: (tile[v], j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            **_OWN_COMPILER_PARAMS),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize * (
                m * k * pl.cdiv(n, tn) + rhs.size + m * n)),
        interpret=interpret,
    )(walk["bounds"], walk["group"], walk["tile"], lhs, rhs)


def _tgmm_kernel(bounds_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
                 acc_ref):
    """One visit: the tile's rows of `lhs`, transposed, times its rows of
    `rhs`, added into the group's float32 [tk, tn] accumulator, which is
    rounded into the group's block when the group's visits end. Only a
    tile the group shares is masked."""
    v = pl.program_id(2)
    last = pl.num_programs(2) - 1
    group = group_ref[v]
    whole, empty, mine = _rows_of_group(bounds_ref, group_ref, tile_ref, v,
                                        lhs_ref.shape)

    @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != group))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(whole)
    def _():
        acc_ref[...] += _product(lhs_ref[...].T, rhs_ref[...], _NN)

    @pl.when(~whole & ~empty)
    def _():
        lhs = jnp.where(mine(), lhs_ref[...].astype(jnp.float32), 0.0)
        acc_ref[...] += _product(lhs.astype(lhs_ref.dtype).T, rhs_ref[...], _NN)

    @pl.when((v == last) | (group_ref[jnp.minimum(v + 1, last)] != group))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def own_tgmm(lhs, rhs, group_sizes, tm: int, tk: int, tn: int, interpret: bool):
    """lhs [m, k], rhs [m, n], rows sorted by group, group_sizes [g]
    int32 (sum <= m) -> [g, k, n] in rhs' dtype: out[i] = lhs_i^T rhs_i
    over group i's rows, float32 products and sums, rounded once; zeros
    for a group with no row. m a multiple of `tm`; `tk`, `tn` k, n or
    multiples of 128 (a ragged last tile is fine).

    Grid (n tiles, k tiles, visits): the group's [tk, tn] block and its
    accumulator stay over the group's visits. The rows of `lhs` outside
    the group are zeroed in a tile the group shares (those of `rhs` then
    meet zeros: they have to be finite)."""
    m, k = lhs.shape
    n = rhs.shape[1]
    g = group_sizes.shape[0]
    walk = grouped_visits(group_sizes, m, tm, visit_empty=True)
    return pl.pallas_call(
        _tgmm_kernel,
        name="tgmm",
        out_shape=jax.ShapeDtypeStruct((g, k, n), rhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), pl.cdiv(k, tk), walk["count"]),
            in_specs=[
                pl.BlockSpec(
                    (tm, tk),
                    lambda j, i, v, bounds, group, tile: (tile[v], i)),
                pl.BlockSpec(
                    (tm, tn),
                    lambda j, i, v, bounds, group, tile: (tile[v], j))],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda j, i, v, bounds, group, tile: (group[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            **_OWN_COMPILER_PARAMS),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize * (
                m * k * pl.cdiv(n, tn) + m * n * pl.cdiv(k, tk)
                + g * k * n)),
        interpret=interpret,
    )(walk["bounds"], walk["group"], walk["tile"], lhs, rhs)


# ---------------------------------------------------------------------------


def variants(kind, m, m512, m_now, groups, K, N):
    """name -> (rows of the buffer, function of (lhs, rhs, sizes), (tm,
    tk, tn)) for one product; `m` the parent's buffer, `m512` the
    candidates', `m_now` what `MoELayer.buffer_rows` gives."""
    from flexflow_tpu.ops import moe

    stock = moe._megablox()
    t = kind == "gmm.t"
    _, tk0, tn0 = parent_tiling(m, K, N)

    def stock_gmm(tiling):
        return lambda lhs, rhs, sizes: stock.gmm(
            lhs, rhs, sizes, lhs.dtype, tiling, transpose_rhs=t)

    def stock_tgmm(tiling):
        return lambda lhs, rhs, sizes: stock.tgmm(
            lhs.swapaxes(0, 1), rhs, sizes, rhs.dtype, tiling,
            num_actual_groups=groups)

    def ours(tm, tn):
        return lambda lhs, rhs, sizes: own_gmm(
            lhs, rhs, sizes, tm, tn, t, False)

    def ours_t(tm, tk, tn):
        return lambda lhs, rhs, sizes: own_tgmm(
            lhs, rhs, sizes, tm, tk, tn, False)

    out = {}
    if kind == "tgmm":
        out["parent"] = (m, stock_tgmm(parent_tiling(m, K, N)),
                         parent_tiling(m, K, N))
        for tm in (256, 512):
            out[f"stock.tm{tm}"] = (m512, stock_tgmm((tm, tk0, tn0)),
                                    (tm, tk0, tn0))
        for tm in (128, 256, 512):
            out[f"own.tm{tm}"] = (m512, ours_t(tm, tk0, tn0),
                                  (tm, tk0, tn0))
        chosen = moe._gmm_tiling(m_now, groups, K, N, transposed=True)
        out["chosen"] = (m_now, stock_tgmm(chosen), chosen)
        return out
    out["parent"] = (m, stock_gmm(parent_tiling(m, K, N)),
                     parent_tiling(m, K, N))
    for tm in (128, 256, 512):
        for tn in sorted({tn0, min(512, tn0)}, reverse=True):
            out[f"stock.tm{tm}.tn{tn}"] = (m512, stock_gmm((tm, K, tn)),
                                           (tm, K, tn))
            out[f"own.tm{tm}.tn{tn}"] = (m512, ours(tm, tn), (tm, K, tn))
    chosen = moe._gmm_tiling(m_now, groups, K, N)
    out["chosen"] = (m_now, stock_gmm(chosen), chosen)
    return out


def visits(sizes, tm, visit_empty):
    """Row-tile visits of a walk over groups of these sizes."""
    n, at = 0, 0
    for size in sizes:
        if size:
            n += -(-(at + size) // tm) - at // tm
        elif visit_empty:
            n += 1
        at += size
    return n


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--deviceless", action="store_true")
    ap.add_argument("--cell", default="", help="cells whose name holds this")
    ap.add_argument("--only", default="",
                    help="variants whose name holds one of these (a,b)")
    opts = ap.parse_args()
    if opts.deviceless:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import numpy as np
    from flexflow_tpu.ops import experts, moe
    from moe_combine_lab import device_ms, draw_experts

    if opts.deviceless:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.devices()[0].platform != "tpu":
        sys.exit("gmm_lab: no TPU here (try --deviceless)")

    wanted = [w for w in opts.only.split(",") if w]
    out, tables = {}, []
    for cell, s in SHAPES.items():
        if opts.cell not in cell:
            continue
        groups = s["held"]
        pairs = s["T"] * s["k"]
        m = -(-(int(pairs * groups / s["E"] * 1.5) + 1) // 128) * 128
        m512 = -(-m // 512) * 512   # the candidates' buffer
        m_now = experts.buffer_rows(pairs, groups, s["E"], 0.5)
        sizes = moe.route_held_experts(
            draw_experts(s, jax.random.PRNGKey(0)), groups, 0, m
        )["group_sizes"]
        host_sizes = [int(v) for v in np.asarray(sizes)]
        held = sum(host_sizes)
        line = dict(cell=cell, rows=m, rows_now=m_now, rows_held=held, group_sizes=host_sizes,
                    device=("deviceless v5e" if opts.deviceless
                            else jax.devices()[0].device_kind))
        jitted, meta = {}, {}
        keys = jax.random.split(jax.random.PRNGKey(1), 3)
        for product, (kind, times, K, N) in products(s).items():
            for name, (rows, fn, tiling) in variants(
                    kind, m, m512, m_now, groups, K, N).items():
                if wanted and not any(w in name for w in wanted):
                    continue
                lhs = jax.ShapeDtypeStruct((rows, K), jnp.bfloat16)
                rhs = jax.ShapeDtypeStruct(
                    (rows, N) if kind == "tgmm"
                    else (groups, N, K) if kind == "gmm.t"
                    else (groups, K, N), jnp.bfloat16)
                tm, tk, tn = tiling
                steps = (visits(host_sizes, tm, kind == "tgmm")
                         * -(-N // tn) * -(-K // tk))
                fetched = tm * tk + tm * tn + (
                    tk * tn if kind != "tgmm" and tk < K else 0)
                key = f"{product}/{name}"
                meta[key] = dict(
                    rows=rows, tiling=list(tiling), grid_steps=steps,
                    bytes_a_step=2 * fetched, times_a_layer=times,
                    flops=2 * held * K * N)
                before = meta.get(f"{product}/parent", {})
                if name == "chosen" and (rows, list(tiling)) == (
                        before.get("rows"), before.get("tiling")):
                    # the parent's program: one executable, one timing
                    meta[key]["as"] = f"{product}/parent"
                    continue
                fn.__name__ = "p%03d" % len(meta)
                try:
                    if opts.deviceless:
                        jax.jit(fn).lower(
                            *(jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=chip)
                              for a in (lhs, rhs, sizes))).compile()
                        meta[key]["compiled"] = True
                        continue
                    args = [
                        jax.random.normal(keys[0], lhs.shape, lhs.dtype),
                        jax.random.normal(keys[1], rhs.shape, rhs.dtype),
                        sizes]
                    jit = jax.jit(fn)
                    jax.block_until_ready(jit(*args))
                    jitted[key] = (jit, args)
                except Exception as e:    # the compiler's refusal, kept
                    meta[key]["refused"] = str(e).strip().split("\n")[0][
                        :160]
        for key, (ms, ops) in (device_ms(jitted, stems=4)
                               if jitted else {}).items():
            kernel = sum(v for k, v in ops.items() if "gmm" in k)
            meta[key].update(
                ms=round(kernel, 4), program_ms=round(ms, 4),
                peak_share_pct=round(
                    100 * meta[key]["flops"] / (kernel * 1e-3) / PEAK_FLOPS,
                    1) if kernel else None)
        for v in meta.values():
            v.update({k: meta[v["as"]][k] for k in (
                "ms", "program_ms", "peak_share_pct", "compiled", "refused")
                if "as" in v and k in meta[v["as"]]})
        line["products"] = meta
        tables.append(table(cell, s, meta))
        print(json.dumps({k: v for k, v in line.items() if k != "products"}),
              flush=True)
        print(tables[-1], flush=True)
        out[cell] = line
    os.makedirs("chiprun_out", exist_ok=True)
    stem = "chiprun_out/gmm_lab" + (".deviceless" if opts.deviceless else "")
    with open(stem + ".json", "w") as f:
        json.dump(out, f, indent=1)
    with open(stem + ".md", "w") as f:
        f.write("\n\n".join(tables) + "\n")


def table(cell, s, meta):
    """The cell's variants by product, and the parent's and the chosen
    tiling's `gmm` / `tgmm` ms a step beside the ledger's."""
    rows = [f"### {cell}", "",
            "| product / variant | (tm, tk, tn) | ms | program ms | grid steps "
            "| bytes a step | % of peak |", "| --- | --- | --- | --- | --- | "
            "--- | --- |"]
    for key, v in meta.items():
        rows.append(
            f"| {key} | {tuple(v['tiling'])} | "
            + (f"refused: {v['refused']} | | " if "refused" in v
               else "compiled | | " if "compiled" in v
               else f"{v.get('ms')} | {v.get('program_ms')} | ")
            + f"{v['grid_steps']} | {v['bytes_a_step']} | "
              f"{v.get('peak_share_pct', '')} |")
    for which in ("parent", "chosen"):
        sums = {"gmm": 0.0, "tgmm": 0.0}
        for key, v in meta.items():
            if key.endswith("/" + which) and v.get("ms"):
                sums["tgmm" if "tgmm" in key else "gmm"] += (
                    v["ms"] * v["times_a_layer"] * s["layers"])
        if any(sums.values()):
            rows.append("")
            rows.append(
                f"`{which}` a step ({s['layers']} layers): gmm "
                f"{sums['gmm']:.2f} ms (ledger {s['gmm']}), tgmm "
                f"{sums['tgmm']:.2f} ms (ledger {s['tgmm']})")
    return "\n".join(rows)


if __name__ == "__main__":
    main()
