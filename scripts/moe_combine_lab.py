#!/usr/bin/env python3
"""The hand look behind how `MoELayer` brings rows back to tokens (PR 32,
PR 37, PR 49).

On the chip, at the shapes of five of the cells that run `MoELayer`, each
piece alone, 20 calls after a warm-up, wall time a call (`<piece>_ms`),
and then under the profiler the device's own time of the piece's program
(`<piece>_device_ms`, with its ops by stem):

- what the layer did until PR 32, kept HERE as the yardstick: the combine
  as a scatter-add of the buffer's rows, and the dispatch gather whose
  automatic backward is the same scatter-add;
- what ships (`flexflow_tpu/ops/moe.py`): `route_held_experts` with the
  inverse map and the rows' token order, `tokens_from_rows` in both its
  bodies, each without the routing (k row gathers and one multiply-add,
  PR 32; the rows into token order by one gather and the kernel
  `moe_sum_rows`, PR 37, and that form's parts alone: the order's sort,
  the gather, the kernel), `combine_rows` and `rows_from_tokens` forward
  and backward; `combine_rows`' backward alone in both its bodies, the
  forward's residuals given (`combine_bwd.by_gathers`: dY's rows
  gathered, PR 32; `combine_bwd.by_kernel`: the kernel `moe_spread_rows`
  over the rows in token order and the gather back, PR 49, and that
  form's parts alone: the kernel, the gather back, the sort that makes
  the order's inverse);
- forms that were tried against it: one gather of all T*k rows and a
  reduction over k; the inverse map from a cumulative count in place of
  the second sort; a form whose cost follows the buffer's rows (rows into
  token order, then megablox `tgmm` with tiles of 128 tokens as groups).

Prints one JSON line a shape, with `byte_floor_ms` (the buffer read once
in bfloat16 and [T, d] written once in float32, at the HBM's peak), and
writes them to `chiprun_out/moe_combine_lab.json`. With `--deviceless`
nothing runs: each piece is compiled for a described v5e and the line
holds the compiler's temporary bytes and the number of scatters in the
optimized HLO. Nothing here is a benchmark metric.

    python scripts/moe_combine_lab.py [--deviceless] [--only by_kernel]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# tokens, k, experts, held, width, buffer rows (`MoELayer.buffer_rows`);
# `mask_share` of the tokens choose the same k experts, as the mask token
# of a block-diffusion sample does
SHAPES = {
    "smallthinker_21b_a3b.s16384_b1": dict(T=16384, k=6, E=64, held=8,
                                           d=2560, rows=18688),
    "nemotron3_nano_30b_a3b.s8192_b1": dict(T=8192, k=6, E=128, held=8,
                                            d=2688, rows=4736),
    "sdar_30b_a3b.s8192_b1": dict(T=16384, k=8, E=128, held=16, d=2048,
                                  rows=24832, mask_share=0.25),
    "lfm2_8b_a1b.s16384_b1": dict(T=16384, k=4, E=32, held=8, d=2048,
                                  rows=24832),
    "laguna_xs2.s8192_b1": dict(T=8192, k=8, E=256, held=16, d=2048,
                                rows=6272),
}
HBM_BYTES_PER_S = 819e9   # v5e, as benchmarks/peaks.json has it


def pieces(s):
    """name -> (function, argument names): every piece takes arrays only."""
    import jax
    import jax.numpy as jnp
    from flexflow_tpu.ops import moe, pallas_kernels

    T, k, held, rows = s["T"], s["k"], s["held"], s["rows"]
    f32 = jnp.float32

    def route(experts):
        return moe.route_held_experts(experts, held, 0, rows)

    def total(x):
        return jnp.sum(x.astype(f32))

    # --- until PR 32 -----------------------------------------------------
    def sort_only_old(experts):
        flat = experts.reshape(-1)
        key = jnp.where(flat < held, flat, held)
        return jnp.argsort(key, stable=True).astype(jnp.int32)[:rows]

    def scatter_combine(o, w_row, token):
        return jnp.zeros((T, o.shape[1]), f32).at[token].add(
            o.astype(f32) * w_row[:, None])

    def scatter_combine_bwd(o, w_row, token):
        return jax.grad(lambda o, w: total(scatter_combine(o, w, token)),
                        (0, 1))(o, w_row)

    def gather_dispatch_bwd_scatter(x, token):
        return jax.grad(lambda x: total(x[token]))(x)

    # --- what ships ------------------------------------------------------
    def route_with_inverse(experts):
        r = route(experts)
        return r["slot"], r["row_of_pair"], r["pair_valid"]

    def tokens_from_rows_weighted(o, weights, experts):
        return moe.tokens_from_rows(o, route(experts), weights, f32)

    def tokens_from_rows_plain(o, experts):
        return moe.tokens_from_rows(o, route(experts))

    # `tokens_from_rows`' two bodies and the new one's parts, the routing
    # done outside: ms a call as the issue counts them
    def by_gathers_weighted(o, weights, r):
        return moe._sum_by_gathers(o, r, weights, f32)

    def by_gathers_plain(o, r):
        return moe._sum_by_gathers(o, r, None, o.dtype)

    def by_kernel_weighted(o, weights, r):
        return moe._sum_by_kernel(o, r, weights, f32)

    def by_kernel_plain(o, r):
        return moe._sum_by_kernel(o, r, None, o.dtype)

    def order_sort(r):
        return moe.rows_in_token_order(r["slot"], r["valid"], T, k)

    def order_gather(o, r):
        return moe._rows(o, r["in_token_order"]["row"])

    def kernel_weighted(o, weights, r):
        order = r["in_token_order"]
        return pallas_kernels.moe_sum_rows(
            o, order["token"], weights.reshape(-1)[:rows], order["items"],
            T, f32, False)

    def kernel_plain(o, r):
        order = r["in_token_order"]
        return pallas_kernels.moe_sum_rows(
            o, order["token"], None, order["items"], T, o.dtype, False)

    def combine_fwd_bwd(o, weights, experts):
        r = route(experts)
        return jax.value_and_grad(
            lambda o, w: total(moe.combine_rows(o, w, r)), (0, 1))(o, weights)

    # `combine_rows` backward alone, the routing and the forward's
    # residuals given: the row's side (PR 32; dY's rows gathered) and the
    # kernel's side with its parts (PR 49)
    def bwd_by_gathers(o, weights, r, d_y):
        return moe._spread_by_gathers(o, weights, r, d_y)

    def bwd_by_kernel(o_ordered, w_ordered, weights, r, d_y):
        return moe._spread_in_token_order(o_ordered, w_ordered, weights, r,
                                          d_y)

    def bwd_kernel(o_ordered, w_ordered, r, d_y):
        order = r["in_token_order"]
        return pallas_kernels.moe_spread_rows(
            d_y, o_ordered, order["token"], order["pair"] % k, w_ordered,
            order["items"], k, False)

    def bwd_gather_back(o_ordered, r):
        return moe._rows(o_ordered, r["in_token_order"]["place"])

    def bwd_inverse_sort(r):
        row = r["in_token_order"]["row"]
        return jax.lax.sort((row, jnp.arange(rows, dtype=jnp.int32)),
                            num_keys=1)[1]

    def combine_fwd_bwd_routed(o, weights, r, d_y):
        y, back = jax.vjp(lambda o, w: moe.combine_rows(o, w, r), o, weights)
        return y, back(d_y)

    def dispatch_fwd(x, experts):
        return moe.rows_from_tokens(x, route(experts))

    def dispatch_fwd_bwd(x, experts):
        r = route(experts)
        return jax.value_and_grad(
            lambda x: total(moe.rows_from_tokens(x, r)))(x)

    # --- tried against it ------------------------------------------------
    def one_gather_of_all_pairs(o, weights, experts):
        r = route(experts)
        w = jnp.where(r["pair_valid"], weights, 0.0)
        return jnp.sum(o[r["row_of_pair"]].astype(f32) * w[..., None], axis=1)

    def inverse_by_count(experts):
        flat = experts.reshape(-1)
        key = jnp.where(flat < held, flat, held)
        hot = jax.nn.one_hot(key, held + 1, dtype=jnp.int32)
        load = jnp.sum(hot, axis=0)
        start = jnp.cumsum(load) - load
        before = jnp.cumsum(hot, axis=0) - hot
        return jnp.sum(hot * (before + start), axis=1).reshape(experts.shape)

    def by_tile_product(o, weights, experts, tile=128):
        """Cost that follows the buffer's rows, not T*k: the valid pairs
        in token order (a stable sort on `not valid`), their rows by one
        `rows`-long gather, then every tile of 128 tokens adds its run of
        rows by a one-hot product on the MXU: megablox `tgmm` with the
        TILES as groups. A weighted row goes in as three bf16 pieces of
        its float32 product, so that the sum is float32's."""
        r = route(experts)
        valid = r["pair_valid"].reshape(-1)
        pair = jnp.arange(T * k, dtype=jnp.int32)
        _, in_token_order = jax.lax.sort((~valid, pair), num_keys=1,
                                         is_stable=True)
        in_token_order = in_token_order[:rows]
        live = jnp.arange(rows) < jnp.sum(valid)
        row = jnp.where(live, r["row_of_pair"].reshape(-1)[in_token_order], 0)
        hot = ((in_token_order // k % tile)[None, :]
               == jnp.arange(tile)[:, None]) & live[None, :]
        sizes = jnp.sum(valid.reshape(T // tile, tile * k), axis=1,
                        dtype=jnp.int32)
        picked = o[row]
        pieces = 1
        if weights is not None:
            exact = picked.astype(f32) * weights.reshape(-1)[
                in_token_order][:, None]
            pieces = 3
            picked = jnp.stack(pallas_kernels._bf16_pieces(exact, pieces),
                               axis=1).reshape(pieces * rows, -1)
            hot = jnp.repeat(hot, 3, axis=1)
        out = moe._megablox().tgmm(
            hot.astype(jnp.bfloat16), picked, sizes * pieces, f32,
            (128, tile, moe._lane_tile(picked.shape[1])),
            num_actual_groups=T // tile)
        return out.reshape(T, -1)

    def by_tile_product_plain(o, experts):
        return by_tile_product(o, None, experts).astype(o.dtype)

    return {
        "old.sort_only": (sort_only_old, ("experts",)),
        "old.scatter_combine_fwd": (scatter_combine, ("o", "w_row", "token")),
        "old.scatter_combine_bwd": (scatter_combine_bwd,
                                    ("o", "w_row", "token")),
        "old.dispatch_bwd_scatter": (gather_dispatch_bwd_scatter,
                                     ("x", "token")),
        "route_with_inverse": (route_with_inverse, ("experts",)),
        "route+tokens_from_rows_weighted": (tokens_from_rows_weighted,
                                            ("o", "weights", "experts")),
        "route+tokens_from_rows_plain": (tokens_from_rows_plain,
                                         ("o", "experts")),
        "by_gathers_weighted": (by_gathers_weighted,
                                ("o", "weights", "route")),
        "by_gathers_plain": (by_gathers_plain, ("o", "route")),
        "by_kernel_weighted": (by_kernel_weighted,
                               ("o", "weights", "route")),
        "by_kernel_plain": (by_kernel_plain, ("o", "route")),
        "by_kernel.order_sort": (order_sort, ("route",)),
        "by_kernel.order_gather": (order_gather, ("o", "route")),
        "by_kernel.kernel_weighted": (kernel_weighted,
                                      ("o", "weights", "route")),
        "by_kernel.kernel_plain": (kernel_plain, ("o", "route")),
        "route+combine_fwd_bwd": (combine_fwd_bwd,
                                  ("o", "weights", "experts")),
        "combine_fwd_bwd": (combine_fwd_bwd_routed,
                            ("o", "weights", "route", "d_y")),
        "combine_bwd.by_gathers": (bwd_by_gathers,
                                   ("o", "weights", "route", "d_y")),
        "combine_bwd.by_kernel": (
            bwd_by_kernel,
            ("o_ordered", "w_ordered", "weights", "route", "d_y")),
        "combine_bwd.by_kernel.kernel": (
            bwd_kernel, ("o_ordered", "w_ordered", "route", "d_y")),
        "combine_bwd.by_kernel.gather_back": (bwd_gather_back,
                                              ("o_ordered", "route")),
        "combine_bwd.by_kernel.inverse_sort": (bwd_inverse_sort, ("route",)),
        "route+dispatch_fwd": (dispatch_fwd, ("x", "experts")),
        "route+dispatch_fwd_bwd": (dispatch_fwd_bwd, ("x", "experts")),
        "tried.route+one_gather_of_all_pairs": (
            one_gather_of_all_pairs, ("o", "weights", "experts")),
        "tried.inverse_by_count": (inverse_by_count, ("experts",)),
        "tried.route+by_tile_product_weighted": (
            by_tile_product, ("o", "weights", "experts")),
        "tried.route+by_tile_product_plain": (
            by_tile_product_plain, ("o", "experts")),
    }


def draw_experts(s, key):
    """experts [T, k] int32: distinct experts a token, uniform: the held
    ones get their share. With `mask_share`, that share of the tokens
    choose the same k experts, one of them held: half of them at random
    places, half as one run from token 0; and the 256 tokens after that
    run choose k held experts, so that tiles with no row, with a row a
    token and with k rows a token all occur."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 2)
    T, k = s["T"], s["k"]
    _, experts = jax.lax.top_k(jax.random.uniform(ks[0], (T, s["E"])), k)
    if s.get("mask_share"):
        token = jnp.arange(T)
        run = int(T * s["mask_share"] / 2)
        masked = (token < run) | (
            jax.random.uniform(ks[1], (T,)) < s["mask_share"] / 2)
        of_mask = jnp.arange(k).at[1:].add(s["held"])   # expert 0 is held
        experts = jnp.where(masked[:, None], of_mask, experts)
        experts = jnp.where(((token >= run) & (token < run + 256))[:, None],
                            jnp.arange(k), experts)
    return experts.astype(jnp.int32)


def make_arguments(s):
    """A shape's arrays by the names `pieces` asks for them."""
    import jax
    import jax.numpy as jnp
    from flexflow_tpu.ops import moe

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    experts = draw_experts(s, ks[0])
    r = moe.route_held_experts(experts, s["held"], 0, s["rows"])
    weights = jax.random.uniform(ks[1], (s["T"], s["k"]))
    o = jax.random.normal(ks[3], (s["rows"], s["d"]), jnp.bfloat16)
    o_ordered, w_ordered = moe._in_token_order(o, r, weights)
    return dict(
        x=jax.random.normal(ks[2], (s["T"], s["d"]), jnp.bfloat16),
        d_y=jax.random.normal(ks[2], (s["T"], s["d"]), jnp.float32),
        o=o, o_ordered=o_ordered, w_ordered=w_ordered,
        weights=weights, experts=experts, token=r["slot"] // s["k"],
        route=r,
        w_row=jnp.where(r["valid"], weights.reshape(-1)[r["slot"]], 0.0),
        overflow=r["overflow"])


def timed_ms(fn, args, n=20):
    import jax
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def device_ms(jitted, calls=5, stems=6, whole=()):
    """name -> (function, arguments): every piece `calls` times under the
    profiler -> name -> (median device ms of its program, ms a call of
    its ops by stem, largest first; an op whose stem is in ``whole``
    under its own name, `flash_full.3` apart from `flash_full.4`). A call's wall time has a floor, what
    the host takes to hand over a piece's arguments (0.25 ms with a
    routing's fifteen arrays); the device's own clock has none."""
    import collections
    import statistics
    import tempfile

    import jax
    from benchmarks import trace_reduce as tr

    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for fn, args in jitted.values():
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        dev = tr.load_xplane(tr.newest_xplane(trace_dir))[0]
    out = {}
    for name, (fn, _) in jitted.items():
        runs = [(s, s + d) for n, s, d in dev.lines.get(tr.MODULES, ())
                if n.split("(")[0] == "jit_" + fn.__name__]
        if not runs:
            continue
        ops = collections.Counter()
        for n, s, d in dev.lines.get(tr.OPS, ()):
            if any(a <= s < b for a, b in runs) and not n.startswith(
                    tr.ENCLOSING):
                ops[n if tr.stem(n) in whole else tr.stem(n)] += (
                    d / len(runs) * 1e3)
        out[name] = (statistics.median(b - a for a, b in runs) * 1e3,
                     {k: round(v, 4) for k, v in ops.most_common(stems)})
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--deviceless", action="store_true")
    ap.add_argument("--only", default="",
                    help="pieces whose name holds this")
    opts = ap.parse_args()
    if opts.deviceless:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from flexflow_tpu.obs.inspect import scatters_in

    if opts.deviceless:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.devices()[0].platform != "tpu":
        sys.exit("moe_combine_lab: no TPU here (try --deviceless)")

    out = {}
    for cell, s in SHAPES.items():
        line = dict(cell=cell, device=("deviceless v5e" if opts.deviceless
                                       else jax.devices()[0].device_kind),
                    byte_floor_ms=round(
                        (2 * s["rows"] + 4 * s["T"]) * s["d"]
                        / HBM_BYTES_PER_S * 1e3, 3))
        if opts.deviceless:
            arrays = jax.eval_shape(lambda: make_arguments(s))
        else:
            arrays = make_arguments(s)
            assert int(arrays["overflow"]) == 0
            line["rows_held"] = int(arrays["route"]["valid"].sum())
        jitted = {}
        for name, (fn, names) in pieces(s).items():
            if opts.only not in name:
                continue
            if opts.deviceless:
                compiled = jax.jit(fn).lower(*(
                    jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=chip), arrays[n])
                    for n in names)).compile()
                line[name] = dict(
                    temp_mb=round(
                        compiled.memory_analysis().temp_size_in_bytes / 1e6),
                    scatters=len(scatters_in(compiled.as_text())))
            else:
                jitted[name] = (jax.jit(fn), [arrays[n] for n in names])
                line[name + "_ms"] = round(timed_ms(*jitted[name]), 3)
        for name, (ms, ops) in (device_ms(jitted) if jitted else {}).items():
            line[name + "_device_ms"] = round(ms, 3)
            line[name + "_device_ops"] = ops
        print(json.dumps(line), flush=True)
        out[cell] = line
    if not opts.deviceless:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/moe_combine_lab.json", "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
