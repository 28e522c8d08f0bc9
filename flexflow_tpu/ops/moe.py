"""Mixture-of-Experts ops: GroupBy, Aggregate, AggregateSpec, Cache, and
the routing and grouped product of the dropless layer (ops/experts.py).

Analogs of src/ops/{group_by,aggregate,aggregate_spec,cache}.cc/.cu.
TPU re-design: the reference scatters tokens into per-expert CUDA buffers
with dynamic counts; under XLA everything must be static-shape, so dispatch
is expressed GShard-style — one-hot dispatch/combine tensors with a fixed
per-expert capacity (capacity factor `alpha`, same knob as the reference's
Group_by alpha) — lowered to einsums on the MXU, and to all_to_all over the
'expert' mesh axis when experts are sharded (see parallel/expert.py).

That one-hot form stays for the ops with the upstream signature
(GROUP_BY / AGGREGATE / AGGREGATE_SPEC) and for the toy `Experts` op whose
exchange across an 'expert' axis is built on it. The layer a real
mixture-of-experts model uses (`MoELayer`) does not go through it: its
tensor would have tokens x k x experts x capacity elements and drops what
overflows a per-expert capacity. `route_held_experts` and
`grouped_matmul` below are its routing and its product: slots sorted by
expert into one buffer, no per-expert capacity, every dropped slot
counted.

Rows and tokens (PR 32). The routing sort is a permutation, known in both
directions: `slot` says which (token, slot) pair a row holds,
`row_of_pair` which row a pair went to. Both ways across it are GATHERS:
`rows_from_tokens` reads a row's token, `tokens_from_rows` reads a
token's k rows and adds them. Neither is a scatter, forward or backward:
on the TPU v5e XLA runs a scatter-add of rows as a serial loop, 0.43 us a
row (18,560 rows of 2560 in 8.0 ms, 3% of the HBM's rate), where the same
rows are gathered in 0.3 ms. So each `custom_vjp` below replaces the
transpose autodiff would pick (a gather's is a scatter-add) by the gather
through the other map.

The token's side at the cost of the buffer's rows (PR 37). A chip holds
an eighth or a sixteenth of the experts, so of a token's k pairs most
have no row here, and k gathers of T rows each read T * k rows to use
`rows` of them: XLA's gather costs 14-19 ns a row asked for, whatever it
holds. A third sort, of the rows by their pair, puts them in token order
(`rows_in_token_order`); one gather of `rows` rows makes that copy, and
the kernel `pallas_kernels.moe_sum_rows` adds each token tile's run of
it: 0.84 ms against 1.80 (weighted) and 0.50 against 1.68 a call at
T = 16,384, k = 8, 24,704 rows of 2048. `sums_rows_by_kernel` picks the
body from the static shapes; the k gathers stay where the buffer holds
every pair, for shapes the kernel does not take whole, and off the TPU.

The other direction at that cost too (PR 49). `combine_rows`' backward
went from the row's side: dY's rows gathered as float32 and passed over
twice, and `d weights` gathered through `row_of_pair`, T * k scalars,
the last cost that followed the pairs. Where the forward sums by the
kernel the backward is now that kernel's transpose, over the same grid
and by the same 0/1 matrix the other way round:
`pallas_kernels.moe_spread_rows` gives a row its token's dY in VMEM,
`d o = w * dY` rounded once and `<dY, o>`, and from the latter `d
weights` a token tile; one gather of `rows` rows through the order's
inverse (`place`, a fourth sort, of `rows` keys) brings `d o` back to
the buffer's order. The forward keeps its token-ordered rows for it in
place of `o`. 0.62 ms against 2.29 a call at the shape above, 0.62
against 1.97 at T = 16,384, k = 6, 18,560 rows of 2560 (the kernel
0.46-0.61, the gather back 0.15, the sort 0.02). So, by direction:
tokens -> rows (`rows_from_tokens`) is a gather forward and the sum by
the kernel backward; rows -> tokens (`combine_rows`) is the sum by the
kernel forward and its transpose backward; each falls back to gathers
through the other map where `sums_rows_by_kernel` says no.
"""

from __future__ import annotations

import functools
import math
from typing import List

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops import pallas_kernels
from flexflow_tpu.ops.base import DimRole, Op, OpContext, register_op


def route_scores(scores, bias, k: int, norm_topk: bool, scaling: float,
                 scoring: str = "sigmoid"):
    """scores [T, E] float32, bias [E] or None -> (weights [T, k] float32,
    experts [T, k] int32): the k largest of scores + bias a token (the
    bias takes part in the choice only).

    ``scoring`` "sigmoid": ``scores`` are the sigmoids; the chosen ones'
    weights are s_j / (sum of the k + 1e-20) if `norm_topk`, times
    `scaling`. ``scoring`` "softmax": ``scores`` are the router's logits;
    the weights are the softmax over the k chosen logits if `norm_topk`
    (which is the softmax over all E, renormalised over the chosen), else
    the chosen entries of the softmax over all E; times `scaling`."""
    choose = scores if bias is None else scores + bias
    _, idx = jax.lax.top_k(choose, k)
    # the chosen scores by a one-hot select, not `take_along_axis`: that
    # one's backward is a scatter-add into [T, E] (module docstring)
    chosen = idx[..., None] == jnp.arange(scores.shape[-1], dtype=idx.dtype)
    top = jnp.sum(jnp.where(chosen, scores[..., None, :], 0.0), axis=-1)
    if scoring == "softmax":
        if norm_topk:
            top = jax.nn.softmax(top, axis=-1)
        else:
            top = jnp.exp(top - jax.nn.logsumexp(scores, axis=-1,
                                                 keepdims=True))
    elif scoring != "sigmoid":
        raise ValueError(f"route_scores: unknown scoring {scoring!r}")
    elif norm_topk:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return top * scaling, idx.astype(jnp.int32)


def route_held_experts(experts, held: int, offset: int, rows: int):
    """Sort the (token, slot) pairs whose expert is one of the `held`
    experts [offset, offset + held) into one buffer of `rows` rows, by
    expert; pairs routed elsewhere contribute nothing here.

    experts [T, k] int32 -> dict of
      slot        [rows] int32  flat index t * k + j of the pair in a row
      valid       [rows] bool   the row holds a pair of a held expert
      row_of_pair [T, k] int32  the row a pair went to (0 where it has
                                none): the inverse of `slot`
      pair_valid  [T, k] bool   the pair's expert is held and it found a
                                row
      group_sizes [held] int32  rows of each held expert, in order, cut so
                                that their sum is at most `rows`
      load        [held] int32  pairs of each held expert before the cut
      overflow    []     int32  held pairs that found no row (0 unless the
                                buffer is too small): counted, never
                                silently dropped
      in_token_order     dict   the rows sorted by the pair they hold,
                                which is by token and within a token by
                                slot (`rows_in_token_order`)
    """
    flat = experts.reshape(-1) - offset
    here = (flat >= 0) & (flat < held)
    key = jnp.where(here, flat, held)
    pair = jnp.arange(key.shape[0], dtype=jnp.int32)
    _, order = jax.lax.sort((key, pair), num_keys=1, is_stable=True)
    # the inverse permutation by a second sort, not by a scatter of `pair`
    # to `order`; held pairs sort first, so a held pair's place is its row
    _, place = jax.lax.sort((order, pair), num_keys=1)
    # a buffer rounded up past the number of pairs: the rest is not valid
    order = jnp.pad(order, (0, max(0, rows - order.shape[0])))
    load = jnp.sum(jax.nn.one_hot(key, held + 1, dtype=jnp.int32),
                   axis=0)[:held]
    ends = jnp.minimum(jnp.cumsum(load), rows)
    group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    n_rows = ends[-1]
    pair_valid = here & (place < n_rows)
    slot = order[:rows]
    valid = jnp.arange(rows, dtype=jnp.int32) < n_rows
    return dict(slot=slot, valid=valid,
                in_token_order=rows_in_token_order(slot, valid,
                                                   *experts.shape),
                row_of_pair=jnp.where(pair_valid, place, 0
                                      ).reshape(experts.shape),
                pair_valid=pair_valid.reshape(experts.shape),
                group_sizes=group_sizes, load=load,
                overflow=jnp.sum(load) - n_rows)


def rows_in_token_order(slot, valid, tokens: int, k: int):
    """The buffer's rows in the order of the pairs they hold (PR 37):
    `slot[r] = t * k + j` is a row's place among the pairs, so one stable
    key/value sort of `slot` puts a token's rows side by side, in the
    order of their slots; rows that hold no pair sort last. What
    `tokens_from_rows` needs to read each row once:

      row        [rows] int32  the buffer row at each place of the order
      pair       [rows] int32  the pair it holds (any pair past the valid)
      token      [rows] int32  its token; `tokens` past the valid rows
      place      [rows] int32  the inverse of `row`: where in the order
                               each buffer row stands (what brings the
                               combine's `d o` back, PR 49)
      tile_start [tiles + 1]   where the run of each tile of
                               `pallas_kernels.SUM_TOKENS` tokens starts
                               (the last: where the valid rows end)
      items      dict          the kernels' grid (`moe_sum_rows_items`)
    """
    rows = slot.shape[0]
    at = jnp.arange(rows, dtype=jnp.int32)
    pair, row = jax.lax.sort((jnp.where(valid, slot, tokens * k), at),
                             num_keys=1, is_stable=True)
    _, place = jax.lax.sort((row, at), num_keys=1)
    token = pair // k
    tile_start = pallas_kernels.sum_rows_tile_starts(token, tokens)
    return dict(row=row, pair=jnp.minimum(pair, tokens * k - 1), token=token,
                place=place, tile_start=tile_start,
                items=pallas_kernels.moe_sum_rows_items(tile_start, rows))


def _rows(buf, index):
    """buf[index] for indices known to be in range."""
    return buf.at[index].get(mode="promise_in_bounds")


# `tokens_from_rows` sums by the kernel when the buffer holds at most this
# share of the tokens' pairs: the k gathers cost per PAIR, the order's
# gather and the kernel per ROW. (the lab's numbers: PERF.md section 6)
SUM_ROWS_MAX_SHARE = 0.5


def sums_rows_by_kernel(rows: int, width: int, tokens: int, k: int) -> bool:
    """Whether `tokens_from_rows` runs `moe_sum_rows` for these static
    shapes here: the Pallas kernels are on (the TPU, or interpreted), the
    shape is one the kernel takes whole, and the buffer is a small enough
    share of the pairs. A layer that holds every expert has a row a pair,
    gains nothing from the order and keeps the k gathers."""
    return (pallas_kernels.pallas_mode() != "off"
            and pallas_kernels.moe_sum_rows_shape_legal(rows, width, tokens)
            and rows <= SUM_ROWS_MAX_SHARE * tokens * k)


def tokens_from_rows(buf, route, weight=None, dtype=None):
    """Add a buffer's rows into their tokens, from the token's side:

        out[t] = sum over j of pair_valid[t, j] * weight[t, j]
                               * buf[row_of_pair[t, j]]

    buf [rows, d], `route` what `route_held_experts` returned, weight
    [T, k] float32 or None for 1 -> [T, d] in `dtype` (buf's if None):
    products and the sum over j in float32, rounded once; no scatter.

    Two bodies, picked by what is static (`sums_rows_by_kernel`). Where
    the buffer is a small share of the T * k pairs, on the TPU: the rows
    into token order by ONE gather of `rows` rows, then the kernel
    `moe_sum_rows`, which adds each token tile's run of them (PR 37): a
    cost that follows the buffer's rows. Else k row gathers of T rows
    each and one masked multiply-add over them, slot by slot, so that no
    float32 [T, k, d] exists: T * k rows, of which the pairs held
    elsewhere read row 0 and are multiplied by nothing."""
    tokens, k = route["row_of_pair"].shape
    body = (_sum_by_kernel if sums_rows_by_kernel(*buf.shape, tokens, k)
            else _sum_by_gathers)
    return _row_major(body(buf, route, weight, dtype or buf.dtype))


def _row_major(out):
    # rows come out of a gather (and of the kernel) row-major; a consumer
    # that keeps [T, d] with T minor (the decoders' residual stream on the
    # TPU) would have each of the k gathered arrays transposed to meet
    # it: ask for the sum row-major, so that it is transposed once
    return with_layout_constraint(out, Layout(major_to_minor=(0, 1)))


def _in_token_order(buf, route, weight):
    """The buffer's rows and their pairs' weights (None for None) in
    token order: one gather of `rows` rows, one of `rows` scalars."""
    order = route["in_token_order"]
    return _rows(buf, order["row"]), None if weight is None else _rows(
        weight.reshape(-1).astype(jnp.float32), order["pair"])


def _sum_by_kernel(buf, route, weight, dtype):
    return _sum_in_token_order(*_in_token_order(buf, route, weight), route,
                               dtype)


def _sum_in_token_order(ordered, weight, route, dtype):
    order = route["in_token_order"]
    return pallas_kernels.moe_sum_rows(
        ordered, order["token"], weight, order["items"],
        route["row_of_pair"].shape[0], dtype,
        pallas_kernels.pallas_mode() == "interpret")


def _sum_by_gathers(buf, route, weight, dtype):
    row_of_pair, pair_valid = route["row_of_pair"], route["pair_valid"]
    acc = None
    for j in range(row_of_pair.shape[1]):
        term = _rows(buf, row_of_pair[:, j]).astype(jnp.float32)
        if weight is not None:
            term = term * weight[:, j, None].astype(jnp.float32)
        term = jnp.where(pair_valid[:, j, None], term, 0.0)
        acc = term if acc is None else acc + term
    return acc.astype(dtype)


@jax.custom_vjp
def rows_from_tokens(xt, route):
    """xt [T, d] -> [rows, d]: each row's token (`route` is what
    `route_held_experts` returned; rows that hold no pair read some
    token, and the grouped product leaves them out). Backward: the rows'
    gradients added into their tokens by `tokens_from_rows`, where
    autodiff would scatter-add them."""
    return _rows(xt, route["slot"] // route["row_of_pair"].shape[1])


def _rows_from_tokens_fwd(xt, route):
    return rows_from_tokens(xt, route), route


def _rows_from_tokens_bwd(route, d_rows):
    return tokens_from_rows(d_rows, route), None


rows_from_tokens.defvjp(_rows_from_tokens_fwd, _rows_from_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine_rows(o, weights, route, spreads=None):
    """o [rows, d] the experts' outputs, weights [T, k] float32 ->
    [T, d] float32: every token's held pairs' rows, each times its weight
    (`tokens_from_rows`). Backward, as autodiff has it for the
    scatter-add this replaces: d o = dY[token] * w on the row side,
    d weights = <dY[token], o> of a pair's row. Where the forward sums by
    the kernel (`sums_rows_by_kernel`) the backward is its transpose
    (PR 49): ONE kernel, `moe_spread_rows`, over the rows in token order
    (kept from the forward in place of `o`) gives both, and one gather of
    `rows` rows brings `d o` back to the buffer's order; nothing follows
    the T * k pairs. Else dY's rows are gathered (float32) and `d
    weights` through `row_of_pair`, as since PR 32. `spreads`, if given,
    is called (at trace time) by a backward that took the kernel."""
    return tokens_from_rows(o, route, weights, jnp.float32)


def _combine_rows_fwd(o, weights, route, spreads):
    if not sums_rows_by_kernel(*o.shape, *weights.shape):
        return combine_rows(o, weights, route), (o, None, weights, route)
    ordered, w_row = _in_token_order(o, route, weights)
    return (_row_major(_sum_in_token_order(ordered, w_row, route,
                                           jnp.float32)),
            (ordered, w_row, weights, route))


def _combine_rows_bwd(spreads, res, d_y):
    o, w_row, weights, route = res
    if w_row is None:
        return *_spread_by_gathers(o, weights, route, d_y), None
    if spreads is not None:
        spreads()
    return *_spread_in_token_order(o, w_row, weights, route, d_y), None


def _spread_by_gathers(o, weights, route, d_y):
    """`combine_rows`' backward from the row's side: dY's rows gathered
    as float32 and passed over twice, `d weights` a gather of T * k
    scalars through `row_of_pair`."""
    slot, valid = route["slot"], route["valid"]
    d_rows = _rows(d_y, slot // weights.shape[1]).astype(jnp.float32)
    w_row = jnp.where(valid, _rows(weights.reshape(-1), slot), 0.0)
    d_o = (d_rows * w_row[:, None]).astype(o.dtype)
    d_w_row = jnp.sum(d_rows * o.astype(jnp.float32), axis=-1)
    d_weights = jnp.where(route["pair_valid"],
                          _rows(d_w_row, route["row_of_pair"]), 0.0)
    return d_o, d_weights.astype(weights.dtype)


def _spread_in_token_order(ordered, w_row, weights, route, d_y):
    """`combine_rows`' backward where its forward summed by the kernel:
    `ordered` and `w_row` are the forward's rows and weights in token
    order, and the kernel's `d ordered` goes back to the buffer's order
    by one gather of `rows` rows."""
    order = route["in_token_order"]
    k = weights.shape[1]
    d_ordered, d_weights = pallas_kernels.moe_spread_rows(
        d_y.astype(jnp.float32), ordered, order["token"], order["pair"] % k,
        w_row, order["items"], k,
        pallas_kernels.pallas_mode() == "interpret")
    return _rows(d_ordered, order["place"]), d_weights.astype(weights.dtype)


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


# What the megablox kernels' blocks of a grid step may take of the 16 MiB
# of scoped VMEM the compiler gives a kernel that asks for nothing: the
# operands' and the output's blocks twice (the pipeline's two buffers)
# and the float32 accumulator and product. The count is on the safe
# side (PR 53's lab: [256, 2688] x [2688, 1024], 16.9 MB by it, still
# compiles and runs; [512, 2560] x [2560, 768], 17.8 MB, is refused).
GMM_VMEM_BYTES = 15 << 20


def gmm_row_tile(m: int, groups: int) -> int:
    """The row tile of a layer's grouped products, from the buffer's rows
    and the groups alone: 256 where a group's share of the buffer is
    1,024 rows or more, else 128. A tile in which a group starts is
    visited once more, whole, so a tile has to stay a small part of a
    group; the lab (scripts/gmm_lab.py, PR 53) found 256 rows a step the
    fastest from 1,544 rows of buffer a group up (about 1,000 that hold a
    pair), 128 up to 592, and 512 nowhere."""
    return 256 if m >= 1024 * groups else 128


def _lane_tile(dim: int) -> int:
    """A tile of a dimension that lies along the lanes: `dim` whole up to
    1024, a longer one in its largest divisor that is a multiple of 128,
    or in 1024s with a ragged last tile, which the kernels mask."""
    if dim <= 1024:
        return dim
    return next((t for t in range(1024, 127, -128) if dim % t == 0), 1024)


def _gmm_tiling(m: int, groups: int, k: int, n: int, transposed=False):
    """(row tile, contraction tile, output tile) of the megablox kernels
    for a grouped product over [m, ..] rows in `groups` groups:
    [m, k] x [g, k, n] (with the roles of k and n as the caller gives
    them, also the rows' gradient), or with `transposed` the weights'
    gradient [m, k]^T [m, n] a group.

    The row tile is `gmm_row_tile`'s if it divides m (`MoELayer` makes
    its buffer so), else 128. The first kind contracts k WHOLE: the
    kernel's grid is (n tiles, visited row tiles, k tiles) and the weight
    block's index (group, k tile, n tile), so with ONE k tile the index
    stays over a group's row tiles and the [k, tn] panel is fetched from
    HBM once a (group, n tile); with two or more it changes every grid
    step and the panel is fetched again for every row tile (until PR 53:
    2 MB for every 128 rows, the products at 34-45% of the MXU's peak,
    bound by the HBM). The output tile is narrowed while a step's blocks
    overrun `GMM_VMEM_BYTES` (at the widest cell, nemotron's [128, 2688]
    x [2688, 1024]: 14.0 MB; `lfm2`'s [256, 2048] x [2048, 896]: 12.2
    MB), and a contraction too long even for 128 lanes of output is
    split as before. The transposed kind holds a float32 [tk, tn]
    accumulator over a group's row tiles and tiles both in up to 1024."""
    tm = gmm_row_tile(m, groups)
    tm = tm if m % tm == 0 else 128
    if transposed:
        return tm, _lane_tile(k), _lane_tile(n)

    def fits(tk, tn):
        return 4 * (tm * tk + tk * tn + tm * tn) + 8 * tm * tn <= (
            GMM_VMEM_BYTES)

    tn = _lane_tile(n)
    while tn > 128 and not fits(k, tn):
        tn = max(128, tn // 2 // 128 * 128)
    return tm, k if fits(k, tn) else _lane_tile(k), tn


def _megablox():
    # the package's `gmm` name is its differentiable wrapper with one
    # tiling for all three products; the kernels are in the module
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _megablox_gmm(lhs, rhs, group_sizes, interpret):
    backend = _megablox()
    m, k = lhs.shape
    groups, _, n = rhs.shape
    out = backend.gmm(lhs, rhs, group_sizes, lhs.dtype,
                      _gmm_tiling(m, groups, k, n), interpret=interpret)
    return _zero_past(out, group_sizes)


def _zero_past(rows, group_sizes):
    """The kernels leave the rows past the groups' sum unwritten."""
    covered = jnp.arange(rows.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(covered[:, None], rows, 0)


def _megablox_fwd(lhs, rhs, group_sizes, interpret):
    return _megablox_gmm(lhs, rhs, group_sizes, interpret), (
        lhs, rhs, group_sizes)


def _megablox_bwd(interpret, res, grad):
    backend = _megablox()
    lhs, rhs, group_sizes = res
    m, k = lhs.shape
    groups, _, n = rhs.shape
    # d lhs = grad [m, n] x rhs^T: contracts n; d rhs[g] = lhs_g^T grad_g
    d_lhs = _zero_past(
        backend.gmm(grad, rhs, group_sizes, lhs.dtype,
                    _gmm_tiling(m, groups, n, k), transpose_rhs=True,
                    interpret=interpret), group_sizes)
    d_rhs = backend.tgmm(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
                         _gmm_tiling(m, groups, k, n, transposed=True),
                         num_actual_groups=groups, interpret=interpret)
    return d_lhs, d_rhs, None


_megablox_gmm.defvjp(_megablox_fwd, _megablox_bwd)


def _kernels_mode(m: int):
    """`pallas_mode()` where the kernels take a buffer of m rows, else
    None (`lax.ragged_dot` runs)."""
    mode = pallas_kernels.pallas_mode()
    return mode if mode != "off" and m % 128 == 0 else None


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs [m, k] rows sorted by group, rhs [g, k, n], group_sizes [g]
    (sum <= m) -> [m, n]: rows of group i times rhs[i]; rows past the
    groups' sum are zero, and so is their gradient. On the TPU (and
    under FLEXFLOW_TPU_PALLAS=interpret) the Pallas megablox kernels that
    ship with JAX, walked as `_gmm_tiling` says: row tiles of 256 where
    the groups are large and of 128 where they are small (m must be a
    multiple of 128), and the contraction in ONE tile, so that a group's
    weight panel is fetched once a group and not once a row tile;
    elsewhere `lax.ragged_dot`."""
    mode = _kernels_mode(lhs.shape[0])
    if mode:
        return _megablox_gmm(lhs, rhs, group_sizes, mode == "interpret")
    return _zero_past(
        jax.lax.ragged_dot(lhs, rhs, group_sizes,
                           preferred_element_type=jnp.float32
                           ).astype(lhs.dtype), group_sizes)


def grouped_products_walk(m: int, groups: int, d: int, f: int,
                          matrices: int):
    """(row tile, `gmm` products whose contraction is ONE tile) of an
    expert layer's grouped products over [m, d] rows, its `matrices`
    [d, f] / [f, d] a group: what `grouped_matmul` runs for these static
    shapes here, forward and `d lhs`; (0, 0) where it is
    `lax.ragged_dot`."""
    if not _kernels_mode(m):
        return 0, 0
    # up (and gate) contract d forward and f in `d lhs`; down the reverse
    up, down = _gmm_tiling(m, groups, d, f), _gmm_tiling(m, groups, f, d)
    return up[0], matrices * ((up[1] == d) + (down[1] == f))


def expert_capacity(batch: int, k: int, n_experts: int, alpha: float) -> int:
    return max(1, int(math.ceil(alpha * k * batch / n_experts)))


def load_balance_loss(assign, gate, n_experts: int, lambda_bal: float):
    """Switch/GShard auxiliary loss: lambda * E * <f, P> with f the token
    fraction per expert over ALL top-k slots (the reference's Aggregate
    backward loops every k slot, src/ops/aggregate.cu agg_backward_kernel)
    and P the mean router probability. assign [B,K] int, gate [B,E]."""
    f = jnp.mean(jax.nn.one_hot(assign, n_experts, dtype=jnp.float32),
                 axis=(0, 1))
    p_mean = jnp.mean(gate.astype(jnp.float32), axis=0)
    return lambda_bal * n_experts * jnp.sum(f * p_mean)


def make_dispatch_tensors(assign, gates, n_experts: int, capacity: int):
    """assign [B,K] int, gates [B,K] -> dispatch [B,K,E,C] bool-ish f32,
    combine [B,K,E,C] f32 (gate-weighted), overflow dropped."""
    b, k = assign.shape
    expert_onehot = jax.nn.one_hot(assign, n_experts, dtype=jnp.float32)  # [B,K,E]
    flat = expert_onehot.reshape(b * k, n_experts)
    # position of each (token, slot) within its expert, in flat order
    pos = jnp.cumsum(flat, axis=0) * flat - flat  # [B*K, E], 0-based
    pos = pos.reshape(b, k, n_experts)
    in_cap = pos < capacity
    pos_onehot = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
    dispatch = expert_onehot[..., None] * pos_onehot * in_cap[..., None]
    combine = dispatch * gates[..., None, None]
    return dispatch, combine


@register_op(OperatorType.GROUP_BY)
class GroupBy(Op):
    """inputs: (data [B,D], assign [B,K]) -> n_experts tensors [C, D].

    Reference Group_by (src/ops/group_by.cu) writes variable-count rows per
    expert buffer sized alpha*K*B/n; we produce fixed-capacity buffers via
    the dispatch einsum (overflowed tokens drop, as in the reference).
    """

    def __init__(self, layer, input_shapes):
        self.n_experts = layer.get_property("n")
        self.alpha = layer.get_property("alpha", 1.0)
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        data, assign = self.input_shapes
        b, k = assign
        cap = expert_capacity(b, k, self.n_experts, self.alpha)
        return [(cap, data[-1])] * self.n_experts

    def forward(self, params, inputs, ctx: OpContext):
        data, assign = inputs
        b, k = assign.shape
        cap = expert_capacity(b, k, self.n_experts, self.alpha)
        dispatch, _ = make_dispatch_tensors(
            assign, jnp.ones(assign.shape, jnp.float32), self.n_experts, cap
        )
        grouped = jnp.einsum("bd,bkec->ecd", data.astype(jnp.float32), dispatch)
        return [grouped[e].astype(data.dtype) for e in range(self.n_experts)]

    def output_dim_roles(self):
        return [(DimRole.OTHER, DimRole.CHANNEL)] * self.n_experts


@register_op(OperatorType.AGGREGATE)
class Aggregate(Op):
    """inputs: (gate_preds [B,K], gate_assign [B,K], true_gate_assign [B,K],
    gate_grads [B,K], expert_out_0 [C,D] ... expert_out_{n-1}) -> [B,D].

    Matches the reference's 4+n input signature (src/ops/aggregate.cc) —
    the two extra assign/grad inputs exist for the load-balance loss path;
    autodiff handles the gate gradient here so they are accepted and the
    lb loss is exposed via aggregate load stats.
    """

    def __init__(self, layer, input_shapes):
        self.n_experts = layer.get_property("n")
        self.lambda_bal = layer.get_property("lambda_bal", 0.0)
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        b, k = self.input_shapes[0]
        d = self.input_shapes[-1][-1]
        return [(b, d)]

    def forward(self, params, inputs, ctx: OpContext):
        gate_preds, gate_assign = inputs[0], inputs[1]
        expert_outs = inputs[-self.n_experts:]
        b, k = gate_assign.shape
        cap = expert_outs[0].shape[0]
        _, combine = make_dispatch_tensors(
            gate_assign, gate_preds.astype(jnp.float32), self.n_experts, cap
        )
        stacked = jnp.stack(expert_outs, axis=0).astype(jnp.float32)  # [E,C,D]
        out = jnp.einsum("bkec,ecd->bd", combine, stacked)
        if self.lambda_bal > 0.0 and len(inputs) >= 4 + self.n_experts:
            # inputs[3] is the full gate output [B, E] from the moe sugar
            self._aux_loss = load_balance_loss(
                gate_assign, inputs[3], self.n_experts, self.lambda_bal)
        return [out.astype(expert_outs[0].dtype)]

    def output_dim_roles(self):
        return [(DimRole.SAMPLE, DimRole.CHANNEL)]


@register_op(OperatorType.AGGREGATE_SPEC)
class AggregateSpec(Op):
    """Speculative aggregate (src/ops/aggregate_spec.cc): same combine but
    experts received *all* K assignments; output matches Aggregate."""

    def __init__(self, layer, input_shapes):
        self.n_experts = layer.get_property("n")
        self.lambda_bal = layer.get_property("lambda_bal", 0.0)
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        b, k = self.input_shapes[0]
        d = self.input_shapes[-1][-1]
        return [(b, d)]

    forward = Aggregate.forward
    output_dim_roles = Aggregate.output_dim_roles


@register_op(OperatorType.CACHE)
class Cache(Op):
    """Activation/score cache (src/ops/cache.cc): stores the input tensor
    across iterations; a user-provided score function decides whether the
    cached value is fresh enough to reuse. State lives in the model's
    non-trainable state collection; under jit the trigger works on
    materialized scores (host callback-free: score is returned as a metric).
    """

    def __init__(self, layer, input_shapes):
        self.num_batches = layer.get_property("num_batches", 1)
        self.score_fn = layer.get_property("score_fn")
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        return [self.input_shapes[0]]

    def init_state(self):
        return {
            "cached": jnp.zeros(self.input_shapes[0]),
            "score": jnp.zeros(()),
        }

    def forward(self, params, inputs, ctx: OpContext, state=None):
        (x,) = inputs
        if state is not None:
            score = (
                self.score_fn(state["cached"], x)
                if self.score_fn is not None
                else jnp.mean((state["cached"] - x) ** 2)
            )
            self._new_state = {"cached": x, "score": score}
        return [x]
