"""Embedding.

Analog of src/ops/embedding.cc (+ kernels): aggregation modes SUM/AVG/NONE
over a bag of token ids. The vocab (or output) dim of the weight is the
parameter-parallel shardable axis used by DLRM-style strategies.

The table's gradient without a scatter (PR 57). Autodiff's transpose of
`jnp.take` is a scatter-add of the `B * S` cotangent rows into `[V, E]`,
and on the TPU v5e XLA runs a scatter-add of rows as a serial loop (0.5
to 1.3 us a row: `ops/moe.py`'s header met the same instruction). Under
`AGGR_MODE_NONE` the lookup therefore carries its own backward
(`rows_of_table`): the ids sorted with their positions, ONE gather of the
cotangent's rows into id order, and the expert layer's sum-of-rows kernel
(`pallas_kernels.moe_sum_rows`, unweighted) with "token" = table row,
which writes every tile of 128 table rows once, zeros where no id falls:
float32 sums of the cotangent's own values, in another order. On the
v5e, the device's own ms (`scripts/embedding_lab.py`, PR 57; a step's
cotangent is bfloat16): 16,384 rows of 2560 into `[18,992, 2560]` 8.56
by the scatter-add, 0.58 so (sort 0.01, gather 0.13, kernel 0.45; in
the step the gather reads 0.68, its rows not fresh from a call before);
8,192 rows into `[25,008, 2560]` 10.18 against 0.32; the least of the
nine decoder cells' shapes, 4,096 rows into `[6,144, 2048]`, 0.55
against 0.10. Which body runs is read from what is static
(`sums_rows_by_kernel`); where it says no, the transposed `take` stays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import AggrMode, OperatorType
from flexflow_tpu.initializers import DefaultWeightInitializer
from flexflow_tpu.ops import pallas_kernels
from flexflow_tpu.ops.base import (DimRole, Op, OpContext, register_op,
                                    scoped)

# the backward's nested call and its kernel in the device trace (XLA names
# a custom call's events after the innermost call around it)
SUM_KERNEL_NAME = "embedding_sum_rows"


def sums_rows_by_kernel(lookups: int, entries: int, width: int,
                        mesh=None) -> bool:
    """Whether the backward of `lookups` rows of a `[entries, width]`
    table adds them by the kernel here: the Pallas kernels are on (the
    TPU, or interpreted), one device (under a mesh of several the table
    may be sharded, and the transposed `take` is what GSPMD partitions),
    whole blocks of cotangent rows that fill the lanes, and a table of
    at least one tile (its LAST tile may be short: the kernel's ragged
    output block, no padded table and no slice)."""
    return (pallas_kernels.pallas_mode() != "off"
            and (mesh is None or mesh.devices.size == 1)
            and pallas_kernels.sum_rows_blocks_legal(lookups, width)
            and entries >= pallas_kernels.SUM_TOKENS)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rows_of_table(table, ids, sums=None):
    """`jnp.take(table, ids, axis=0)` whose backward adds the cotangent's
    rows into the table's by the sum-of-rows kernel and not by a
    scatter-add (module docstring); for the shapes `sums_rows_by_kernel`
    takes. An id outside the table reads NaN and adds nothing, as
    `take`'s does. `sums`, if given, is called (at trace time) by the
    backward."""
    return jnp.take(table, ids, axis=0)


def _rows_of_table_fwd(table, ids, sums):
    # (the table itself is not needed back: an empty slice of it carries
    # its row count and dtype)
    return rows_of_table(table, ids), (ids, table[:, :0])


def _rows_of_table_bwd(sums, res, d_rows):
    ids, like = res
    if sums is not None:
        sums()
    return scoped(SUM_KERNEL_NAME, functools.partial(
        table_gradient, entries=like.shape[0], dtype=like.dtype))(
            ids, d_rows), None


rows_of_table.defvjp(_rows_of_table_fwd, _rows_of_table_bwd)


def ids_in_order(ids, entries: int):
    """ids [...] int -> (entry, at) [lookups] int32: the ids ascending
    (one below 0 counted from the end, `jnp.take`'s own reading) and the
    lookup each came from; equal ids keep their lookups' order."""
    flat = ids.reshape(-1).astype(jnp.int32)
    flat = jnp.where(flat < 0, flat + entries, flat)
    return jax.lax.sort((flat, jnp.arange(flat.shape[0], dtype=jnp.int32)),
                        num_keys=1, is_stable=True)


def sum_in_id_order(ordered, entry, entries: int, dtype):
    """ordered [lookups, E] the cotangent's rows in the order of `entry`
    [lookups] (ascending) -> [entries, E] in `dtype`: the kernel, a tile
    of table rows a run of `ordered`."""
    starts = pallas_kernels.sum_rows_tile_starts(entry, entries)
    return pallas_kernels.moe_sum_rows(
        ordered, entry, None,
        pallas_kernels.moe_sum_rows_items(starts, entry.shape[0]), entries,
        dtype, pallas_kernels.pallas_mode() == "interpret",
        name=SUM_KERNEL_NAME)


def table_gradient(ids, d_rows, entries: int, dtype):
    """ids [...] int, d_rows [..., E] -> [entries, E] in `dtype`:
    out[v] = sum of d_rows[i] over the lookups i with ids[i] == v, the
    sums float32 and rounded once. d_rows is gathered in the dtype it
    comes in and no two rows are merged ahead of the kernel's sum; a
    row that is not finite reaches the 128 table rows of its tile (the
    kernel's 0 is a product)."""
    entry, at = ids_in_order(ids, entries)
    ordered = d_rows.reshape(at.shape[0], d_rows.shape[-1]).at[at].get(
        mode="promise_in_bounds")
    return sum_in_id_order(ordered, entry, entries, dtype)


@register_op(OperatorType.EMBEDDING)
class Embedding(Op):
    """input ids [B, S](int) -> [B, out_dim] (SUM/AVG over S) or
    [B, S, out_dim] (AGGR_MODE_NONE)."""

    def __init__(self, layer, input_shapes):
        p = layer.properties
        self.num_entries = p["num_entries"]
        self.out_dim = p["out_dim"]
        self.aggr = p.get("aggr", AggrMode.AGGR_MODE_NONE)
        self.kernel_init = p.get("kernel_initializer") or DefaultWeightInitializer()
        # a backward of the lookup has been traced and took the kernel
        # (`traced_gauges`)
        self._sum_kernel = False
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        in_shape = self.input_shapes[0]
        if self.aggr == AggrMode.AGGR_MODE_NONE:
            return [tuple(in_shape) + (self.out_dim,)]
        return [tuple(in_shape[:-1]) + (self.out_dim,)]

    def init_params(self, rng):
        return {"kernel": self.kernel_init(rng, (self.num_entries, self.out_dim))}

    def forward(self, params, inputs, ctx: OpContext):
        (ids,) = inputs
        table, ids = params["kernel"], ids.astype(jnp.int32)
        if (self.aggr == AggrMode.AGGR_MODE_NONE and sums_rows_by_kernel(
                ids.size, *table.shape, ctx.mesh)):
            return [rows_of_table(table, ids, self._saw_sum_kernel)]
        self._sum_kernel = False    # this is trace time
        emb = jnp.take(table, ids, axis=0)
        if self.aggr == AggrMode.AGGR_MODE_SUM:
            emb = jnp.sum(emb, axis=-2)
        elif self.aggr == AggrMode.AGGR_MODE_AVG:
            emb = jnp.mean(emb, axis=-2)
        return [emb]

    def _saw_sum_kernel(self):
        self._sum_kernel = True

    def traced_gauges(self):
        """`executor.embedding_sum_kernel_ops` (PR 57): 1 where the
        lookup's backward, as last traced, added the cotangent's rows
        into the table's by the kernel (`rows_of_table` calls
        `_saw_sum_kernel` from there); 0 where `take`'s transpose, a
        scatter-add, stayed (`sums_rows_by_kernel` said no: SUM / AVG, a
        mesh of several devices, Pallas off, rows that are no whole
        blocks), and before a backward has been traced."""
        return {"executor.embedding_sum_kernel_ops": int(self._sum_kernel)}

    def output_dim_roles(self):
        # token-position dim of [B,S,E] output is a sequence dim (lookups
        # are independent per position)
        shp = self.output_shapes[0]
        mid = DimRole.SEQ if len(shp) == 3 else DimRole.OTHER
        roles = [DimRole.SAMPLE] + [mid] * (len(shp) - 2) + [DimRole.CHANNEL]
        return [tuple(roles)]

    def params_elems(self):
        return self.num_entries * self.out_dim
