"""The embedding's own backward (PR 57; `flexflow_tpu/ops/embedding.py`):
under `AGGR_MODE_NONE` the table's gradient is formed by a sort of the
ids, one gather of the cotangent's rows and the sum-of-rows kernel
(`pallas_kernels.moe_sum_rows` under the name `embedding_sum_rows`), not
by the scatter-add that is `jnp.take`'s transpose. Here, in interpret
mode on the CPU: the new backward against the sums a scatter-add forms
(numpy, float64) at the claimed cells' shapes cut to tier-1's time with
what makes them hard kept (V % 128 = 48 as 18,992 and 25,008 have it, V
below the lookups as `lfm2` has it); the table that is also the head's;
and which body the static shapes pick.

Tolerances: a float32 result is float32 sums in another order (1e-5 of
the largest sum, a run of a thousand rows included); a bfloat16 result
is the float32 sum rounded once, so it stands within one bfloat16 unit
(2^-8 relative) of the float64 sum."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from flexflow_tpu import (AdamOptimizer, FFConfig, FFModel,  # noqa: E402
                          LossType)
from flexflow_tpu.ffconst import AggrMode, DataType  # noqa: E402
from flexflow_tpu.models import DecoderConfig, create_decoder  # noqa: E402
from flexflow_tpu.ops import embedding  # noqa: E402
from flexflow_tpu.ops import pallas_kernels as pk  # noqa: E402
from flexflow_tpu.ops.base import OpContext  # noqa: E402

# (lookups, V, E): the claimed cells' shapes, cut
SHAPES = {
    "smallthinker": (2048, 18 * 128 + 48, 256),   # of (16384, 18992, 2560)
    "phi4": (1024, 24 * 128 + 48, 256),           # of (8192, 25008, 2560)
    "lfm2": (2048, 1024, 128),                    # of (16384, 8192, 2048)
}


def draw_ids(case, lookups, entries, rng):
    if case == "uniform":
        return rng.integers(0, entries, lookups)
    if case == "every_id_equal":
        return np.full(lookups, entries // 2)
    if case == "first_and_last_row":
        return np.where(rng.integers(0, 2, lookups) == 1, entries - 1, 0)
    assert case == "a_tile_no_id_reaches"
    ids = rng.integers(0, entries - 128, lookups)
    return np.where(ids >= 256, ids + 128, ids)     # none in [256, 384)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["uniform", "every_id_equal",
                                  "first_and_last_row",
                                  "a_tile_no_id_reaches"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_backward_adds_the_rows_a_scatter_add_would(
        interpreted, shape, case, dtype):
    lookups, entries, width = SHAPES[shape]
    assert embedding.sums_rows_by_kernel(lookups, entries, width)
    rng = np.random.default_rng(len(shape) + len(case))
    ids = draw_ids(case, lookups, entries, rng).astype(np.int32).reshape(
        2, lookups // 2)
    d_rows = jnp.asarray(rng.standard_normal((2, lookups // 2, width)),
                         dtype)
    table = jnp.asarray(rng.standard_normal((entries, width)), dtype)

    @jax.jit
    def run(table, ids, d_rows):
        out, back = jax.vjp(lambda t: embedding.rows_of_table(t, ids), table)
        return out, back(d_rows)[0]

    out, got = run(table, ids, d_rows)
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(table, np.float32)[ids])
    assert got.dtype == table.dtype and got.shape == table.shape
    want = np.zeros((entries, width))
    np.add.at(want, ids.reshape(-1),
              np.asarray(d_rows, np.float64).reshape(lookups, width))
    got = np.asarray(got, np.float64)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-5 * np.abs(want).max())
    else:
        np.testing.assert_allclose(got, want, rtol=2.0 ** -8, atol=1e-30)
    # a row no id reads holds exact zeros, a whole tile of them too
    unread = np.setdiff1d(np.arange(entries), ids)
    assert unread.size and not got[unread].any()
    if case == "a_tile_no_id_reaches":
        assert set(range(256, 384)) <= set(unread.tolist())


def test_an_id_outside_the_table_adds_nothing_and_one_below_zero_wraps(
        interpreted):
    """`jnp.take`'s own reading of an id, kept."""
    entries, width = 200, 128
    ids = np.arange(128, dtype=np.int32).reshape(1, 128)
    ids[0, :4] = (-1, -entries, entries, entries + 70)
    rng = np.random.default_rng(0)
    d_rows = jnp.asarray(rng.standard_normal((1, 128, width)), jnp.float32)
    table = jnp.zeros((entries, width), jnp.float32)
    got, want = (jax.jit(lambda t, g, fn=fn: jax.vjp(
        lambda t: fn(t, ids), t)[1](g)[0])(table, d_rows)
        for fn in (embedding.rows_of_table,
                   lambda t, ids: jnp.take(t, ids, axis=0)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    assert np.asarray(got)[entries - 1].any()       # id -1 reached the last


def tied_decoder(seed=3):
    cfg = DecoderConfig(hybrid_override_pattern="-", vocab_size=200,
                        hidden_size=128, intermediate_size=64,
                        tie_word_embeddings=True, batch_size=2,
                        seq_length=64)
    ff = create_decoder(cfg, FFConfig(batch_size=2, seed=seed,
                                      workers_per_node=1))
    ff.compile(AdamOptimizer(alpha=1e-3),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
    return ff


def leaf_gradients(ff, ids, labels):
    ex = ff.executor
    inputs, labels = ff._stage_inputs([ids]), ff._shard_batch(labels)

    def loss(p):
        ctx = OpContext(training=True, rng=jax.random.PRNGKey(0),
                        compute_dtype=ex.compute_dtype, mesh=ex.mesh)
        values, _, _ = ex.run_graph(p, {}, inputs, ctx)
        return ex._loss_value(values[ex.final_ref], labels)

    return jax.jit(jax.grad(loss))(ff.params)


def test_the_tied_tables_gradient_is_the_heads_plus_the_kernels_sum(
        monkeypatch):
    """One leaf read by `embed_tokens` and by `lm_head`: its gradient is
    head dW + the lookups' sum, by the kernel equal to what the
    transposed `take` gives to float32's summation order; the gauge says
    which body the backward took."""
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 200, (2, 64)).astype(np.int32)
    ids[0, :40] = 7                                   # a run, and a tie
    labels = rng.integers(0, 200, (2, 64)).astype(np.int32)
    got = {}
    for mode in ("off", "interpret"):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
        ff = tied_decoder()
        assert "lm_head" not in ff.params
        got[mode] = np.asarray(
            leaf_gradients(ff, ids, labels)["embed_tokens"]["kernel"])
        assert ff.executor.traced_gauges()[
            "executor.embedding_sum_kernel_ops"] == (mode == "interpret")
    scale = np.abs(got["off"]).max()
    assert scale > 0
    np.testing.assert_allclose(got["interpret"], got["off"],
                               atol=2e-6 * scale)
    # the lookups' share is there: every row has the head's softmax
    # gradient, row 7 the sum of a run of forty lookups as well
    assert np.abs(got["interpret"]).max(axis=1).argmax() == 7


def lookup_model(aggr, width, devices, batch=8, seq=16):
    ff = FFModel(FFConfig(batch_size=batch, workers_per_node=devices))
    t = ff.create_tensor((batch, seq), dtype=DataType.INT32, name="ids")
    t = ff.embedding(t, 200, width, aggr=aggr, name="table")
    if aggr == AggrMode.AGGR_MODE_NONE:
        t = ff.flat(t)
    t = ff.dense(t, 4)
    ff.softmax(t)
    ff.compile(AdamOptimizer(alpha=1e-3),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
    assert ff.mesh.devices.size == devices
    return ff


ROUTES = {
    # name: (Pallas mode, aggregation, width, devices) -> by the kernel?
    "the_kernel": ("interpret", AggrMode.AGGR_MODE_NONE, 128, 1, True),
    "pallas_off": ("off", AggrMode.AGGR_MODE_NONE, 128, 1, False),
    "sum": ("interpret", AggrMode.AGGR_MODE_SUM, 128, 1, False),
    "avg": ("interpret", AggrMode.AGGR_MODE_AVG, 128, 1, False),
    "rows_that_do_not_fill_the_lanes": (
        "interpret", AggrMode.AGGR_MODE_NONE, 96, 1, False),
    "a_mesh_of_several_devices": (
        "interpret", AggrMode.AGGR_MODE_NONE, 128, 4, False),
}


@pytest.mark.parametrize("name", list(ROUTES))
def test_the_body_is_read_from_what_is_static(monkeypatch, name):
    """A traced train step of a model that looks rows up: the kernel's
    name in its jaxpr and the gauge at 1 where the rule takes the shape,
    `take`'s scatter-add and 0 everywhere else; the gradient the same."""
    mode, aggr, width, devices, by_kernel = ROUTES[name]
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
    ff = lookup_model(aggr, width, devices)
    assert ff.executor.traced_gauges()[
        "executor.embedding_sum_kernel_ops"] == 0      # nothing traced yet
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 200, (8, 16)).astype(np.int32)
    labels = rng.integers(0, 4, (8,)).astype(np.int32)
    step = ff.executor.make_train_step()
    jaxpr = str(jax.make_jaxpr(step)(
        ff.params, ff.opt_state, ff.state, ff._stage_inputs([ids]),
        ff._shard_batch(labels), jax.random.PRNGKey(0)))
    assert (embedding.SUM_KERNEL_NAME in jaxpr) == by_kernel
    # (`take`'s transpose: the scatter-add that gives the table's shape)
    into_table = re.search(r"f32\[200,%d\] = scatter-add" % width, jaxpr)
    assert bool(into_table) != by_kernel
    assert ff.executor.traced_gauges()[
        "executor.embedding_sum_kernel_ops"] == int(by_kernel)


def test_the_rule_by_its_sizes(monkeypatch):
    monkeypatch.setattr(pk, "pallas_mode", lambda: "tpu")
    rule = embedding.sums_rows_by_kernel
    # the nine decoder cells' (lookups, V, E)
    for shape in ((8192, 16384, 2688), (16384, 18992, 2560),
                  (16384, 18992, 2048), (4096, 16160, 2048),
                  (8192, 12544, 2048), (16384, 8192, 2048),
                  (4096, 6144, 2048), (8192, 25008, 2560)):
        assert rule(*shape)
    assert not rule(16384 + 64, 18992, 2560)     # no whole blocks of rows
    assert not rule(16384, 18992, 2560 + 64)     # rows that leave lanes
    assert not rule(16384, 18992, pk.MAX_SUM_WIDTH + 128)
    assert not rule(16384, 100, 2560)            # a table below one tile
    monkeypatch.setattr(pk, "pallas_mode", lambda: "off")
    assert not rule(16384, 18992, 2560)
