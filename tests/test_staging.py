"""Input staging (`FFModel._shard_batch` / `_stage_inputs`).

The host hands a batch to the runtime as its raw row-major bytes and a
small jitted program shapes, casts and lays it out on the device. What
reaches the train step has to be bit for bit what the earlier recipe
staged, `jnp.asarray(x).astype(compute_dtype)` placed on the same
sharding, on every batch layout the executors have; a shape is compiled
once; the program moves nothing between devices.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import (FFConfig, FFModel, LossType, SGDOptimizer,
                          create_data_loaders)
from flexflow_tpu.machine import make_mesh

LAYOUTS = ("one_device", "data4", "pipeline")


def _two_input_mlp(mesh_axes):
    ff = FFModel(FFConfig(batch_size=8, seed=3))
    a = ff.create_tensor((8, 6))
    b = ff.create_tensor((8, 10))
    t = ff.concat([ff.dense(a, 4), ff.dense(b, 4)], axis=1)
    ff.dense(t, 2)
    n = int(np.prod(list(mesh_axes.values())))
    ff.compile(SGDOptimizer(lr=0.1), LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [], mesh=make_mesh(n, mesh_axes))
    return ff


def _pipelined_transformer():
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 create_transformer)
    cfg = TransformerConfig(num_layers=2, hidden_size=32, num_heads=2,
                            seq_length=8, batch_size=8)
    c = FFConfig(batch_size=8, seed=3, pipeline_schedule="circular")
    c.pipeline_microbatches = 4
    ff = create_transformer(cfg, c)
    ff.compile(SGDOptimizer(lr=0.01), LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [], mesh=make_mesh(4, {"pipe": 2, "data": 2}))
    return ff


@pytest.fixture(scope="module")
def models():
    """One compiled model a batch layout. The CPU mesh computes in
    float32; the staging under test is given the chip's bf16, so that the
    cast is one."""
    out = dict(one_device=_two_input_mlp({"data": 1}),
               data4=_two_input_mlp({"data": 4}),
               pipeline=_pipelined_transformer())
    for ff in out.values():
        ff.executor.compute_dtype = jnp.bfloat16
    return out


def test_the_layouts_are_the_ones_meant(models):
    def spec(s):
        return tuple(s.spec)

    one, data4, pipe = (models[k].executor for k in LAYOUTS)
    assert one.mesh.devices.size == 1
    assert spec(data4.batch_sharding()) in ((("data",),), ("data",))
    assert data4.mesh.devices.size == 4
    # the pipeline's sharded microbatch queue: inputs over pipe x data,
    # labels on the data axis alone
    assert spec(pipe.batch_sharding()) == (("pipe", "data"),)
    assert spec(pipe.label_sharding()) in ((("data",),), ("data",))


def parent_recipe(ff, arr, cast=False, inputs=False):
    """`_shard_batch` as it was before PR 26."""
    arr = jnp.asarray(arr)
    if cast and jnp.issubdtype(arr.dtype, jnp.floating):
        arr = arr.astype(ff.executor.compute_dtype)
    return jax.device_put(arr, ff.executor.batch_sharding() if inputs
                          else ff.executor.label_sharding())


def assert_same_staged(got, want):
    assert got.dtype == want.dtype
    assert got.shape == want.shape
    assert got.sharding == want.sharding
    assert got.committed and want.committed
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    for g, w in zip(got.addressable_shards, want.addressable_shards):
        assert (g.device, g.index) == (w.device, w.index)
        assert np.asarray(g.data).tobytes() == np.asarray(w.data).tobytes()


def _floats(shape, seed, dtype=np.float32):
    """Values whose rounding to bf16 is not trivial, with the specials."""
    x = np.random.default_rng(seed).standard_normal(shape).astype(dtype)
    flat = x.reshape(-1)
    flat[:8] = [0.0, -0.0, np.inf, -np.inf, np.nan, 1e-40, 3.0e38, 1.00390625]
    return x


def _input_case(arr):
    return lambda ff: [(ff._shard_batch(arr, cast=True, inputs=True),
                        parent_recipe(ff, arr, cast=True, inputs=True))]


def _label_case(arr):
    return lambda ff: [(ff._shard_batch(arr), parent_recipe(ff, arr))]


def _all_inputs_case(ff):
    """`_stage_inputs` over every input of the model at once: two of
    unlike widths for the MLPs, the pipeline's one."""
    xs = [_floats(tuple(t.shape), 40 + i)
          for i, t in enumerate(ff.input_tensors)]
    got = ff._stage_inputs(xs)
    assert list(got) == list(ff.executor.input_names)
    return [(got[n], parent_recipe(ff, x, cast=True, inputs=True))
            for n, x in zip(ff.executor.input_names, xs)]


CASES = dict(
    inception_like_f32=_input_case(_floats((8, 3, 19, 19), 1)),
    sequence_f32=_input_case(_floats((4, 16, 32), 2)),
    int32_ids_uncast=_input_case(
        np.random.default_rng(3).integers(-5, 30000, (8, 16), dtype=np.int32)),
    float32_labels_uncast=_label_case(_floats((8, 1), 4)),
    int64_class_labels=_label_case(
        np.random.default_rng(5).integers(0, 1000, (8,), dtype=np.int64)),
    float64_input=_input_case(_floats((8, 5), 6, np.float64)),
    non_contiguous=_input_case(_floats((8, 12), 7)[:, ::-1]),
    a_row_slice_of_the_dataset=_input_case(_floats((32, 3, 5), 8)[8:16]),
    all_inputs_at_once=_all_inputs_case,
)


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("layout", LAYOUTS)
def test_staged_bitwise_equal_to_the_parents_recipe(models, layout, case):
    pairs = CASES[case](models[layout])
    assert pairs
    for got, want in pairs:
        assert_same_staged(got, want)


def test_bf16_cast_is_a_real_one(models):
    x = _floats((8, 3, 19, 19), 1)
    got = models["data4"]._shard_batch(x, cast=True, inputs=True)
    assert got.dtype == jnp.bfloat16
    assert not np.array_equal(np.asarray(got).astype(np.float32)[1], x[1])


class Compiles:
    """Backend compiles, counted as the benchmark's harness counts them."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.n += 1


@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_shape_compiles_once(models, layout):
    ff = models[layout]
    seen = Compiles()
    x = _floats((8, 7, 3), 9)     # a shape no other test stages
    ff._shard_batch(x, cast=True, inputs=True)
    first = seen.n
    assert first >= 1             # the control: the listener sees compiles
    programs = len(ff._unpackers)
    for _ in range(3):
        ff._shard_batch(x + 1, cast=True, inputs=True)
    assert seen.n == first
    assert len(ff._unpackers) == programs
    ff._shard_batch(x, inputs=False)          # labels: another program
    assert len(ff._unpackers) == programs + 1


@pytest.fixture
def small_pieces(monkeypatch):
    """Pieces of 1 KiB, so that a test-sized batch is handed over in
    several."""
    from flexflow_tpu import model as ffmodel
    monkeypatch.setattr(ffmodel, "_RAW_PIECE_BYTES", 1 << 10)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_handed_over_in_pieces_of_whole_rows(models, layout, small_pieces):
    """Rows of 140 bytes, seven to a piece of about 1 KiB: a shard's 32
    or 8 rows go over in five or two pieces, the last one shorter."""
    ff = models[layout]
    x = _floats((32, 5, 7), 11)
    got = ff._shard_batch(x, cast=True, inputs=True)
    sharding = ff.executor.batch_sharding()
    _, shards, bounds, _ = ff._unpackers[
        (x.shape, np.dtype(np.float32), np.dtype(jnp.bfloat16), sharding)]
    assert shards == len({s.index for s in got.addressable_shards})
    rows = 32 // shards
    assert bounds == [min(r, rows) * 35 for r in range(0, rows + 7, 7)]
    assert len(bounds) - 1 == -(-rows // 7) > 1
    assert_same_staged(got, parent_recipe(ff, x, cast=True, inputs=True))


@pytest.mark.parametrize("pieces", ("one_piece", "several_pieces"))
@pytest.mark.parametrize("layout", ("data4", "pipeline"))
def test_unpack_moves_nothing_between_devices(models, layout, pieces,
                                              request):
    if pieces == "several_pieces":
        request.getfixturevalue("small_pieces")
    ff = models[layout]
    # a plan is kept by shape: each variant stages a shape of its own
    x = _floats((8, 5, 19, 19 if pieces == "one_piece" else 17), 12)
    ff._shard_batch(x, cast=True, inputs=True)
    sharding = ff.executor.batch_sharding()
    flat, shards, bounds, fn = ff._unpackers[
        (x.shape, np.dtype(np.float32), np.dtype(jnp.bfloat16), sharding)]
    assert (len(bounds) > 2) == (pieces == "several_pieces")
    raw = [jax.ShapeDtypeStruct((shards * (hi - lo),), jnp.float32,
                                sharding=flat)
           for lo, hi in zip(bounds, bounds[1:])]
    hlo = fn.lower(*raw).compile().as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in hlo, collective


def test_the_host_side_is_a_view(models, monkeypatch, small_pieces):
    """A contiguous batch is handed over without a host copy: every piece
    the runtime gets shares the caller's memory."""
    handed = []
    real = jax.make_array_from_callback

    def spy(shape, sharding, callback):
        def seen(index):
            handed.append(callback(index))
            return handed[-1]
        return real(shape, sharding, seen)

    monkeypatch.setattr(jax, "make_array_from_callback", spy)
    data = _floats((32, 61, 5), 8)      # rows of 1220 bytes: one a piece
    models["data4"]._shard_batch(data[8:16], cast=True, inputs=True)
    assert len(handed) == 4 * 2     # devices x pieces
    assert all(h.ndim == 1 and np.shares_memory(h, data) for h in handed)
    assert sum(h.size for h in handed) == data[8:16].size


def test_a_device_array_takes_the_earlier_path(models):
    ff = models["data4"]
    x = jnp.asarray(_floats((8, 3, 19, 19), 1))
    assert not ff._stages_raw(x)
    assert_same_staged(ff._shard_batch(x, cast=True, inputs=True),
                       parent_recipe(ff, x, cast=True, inputs=True))


def test_fit_and_predict_see_the_same_values():
    """End to end on a model left in its own dtype: `fit` on host arrays
    and the step on the parent's staging give the same loss."""
    rs = np.random.RandomState(0)
    x = [rs.randn(8, 6).astype(np.float32), rs.randn(8, 10).astype(np.float32)]
    y = rs.randn(8, 2).astype(np.float32)
    ff, ref = _two_input_mlp({"data": 4}), _two_input_mlp({"data": 4})
    np.testing.assert_array_equal(ff.predict(x), ref.predict(x))
    ff.set_batch(x, y)
    ff.forward(); ff.backward(); ff.update()
    ref._current_batch = (
        {n: parent_recipe(ref, a, cast=True, inputs=True)
         for n, a in zip(ref.executor.input_names, x)},
        parent_recipe(ref, y))
    ref.forward(); ref.backward(); ref.update()
    assert np.float32(ff._last_loss) == np.float32(ref._last_loss)


@pytest.mark.parametrize("layout", ("one_device", "data4"))
def test_host_resident_loader_stages_as_the_model_does(models, layout):
    """`SingleDataLoader`'s host-resident batches go through
    `_shard_batch`, uncast, and hold what the device-resident loader's do
    (whose slices XLA places where it likes)."""
    ff = models[layout]
    xs = [_floats((16,) + tuple(t.shape[1:]), 50 + i)
          for i, t in enumerate(ff.input_tensors)]
    y = _floats((16, 2), 60)
    host = create_data_loaders(ff, xs, y, stage_on_device=False)
    device = create_data_loaders(ff, xs, y, stage_on_device=True)
    for b in (0, 1, 0):     # two batches and the wrap-around
        (hi, hl), (di, dl) = host.next_batch(), device.next_batch()
        rows = slice(8 * b, 8 * b + 8)
        for n, x in zip(ff.executor.input_names, xs):
            assert_same_staged(hi[n], parent_recipe(ff, x[rows], inputs=True))
            assert np.asarray(hi[n]).tobytes() == np.asarray(di[n]).tobytes()
        assert_same_staged(hl, parent_recipe(ff, y[rows]))
        assert np.asarray(hl).tobytes() == np.asarray(dl).tobytes()
