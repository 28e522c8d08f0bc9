"""The Mamba-2 scan's kernel pair (PR 62; `pallas_kernels.ssd_scan`) in
`interpret` mode against the recurrence a position (`ssd_stepwise`) AND
the `jax.numpy` chunked form (`ssd_chunked`), forward and every gradient
(x, B, C as the one array the convolution leaves, dt, A, D): float32 and
bfloat16 operands; 8 heads of 64 on one group, 4 heads on 2 groups, heads
of 128, every head its own group (two groups in a 128-lane tile), chunks
of 256; a length no chunk divides and one no block of rows divides; a
head whose decay is near 0 and one near 1 across the whole sample; batch
2 (the state starts from zero at each sample); the rule that says which
shapes the kernels take, and the mixer op under both routes."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from flexflow_tpu.ffconst import OperatorType  # noqa: E402
from flexflow_tpu.layer import Layer  # noqa: E402
from flexflow_tpu.ops import pallas_kernels as pk  # noqa: E402
from flexflow_tpu.ops.base import OpContext, OpRegistry  # noqa: E402
from flexflow_tpu.ops.ssm import ssd_chunked, ssd_stepwise  # noqa: E402

HIGHEST = jax.default_matmul_precision("highest")
LEAVES = ("xbc", "dt", "a", "d")


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")


@functools.partial(jax.jit, static_argnums=tuple(range(8)))
def scan_inputs(batch, length, heads, p, groups, n=128, dtype=jnp.float32,
                seed=0):
    """(xbc, dt, a, d) as the mixer hands them to its scan, and a weight
    for the output. Head 0 forgets at once (dt A about -40 a position),
    head 1 hardly at all (-1e-5): across the whole sample."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    xbc = jax.random.normal(ks[0], (batch, length, heads * p + 2 * groups * n))
    dt = jnp.exp(jax.random.uniform(ks[1], (batch, length, heads),
                                    minval=-6.9, maxval=-2.3))
    dt = dt.at[:, :, 0].set(2.5).at[:, :, 1].set(1e-3)
    a = -jax.random.uniform(ks[2], (heads,), minval=1.0, maxval=16.0)
    a = a.at[0].set(-16.0).at[1].set(-1e-2)
    d = 1.0 + 0.1 * jax.random.normal(ks[3], (heads,))
    wgt = jax.random.normal(ks[4], (batch, length, heads * p))
    return (xbc.astype(dtype), dt, a, d), wgt


def in_views(fn, groups, n=128, **kw):
    """`ssd_stepwise` / `ssd_chunked` (x [B, S, H, P], B and C [B, S, G,
    N]) with D x, over the kernels' operand forms."""
    def scan(xbc, dt, a, d):
        b, s, lanes = xbc.shape
        width = lanes - 2 * groups * n
        xs = xbc[..., :width].reshape(b, s, dt.shape[-1], -1)
        y = fn(xs, dt, a,
               xbc[..., width:width + groups * n].reshape(b, s, groups, n),
               xbc[..., width + groups * n:].reshape(b, s, groups, n), **kw)
        y = y + d[:, None] * xs.astype(jnp.float32)
        return y.reshape(b, s, width)

    return scan


def both_ways(fn, ins, wgt):
    """[y, d xbc, d dt, d A, d D] of a weighted sum of ``fn``, float32."""
    y, grads = jax.jit(lambda *a: (fn(*a), jax.grad(
        lambda *a: jnp.sum(fn(*a) * wgt), argnums=(0, 1, 2, 3))(*a)))(*ins)
    return [np.asarray(t, np.float32) for t in (y, *grads)]


def assert_close(got, want, tol, what):
    for name, a, b in zip(("y",) + LEAVES, got, want):
        top = float(np.max(np.abs(b)))
        assert float(np.max(np.abs(a - b))) <= tol * top, (what, name)


# (batch, length, heads, head size, groups, chunk, rows a grid step)
SHAPES = {
    "eight_heads_one_group.no_chunk_divides": (1, 200, 8, 64, 1, 128, None),
    "four_heads_two_groups": (1, 256, 4, 64, 2, 128, None),
    "a_group_a_head.two_groups_a_tile": (1, 128, 2, 64, 2, 128, None),
    "heads_of_128": (1, 256, 2, 128, 2, 128, None),
    "chunks_of_256": (1, 300, 2, 64, 1, 256, None),
    "no_block_divides.batch2": (2, 384, 2, 64, 1, 128, 256),
}


@pytest.mark.parametrize("case", sorted(SHAPES))
def test_kernels_match_the_recurrence_and_the_chunked_form(
        interpret, monkeypatch, case):
    """float32 operands: the three forms differ by summation order alone
    (a chunk's running sums and products against a position's steps), so
    2e-5 of a leaf's largest value holds them: six times the largest
    difference read (3.6e-6; the two `jax.numpy` forms stand 2.9e-6
    apart)."""
    batch, length, heads, p, groups, chunk, rows = SHAPES[case]
    if rows:    # three blocks of 256 rows hold 384 and 128 of padding
        monkeypatch.setattr(pk, "SSD_ROWS", rows)
    assert pk.ssd_shape_legal(length, heads, p, groups, 128, chunk)
    ins, wgt = scan_inputs(batch, length, heads, p, groups)
    with HIGHEST:
        got = both_ways(lambda *a: pk.ssd_scan(*a, groups, 128, chunk),
                        ins, wgt)
        step = both_ways(in_views(ssd_stepwise, groups), ins, wgt)
        chunked = both_ways(in_views(ssd_chunked, groups, chunk=chunk), ins,
                            wgt)
    assert_close(got, step, 2e-5, "stepwise")
    assert_close(got, chunked, 2e-5, "chunked")
    # the forgetful head's output is the present position's alone, and
    # the state of the one that remembers is still the sample's first
    # positions' at its end: both are there to be compared
    assert float(np.max(np.abs(step[0][..., :p]))) > 0
    assert float(np.max(np.abs(step[1][:, 0, p:2 * p]))) > 0


@pytest.mark.parametrize("heads,groups", [(8, 1), (4, 2)])
def test_bfloat16_operands_round_where_the_chunked_form_rounds(
        interpret, heads, groups):
    """bfloat16 products with float32 decays, sums and state, as the
    configuration states: against `ssd_chunked` at the same dtype the
    kernels differ by a rounding of a cotangent here and there (read: 7e-3
    of a leaf's largest value, two bfloat16 roundings; the limit is 2e-2),
    and both stand as far from the float32 recurrence."""
    ins, wgt = scan_inputs(1, 256, heads, 64, groups, dtype=jnp.bfloat16)
    got = both_ways(lambda *a: pk.ssd_scan(*a, groups, 128, 128), ins, wgt)
    chunked = both_ways(in_views(ssd_chunked, groups, chunk=128,
                                 compute_dtype=jnp.bfloat16), ins, wgt)
    with HIGHEST:
        step = both_ways(in_views(ssd_stepwise, groups), ins, wgt)
    assert_close(got, chunked, 2e-2, "chunked")
    assert_close(got, step, 2e-2, "stepwise")
    assert_close(chunked, step, 2e-2, "chunked against stepwise")


@pytest.mark.parametrize("shape,legal", [
    ((8192, 8, 64, 1, 128, 128), True),     # the nemotron cell's
    ((8192, 8, 64, 1, 128, 256), True),
    ((100, 4, 64, 2, 128, 128), True),      # any length: padded
    ((4096, 3, 128, 3, 128, 128), True),    # heads of 128, a group a head
    ((4096, 16, 64, 8, 128, 256), True),
    ((4096, 3, 64, 1, 128, 128), False),    # half a lane tile left over
    ((4096, 8, 32, 1, 128, 128), False),    # heads of 32
    ((4096, 8, 64, 1, 64, 128), False),     # a state of 64
    ((4096, 8, 64, 1, 128, 64), False),     # chunks of 64
    ((4096, 8, 64, 3, 128, 128), False),    # groups that cut a head
    ((4096, 32, 64, 1, 128, 128), False),   # wider than a block holds
])
def test_the_shapes_the_kernels_take(shape, legal):
    assert pk.ssd_shape_legal(*shape) is legal


def mixer_op(heads, state, seq=192, hidden=48):
    layer = Layer(OperatorType.SSM_MIXER, "mixer", [])
    layer.properties.update(num_heads=heads, head_dim=64, n_groups=1,
                            state_size=state, chunk_size=128)
    return OpRegistry.create(layer, [(2, seq, hidden)])


@pytest.mark.parametrize("state,kernel", [(128, 1), (64, 0)])
def test_the_mixer_takes_the_kernels_where_the_rule_says(monkeypatch, state,
                                                         kernel):
    """`SSMMixer` end to end, output and every leaf's gradient: the route
    with Pallas interpreted against the route with Pallas off. At a state
    of 128 the first is the kernel pair (`ssm/ssd_kernel_ops` 1) and the
    two differ by float32 summation order through the whole mixer (read:
    2.7e-5 of a leaf's largest value, in a leaf of two elements; the
    limit is 1e-4); a state of 64 is a shape the rule refuses, both routes are
    `ssd_chunked` and the gauge reads 0."""
    op = mixer_op(2, state)
    params = op.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 192, 48))
    ctx = OpContext(training=True, compute_dtype=jnp.float32)

    def run(mode):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
        out, grads = jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(jnp.sin(op.forward(p, [x], ctx)[0])),
            argnums=(0, 1)))(params, x)
        return op.traced_gauges(), jax.tree.leaves((out, grads))

    with HIGHEST:
        gauges, got = run("interpret")
        off_gauges, want = run("off")
    assert gauges == {"ssm/ssd_kernel_ops": kernel}
    assert off_gauges == {"ssm/ssd_kernel_ops": 0}
    assert op.scans_by_kernel(None) is False    # Pallas is off again
    for a, b in zip(got, want):
        top = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / top, np.asarray(b) / top,
                                   atol=1e-4)


def test_interior_bytes_count_what_the_route_keeps():
    """The search's memory price of the op: the state that enters every
    chunk always, a chunk's [Q, Q] decay tile a head only at a shape the
    kernels refuse."""
    kept, tiles = (mixer_op(2, n, seq=256).interior_bytes()
                   for n in (128, 64))
    per_chunk = 4 * 2 * 2 * 2    # float32, batch 2, 2 chunks, 2 heads
    rows = 2 * 256 * 4           # batch x positions x the op's bytes
    width = lambda n: 2 * (128 + 128 + 2 * n) + 2    # noqa: E731
    assert kept == rows * width(128) + per_chunk * 64 * 128
    assert tiles == rows * width(64) + per_chunk * (128 * 128 + 64 * 64)
