"""The gated delta rule's forms against each other (PR 58): the chunked
`jax.numpy` form against the recurrence a position at a length no chunk
divides, at C and 2 C, with a head that never forgets and one that
always does; the reference's own recurrence and its control; the
triangular inverse and its backward. And the kernels of PR 58 in
`interpret` mode against their `jax.numpy` forms: the delta rule's kernel pair (`pallas_kernels.delta_rule_fused`,
forward and backward, a grid step a KEY head since PR 60: value heads
once, twice and four times the key heads over one block of rows, twice
over two, and four times in steps of two) against the `jax.numpy`
chunked form and the recurrence a position; that dq and dk leave the
backward's call at key-head width; the flash kernels at a head of 256 lanes, 16 : 2
style groups, causal with and without a window and plain, against the
einsum path; the attention op with 64 rotated lanes of 256 and the gate a
lane through them; the rules that say where they run."""

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

from benchmarks.references import qwen3_next as ref  # noqa: E402
from flexflow_tpu.ffconst import OperatorType  # noqa: E402
from flexflow_tpu.layer import Layer  # noqa: E402
from flexflow_tpu.ops import delta_rule as dr  # noqa: E402
from flexflow_tpu.ops import pallas_kernels as pk  # noqa: E402
from flexflow_tpu.ops.base import OpContext, OpRegistry  # noqa: E402

HIGHEST = jax.default_matmul_precision("highest")


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")


# (operands are made by ONE program a shape: eagerly every `jax.random`
# and `at[].set` call is a program of its own, ROADMAP D10)
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def small_rule_inputs(length, hk=2, hv=4, d=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (2, length, hk, d))
    k = jax.random.normal(ks[1], (2, length, hk, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (2, length, hv, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (2, length, hv), minval=-6.0,
                                    maxval=1.0))
    # a head that remembers everything and one that forgets at once
    g = g.at[:, :, 0].set(-1e-5).at[:, :, 1].set(-30.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, length, hv)))
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_rule_matches_the_recurrence_a_position(chunk):
    ins = small_rule_inputs(37)       # no chunk divides it
    weight = jnp.cos(jnp.arange(8.0))

    def grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4)))

    with HIGHEST:
        want = jax.jit(dr.delta_rule_stepwise)(*ins)
        got = jax.jit(lambda *a: dr.delta_rule_chunked(*a, chunk))(*ins)
        _, dwant = grads(dr.delta_rule_stepwise)(*ins)
        _, dgot = grads(lambda *a: dr.delta_rule_chunked(*a, chunk))(*ins)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the forgetful head's output is the present position's alone
    assert float(jnp.max(jnp.abs(want[:, :, 1]))) > 0
    for name, a, b in zip("q k v g beta".split(), dgot, dwant):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=2e-5,
                                   err_msg=name)


def test_the_reference_runs_the_same_recurrence_and_its_two_controls():
    q, k, v, g, beta = small_rule_inputs(21)
    rep = lambda t: jnp.repeat(t, 2, axis=2)    # noqa: E731
    with HIGHEST:       # one program
        want, got, plain = jax.jit(lambda q, k, v, g, beta: (
            dr.delta_rule_stepwise(q, k, v, g, beta),
            ref.delta_rule(rep(q), rep(k), v, g, beta),
            ref.delta_rule(rep(q), rep(k), v, g, beta, correction=False)))(
                q, k, v, g, beta)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # without the correction the state only accumulates: another output
    assert float(jnp.max(jnp.abs(plain - want))) > 1e-2


def test_unit_lower_inverse_and_its_own_backward():
    a = jnp.tril(0.3 * jax.random.normal(jax.random.PRNGKey(2), (3, 16, 16)),
                 -1)
    w = jax.random.normal(jax.random.PRNGKey(3), a.shape)
    with HIGHEST:
        t, got, want = jax.jit(lambda a: (
            dr.unit_lower_inverse(a),
            jax.grad(lambda a: jnp.sum(dr.unit_lower_inverse(a) * w))(a),
            jax.grad(lambda a: jnp.sum(
                jnp.linalg.inv(jnp.eye(16) + a) * w))(a)))(a)
    np.testing.assert_allclose(
        np.asarray(t) @ (np.eye(16, dtype=np.float32) + np.asarray(a)),
        np.broadcast_to(np.eye(16, dtype=np.float32), a.shape), atol=1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def core_inputs(length, hk=1, hv=2, d=128, seed=0):
    """(qkv, z, g, beta, the norm's scale) as the op hands them over."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    qkv = jax.random.normal(ks[0], (1, length, (2 * hk + hv) * d))
    z = jax.random.normal(ks[1], (1, length, hv * d))
    g = -jnp.exp(jax.random.uniform(ks[3], (1, length, hv), minval=-6.0,
                                    maxval=1.0))
    g = g.at[:, :, 0].set(-1e-3)        # a head that remembers
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, length, hv)))
    scale = 1.0 + 0.1 * jax.random.normal(ks[5], (d,))
    return qkv, z, g, beta, scale


def rule_both_ways(kernel, key_heads, lanes):
    """The value and the five gradients of a weighted sum of
    `delta_rule_core`'s output, jitted."""
    weight = jnp.cos(jnp.arange(float(lanes)))
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(dr.delta_rule_core(
            *a, key_heads, 128, 1e-6, jnp.float32, kernel) * weight),
        argnums=(0, 1, 2, 3, 4)))


@pytest.mark.parametrize("length,hk,rep", [
    (384, 1, 1),        # every value head its own keys
    (384, 1, 2),
    (384, 2, 4),        # the second key head's lanes and rows
    (2048, 1, 2)])
def test_rule_kernels_match_the_scan_forward_and_backward(interpret, length,
                                                          hk, rep):
    """One block of three chunks (the whole sequence) with one, two and
    four value heads a key head walked together in a grid step (the four
    under two key heads), and two blocks of eight chunks with the heads' states and
    their gradients carried between grid steps: the SiLU, the heads' L2
    norms, the rule and the gated head norm as ONE kernel each way
    against the `jax.numpy` form, and that against the recurrence a
    position."""
    hv = hk * rep
    assert pk.delta_heads_a_step(rep) == rep
    ins = core_inputs(length, hk, hv)
    with HIGHEST:
        want, dwant = rule_both_ways(False, hk, hv * 128)(*ins)
        got, dgot = rule_both_ways(True, hk, hv * 128)(*ins)
        if (length, rep) == (384, 2):
            def a_position(qkv, z, g, beta, scale):
                f = jax.nn.silu(qkv).reshape(1, length, 2 * hk + hv, 128)
                q, k, v = f[:, :, :hk], f[:, :, hk:2 * hk], f[:, :, 2 * hk:]
                q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
                    * 128 ** -0.5
                k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
                return dr.heads_rms_norm_gated(
                    dr.delta_rule_stepwise(q, k, v, g, beta),
                    z.reshape(1, length, hv, 128), scale, 1e-6)

            step = jax.jit(a_position)(*ins)
            out = jax.jit(lambda *a: dr.delta_rule_core(
                *a, hk, 128, 1e-6, jnp.float32, True))(*ins)
            np.testing.assert_allclose(out.reshape(step.shape), step,
                                       rtol=1e-3, atol=5e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, a, b in zip("qkv z g beta scale".split(), dgot, dwant):
        scale_ = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / scale_,
                                   np.asarray(b) / scale_, atol=2e-5,
                                   err_msg=name)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (the kernels' own bodies apart: what XLA runs)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("rep,most,width,sums", [
    (2, 4, 1, 0),       # the cell's: a key head a step, nothing to add
    (4, 2, 2, 2)])      # four heads in steps of two: XLA adds the steps'
def test_dq_and_dk_leave_the_backward_at_key_head_width(
        interpret, monkeypatch, rep, most, width, sums):
    """The backward's `pallas_call` returns dq and dk as [B, S, Hk * 128]
    where a grid step walks all of a key head's value heads, and the
    gradient of `delta_rule_fused` then holds no sum over them outside
    the kernels; where a key head takes several steps
    (`delta_heads_a_step`) XLA adds the steps' and the result is the
    same."""
    hk, length = 1, 256
    qkv, z, g, beta, scale = core_inputs(length, hk, hk * rep)

    def rows(t):
        return jnp.moveaxis(t, 1, 2)[:, :, None]

    args = (qkv, z, rows(jnp.cumsum(g.reshape(1, 2, 128, -1), 2).reshape(
        g.shape)), rows(beta), scale[None])

    def grad():     # a function of its own a trace: no cached one is reused
        return jax.grad(lambda *a: jnp.sum(pk.delta_rule_fused(
            *a, hk, 1e-6) ** 2), argnums=(0, 1, 2, 3, 4))

    with HIGHEST:
        want = jax.jit(grad())(*args)
        monkeypatch.setattr(pk, "MAX_DELTA_HEADS_A_STEP", most)
        assert pk.delta_heads_a_step(rep) == rep // width
        eqns = list(_equations(jax.make_jaxpr(grad())(*args).jaxpr))
        got = jax.jit(grad())(*args)
    (bwd,) = [e for e in eqns if e.primitive.name == "pallas_call"
              and e.params["name"] == "delta_rule_bwd"]
    dq, dk, dv = (v.aval.shape for v in bwd.outvars[:3])
    assert dq == dk == (1, length, hk * width * 128)
    assert dv == (1, length, hk * rep * 128)
    assert bwd.params["grid_mapping"].grid == (1, hk * width, 1)
    # a sum over the value heads of a key head is a reduction of a
    # [B, S, Hk, steps, 128] view
    assert sum(e.primitive.name == "reduce_sum"
               and len(e.invars[0].aval.shape) == 5 for e in eqns) == sums
    for name, a, b in zip("qkv z g beta scale".split(), got, want):
        top = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / top, np.asarray(b) / top,
                                   atol=0 if width == 1 else 1e-6,
                                   err_msg=name)


def test_the_heads_a_grid_step_walks(interpret, monkeypatch):
    """`delta_heads_a_step`, and `executor.delta_rule_heads_a_step` of
    an op whose forward was traced: the value heads a key head where the
    kernels run, 0 on the `lax.scan`."""
    assert [pk.delta_heads_a_step(rep) for rep in (1, 2, 3, 4, 6, 7, 8)] == [
        1, 2, 3, 4, 3, 1, 4]

    def traced(value_heads):
        layer = Layer(OperatorType.DELTA_MIXER, "op", [])
        layer.properties.update(num_key_heads=1, num_value_heads=value_heads,
                                key_head_dim=128, value_head_dim=128)
        op = OpRegistry.create(layer, [(1, 256, 64)])
        assert op.traced_gauges()["executor.delta_rule_heads_a_step"] == 0
        jax.eval_shape(
            lambda p, x: op.forward(p, [x], OpContext(
                training=True, compute_dtype=jnp.float32)),
            jax.eval_shape(op.init_params, jax.random.PRNGKey(0)),
            jax.ShapeDtypeStruct((1, 256, 64), jnp.float32))
        return op.traced_gauges()

    assert traced(2) == {"executor.delta_mixer_ops": 1,
                         "executor.delta_rule_kernel_ops": 1,
                         "executor.delta_rule_heads_a_step": 2}
    assert traced(1)["executor.delta_rule_heads_a_step"] == 1
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    assert traced(2) == {"executor.delta_mixer_ops": 1,
                         "executor.delta_rule_kernel_ops": 0,
                         "executor.delta_rule_heads_a_step": 0}


def test_where_the_walk_runs_as_kernels(interpret, monkeypatch):
    legal = pk.delta_rule_shape_legal
    assert legal(16384, 128, 128, 128) and legal(384, 128, 128, 128)
    assert legal(4096, 128, 128, 128) and not legal(4096 + 128, 128, 128, 128)
    assert not legal(16384, 64, 128, 128) and not legal(16384, 128, 128, 64)
    assert not legal(200, 128, 128, 128)
    layer = Layer(OperatorType.DELTA_MIXER, "op", [])
    layer.properties.update(num_key_heads=1, num_value_heads=2,
                            key_head_dim=128, value_head_dim=128)
    op = OpRegistry.create(layer, [(1, 256, 64)])
    assert op.walks_by_kernel(None)
    assert not op.walks_by_kernel(None, seq=200)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    assert not op.walks_by_kernel(None)


def heads(t, n):
    return pk.split_heads(t, n)[0]


def einsum_attention(q, k, v, h, hk, causal, window):
    q, k, v = heads(q, h), heads(k, hk), heads(v, hk)
    k, v = (jnp.repeat(t, h // hk, axis=0) for t in (k, v))
    o = pk._xla_attention(q, k, v, causal, window)
    return pk.merge_heads(o[None])


@pytest.mark.parametrize("seq,causal,window,h,hk", [
    (2048, True, 0, 4, 2),      # forward 512 x 1024: interior and edge tiles
    (1536, True, 300, 4, 2),    # a window that ends inside a block
    (1024, False, 0, 4, 2),
    # the one-kernel backward (PR 59; tiles of 1024, two K blocks a run at
    # 2,048 positions, four at 4,096): K blocks past the first leave the
    # Q blocks ahead of them untouched, and the dQ sum of a Q block
    # leaves for HBM and comes back between runs
    (2048, True, 0, 1, 1),      # every head its own keys: dK, dV as stored
    (2048, True, 700, 4, 1),    # a window that skips the oldest K block
    (2048, False, 0, 2, 2),
    (1536, True, 0, 2, 2),      # three blocks of 512, one a run
    (5120, True, 0, 1, 1),      # five K blocks, one a run: dQ through HBM
    (4096, True, 1500, 1, 1)])  # four K blocks a run under a window
def test_flash_at_a_head_of_256_matches_the_einsum_path(interpret, seq,
                                                        causal, window, h,
                                                        hk):
    d = 256
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (1, seq, h * d))
    k = jax.random.normal(ks[1], (1, seq, hk * d))
    v = jax.random.normal(ks[2], (1, seq, hk * d))
    weight = jax.random.normal(ks[3], (1, seq, h * d))
    assert pk.flash_attention_available(seq, d, h)
    assert pk.grouped_kv_shape_legal(h, hk, d) == (hk < h)
    assert pk.grouped_kv_shape_legal(16, 2, 256)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2)))

    with HIGHEST:
        want, dwant = both(lambda *a: einsum_attention(
            *a, h, hk, causal, window))(q, k, v)
        got, dgot = both(lambda *a: pk.flash_attention(
            *a, h, causal=causal, window=window,
            num_kv_heads=hk if hk < h else None))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, a, b in zip("qkv", dgot, dwant):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=name)
    visited, total, masked = pk.wide_kv_blocks(seq, causal, window)
    assert 0 < visited <= total and masked <= visited
    if (seq, causal, window) == (2048, True, 0):
        assert (visited, total, masked) == (6, 8, 4)
        assert pk._wide_bwd_blocks(seq) == (1024, 1024, 2)
        assert pk.wide_bwd_score_tiles(seq, causal, window) == 3


def test_wide_backward_blocks_and_the_tiles_it_forms():
    """The backward's own blocks: tiles of 1024 where the length allows,
    four K blocks a run where their count allows; its score tiles a head
    at the qwen3_next cell's length are the forward's visited pairs."""
    assert pk._wide_bwd_blocks(16384) == (1024, 1024, 4)
    assert pk._wide_bwd_blocks(4096) == (1024, 1024, 4)
    assert pk._wide_bwd_blocks(5120) == (1024, 1024, 1)
    assert pk._wide_bwd_blocks(1536) == (512, 512, 1)
    assert pk._wide_bwd_blocks(640) == (128, 128, 1)
    assert pk.wide_bwd_score_tiles(16384, True) == 136
    assert pk.wide_kv_blocks(16384, True)[0] == 272    # of half the rows
    assert pk.wide_bwd_score_tiles(16384, False) == 256
    # a window of 4,096: a Q block of 1,024 meets at most five K blocks
    assert pk.wide_bwd_score_tiles(16384, True, 4096) == sum(
        min(i, 4) + 1 for i in range(16))


@pytest.mark.parametrize("seq,window,h,hk", [(2048, 0, 2, 1),
                                             (4096, 1500, 1, 1)])
def test_one_kernel_wide_backward_keeps_the_two_kernel_sum_order(
        interpret, seq, window, h, hk):
    """dQ of the one kernel (its sum leaving for HBM between runs of K
    blocks) against the two kernels PR 58 shipped, which `scripts/
    delta_lab.py` keeps: K blocks of 1024 ascending in both, so dQ is
    the same float32 sum to round-off (a Q block of 1024 adds the same
    tiles' products in the same order as two of 512); dK and dV add their
    Q rows 1024 at a time where the two kernels added 512."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import delta_lab
    d = 256
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    q = jax.random.normal(ks[0], (1, seq, h * d))
    k = jax.random.normal(ks[1], (1, seq, hk * d))
    v = jax.random.normal(ks[2], (1, seq, hk * d))
    do = jax.random.normal(ks[3], (1, seq, h * d))
    glse = jax.random.normal(ks[4], (1, h, 1, seq))
    o, lse = pk._flash_fwd(q, k, v, h, True, True, window=window,
                           num_kv_heads=hk)
    args = (q, k, v, o, lse, do, h, True, True, window, hk, glse)
    with HIGHEST:
        got = jax.jit(lambda: pk._wide_flash_bwd(*args))()
        want = jax.jit(lambda: delta_lab.two_kernel_wide_flash_bwd(*args))()
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   atol=1e-7 if name == "dq" else 2e-6,
                                   err_msg=name)


@pytest.mark.parametrize("causal", [True, False])
def test_wide_backward_carries_the_logsumexp_cotangent(interpret, causal):
    """A ``glse`` cotangent (the ring's merge) through the one-kernel
    backward at a head of 256: `flash_attention_lse`'s gradients of a
    loss on both outputs against the einsum path's."""
    h, d, seq = 2, 256, 2048
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q, k, v, wo = (jax.random.normal(key, (1, seq, h * d)) for key in ks[:4])
    wl = jax.random.normal(ks[4], (1, h, seq))

    def einsum_both(q, k, v):
        o, lse = pk._xla_attention_lse(heads(q, h), heads(k, h),
                                       heads(v, h), causal)
        return pk.merge_heads(o[None]), lse[None]

    def loss(fn):
        def run(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(o * wo) + jnp.sum(lse * wl)
        return jax.jit(jax.grad(run, argnums=(0, 1, 2)))

    with HIGHEST:
        want = loss(einsum_both)(q, k, v)
        got = loss(lambda *a: pk.flash_attention_lse(
            *a, h, causal, True))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=name)


def test_attention_op_at_heads_of_256_runs_the_wide_kernels(interpret):
    """16 : 2 style groups at a head of 256, 64 rotated lanes, the heads'
    zero-centred norm and the gate a lane: the op through the kernels
    against itself on the einsum core."""
    props = dict(embed_dim=64, num_heads=4, num_kv_heads=2, head_dim=256,
                 bias=False, causal=True, rope=True, rope_theta=1e7,
                 partial_rotary_factor=0.25, qk_norm=True,
                 qk_norm_zero_centered=True, lane_gate=True)

    def op_of(**more):
        layer = Layer(OperatorType.MULTIHEAD_ATTENTION, "attn", [])
        layer.properties.update(props, **more)
        return OpRegistry.create(layer, [(1, 1024, 64)] * 3)

    op, plain = op_of(), op_of(kernel_impl="einsum")
    route = op.route({}, True)
    assert (route.core, route.wide_head, route.grouped_kv) == (
        "flash", True, True)
    assert not route.rotary_in_lanes and not route.super_block
    assert route.kv_blocks == pk.wide_kv_blocks(1024, True, 0)
    assert plain.route({}, True).core == "einsum"
    assert op.rotary_dim == 64
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 1024, 64))
    p = op.init_params(jax.random.PRNGKey(3))
    p = dict(p, q_norm=p["q_norm"] + 1.5, k_norm=p["k_norm"] + 1.5)
    ctx = OpContext(training=True, compute_dtype=jnp.float32)

    def loss(o):
        return jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(
            o.forward(p, [x, x, x], ctx)[0] ** 2), argnums=(0, 1)))

    with HIGHEST:
        (got, dgot), (want, dwant) = loss(op)(p, x), loss(plain)(p, x)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(dgot), jax.tree.leaves(dwant)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=2e-5)
    assert op.traced_gauges()["executor.flash_wide_head_ops"] == 1
    # one [1024, 1024] tile a head whose scores the backward forms, once
    assert route.wide_bwd_score_tiles == 1
    assert op.traced_gauges()["executor.flash_wide_bwd_score_tiles"] == (
        pk.wide_bwd_score_tiles(1024, True, 0))
    assert plain.traced_gauges()["executor.flash_wide_bwd_score_tiles"] == 0
    assert op.traced_gauges()["executor.flash_grouped_kv_ops"] == 1
    # a mask the wide kernels do not take keeps the einsum core
    layer = Layer(OperatorType.MULTIHEAD_ATTENTION, "bd", [])
    layer.properties.update(embed_dim=64, num_heads=4, head_dim=256,
                            bias=False, block_diffusion=(512, 4))
    masked = OpRegistry.create(layer, [(1, 1024, 64)] * 3)
    assert masked.route({}, True).core == "einsum"
    assert masked.route({}, True).blocked == "shape"
