"""Ring attention (sequence/context parallelism) + Pallas flash attention.

SURVEY §5.7: the reference has NO sequence parallelism — this is the
first-class TPU capability that replaces it. Numerics are validated
against the dense einsum attention path.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from flexflow_tpu.machine import make_mesh
from flexflow_tpu.ops.attention import scaled_dot_product_attention
from flexflow_tpu.parallel.ring_attention import ring_attention


def qkv(b=4, h=2, s=32, d=8, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, h, s, d).astype(np.float32))
    return mk(), mk(), mk()


def flash_heads_first(q, k, v, causal, run=None):
    """The kernels take [B, S, H*D]; these tests hold [B, H, S, D] like
    the einsum core they compare with, and convert at the boundary."""
    from flexflow_tpu.ops.pallas_kernels import (flash_attention,
                                                 merge_heads, split_heads)
    h = q.shape[1]
    run = run or flash_attention
    return split_heads(run(merge_heads(q), merge_heads(k), merge_heads(v),
                           h, causal=causal), h)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_attention(self, causal):
        mesh = make_mesh(8, {"data": 2, "seq": 4})
        q, k, v = qkv()
        want = scaled_dot_product_attention(q, k, v, causal=causal)
        got = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh, causal=causal)
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_seq_only_mesh(self):
        mesh = make_mesh(8, {"seq": 8})
        q, k, v = qkv(s=64)
        want = scaled_dot_product_attention(q, k, v, causal=True)
        got = ring_attention(q, k, v, mesh, batch_axis=None, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.slow
    def test_gradients_flow(self):
        mesh = make_mesh(8, {"data": 2, "seq": 4})
        q, k, v = qkv()

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(
                scaled_dot_product_attention(q, k, v, causal=True) ** 2)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_dense):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)


class TestFlashAttention:
    @pytest.fixture(autouse=True)
    def _interpret_mode(self, monkeypatch):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        from flexflow_tpu.ops.pallas_kernels import flash_attention_available

        assert flash_attention_available(256, 8, 2)
        q, k, v = qkv(b=2, h=2, s=256, d=8, seed=1)
        want = scaled_dot_product_attention(q, k, v, causal=causal)
        got = flash_heads_first(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_backward_matches_dense(self):
        q, k, v = qkv(b=1, h=2, s=128, d=8, seed=2)
        g1 = jax.grad(lambda q: jnp.sum(
            flash_heads_first(q, k, v, True) ** 2))(q)
        g2 = jax.grad(lambda q: jnp.sum(
            scaled_dot_product_attention(q, k, v, causal=True) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-3, atol=1e-4)

    def test_unavailable_for_ragged_seq(self):
        from flexflow_tpu.ops.pallas_kernels import flash_attention_available

        assert not flash_attention_available(100, 8, 2)  # S % 128 != 0

    def test_sharded_flash_on_dp_mp_mesh(self):
        # round-1 advisor finding: a bare pallas_call inside a GSPMD jit is
        # an unpartitionable custom call. The shard_map wrapper must
        # compile on a dp x mp mesh and match the einsum path.
        from flexflow_tpu.ops.pallas_kernels import flash_attention_sharded

        mesh = make_mesh(8, {"data": 2, "model": 4})
        q, k, v = qkv(b=2, h=4, s=128, d=8, seed=3)
        want = scaled_dot_product_attention(q, k, v, causal=True)
        # [2, 128, 4*8] with the heads' lanes over 'model': a shard holds
        # one head, its whole row one column block
        sharded = lambda q, k, v, h, causal: flash_attention_sharded(
            q, k, v, h, mesh, batch_axis="data", head_axis="model",
            causal=causal)
        got = jax.jit(lambda q, k, v: flash_heads_first(
            q, k, v, True, run=sharded))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_attention_op_picks_sharded_flash_under_mesh(self):
        # the op's own dispatch: non-trivial mesh + flash available must
        # route through the shard_map wrapper and still match the dense
        # path end to end (forward traced with ctx.mesh set, under jit)
        from flexflow_tpu.ffconst import DataType, OperatorType
        from flexflow_tpu.layer import Layer
        from flexflow_tpu.ops import OpRegistry
        from flexflow_tpu.ops.base import OpContext

        mesh = make_mesh(8, {"data": 2, "model": 4})
        b, s, e, h = 2, 128, 32, 4
        lyr = Layer(OperatorType.MULTIHEAD_ATTENTION, "attn", [],
                    data_type=DataType.FLOAT)
        lyr.properties.update(embed_dim=e, num_heads=h, dropout=0.0,
                              causal=False, head_parallel="model")
        op = OpRegistry.create(lyr, [(b, s, e), (b, s, e), (b, s, e)])
        params = op.init_params(jax.random.PRNGKey(0))
        rs = np.random.RandomState(4)
        x = jnp.asarray(rs.randn(b, s, e).astype(np.float32))

        def fwd(p, x, use_mesh):
            ctx = OpContext(training=False, mesh=mesh if use_mesh else None)
            return op.forward(p, [x, x, x], ctx)[0]

        got = jax.jit(lambda p, x: fwd(p, x, True))(params, x)
        want = fwd(params, x, False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-4)


class TestSeqParallelModel:
    def test_transformer_block_with_ring_attention_trains(self):
        from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                                  SGDOptimizer)
        from flexflow_tpu.ffconst import ActiMode
        from flexflow_tpu.machine import make_mesh

        b, s, e, hds = 4, 32, 16, 4
        mesh = make_mesh(8, {"data": 2, "seq": 4})

        def build(seq_parallel):
            cfg = FFConfig(batch_size=b, only_data_parallel=True)
            ff = FFModel(cfg)
            t = ff.create_tensor((b, s, e))
            a = ff.multihead_attention(t, t, t, e, hds, causal=True,
                                       seq_parallel=seq_parallel, name="attn")
            h = ff.add(a, t, name="res")
            h = ff.layer_norm(h, name="ln")
            out = ff.dense(h, 1, name="head")
            ff.compile(SGDOptimizer(lr=0.01),
                       LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
                       [MetricsType.MEAN_SQUARED_ERROR],
                       mesh=mesh if seq_parallel else None)
            return ff

        rs = np.random.RandomState(0)
        x = rs.randn(b * 4, s, e).astype(np.float32)
        y = rs.randn(b * 4, s, 1).astype(np.float32)

        ff_sp = build("seq")
        ff_ref = build(None)
        # align initial params
        for lname, sub in ff_ref.params.items():
            for pname in sub:
                ff_sp.set_parameter(lname, np.asarray(sub[pname]), pname)
        p_sp = ff_sp.predict(x[:b])
        p_ref = ff_ref.predict(x[:b])
        np.testing.assert_allclose(p_sp, p_ref, rtol=2e-4, atol=2e-5)
        ff_sp.fit(x, y, epochs=1, verbose=False)  # trains under dp x sp


class TestRingFlashInner:
    """r4: the ring's inner block runs the Pallas flash kernel (scores in
    VMEM, never HBM) — numerics and gradients must match the dense path
    exactly. Interpret mode exercises the kernel on CPU."""

    @pytest.fixture(autouse=True)
    def _interpret_mode(self, monkeypatch):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_inner_matches_dense(self, causal):
        # S_loc = 512/4 = 128 = BLK_Q -> flash path taken per shard
        mesh = make_mesh(8, {"data": 2, "seq": 4})
        q, k, v = qkv(b=2, h=2, s=512, d=8)
        want = scaled_dot_product_attention(q, k, v, causal=causal)
        got = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh, causal=causal)
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.slow
    def test_flash_inner_gradients(self):
        mesh = make_mesh(8, {"seq": 8})
        q, k, v = qkv(b=1, h=2, s=1024, d=8)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh, batch_axis=None,
                                          causal=True) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(
                scaled_dot_product_attention(q, k, v, causal=True) ** 2)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_dense):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)

    @pytest.mark.slow
    def test_flash_lse_primitive(self):
        """flash_attention_lse's lse output and its gradient path."""
        from flexflow_tpu.ops.pallas_kernels import flash_attention_lse

        # [B, S, H*D] with two heads of 8: o comes back in that form,
        # lse as [B, H, S]
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(1, 128, 16).astype(np.float32))
        k = jnp.asarray(rs.randn(1, 128, 16).astype(np.float32))
        v = jnp.asarray(rs.randn(1, 128, 16).astype(np.float32))

        def ref(q, k, v):
            q, k, v = (x.reshape(1, 128, 2, 8) for x in (q, k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(8))
            lse = jax.scipy.special.logsumexp(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v)
            return o.reshape(1, 128, 16), lse

        o, lse = flash_attention_lse(q, k, v, 2, False, True)
        o_r, lse_r = ref(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                                   rtol=1e-5, atol=1e-5)
        # gradient including the lse output (the ring-merge dependency)
        f = lambda q, k, v: (
            jnp.sum(flash_attention_lse(q, k, v, 2, False, True)[0] ** 2)
            + jnp.sum(jnp.sin(
                flash_attention_lse(q, k, v, 2, False, True)[1])))
        fr = lambda q, k, v: (jnp.sum(ref(q, k, v)[0] ** 2)
                              + jnp.sum(jnp.sin(ref(q, k, v)[1])))
        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_blocked_backward_long_seq(self, causal):
        """S > MAX_BWD_SEQ takes the K-blocked backward kernel — grads
        must match the einsum reference (scores stay in VMEM tiles)."""
        from flexflow_tpu.ops.pallas_kernels import (MAX_BWD_SEQ, _flash,
                                                     _xla_attention)

        rs = np.random.RandomState(1)
        s = MAX_BWD_SEQ * 2
        q = jnp.asarray(rs.randn(1, s, 8).astype(np.float32))
        k = jnp.asarray(rs.randn(1, s, 8).astype(np.float32))
        v = jnp.asarray(rs.randn(1, s, 8).astype(np.float32))
        f = lambda q, k, v: jnp.sum(_flash(q, k, v, 1, causal, True) ** 2)
        fr = lambda q, k, v: jnp.sum(_xla_attention(q, k, v, causal) ** 2)
        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)
