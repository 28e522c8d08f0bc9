"""Deviceless compiles for a described TPU v5e (see `tests/tpu_compile.py`
and `tests/test_tpu_compile.py`): the ops of the pattern-driven decoder's
cells, each alone at its cell's widths, forward and backward."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from flexflow_tpu.obs.inspect import pallas_kernel_count
from flexflow_tpu.obs.step_scopes import table_of
from flexflow_tpu.ops import pallas_kernels as pk
from tpu_compile import (_compile, _no_compilation_cache,  # noqa: F401
                         abstract_op, on_tpu, topo)


def _wide_grads(heads, kv_heads):
    """Gradients through the wide-head kernels, causal, grouped keys."""
    def grads(q, k, v):
        def loss(q, k, v):
            return pk._flash(q, k, v, heads, True, False, 0, None, None,
                             kv_heads).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    return grads


class TestHybridDecoderKernels:
    """The new ops of the pattern-driven decoder at the widths of the
    `nemotron3_nano_30b_a3b` cell (one chip's share: 8 Mamba heads, 8
    held experts, 8,192 tokens), forward and backward."""

    @pytest.mark.parametrize("rows,groups,d,f,gated", [
        pytest.param(24832, 8, 2048, 1792, True, id="lfm2"),
        pytest.param(24832, 16, 2048, 768, True, id="sdar"),
        pytest.param(18688, 8, 2560, 768, True, id="smallthinker"),
        pytest.param(4736, 8, 2688, 1856, False, id="nemotron"),
        pytest.param(6272, 16, 2048, 512, True, id="laguna"),
        pytest.param(1664, 8, 2048, 768, True, id="joyai"),
    ])
    def test_grouped_matmul_at_the_cells_widths(self, topo, on_tpu, rows,
                                                groups, d, f, gated):
        """An expert layer's products at the six cells' shapes (the
        buffer `MoELayer.buffer_rows` makes there), forward and
        backward: the tiles `moe._gmm_tiling` picks, the contraction of
        every `gmm` product whole, fit the compiler's 16 MiB of VMEM."""
        from flexflow_tpu.ops.moe import grouped_matmul
        one = SingleDeviceSharding(topo.devices[0])

        def shape(*dims):
            return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one)

        ups = [shape(groups, d, f)] * (2 if gated else 1)
        sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one)

        def loss(x, ups, down, sizes):
            h = jax.nn.relu(grouped_matmul(x, ups[0], sizes))
            h = h * (grouped_matmul(x, ups[1], sizes) if gated else h)
            return grouped_matmul(h, down, sizes).astype(jnp.float32).sum()

        hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                       shape(rows, d), ups, shape(groups, f, d), sizes)
        # a matrix: one product forward, one for the rows' gradient, one
        # for its own
        assert pallas_kernel_count(hlo) == 3 * (len(ups) + 1)

    @pytest.mark.parametrize("tokens,width,props", [
        pytest.param(8192, 2688, dict(
            n_experts=128, k=6, hidden_size=1856, shared_width=3712,
            routed_scaling=2.5, experts_held=8), id="nemotron"),
        pytest.param(16384, 2560, dict(
            n_experts=64, k=6, hidden_size=768, scoring="softmax",
            gated=True, experts_held=8), id="smallthinker"),
        pytest.param(16384, 2048, dict(
            n_experts=128, k=8, hidden_size=768, scoring="softmax",
            gated=True, activation="silu", experts_held=16), id="sdar"),
    ])
    def test_expert_layer_moves_rows_by_gathers_at_the_cells_widths(
            self, topo, on_tpu, tokens, width, props):
        """A whole `MoELayer`, forward and backward, as the three cells
        run it: no scatter in the chip's program but the megablox kernels'
        own tile tables, and no float32 [tokens, k, width] among the
        temporaries. The rows come back to their tokens through the
        kernel `moe_sum_rows` (PR 37), once for the combine and once for
        the dispatch's backward, beside the grouped products' kernels."""
        from flexflow_tpu.ffconst import OperatorType
        from flexflow_tpu.layer import Layer
        from flexflow_tpu.obs.inspect import scatters_in
        from flexflow_tpu.ops.base import OpContext, OpRegistry
        one = SingleDeviceSharding(topo.devices[0])
        layer = Layer(OperatorType.MOE_LAYER, "experts", [])
        layer.properties.update(props)
        second_input = props.get("gated") and "activation" not in props
        shapes = [(1, tokens, width)] * (2 if second_input else 1)
        op = OpRegistry.create(layer, shapes)
        ctx = OpContext(training=True, compute_dtype=jnp.bfloat16)

        def abstract(a):
            full = a.ndim < 3 and a.shape[-1] == props["n_experts"]
            return jax.ShapeDtypeStruct(
                a.shape, jnp.float32 if full else jnp.bfloat16, sharding=one)

        params = jax.tree.map(abstract, jax.eval_shape(
            op.init_params, jax.random.PRNGKey(0)))
        inputs = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one)
                  for s in shapes]

        def loss(params, inputs):
            return op.forward(params, inputs, ctx)[0].astype(
                jnp.float32).sum()

        # (the value too: a sum's gradient does not need the combine)
        compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            params, inputs).compile()
        # (the chip's compiler cuts some of their names to `scatter-add`:
        # a table has a tile's or a group's entry, an activation a row's)
        scatters = scatters_in(compiled.as_text())
        assert scatters and all(size < 256 for _, size in scatters), scatters
        hlo = compiled.as_text()
        assert "jit(moe_combine)" in hlo
        assert (compiled.memory_analysis().temp_size_in_bytes
                < 4 * tokens * props["k"] * width)
        assert op.traced_gauges()["executor.moe_sum_rows_ops"] == 1
        assert op.traced_gauges()["executor.moe_spread_rows_ops"] == 1
        kernels = [line for line in hlo.splitlines()
                   if "custom_call_target=\"tpu_custom_call\"" in line]
        sums = [line for line in kernels if "moe_sum_rows" in line]
        assert len(sums) == 2 and all("moe_combine" in s for s in sums)
        # the combine's backward is their transpose, ONE kernel (PR 49)
        spreads = [line for line in kernels if "moe_spread_rows" in line]
        assert len(spreads) == 1 and "moe_combine" in spreads[0]
        # and nothing in it follows the tokens * k pairs: no gather
        # through `row_of_pair`, which gave d weights [tokens, k]
        pairs = re.compile(r" = \w+\[(%d,%d|%d)\]\S* gather\(" % (
            tokens, props["k"], tokens * props["k"]))
        assert not [
            line for line in hlo.splitlines() if pairs.search(line)
            and "transpose(jvp(jit(moe_layer)))/jit(moe_combine)" in line]
        # three products an expert matrix: forward, d rows, d weights
        assert pallas_kernel_count(hlo) == 3 + 3 * op.matrices

    def test_chunked_scan_at_the_cells_widths(self, topo):
        from flexflow_tpu.ops.ssm import ssd_chunked
        one = SingleDeviceSharding(topo.devices[0])
        x = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16,
                                 sharding=one)
        dt = jax.ShapeDtypeStruct((1, 8192, 8), jnp.float32, sharding=one)
        a = jax.ShapeDtypeStruct((8,), jnp.float32, sharding=one)
        bc = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16,
                                  sharding=one)

        def loss(x, dt, a, bm, cm):
            return ssd_chunked(x, dt, a, bm, cm, 128, jnp.bfloat16).sum()

        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            x, dt, a, bc, bc).compile()
        # the per-chunk decay matrices, float32: 8 heads x 64 chunks of
        # 128 x 128, a few copies live at once
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30

    def test_scan_kernels_of_the_mixer_at_the_cells_widths(self, topo,
                                                          on_tpu):
        """The Mamba-2 mixer of the nemotron cell (8,192 positions, hidden
        2688, 8 heads of 64 on one group, a state of 128, chunks of 128,
        bfloat16), forward and backward (PR 62): the scan is two kernels,
        `ssd_scan_fwd` and `ssd_scan_bwd`, that compile inside the VMEM
        they ask for, both under `ssm_mixer` / `ssd_scan` (part `ssm`);
        what the pair keeps is the state that enters every chunk, 16.8 MB
        of float32, and no [.., 128, 128] float32 decay tile a chunk and
        head is left in HBM (`ssd_chunked` above keeps several: eight
        heads x 64 chunks of them are 33.5 MB each)."""
        from flexflow_tpu.ffconst import OperatorType
        from flexflow_tpu.layer import Layer
        from flexflow_tpu.ops.base import OpContext, OpRegistry
        seq, hidden = 8192, 2688
        layer = Layer(OperatorType.SSM_MIXER, "mixer", [])
        layer.properties.update(num_heads=8, head_dim=64, n_groups=1,
                                state_size=128, chunk_size=128)
        op = OpRegistry.create(layer, [(1, seq, hidden)])
        assert op.scans_by_kernel(None)
        params, (x,) = abstract_op(topo, op)
        ctx = OpContext(training=True, compute_dtype=jnp.bfloat16)
        compiled = jax.jit(jax.grad(lambda p, x: op.forward(
            p, [x], ctx)[0].astype(jnp.float32).sum(), argnums=(0, 1))).lower(
                params, x).compile()
        hlo = compiled.as_text()
        assert op.traced_gauges() == {"ssm/ssd_kernel_ops": 1}
        assert pallas_kernel_count(hlo) == 2
        table = table_of(hlo)
        kernels = [table[re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)[1]]
                   for line in hlo.splitlines()
                   if "custom_call_target=\"tpu_custom_call\"" in line]
        assert sorted((r["part"], r["direction"]) for r in kernels) == [
            ("ssm", "backward"), ("ssm", "forward")], kernels
        assert all("jit(ssm_mixer)" in r["op_name"]
                   and "jit(ssd_scan)" in r["op_name"] for r in kernels)
        assert "ssd_scan_fwd" in hlo and "ssd_scan_bwd" in hlo
        assert not re.search(r"f32\[(?:\d+,)*128,128\]", re.sub(
            r"f32\[1,64,128,512\]", "", hlo))
        assert re.search(r"f32\[1,64,128,512\]", hlo)   # the states kept
        # the projection, the convolved [x ; B ; C], y and their
        # gradients, the states: no more than the `jax.numpy` form's tiles
        assert compiled.memory_analysis().temp_size_in_bytes < 400 << 20

    def test_gated_conv_op_at_the_cells_widths(self, topo, on_tpu):
        """The short convolution op of the lfm2 cell (16,384 positions,
        2048 lanes, 3 taps, bfloat16), forward and backward: two kernels
        between the two products, and no float32 [S, E] array written
        outside them (XLA's own fusions of the pass write four)."""
        from flexflow_tpu.ffconst import OperatorType
        from flexflow_tpu.layer import Layer
        from flexflow_tpu.obs.inspect import arrays_between_fusions
        from flexflow_tpu.ops.base import OpContext, OpRegistry
        seq, width = 16384, 2048
        assert pk.gated_conv_shape_legal(seq, width, 3)
        one = SingleDeviceSharding(topo.devices[0])
        op = OpRegistry.create(Layer(OperatorType.SHORT_CONV, "conv", []),
                               [(1, seq, width)])
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, jnp.float32 if a.shape[0] == 3 else jnp.bfloat16,
                sharding=one),
            jax.eval_shape(op.init_params, jax.random.PRNGKey(0)))
        x = jax.ShapeDtypeStruct((1, seq, width), jnp.bfloat16, sharding=one)

        def hlo_of(pallas):
            ctx = OpContext(training=True, compute_dtype=jnp.bfloat16)
            if not pallas:
                op.in_one_pass = lambda *a: False
            text = _compile(jax.grad(lambda p, x: op.forward(
                p, [x], ctx)[0].astype(jnp.float32).sum(), argnums=(0, 1)),
                params, x)
            assert op.traced_gauges()[
                "executor.gated_conv_kernel_ops"] == int(pallas)
            return text

        hlo = hlo_of(True)
        assert pallas_kernel_count(hlo) == 2
        assert not arrays_between_fusions(hlo, "f32", seq * width)
        assert len(arrays_between_fusions(hlo_of(False), "f32",
                                          seq * width)) >= 3


    def test_hyper_connection_ops_at_the_xing4_cells_widths(self, topo,
                                                            on_tpu):
        """A sublayer's two ops of the xing4 cell (4,096 positions, 4
        streams of 3584 lanes, bfloat16 stream, float32 leaves), forward
        and backward: six kernels (read, maps, write, each way), no
        [S, 4, 3584] view and no float32 copy of the stream outside
        them, and the stream's two cotangents met inside the read's
        backward (no add of two [S, 4 * 3584] arrays in XLA)."""
        from flexflow_tpu.ffconst import OperatorType
        from flexflow_tpu.layer import Layer
        from flexflow_tpu.obs.inspect import arrays_between_fusions
        from flexflow_tpu.ops.base import OpContext, OpRegistry
        seq, n, c = 4096, 4, 3584
        assert pk.hc_shape_legal(seq, n, c)
        pre_layer = Layer(OperatorType.HC_PRE, "pre", [])
        pre_layer.properties.update(streams=n)
        pre = OpRegistry.create(pre_layer, [(1, seq, n * c)])
        post_layer = Layer(OperatorType.HC_POST, "post", [])
        post_layer.properties.update(streams=n)
        post = OpRegistry.create(post_layer, [
            (1, seq, n * c), (1, seq, c), (1, seq, 128)])
        params, (x,) = abstract_op(topo, pre)
        assert all(p.dtype == jnp.float32 for p in params.values())
        ctx = OpContext(training=True, compute_dtype=jnp.bfloat16)

        def sublayer(p, x):
            h, maps, stream = pre.forward(p, [x], ctx)
            pre._counters = None
            return post.forward({}, [stream, h * 2, maps], ctx)[0].astype(
                jnp.float32).sum()

        hlo = _compile(jax.value_and_grad(sublayer, argnums=(0, 1)), params, x)
        assert pre.traced_gauges()["hc/kernel_fallbacks"] == 0
        assert post.traced_gauges()["hc/kernel_fallbacks"] == 0
        assert pallas_kernel_count(hlo) == 6
        assert not arrays_between_fusions(hlo, "f32", seq * n * c)
        assert not re.search(r"\[1,4096,4,3584\]|\[4096,4,3584\]", hlo)
        parts = {(r["part"], r["direction"]) for r in table_of(hlo).values()}
        assert {("hyper_connection", "forward"),
                ("hyper_connection", "backward")} <= parts

    def test_the_new_ops_of_the_phi4_mini_flash_cell_at_its_widths(
            self, topo, on_tpu):
        """PR 52's op kinds at the cell's widths (8,192 positions, hidden
        2560, bfloat16), forward and backward of each op alone: the
        Mamba-1 mixer (d_inner 5120, state 16: two scan kernels, the
        state never written out a position), differential attention at
        40 : 20 heads of 64 that exports its keys and values and the
        cross-attention op that reads them: each takes the flash route at
        20 : 10 heads of 128 with the keys and values at the KV heads,
        two maps a forward; no [S, S] and no [S, 5120, 16] array in any
        of them."""
        from flexflow_tpu import FFConfig, FFModel
        from flexflow_tpu.ops.base import OpContext, OpRegistry
        seq, hidden = 8192, 2560
        ff = FFModel(FFConfig(batch_size=1))
        x = ff.create_tensor((1, seq, hidden))
        kw = dict(bias=True, qkv_bias=True, causal=True, num_kv_heads=20,
                  head_dim=64, differential=True, lambda_init=0.79)
        ff.mamba_mixer(x, export_memory=True, name="mamba")
        _, k, v = ff.multihead_attention(x, x, x, hidden, 40, export_kv=True,
                                         name="full", **kw)
        ff.multihead_attention(x, k, v, hidden, 40, kv_given=True,
                               name="cross", **kw)
        square = re.compile(r"\[(?:\d+,)*8192,8192\]")
        states = re.compile(r"8192,5120,16\]|8192,16,5120\]|"
                            r"8192,16,5,8,128\]")
        for name in ("mamba", "full", "cross"):
            layer = ff._layer_named[name]
            op = OpRegistry.create(layer, [t.shape for t in layer.inputs])
            params, inputs = abstract_op(topo, op)

            def loss(params, inputs, op=op):
                ctx = OpContext(training=True, compute_dtype=jnp.bfloat16)
                return sum(o.astype(jnp.float32).sum()
                           for o in op.forward(params, list(inputs), ctx))

            compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
                params, inputs).compile()
            hlo = compiled.as_text()
            assert not square.search(hlo), name
            assert not states.search(hlo), name
            assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
            if name == "mamba":
                assert pallas_kernel_count(hlo) == 2
                assert op.traced_gauges()[
                    "ssm/selective_scan_kernel_ops"] == 1
                continue
            route = op._route
            assert (route.core, route.grouped_kv) == ("flash", True), name
            assert route.scope == "diff_" + name
            assert route.super_block
            assert op.core_heads == (20, 10, 128)
            # two maps: two forward and two backward kernels
            assert pallas_kernel_count(hlo) == 4, name

    def test_the_new_ops_of_the_qwen3_next_cell_at_its_widths(
            self, topo, on_tpu):
        """PR 58's ops at the cell's widths (16,384 positions, hidden
        2048, bfloat16), forward and backward of each op alone: the gated
        delta-rule mixer (16 key and 32 value heads of 128, chunks of
        128: the walk's two kernels, no state a position and no [S, S]
        array) and the attention op at 16 : 2 heads of 256 with the gate
        a lane (the wide-head kernels: the forward and, since PR 59, ONE
        backward kernel, `flash_bwd_wide`, in place of `flash_bwd_wide_dq`
        and `flash_bwd_wide_dkv`; the keys and values at the KV heads; no
        [S, S] array); each inside the VMEM its kernels ask for (96 MiB:
        the backward holds four K blocks of 1024 of K, V, dK and dV), or
        the compile would have refused. The attention op compiles in
        float32 too."""
        from flexflow_tpu import FFConfig, FFModel
        from flexflow_tpu.ops.base import OpContext, OpRegistry
        seq, hidden = 16384, 2048
        one = SingleDeviceSharding(topo.devices[0])
        ff = FFModel(FFConfig(batch_size=1))
        x = ff.create_tensor((1, seq, hidden))
        ff.delta_mixer(x, 16, 32, 128, 128, name="delta")
        ff.multihead_attention(
            x, x, x, hidden, 16, bias=False, causal=True, num_kv_heads=2,
            head_dim=256, rope=True, rope_theta=1e7,
            partial_rotary_factor=0.25, qk_norm=True,
            qk_norm_zero_centered=True, lane_gate=True, name="attn")
        square = re.compile(r"\[(?:\d+,)*16384,16384\]")
        # a [128, 128] state a position, whatever the layout
        states = re.compile(r"16384,32,128,128\]|32,16384,128,128\]|"
                            r"16384,4096,128\]")
        for name in ("delta", "attn"):
            layer = ff._layer_named[name]
            op = OpRegistry.create(layer, [t.shape for t in layer.inputs])
            params, inputs = abstract_op(topo, op)

            def loss(params, inputs, op=op):
                ctx = OpContext(training=True, compute_dtype=jnp.bfloat16)
                (y,) = op.forward(params, list(inputs), ctx)
                op._counters = None
                return y.astype(jnp.float32).sum()

            compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
                params, inputs).compile()
            hlo = compiled.as_text()
            assert not square.search(hlo), name
            assert not states.search(hlo), name
            assert compiled.memory_analysis().temp_size_in_bytes < 5 << 30
            if name == "delta":
                assert "delta_rule_fwd" in hlo and "delta_rule_bwd" in hlo
                assert op.traced_gauges() == {
                    "executor.delta_mixer_ops": 1,
                    "executor.delta_rule_kernel_ops": 1,
                    "executor.delta_rule_heads_a_step": 2}
                continue
            route = op._route
            assert (route.core, route.grouped_kv, route.wide_head,
                    route.scope) == ("flash", True, True, "full")
            assert "flash_fwd_wide" in hlo and "flash_bwd_wide" in hlo
            assert "flash_bwd_wide_d" not in hlo    # PR 58's two kernels
            assert pallas_kernel_count(hlo) == 2
            assert route.wide_bwd_score_tiles == 136
            assert pk._wide_bwd_blocks(seq) == (1024, 1024, 4)
            kernels = jax.jit(_wide_grads(16, 2)).lower(*(
                jax.ShapeDtypeStruct((1, seq, n * 256), jnp.float32,
                                     sharding=one) for n in (16, 2, 2)))
            assert pallas_kernel_count(kernels.compile().as_text()) == 2

    def test_learned_sparse_attention_at_the_keye_cells_widths(
            self, topo, on_tpu):
        """PR 54's op at the cell's widths (16,384 positions, hidden 2048,
        8 : 1 heads of 128, an indexer of 16 heads of 64 that keeps 2,048
        keys a query, bfloat16), forward with its loss and backward: the
        selection, the loss and the main attention run their kernels
        (`index_select`, `index_kl`, the chunk-loop flash kernels with
        the mask operand); the compiled program holds no [S, S] float32
        array and no [H, S, S] array of any dtype, and the mask's buffer
        is the size the configuration's file says."""
        import json

        from flexflow_tpu import FFConfig, FFModel
        from flexflow_tpu.ops.base import OpContext, OpRegistry
        seq, hidden = 16384, 2048
        ff = FFModel(FFConfig(batch_size=1))
        x = ff.create_tensor((1, seq, hidden))
        ff.multihead_attention(
            x, x, x, hidden, 8, bias=False, causal=True, num_kv_heads=1,
            head_dim=128, rope=True, rope_theta=1e7, qk_norm=True,
            sparse_index=(16, 64, 2048), mrope_section=(16, 24, 24),
            name="sparse")
        layer = ff._layer_named["sparse"]
        op = OpRegistry.create(layer, [t.shape for t in layer.inputs])
        params, inputs = abstract_op(topo, op)

        def loss(params, inputs):
            ctx = OpContext(training=True, compute_dtype=jnp.bfloat16)
            (y,) = op.forward(params, list(inputs), ctx)
            aux, op._aux_loss, op._counters = op._aux_loss, None, None
            return y.astype(jnp.float32).sum() + aux

        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, inputs).compile()
        hlo = compiled.as_text()
        route = op._route
        assert (route.core, route.grouped_kv, route.sparse_kernels,
                route.scope) == ("flash", True, True, "sparse")
        assert not re.search(r"f32\[(?:\d+,)*16384,16384\]", hlo)
        # [H, S, S], and a batch of more than one such square
        assert not re.search(r"\[(?:\d+,)*(?:[2-9]|\d\d+),16384,16384\]", hlo)
        squares = set(re.findall(r"(\w+)\[1,16384,16384\]", hlo))
        assert squares == {"s8"}, squares       # the mask and its transpose
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "benchmarks", "configs",
                "keye_vl2_30b_a3b.json")) as f:
            assert json.load(f)["mask_bytes_a_layer"] == seq * seq
        # index_select, flash forward, index_kl, flash backward, and the
        # lane-dense rotary's two passes each way
        assert pallas_kernel_count(hlo) >= 4
        assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30
