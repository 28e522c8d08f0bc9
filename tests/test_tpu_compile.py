"""Deviceless compiles for a described TPU v5e 2x2: what the chip's
compiler would refuse is refused here, at no chip time.

The TPU compiler ships with the installation and compiles for a topology
that is described, not attached (`jax.experimental.topologies`). Nothing
runs, so these tests say nothing about results or times — they catch
what interpret-mode Pallas on the CPU cannot: a kernel over the VMEM
budget, a kernel the SPMD partitioner cannot split, a step that does not
fit HBM. Code that asks the live backend which platform it is on
(`pallas_mode`) still sees the CPU, so the tests steer it to its TPU
branch themselves.

Tier-1 keeps the kernels of the main path at real widths plus one whole
train step at depth 2; the full-depth steps of every `chip_smoke.py` arm
(30-90 s each) are `-m slow` and are the rehearsal to run before a
four-chip call.
"""

import collections
import dataclasses
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from flexflow_tpu import AdamOptimizer, FFConfig, LossType, MetricsType
from flexflow_tpu.machine import MachineSpec, make_mesh
from flexflow_tpu.models import TransformerConfig, create_transformer
from flexflow_tpu.obs.inspect import pallas_kernel_count
from flexflow_tpu.obs.step_scopes import table_of
from flexflow_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")


@pytest.fixture(scope="module", autouse=True)
def _no_compilation_cache():
    """A deviceless executable can be written to the persistent cache
    but not read back without a chip; keep the cache out of the way."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Take the kernels' TPU branch although the live backend is CPU."""
    monkeypatch.delenv("FLEXFLOW_TPU_PALLAS", raising=False)
    monkeypatch.setattr(pk, "pallas_mode", lambda: "tpu")


def described_mesh(topo, axes):
    n = int(np.prod(list(axes.values())))
    devs = np.array(topo.devices[:n]).reshape(tuple(axes.values()))
    return Mesh(devs, tuple(axes))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


_FLASH_STEPS = {}


def _flash_step(topo, heads, seq, dtype=jnp.bfloat16, causal=True, window=0,
                block_diffusion=None, kv_heads=None, head_dim=128,
                rope=False):
    """The compiled gradients of sum(`pk._flash`) for q, k, v (and the
    two-part score's rotated parts) on one described chip: q [1, S,
    heads * head_dim] of ``dtype``, k and v alike or, with ``kv_heads``,
    [1, S, kv_heads * head_dim] float32 as `flash_attention` hands them
    over. One compile a distinct call for the module: the cells' shapes
    recur across the tests below (smallthinker's and sdar's layers are
    both a super-block and a grouped-keys case), and what is kept of it
    is its text and its temporaries' size, not the executable.
    -> (hlo, temp bytes, q, k, the function compiled)"""
    key = (heads, seq, jnp.dtype(dtype).name, causal, window,
           block_diffusion, kv_heads, head_dim, rope)
    if key not in _FLASH_STEPS:
        one = SingleDeviceSharding(topo.devices[0])
        q = jax.ShapeDtypeStruct((1, seq, heads * head_dim), dtype,
                                 sharding=one)
        k = q if kv_heads is None else jax.ShapeDtypeStruct(
            (1, seq, kv_heads * head_dim), jnp.float32, sharding=one)
        parts_of_score = (
            jax.ShapeDtypeStruct((1, seq, heads * 64), dtype, sharding=one),
            jax.ShapeDtypeStruct((1, seq, 64), dtype, sharding=one),
        ) if rope else ()

        def grads(q, k, v, *r):
            return jax.grad(lambda q, k, v, *r: pk._flash(
                q, k, v, heads, causal, False, window, block_diffusion,
                r or None, kv_heads).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v, *r)

        compiled = jax.jit(grads).lower(q, k, k, *parts_of_score).compile()
        _FLASH_STEPS[key] = (
            compiled.as_text(),
            compiled.memory_analysis().temp_size_in_bytes, q, k, grads)
    return _FLASH_STEPS[key]


def _flash_grads(heads):
    """Gradients through the kernels of q, k, v [B, S, heads * D]."""
    def grads(q, k, v):
        def loss(q, k, v):
            return pk._flash(q, k, v, heads, False, False).astype(
                jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    return grads


def _wide_grads(heads, kv_heads):
    """Gradients through the wide-head kernels, causal, grouped keys."""
    def grads(q, k, v):
        def loss(q, k, v):
            return pk._flash(q, k, v, heads, True, False, 0, None, None,
                             kv_heads).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    return grads


def _flash_lse_grads(heads):
    def grads(q, k, v):
        def loss(q, k, v):
            o, lse = pk.flash_attention_lse(q, k, v, heads, False, False)
            return o.sum() + lse.sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    return grads


_BYTES = {"bf16": 2, "f32": 4}
_ARRAY = re.compile(r"(bf16|f32)\[([\d,]+)\]\{([\d,]+)")


def layout_faults(hlo, big, weights=()):
    """What the [B, S, H*D] operand form exists to remove from a compiled
    step: `copy` instructions whose result holds ``big`` bytes or more (a
    whole q, k, v or o changing layout; a result shaped like one of
    ``weights`` is a parameter's copy and none of this), and operands or
    results of a flash kernel whose minor dimension is narrower than the
    128 lanes it is padded to in HBM."""
    faults = []
    weights = {",".join(map(str, w)) for w in weights}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (bf16|f32)\[([\d,]+)\]"
                     r"\S* copy\(", line)
        if m and m.group(3) not in weights and (
                int(np.prod([int(d) for d in m.group(3).split(",")]))
                * _BYTES[m.group(2)] >= big):
            faults.append(f"copy {m.group(1)} {m.group(2)}[{m.group(3)}]")
        if ('custom_call_target="tpu_custom_call"' in line
                and "flash_" in line.split("metadata=")[-1][:200]):
            for dt, dims, layout in _ARRAY.findall(line.split("metadata=")[0]):
                dims = [int(d) for d in dims.split(",")]
                if dims[int(layout.split(",")[0])] < pk.LANES:
                    faults.append(f"flash operand {dt}{dims}{{{layout}}}")
    return faults


class TestFlashKernels:
    def test_bert_shape_fwd_bwd(self, topo):
        q = jax.ShapeDtypeStruct((8, 512, 16 * 64), jnp.bfloat16,
                                 sharding=SingleDeviceSharding(
                                     topo.devices[0]))
        hlo = _compile(_flash_grads(16), q, q, q)
        assert pallas_kernel_count(hlo) == 2
        assert layout_faults(hlo, q.size * 2) == []
        # the kernels' names, in the custom calls' `op_name`
        assert "tpu_custom_call_flash_fwd" in hlo
        assert "tpu_custom_call_flash_bwd" in hlo

    @pytest.mark.parametrize("head_dim", [128, pk.MAX_FLASH_HEAD_DIM])
    @pytest.mark.parametrize("grads,dtype", [
        # the ring variant (f32 output, lse gradient) needs the most VMEM
        (_flash_lse_grads, jnp.bfloat16),
        pytest.param(_flash_grads, jnp.bfloat16, marks=pytest.mark.slow),
        pytest.param(_flash_grads, jnp.float32, marks=pytest.mark.slow),
        pytest.param(_flash_lse_grads, jnp.float32,
                     marks=pytest.mark.slow),
    ])
    def test_longest_admitted_shape_compiles(self, topo, grads, dtype,
                                             head_dim):
        """Forward and K-blocked backward at the gate's upper bounds: a
        head of one lane block (two kernels), and of two (PR 58's
        forward and PR 59's one backward kernel: dQ, dK and dV from a
        tile's scores formed once)."""
        q = jax.ShapeDtypeStruct(
            (1, pk.MAX_FLASH_SEQ, head_dim), dtype,
            sharding=SingleDeviceSharding(topo.devices[0]))
        assert pallas_kernel_count(_compile(grads(1), q, q, q)) == 2

    @pytest.mark.parametrize("seq", [8192, pk.MAX_FLASH_SEQ])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("kv_heads", [None, 1])
    def test_one_span_steps_stay_inside_the_vmem_budget(self, topo, seq,
                                                        dtype, kv_heads):
        """The one-span kernels (PR 46) at the widest window the rule
        admits: a grid step takes a whole head's blocks up to 8,192
        positions and half a head's at 16,384 (`_span_tiles`), with the
        Q / O / dO / dQ panels resident; in float32, and with a group's
        whole float32 dK / dV panels, that is what fills the 96 MiB."""
        heads, window = 2, 769
        assert pk.one_span(seq, True, window) == ((256, 1024), (128, 896))
        assert pk._span_tiles(seq, 128)[0] * 128 == {8192: 8192,
                                                     16384: 4096}[seq]
        hlo, *_ = _flash_step(topo, heads, seq, dtype, window=window,
                              kv_heads=kv_heads)
        assert pallas_kernel_count(hlo) == 2

    @pytest.mark.parametrize("case,dtype", [
        ("ouro", jnp.bfloat16), ("joyai", jnp.bfloat16),
        ("chunk-512", jnp.bfloat16), ("smallthinker", jnp.bfloat16),
        ("sdar", jnp.bfloat16), ("lfm2", jnp.bfloat16),
        # the panels and the tiles at twice the bytes, at 16,384 positions
        ("smallthinker", jnp.float32), ("sdar", jnp.float32),
        ("lfm2", jnp.float32)])
    def test_super_block_steps_stay_inside_the_vmem_budget(self, topo, case,
                                                           dtype):
        """The chunk-loop kernels' super-blocks (PR 51) at the cells'
        shapes: a forward grid step holds four Q blocks' [256, 1024]
        float32 score tiles and their (max, sum, accumulator) carries
        beside the K / V panels (2 x 4 MB double-buffered at 16,384
        positions, float32 twice that), and slices a chunk at multiples
        of 256 keys for the sub-tiles of the diagonal, the far and the
        noised chunk; the backward's own chunk runs as four [256, <=
        1024] sub-blocks whose dQ^T parts are padded back to the chunk.
        The compiler takes each form inside the 96 MiB, in float32 too."""
        heads, kv_heads, d, seq, window, bd, rope = {
            "ouro": (2, None, 128, 4096, 0, None, False),
            "joyai": (2, None, 128, 4096, 0, None, True),
            "smallthinker": (7, 1, 128, 16384, 4096, None, False),
            "sdar": (8, 1, 128, 16384, 0, (8192, 4), False),
            "lfm2": (8, 2, 64, 16384, 0, None, False),
            "chunk-512": (2, None, 128, 1536, 0, None, False),
        }[case]
        parts = {"chunk-512": 2}.get(case, 4)
        assert pk.super_block(seq, window, bd) == (
            (parts, parts, True), parts)
        assert pk.super_block_engaged(seq, bd is None, window, bd,
                                      64 if rope else 0)
        hlo, *_ = _flash_step(topo, heads, seq, dtype, bd is None, window,
                              bd, kv_heads, d, rope)
        assert pallas_kernel_count(hlo) == 2

    @pytest.mark.parametrize("seq,block", [(16384, 4), (2048, 32),
                                           (512, 4)])
    def test_block_diffusion_mask_compiles_at_the_cells_widths(
            self, topo, seq, block):
        """The sdar cell's 8 heads of 128 under the block-diffusion mask
        (PR 34): the blocked kernels with two ranges of chunks a tile at
        16,384 and 2,048 positions, the whole-tile ones at 512; the
        mask's positions are a column and a row that Mosaic has to
        broadcast against each other."""
        hlo, _, q, *_ = _flash_step(
            topo, 8, seq, causal=False, block_diffusion=(seq // 2, block))
        assert pallas_kernel_count(hlo) == 2
        assert layout_faults(hlo, q.size * 2) == []
        assert ("tpu_custom_call_flash_bwd_blocked" in hlo) == (seq > 1024)

    @pytest.mark.parametrize("heads,seq,window", [
        (7, 16384, 4096), (7, 16384, 0), (4, 8192, 0), (7, 16384, 1000),
        (64, 8192, 512), (48, 8192, 0), (8, 16384, 512), (8, 8192, 769),
        (8, 1152, 200)])
    def test_causal_and_window_split_compile_at_the_cells_shapes(
            self, topo, heads, seq, window):
        """The smallthinker cell's 7 heads of 128 at 16,384 under a
        window of 4096 and under none, and the nemotron cell's 4 at
        8,192: forward and K-blocked backward whose loops are cut into
        far edge, interior and diagonal (PR 35), each a `fori_loop` with
        bounds computed from the grid index, inside the 96 MiB budget;
        and a window so narrow that the interior range is empty. The
        laguna cell's 64 heads under a window of 512, half of a chunk of
        1024, and its 48 under none. Since PR 46 that window takes the
        one-span kernels (a block's reach as ONE [256, 768] tile, sixteen
        tiles a grid step, no chunk loop): the same at 16,384 positions,
        at the widest window the rule admits (769: a [256, 1024] tile)
        and at Q blocks of 128 (S = 1152)."""
        hlo, _, q, *_ = _flash_step(topo, heads, seq, window=window)
        assert pallas_kernel_count(hlo) == 2
        assert layout_faults(hlo, q.size * 2) == []
        assert "tpu_custom_call_flash_bwd_blocked" in hlo
        assert 0 < pk.kv_blocks_masked(seq, True, window) <= (
            pk.kv_blocks(seq, True, window)[0])
        assert (pk.one_span(seq, True, window) is not None) == (
            0 < window <= 769)

    @pytest.mark.parametrize("heads,kv_heads,seq,window,block_diffusion", [
        (7, 1, 16384, 4096, None), (7, 1, 16384, 0, None),
        (4, 1, 8192, 0, None), (64, 8, 8192, 512, None),
        (48, 8, 8192, 0, None), (8, 1, 16384, 0, (8192, 4)),
        (8, 2, 1024, 0, None),
        # heads of 64 (PR 47): lfm2's op, the whole-tile kernels at their
        # longest, and the one-span kernels
        ((32, 64), 8, 16384, 0, None), ((32, 64), 8, 1024, 0, None),
        ((32, 64), 8, 8192, 512, None)])
    def test_grouped_keys_compile_at_the_cells_shapes(
            self, topo, heads, kv_heads, seq, window, block_diffusion):
        """K and V at the KV heads (PR 43) at the six grouped-query
        shapes of the decoder cells, and the whole-tile kernels at their
        longest: the backward's dK and dV are the KV head's whole float32
        [S, 128] panels, resident across a group's heads (8 MB each at
        16,384 positions, twice with the pipeline's second buffer), beside
        the q, o, dO and dQ panels, inside the 96 MiB budget. No operand
        or result but q, o, dO and dQ is H * D wide. At heads of 64
        (``heads`` = (H, 64); PR 47) a panel is a K / V lane block of two
        KV heads, resident across the four column blocks it serves."""
        heads, d = heads if isinstance(heads, tuple) else (heads, 128)
        hlo, temp_bytes, q, k, grads = _flash_step(
            topo, heads, seq, jnp.bfloat16, not block_diffusion, window,
            block_diffusion, kv_heads, d)
        assert pallas_kernel_count(hlo) == 2
        assert layout_faults(hlo, q.size * 2) == []
        # q, o, dO, lse live at once; nothing else of q's size
        assert temp_bytes < 5 * q.size * 2
        out = jax.eval_shape(grads, q, k, k)
        assert [(a.shape, a.dtype) for a in out] == [
            (q.shape, jnp.bfloat16), (k.shape, jnp.float32),
            (k.shape, jnp.float32)]

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("batch,heads,seq,head_dim", [
        # whole-tile kernels: 8 heads a step, then 2 at their longest,
        # one head a column block and two
        (16, 1, 512, 128), (16, 1, pk.MAX_BWD_SEQ, 128),
        (4, 4, 512, 64), (2, 2, pk.MAX_BWD_SEQ, 64),
        # K-blocked backward at its widest block, two heads of 64 a
        # column block and the nemotron cell's four of 128
        (2, 2, 2 * pk.MAX_BWD_SEQ, 64), (1, 4, 8192, 128),
    ])
    def test_tiles_derived_from_shape_and_dtype_compile(
            self, topo, batch, heads, seq, head_dim, dtype):
        """Heads a column block, batch rows a step and K/V rows a block
        follow from (S, H, D): each choice at its largest footprint,
        under the same VMEM budget."""
        q = jax.ShapeDtypeStruct((batch, seq, heads * head_dim), dtype,
                                 sharding=SingleDeviceSharding(
                                     topo.devices[0]))
        hlo = _compile(_flash_lse_grads(heads), q, q, q)
        assert pallas_kernel_count(hlo) == 2
        whole = seq <= pk.MAX_BWD_SEQ
        assert ("tpu_custom_call_flash_fwd_whole" in hlo) == whole
        assert ("tpu_custom_call_flash_bwd_blocked" in hlo) != whole

    def test_lowering_ignores_the_call_site_once_the_cache_is_configured(
            self, topo):
        """The persistent cache keys on the kernel's serialized MLIR; with
        Python tracebacks in its locations, the same step lowered from two
        lines never hits."""
        from flexflow_tpu.utils.compile_cache import configure_compile_cache
        names = ("jax_compilation_cache_dir",
                 "jax_include_full_tracebacks_in_locations")
        prev = {n: getattr(jax.config, n) for n in names}
        q = jax.ShapeDtypeStruct((1, 512, 16 * 64), jnp.bfloat16,
                                 sharding=SingleDeviceSharding(
                                     topo.devices[0]))
        try:
            configure_compile_cache()
            here = jax.jit(_flash_grads(16)).lower(q, q, q).as_text()
            jax.clear_caches()
            there = jax.jit(_flash_grads(16)).lower(q, q, q).as_text()
        finally:
            for n, v in prev.items():
                jax.config.update(n, v)
        assert here == there

    def test_gate_refuses_one_past_each_bound(self, on_tpu):
        ok = pk.flash_attention_available
        assert ok(pk.MAX_FLASH_SEQ, pk.MAX_FLASH_HEAD_DIM, 1)
        assert not ok(pk.MAX_FLASH_SEQ + pk.BLK_Q, 64, 2)
        assert not ok(512, pk.MAX_FLASH_HEAD_DIM + 8, 1)
        # past one lane block a head is two exactly (PR 58)
        assert pk.MAX_FLASH_HEAD_DIM == 256 and ok(pk.MAX_FLASH_SEQ, 256, 16)
        assert not ok(512, 192, 2) and not ok(512, 136, 1)
        # both cells' shapes, and heads that do not tile the lanes
        assert ok(512, 64, 16) and ok(8192, 128, 4)
        assert not ok(512, 64, 3) and not ok(512, 96, 4)

    def test_ring_attention_4way(self, topo, on_tpu):
        from flexflow_tpu.parallel.ring_attention import ring_attention
        mesh = described_mesh(topo, {"seq": 4})
        q = jax.ShapeDtypeStruct(
            (2, 4, 2048, 64), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(None, None, "seq", None)))

        def grads(q, k, v):
            def loss(q, k, v):
                o = ring_attention(q, k, v, mesh, seq_axis="seq",
                                   batch_axis=None)
                return o.astype(jnp.float32).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        hlo = _compile(grads, q, q, q)
        assert pallas_kernel_count(hlo) >= 2
        assert "collective-permute" in hlo


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
        one = SingleDeviceSharding(topo.devices[0])
        layer = Layer(OperatorType.SSM_MIXER, "mixer", [])
        layer.properties.update(num_heads=8, head_dim=64, n_groups=1,
                                state_size=128, chunk_size=128)
        op = OpRegistry.create(layer, [(1, seq, hidden)])
        assert op.scans_by_kernel(None)
        params = {
            leaf: jax.ShapeDtypeStruct(
                a.shape, jnp.float32 if leaf in op.full_precision_params
                else jnp.bfloat16, sharding=one)
            for leaf, a in jax.eval_shape(
                op.init_params, jax.random.PRNGKey(0)).items()}
        x = jax.ShapeDtypeStruct((1, seq, hidden), jnp.bfloat16, sharding=one)
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
        one = SingleDeviceSharding(topo.devices[0])
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
            params = {
                leaf: jax.ShapeDtypeStruct(
                    a.shape, jnp.float32 if leaf in op.full_precision_params
                    else jnp.bfloat16, sharding=one)
                for leaf, a in jax.eval_shape(
                    op.init_params, jax.random.PRNGKey(0)).items()}
            inputs = tuple(jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                                sharding=one)
                           for shape in op.input_shapes)

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
            params = {
                leaf: jax.ShapeDtypeStruct(
                    a.shape, jnp.float32 if leaf in op.full_precision_params
                    else jnp.bfloat16, sharding=one)
                for leaf, a in jax.eval_shape(
                    op.init_params, jax.random.PRNGKey(0)).items()}
            inputs = tuple(jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                                sharding=one)
                           for shape in op.input_shapes)

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
        one = SingleDeviceSharding(topo.devices[0])
        ff = FFModel(FFConfig(batch_size=1))
        x = ff.create_tensor((1, seq, hidden))
        ff.multihead_attention(
            x, x, x, hidden, 8, bias=False, causal=True, num_kv_heads=1,
            head_dim=128, rope=True, rope_theta=1e7, qk_norm=True,
            sparse_index=(16, 64, 2048), mrope_section=(16, 24, 24),
            name="sparse")
        layer = ff._layer_named["sparse"]
        op = OpRegistry.create(layer, [t.shape for t in layer.inputs])
        params = {
            leaf: jax.ShapeDtypeStruct(
                a.shape, jnp.float32 if leaf in op.full_precision_params
                else jnp.bfloat16, sharding=one)
            for leaf, a in jax.eval_shape(
                op.init_params, jax.random.PRNGKey(0)).items()}
        inputs = tuple(jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                            sharding=one)
                       for shape in op.input_shapes)

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


class TestEmbeddingBackward:
    """The `Embedding` op, forward and backward under its scope, at the
    two tables that paid most for `take`'s transpose (PR 57): the table's
    gradient comes from the kernel `embedding_sum_rows`, whose last
    output block is ragged (18,992 = 148 x 128 + 48, as 25,008 is), and
    the program under `op_embedding` holds no scatter; with the Pallas
    kernels off, the body it replaced, it holds the scatter-add into
    the table."""
    SHAPES = {"smallthinker": (16384, 18992, 2560),
              "phi4": (8192, 25008, 2560)}

    def _hlo(self, topo, lookups, entries, width, dtype):
        from flexflow_tpu.ffconst import OperatorType
        from flexflow_tpu.layer import Layer
        from flexflow_tpu.ops.base import OpContext, OpRegistry, scoped
        one = SingleDeviceSharding(topo.devices[0])
        layer = Layer(OperatorType.EMBEDDING, "embed_tokens", [])
        layer.properties.update(num_entries=entries, out_dim=width)
        op = OpRegistry.create(layer, [(1, lookups)])
        ctx = OpContext(training=True, compute_dtype=dtype)

        def lookup(table, ids):
            return op.forward({"kernel": table}, [ids], ctx)[0]

        def objective(table, ids, weight):
            return jnp.sum(scoped("op_embedding", lookup)(table, ids).astype(
                jnp.float32) * weight)

        hlo = _compile(
            jax.grad(objective),
            jax.ShapeDtypeStruct((entries, width), dtype, sharding=one),
            jax.ShapeDtypeStruct((1, lookups), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((1, lookups, width), dtype, sharding=one))
        return op, hlo

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                             ids=["bfloat16", "float32"])
    @pytest.mark.parametrize("cell", list(SHAPES))
    def test_the_tables_gradient_is_the_kernels_at_the_cells_shapes(
            self, topo, on_tpu, cell, dtype):
        from flexflow_tpu.obs.inspect import scatters_in
        from flexflow_tpu.ops.embedding import SUM_KERNEL_NAME
        op, hlo = self._hlo(topo, *self.SHAPES[cell], dtype)
        assert op.traced_gauges()["executor.embedding_sum_kernel_ops"] == 1
        kernels = [line for line in hlo.splitlines()
                   if "custom_call_target=\"tpu_custom_call\"" in line]
        assert len(kernels) == 1 and SUM_KERNEL_NAME in kernels[0]
        # under the op's part, in a nested call of its own: the name
        # the device trace gives the kernel's events
        assert ("transpose(jvp(jit(op_embedding)))/jit(%s)" % SUM_KERNEL_NAME
                in kernels[0])
        assert scatters_in(hlo) == []

    def test_the_body_it_replaced_holds_the_scatter(self, topo, monkeypatch):
        from flexflow_tpu.obs.inspect import scatters_in
        monkeypatch.setattr(pk, "pallas_mode", lambda: "off")
        lookups, entries, width = self.SHAPES["smallthinker"]
        op, hlo = self._hlo(topo, lookups, entries, width, jnp.bfloat16)
        assert op.traced_gauges()["executor.embedding_sum_kernel_ops"] == 0
        assert pallas_kernel_count(hlo) == 0
        assert (entries * width) in [
            size for _, size in scatters_in(hlo, "op_embedding")]


class TestFusedAdam:
    KW = dict(beta1=0.9, beta2=0.999, eps=1e-8, wd=1e-4)

    def _args(self, sharding):
        f32 = jax.ShapeDtypeStruct((1024, 4096), jnp.float32,
                                   sharding=sharding)
        bf16 = jax.ShapeDtypeStruct((1024, 4096), jnp.bfloat16,
                                    sharding=sharding)
        return f32, bf16, bf16, bf16  # p, g, m, v (bf16 Adam state)

    def test_leaf_alone(self, topo, on_tpu):
        from flexflow_tpu.ops.fused_update import fused_adam_leaf
        fn = lambda p, g, m, v: fused_adam_leaf(p, g, m, v,
                                                jnp.float32(1e-4), **self.KW)
        hlo = _compile(fn, *self._args(SingleDeviceSharding(topo.devices[0])))
        assert pallas_kernel_count(hlo) == 1
        assert "tpu_custom_call_fused_adam" in hlo

    def test_leaf_under_wus_spec_on_four_devices(self, topo, on_tpu):
        from flexflow_tpu.ops.fused_update import fused_adam_leaf
        mesh = described_mesh(topo, {"data": 4})
        spec = P("data", None)
        fn = lambda p, g, m, v: fused_adam_leaf(
            p, g, m, v, jnp.float32(1e-4), mesh=mesh, spec=spec, **self.KW)
        hlo = _compile(fn, *self._args(NamedSharding(mesh, spec)))
        assert pallas_kernel_count(hlo) == 1
        assert "all-gather" not in hlo and "all-reduce" not in hlo


class TestHeadAndLoss:
    """`dense` + the sparse cross-entropy + `grad` at [1, 2048, 16384]
    bf16 logits (PR 40): with `losses.target_log_probs` the optimized
    program writes no float32 array of the logits' shape and runs no
    scatter; the body it replaced, inlined here as the control, does
    both (the float32 log-probabilities kept for the backward, and the
    transpose of `take_along_axis`)."""
    ROWS, HIDDEN, VOCAB = 2048, 1024, 16384

    def _hlo(self, topo, loss):
        from flexflow_tpu.ops.base import scoped
        one = SingleDeviceSharding(topo.devices[0])
        x = jax.ShapeDtypeStruct((1, self.ROWS, self.HIDDEN), jnp.bfloat16,
                                 sharding=one)
        w = jax.ShapeDtypeStruct((self.HIDDEN, self.VOCAB), jnp.bfloat16,
                                 sharding=one)
        ids = jax.ShapeDtypeStruct((1, self.ROWS), jnp.int32, sharding=one)

        def head(x, w):     # ops/linear.py `Linear.forward`
            return jnp.dot(x, w, preferred_element_type=jnp.float32
                           ).astype(x.dtype)

        def objective(x, w, ids):
            return scoped("loss", loss)(scoped("head", head)(x, w), ids)

        return _compile(jax.value_and_grad(objective, (0, 1)), x, w, ids)

    def _faults(self, hlo):
        from flexflow_tpu.obs.inspect import (arrays_between_fusions,
                                              scatters_in)
        return (arrays_between_fusions(hlo, "f32", self.ROWS * self.VOCAB),
                scatters_in(hlo))

    def test_own_backward_leaves_no_float32_logits_and_no_scatter(
            self, topo):
        from flexflow_tpu.losses import sparse_categorical_crossentropy
        hlo = self._hlo(topo, sparse_categorical_crossentropy)
        assert self._faults(hlo) == ([], [])
        # both directions of the loss are there, under its scope
        assert "jvp(jit(loss))" in hlo
        assert "transpose(jvp(jit(loss)))" in hlo

    def test_the_body_it_replaced_holds_both(self, topo):
        def parent_style(logits, ids):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, ids[..., None],
                                                 axis=-1))
        f32, scatters = self._faults(self._hlo(topo, parent_style))
        assert f32 and scatters, (f32, scatters)


class TestRotaryLanes:
    """One attention op, forward + backward, at two decoder cells' shapes
    (PR 42): with the heads' norm and rotary as the lane-dense pass
    (`pallas_kernels.rotary_lanes`) the optimized program writes no
    float32 array of S x H x 128 elements between a projection's product
    and a flash kernel but the product's own result (the pass's operand)
    and the pass's, and copies no float32 `[.., H, 128]` array there. The
    same op steered to the `[B, S, H, D]` view, the shipped form until PR
    42, holds them. With the pass nothing is left (PR 43): the K/V
    repeat's backward (dK and dV of the repeated heads as float32
    through a 4-D view: a convert and a copy each, 4 / 2 / 4 such passes
    an op until then) is gone too, because the flash kernels read K and
    V at the KV heads and hand back the groups' float32 sums. Since PR
    47 the same holds of lfm2's op at heads of 64, two a 128-lane
    column: 32 : 8 heads with the heads' norm at 16,384 positions."""
    YARN = dict(rope_type="yarn", factor=64, beta_fast=64, beta_slow=1,
                original_max_position_embeddings=4096,
                attention_factor=1.4158883083359672)
    # seq, width, the op's properties (heads of 128 unless they say);
    # XLA's passes over an S x H x D float32 array with the pass (none)
    # and on the view (rotary's own: with grouped keys the repeat's
    # 4 / 2 / 4 are gone from it as well)
    OPS = {
        "laguna_window_64_8": (8192, 2048, dict(
            num_heads=64, num_kv_heads=8, causal=True, window=512,
            gate=True), 0, 8),
        "laguna_full_48_8_partial_yarn": (8192, 2048, dict(
            num_heads=48, num_kv_heads=8, causal=True, gate=True,
            rope_theta=500000.0, partial_rotary_factor=0.5,
            rope_scaling=YARN), 0, 9),
        "sdar_8_1_normed": (16384, 2048, dict(
            num_heads=8, num_kv_heads=1, block_diffusion=(8192, 4),
            rope_wrap=8192, qk_norm=True, rope_theta=1000000.0), 0, 13),
        "lfm2_full_32_8_normed_heads_of_64": (16384, 2048, dict(
            num_heads=32, num_kv_heads=8, head_dim=64, causal=True,
            qk_norm=True, rope_theta=1000000.0), 0, 13),
    }

    def _hlo(self, topo, seq, hidden, props, lanes):
        from flexflow_tpu.ffconst import OperatorType
        from flexflow_tpu.layer import Layer
        from flexflow_tpu.ops.base import OpContext, OpRegistry

        layer = Layer(OperatorType.MULTIHEAD_ATTENTION, "op", [])
        layer.properties.update(dict(dict(head_dim=128), **props,
                                     embed_dim=hidden, bias=False, rope=True))
        op = OpRegistry.create(layer, [(1, seq, hidden)] * 3)
        if not lanes:
            route = op.route
            op.route = lambda *a, **k: dataclasses.replace(
                route(*a, **k), rotary_in_lanes=False)
        one = SingleDeviceSharding(topo.devices[0])

        def step(params, x, g):
            ctx = OpContext(training=True, compute_dtype=jnp.bfloat16)
            return jax.value_and_grad(lambda p, x: jnp.sum(
                op.forward(p, [x], ctx)[0].astype(jnp.float32) * g),
                argnums=(0, 1))(params, x)

        x = jax.ShapeDtypeStruct((1, seq, hidden), jnp.bfloat16, sharding=one)
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            jax.eval_shape(op.init_params, jax.random.PRNGKey(0)))
        hlo = _compile(step, params, x, x)
        assert op._route.rotary_in_lanes == lanes
        return hlo

    @staticmethod
    def passes_over(hlo, elements):
        """(opcode, name, result, op_name) of the instructions outside a
        fusion's body that write a float32 array of ``elements`` elements
        and are neither a kernel nor a product: XLA's own passes over
        it."""
        from flexflow_tpu.obs.inspect import (_INSTRUCTION,
                                              arrays_between_fusions)
        names = set(arrays_between_fusions(hlo, "f32", elements))
        out = []
        for line in hlo.splitlines():
            m = _INSTRUCTION.match(line)
            if not m or m.group(1) not in names:
                continue
            name, result, opcode = m.groups()
            op_name = re.search(r'op_name="([^"]*)"', line)
            if opcode in ("custom-call", "get-tuple-element", "bitcast",
                          "tuple") or opcode.endswith(("-start", "-done")):
                continue
            # a product, or a kernel with the cast of its operand fused
            # into its fetch (`allow_input_fusion`)
            if opcode == "fusion" and op_name and op_name.group(
                    1).endswith(("dot_general", "pallas_call")):
                continue
            out.append((opcode, name, result,
                        op_name.group(1) if op_name else ""))
        return out

    @staticmethod
    def assert_keys_stay_at_the_kv_heads(hlo, seq, heads, kv_heads, d=128):
        """The K/V repeat is in the program in neither direction (PR
        43): no bf16 array of S x H x D elements is written by a
        `broadcast`, `reshape` or `copy` (the repeated K or V); the
        flash forward reads ONE operand that wide, q, and K and V at the
        KV heads; the flash backward hands out one float32 result that
        wide, dQ, and dK and dV as float32 [1, S, Hk*128], the groups'
        sums, which is all that lies between it and the K / V
        projections' transposed products."""
        from flexflow_tpu.obs.inspect import (_INSTRUCTION,
                                              arrays_between_fusions)
        wide, narrow = [1, seq, heads * d], [1, seq, kv_heads * d]
        names = set(arrays_between_fusions(hlo, "bf16", seq * heads * d))
        calls = {}
        for line in hlo.splitlines():
            m = _INSTRUCTION.match(line)
            if m and m.group(1) in names:
                assert m.group(3) not in ("broadcast", "reshape", "copy"), line
            if 'custom_call_target="tpu_custom_call"' not in line:
                continue
            kernel = re.search(r"tpu_custom_call_(flash_\w+)/pallas_call",
                               line.split("metadata=")[-1])
            if kernel:
                results, operands = line.split("custom-call(")[0], line.split(
                    "operand_layout_constraints=")[1].split("}}")[0]
                calls[kernel.group(1)] = tuple(
                    [(dt, [int(n) for n in dims.split(",")])
                     for dt, dims in re.findall(r"(bf16|f32)\[([\d,]+)\]",
                                                part)]
                    for part in (results, operands))
        _, operands = calls["flash_fwd"]
        assert operands == [("bf16", wide), ("bf16", narrow),
                            ("bf16", narrow)], operands
        results, operands = calls["flash_bwd_blocked"]
        assert results[:3] == [("f32", wide), ("f32", narrow),
                               ("f32", narrow)], results
        assert [o for o in operands if o[1] == wide] == [
            ("bf16", wide)] * 3, operands        # q, o and dO

    @pytest.mark.parametrize("kind", list(OPS))
    def test_no_float32_relayout_between_projection_and_flash(
            self, topo, on_tpu, kind):
        seq, hidden, props, with_the_pass, on_the_view = self.OPS[kind]
        heads, kv_heads = props["num_heads"], props["num_kv_heads"]
        d = props.get("head_dim", 128)
        hlo = self._hlo(topo, seq, hidden, props, True)
        left = self.passes_over(hlo, seq * heads * d)
        # nothing: the repeat's backward is gone too
        assert len(left) == with_the_pass, left
        self.assert_keys_stay_at_the_kv_heads(hlo, seq, heads, kv_heads, d)
        view = self.passes_over(self._hlo(topo, seq, hidden, props, False),
                                seq * heads * d)
        # what this PR took out of the op
        assert len(view) == on_the_view, view
        assert sum("jit(rotary_" in scope for _, _, _, scope in view) >= 5

    def test_the_kernels_scopes(self, topo, on_tpu):
        """The pass's calls sit under the rotary scope inside the
        attention op's, forward and backward, so the share metrics count
        them with the op; they are under no `flash_*` scope (the flash
        rooflines divide by those events) and no top-level
        `tpu_custom_call*` (what `kernels.flash_roofline` sums). In the
        benchmark's step on the chip their events read `rotary_whole.N`
        / `rotary_partial_yarn.N` (my chip runs, PR 42); compiled here
        the instructions keep the kernels' names."""
        _, hidden, props, _, _ = self.OPS["laguna_window_64_8"]
        hlo = self._hlo(topo, 1024, hidden, props, True)
        calls = [line for line in hlo.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line
                 and "rotary_lanes" in line.split("metadata=")[-1][:400]]
        assert len(calls) == 4      # q and k, forward and backward
        for line in calls:
            name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) =", line).group(1)
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert not name.startswith(("tpu_custom_call", "flash")), name
            assert "jit(attention_window)" in op_name
            assert "jit(rotary_whole)/rotary_lanes" in op_name
            assert "flash" not in op_name


# ---------------------------------------------------------------------------
# whole train steps


def build_bert(num_layers, batch, mesh_axes=None, chips=4, seq_parallel=None,
               **cfg_kw):
    """BERT-proxy at full width through the normal entry points, placed
    on virtual CPU devices: `compile()` builds its mesh from
    `jax.devices()` and puts parameters there. The machine is described
    to the search as v5e so strategy, dtype and layout are the chip's."""
    tc = TransformerConfig(num_layers=num_layers, batch_size=batch,
                           seq_parallel=seq_parallel)
    cfg = FFConfig(batch_size=batch, workers_per_node=chips, **cfg_kw)
    ff = create_transformer(tc, cfg)
    mesh = (make_mesh(int(np.prod(list(mesh_axes.values()))), mesh_axes)
            if mesh_axes else None)
    ff.compile(AdamOptimizer(alpha=1e-4, state_dtype=jnp.bfloat16),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [MetricsType.MEAN_SQUARED_ERROR], mesh=mesh,
               machine_spec=MachineSpec("tpu-v5e", chips_per_slice=chips))
    return ff


def compile_step_for(ff, topo, label_shape=None):
    """Compile `ff`'s train step for the described chips: the executor's
    mesh is swapped for the same axes over `topo`'s devices and every
    argument becomes a shape carrying its live spec on that mesh.
    ``label_shape``: the labels' where they are not one value a
    position."""
    ex = ff.executor
    axes = dict(zip(ex.mesh.axis_names, ex.mesh.devices.shape))
    mesh = described_mesh(topo, axes)

    def abstract(a):
        spec = getattr(a.sharding, "spec", P())  # scalars sit on one device
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    params, opt_state, state = jax.tree.map(
        abstract, (ff.params, ff.opt_state, ff.state))
    x = ff.input_tensors[0].shape
    inputs = {ex.input_names[0]: jax.ShapeDtypeStruct(
        x, ex.compute_dtype,
        sharding=NamedSharding(mesh, ex.batch_sharding().spec))}
    labels = jax.ShapeDtypeStruct(
        label_shape or x[:-1] + (1,), jnp.float32,
        sharding=NamedSharding(mesh, ex.label_sharding().spec))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    live, ex.mesh = ex.mesh, mesh
    try:
        return jax.jit(ex._train_step_fn(), donate_argnums=(0, 1, 2)).lower(
            params, opt_state, state, inputs, labels, rng).compile()
    finally:
        ex.mesh = live


def _choices(ff):
    return [getattr(s, "choice", None) or "" for s in ff.strategy.values()]


def test_wus_step_with_fused_update_compiles_for_four_chips(topo, on_tpu):
    """The strategy the search picks for four chips: {data:4}, WUS with
    overlap, flash attention and the fused optimizer update. Before the
    fused update ran under shard_map this raised "Mosaic kernels cannot
    be automatically partitioned"."""
    ff = build_bert(2, 32, search_budget=30, enable_parameter_parallel=True)
    assert dict(zip(ff.mesh.axis_names, ff.mesh.devices.shape)) == {"data": 4}
    assert ff.wus_enabled and ff.executor.fused_update_ops
    flash = sum("_k:flash" in c for c in _choices(ff))
    assert flash >= 1
    hlo = compile_step_for(ff, topo).as_text()
    # a forward and a backward per flash op, plus at least one update
    # kernel of the fused ops
    assert pallas_kernel_count(hlo) > 2 * flash
    # the nested calls that tell the optimizer and the loss from the
    # layers; the update's kernels lie in its scope, the attention
    # kernels at the top level, told apart by their own names
    assert "/jit(optimizer_update)/" in hlo and "/jvp(jit(loss))/" in hlo
    table = table_of(hlo)
    kernels = collections.Counter(
        (table[m.group(1)]["part"], table[m.group(1)]["direction"])
        for m in re.finditer(r"^\s*%?([\w.\-]+) = [^\n]*custom_call_target="
                             r'"tpu_custom_call"', hlo, re.M))
    assert kernels[("attention", "forward")] == flash
    assert kernels[("attention", "backward")] == flash
    assert kernels[("optimizer_update", "optimizer")] >= 1
    assert set(kernels) == {("attention", "forward"),
                            ("attention", "backward"),
                            ("optimizer_update", "optimizer")}
    # on each chip q, k, v, o reach the kernels as the projections wrote
    # them: no copy of a [8, 512, 1024] array, no 64-wide minor dimension
    # (the FFN kernels are as large there, and are copied as parameters)
    weights = {p.shape for p in jax.tree.leaves(ff.params)}
    assert layout_faults(hlo, 8 * 512 * 1024 * 2, weights) == []


def test_remat_frees_an_ops_interior_on_the_chip(topo):
    """`_r` on the flat executor (`jax.checkpoint` around the op), as
    the chip's compiler sees it: the MLP of `tests/test_remat.py` at
    8,192 rows on one chip, its four wide projections checkpointed. The
    step's temporaries fall from 272,129,024 to 102,598,656 bytes
    (-62%: the float32 [8192, 2048] pre-activation of each is
    recomputed in the backward, not kept). XLA:CPU's memory analysis is
    blind to it (the same peak to the byte), which is why that file
    asserts the saved residuals; and a BARE projection checkpointed the
    same way saves nothing here either (272,129,024 -> 273,564,672:
    its output is the next op's residual whatever it does itself)."""
    from test_remat import _mlp
    batch = 8192
    temps = {}
    for mode in ("off", "on"):
        ff = _mlp({f"up{i}" for i in range(4)} if mode == "on" else None,
                  batch=batch, devices=1)
        temps[mode] = compile_step_for(
            ff, topo, (batch, 64)).memory_analysis().temp_size_in_bytes
    assert temps["on"] <= 0.8 * temps["off"], temps


def test_one_chip_step_keeps_qkvo_lane_dense(topo, on_tpu):
    """The searched one-chip step of the `bert_ae` cell at depth 2 and
    its batch of 32: two named kernels a layer, and between the q/k/v
    projections and the output projection no XLA pass over a
    q/k/v/o-sized array. With q, k, v as [b, heads, s, 64] this step
    held seven `copy` instructions of a bf16[32,16,512,64] a layer
    (7.9 ms of a 119.7 ms step on the chip, PR 28) and every kernel
    operand padded 64 lanes to 128."""
    ff = build_bert(2, 32, chips=1, search_budget=30)
    assert sum("_k:flash" in c for c in _choices(ff)) == 2
    hlo = compile_step_for(ff, topo).as_text()
    kernels = [re.search(r"tpu_custom_call_(\w+)", line.split(
        "metadata=")[-1]).group(1) for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(kernels) == ["flash_bwd"] * 2 + ["flash_fwd_whole"] * 2
    assert layout_faults(hlo, 32 * 512 * 1024 * 2) == []
    # the non-causal op's kernels stay top-level calls (their events keep
    # the name `tpu_custom_call*` that `kernels.flash_roofline` sums);
    # what lies around them is under `attention_plain`
    table = table_of(hlo)
    calls = [table[m.group(1)] for m in re.finditer(
        r'^\s*%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"',
        hlo, re.M)]
    assert all("jit(" not in c["op_name"].replace("jit(train_step)", "")
               for c in calls)
    assert sorted((c["part"], c["direction"]) for c in calls) == [
        ("attention", "backward")] * 2 + [("attention", "forward")] * 2
    assert "jvp(jit(attention_plain))" in hlo
    assert "transpose(jvp(jit(attention_plain)))" in hlo
    # the guard sees the form it guards against
    assert layout_faults(
        "  %copy.1 = bf16[32,16,512,64]{3,2,1,0:T(8,128)(2,1)} copy(%x)\n"
        '  %k = bf16[512,512,64]{2,1,0} custom-call(%a), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints={bf16[512,512,64]'
        '{2,1,0}}, metadata={op_name="jvp(tpu_custom_call_flash_fwd_whole)"}',
        32 * 512 * 1024 * 2) == [
            "copy copy.1 bf16[32,16,512,64]",
            "flash operand bf16[512, 512, 64]{2,1,0}",
            "flash operand bf16[512, 512, 64]{2,1,0}"]


# arm -> fewest kernels its step holds: a flash forward and backward per
# layer; the searched four-chip step adds fused updates; the pipeline
# (searched at batch 8) runs one block per tick inside a loop; the ring's
# 256-row shards are below MIN_SEQ_FOR_FLASH and take the einsum body
ARM_KERNELS = {"one_chip": 24, "one_chip_searched": 24, "dp": 24,
               "searched": 25, "searched_b8": 2, "hybrid": 24, "ring": 0}


@pytest.mark.slow
@pytest.mark.parametrize("arm", list(ARM_KERNELS))
def test_full_depth_step_of_each_chip_smoke_arm(topo, on_tpu, arm):
    build = {
        "one_chip": lambda: build_bert(12, 8, chips=1),
        "one_chip_searched": lambda: build_bert(12, 8, chips=1,
                                                search_budget=30),
        "dp": lambda: build_bert(12, 32, only_data_parallel=True),
        "searched": lambda: build_bert(12, 32, search_budget=30,
                                       enable_parameter_parallel=True),
        "searched_b8": lambda: build_bert(12, 8, search_budget=30,
                                          enable_parameter_parallel=True),
        "hybrid": lambda: build_bert(12, 32, {"data": 2, "model": 2},
                                     enable_parameter_parallel=True),
        "ring": lambda: build_bert(12, 32, {"data": 2, "seq": 2},
                                   seq_parallel="seq"),
    }
    ff = build[arm]()
    compiled = compile_step_for(ff, topo)
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9
    assert pallas_kernel_count(compiled.as_text()) >= ARM_KERNELS[arm]
