"""Deviceless compiles for a described TPU v5e (see `tests/tpu_compile.py`
and `tests/test_tpu_compile.py`): the flash kernels at the cells' real
widths and at the gate's bounds, forward and backward. Nothing runs."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from flexflow_tpu.obs.inspect import pallas_kernel_count
from flexflow_tpu.ops import pallas_kernels as pk
from tpu_compile import (_compile, _no_compilation_cache,  # noqa: F401
                         described_mesh, layout_faults, on_tpu, topo)


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


def _flash_lse_grads(heads):
    def grads(q, k, v):
        def loss(q, k, v):
            o, lse = pk.flash_attention_lse(q, k, v, heads, False, False)
            return o.sum() + lse.sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    return grads


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
