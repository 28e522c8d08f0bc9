"""The expert layers' grouped products (PR 53): the megablox kernels under
the tiles `moe._gmm_tiling` gives them, interpreted, against
`lax.ragged_dot`; that rule, a function of the static shapes, pinned at
the six cells' shapes; the buffer the layer makes for the products
(`experts.buffer_rows`); what the layer's gauges say of them."""

import jax
import numpy as np
import pytest

from flexflow_tpu.ops import experts, moe

HIGHEST = jax.default_matmul_precision("highest")


# ---------------------------------------------------------------------------
# the kernels against `ragged_dot`, a case a branch of the rule

# name -> (m, k, n, group sizes, the tiling the rule gives the forward
# product, VMEM budget to run under or None for the module's)
CASES = {
    # small groups: the narrow row tile; an empty group first and in the
    # middle, a group smaller than a tile, boundaries inside tiles, rows
    # past the groups' sum
    "tile_128": (512, 128, 256, [0, 100, 28, 0, 200, 60], (128, 128, 256),
                 None),
    # 1,024 rows of buffer a group and more: 256 rows a step; the second
    # group starts inside a tile and the third is empty
    "tile_256": (3072, 64, 128, [1100, 1500, 0], (256, 64, 128), None),
    # ... unless the buffer is not whole tiles of 256
    "tile_128_for_an_odd_buffer": (1152, 64, 128, [700, 300],
                                   (128, 64, 128), None),
    # an output wider than 1024 goes in its largest divisor that is a
    # multiple of 128; every row holds a pair
    "n_in_divisors": (256, 64, 1792, [100, 156], (128, 64, 896), None),
    # no such divisor: 1024s and a ragged last tile
    "n_ragged": (256, 64, 1088, [0, 256], (128, 64, 1024), None),
    # the blocks would not fit: a narrower output tile, the contraction
    # still whole
    "n_narrowed_for_vmem": (256, 256, 1024, [31, 200], (128, 256, 256),
                            800_000),
    # not even at 128 lanes of output: the contraction in 1024s
    "contraction_split_for_vmem": (128, 2048, 128, [100], (128, 1024, 128),
                                   2_000_000),
    # no group holds a row
    "all_empty": (256, 128, 128, [0, 0, 0], (128, 128, 128), None),
}


@pytest.mark.parametrize("name", list(CASES))
def test_grouped_matmul_interpreted_matches_ragged_dot(name, monkeypatch):
    """`grouped_matmul` under the interpret mode (the TPU's path: megablox
    `gmm` forward and for `d lhs`, `tgmm` for `d rhs`) against `lax.ragged_dot`
    with the kernels off: the output (zero past the groups' sum), and
    both gradients (zero for the rows past the sum; zero for an empty
    group's matrix)."""
    m, k, n, sizes, tiling, vmem = CASES[name]
    if vmem:
        monkeypatch.setattr(moe, "GMM_VMEM_BYTES", vmem)
    assert moe._gmm_tiling(m, len(sizes), k, n) == tiling
    rs = np.random.RandomState(3)
    lhs = rs.randn(m, k).astype(np.float32)
    rhs = rs.randn(len(sizes), k, n).astype(np.float32)
    probe = rs.randn(m, n).astype(np.float32)
    sizes = np.asarray(sizes, np.int32)
    held = int(sizes.sum())

    def run(mode):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)

        def both(lhs, rhs, sizes, probe):   # one program a mode
            out, back = jax.vjp(
                lambda a, b: moe.grouped_matmul(a, b, sizes), lhs, rhs)
            return (out, *back(probe))
        with HIGHEST:
            return jax.jit(both)(lhs, rhs, sizes, probe)

    got, want = run("interpret"), run("off")
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-3)
    out, d_lhs, d_rhs = got
    assert not np.any(out[held:]) and not np.any(d_lhs[held:])
    assert not np.any(np.asarray(d_rhs)[np.asarray(sizes) == 0])


# ---------------------------------------------------------------------------
# the rule at the six cells' shapes

# cell -> (tokens, k, experts, held, d, f, the buffer's rows until PR 53,
# its rows now, the row tile)
CELLS = {
    "lfm2_8b_a1b": (16384, 4, 32, 8, 2048, 1792, 24704, 24832, 256),
    "sdar_30b_a3b": (16384, 8, 128, 16, 2048, 768, 24704, 24832, 256),
    "smallthinker_21b_a3b": (16384, 6, 64, 8, 2560, 768, 18560, 18688, 256),
    "nemotron3_nano_30b_a3b": (8192, 6, 128, 8, 2688, 1856, 4736, 4736, 128),
    "laguna_xs2": (8192, 8, 256, 16, 2048, 512, 6272, 6272, 128),
    "joyai_llm_flash": (4096, 8, 256, 8, 2048, 768, 1664, 1664, 128),
}
# cell -> the (contraction tile, output tile) of up / gate, down, their
# two `d lhs`, and the (k tile, n tile) of the two `tgmm`
TILES = {
    "lfm2_8b_a1b": [(2048, 896), (1792, 1024), (1792, 1024), (2048, 896),
                    (1024, 896), (896, 1024)],
    "sdar_30b_a3b": [(2048, 768), (768, 1024), (768, 1024), (2048, 768),
                     (1024, 768), (768, 1024)],
    "smallthinker_21b_a3b": [(2560, 768), (768, 640), (768, 640),
                             (2560, 768), (640, 768), (768, 640)],
    "nemotron3_nano_30b_a3b": [(2688, 1024), (1856, 896), (1856, 896),
                               (2688, 1024), (896, 1024), (1024, 896)],
    "laguna_xs2": [(2048, 512), (512, 1024), (512, 1024), (2048, 512),
                   (1024, 512), (512, 1024)],
    "joyai_llm_flash": [(2048, 768), (768, 1024), (768, 1024), (2048, 768),
                        (1024, 768), (768, 1024)],
}
PRODUCTS = ["up", "down", "up.dlhs", "down.dlhs", "up.tgmm", "down.tgmm"]


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_buffer_is_whole_row_tiles_and_never_smaller(cell):
    tokens, k, n_experts, held, _, _, before, now, tile = CELLS[cell]
    rows = experts.buffer_rows(tokens * k, held, n_experts, 0.5)
    assert rows == now and before <= rows < before + 256
    assert moe.gmm_row_tile(rows, held) == tile and rows % tile == 0
    # every expert held: a row a pair, no slack
    assert experts.buffer_rows(tokens * k, held, held, 0.5) == tokens * k


@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_rule_at_the_cells_shapes(cell, product):
    """(row tile, contraction tile, output tile) of each of a layer's six
    products: the contraction of a `gmm` product is whole in every cell
    (the weight panel resident over a group), and a step's blocks fit the
    budget."""
    *_, held, d, f, _, rows, tile = CELLS[cell]
    at = PRODUCTS.index(product)
    contraction, out = (d, f) if product.startswith("up") else (f, d)
    if product.endswith(".dlhs"):
        contraction, out = out, contraction
    transposed = product.endswith(".tgmm")
    tiling = moe._gmm_tiling(rows, held, contraction, out,
                             transposed=transposed)
    assert tiling == (tile, *TILES[cell][at])
    tm, tk, tn = tiling
    if not transposed:
        assert tk == contraction
        assert (4 * (tm * tk + tk * tn + tm * tn) + 8 * tm * tn
                <= moe.GMM_VMEM_BYTES)


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_gauges_value_at_the_cells_shapes(cell, monkeypatch):
    """`grouped_products_walk`, what `MoELayer.traced_gauges` publishes a
    layer: the row tile and all of the layer's `gmm` products where the
    kernels run, (0, 0) where `lax.ragged_dot` does."""
    *_, held, d, f, _, rows, tile = CELLS[cell]
    matrices = 2 if cell.startswith("nemotron") else 3
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert moe.grouped_products_walk(rows, held, d, f, matrices) == (
        tile, 2 * matrices)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    assert moe.grouped_products_walk(rows, held, d, f, matrices) == (0, 0)
