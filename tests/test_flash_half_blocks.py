"""Grouped-query keys at two heads a 128-lane block (PR 47): the flash
kernels on k, v [B, S, Hk*64] against the same kernels on `jnp.repeat`ed
keys, in every kernel family, and the rule that admits the shapes. A
file of its own beside `test_flash_kernels.py` (whose helpers it takes)
so that the interpreted kernels at 32 heads do not lengthen that file's
worker. What the OP does with such shapes is `GROUPED_OPS` there and
`tests/test_attention_route.py`."""

import jax.numpy as jnp
import pytest
from test_flash_kernels import _assert_grouped_is_the_repeated_form, _qkv

from flexflow_tpu.ops import pallas_kernels as pk

# heads of 64 (PR 47): (kernel family) -> (positions, mask)
HALF_BLOCK_KINDS = {
    "whole_tile": (256, dict()),                 # flash_fwd_whole, flash_bwd
    # flash_fwd, flash_bwd_blocked: the shortest length past MAX_BWD_SEQ
    # at which the super-blocks engage (two Q blocks a K chunk of 512)
    "chunk_loop": (1536, dict()),
    # the span kernels: past MAX_BWD_SEQ, the same [256, 384] tile a block
    "one_span": (1280, dict(window=128)),
}


@pytest.mark.parametrize("kind", list(HALF_BLOCK_KINDS))
@pytest.mark.parametrize("heads", [(8, 2), (32, 8)], ids=["8_2", "32_8"])
def test_grouped_keys_at_two_heads_a_lane_block(heads, kind, monkeypatch):
    """`num_kv_heads` at heads of 64 (PR 47): a column block's two query
    heads share ONE KV head, a HALF of the K / V lane block that the
    BlockSpec's `j // rep` picks; the kernels lay that half twice side by
    side (`_own_kv_head`), which is the block the repeated form fetches,
    or in the blocked forwards move the query heads to it
    (`_half_moved`: the same products with the zeroed lanes elsewhere),
    so o, lse and dQ are its bits; the backward adds the two heads'
    dK^T / dV^T into the KV head's half of its float32 panel
    (`_group_halves`), the other half zeros from these members: within
    `2 U` of the sum of the repeated form's rounded partials, as at 128.
    In every kernel family the rule admits."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    (h, hk), d = heads, 64
    seq, kw = HALF_BLOCK_KINDS[kind]
    assert pk.grouped_kv_shape_legal(h, hk, d)
    assert (pk.one_span(seq, True, **kw) is not None) == (kind == "one_span")
    assert pk.super_block_engaged(seq, True, 0, None, 0) == (
        kind == "chunk_loop")
    q, _, _, do = _qkv(seq, d, jnp.bfloat16, seed=seq + h, h=h)
    k, v, _, _ = _qkv(seq, d, jnp.bfloat16, seed=seq + h + 1, h=hk)
    _assert_grouped_is_the_repeated_form(q, k, v, do, h, hk, True, kw)


@pytest.mark.parametrize("h,hk,d,legal", [
    (32, 8, 64, True), (4, 2, 64, True), (8, 2, 64, True),
    (6, 2, 64, False),      # a group of 3: a column block meets two KV heads
    (6, 3, 64, False),      # three KV heads of 64 are no whole lane blocks
    (2, 1, 64, False), (8, 4, 32, False), (4, 4, 64, False),
    (7, 1, 128, True), (64, 8, 128, True), (4, 4, 128, False),
    (6, 4, 128, False)])
def test_the_grouped_rule_is_a_function_of_the_heads_shapes(h, hk, d, legal):
    assert pk.grouped_kv_shape_legal(h, hk, d) == legal
