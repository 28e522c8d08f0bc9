"""Pallas TPU kernels for the ops where XLA's default lowering underperforms.

Analog of the reference's hand-written CUDA kernels (src/ops/kernels/*.cu)
— but only where needed: XLA already fuses elementwise chains into matmuls,
so the win is in attention, where materializing the [B,H,S,S] score tensor
in HBM is the bottleneck. ``flash_attention`` keeps a head's scores in
VMEM: forward and backward recompute them from Q and K, and only the
per-row logsumexp goes to HBM beside the output.

Four attention kernels, told apart in a trace by name. Up to MAX_BWD_SEQ
a grid step holds the whole S x S tile of several heads (`flash_fwd_whole`,
`flash_bwd`: dQ/dK/dV from P recomputed out of the saved LSE); longer
sequences take the Q-blocked forward (`flash_fwd`) and the K-blocked
backward (`flash_bwd_blocked`), up to MAX_FLASH_SEQ — the one upper bound
the gate (``flash_attention_available``), the backward dispatch and the
native ``kernel_gate`` share; past it attention runs the einsum path.

One visibility rule (``visible``, PR 31): under ``causal`` a query sees
the keys up to its own, and with a ``window`` only the last ``window`` of
them. The whole-tile kernels mask; the blocked ones also SKIP: the
forward loops over the K chunks, the backward over the Q chunks, that
hold a visible pair (``_k_chunks``, ``_q_chunks``), so a causal layer
does half the square's work and a window layer S x window of it.
``window >= S`` is ``causal``, bit for bit (``normalized_window``).
A third kind (PR 34) is not an interval of keys: ``block_diffusion =
(L, B)`` over a sequence of 2L positions that holds a noised copy of a
sample and then the clean one, in blocks of B. A noised block sees itself
and the clean blocks before it, a clean block the clean blocks up to
itself: a tile's visible K chunks (forward) and Q chunks (backward) are
then TWO ranges (``_k_ranges``, ``_q_ranges``), and the blocked kernels
loop over both. Of the tiles they visit they MASK only those that hold a
hidden pair (PR 35): each range is cut, by ``visible`` at a tile's
extreme pairs, into sub-ranges of interior tiles, whose loop body builds
no mask, and of edge tiles (``_k_split``, ``_q_split``). Under a causal
window so narrow that a block's reach fits one score tile (PR 46;
``one_span``: window + 255 <= 1024 positions, laguna's 512)
there is no chunk loop at all: a Q block meets the keys it reaches, a K
block the queries that see it, as ONE masked tile each, the plain
softmax in straight-line code (``_flash_fwd_span_kernel``,
``_flash_bwd_span_kernel``). In the chunk loop a forward grid step
takes the Q blocks of one K chunk together (PR 51; ``super_block``), as
independent chains of one loop body, and of a chunk that holds a
block's own positions, or lies a whole window from them, a sub-tile
(a sub-block of the backward's K block) takes the static slice it can
see any of (``_key_trim``, ``_query_trim``): 1.06 visited pairs a
visible one at 4,096 positions where whole tiles visit 1.25.

One operand form (PR 30): q, k, v, o and their gradients are
[B, S, H*D], the heads side by side along the lanes, which is what the
projections' 2-D products write and the output projection reads. A
block is S (or a block of) rows by one column block of 128 lanes: one
head of 128 or two of 64 (``_heads_per_block``), picked by the
BlockSpec's last index. So no XLA pass changes a layout between a
projection and a kernel, and no array the kernels touch pads a 64-wide
minor dimension to 128 lanes in HBM. Inside, a block's K^T, V^T, O^T,
dK^T ... are [128, S] tiles whose heads are sublane ranges; where one
head's lanes are wanted out of a [rows, 128] operand the others are
zeroed (``_only_head``) and the MXU contracts all 128 (in one kernel
the zeroed lanes carry something: `index_select`'s float32 index
products lay a head's bfloat16 LOW part there, so three products are
two passes; PR 55, ``_index_tiles``). Under
grouped-query attention with heads of 128 (PR 43) k and v are
[B, S, Hk*D], the KV heads alone: a K / V BlockSpec's last index is the
query column block's group, ``j // rep``, and the backward adds a
group's dK and dV up in the KV head's float32 panel; no array holds a
K or V head once for each of its query heads. The same at heads of 64
(PR 47) where a column block's two query heads share ONE KV head, which
is then a 64-lane half of the K / V lane block ``j // rep``: the
kernels move the query heads to that half (``_half_moved``, the blocked
forwards) or lay it twice (``_own_kv_head``), and add the two heads' dK
and dV into it (``_group_halves``).

Every MXU product takes its operands in the dtype the caller stored and
accumulates in float32 (``_dot``); softmax statistics, ``exp``, scale
and mask are float32. Tile sizes follow from the shape
(``_heads_per_block``, ``_rows_per_step``, ``_seq_block``); the timings
quoted beside them are the kernels alone on a v5e (PR 28), and PERF.md
has what they gave through the benchmark's full step.

Kernels outside the attention core: `moe_sum_rows` (PR 37), the expert
layer's sum of rows into their tokens, with its transpose
`moe_spread_rows` (PR 49), that sum's backward; and `rotary_lanes` (PR
42), the heads' RMS norm and rotary in one pass over a projection's
[B, S, H*128] result (or [B, S, H*64], two heads a 128-lane column, PR
47), between the product and a flash kernel; `gated_conv_lanes` (PR 45),
the gated short convolution's pass between its two products; and
`selective_scan` (PR 52), the Mamba-1 recurrence with a decay a channel
and state, forward and backward, time walked inside the kernel;
`delta_rule_fused` (PR 58), the gated delta rule in chunks, and `ssd_scan`
(PR 62), the Mamba-2 recurrence in chunks: one kernel each way, the state
in VMEM, the backward the chunk function's `jax.vjp` inside the kernel;
`hc_read_lanes` / `hc_write_lanes` (PR 64), the two passes of a
hyper-connection over the residual streams, one kernel each way.

CPU fallback: the same kernels run under ``interpret=True`` when
FLEXFLOW_TPU_PALLAS=interpret (used by the deviceless tests); otherwise
non-TPU backends take the XLA path.
"""

from __future__ import annotations

import functools
import operator
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLK_Q = 128  # the tile every admitted S is a multiple of (flash_shape_legal)
LANES = 128  # a vreg's and an HBM tile's minor extent

# Mosaic's default scoped-VMEM budget on v5e is 16 MiB, which the
# kernels below outgrow long before the chip's 128 MiB of VMEM is used
# (they keep whole [S, 128] panels resident): at 16 MiB the compiler
# refuses the bf16 K-blocked backward from S = 8192 and the forward from
# S = 16384. Every flash pallas_call asks for 96 MiB instead; with that
# the deviceless v5e compile accepts forward and both backwards for
# S <= MAX_FLASH_SEQ at head_dim <= 128 in bf16 and f32
# (tests/test_tpu_compile.py). A head of 256 lanes (PR 58) does not fit
# these kernels' resident [S, W] panels at S = 16384 (the compile was
# refused); it takes kernels of its own, one [Q block, K block] tile a
# grid step: two since PR 59, `_wide_flash_fwd` (tiles of 512 x 1024) and
# `_wide_flash_bwd` (tiles of 1024 x 1024; dQ, dK and dV from ONE kernel);
# 16 : 2 heads of 256 at S = 16384 accepted deviceless, bf16 and f32.
# They ask for the same 96 MiB: the forward uses about 8 MiB of it (a
# [512, 1024] float32 score tile, its probabilities, double-buffered
# [512, 256] and [1024, 256] operand blocks), the backward about 50 in
# bf16 and 66 in f32 (K, V, dK and dV as blocks of four K blocks,
# [4096, 256], double-buffered, dK and dV float32 scratch of the same,
# and four [1024, 1024] float32 tiles of scores).
_FLASH_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=96 << 20)
# with a mask operand (PR 54) a grid step also holds its rows of the
# mask, [1024, S] bytes twice (16 MiB each at S = 16384), which the
# backward's panels leave no room for under 96 MiB
_MASKED_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=120 << 20)

# Every kernel's ``pallas_call`` carries a ``name=``: XLA names the custom
# call's HLO instruction after it, and the profiler names a device event
# by its instruction, so a chip trace shows `tpu_custom_call_flash_fwd.3`
# where it showed `tpu_custom_call.3`. The flash names keep the prefix an
# unnamed kernel gets: reductions of a trace find them by it (benchmarks/
# trace_reduce.KERNEL_PREFIX); `moe_sum_rows` and `moe_spread_rows` go
# without, as `gmm` does.
KERNEL_NAME_PREFIX = "tpu_custom_call_"

# Longest sequence whose whole S x S f32 score tile of a head is one grid
# step's work, forward (`flash_fwd_whole`) and backward (`flash_bwd`)
# alike. Re-measured in PR 28 (v5e, kernel alone, bf16, head_dim 64): at
# S = 1024 the whole-tile backward takes 652 us for 128 heads against 745
# for the K-blocked one, the whole-tile forward 408 against 700 for the
# Q-blocked one; past it the tile (4 bytes x S^2, and three more like it)
# outgrows the VMEM budget.
MAX_BWD_SEQ = 1024
# Upper bounds of the flash path, forward and K-blocked backward alike.
# What binds is the VMEM budget above, not the chip's physical VMEM: the
# blocked backward holds the Q/O/dO/dQ panels of a column block ([S, 128]:
# one head of 128 or two of 64 side by side, every lane a value) plus
# [BLK, BLK] f32 score tiles, and the forward holds the K/V panels plus
# a chunk's tile. Mirrored by the native kernel_gate
# (native/ffs_strategy.hpp) so the search never prices a length the
# compiler refuses.
MAX_FLASH_SEQ = 16384
# 128 for every form of the kernels; 256 exactly (two lane blocks a head)
# under a causal or plain mask with or without a window (PR 58)
MAX_FLASH_HEAD_DIM = 256

_NT = (((1,), (1,)), ((), ()))  # a[m, c] . b[n, c] -> [m, n]
_NN = (((1,), (0,)), ((), ()))  # a[m, c] . b[c, n] -> [m, n]


def _dot(a, b, dims):
    """One MXU product: operands in the dtype they have, f32 accumulator.
    q, k, v, dO come as the caller stored them; the kernels round `P` and
    `dS` to that dtype for the product they enter and for nothing else
    (as the einsum path rounds `probs`), so bf16 storage means bf16
    products and f32 storage f32 products."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def split_heads(x, num_heads: int):
    """[B, S, H*D], the kernels' form, as [B, H, S, D]."""
    b, s, hd = x.shape
    return x.reshape(b, s, num_heads, hd // num_heads).transpose(0, 2, 1, 3)


def merge_heads(x):
    """[B, H, S, D] as the [B, S, H*D] the kernels take."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _heads_per_block(num_heads: int, head_dim: int) -> int:
    """Heads that sit side by side in one column block of a [B, S, H*D]
    operand: as many as fill 128 lanes (two at head_dim 64, one at 128),
    and all of them where the whole row is narrower than that."""
    return min(num_heads, max(1, LANES // head_dim))


def _rows_per_step(batch: int, heads_per_block: int, s: int) -> int:
    """Batch rows a grid step of the whole-tile kernels works through,
    each with the heads of its column block: the most that keeps the
    step at 8 heads and S^2 x heads within 2^21 score elements, and
    divides ``batch``. A step costs about 0.35 us before it computes.
    v5e, bf16, head_dim 64, forward / backward us for the same work at
    the [B*H, S, 64] operand form of PR 28, every head of a step
    unrolled: S = 512: 618 / 864 at 1 head a step, 462 / 752 at 4,
    449 / 737 at 8; S = 1024: 422 / 681 at 1, 408 / 652 at 2; S = 256:
    499 / 735 at 4, 438 / 704 at 8, 422 / 705 at 16."""
    rows = max(1, 8 // heads_per_block)
    while rows > 1 and (rows * heads_per_block * s * s > 1 << 21
                        or batch % rows):
        rows //= 2
    return rows


def _for_rows(rows: int, unroll: int, body) -> None:
    """``body(b)`` for every batch row of a grid step: a loop whose
    iteration works through ``unroll`` rows, so that the scheduler can
    fill one head's MXU waits with the next one's VPU passes without the
    kernel growing with the step (both powers of two). v5e, S = 512, 8
    heads a step (PR 28): the forward takes 584 us at one head an
    iteration, 509 at 2, 474 at 4, 449 all 8 unrolled; the backward 777,
    734, 737, 737. Twelve layers' kernels lower and compile (deviceless,
    cold) in 0.7 + 1.2 s at forward 4 / backward 2, in 1.5 + 3.7 s
    unrolled."""
    unroll = min(unroll, rows)

    def step(i, carry):
        for u in range(unroll):
            body(i * unroll + u)
        return carry

    jax.lax.fori_loop(0, rows // unroll, step, None)


def _seq_block(s: int, block_diffusion=None, window: int = 0) -> int:
    """Rows of a block along the sequence in the blocked kernels (the K
    chunks the forward's loop takes, the K rows a grid step and the Q
    chunks a loop step of the backward): the largest of 1024, 512, 256,
    128 that divides S. Until PR 31 a step held a block against the
    WHOLE sequence, a [BLK, S] tile, and the block had to shrink with S;
    a step's tile is now [BLK, BLK] whatever S. v5e, bf16, kernels alone,
    forward / backward ms (PR 31, Q blocks of 256): 7 heads of 128 at
    S = 16384, window 4096: 3.90 / 6.21 at 256, 2.38 / 4.23 at 512,
    2.43 / 4.02 at 1024; the same, full causal: 8.00 / 12.88, 4.40 /
    8.33, 4.15 / 7.71 (the parent's kernels, which visit every block:
    7.05 / 14.42); 4 heads of 128 at S = 8192, causal: 1.27 / 1.95,
    0.75 / 1.27, 0.73 / 1.21 (parent 1.08 / 2.03); 16 heads of 64 at
    S = 8192, not causal, where nothing is skipped and the chunks' running
    softmax is all cost: 8.55 / 9.16, 4.59 / 6.18, 4.14 / 5.41 (parent,
    one pass over a [128, S] tile: 3.85 / 5.30).

    Under ``block_diffusion`` (L, B) the block divides L, so that none
    lies across the halves. Until PR 35 it was 512 at most there (a
    quarter of the square is visible, along two diagonals whose tiles
    are visited whole: at 2L = 16384 blocks of 1024 visit 0.3125 of the
    square, blocks of 512 0.281); re-measured (PR 35, 8 heads of 128,
    2L = 16384, B = 4, Q blocks of 256, forward / backward ms): 2.87 /
    5.11 at 512, 2.83 / 4.97 at 1024: a chunk's fixed cost (the running
    sums' rescaling, the seven transposes and dQ's read-modify-write a
    tile) outweighs a ninth more of the square.

    Under a ``window`` (as ``normalized_window`` leaves it) narrower than
    1024 the block is the window's size at most, and 256 at least (PR
    41): a Q block of 256 rows reaches 255 + window keys, which lie in
    one or two chunks of 1024 whatever the window, and a K block of 1024
    meets two Q chunks of 1024. At window 512, S = 8192 the forward
    visits 1536 keys a row at chunks of 1024 and 1024 at 512, the
    backward 2048 queries a key and 1024, for 512 visible; every visited
    tile is an edge tile either way. v5e, bf16, kernels alone, 64 heads
    of 128, S = 8192, window 512, forward / backward ms (PR 41,
    `scripts/flash_lab.py --only laguna`): 4.19 / 7.97 at 1024, 3.75 /
    5.81 at 512, 4.64 / 7.13 at 256 (a chunk's fixed cost again).

    Since PR 46 such a window does not take this loop at all: where a
    block's reach fits one tile (``one_span``) the kernels take it as
    ONE span, and this block is only what the chunk loop WOULD take (the
    two-part score still does). The same shape, the same lab (PR 46,
    `--only narrow_window`; the three rows above re-read to the digit),
    forward / backward ms, a line's two kernels at one tile, written
    rows of a block / positions of its span, and tiles a grid step x
    tiles a loop iteration (``_span_tiles``; the loop carries nothing).
    At 256 / 768 (1.55 visited pairs a visible one): 2.64 / 4.61 at
    1 x 1; 2.22 / 3.51 at 8 x 1 and the same at 16 x 1; 2.02 / 3.18 at
    8 x 2, 2.00 / 3.15 at 16 x 2; 1.89 / 3.01 at 16 x 4 and at 32 x 4;
    1.97 / 3.15 at 8 x 8. At 128 / 640 (1.29): 2.81 / 5.24 at 1 x 1;
    2.47 / 3.18 at 32 x 2; 2.16 / 3.10 at 16 x 4, 2.16 / 2.76 at 32 x 4,
    2.15 / 2.74 at 64 x 4; 2.17 / 3.05 at 16 x 8. At 512 / 1024 (2.06):
    2.58 / 4.95 at 1 x 1, 2.25 / 3.84 at 16 x 2. **As shipped, 256 / 768
    forward at 32 x 4 and 128 / 640 backward at 64 x 4: 1.89 / 2.74**
    (3.75 / 5.81 in the chunk loop). What the table says: a third of
    the gain over the chunk loop is the visited pairs and the loop's
    carries; a grid step's own cost is small (1 x 1 -> 8 x 1) next to
    what the scheduler gains from several tiles in one loop iteration,
    one tile's MXU waits filled with the next one's VPU passes (x 1 ->
    x 4; all eight unrolled lose again); the backward's five products of
    a [256, 768] tile are 2.6 ms at the chip's peak, so at 3.01 it is
    bound by the pairs it visits and only the shorter block helps it,
    and that only where a step is a head long (16 x 4 -> 64 x 4: a
    head's 10 MB of panels are fetched behind the step before); the
    forward is bound by the tile's element-wise passes and wants the
    taller tile. The upper end of the rule, a window of 768 (spans 1024
    and 896): chunk loop (chunks of 512) 4.26 / 7.63; 256 / 1024 2.77 /
    5.49 at 1 x 1, 2.22 / 3.89 at 32 x 4; 128 / 896 2.55 / 3.70 at
    64 x 4; as shipped 2.22 / 3.70.

    Since PR 51 the chunk loop's own blocks a grid step follow
    ``super_block``: the forward takes the Q blocks of one K chunk
    together, and both directions take of a chunk at their own
    positions the part a sub-tile can see (that lab's table is in
    ``super_block``'s docstring)."""
    if block_diffusion is not None:
        s = block_diffusion[0]
    most = max(window, 2 * BLK_Q) if 0 < window < 1024 else 1024
    return next(b for b in (1024, 512, 256, BLK_Q)
                if b <= most and s % b == 0)


def _q_block(s: int, block_diffusion=None) -> int:
    """Q rows a grid step of the blocked forward takes: 256 where that
    divides S, else the 128 every admitted S is a multiple of. v5e as
    above, K chunks of 512, forward ms at 128 / 256 / 512 rows: window
    3.18 / 2.38 / 2.30, full causal 6.24 / 4.40 / 4.22, S = 8192 causal
    1.00 / 0.75 / 0.74: a chunk's fixed cost (the loop, the rescaling of
    the running sums) is paid half as often. Under ``block_diffusion``
    the block divides L. Since PR 51 a grid step takes the Q blocks of
    one K chunk together (``super_block``, whose docstring has that
    lab's table)."""
    if block_diffusion is not None:
        s = block_diffusion[0]
    return 2 * BLK_Q if s % (2 * BLK_Q) == 0 else BLK_Q


# widest score tile (positions along the span) the one-span form takes
MAX_SPAN = 1024


def one_span(s: int, causal: bool, window: int = 0, block_diffusion=None,
             rope_dim: int = 0):
    """THE rule of the blocked kernels' form under a narrow causal window
    (PR 46): ((Q rows, span of keys), (K rows, span of queries)), the
    forward's and the backward's tile, where the positions a block can
    reach, window + rows - 1 rounded up to the 128 every S is a multiple
    of, fit ONE score tile of MAX_SPAN positions at most; None where the
    kernels take the chunk loop. A function of what the code can
    observe, (S, window), and of nothing else: `_flash_fwd`,
    `_flash_bwd`, the counters (``kv_blocks``, ``kv_blocks_masked``,
    ``visited_pairs``) and the op's route
    (``executor.flash_one_span_ops``) all ask it.

    Under a span the forward takes a Q block against the keys that end
    with the block's last query (``_k_span``), the backward a K block
    against the queries from its first key on (``_q_span``): one masked
    tile each, straight-line code, the plain softmax and no sums over
    chunks. The forward's block is ``_q_block``'s 256 rows (768 keys at a
    window of 512: 1.5 visited pairs a visible one), the backward's 128
    (640 queries: 1.25): the backward's five products run near the
    MXU's peak, so fewer visited pairs are its only gain, while the
    forward's element-wise passes want the taller tile (the lab's table
    in ``_seq_block``). The chunk loop stays for every wider window
    (smallthinker's 4096), for full causal, for ``block_diffusion``
    (which never carries a window) and for the two-part score
    (``rope_dim``; no cell's latent op has a window): a third
    half-supported form would be worse than none. The whole-tile kernels
    (S <= MAX_BWD_SEQ) hold the square."""
    window = normalized_window(s, causal, window)
    if (not window or s <= MAX_BWD_SEQ or rope_dim
            or checked_block_diffusion(s, causal, window, block_diffusion)):
        return None

    def tile(rows):
        return rows, -(-(window + rows - 1) // BLK_Q) * BLK_Q

    forward, backward = tile(_q_block(s)), tile(BLK_Q)
    return (forward, backward) if forward[1] <= MAX_SPAN else None


def _k_span(q0, blk: int, span: int):
    """First key of the ``span`` keys the one-span forward takes for the
    Q block at ``q0``: they end with the block's last query, and at the
    sequence's start the span is held at 0 (the mask hides what lies
    past the diagonal). A multiple of BLK_Q. Traced or, where the
    counters count, a Python int."""
    return _pick(max, jnp.maximum, q0 + blk - span, 0)


def _q_span(k0, span: int, s: int):
    """First query of the ``span`` queries the one-span backward takes
    for the K block at ``k0``: from the block's first key on, and at the
    sequence's end held at S - span (the mask hides the queries before
    ``k0``)."""
    return _pick(min, jnp.minimum, k0, s - span)


def _span_tiles(s: int, blk: int):
    """(one-span tiles a grid step works through, tiles a loop
    iteration at most): a step takes ``tiles`` blocks of ``blk`` rows,
    each against its own span, in a loop that carries nothing and whose
    iteration holds several tiles, so that the scheduler fills one
    tile's MXU waits with the next one's VPU passes (``_for_rows``). As
    many tiles as make a step a whole head up to 8,192 positions: a
    head's panels (10 MB in the backward) are fetched while the step
    before runs, and only a long step hides them. Past that a step's
    rows times S stay within 2^26 (half a head at 16,384), which keeps
    the float32 backward's panels and a step's K, V, dK, dV blocks
    inside the 96 MiB of VMEM. The lab's table is in ``_seq_block``'s
    docstring."""
    return next(n for n in (64, 32, 16, 8, 4, 2, 1)
                if n * blk * s <= 1 << 26 and s % (n * blk) == 0), 4


def _kept(trim, n: int, parts: int):
    """[lo, hi) of the ``parts`` equal parts of a chunk that part ``n``
    of a block of as many parts meets under ``trim``: the whole chunk
    (None); the parts up to its own ("upto": the keys of a diagonal
    chunk that lie behind a sub-tile's last query; the queries of a far
    chunk that still see a K sub-block); the parts from its own on
    ("from": the transpose of those); its own part alone ("own": the
    noised diagonal of the block-diffusion mask). In the parts left out
    no pair is visible. The kernels slice by it and ``visited_pairs``
    counts by it."""
    return {None: (0, parts), "upto": (0, n + 1), "from": (n, parts),
            "own": (n, n + 1)}[trim]


def _key_trim(n: int, of: int, peeled: bool, window: int, blk_k: int,
              block_diffusion):
    """``_kept``'s ``trim`` for sub-range ``n`` of the ``of`` that
    ``_k_split`` cut (in ITS order: who hands the kernels another cut
    holds ``super_block`` to one block), for a forward super-block that IS a
    K chunk and whose last sub-range is one chunk (``peeled``). That
    last chunk, the diagonal (under ``block_diffusion`` the last clean
    one), holds no visible key past a sub-tile's own last query. Under a
    window of whole chunks the first sub-range is the ONE far chunk,
    whose keys ahead of a sub-tile's first query lie more than the
    window behind it. Under ``block_diffusion`` (B | BLK_Q, which
    ``peeled`` says) the first is the noised chunk at the super-block's
    own positions, of which a sub-tile sees its own blocks of B."""
    if not peeled:
        return None
    if n == of - 1:
        return "upto"
    if n == 0 and block_diffusion is not None:
        return "own"
    if n == 0 and window and window % blk_k == 0:
        return "from"
    return None


def _query_trim(n: int, causal: bool, window: int, blk: int, sub: int,
                block_diffusion):
    """``_kept``'s ``trim`` for sub-range ``n`` of ``_q_split`` (in its
    order), for a K block in sub-blocks of ``sub`` keys: the transpose
    of ``_key_trim``. Under ``causal`` the first sub-range is the ONE
    chunk at the K block's own positions, hidden from the queries ahead
    of a sub-block's first key; under a window of whole chunks the third
    is the ONE far chunk, whose queries past a sub-block's last key no
    longer see it. Under ``block_diffusion`` (B | sub) the first and the
    third are the noised and the clean chunk at the K block's own
    positions, both hidden from the queries ahead of a sub-block's first
    key; whether the block is a noised one, whose own chunk ends with
    the sub-block too, is not known where the kernel is traced."""
    if block_diffusion is not None:
        b = block_diffusion[1]
        return "from" if n in (0, 2) and b < blk and sub % b == 0 else None
    if not causal:
        return None
    if n == 0:
        return "from"
    return "upto" if n == 2 and window % blk == 0 else None


def _union_is_each(s: int, window: int, block_diffusion, chains: int) -> bool:
    """Whether a super-block of ``chains`` Q blocks loops over the very
    sub-ranges each of its Q blocks would alone (``_k_split`` of the
    union against ``_k_split`` of every block, by the kernel's own
    lines): then no Q block meets a chunk it sees nothing of, no tile
    changes its class, and every count of tiles stands. True for a
    super-block that IS a K chunk under full causal, under a window of
    whole chunks (smallthinker's 4096: the far edge is chunk a - 4 and
    ``near`` a - 3 for every Q block of chunk a) and under
    ``block_diffusion`` with B | BLK_Q; not under a window that ends
    inside a chunk, where the later Q blocks of a super-block have
    left the first one's far chunk behind."""
    blk_q = _q_block(s, block_diffusion)
    blk_k = _seq_block(s, block_diffusion, window)
    causal = block_diffusion is None   # without a mask nothing is cut
    rows = chains * blk_q
    return s % rows == 0 and all(
        _k_split(q0, blk_q, blk_k, s, causal, window, block_diffusion)
        == _k_split(first, rows, blk_k, s, causal, window, block_diffusion)
        for first in range(0, s, rows)
        for q0 in range(first, first + rows, blk_q))


def super_block(s: int, window: int = 0, block_diffusion=None):
    """THE rule of the chunk-loop kernels' blocks a grid step (PR 51):
    ((Q blocks a grid step, Q blocks a loop iteration, whether their
    sub-tiles are trimmed), K sub-blocks of a trimmed chunk of the
    backward). A function of what the code can observe, (S, window,
    block_diffusion) as ``_flash_fwd`` normalized them, and of nothing
    else: `_flash_fwd`, `_flash_bwd`, ``visited_pairs`` and the op's
    route (``super_block_engaged``) ask it, as they ask ``one_span``.

    The forward's grid step takes a SUPER-BLOCK, the Q blocks of
    ``_q_block`` rows that make up one K chunk (4 x 256 = 1024; 2 at
    chunks of 512), where ``_union_is_each`` holds. Its chunk loops are
    one Q block's; an iteration loads the K / V chunk once and runs the
    Q blocks' [256, 1024] tiles against it, each with its own (max, sum,
    accumulator): independent chains, one's MXU waits filled with the
    next one's VPU passes. And because the super-block is aligned to
    the chunks, what a Q block sees of a chunk at the super-block's own
    positions (or a whole window behind them) is known where the kernel
    is traced: a sub-tile takes the STATIC slice of keys it sees any of
    (``_key_trim``), 10 of a diagonal chunk's 16 squares of 256 x 256.
    The backward's grid step is a K block as before; its chunk at the
    block's own positions (and a whole window ahead) runs as the K
    block's sub-blocks of ``_q_block`` keys, each against the slice of
    queries that see any of it (``_query_trim``), with the chunk's
    Q^T, dO^T and delta formed once and dQ's rows written once.

    v5e, bf16, kernels alone, ms an op (`scripts/flash_lab.py
    --programs super_block`, PR 51, three runs that agree to 0.002), at
    the cells' shapes: ouro 16 heads of 128, S 4096 | laguna 48, 8192 |
    joyai 32 of 128 + 64, 4096 | nemotron 4, 8192 | smallthinker 7,
    16384, full | the same, window 4096 | sdar 8, 2L 16384, B 4 | lfm2
    32 : 8 heads of 64, 16384. FORWARD, Q blocks a grid step x Q blocks
    a loop iteration, sub-tiles whole:
    1 x 1 (the parent) 0.712 | 7.215 | 1.918 | 0.608 | 3.841 | 2.205 |
    2.830 | 16.706;
    2 x 2  0.683 | 6.884 | 1.833 | 0.580 | 3.648 | 2.101 | 2.653 | 16.213;
    4 x 4  0.676 | 6.729 | 1.801 | 0.567 | 3.533 | 2.054 | 2.603 | 16.307;
    4 x 2 (two super-blocks of 512 rows a step, in a loop that carries
    nothing) 0.680 | 6.856 | 1.820 | 0.578 | 3.631 | 2.086 | 2.650 | 16.207;
    4 x 1  0.709 | 7.196 | 1.891 | 0.606 | 3.834 | 2.152 | 2.785 | 16.656;
    16 x 1 0.707 | 7.181 | 1.888 | 0.607 | 3.833 | 2.156 | 2.781 | 16.640;
    8 x 4  0.676 | 6.726 | 1.794 | 0.568 | 3.535 | 2.054 | 2.604 | 16.310;
    16 x 4 0.676 | 6.722 | 1.794 | 0.568 | 3.535 | 2.053 | 2.603 | 16.305;
    a head a step (16 / 32 / 64 x 4) - | 6.720 | - | 0.571 | 3.542 |
    2.062 | 2.605 | 16.309;
    4 x 4 TRIMMED (**as shipped**) **0.622 | 6.416 | 1.599 | 0.541 |
    3.449 | 1.875 | 2.413 | 15.870**;
    16 x 4 trimmed 0.620 | 6.378 | 1.593 | 0.540 | 3.431 | 1.873 | 2.402
    | 15.840.
    BACKWARD, the chunk at the K block's own positions (under a window
    the far one too; under block diffusion the noised and the clean one)
    in sub-blocks, each against the queries that see it:
    x 1 (the parent) 1.273 | 13.444 | 3.811 | 1.119 | 7.312 | 3.888 |
    4.966 | 22.566;
    x 2  1.154 | 12.925 | 3.474 | 1.066 | 7.123 | 3.586 | 4.697 | 22.040;
    x 4 (**as shipped**) **1.109 | 12.703 | 3.305 | 1.043 | 7.040 |
    3.444 | 4.602 | 21.804**.
    Rows of the first run that are not the shipped kernel's, a backward
    whose EVERY chunk ran as sub-blocks (the candidate of ISSUE 51: the
    chunk's three transposes and delta formed once, dQ written once,
    the sub-blocks' products independent): whole, x 2 1.269 | 13.385 |
    3.849 | 1.114 | 7.275 | 3.883 | 4.962 | 22.714, x 4 1.269 | 13.406 |
    3.826 | 1.116 | 7.292 | 3.892 | 4.968 | 22.902: within 0.5% of the
    parent and 1.5% SLOWER at heads of 64: a measured no; with the own
    chunk trimmed besides, x 4 1.109 | 12.687 | 3.312 | 1.041 | 7.026 |
    3.648 (no far chunk yet) | - | 22.065: what the trimming gives, and
    at heads of 64 less than with the other chunks left whole.

    What the table says. A grid step's own cost is nothing here (4 x 1,
    16 x 1 against 1 x 1: a step already holds 1-16 chunks); several
    super-blocks a step give 0.0-0.6% (16 x 4: one ships). Independent
    chains give the forward 5-8% (1 x 1 -> 4 x 4), a third of what PR
    46's one-span kernels gained from them: with a running softmax a
    chain's rescaling still stands in line, and at two heads a lane
    block (lfm2) four Q blocks are eight chains and no better than two
    (2 x 2 16.213, 4 x 4 16.307). The larger part is the PAIRS: a
    trimmed diagonal is 10 of 16 squares, which at S 4096 is 136 of 160
    squares a head forward and backward (ouro -8.0% and -12.9%, joyai
    -11.2% and -13.3%), at 16,384 full 2,080 of 2,176 (-2.4%, -3.7%);
    under smallthinker's window the far chunk doubles it (-8.7%,
    -11.4%); under sdar's mask the noised chunk is 4 of 16 squares
    forward (-7.3%) and 10 of 16 backward (-7.3%; that a noised K
    block's chunk also ENDS with the sub-block cannot be known where
    the kernel is traced). Visited pairs a visible one, forward and
    backward: 1.25 -> 1.06 at S 4096, 1.12 -> 1.03 at 8,192, 1.14 ->
    1.06 under the window (``visited_pairs``). Neither the heads a lane
    block nor the two-part score (``rope_dim``) changes which form
    wins, so the rule does not ask them."""
    rows = _q_block(s, block_diffusion)
    chains = _seq_block(s, block_diffusion, window) // rows
    aligned = _union_is_each(s, window, block_diffusion, chains)
    forward = (chains, chains, True) if aligned else (1, 1, False)
    return forward, chains


def super_block_engaged(s: int, causal: bool, window: int = 0,
                        block_diffusion=None, rope_dim: int = 0) -> bool:
    """Whether the flash kernels of this op take the chunk loop with
    more than one block a grid step (``super_block``): what the op's
    route publishes as ``executor.flash_super_block_ops``."""
    window = normalized_window(s, causal, window)
    bd = checked_block_diffusion(s, causal, window, block_diffusion)
    if s <= MAX_BWD_SEQ or one_span(s, causal, window, bd, rope_dim):
        return False
    return super_block(s, window, bd)[0][0] > 1


# what a masked score is set to. Finite, so that a chunk of the blocked
# forward in which a row sees no key at all (its window starts in the
# next one) leaves max - max = 0, not inf - inf: what that row gathers
# there is wiped by exp(_MASKED - m) = 0 once it meets a visible key,
# which it always does (its own).
_MASKED = -1e30


def visible(qq, kk, window: int, block_diffusion=None):
    """THE visibility rule, for the four kernels, the einsum core and
    whoever counts pairs. Under ``causal``: query ``qq`` sees key ``kk``
    iff kk <= qq and, with a ``window``, qq - kk < window (the key at
    distance exactly ``window`` is masked). ``window`` 0: no window.

    ``block_diffusion`` (L, B): the sequence is 2L positions, a noised
    copy of a sample at 0..L-1 and the clean copy at L..2L-1, in blocks
    of B. With half(i) = i // L and blk(i) = (i mod L) // B,
        noised query, noised key:  blk(k) == blk(q)
        noised query, clean key:   blk(k) <  blk(q)
        clean query,  clean key:   blk(k) <= blk(q)
        clean query,  noised key:  never.
    Written as two compares of the pair: a query carries the last clean
    block it sees (``q_clean``) and the noised block it sees
    (``q_noised``, none for a clean query), a key its block on the side
    it is on and a value no query matches on the other; what is taken
    per query and per key alone stays as small as the caller's ``qq`` and
    ``kk`` are."""
    if block_diffusion is None:
        v = kk <= qq
        if window:
            v = v & (qq - kk < window)
        return v
    length, b = block_diffusion
    q_is_clean, k_is_clean = qq >= length, kk >= length
    qb = (qq - jnp.where(q_is_clean, length, 0)) // b
    kb = (kk - jnp.where(k_is_clean, length, 0)) // b
    q_clean = jnp.where(q_is_clean, qb, qb - 1)
    q_noised = jnp.where(q_is_clean, -1, qb)
    k_clean = jnp.where(k_is_clean, kb, 2 * length)
    k_noised = jnp.where(k_is_clean, -2, kb)
    return (k_clean <= q_clean) | (k_noised == q_noised)


def _tile_visible(q0, k0, shape, q_axis: int, window: int, block_diffusion):
    """``visible`` over a score tile of ``shape`` whose queries run along
    ``q_axis`` from ``q0`` and whose keys along the other axis from
    ``k0``. The positions are a column and a row, so that what
    ``visible`` takes per query and per key is not tile-sized work."""
    k_axis = 1 - q_axis
    q_shape = tuple(n if a == q_axis else 1 for a, n in enumerate(shape))
    k_shape = tuple(n if a == k_axis else 1 for a, n in enumerate(shape))
    qq = q0 + jax.lax.broadcasted_iota(jnp.int32, q_shape, q_axis)
    kk = k0 + jax.lax.broadcasted_iota(jnp.int32, k_shape, k_axis)
    return visible(qq, kk, window, block_diffusion)


def _mask(st, k0, q0, window: int, block_diffusion=None):
    """_MASKED where the query may not see the key, in a [k, q] tile
    whose first row is key ``k0`` and first column query ``q0``."""
    if block_diffusion is not None:
        return jnp.where(_tile_visible(q0, k0, st.shape, 1, window,
                                       block_diffusion), st, _MASKED)
    kk = k0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
    qq = q0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
    return jnp.where(visible(qq, kk, window), st, _MASKED)


def _pick(of_ints, of_traced, a, b):
    """``kv_blocks`` counts with Python ints, also while a step is being
    traced, where a ``jnp`` call on them would be staged into the trace."""
    return (of_ints if isinstance(a, int) and isinstance(b, int)
            else of_traced)(a, b)


def _either(cond, a, b):
    """``a`` where ``cond`` else ``b``, for a Python bool (``kv_blocks``
    counts with ints) or a traced one (a kernel's block start)."""
    return (a if cond else b) if isinstance(cond, bool) else jnp.where(
        cond, a, b)


def _k_chunks(q0, blk_q: int, blk_k: int, s: int, causal: bool,
              window: int):
    """[lo, hi): the K chunks of ``blk_k`` rows that hold a key some
    query of the block [q0, q0 + blk_q) sees. ``q0`` is the kernel's
    traced block start or, where ``kv_blocks`` counts, a Python int: the
    loop and the count are the same lines."""
    hi = (q0 + blk_q - 1) // blk_k + 1 if causal else s // blk_k
    lo = _pick(max, jnp.maximum, q0 - window + 1, 0) // blk_k if window else 0
    return lo, hi


def _q_chunks(k0, blk_k: int, blk_q: int, s: int, causal: bool,
              window: int):
    """[lo, hi): the Q chunks of ``blk_q`` rows that hold a query which
    sees some key of the block [k0, k0 + blk_k): the backward's loop."""
    lo = k0 // blk_q if causal else 0
    hi = s // blk_q
    if window:
        hi = _pick(min, jnp.minimum, (k0 + blk_k + window - 2) // blk_q + 1,
                   hi)
    return lo, hi


def _k_ranges(q0, blk_q: int, blk_k: int, s: int, causal: bool, window: int,
              block_diffusion=None):
    """The ranges [lo, hi) of K chunks the forward's loops run over for
    the query block at ``q0``: ``_k_chunks``' one under ``causal`` and a
    window; two under ``block_diffusion`` (first the noised keys of the
    block's own diagonal, empty for a clean block; then the clean keys
    from L on). Both block sizes then divide L (``_seq_block``,
    ``_q_block`` are taken of L), so no block lies across the halves."""
    if block_diffusion is None:
        return (_k_chunks(q0, blk_q, blk_k, s, causal, window),)
    length, b = block_diffusion
    noised = q0 < length
    # the block's first and last query as positions of their half
    first = q0 - _either(noised, 0, length)
    last = first + blk_q - 1
    lo1 = first // b * b // blk_k
    hi1 = _either(noised, ((last // b + 1) * b - 1) // blk_k + 1, lo1)
    # clean keys seen: the blocks before the last query's, and for a
    # clean query its own too
    clean = _either(noised, last // b * b, (last // b + 1) * b)
    lo2 = length // blk_k
    return (lo1, hi1), (lo2, lo2 + (clean + blk_k - 1) // blk_k)


def _q_ranges(k0, blk_k: int, blk_q: int, s: int, causal: bool, window: int,
              block_diffusion=None):
    """The ranges [lo, hi) of Q chunks the backward's loops run over for
    the key block at ``k0``, the transpose of ``_k_ranges``. Under
    ``block_diffusion`` a noised key block is seen by the noised queries
    of its own blocks alone; a clean one by the noised queries of the
    blocks after its first and by the clean queries from its first
    block on."""
    if block_diffusion is None:
        return (_q_chunks(k0, blk_k, blk_q, s, causal, window),)
    length, b = block_diffusion
    noised = k0 < length
    first = k0 - _either(noised, 0, length)
    last = first + blk_k - 1
    lo1 = _either(noised, first // b * b, (first // b + 1) * b) // blk_q
    hi1 = _either(noised, ((last // b + 1) * b - 1) // blk_q + 1,
                  length // blk_q)
    lo2 = (length + first // b * b) // blk_q
    return (lo1, hi1), (_either(noised, s // blk_q, lo2), s // blk_q)


def _cut(lo, hi, points, edge: bool):
    """The range [lo, hi) cut at ``points`` into consecutive sub-ranges
    (lo, hi, edge), edge and interior by turns from ``edge`` on. A point
    is held into what is left of the range, so a sub-range may be empty
    and the sub-ranges are the range, in its order, whatever the points."""
    out = []
    for p in points:
        p = _pick(min, jnp.minimum, _pick(max, jnp.maximum, p, lo), hi)
        out.append((lo, p, edge))
        lo, edge = p, not edge
    return (*out, (lo, hi, edge))


def _k_split(q0, blk_q: int, blk_k: int, s: int, causal: bool, window: int,
             block_diffusion=None):
    """``_k_ranges`` cut into sub-ranges (lo, hi, edge) in the order the
    forward's loop runs (PR 35): a tile is INTERIOR where every query of
    the block sees every key of the chunk, which ``visible`` says at the
    tile's extreme pairs, and EDGE where it holds a hidden pair; only an
    edge tile is masked. Under ``causal`` the chunks whose last key is
    the block's first query at most are interior, then the diagonal;
    under a window the chunks ahead of those whose first key the block's
    last query still sees are the far edge. Under ``block_diffusion`` the
    clean chunks that end among the keys the block's FIRST query sees are
    interior, then the last; a noised tile is interior only where one
    block of B holds all its queries and keys (never at B = 4: one edge
    range). Without a mask every tile is interior. Of a head's forward
    at the cells' shapes 96 of 320 tiles are edge (sdar), 64 of 544 (full
    causal, S 16384), 112 of 280 (window 4096): ``kv_blocks_masked``.

    Returns (sub-ranges, whether the LAST sub-range is exactly one chunk
    for every block): a Q block that lies inside one K chunk has one
    diagonal chunk under ``causal``, and under ``block_diffusion`` with
    B | BLK_Q one last clean chunk. The forward runs that chunk as
    straight-line code after its loops."""
    ranges = _k_ranges(q0, blk_q, blk_k, s, causal, window, block_diffusion)
    whole = blk_k % blk_q == 0    # a Q block lies inside one K chunk
    if block_diffusion is None:
        (lo, hi), = ranges
        if not causal:
            return ((lo, hi, False),), False
        diag = (q0 + 1) // blk_k
        if not window:
            return _cut(lo, hi, (diag,), False), whole
        near = (_pick(max, jnp.maximum, q0 + blk_q - window, 0)
                + blk_k - 1) // blk_k
        # a narrow window's far edge runs into the diagonal, which stays
        # the last sub-range
        near = _pick(min, jnp.minimum, near, diag)
        return _cut(lo, hi, (near, diag), True), whole
    length, b = block_diffusion
    (lo1, hi1), (lo2, hi2) = ranges
    noised = q0 < length
    first = q0 - _either(noised, 0, length)
    own = ()
    if b >= max(blk_q, blk_k):
        # the chunks inside the block of B that holds the whole Q block
        at = first // b * b
        inside = (at + blk_k - 1) // blk_k
        own = (inside, _either((first + blk_q - 1) // b * b == at,
                               (at + b) // blk_k, inside))
    clean = _either(noised, first // b * b, (first // b + 1) * b)
    return (_cut(lo1, hi1, own, True)
            + _cut(lo2, hi2, (lo2 + clean // blk_k,), False),
            whole and b < blk_q and blk_q % b == 0)


def _q_split(k0, blk_k: int, blk_q: int, s: int, causal: bool, window: int,
             block_diffusion=None):
    """``_q_ranges`` cut into sub-ranges (lo, hi, edge) in the order the
    backward's loop runs, the transpose of ``_k_split``: under ``causal``
    the diagonal, then the chunks whose first query is the block's last
    key at least, and under a window the far edge after the chunks whose
    last query still sees the block's first key. Under
    ``block_diffusion`` a noised key block's own queries are edge (but
    where one block of B holds the tile); a clean one's are interior
    from the chunk on whose first query sees its LAST key, among the
    noised queries and among the clean ones."""
    ranges = _q_ranges(k0, blk_k, blk_q, s, causal, window, block_diffusion)
    if block_diffusion is None:
        (lo, hi), = ranges
        if not causal:
            return ((lo, hi, False),)
        diag = (k0 + blk_k + blk_q - 2) // blk_q
        far = ((k0 + window) // blk_q,) if window else ()
        return _cut(lo, hi, (diag, *far), True)
    length, b = block_diffusion
    (lo1, hi1), (lo2, hi2) = ranges
    noised = k0 < length
    first = k0 - _either(noised, 0, length)
    after = (first + blk_k - 1) // b * b   # the last key's block starts here
    own = (_either(noised, hi1, (after + b + blk_q - 1) // blk_q),)
    if b >= max(blk_q, blk_k):
        at = first // b * b
        inside = (at + blk_q - 1) // blk_q
        own = (_either(noised, inside, own[0]),
               _either(noised, _either(after == at, (at + b) // blk_q,
                                       inside), hi1))
    return (_cut(lo1, hi1, own, True)
            + _cut(lo2, hi2, ((length + after + blk_q - 1) // blk_q,), True))


def normalized_window(s: int, causal: bool, window: int) -> int:
    """0 where the window hides nothing (none given, or >= S): those run
    the very code ``causal`` alone runs."""
    if window and not causal:
        raise ValueError("a sliding window needs causal attention")
    return window if 0 < window < s else 0


def checked_block_diffusion(s: int, causal: bool, window: int,
                            block_diffusion):
    """``block_diffusion`` as the (L, B) tuple the kernels take, or None;
    refuses one that does not describe the sequence."""
    if not block_diffusion:
        return None
    length, b = (int(n) for n in block_diffusion)
    if causal or window:
        raise ValueError("the block-diffusion mask is a visibility rule "
                         "of its own: not with causal or a window")
    if s != 2 * length or b <= 0 or length % b:
        raise ValueError(f"block-diffusion mask (L={length}, B={b}) over "
                         f"{s} positions: needs 2L positions and B | L")
    return length, b


def _forward_tiles(s: int, causal: bool, window: int, block_diffusion,
                   rope_dim: int = 0):
    """(every sub-range (lo, hi, edge) of K chunks that the blocked
    forward of one head loops over at sequence ``s``, Q block after Q
    block; the tiles of the whole square): what ``kv_blocks`` and
    ``kv_blocks_masked`` count, by the kernel's own lines. Under one
    span (``one_span``) a Q block's tile is its one masked span, and
    the square is cut into tiles of that size, the last of a row
    rounded up."""
    window = normalized_window(s, causal, window)
    bd = checked_block_diffusion(s, causal, window, block_diffusion)
    one = one_span(s, causal, window, bd, rope_dim)
    if one is not None:
        blk, span = one[0]
        return [(0, 1, True)] * (s // blk), (s // blk) * -(-s // span)
    blk_q, blk_k = _q_block(s, bd), _seq_block(s, bd, window)
    cut = [sub for q0 in range(0, s, blk_q)
           for sub in _k_split(q0, blk_q, blk_k, s, causal, window, bd)[0]]
    return cut, (s // blk_q) * (s // blk_k)


def visited_pairs(s: int, causal: bool, window: int = 0,
                  block_diffusion=None, rope_dim: int = 0):
    """(query, key) pairs of one head and sequence that lie in the tiles
    the flash kernels work through, forward and backward added: the
    forward's [Q block, K chunk] tiles (``_k_split``) and the backward's
    [K block, Q chunk] tiles (``_q_split``), by the kernels' own ranges;
    under one span (``one_span``) a [rows, span] tile a Q block and
    one a K block; in the chunk loop less what ``super_block``'s
    sub-tiles leave out of a tile (``_kept``: the kernels slice by it,
    this counts by it). Against twice the visible pairs it says how much of
    the kernels' work the mask then throws away. The whole-tile kernels
    (S <= MAX_BWD_SEQ) hold the square, once each."""
    if s <= MAX_BWD_SEQ:
        return 2 * s * s
    window = normalized_window(s, causal, window)
    bd = checked_block_diffusion(s, causal, window, block_diffusion)
    one = one_span(s, causal, window, bd, rope_dim)
    if one is not None:
        return s * (one[0][1] + one[1][1])
    blk_q, blk = _q_block(s, bd), _seq_block(s, bd, window)
    (_, chains, trim), subs = super_block(s, window, bd)
    rows, sub = chains * blk_q, blk // subs

    def squares(trim, parts):   # of a [block, chunk] tile, in parts^2
        return sum(hi - lo for lo, hi in (
            _kept(trim, n, parts) for n in range(parts)))

    forward = backward = 0
    for first in range(0, s, rows):     # a forward grid step's loops
        split, peeled = _k_split(first, rows, blk, s, causal, window, bd)
        for n, (lo, hi, _) in enumerate(split):
            kept = _key_trim(n, len(split), peeled, window, blk,
                             bd) if trim else None
            forward += (hi - lo) * squares(kept, chains) * blk_q * (
                blk // chains)
    for k0 in range(0, s, blk):         # a backward grid step's loops
        for n, (lo, hi, _) in enumerate(
                _q_split(k0, blk, blk, s, causal, window, bd)):
            kept = _query_trim(n, causal, window, blk, sub,
                               bd) if subs > 1 else None
            backward += (hi - lo) * squares(kept, subs) * sub ** 2
    return forward + backward


def visible_pairs(s: int, causal: bool, window: int = 0) -> int:
    """(query, key) pairs of one head and sequence that ``visible`` admits
    under ``causal`` and a window, counted exactly: a query sees itself
    and the keys before it, the last ``window`` of them at most."""
    if not causal:
        return s * s
    w = normalized_window(s, causal, window) or s
    return w * (w + 1) // 2 + (s - w) * w


def kv_blocks(s: int, causal: bool, window: int = 0, block_diffusion=None,
              rope_dim: int = 0):
    """(visited, total) tiles of [a Q block, a K chunk] that the
    forward of one head works through at sequence ``s``: what the
    gauges ``attention/kv_blocks_visited`` / ``_total`` add up. The
    whole-tile kernels (S <= MAX_BWD_SEQ) hold one tile and mask."""
    if s <= MAX_BWD_SEQ:
        return 1, 1
    cut, total = _forward_tiles(s, causal, window, block_diffusion, rope_dim)
    return sum(hi - lo for lo, hi, _ in cut), total


def kv_blocks_masked(s: int, causal: bool, window: int = 0,
                     block_diffusion=None, rope_dim: int = 0) -> int:
    """Of ``kv_blocks``' visited tiles, those that hold a hidden pair and
    run the masked body (``_k_split``'s edge tiles): what the gauge
    ``attention/kv_blocks_masked`` adds up. The whole-tile kernels mask
    their one tile under any mask."""
    if s <= MAX_BWD_SEQ:
        return int(causal or bool(block_diffusion))
    cut, _ = _forward_tiles(s, causal, window, block_diffusion, rope_dim)
    return sum(hi - lo for lo, hi, edge in cut if edge)


def _only_head(x, h: int, head_dim: int):
    """``x`` [rows, W] with every lane but head ``h``'s zeroed (itself
    where the block is one head). A product that contracts over all W
    lanes of a column block, or writes all of them, is then head ``h``'s
    alone; the MXU contracts 128 deep and writes 128 wide whether 64 of
    them are zero or not, and no operand is sliced at half a vreg."""
    if x.shape[-1] == head_dim:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    mine = (lane >= h * head_dim) & (lane < (h + 1) * head_dim)
    return jnp.where(mine, x, jnp.zeros_like(x))


def _dot_head(a, b, h: int, head_dim: int):
    """a[m, W] . b[n, W]^T over head ``h``'s lanes of a column block."""
    return _dot(_only_head(a, h, head_dim), b, _NT)


def _rope_lanes(x, head, rope_dim: int):
    """``x`` [rows, 128], a column block of the rotated query parts
    (128 // rope_dim heads side by side), with every lane but those of
    head ``head`` of the operand zeroed: ``head`` is the grid's (traced)
    column block of the 128-wide parts, one head each. Against the
    shared rotated key laid side by side as often ([k_r ; k_r]) the MXU
    then contracts head ``head``'s rope_dim lanes alone."""
    return jnp.where(_rope_lanes_of(x.shape, head, rope_dim), x,
                     jnp.zeros_like(x))


def _rope_lanes_of(shape, head, rope_dim: int):
    """Where, in a [rows, 128] block of rotated parts, head ``head``'s
    lanes are."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return lane // rope_dim == head % (LANES // rope_dim)


def _stack_heads(parts):
    """Per-head [D, S] results as the [W, S] tile of their column block:
    heads are sublane ranges there, so this moves nothing across lanes."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _kv_part(axis: int, group):
    """Which of the KV heads that lie side by side in a K / V lane block
    this grid step's query column block reads (PR 47). ``group`` = (rep,
    heads a lane block): the lane block serves ``rep`` consecutive query
    column blocks, the first rep / heads of them its first KV head, and
    so on; grid axis ``axis`` runs over the column blocks (the forward)
    or over a lane block's ``rep`` (the grouped backward). None without
    a ``group`` (one head a lane block, or every head its own keys):
    ``_own_kv_head`` and ``_group_halves`` then hand back what they are
    given, and nothing is traced."""
    if group is None:
        return None
    rep, hpb = group
    return pl.program_id(axis) % rep * hpb // rep


def _lane_roll(x, shift: int):
    """x [rows, 128] rolled ``shift`` lanes. The chip's compiler rotates
    32-bit words only, so a bfloat16 tile goes as the words that hold two
    rows' values a lane: a lane roll does not look at a row."""
    if x.dtype.itemsize == 4:
        return pltpu.roll(x, shift, 1)
    words = pltpu.roll(pltpu.bitcast(x, jnp.uint32), shift, 1)
    return pltpu.bitcast(words, x.dtype)


def _own_kv_head(x, part, head_dim: int):
    """x [rows, 128], a K or V lane block of two KV heads of 64, with
    head ``part`` (traced) laid in both halves, [k_g ; k_g]: the column
    block the repeated form would have fetched, so every product after
    it sees the values it saw there. One lane roll by half a block (its
    own inverse) and a select. Where K and V are what a grid step holds
    (the backward's K block, the whole-tile kernels' rows); the blocked
    forwards, which read K and V a chunk at a time, move the query
    block instead (``_half_moved``)."""
    if part is None:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane // head_dim == part, x,
                     _lane_roll(x, head_dim))


def _half_moved(x, there, head_dim: int):
    """x [rows, 128] as it is where ``there`` (a traced scalar) holds,
    else with its two 64-lane halves exchanged: a query head moved to
    the half of the lane block its KV head lies in, or a head's output
    moved back from it."""
    return jnp.where(there, x, _lane_roll(x, head_dim))


def _group_halves(xt, part, head_dim: int):
    """xt [128, n], the dK^T (dV^T) of a column block's two query heads
    as sublane ranges, both of them gradients of KV head ``part``
    (traced) of the K / V lane block: their sum in that head's sublanes
    and zeros in the other's, which these query heads do not read.
    Nothing moves across lanes."""
    if part is None:
        return xt
    both = xt[:head_dim] + xt[head_dim:]
    zero = jnp.zeros_like(both)
    return jnp.concatenate([jnp.where(part == 0, both, zero),
                            jnp.where(part == 0, zero, both)], axis=0)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, causal: bool,
                      window: int, scale: float, blk_q: int, blk_k: int,
                      head_dim: int, block_diffusion=None,
                      rope_dim: int = 0, group=None, tiles: int = 1,
                      chains: int = 1, trim: bool = False,
                      masked: bool = False):
    """One (batch row, column block, ``tiles`` Q blocks) grid cell: q
    [1, tiles * BLK_Q, W] against the K/V panels [1,S,W] resident in
    VMEM, in chunks of ``blk_k`` keys with a running max and sum (the
    online softmax), and ONLY the chunks that hold a key some query
    sees (``_k_chunks``): under ``causal`` those up to the diagonal,
    under a window the few behind it, under ``block_diffusion`` the
    block's own noised tile and then the clean chunks it sees. Each
    range runs as ``_k_split``'s sub-ranges, one loop each: the body
    masks an edge tile and builds no mask for an interior one. Scores
    never touch HBM. Also emits the per-row logsumexp so the fused
    backward can recompute P exactly. The forward of sequences past
    MAX_BWD_SEQ.

    A SUPER-BLOCK (PR 51; ``super_block`` is the rule and has the
    lab's table): ``chains`` consecutive Q blocks share their chunk
    loops, which run over the sub-ranges of their union (the rule takes
    a super-block only where those are each Q block's own). An
    iteration loads the K / V chunk (and the rotated key's) ONCE and
    runs every Q block's [BLK_Q, blk_k] tile against it, each with its
    own (max, sum, accumulator) carry and its own mask: chains x heads
    a lane block independent chains in one loop body. With ``trim``,
    where a super-block is one K chunk, a sub-tile takes of the chunks
    ``_key_trim`` names the static slice of keys it sees any of. The
    tile, the products, their operand dtypes, the float32 statistics
    and the order of a row's chunks are one Q block's: without ``trim``
    o and lse are that kernel's bit for bit, with it a row's sums run
    over fewer keys whose terms were exact zeros. ``tiles`` > ``chains``:
    several super-blocks a grid step in a loop that carries nothing
    (the lab's 8 x 4, 16 x 4; one ships).

    The last sub-range, where the split says it is one chunk for every
    block (the diagonal; the last clean chunk), runs as straight-line
    code after the loops and not as a loop of one step: the scheduler
    then overlaps it with the division by the sums and the logsumexp.

    v5e, kernels alone, forward ms, every tile masked in one loop a
    range -> the last chunk out of the loop -> the split (PR 35): 8
    heads of 128 at 2L = 16384 under block diffusion 3.09 -> 2.92 ->
    2.83 (K chunks of 512: 3.36 -> 3.24 -> 2.87; the mask there is
    integer divisions on a [BLK_Q, 1] column a chunk); 7 heads at
    S = 16384 full causal 4.08 -> 3.91 -> 3.84, window 4096 2.34 ->
    2.19 -> 2.20 (three short loops a Q block); 4 heads at S = 8192
    causal 0.66 -> 0.62 -> 0.61. At chunks of 1024 most of the mask's
    passes hide under the products.

    With ``rope_dim`` (PR 39, the two-part score of latent attention)
    two more operands stand before the outputs: the heads' rotated query
    parts (``_rope_lanes`` picks this head's) and the ONE rotated key a
    position, whose product is added to the score ahead of the scale.

    ``group`` (PR 47; ``_kv_part``): the K / V panels hold two KV heads
    of 64, of which this column block's two query heads share one, in
    half ``part`` of the lanes. The chunks are read as they lie: each
    query head is moved to that half ONCE a grid step (``_half_moved``;
    the other half zeros), so that q k^T contracts it against its KV
    head alone, and P V's half ``part`` is moved back to the head's own
    lanes at the end. Laying the KV head twice a fetched chunk
    (``_own_kv_head``) costs a roll and a select of [1024, 128] K and V
    8,704 times an op at S = 16384 and was 1.25 ms slower in the lab
    (``grouped_kv_shape_legal``'s table).

    ``masked`` (PR 54, learned sparse attention): two more operands
    stand before the outputs, a MASK that is data, one byte a (query,
    key) pair, this step's rows of it [1, rows, S], and in SMEM its
    per-tile summary [B, S / rows, S / blk_k] (pairs selected in the
    tile of a super-block's rows and a K chunk). A tile's score is
    masked where the byte is 0, in every chunk (the mask holds no pair
    the causal rule hides, so the static rule's own mask is left out),
    and a chunk whose summary is 0 is SKIPPED: the carry passes through
    a `lax.cond`. Without ``masked`` nothing here is traced and the
    kernel is what it was."""
    if masked:
        assert not rope_dim and not block_diffusion, "mask operand: plain"
        mask_ref, any_ref, o_ref, lse_ref = rest
    elif rope_dim:
        qr_ref, kr_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    part = _kv_part(1, group)
    # the grid's place, taken outside the loop over the super-blocks
    head, step = pl.program_id(1), pl.program_id(2)
    row = pl.program_id(0) if masked else None
    heads = q_ref.shape[-1] // head_dim
    rows = chains * blk_q             # a super-block's
    supers = tiles // chains          # super-blocks a grid step
    # trimmed, a super-block IS a K chunk: where that chunk holds the
    # super-block's own positions, or lies a whole window behind them, a
    # sub-tile takes the static slice of keys it sees any of (`_key_trim`)
    assert not trim or rows == blk_k, (rows, blk_k)

    def super_block(i):
        """The ``chains`` Q blocks from row ``i * rows`` of the step's
        block on (``i`` a Python 0 where the step holds one), against the
        chunks their union sees."""
        first = (step * supers + i) * rows
        split, last_is_one = _k_split(first, rows, blk_k, k_ref.shape[1],
                                      causal, window, block_diffusion)
        at = [pl.ds(i * rows + t * blk_q, blk_q) if isinstance(i, int)
              else pl.ds(pl.multiple_of(i * rows + t * blk_q, blk_q), blk_q)
              for t in range(chains)]
        q0s = [first + t * blk_q for t in range(chains)]
        qs = []
        for t in range(chains):
            q = q_ref[0, at[t], :]  # [BLK_Q, W]
            if group:   # head h's lanes in its KV head's half, zeros beside
                qs.append([_only_head(_half_moved(q, part == h, head_dim),
                                      part, head_dim) for h in range(heads)])
            else:
                qs.append([_only_head(q, h, head_dim) for h in range(heads)])
        if rope_dim:
            qrs = [_rope_lanes(qr_ref[0, at[t], :], head, rope_dim)
                   for t in range(chains)]

        def seen(q0, k0, tile):
            if block_diffusion is not None:
                return _tile_visible(q0, k0, tile, 0, window,
                                     block_diffusion)
            return visible(
                q0 + jax.lax.broadcasted_iota(jnp.int32, tile, 0),
                k0 + jax.lax.broadcasted_iota(jnp.int32, tile, 1), window)

        def chunk(c, carry, edge, kept=None):
            if masked:      # a tile no query of which selected a key
                return jax.lax.cond(
                    any_ref[row, step * supers + i, c] > 0,
                    lambda carry: work(c, carry, True, kept),
                    lambda carry: carry, carry)
            return work(c, carry, edge, kept)

        def work(c, carry, edge, kept=None):
            k0 = pl.multiple_of(c * blk_k, blk_k)
            k = k_ref[0, pl.ds(k0, blk_k), :]  # [BLK_K, W], once a chunk
            v = v_ref[0, pl.ds(k0, blk_k), :]
            kr = kr_ref[0, pl.ds(k0, blk_k), :] if rope_dim else None
            out = []
            for t in range(chains):
                # the keys sub-tile t can see any of: a static slice
                lo, hi = (blk_q * n for n in _kept(kept, t, chains)
                          ) if kept else (0, blk_k)
                whole = (lo, hi) == (0, blk_k)
                kt, vt, krt = (k, v, kr) if whole else (
                    k[lo:hi], v[lo:hi], kr[lo:hi] if rope_dim else None)
                if masked:
                    mask = mask_ref[0, at[t], pl.ds(k0 + lo, hi - lo)
                                    ].astype(jnp.int32) != 0
                else:
                    mask = seen(q0s[t], k0 + lo,
                                (blk_q, hi - lo)) if edge else None
                for h in range(heads):
                    m, l, acc = carry[t * heads + h]
                    s = _dot(qs[t][h], kt, _NT)
                    if rope_dim:
                        s = s + _dot(qrs[t], krt, _NT)
                    s = s * scale
                    if edge:
                        s = jnp.where(mask, s, _MASKED)
                    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                    alpha = jnp.exp(m - m_new)
                    p = jnp.exp(s - m_new)
                    l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                    # P against every head of the block; the other heads'
                    # lanes are dropped from the [BLK_Q, W] result at the end
                    acc = alpha * acc + _dot(p.astype(vt.dtype), vt, _NN)
                    out.append((m_new, l, acc))
            return tuple(out)

        carry = tuple(
            (jnp.full((blk_q, 1), _MASKED, jnp.float32),
             jnp.zeros((blk_q, 1), jnp.float32),
             jnp.zeros((blk_q, q_ref.shape[-1]), jnp.float32))
            for _ in range(chains * heads))
        for n, (lo, hi, edge) in enumerate(split):
            kept = _key_trim(n, len(split), last_is_one, window, blk_k,
                             block_diffusion) if trim else None
            if last_is_one and n == len(split) - 1:
                carry = chunk(lo, carry, edge, kept)
            else:
                carry = jax.lax.fori_loop(lo, hi, functools.partial(
                    chunk, edge=edge, kept=kept), carry)
        for t in range(chains):
            o = None
            for h in range(heads):
                m, l, acc = carry[t * heads + h]
                oh = acc / l
                if group:   # P against the lane block's two KV heads: this one's
                    oh = _half_moved(oh, part == h, head_dim)
                oh = _only_head(oh, h, head_dim)
                o = oh if o is None else o + oh
                lse_ref[0, h, 0, at[t]] = (m + jnp.log(l))[:, 0]
            o_ref[0, at[t], :] = o.astype(o_ref.dtype)

    if supers == 1:
        super_block(0)
    else:
        _for_rows(supers, 1, super_block)


def _flash_fwd_span_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                           window: int, scale: float, blk_q: int, span: int,
                           tiles: int, unroll: int, head_dim: int,
                           group=None):
    """The blocked forward under a window so narrow that the keys a Q
    block reaches are ONE score tile (``one_span``, PR 46): each of the
    grid step's ``tiles`` Q blocks [BLK_Q, W] against the ``span`` keys
    of the resident K/V panels that end with the block's last query
    (``_k_span``: a ``pl.ds`` slice at a multiple of the block, not of
    the span), as straight-line code: one product, one mask, ONE max,
    one ``exp``, one sum, P V, the division and the logsumexp: the plain
    softmax. No loop over chunks, no running (max, sum, accumulator), no
    rescaling; the loop over the step's Q blocks carries nothing. Every
    row sees its own key, so no row's sum is empty. The same products at
    the same precision as ``_flash_fwd_kernel``: operands as stored,
    float32 scores, statistics and lse; ``group`` as there: the query
    heads move to their KV head's half, the keys are read as they lie."""
    first = pl.program_id(2) * tiles
    part = _kv_part(1, group)

    def block(t):
        at = pl.multiple_of(t * blk_q, blk_q)
        rows = pl.ds(at, blk_q)
        q = q_ref[0, rows, :]  # [BLK_Q, W]
        q0 = (first + t) * blk_q
        k0 = pl.multiple_of(_k_span(q0, blk_q, span), BLK_Q)
        k = k_ref[0, pl.ds(k0, span), :]  # [SPAN, W]
        v = v_ref[0, pl.ds(k0, span), :]
        tile = (blk_q, span)
        mask = visible(
            q0 + jax.lax.broadcasted_iota(jnp.int32, tile, 0),
            k0 + jax.lax.broadcasted_iota(jnp.int32, tile, 1), window)
        o = None
        for h in range(q.shape[-1] // head_dim):
            if group:   # the head in its KV head's half (`_flash_fwd_kernel`)
                s = _dot_head(_half_moved(q, part == h, head_dim), k, part,
                              head_dim)
            else:
                s = _dot_head(q, k, h, head_dim)
            s = jnp.where(mask, s * scale, _MASKED)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            # P against every head of the block, this head's lanes kept
            oh = _dot(p.astype(v.dtype), v, _NN) / l
            if group:
                oh = _half_moved(oh, part == h, head_dim)
            oh = _only_head(oh, h, head_dim)
            o = oh if o is None else o + oh
            lse_ref[0, h, 0, rows] = (m + jnp.log(l))[:, 0]
        o_ref[0, rows, :] = o.astype(o_ref.dtype)

    _for_rows(tiles, unroll, block)


def _flash_fwd_whole_kernel(q_ref, k_ref, v_ref, *rest,
                            causal: bool, window: int, scale: float,
                            rows: int, head_dim: int, block_diffusion=None,
                            rope_dim: int = 0, group=None):
    """``rows`` batch rows a grid cell, each with the heads of one column
    block and their whole sequence: the forward up to MAX_BWD_SEQ. The
    score tile is held as [k, q]: the softmax's max and sum then run down
    the sublanes (vreg against vreg) and come out as the [1, S] rows the
    logsumexp is stored as, where the [q, k] tile needs two cross-lane
    reductions a row and a relayout; and O^T = V^T P^T streams D rows
    through the MXU against the tile, which a head_dim of 64 fills where
    P V fills half its width. V^T and O^T are [W, S] tiles of the whole
    block: one transpose each serves every head of it. ``rope_dim``:
    the two-part score, ``group``: the KV head two query heads of 64
    share, both as in ``_flash_fwd_kernel``."""
    heads = q_ref.shape[-1] // head_dim
    if rope_dim:
        qr_ref, kr_ref, o_ref, lse_ref = rest
        head = pl.program_id(1)   # taken outside the rows' loop
    else:
        o_ref, lse_ref = rest
    part = _kv_part(1, group)

    def row(b):
        q = q_ref[b]                                 # [S, W]
        k, v = (_own_kv_head(t[b], part, head_dim) for t in (k_ref, v_ref))
        vt = v.T                                     # [W, S]
        ots = []
        for h in range(heads):
            st = _dot_head(k, q, h, head_dim)           # [k, q]
            if rope_dim:
                st = st + _dot(kr_ref[b], _rope_lanes(
                    qr_ref[b], head, rope_dim), _NT)
            st = st * scale
            if causal or block_diffusion is not None:
                st = _mask(st, 0, 0, window, block_diffusion)
            m = jnp.max(st, axis=0, keepdims=True)   # [1, S]
            pt = jnp.exp(st - m)
            l = jnp.sum(pt, axis=0, keepdims=True)
            ot = _dot(vt[h * head_dim:(h + 1) * head_dim],
                      pt.astype(v.dtype), _NN)       # [D, q]
            ots.append(ot / l)
            lse_ref[b, h] = m + jnp.log(l)
        o_ref[b] = _stack_heads(ots).T.astype(o_ref.dtype)

    _for_rows(rows, max(1, 4 // heads), row)


def _rope_operands(rope, num_heads: int, head_dim: int):
    """The two-part score's further operands as the kernels take them:
    (rope_dim, heads a 128-lane block of the rotated query parts,
    q_rope [B, S, H*rope_dim], the one rotated key [B, S, rope_dim] laid
    side by side to fill 128 lanes), or (0, 1) and nothing."""
    if rope is None:
        return 0, 1, ()
    q_rope, k_rope = rope
    rope_dim = k_rope.shape[-1]
    per = LANES // rope_dim
    assert head_dim == LANES and q_rope.shape[-1] == num_heads * rope_dim \
        and num_heads % per == 0, (head_dim, q_rope.shape, k_rope.shape)
    return rope_dim, per, (q_rope, jnp.concatenate([k_rope] * per, axis=-1))


def _group_size(num_heads: int, num_kv_heads, head_dim: int, rope=None):
    """(rep, group): the query heads that share a K/V head in the
    operands the kernels are handed, 1 where k and v hold every head
    (``num_kv_heads`` None or the heads), else the group's size, which
    ``grouped_kv_shape_legal`` has admitted; and what the kernels are
    told where a K / V lane block holds two KV heads of 64 (PR 47),
    (rep, heads a lane block) for ``_kv_part``, else None: at one head a
    lane block the BlockSpec's ``j // rep`` has picked the head."""
    rep = num_heads // (num_kv_heads or num_heads)
    assert rep == 1 or (rope is None and grouped_kv_shape_legal(
        num_heads, num_kv_heads, head_dim)), (num_heads, num_kv_heads)
    hpb = _heads_per_block(num_heads, head_dim)
    return rep, (rep, hpb) if rep > 1 and hpb > 1 else None


def _flash_fwd(q, k, v, num_heads: int, causal: bool, interpret: bool,
               out_dtype=None, window: int = 0, block_diffusion=None,
               rope=None, num_kv_heads=None, mask=None):
    """q, k, v: [B, S, H*D] with S % BLK_Q == 0 -> (o [B, S, H*D],
    lse [B, H, 1, S]). A block is S (or BLK_Q) rows by one column block
    of the operand, picked by the BlockSpec's last index: in HBM's
    (8, 128) tiles that is a run of whole tiles, no lane of it padding.

    With ``num_kv_heads`` < H (PR 43) k and v are [B, S, Hk*D], as their
    projections leave them, and the K / V BlockSpecs' last index is the
    query column block's group, ``j // rep``: consecutive heads of a
    group name the same block, which Pallas does not fetch again. The
    kernels' bodies are the same: the same K and V values meet the same
    Q block, so o and lse are those of the repeated keys bit for bit.
    At heads of 64 (PR 47) the column block ``j`` holds two query heads
    of ONE KV head ``2 j // rep``, which is a half of lane block
    ``j // rep`` of k and v: the same index map, and the kernels lay
    that half twice side by side (``_own_kv_head``), which is the block
    the repeated form fetched, or, where K and V are read a chunk at a
    time, move the query heads to that half (``_half_moved``): the same
    products of the same values either way.

    ``rope`` = (q_rope [B, S, H*R], k_rope [B, S, R]): the score of a
    head is q k^T + q_rope k_rope^T over sqrt(D + R), the rotated key
    ONE vector a position for all heads (latent attention; D = 128). The
    kernel reads that key as it is, laid twice side by side so that a
    128-lane block of q_rope (two heads' parts, the other's zeroed)
    contracts against it; nothing is assembled per head in HBM.

    ``mask`` (PR 54) = (mask [B, S, S] int8, its summary over the
    forward's tiles, ``mask_tiles_any(mask, *masked_tiles(S)[0])``): the
    chunk-loop kernel alone takes it (``masked_flash_legal``)."""
    b, s, hd = q.shape
    d = hd // num_heads
    if d > LANES:   # a head of two lane blocks: a tile a grid step
        assert block_diffusion is None and rope is None and mask is None
        return _wide_flash_fwd(q, k, v, num_heads, causal, interpret,
                               out_dtype, window, num_kv_heads)
    hpb = _heads_per_block(num_heads, d)
    w = hpb * d
    rope_dim, per, rope_ops = _rope_operands(rope, num_heads, d)
    rep, group = _group_size(num_heads, num_kv_heads, d, rope)
    scale = 1.0 / float(d + rope_dim) ** 0.5
    window = normalized_window(s, causal, window)
    bd = checked_block_diffusion(s, causal, window, block_diffusion)
    # lse is (b, h, 1, s): TPU requires the last two block dims be
    # (8,128)-aligned or span the array — a singleton before the
    # sequence satisfies that while keeping one row per head
    out_shape = (jax.ShapeDtypeStruct((b, s, hd), out_dtype or q.dtype),
                 jax.ShapeDtypeStruct((b, num_heads, 1, s), jnp.float32))
    if s <= MAX_BWD_SEQ:
        rows = _rows_per_step(b, hpb, s)
        seq_spec = pl.BlockSpec((rows, s, w), lambda i, j: (i, 0, j))
        kv_spec = seq_spec if rep == 1 else pl.BlockSpec(
            (rows, s, w), lambda i, j: (i, 0, j // rep))
        rope_specs = [
            pl.BlockSpec((rows, s, LANES), lambda i, j: (i, 0, j // per)),
            pl.BlockSpec((rows, s, LANES), lambda i, j: (i, 0, 0)),
        ] if rope_dim else []
        return pl.pallas_call(
            functools.partial(_flash_fwd_whole_kernel, causal=causal,
                              window=window, scale=scale, rows=rows,
                              head_dim=d, block_diffusion=bd,
                              rope_dim=rope_dim, group=group),
            name=KERNEL_NAME_PREFIX + "flash_fwd_whole",
            out_shape=out_shape,
            grid=(b // rows, num_heads // hpb),
            in_specs=[seq_spec, kv_spec, kv_spec] + rope_specs,
            out_specs=(seq_spec,
                       pl.BlockSpec((rows, hpb, 1, s),
                                    lambda i, j: (i, j, 0, 0))),
            interpret=interpret,
            compiler_params=_FLASH_COMPILER_PARAMS,
        )(q, k, v, *rope_ops)
    one = one_span(s, causal, window, bd, rope_dim)
    if one is not None:     # the reachable keys of a Q block as one tile
        rows, span = one[0]
        tiles, unroll = _span_tiles(s, rows)
        kernel = functools.partial(_flash_fwd_span_kernel, window=window,
                                   scale=scale, blk_q=rows, span=span,
                                   tiles=tiles, unroll=unroll, head_dim=d,
                                   group=group)
        blk = tiles * rows
    else:
        rows = _q_block(s, bd)
        tiles, chains, trim = super_block(s, window, bd)[0]
        kernel = functools.partial(
            _flash_fwd_kernel, causal=causal, window=window, scale=scale,
            blk_q=rows, blk_k=_seq_block(s, bd, window), head_dim=d,
            block_diffusion=bd, rope_dim=rope_dim, group=group,
            tiles=tiles, chains=chains, trim=trim,
            **({"masked": True} if mask is not None else {}))
        blk = tiles * rows
    rope_specs = [
        pl.BlockSpec((1, blk, LANES), lambda b, j, i: (b, i, j // per)),
        pl.BlockSpec((1, s, LANES), lambda b, j, i: (b, 0, 0)),
    ] if rope_dim else []
    mask_specs = []
    if mask is not None:    # this step's rows of the mask; its summary
        assert one is None and not rope_dim and tiles == chains, (s, window)
        mask_specs = [pl.BlockSpec((1, blk, s), lambda b, j, i: (b, i, 0)),
                      pl.BlockSpec(memory_space=pltpu.SMEM)]
    return pl.pallas_call(
        kernel,
        name=KERNEL_NAME_PREFIX + "flash_fwd",
        out_shape=out_shape,
        grid=(b, num_heads // hpb, s // blk),
        in_specs=[
            pl.BlockSpec((1, blk, w), lambda b, j, i: (b, i, j)),
        ] + [pl.BlockSpec((1, s, w), (lambda b, j, i: (b, 0, j)) if rep == 1
                          else (lambda b, j, i: (b, 0, j // rep)))] * 2
        + rope_specs + mask_specs,
        out_specs=(pl.BlockSpec((1, blk, w), lambda b, j, i: (b, i, j)),
                   pl.BlockSpec((1, hpb, 1, blk),
                                lambda b, j, i: (b, j, 0, i))),
        interpret=interpret,
        compiler_params=(_FLASH_COMPILER_PARAMS if mask is None
                         else _MASKED_COMPILER_PARAMS),
    )(q, k, v, *rope_ops, *(mask or ()))


def _turned(q, o, do):
    """What ``_flash_bwd_tile`` forms of a Q chunk alone, whatever keys
    it meets: Q^T, dO^T [W, Bq] and the float32 dO^T * O^T whose sums
    down a head's sublanes are delta."""
    qt, dot = q.T, do.T
    return qt, dot, dot.astype(jnp.float32) * o.T.astype(jnp.float32)


def _flash_bwd_tile(q, k, kt, v, o, do, lse, glse, scale: float, mask,
                    head_dim: int, rope=None, turned=None):
    """FlashAttention-2 backward of the [k, q] tiles of one column
    block: k, v [Bk, W] (and ``kt`` = k^T, which the caller forms once)
    against q, O, dO [Bq, W] and, a head, the [1, Bq] rows lse and
    g_lse, the upstream gradient on the logsumexp output (zero when only
    o is consumed; nonzero under ring attention's streaming merge, whose
    weights are functions of each block's lse). ``mask``: None (every
    pair visible) or ``_mask``'s (first key, first query, window, block
    diffusion) of the tile. Recompute P
    from the saved lse, then dV = P^T dO, dS = P * (dO V^T - delta +
    g_lse) with delta = rowsum(dO * O), dQ = dS K, dK = dS^T Q; returns
    them TRANSPOSED and without the softmax scale, float32 dQ^T [W, Bq],
    dK^T, dV^T [W, Bk]: the blocked backward adds dK^T and dV^T up over
    its Q chunks and turns them once.

    delta is formed here, from the O^T and dO^T tiles, as sums down a
    head's sublanes: outside, XLA writes the [.., S]-minor rows by
    transposing the whole float32 product dO * O first (a 67 MB copy a
    layer at bert_ae's sizes, deviceless compile, PR 30).

    With the tile as [k, q] the three products that contract over the
    sequence stream the D rows of dO^T, Q^T, K^T against it and come out
    as [D, S]: none transposes the tile, a head_dim of 64 fills the MXU,
    and the row statistics broadcast down the sublanes as they are
    stored. Q^T, K^T, O^T, dO^T and the results are [W, S] tiles of the
    whole block, whose heads are sublane ranges: seven transposes a
    block whatever the heads in it. v5e, bf16, S = 512, head_dim 64, 512
    heads, one a step at the [B*H, S, 64] form (PR 28): 864 us this way;
    989 with the tile as [q, k] and dK, dV contracting over its rows;
    980 as [k, q] with [S, D] results, and 980 still with the
    element-wise work taken out of that one.

    ``rope`` = (qr [Bq, 128] with this head's lanes alone, kr [Bk, 128]
    the shared rotated key laid side by side): the two-part score; two
    more results then, dQr^T [128, Bq] = Kr^T dS (every copy of the key
    gives the same rows: the caller keeps this head's) and dKr^T
    [128, Bk] = Qr^T dS^T (this head's sublanes, zeros elsewhere)."""
    qt, dot, dot_ot = turned or _turned(q, o, do)    # [W, S]
    dqt, dkt, dvt = [], [], []
    for h in range(q.shape[-1] // head_dim):
        mine = slice(h * head_dim, (h + 1) * head_dim)
        st = _dot_head(k, q, h, head_dim)                # [k, q]
        if rope is not None:
            st = st + _dot(rope[1], rope[0], _NT)
        st = st * scale
        if isinstance(mask, tuple):
            st = _mask(st, *mask)
        elif mask is not None:   # a mask that is data, [k, q] (PR 54)
            st = jnp.where(mask, st, _MASKED)
        pt = jnp.exp(st - lse[h])                    # exact softmax probs
        dpt = _dot_head(v, do, h, head_dim)
        delta = jnp.sum(dot_ot[mine], axis=0, keepdims=True)   # [1, q]
        dst = (pt * (dpt - (delta - glse[h]))).astype(q.dtype)
        dvt.append(_dot(dot[mine], pt.astype(do.dtype), _NT))  # [D, k]
        dkt.append(_dot(qt[mine], dst, _NT))                   # [D, k]
        dqt.append(_dot(kt[mine], dst, _NN))                   # [D, q]
    if rope is not None:     # one head a block (head_dim 128): `dst` is its
        return (_stack_heads(dqt), _stack_heads(dkt), _stack_heads(dvt),
                _dot(rope[1].T, dst, _NN), _dot(rope[0].T, dst, _NT))
    return _stack_heads(dqt), _stack_heads(dkt), _stack_heads(dvt)


def _group_sum(ref, at, value, grouped: bool, member):
    """``ref[at] = value``, or under grouped keys the sum over a group:
    dK's and dV's blocks are the KV head's, float32, resident while the
    grid runs through the group's heads; the first (``member`` 0) writes
    and the others add. A select, not a branch: what the block holds
    before the first head wrote is never the result."""
    if not grouped:
        ref[at] = value.astype(ref.dtype)
    else:
        ref[at] = jnp.where(member == 0, value, ref[at] + value)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                      glse_ref, *rest, causal: bool, window: int,
                      scale: float, rows: int, head_dim: int,
                      block_diffusion=None, rope_dim: int = 0,
                      grouped: bool = False, group=None):
    """``rows`` batch rows a grid cell, each with the heads of one column
    block and the whole sequence in VMEM (gated by MAX_BWD_SEQ).
    Scores/probabilities never touch HBM — the reason XLA's einsum
    backward loses at these shapes. ``rope_dim``: the two-part score;
    dQr's block holds 128 // rope_dim heads and stays in VMEM while the
    grid runs through them, each writing its own lanes. ``grouped``
    (PR 43): the grid is (rows, KV lane block, query column block of
    its group) and dK, dV the group's float32 sums (``_group_sum``).
    ``group`` (PR 47): the lane block is two KV heads of 64 and the
    column block's two query heads share one: K and V are laid as that
    head twice (``_own_kv_head``) and the two heads' dK^T / dV^T added
    into its sublanes (``_group_halves``)."""
    member = pl.program_id(2) if grouped else 0
    part = _kv_part(2, group)
    if rope_dim:
        qr_ref, kr_ref, dq_ref, dk_ref, dv_ref, dqr_ref, dkr_ref = rest
        head = pl.program_id(1)
    else:
        dq_ref, dk_ref, dv_ref = rest

    def row(b):
        k = _own_kv_head(k_ref[b], part, head_dim)
        rope = (_rope_lanes(qr_ref[b], head, rope_dim),
                kr_ref[b]) if rope_dim else None
        dqt, dkt, dvt, *dr = _flash_bwd_tile(
            q_ref[b], k, k.T, _own_kv_head(v_ref[b], part, head_dim),
            o_ref[b], do_ref[b], lse_ref[b], glse_ref[b], scale,
            (0, 0, window, block_diffusion)
            if causal or block_diffusion is not None else None, head_dim,
            rope)
        dkt, dvt = (_group_halves(t, part, head_dim) for t in (dkt, dvt))
        dq_ref[b] = (dqt * scale).T.astype(dq_ref.dtype)
        _group_sum(dk_ref, b, (dkt * scale).T, grouped, member)
        _group_sum(dv_ref, b, dvt.T, grouped, member)
        if rope_dim:
            dqr = (dr[0] * scale).T.astype(dqr_ref.dtype)
            dqr_ref[b] = jnp.where(_rope_lanes_of(dqr.shape, head, rope_dim),
                                   dqr, dqr_ref[b])
            dkr_ref[b] = (dr[1] * scale).T.astype(dkr_ref.dtype)

    _for_rows(rows, max(1, 2 // (q_ref.shape[-1] // head_dim)), row)


def _flash_bwd_blocked_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                              glse_ref, *rest, causal: bool, window: int,
                              scale: float, blk: int, head_dim: int,
                              block_diffusion=None, rope_dim: int = 0,
                              grouped: bool = False, group=None,
                              subs: int = 1, masked: bool = False):
    """FA2 backward for sequences past MAX_BWD_SEQ: grid cell = one
    (batch row, column block, K-block). The Q/O/dO panels are resident;
    the K-block meets them in chunks of ``blk`` queries, and ONLY the
    chunks that hold a query which sees one of its keys (``_q_chunks``:
    from the diagonal on, and under a window no further than the window
    behind the block's last key; under ``block_diffusion`` two ranges,
    the noised queries and the clean ones that see it); a chunk's
    [BLK, BLK] score tile is recomputed in VMEM, and masked only in the
    sub-ranges ``_q_split`` classes edge. dK^T/dV^T add up over the
    chunks in two float32 scratch tiles and are turned into their block
    at the end; dQ adds up in place, a chunk's rows at a time, across
    the K-block grid dimension (same output block revisited -> Pallas
    keeps it in VMEM between consecutive steps).

    ``subs`` > 1 (PR 51; ``super_block``): the chunks ``_query_trim``
    names, those at the K block's own positions and a whole window
    ahead of them, run as ``subs`` sub-blocks of the K block's keys,
    each against the static slice of the chunk's queries that see any
    of it, one ``_flash_bwd_tile`` each: the chunk's Q^T, dO^T and
    delta are formed once (``_turned``), a sub-block's dK^T / dV^T add
    into its lanes of the scratch, and the sub-blocks' dQ^T, padded
    back to the chunk with zeros, are added up and written once. Every
    other chunk is the one whole tile it was: run as sub-blocks too
    they gained nothing (the lab's table in ``super_block``).

    The sums live in scratch and not in the loops' carries because a
    loop boundary moves its carries: v5e, kernels alone, backward ms
    with every tile masked, carries -> scratch (PR 35): 8 heads of 128
    at 2L = 16384 under block diffusion 5.63 -> 5.39 (K blocks of 512)
    and 5.17 -> 4.95 (1024); 7 heads, S = 16384, window 4096 3.95 ->
    3.84, full causal 7.64 -> 7.44; 4 heads, S = 8192 causal 1.17 ->
    1.13. With the loops cut by ``_q_split`` the carries cost more
    (window 4.16, block diffusion at 1024 5.34), the scratch does not
    (3.89, 4.97; full causal 7.31). The forward's carries are a Q
    block's [BLK_Q, W] sums; in scratch they were slower (block
    diffusion at 512 3.08 -> 4.57 ms: the [BLK_Q, 1] max and sum as
    stores), so they stay carries. A first chunk in straight-line code
    ahead of the loops, as the forward has its last, is refused by the
    chip's compiler here (an internal check of its MXU pass).

    ``rope_dim`` (PR 39): the two-part score. The rotated query parts'
    panel and dQr's hold 128 // rope_dim heads: dQr's block stays in
    VMEM while the grid runs through those heads and all their K
    blocks, zeroed at the first and each head adding into its own
    lanes. dKr, the gradient of the ONE rotated key, leaves a head at a
    time (this head's lanes of a 128-wide block, zeros in the others):
    the grid's order (a head's K blocks together, for dQ) lets no two
    heads meet in one output block, so XLA adds the heads up.

    ``grouped`` (PR 43): k and v are the KV heads' and the grid is
    (batch row, KV head, head of its group, K block): the same order of
    steps, so dQ's block is still revisited consecutively. A group's dK
    and dV cannot be a K block each (between two heads' visits of one
    the grid passes through the other K blocks, and Pallas keeps only a
    block that is revisited at once), so their blocks are the KV head's
    WHOLE float32 [S, W] panels, resident while the grid runs through
    the group's heads and all their K blocks and written once a group:
    a step turns its sums into the block's rows, the group's first head
    writing and the others adding (``_group_sum``). Nothing of H * D
    width leaves the backward but dQ.

    ``group`` (PR 47): the K / V lane block holds two KV heads of 64 and
    the grid's second and third axes are (KV lane block, query column
    block of the ``rep`` it serves). The column block's two query heads
    share the KV head ``_kv_part`` names: the step's K and V blocks are
    laid as that head twice (``_own_kv_head``, once a grid step, not a
    chunk), and at the end the two heads' dK^T / dV^T, sublane ranges of
    the scratch, are added into that head's sublanes
    (``_group_halves``); the other half gets zeros from this member, so
    the lane block's first member still writes without reading.

    ``masked`` (PR 54): the mask that is data, TRANSPOSED, this K
    block's rows of it [1, blk, S] (keys by queries, as the score tile
    lies here), and in SMEM the summary [B, S / blk, S / blk] by (Q
    chunk, K block); a chunk whose summary is 0 is skipped, every other
    is masked by the bytes alone (``_flash_fwd_kernel``)."""
    j = pl.program_id(3 if grouped else 2)
    k0 = j * blk
    part = _kv_part(2, group)
    k, v = (_own_kv_head(t[0], part, head_dim) for t in (k_ref, v_ref))
    kt = k.T
    split = _q_split(k0, blk, blk, q_ref.shape[1], causal, window,
                     block_diffusion)
    if rope_dim:
        (qr_ref, kr_ref, dq_ref, dk_ref, dv_ref, dqr_ref, dkr_ref, dkt_ref,
         dvt_ref, dkrt_ref) = rest
        head = pl.program_id(1)
        kr = kr_ref[0]

        @pl.when((j == 0) & (head % (LANES // rope_dim) == 0))
        def _init_rope():
            dqr_ref[0] = jnp.zeros(dqr_ref.shape[1:], dqr_ref.dtype)

        dkrt_ref[...] = jnp.zeros(dkrt_ref.shape, jnp.float32)
    elif masked:
        assert not block_diffusion, "mask operand: plain"
        (maskt_ref, any_ref, dq_ref, dk_ref, dv_ref, dkt_ref,
         dvt_ref) = rest
        row = pl.program_id(0)
    else:
        dq_ref, dk_ref, dv_ref, dkt_ref, dvt_ref = rest

    @pl.when(j == 0)
    def _init():
        dq_ref[0] = jnp.zeros(dq_ref.shape[1:], dq_ref.dtype)

    def chunk(c, _, edge, kept=None):
        if masked:      # a tile no query of which selected a key
            pl.when(any_ref[row, c, j] > 0)(
                lambda: work(c, True, kept))
        else:
            work(c, edge, kept)

    def work(c, edge, kept=None):
        q0 = pl.multiple_of(c * blk, blk)
        rows = pl.ds(q0, blk)
        q, o, do = q_ref[0, rows, :], o_ref[0, rows, :], do_ref[0, rows, :]
        lse, glse = lse_ref[0, :, :, rows], glse_ref[0, :, :, rows]
        qr = _rope_lanes(qr_ref[0, rows, :], head, rope_dim) if rope_dim \
            else None
        turned = _turned(q, o, do)    # once for the chunk's sub-blocks
        parts = subs if kept else 1
        sub = blk // parts
        sums = None
        for n in range(parts):
            # the queries that see any key of sub-block n: a static slice
            lo, hi = (sub * m for m in _kept(kept, n, parts))
            whole, keys = (lo, hi) == (0, blk), slice(n * sub, (n + 1) * sub)

            def of_rows(x):
                return x if whole else x[lo:hi]

            def of_lanes(x):
                return x if whole else x[..., lo:hi]

            kn, ktn, vn, krn = (
                (k, kt, v, kr if rope_dim else None) if parts == 1 else
                (k[keys], kt[:, keys], v[keys], kr[keys] if rope_dim else None))
            dqt, dkt, dvt, *dr = _flash_bwd_tile(
                of_rows(q), kn, ktn, vn, of_rows(o), of_rows(do),
                of_lanes(lse), of_lanes(glse), scale,
                (maskt_ref[0, keys, pl.ds(q0 + lo, hi - lo)
                           ].astype(jnp.int32) != 0) if masked else
                (k0 + n * sub, q0 + lo, window, block_diffusion)
                if edge else None, head_dim,
                (of_rows(qr), krn) if rope_dim else None,
                tuple(of_lanes(t) for t in turned))
            dkt_ref[:, keys] += dkt
            dvt_ref[:, keys] += dvt
            if rope_dim:
                dkrt_ref[:, keys] += dr[1]
            mine = [dqt] + dr[:1]
            if not whole:   # zeros for the queries that do not meet it
                mine = [jnp.pad(t, ((0, 0), (lo, blk - hi))) for t in mine]
            sums = mine if sums is None else [a + b for a, b in
                                              zip(sums, mine)]
        dq_ref[0, rows, :] += (sums[0] * scale).T
        if rope_dim:
            dqr_ref[0, rows, :] += _rope_lanes((sums[1] * scale).T, head,
                                               rope_dim)

    dkt_ref[...] = jnp.zeros(dkt_ref.shape, jnp.float32)
    dvt_ref[...] = jnp.zeros(dvt_ref.shape, jnp.float32)
    for n, (lo, hi, edge) in enumerate(split):
        kept = _query_trim(n, causal, window, blk, blk // subs,
                           block_diffusion) if subs > 1 else None
        jax.lax.fori_loop(lo, hi, functools.partial(
            chunk, edge=edge, kept=kept), None)
    dkt, dvt = (_group_halves(t[...], part, head_dim)
                for t in (dkt_ref, dvt_ref))
    at = (0, pl.ds(pl.multiple_of(k0, blk), blk)) if grouped else 0
    member = pl.program_id(2) if grouped else 0
    _group_sum(dk_ref, at, (dkt * scale).T, grouped, member)
    _group_sum(dv_ref, at, dvt.T, grouped, member)
    if rope_dim:
        dkr_ref[0] = (dkrt_ref[...] * scale).T.astype(dkr_ref.dtype)


def _flash_bwd_span_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                           glse_ref, dq_ref, dk_ref, dv_ref, *, window: int,
                           scale: float, blk: int, span: int, tiles: int,
                           unroll: int, head_dim: int, grouped: bool = False,
                           group=None):
    """The K-blocked backward under a window so narrow that the queries
    which see a K block are ONE score tile (``one_span``, PR 46): each
    of the grid step's ``tiles`` K blocks meets the ``span`` queries of
    the resident Q/O/dO panels from its first key on (``_q_span``) as
    one masked [BLK, SPAN] tile of ``_flash_bwd_tile``, straight-line
    code. dK^T and dV^T are complete after that tile: no scratch sums,
    no zeroing, no ``+=``; dQ's rows of the span add into the resident
    float32 panel as in ``_flash_bwd_blocked_kernel`` (neighbouring K
    blocks' spans overlap). The grids, the BlockSpecs and the grouped
    form's sums into the KV head's panels (``_group_sum``) are that
    kernel's, ``group`` too."""
    j = pl.program_id(3 if grouped else 2)
    member = pl.program_id(2) if grouped else 0
    part = _kv_part(2, group)

    @pl.when(j == 0)
    def _init():
        dq_ref[0] = jnp.zeros(dq_ref.shape[1:], dq_ref.dtype)

    def block(t):
        mine = pl.ds(pl.multiple_of(t * blk, blk), blk)
        k0 = pl.multiple_of((j * tiles + t) * blk, blk)
        k, v = (_own_kv_head(t[0, mine, :], part, head_dim)
                for t in (k_ref, v_ref))
        q0 = pl.multiple_of(_q_span(k0, span, q_ref.shape[1]), BLK_Q)
        rows = pl.ds(q0, span)
        dqt, dkt, dvt = _flash_bwd_tile(
            q_ref[0, rows, :], k, k.T, v, o_ref[0, rows, :],
            do_ref[0, rows, :], lse_ref[0, :, :, rows],
            glse_ref[0, :, :, rows], scale, (k0, q0, window, None), head_dim)
        dkt, dvt = (_group_halves(t, part, head_dim) for t in (dkt, dvt))
        dq_ref[0, rows, :] += (dqt * scale).T
        # a group's dK / dV block is the KV head's whole panel
        at = (0, pl.ds(k0, blk) if grouped else mine)
        _group_sum(dk_ref, at, (dkt * scale).T, grouped, member)
        _group_sum(dv_ref, at, dvt.T, grouped, member)

    _for_rows(tiles, unroll, block)


def _flash_bwd(q, k, v, o, lse, do, num_heads: int, causal: bool,
               interpret: bool, glse=None, window: int = 0,
               block_diffusion=None, rope=None, num_kv_heads=None,
               mask=None):
    """dq, dk, dv [B, S, H*D] from the saved (o, lse[B, H, 1, S]): one
    whole-tile step for several heads up to MAX_BWD_SEQ, K-blocked past
    it — scores stay in VMEM tiles at every length the gate admits
    (flash_attention_available caps S at MAX_FLASH_SEQ). With ``rope``
    (``_flash_fwd``) also (dq_rope [B, S, H*R], dk_rope [B, S, R]): the
    kernels hand dk_rope out a head at a time and the sum over the
    heads, into the one rotated key, is taken here. With
    ``num_kv_heads`` < H (k, v [B, S, Hk*D]) dk and dv are the groups'
    sums [B, S, Hk*D] in FLOAT32, added up inside the kernels from their
    float32 tiles: no query head's dK or dV is written, or rounded. At
    heads of 64 (PR 47) the grouped grids run over the KV LANE blocks
    (two KV heads) and the ``rep`` query column blocks each serves."""
    b, s, hd = q.shape
    d = hd // num_heads
    if d > LANES:
        assert block_diffusion is None and rope is None and mask is None
        return _wide_flash_bwd(q, k, v, o, lse, do, num_heads, causal,
                               interpret, window, num_kv_heads, glse)
    hpb = _heads_per_block(num_heads, d)
    w = hpb * d
    rope_dim, per, rope_ops = _rope_operands(rope, num_heads, d)
    rep, group = _group_size(num_heads, num_kv_heads, d, rope)
    grouped = rep > 1
    dk_shape, dv_shape = (jax.ShapeDtypeStruct(
        t.shape, jnp.float32 if grouped else t.dtype) for t in (k, v))
    scale = 1.0 / float(d + rope_dim) ** 0.5
    window = normalized_window(s, causal, window)
    bd = checked_block_diffusion(s, causal, window, block_diffusion)
    # the ring's merge hands a float32 dO (its o is float32): as an MXU
    # operand it takes the stored dtype, like P and dS
    do = do.astype(q.dtype)
    if glse is None:
        glse = jnp.zeros((b, num_heads, 1, s), jnp.float32)

    def finish(dq, dk, dv, dqr=None, dkr=None):
        if not rope_dim:
            return dq.astype(q.dtype), dk, dv
        # dkr [B, S, H*128]: head h's part in its own lanes of block h;
        # the heads add up, then the copies of the key laid side by side
        dkr = jnp.sum(dkr.astype(jnp.float32).reshape(
            b, s, num_heads, per, rope_dim), axis=(2, 3))
        return (dq.astype(q.dtype), dk, dv,
                (dqr.astype(rope[0].dtype), dkr.astype(rope[1].dtype)))

    if s <= MAX_BWD_SEQ:
        rows = _rows_per_step(b, hpb, s)
        seq_spec = pl.BlockSpec((rows, s, w), lambda i, j: (i, 0, j))
        row_spec = pl.BlockSpec((rows, hpb, 1, s), lambda i, j: (i, j, 0, 0))
        qr_spec = pl.BlockSpec((rows, s, LANES), lambda i, j: (i, 0, j // per))
        kr_spec = pl.BlockSpec((rows, s, LANES), lambda i, j: (i, 0, 0))
        kv_spec, grid = seq_spec, (b // rows, num_heads // hpb)
        if grouped:     # (rows, KV lane block g, column block r of its rep)
            seq_spec = pl.BlockSpec((rows, s, w),
                                    lambda i, g, r: (i, 0, g * rep + r))
            row_spec = pl.BlockSpec((rows, hpb, 1, s),
                                    lambda i, g, r: (i, g * rep + r, 0, 0))
            kv_spec = pl.BlockSpec((rows, s, w), lambda i, g, r: (i, 0, g))
            grid = (b // rows, num_heads // hpb // rep, rep)
        return finish(*pl.pallas_call(
            functools.partial(_flash_bwd_kernel, causal=causal,
                              window=window, scale=scale, rows=rows,
                              head_dim=d, block_diffusion=bd,
                              rope_dim=rope_dim, grouped=grouped,
                              group=group),
            name=KERNEL_NAME_PREFIX + "flash_bwd",
            out_shape=(jax.ShapeDtypeStruct((b, s, hd), q.dtype),
                       dk_shape, dv_shape) + ((
                           jax.ShapeDtypeStruct(rope_ops[0].shape, q.dtype),
                           jax.ShapeDtypeStruct((b, s, num_heads * LANES),
                                                k.dtype),
                       ) if rope_dim else ()),
            grid=grid,
            in_specs=[seq_spec, kv_spec, kv_spec, seq_spec, seq_spec,
                      row_spec, row_spec] + (
                          [qr_spec, kr_spec] if rope_dim else []),
            out_specs=(seq_spec, kv_spec, kv_spec) + (
                (qr_spec, seq_spec) if rope_dim else ()),
            interpret=interpret,
            compiler_params=_FLASH_COMPILER_PARAMS,
        )(q, k, v, o, do, lse, glse, *rope_ops))
    one = one_span(s, causal, window, bd, rope_dim)
    if one is not None:     # the queries that see a K block as one tile
        rows, span = one[1]
        tiles, unroll = _span_tiles(s, rows)
        kernel = functools.partial(_flash_bwd_span_kernel, window=window,
                                   scale=scale, blk=rows, span=span,
                                   tiles=tiles, unroll=unroll, head_dim=d,
                                   grouped=grouped, group=group)
        blk, scratch = tiles * rows, []
    else:
        blk = _seq_block(s, bd, window)
        kernel = functools.partial(
            _flash_bwd_blocked_kernel, causal=causal, window=window,
            scale=scale, blk=blk, head_dim=d, block_diffusion=bd,
            rope_dim=rope_dim, grouped=grouped, group=group,
            subs=super_block(s, window, bd)[1],
            **({"masked": True} if mask is not None else {}))
        # dK^T and dV^T (and dKr^T) added up over a block's Q chunks
        scratch = [pltpu.VMEM((w, blk), jnp.float32)] * 2 + (
            [pltpu.VMEM((LANES, blk), jnp.float32)] if rope_dim else [])
    seq_spec = pl.BlockSpec((1, s, w), lambda b, c, j: (b, 0, c))
    kblk_spec = pl.BlockSpec((1, blk, w), lambda b, c, j: (b, j, c))
    row_spec = pl.BlockSpec((1, hpb, 1, s), lambda b, c, j: (b, c, 0, 0))
    qr_spec = pl.BlockSpec((1, s, LANES), lambda b, c, j: (b, 0, c // per))
    kr_spec = pl.BlockSpec((1, blk, LANES), lambda b, c, j: (b, j, 0))
    dkv_spec, grid = kblk_spec, (b, num_heads // hpb, s // blk)
    if grouped:     # (batch row, KV lane block g, column block r, K block)
        seq_spec = pl.BlockSpec((1, s, w),
                                lambda b, g, r, j: (b, 0, g * rep + r))
        row_spec = pl.BlockSpec((1, hpb, 1, s),
                                lambda b, g, r, j: (b, g * rep + r, 0, 0))
        kblk_spec = pl.BlockSpec((1, blk, w), lambda b, g, r, j: (b, j, g))
        # the KV head's whole panel: resident across its group
        dkv_spec = pl.BlockSpec((1, s, w), lambda b, g, r, j: (b, 0, g))
        grid = (b, num_heads // hpb // rep, rep, s // blk)
    mask_specs = []
    if mask is not None:    # (mask^T [B, S, S] int8, its summary)
        assert one is None and not rope_dim and s > MAX_BWD_SEQ, (s, window)
        mask_specs = [
            pl.BlockSpec((1, blk, s), (lambda b, g, r, j: (b, j, 0))
                         if grouped else (lambda b, c, j: (b, j, 0))),
            pl.BlockSpec(memory_space=pltpu.SMEM)]
    return finish(*pl.pallas_call(
        kernel,
        name=KERNEL_NAME_PREFIX + "flash_bwd_blocked",
        out_shape=(jax.ShapeDtypeStruct((b, s, hd), jnp.float32),  # dq acc
                   dk_shape, dv_shape) + ((
                       jax.ShapeDtypeStruct(rope_ops[0].shape, jnp.float32),
                       jax.ShapeDtypeStruct((b, s, num_heads * LANES),
                                            k.dtype),
                   ) if rope_dim else ()),
        grid=grid,
        in_specs=[seq_spec, kblk_spec, kblk_spec, seq_spec, seq_spec,
                  row_spec, row_spec] + (
                      [qr_spec, kr_spec] if rope_dim else []) + mask_specs,
        out_specs=(seq_spec, dkv_spec, dkv_spec) + (
            (qr_spec, kblk_spec) if rope_dim else ()),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=(_FLASH_COMPILER_PARAMS if mask is None
                         else _MASKED_COMPILER_PARAMS),
    )(q, k, v, o, do, lse, glse, *rope_ops, *(mask or ())))


# ---------------------------------------------------------------------------
# heads wider than a lane block (PR 58): a head of 256 is two 128-lane
# blocks side by side, the score the sum of two 128-deep products (one
# contraction of 256), the output two halves. The panels the kernels
# above keep resident ([S, W] of K and V forward, of Q, O, dO, dQ
# backward) would be [S, 256] here, which the 96 MiB do not hold at
# S = 16384 beside the score tiles; so these take ONE [Q block, K block]
# tile a grid step, every operand a block its BlockSpec fetches, the
# running statistics and the sums in VMEM scratch across the innermost
# grid axis. Causal or not, a window; no block-diffusion mask, no
# two-part score, no mask operand (nobody has needed them at this width).
#
# The backward is ONE kernel (PR 59; PR 58 shipped two, dQ and dK with
# dV, which both formed a visited tile's P^T and dS^T: seven 256-deep
# products a tile and the tile's exp twice, where five and once are the
# mathematics). `scripts/delta_lab.py` keeps the two-kernel form and
# times both (my chip runs, PR 59; v5e, 16 : 2 heads of 256, 16,384
# causal positions, bf16; device ms of the backward's kernels alone; the
# forward beside them 16.80):
#
#   two kernels, 512 x 1024 (PR 58)     dQ 20.91 + dK, dV 25.64 = 46.55
#   one kernel, Q x K block, K blocks a run:
#     512 x 1024, 1    36.59   (dQ's copies left out, a wrong dQ: 32.44)
#     512 x  512, 1    42.59       256 x 1024, 1    40.20
#     512 x 2048, 1    34.92       256 x 2048, 1    36.51
#    1024 x 2048, 1    75.85      1024 x 1024, 1    34.71  (no copies: 31.31)
#    1024 x 1024, 2    33.23      1024 x 1024, 4    32.43  <- ships
#    1024 x 1024, 8    32.07  (float32: VMEM refused); 4, no copies: 31.67
#     512 x 1024, 4    33.87      1024 x  512, 8    34.07
#     512 x 2048, 2    34.07
#
# dQ's float32 sum cannot stay in VMEM from one K block to the next (the
# other Q blocks and the group's heads come between), so it travels to
# HBM and back by the kernel's own copies. Those copies cost the products
# about a microsecond a MB WHEREVER they are started and waited for (at
# 512 x 1024: fetched under the first products and sent back under the
# last 36.53, sent back and waited for in the next visited step 36.80,
# waited for where started 43.27): not latency to hide but bytes, so the
# grid walks a RUN of K blocks under each Q block before it moves on, the
# sum staying in VMEM meanwhile; a run of four moves a quarter of the
# bytes. A tile of 1024 x 1024 halves the grid's steps at the same
# visited pairs (136 tiles a head for the forward's 272 of 512 x 1024).
WIDE_BLK_Q = 512
WIDE_BLK_K = 1024
WIDE_BWD_BLK = 1024     # the backward's tile, both ways
WIDE_BWD_RUN = 4        # K blocks under a Q block before its dQ sum leaves


def _wide_blocks(s: int):
    return (next(b for b in (WIDE_BLK_Q, 256, BLK_Q) if s % b == 0),
            next(b for b in (WIDE_BLK_K, 512, 256, BLK_Q) if s % b == 0))


def _wide_bwd_blocks(s: int):
    """The backward's (Q block, K block, K blocks a run), its own choice
    (the lab's table above)."""
    blk = next(b for b in (WIDE_BWD_BLK, 512, 256, BLK_Q) if s % b == 0)
    run = next(n for n in (WIDE_BWD_RUN, 2, 1) if s // blk % n == 0)
    return blk, blk, run


def wide_kv_blocks(s: int, causal: bool, window: int = 0):
    """(visited, total, masked) [Q block, K block] tiles a head of the
    wide-head forward works through, as `kv_blocks` / `kv_blocks_masked`
    count the chunk loop's."""
    return _wide_tiles(s, *_wide_blocks(s), causal, window)


def wide_bwd_score_tiles(s: int, causal: bool, window: int = 0) -> int:
    """The backward's own [K block, Q block] tiles a head whose P^T and
    dS^T it forms, each ONCE (PR 58's two kernels formed each twice):
    136 of [1024, 1024] at 16,384 causal positions."""
    blk_q, blk_k, _ = _wide_bwd_blocks(s)
    return _wide_tiles(s, blk_q, blk_k, causal, window)[0]


def _wide_tiles(s: int, blk_q: int, blk_k: int, causal: bool, window: int):
    window = normalized_window(s, causal, window)
    nq, nk = s // blk_q, s // blk_k
    visited = masked = 0
    for iq in range(nq):
        first, last = _wide_k_range(iq, blk_q, blk_k, nk, causal, window)
        for ik in range(int(first), int(last) + 1):
            visited += 1
            masked += not _wide_interior(iq * blk_q, ik * blk_k, blk_q,
                                         blk_k, causal, window)
    return visited, nq * nk, int(masked)


def _wide_k_range(iq, blk_q: int, blk_k: int, nk: int, causal: bool,
                  window: int):
    """(first, last) K block that holds a key some query of Q block
    ``iq`` sees."""
    if not causal:
        return 0, nk - 1
    last = (iq * blk_q + blk_q - 1) // blk_k
    first = jnp.maximum(iq * blk_q - window + 1, 0) // blk_k if window else 0
    return first, last


def _wide_q_range(ik, blk_q: int, blk_k: int, nq: int, causal: bool,
                  window: int):
    """(first, last) Q block that holds a query which sees some key of K
    block ``ik``."""
    if not causal:
        return 0, nq - 1
    first = ik * blk_k // blk_q
    last = (jnp.minimum((ik * blk_k + blk_k - 1 + window - 1) // blk_q,
                        nq - 1) if window else nq - 1)
    return first, last


def _wide_interior(q0, k0, blk_q: int, blk_k: int, causal: bool,
                   window: int):
    """Every pair of the tile is visible: no mask is built."""
    if not causal:
        return True
    inside = k0 + blk_k - 1 <= q0
    if window:
        inside = jnp.logical_and(inside, k0 > q0 + blk_q - 1 - window)
    return inside


def _wide_visit(tile, at, span, q0, k0, blk_q: int, blk_k: int,
                causal: bool, window: int) -> None:
    """``tile(masked)`` for the grid step's [Q block, K block] tile where
    block ``at`` lies inside ``span`` (first, last), the blocks that hold
    a visible pair: without a mask where every pair is visible, and not
    at all outside the span."""
    if not causal:
        tile(False)
        return
    needed = jnp.logical_and(at >= span[0], at <= span[1])
    interior = _wide_interior(q0, k0, blk_q, blk_k, causal, window)
    pl.when(jnp.logical_and(needed, interior))(lambda: tile(False))
    pl.when(jnp.logical_and(needed, jnp.logical_not(interior)))(
        lambda: tile(True))


def _wide_visible(q0, k0, shape, q_axis: int, window: int):
    qq = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kk = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    seen = kk <= qq
    if window:
        seen = jnp.logical_and(seen, kk > qq - window)
    return seen


def _wide_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                     acc_scr, *, scale: float, causal: bool, window: int,
                     blk_q: int, blk_k: int, nk: int):
    iq, ik = pl.program_id(2), pl.program_id(3)
    q0, k0 = iq * blk_q, ik * blk_k

    @pl.when(ik == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _MASKED)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(masked: bool):
        s = _dot(q_ref[0], k_ref[0], _NT) * scale
        if masked:
            s = jnp.where(_wide_visible(q0, k0, s.shape, 0, window), s,
                          _MASKED)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + _dot(
            p.astype(v_ref.dtype), v_ref[0], _NN)
        m_scr[...] = m_new

    _wide_visit(tile, ik, _wide_k_range(iq, blk_q, blk_k, nk, causal, window),
                q0, k0, blk_q, blk_k, causal, window)

    @pl.when(ik == nk - 1)
    def _():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, 0, :] = (m_scr[...] + jnp.log(l))[:, 0]


def _wide_bwd_tile(q, k, v, do, lse, delta, q0, k0, masked: bool,
                   scale: float, window: int):
    """(P^T, dS^T) [Bk, Bq] of one tile from the saved log-sum-exp and
    delta rows: the tile as [k, q], so that the row statistics broadcast
    down the sublanes as they are stored."""
    st = _dot(k, q, _NT) * scale
    if masked:
        st = jnp.where(_wide_visible(q0, k0, st.shape, 1, window), st,
                       _MASKED)
    pt = jnp.exp(st - lse)
    dpt = _dot(v, do, _NT)
    return pt, (pt * (dpt - delta)).astype(q.dtype)


def _wide_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, sums_ref, dk_scr, dv_scr,
                     dq_scr, out_scr, sem, *, scale: float, causal: bool,
                     window: int, blk_q: int, blk_k: int, nq: int, nk: int,
                     rep: int, run: int):
    """One [K block, Q block] tile a grid step: P^T and dS^T are formed
    ONCE and give all three gradients. The grid walks ``run`` K blocks
    (the innermost axis) under each Q block of each of the group's heads,
    and then the next ``run``: K, V, dK and dV are blocks of ``run`` K
    blocks, dK and dV float32 scratch over the group's heads and the Q
    blocks. dQ's rows meet the next run of K blocks ``rep * nq * run``
    steps later, so their float32 sum lives in HBM between runs
    (``sums_ref`` [B, H, S, D], this kernel's alone): a run's first tile
    fetches its block under its first products, its last tile sends it
    back under its last ones, and between them the sum stays in VMEM (a
    copy costs the products its bytes' time wherever it is put: the
    lab's table above). A Q block's first tile of all fetches nothing and
    its last rounds the sum into dQ, so no pass runs before or after the
    kernel; a step outside the causal span starts no copy."""
    b, g, jk, r, iq, c = (pl.program_id(a) for a in range(6))
    h, ik = g * rep + r, jk * run + c
    q0, k0 = iq * blk_q, ik * blk_k
    d = q_ref.shape[-1]
    rows = pl.ds(pl.multiple_of(c * blk_k, blk_k), blk_k)

    @pl.when(jnp.logical_and(jnp.logical_and(r == 0, iq == 0), c == 0))
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    first, last = _wide_k_range(iq, blk_q, blk_k, nk, causal, window)
    # the K blocks of this run that the Q block meets
    enter = jnp.maximum(first, jk * run)
    leave = jnp.minimum(last, jk * run + run - 1)
    fetches = jnp.logical_and(ik == enter, ik > first)
    keeps = jnp.logical_and(ik == leave, ik < last)
    sums = sums_ref.at[b, h, pl.ds(q0, blk_q)]
    fetch = pltpu.make_async_copy(sums, dq_scr, sem.at[0])
    keep = pltpu.make_async_copy(dq_scr, sums, sem.at[1])
    hand_out = pltpu.make_async_copy(
        out_scr, dq_ref.at[b, pl.ds(q0, blk_q), pl.ds(h * d, d)], sem.at[1])

    def tile(masked: bool):
        pl.when(fetches)(fetch.start)
        k = k_ref[0, rows]
        pt, dst = _wide_bwd_tile(q_ref[0], k, v_ref[0, rows], do_ref[0],
                                 lse_ref[0, 0], delta_ref[0, 0], q0, k0,
                                 masked, scale, window)
        part = _dot(dst, k, _TN)

        @pl.when(ik == first)
        def _():
            dq_scr[...] = part

        @pl.when(ik > first)
        def _():
            pl.when(fetches)(fetch.wait)
            dq_scr[...] += part

        pl.when(keeps)(keep.start)

        @pl.when(ik == last)
        def _():
            out_scr[...] = (dq_scr[...] * scale).astype(out_scr.dtype)
            hand_out.start()

        dv_scr[rows] += _dot(pt.astype(do_ref.dtype), do_ref[0], _NN)
        dk_scr[rows] += _dot(dst, q_ref[0], _NN)
        pl.when(keeps)(keep.wait)
        pl.when(ik == last)(hand_out.wait)

    _wide_visit(tile, iq, _wide_q_range(ik, blk_q, blk_k, nq, causal, window),
                q0, k0, blk_q, blk_k, causal, window)

    @pl.when(jnp.logical_and(jnp.logical_and(r == rep - 1, iq == nq - 1),
                             c == run - 1))
    def _():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _wide_key_block(blk_q: int, blk_k: int, nk: int, causal: bool,
                    window: int):
    """(Q block, K block) -> the K block a grid step's BlockSpec names:
    the step's own inside the Q block's span, else the span's nearest,
    so that a block no query sees is not fetched anew."""
    def key_block(iq, ik):
        first, last = _wide_k_range(iq, blk_q, blk_k, nk, causal, window)
        return jnp.clip(ik, first, last) if causal else ik

    return key_block


def _wide_flash_fwd(q, k, v, num_heads: int, causal: bool, interpret: bool,
                    out_dtype, window: int, num_kv_heads):
    """`_flash_fwd` at a head wider than 128 lanes: (o [B, S, H*D], lse
    [B, H, 1, S]); k and v [B, S, Hk*D], a KV head's block fetched for
    each of its query heads by the BlockSpec's ``h // rep``."""
    b, s, hd = q.shape
    d = hd // num_heads
    rep = num_heads // (num_kv_heads or num_heads)
    window = normalized_window(s, causal, window)
    blk_q, blk_k = _wide_blocks(s)
    nq, nk = s // blk_q, s // blk_k

    key_block = _wide_key_block(blk_q, blk_k, nk, causal, window)
    return pl.pallas_call(
        functools.partial(_wide_fwd_kernel, scale=1.0 / float(d) ** 0.5,
                          causal=causal, window=window, blk_q=blk_q,
                          blk_k=blk_k, nk=nk),
        name=KERNEL_NAME_PREFIX + "flash_fwd_wide",
        out_shape=(jax.ShapeDtypeStruct((b, s, hd), out_dtype or q.dtype),
                   jax.ShapeDtypeStruct((b, num_heads, 1, s), jnp.float32)),
        grid=(b, num_heads, nq, nk),
        in_specs=[pl.BlockSpec((1, blk_q, d), lambda b, h, i, j: (b, i, h))]
        + [pl.BlockSpec((1, blk_k, d),
                        lambda b, h, i, j: (b, key_block(i, j), h // rep))
           ] * 2,
        out_specs=(pl.BlockSpec((1, blk_q, d), lambda b, h, i, j: (b, i, h)),
                   pl.BlockSpec((1, 1, 1, blk_q),
                                lambda b, h, i, j: (b, h, 0, i))),
        scratch_shapes=[pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=96 << 20),
    )(q, k, v)


def _wide_delta(q, o, do, num_heads: int, glse):
    """(dO as an MXU operand, delta [B, H, 1, S] like lse): delta =
    rowsum(dO * O) less ``glse``, the upstream gradient on the logsumexp
    output (the ring's streaming merge): dS = P * (dP - delta + g_lse)."""
    b, s, hd = q.shape
    do = do.astype(q.dtype)
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
        b, s, num_heads, hd // num_heads), axis=-1).transpose(0, 2, 1)
    delta = delta[:, :, None, :]
    return do, delta if glse is None else delta - glse


def _wide_flash_bwd(q, k, v, o, lse, do, num_heads: int, causal: bool,
                    interpret: bool, window: int, num_kv_heads, glse=None):
    """`_flash_bwd` at a head wider than 128 lanes, ONE kernel
    (`_wide_bwd_kernel`): a K block over the Q blocks of its group's
    heads gives dK and dV (float32 sums [B, S, Hk*D] under grouped keys)
    and each tile's part of dQ."""
    b, s, hd = q.shape
    d = hd // num_heads
    hk = num_kv_heads or num_heads
    rep = num_heads // hk
    window = normalized_window(s, causal, window)
    blk_q, blk_k, run = _wide_bwd_blocks(s)
    nq, nk = s // blk_q, s // blk_k
    do, delta = _wide_delta(q, o, do, num_heads, glse)

    def query_block(jk, iq):
        first, last = _wide_q_range(jk, blk_q, run * blk_k, nq, causal,
                                    window)
        return jnp.clip(iq, first, last) if causal else iq

    def of_query(b, g, j, r, i, c):
        return b, query_block(j, i), g * rep + r

    def row_of_query(b, g, j, r, i, c):
        return b, g * rep + r, 0, query_block(j, i)

    def of_keys(b, g, j, r, i, c):
        return b, j, g

    kv_dtype = jnp.float32 if rep > 1 else k.dtype
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    keys = pl.BlockSpec((1, run * blk_k, d), of_keys)
    dq, dk, dv, _ = pl.pallas_call(
        functools.partial(_wide_bwd_kernel, scale=1.0 / float(d) ** 0.5,
                          causal=causal, window=window, blk_q=blk_q,
                          blk_k=blk_k, nq=nq, nk=nk, rep=rep, run=run),
        name=KERNEL_NAME_PREFIX + "flash_bwd_wide",
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, kv_dtype),
                   jax.ShapeDtypeStruct(v.shape, kv_dtype),
                   jax.ShapeDtypeStruct((b, num_heads, s, d), jnp.float32)),
        grid=(b, hk, nk // run, rep, nq, run),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), of_query), keys, keys,
            pl.BlockSpec((1, blk_q, d), of_query),
            pl.BlockSpec((1, 1, 1, blk_q), row_of_query),
            pl.BlockSpec((1, 1, 1, blk_q), row_of_query),
        ],
        out_specs=(in_hbm, keys, keys, in_hbm),
        scratch_shapes=[pltpu.VMEM((run * blk_k, d), jnp.float32),
                        pltpu.VMEM((run * blk_k, d), jnp.float32),
                        pltpu.VMEM((blk_q, d), jnp.float32),
                        pltpu.VMEM((blk_q, d), q.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
        # a run of K blocks reads what the one before it left of dQ's
        # sums: the runs go in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=96 << 20),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _xla_attention(q, k, v, causal: bool, window: int = 0,
                   block_diffusion=None):
    """Reference einsum attention the kernel tests compare against."""
    return _xla_attention_lse(q, k, v, causal, window, block_diffusion)[0]


def _xla_attention_lse(q, k, v, causal: bool, window: int = 0,
                       block_diffusion=None):
    """Reference einsum path that also emits the per-row logsumexp."""
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(d))
    if causal or block_diffusion:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = visible(jnp.arange(sq)[:, None] + (sk - sq),
                       jnp.arange(sk)[None, :], window, block_diffusion)
        s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)
    return o, lse


def _stored(q, k, v, num_kv_heads):
    """k and v as the kernels read them. Grouped keys and values
    (``num_kv_heads``) come in float32 (``flash_attention``), so that
    their cotangents, a group's sums, leave in float32; the kernels read
    them rounded to q's dtype, as they read the repeated ones."""
    if num_kv_heads is None:
        return k, v
    return k.astype(q.dtype), v.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 9))
def _flash(q, k, v, num_heads, causal, interpret, window=0,
           block_diffusion=None, rope=None, num_kv_heads=None):
    return _flash_fwd(q, *_stored(q, k, v, num_kv_heads), num_heads, causal,
                      interpret, window=window,
                      block_diffusion=block_diffusion, rope=rope,
                      num_kv_heads=num_kv_heads)[0]


def _flash_vjp_fwd(q, k, v, num_heads, causal, interpret, window=0,
                   block_diffusion=None, rope=None, num_kv_heads=None):
    k, v = _stored(q, k, v, num_kv_heads)
    o, lse = _flash_fwd(q, k, v, num_heads, causal, interpret,
                        window=window, block_diffusion=block_diffusion,
                        rope=rope, num_kv_heads=num_kv_heads)
    return o, (q, k, v, rope, o, lse)


def _flash_vjp_bwd(num_heads, causal, interpret, window, block_diffusion,
                   num_kv_heads, res, g):
    q, k, v, rope, o, lse = res
    grads = _flash_bwd(q, k, v, o, lse, g, num_heads, causal, interpret,
                       window=window, block_diffusion=block_diffusion,
                       rope=rope, num_kv_heads=num_kv_heads)
    return grads if rope is not None else (*grads, None)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_lse(q, k, v, num_heads, causal, interpret):
    """Flash attention returning (o [B, S, H*D], lse [B, H, S]) — the
    streaming-merge primitive ring attention accumulates per K/V block.
    Differentiable: the backward kernel carries the upstream lse
    gradient (the merge weights are functions of lse). q, k, v:
    [B, S, H*D]. ``o`` is emitted in f32: the ring merge accumulates in
    f32, and rounding each block's normalized output to bf16 first would
    compound per-block error. No window: a ring's blocks would each need
    their own offset into it."""
    o, lse = _flash_fwd(q, k, v, num_heads, causal, interpret,
                        out_dtype=jnp.float32)
    return o, lse[:, :, 0, :]


def _flash_lse_vjp_fwd(q, k, v, num_heads, causal, interpret):
    o, lse = _flash_fwd(q, k, v, num_heads, causal, interpret,
                        out_dtype=jnp.float32)
    return (o, lse[:, :, 0, :]), (q, k, v, o, lse)


def _flash_lse_vjp_bwd(num_heads, causal, interpret, res, gs):
    q, k, v, o, lse = res
    g_o, g_lse = gs
    glse = g_lse[:, :, None, :].astype(jnp.float32)
    return _flash_bwd(q, k, v, o, lse, g_o, num_heads, causal, interpret,
                      glse=glse)


flash_attention_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


# ---------------------------------------------------------------------------
# learned sparse attention (PR 54): a mask that is DATA. An indexer scores
# every causal (query, key) pair, a query keeps its `topk` best keys, the
# main attention runs over those alone (the chunk-loop kernels above with
# a mask operand), and the indexer learns from the KL divergence between
# the main attention's head-summed probabilities and its own softmax over
# the kept keys. Three kernels: `index_select` (scores in blocks of query
# rows that never leave VMEM, and an EXACT selection: the k-th largest of
# a row by bisection over the bits of its scores, ties to the lower key,
# `lax.top_k`'s set), the masked flash pair, and `index_kl` (the loss a
# row and its gradient to the indexer's queries, key and weights, tile by
# tile from q, k and the saved log-sum-exps).

INDEX_LANES = 64    # an index head's width: two heads a 128-lane block


def masked_flash_legal(s: int, num_heads: int, head_dim: int) -> bool:
    """Whether the chunk-loop flash kernels take a mask operand at this
    shape: a length past the whole-tile kernels, heads of 128 (a column
    block a head; the grouped form reads the KV heads as they are)."""
    return (s > MAX_BWD_SEQ and head_dim == LANES
            and flash_shape_legal(s, head_dim, num_heads))


def masked_tiles(s: int):
    """((rows, keys) of the forward's tile a summary entry stands for,
    the backward's): a super-block's rows by a K chunk, a Q chunk by a
    K block."""
    tiles, chains, _ = super_block(s)[0]
    blk = _seq_block(s)
    return (chains * _q_block(s), blk), (blk, blk)


def mask_tiles_any(mask, rows: int, keys: int, counts=None):
    """[B, S / rows, S / keys] int32: the pairs a tile of the mask
    [B, S, S] holds (0: the kernels skip it). ``counts``
    (`index_select`'s third result): added up from its finer tiles
    where they nest in these, with no pass over the mask."""
    b, s, _ = mask.shape
    if counts is not None:
        r, k = s // counts.shape[1], s // counts.shape[2]
        if rows % r == 0 and keys % k == 0:
            return jnp.sum(counts.reshape(b, s // rows, rows // r,
                                          s // keys, keys // k), axis=(2, 4))
    return jnp.sum(mask.reshape(b, s // rows, rows, s // keys, keys),
                   axis=(2, 4), dtype=jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_masked(q, k, v, mask, counts, num_heads, num_kv_heads, interpret):
    return _flash_masked_fwd(q, k, v, mask, counts, num_heads, num_kv_heads,
                             interpret)[0]


def _flash_masked_fwd(q, k, v, mask, counts, num_heads, num_kv_heads,
                      interpret):
    k, v = _stored(q, k, v, num_kv_heads)
    fwd, bwd = masked_tiles(q.shape[1])
    o, lse = _flash_fwd(q, k, v, num_heads, True, interpret,
                        num_kv_heads=num_kv_heads,
                        mask=(mask, mask_tiles_any(mask, *fwd, counts)))
    return (o, lse), (q, k, v, mask, mask_tiles_any(mask, *bwd, counts), o,
                      lse)


def _flash_masked_bwd(num_heads, num_kv_heads, interpret, res, g):
    q, k, v, mask, tiles, o, lse = res
    # the score tile lies [keys, queries] in the backward: the mask
    # turned once an op, and its summary by (Q chunk, K block)
    dq, dk, dv = _flash_bwd(
        q, k, v, o, lse, g[0], num_heads, True, interpret,
        num_kv_heads=num_kv_heads, mask=(jnp.swapaxes(mask, 1, 2), tiles))
    return dq, dk, dv, None, None


_flash_masked.defvjp(_flash_masked_fwd, _flash_masked_bwd)


def flash_attention_masked(q, k, v, mask, num_heads: int, num_kv_heads=None,
                           counts=None):
    """(o [B, S, H*D], lse [B, H, 1, S]) of causal attention over the
    pairs ``mask`` [B, S, S] (int8, 0 or 1, no pair the causal rule
    hides) keeps. The mask gets no gradient and lse's cotangent is not
    read (the indexer's loss takes it detached). ``counts``:
    `index_select`'s, for the per-tile summaries (`mask_tiles_any`)."""
    assert masked_flash_legal(q.shape[1], num_heads,
                              q.shape[2] // num_heads), q.shape
    interpret = pallas_mode() == "interpret"
    if num_kv_heads in (None, num_heads):
        return _flash_masked(q, k, v, mask, counts, num_heads, None,
                             interpret)
    return _flash_masked(q, k.astype(jnp.float32), v.astype(jnp.float32),
                         mask, counts, num_heads, num_kv_heads, interpret)


def index_blocks(s: int):
    """(query rows a grid step of `index_select`, keys a chunk of its
    loops): the largest of 256 / 128 rows and 512 / 256 / 128 keys that
    divide S (the published kernel tiles by 512)."""
    rows = 256 if s % 256 == 0 else 128
    keys = next(c for c in (512, 256, 128) if s % c == 0)
    return rows, keys


def _sortable(x):
    """float32 -> int32 of the same order (-0.0 first made +0.0)."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _unsortable(key):
    return jax.lax.bitcast_convert_type(
        jnp.where(key < 0, key ^ jnp.int32(0x7FFFFFFF), key), jnp.float32)


_INT_MIN = -2 ** 31


BF16_3X = "bf16_3x"     # float32 operands as three bfloat16 products


def _split(x):
    """A float32 tile as (high, low) bfloat16 parts: x = high + low to
    2^-17 of its size."""
    high = x.astype(jnp.bfloat16)
    return high, (x - high.astype(jnp.float32)).astype(jnp.bfloat16)


def _index_tiles(q, heads: int, precision):
    """The left-hand operands of `_index_products`, a tuple of [rows,
    128] tiles a head, from q [rows, heads * 64], two heads a 128-lane
    block. They depend on the query block alone, so `index_select` forms
    them once a grid step and not once a chunk of keys. One tile, the
    head's lanes with the other head's zeroed (``_only_head``), but under
    ``BF16_3X`` two (PR 55): float32 q split into bfloat16 high and low
    parts, the head's high part in its own 64 lanes with its LOW part in
    the other head's ([h0 | l0], [l1 | h1]: one rotation of the block's
    low parts by half a block, then each half kept by ``_only_head``),
    then the high part alone."""
    tiles = []
    for blk in range(heads // 2):
        x = q[:, blk * LANES:(blk + 1) * LANES]
        if precision != BF16_3X:
            tiles += [(_only_head(x, h, INDEX_LANES),) for h in range(2)]
            continue
        high = x.astype(jnp.bfloat16).astype(jnp.float32)
        swapped = _lane_roll(x - high, INDEX_LANES)         # [l1 | l0]
        for h in range(2):
            alone = _only_head(high, h, INDEX_LANES)
            both = alone + _only_head(swapped, 1 - h, INDEX_LANES)
            tiles.append((both.astype(jnp.bfloat16),
                          alone.astype(jnp.bfloat16)))
    return tiles


def _index_products(tiles, kk, w, precision):
    """I [rows, keys] float32 = sum_j w[:, j] relu(q_j . k) from
    `_index_tiles`' operands, against the ONE key laid twice side by
    side kk [keys, 128]. ``precision``: None (the operands as they come,
    one MXU pass), `Precision.HIGHEST` (float32 operands, the compiler's
    six passes: no program takes it; `scripts/sparse_lab.py` and the
    tests compare against it) or ``BF16_3X`` (float32 operands as
    bfloat16 high and low parts, high . high + low . high + high . low:
    XLA's `Precision.HIGH`, which Mosaic's dot does not take; 2^-16 of a
    product's size). A head of 64 leaves half of a 128-deep contraction
    zeroed, so the three products are TWO passes: [high | low] against
    the key's high part twice adds the first two up in the MXU's float32
    accumulator, and the high part alone meets the key's low part."""
    split = precision == BF16_3X
    if split:
        kk, kk_low = _split(kk)
        precision = None

    def dot(a, b):
        return jax.lax.dot_general(a, b, _NT, precision=precision,
                                   preferred_element_type=jnp.float32)

    acc = None
    for j, tile in enumerate(tiles):
        s = dot(tile[0], kk)
        if split:
            s = s + dot(tile[1], kk_low)
        term = w[:, j:j + 1] * jnp.maximum(s, 0.0)
        acc = term if acc is None else acc + term
    return acc


def _index_scores(q, kk, w, heads: int, precision):
    """`_index_products` of `_index_tiles`: a tile's index scores where
    the left-hand operands are used once (`index_kl`)."""
    return _index_products(_index_tiles(q, heads, precision), kk, w,
                           precision)


def _index_select_kernel(q_ref, k_ref, w_ref, mask_ref, lse_ref, count_ref,
                         key_ref, lhs_ref, *, topk: int, heads: int,
                         rows: int, keys: int, precision):
    """One (batch row, block of ``rows`` queries) grid cell: the block's
    index scores (their left-hand tiles laid once in ``lhs_ref``,
    `_index_tiles`) against every chunk of ``keys`` keys up to its own
    positions, kept in VMEM as sortable integers (``key_ref`` [rows, S];
    a pair the causal rule hides holds the smallest); then a row's
    min(t + 1, topk)-th largest by bisection over the 32 bits (a pass
    over the row's chunks a bit: a compare and a count), the ties at
    that value to the lower key by a second bisection over positions
    (run only where some row of the block has more ties than places);
    then the mask's rows, the log-sum-exp of the kept scores and the
    pairs kept a chunk (``count_ref`` [1, 1, 1, S / keys]: what the
    flash kernels' per-tile summaries add up from, so that no pass over
    the mask forms them)."""
    i = pl.program_id(1)
    q0 = i * rows
    s = k_ref.shape[1]
    chunks = (q0 + rows + keys - 1) // keys      # that hold a causal key
    w = w_ref[0]
    t = q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    want = jnp.minimum(t + 1, topk)
    for j, head in enumerate(_index_tiles(q_ref[0], heads, precision)):
        for n, tile in enumerate(head):
            lhs_ref[j, n] = tile

    def score(c, top):
        k0 = pl.multiple_of(c * keys, keys)
        val = _index_products(
            [[lhs_ref[j, n] for n in range(lhs_ref.shape[1])]
             for j in range(heads)], k_ref[0, pl.ds(k0, keys), :], w,
            precision)
        pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
        key_ref[:, pl.ds(k0, keys)] = jnp.where(pos <= t, _sortable(val),
                                                _INT_MIN)
        return jnp.maximum(top, jnp.max(
            jnp.where(pos <= t, val, _MASKED), axis=1, keepdims=True))

    # a row's largest score (always kept): the shift of its exponentials
    top = jax.lax.fori_loop(0, chunks, score,
                            jnp.full((rows, 1), _MASKED, jnp.float32))

    def count(pred):
        """[rows, 1]: the row's keys of the causal chunks for which
        ``pred(keys, positions)`` holds; lane blocks add up elementwise
        and ONE cross-lane sum closes the pass."""
        def body(c, part):
            k0 = pl.multiple_of(c * keys, keys)
            x = key_ref[:, pl.ds(k0, keys)]
            for u in range(keys // LANES):
                lanes = slice(u * LANES, (u + 1) * LANES)
                pos = k0 + u * LANES + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, LANES), 1)
                part = part + pred(x[:, lanes], pos).astype(jnp.int32)
            return part

        part = jax.lax.fori_loop(0, chunks, body,
                                 jnp.zeros((rows, LANES), jnp.int32))
        return jnp.sum(part, axis=1, keepdims=True)

    # the threshold: the largest value that `want` keys reach
    thr = jnp.where(count(lambda x, _: x >= 0) >= want, 0, _INT_MIN)

    def bit(n, thr):
        cand = thr | (jnp.int32(1) << (30 - n))
        return jnp.where(count(lambda x, _: x >= cand) >= want, cand, thr)

    thr = jax.lax.fori_loop(0, 31, bit, thr)
    above = count(lambda x, _: x > thr)
    ties = count(lambda x, _: x == thr)
    places = want - above       # of the ties, the lowest keys

    def lowest(_):
        """The largest P a row with count(tie and position < P) <=
        places."""
        nbits = s.bit_length()

        def step(n, p):
            cand = p + (jnp.int32(1) << (nbits - 1 - n))
            fits = count(lambda x, pos: (x == thr) & (pos < cand)) <= places
            return jnp.where(fits, cand, p)

        return jax.lax.fori_loop(0, nbits, step,
                                 jnp.zeros((rows, 1), jnp.int32))

    below = jax.lax.cond(jnp.max(ties - places) > 0, lowest,
                         lambda _: jnp.full((rows, 1), s, jnp.int32), None)

    def kept(c):
        k0 = pl.multiple_of(c * keys, keys)
        x = key_ref[:, pl.ds(k0, keys)]
        pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
        return k0, x, (x > thr) | ((x == thr) & (pos < below))

    chunk_of = jax.lax.broadcasted_iota(jnp.int32, count_ref.shape[2:], 1)

    def write(c, carry):
        total, counts = carry
        k0, x, sel = kept(c)
        mask_ref[0, :, pl.ds(k0, keys)] = sel.astype(jnp.int8)
        counts = jnp.where(chunk_of == c, jnp.sum(sel.astype(jnp.int32)),
                           counts)
        return total + jnp.sum(jnp.where(
            sel, jnp.exp(_unsortable(x) - top), 0.0), axis=1,
            keepdims=True), counts

    total, counts = jax.lax.fori_loop(
        0, chunks, write, (jnp.zeros((rows, 1), jnp.float32),
                           jnp.zeros(count_ref.shape[2:], jnp.int32)))
    count_ref[0, 0] = counts

    def blank(c, _):
        mask_ref[0, :, pl.ds(pl.multiple_of(c * keys, keys), keys)] = (
            jnp.zeros((rows, keys), jnp.int8))

    jax.lax.fori_loop(chunks, s // keys, blank, None)
    lse_ref[0] = top + jnp.log(total)


def _key_twice(k):
    """The one index key [B, S, 64] laid twice side by side."""
    return jnp.concatenate([k, k], axis=-1)


def index_select(q, k, w, topk: int, precision=None):
    """(mask [B, S, S] int8, lse [B, S, 1] float32, counts [B, S / rows,
    S / keys] int32 of `index_blocks`' tiles): for query t the
    min(t + 1, topk) keys s <= t of largest I_ts = sum_j w_tj relu(q_tj .
    k_s), ties to the lower s (`lax.top_k`'s set, exactly), the
    log-sum-exp of the kept scores and the pairs kept a tile. q [B, S,
    heads * 64], k [B, S, 64] (the dtype they come in is the products'
    operand dtype; ``precision`` for float32 operands: ``BF16_3X``,
    three bfloat16 products in two MXU passes, or HIGHEST,
    `_index_products`), w [B, S, heads] float32 with every scale folded
    in. No score leaves VMEM; beside the [rows, S] integers a grid step
    holds the products' left-hand tiles (`_index_tiles`: 2 MiB at 16
    heads, 1 MiB in bfloat16 operands)."""
    b, s, width = q.shape
    heads = width // INDEX_LANES
    assert k.shape[-1] == INDEX_LANES and heads % 2 == 0, (q.shape, k.shape)
    rows, keys = index_blocks(s)
    split = precision == BF16_3X        # `_index_tiles`: two a head
    mask, lse, counts = pl.pallas_call(
        functools.partial(_index_select_kernel, topk=topk, heads=heads,
                          rows=rows, keys=keys, precision=precision),
        name="index_select",
        out_shape=(jax.ShapeDtypeStruct((b, s, s), jnp.int8),
                   jax.ShapeDtypeStruct((b, s, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, s // rows, 1, s // keys),
                                        jnp.int32)),
        grid=(b, s // rows),
        in_specs=[pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, s, LANES), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, rows, heads), lambda b, i: (b, i, 0))],
        out_specs=(pl.BlockSpec((1, rows, s), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, rows, 1), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, 1, 1, s // keys),
                                lambda b, i: (b, i, 0, 0))),
        scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32),
                        pltpu.VMEM((heads, 1 + split, rows, LANES),
                                   jnp.bfloat16 if split else q.dtype)],
        interpret=pallas_mode() == "interpret",
        compiler_params=_FLASH_COMPILER_PARAMS,
    )(q, _key_twice(k), w.astype(jnp.float32))
    return mask, lse, counts[:, :, 0, :]


def index_kl_blocks(s: int):
    """(query rows, keys) of a tile of `index_kl`."""
    rows = next(r for r in (1024, 512, 256, 128) if s % r == 0)
    return rows, 256 if s % 256 == 0 else 128


def _index_kl_kernel(qi_ref, ki_ref, w_ref, lsei_ref, mask_ref, qm_ref,
                     km_ref, lsem_ref, kl_ref, dq_ref, dw_ref, dk_ref, *,
                     heads: int, main_heads: int, rep: int, rows: int,
                     keys: int, scale: float, weight: float):
    """One (batch row, block of ``rows`` queries, block of ``keys``
    keys) grid cell, the keys innermost; a tile past the diagonal does
    nothing. Recomputes the tile's index scores I and the main heads'
    probabilities A (from q, k and the saved log-sum-exps), p = sum_g A
    / heads over the kept pairs, and adds up: a row's KL(p || softmax of
    I over its kept keys), and with dI = (softmax(I) - p) * ``weight`` on
    the kept pairs the gradients of I's three operands: dq_j = (dI w_j
    [q_j . k > 0]) k, dw_j = sum dI relu(q_j . k), dk = sum_j (...)^T
    q_j. dq, dw and the KL are the query block's, resident across its
    keys; dk leaves as a PART a (query block, key block), [128] lanes
    the two heads' sums side by side, for XLA to add up."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        kl_ref[0] = jnp.zeros(kl_ref.shape[1:], jnp.float32)
        dq_ref[0] = jnp.zeros(dq_ref.shape[1:], jnp.float32)
        dw_ref[0] = jnp.zeros(dw_ref.shape[1:], jnp.float32)

    @pl.when(j * keys < (i + 1) * rows)
    def _tile():
        q, kk, w = qi_ref[0], ki_ref[0], w_ref[0]
        sel = mask_ref[0].astype(jnp.int32) != 0
        val = _index_scores(q, kk, w, heads, None)
        logq = val - lsei_ref[0]                 # log softmax over the kept
        km = km_ref[0].astype(qm_ref.dtype)
        lsem = lsem_ref[0]
        head_sum = None
        for g in range(main_heads):
            own = slice((g // rep) * LANES, (g // rep + 1) * LANES)
            st = _dot(qm_ref[0, :, g * LANES:(g + 1) * LANES], km[:, own],
                      _NT)
            a = jnp.exp(st * scale - lsem[:, g:g + 1])
            head_sum = a if head_sum is None else head_sum + a
        p = jnp.where(sel, head_sum * (1.0 / main_heads), 0.0)
        kl_ref[0] += jnp.sum(
            jnp.where(p > 0, p * (jnp.log(jnp.maximum(p, 1e-37)) - logq),
                      0.0), axis=1, keepdims=True)
        d_val = jnp.where(sel, (jnp.exp(logq) - p) * weight, 0.0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, heads), 1)
        dw = jnp.zeros((rows, heads), jnp.float32)
        dk = jnp.zeros((keys, LANES), jnp.float32)
        for blk in range(heads // 2):
            lanes = slice(blk * LANES, (blk + 1) * LANES)
            dq = None
            for h in range(2):
                n = 2 * blk + h
                qh = _only_head(q[:, lanes], h, INDEX_LANES)
                st = _dot(qh, kk, _NT)
                dw = dw + jnp.where(lane == n, jnp.sum(
                    d_val * jnp.maximum(st, 0.0), axis=1, keepdims=True),
                    0.0)
                gate = (jnp.where(st > 0, d_val, 0.0) * w[:, n:n + 1]
                        ).astype(q.dtype)
                part = _only_head(_dot(gate, kk, _NN), h, INDEX_LANES)
                dq = part if dq is None else dq + part
                dk = dk + jax.lax.dot_general(
                    gate, qh, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            dq_ref[0, :, lanes] += dq
        dw_ref[0] += dw
        dk_ref[0, 0] = dk


def index_kl(qi, ki, w, lse_i, mask, qm, km, lse_m, num_heads: int,
             weight: float):
    """(kl [B, S, 1], dq [B, S, heads * 64], dw [B, S, heads], dk
    [B, S, 64]), float32: the indexer's loss a query row and the
    gradient of ``weight`` times its sum to the indexer's operands
    (``_index_kl_kernel``). qi, ki, w as `index_select` took them (in
    the dtype the products are to take), lse_i its second result; qm
    [B, S, H * 128], km [B, S, Hk * 128] the main attention's rotated
    queries and keys, lse_m [B, S, H] its log-sum-exps."""
    b, s, width = qi.shape
    heads = width // INDEX_LANES
    rows, keys = index_kl_blocks(s)
    nq, nk = s // rows, s // keys
    scale = 1.0 / float(LANES) ** 0.5

    def last(i):        # the last key block that holds a causal pair
        return ((i + 1) * rows - 1) // keys

    def by_rows(width):
        return pl.BlockSpec((1, rows, width), lambda b, i, j: (b, i, 0))

    def by_keys(width):
        return pl.BlockSpec((1, keys, width),
                            lambda b, i, j: (b, jnp.minimum(j, last(i)), 0))

    kl, dq, dw, parts = pl.pallas_call(
        functools.partial(_index_kl_kernel, heads=heads,
                          main_heads=num_heads,
                          rep=num_heads * LANES // km.shape[-1], rows=rows,
                          keys=keys,
                          scale=scale, weight=weight),
        name="index_kl",
        out_shape=(jax.ShapeDtypeStruct((b, s, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, width), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, heads), jnp.float32),
                   jax.ShapeDtypeStruct((b, nq, s, LANES), jnp.float32)),
        grid=(b, nq, nk),
        in_specs=[by_rows(width), by_keys(LANES), by_rows(heads),
                  by_rows(1),
                  pl.BlockSpec((1, rows, keys), lambda b, i, j: (
                      b, i, jnp.minimum(j, last(i)))),
                  by_rows(num_heads * LANES), by_keys(km.shape[-1]),
                  by_rows(num_heads)],
        out_specs=(by_rows(1), by_rows(width), by_rows(heads),
                   pl.BlockSpec((1, 1, keys, LANES), lambda b, i, j: (
                       b, i, jnp.minimum(j, last(i)), 0))),
        interpret=pallas_mode() == "interpret",
        compiler_params=_FLASH_COMPILER_PARAMS,
    )(qi, _key_twice(ki), w.astype(jnp.float32), lse_i, mask, qm, km, lse_m)
    # a part past a query block's diagonal was never written
    held = (jnp.arange(nk)[None, :] <= last(jnp.arange(nq))[:, None])
    held = jnp.repeat(held, keys, axis=1)[None, :, :, None]
    dk = jnp.sum(jnp.where(held, parts, 0.0), axis=1)
    return kl, dq, dw, dk[..., :INDEX_LANES] + dk[..., INDEX_LANES:]


# ---------------------------------------------------------------------------
# the expert layer's sum of rows into their tokens (PR 37): the one place
# outside attention where XLA's lowering (a gather a (token, slot) pair,
# held here or not) lost to a kernel by a factor (ops/moe.py
# `tokens_from_rows`), and that sum's transpose (PR 49, `combine_rows`'
# backward), over the same grid.

SUM_TOKENS = 128  # tokens a tile of `moe_sum_rows`: one MXU pass high
SUM_ROWS = 128    # token-ordered rows a block: the product's contraction
# widest row whose blocks (rows in, float32 accumulator, output twice)
# stay inside the compiler's default 16 MiB of scoped VMEM
MAX_SUM_WIDTH = 4096


def sum_rows_blocks_legal(rows: int, width: int) -> bool:
    """Whole blocks of rows, rows that fill the lanes: the operand
    `moe_sum_rows` reads without padding anything."""
    return (rows % SUM_ROWS == 0 and width % LANES == 0
            and width <= MAX_SUM_WIDTH)


def moe_sum_rows_shape_legal(rows: int, width: int, tokens: int) -> bool:
    """`sum_rows_blocks_legal` and whole tiles of tokens: what
    `moe_sum_rows` AND its transpose `moe_spread_rows` take. The sum
    alone also takes a last tile short of SUM_TOKENS (its docstring)."""
    return sum_rows_blocks_legal(rows, width) and tokens % SUM_TOKENS == 0


def sum_rows_tile_starts(token, tokens: int):
    """token [rows] int32 ascending -> [tiles + 1] int32: the place at
    which the run of each tile of SUM_TOKENS tokens starts (the last
    entry: where the rows of a token below `tokens` end); the last tile
    may be short."""
    bounds = jnp.minimum(jnp.arange(-(-tokens // SUM_TOKENS) + 1,
                                    dtype=jnp.int32) * SUM_TOKENS, tokens)
    return jnp.searchsorted(token, bounds, side="left",
                            method="compare_all").astype(jnp.int32)


def moe_sum_rows_items(tile_start, rows: int):
    """The grid of `moe_sum_rows`: one item a (token tile, block of
    token-ordered rows) that meet, tile by tile.

    tile_start [tiles + 1] int32, the place in token order at which each
    tile's run of rows starts (the last entry: where the rows that hold a
    pair end) -> dict of `tile`, `block` [tiles + rows / SUM_ROWS] int32
    and `count` [1] int32, the items that are real. A run may be any
    length: an empty one still gets one item (the tile has to be written,
    with zeros), one of SUM_TOKENS * k rows as many as it spans. Two
    neighbours share the block in which one ends and the other starts, so
    tiles + blocks items are enough; those past `count` repeat the last
    one's tile and block, and the kernel passes over them. `every_block`
    is `block` for the kernel that WRITES the blocks (`moe_spread_rows`):
    past `count` it walks on, a block an item, through the blocks no run
    reaches (rows that hold no pair), which have to be written too."""
    blocks = -(-rows // SUM_ROWS)
    start, end = tile_start[:-1], tile_start[1:]
    first = jnp.minimum(start // SUM_ROWS, blocks - 1)
    count = jnp.maximum(-(-end // SUM_ROWS) - first, 1)
    ends = jnp.cumsum(count)
    item = jnp.arange(start.shape[0] + blocks, dtype=jnp.int32)
    tile = jnp.minimum(
        jnp.searchsorted(ends, item, side="right", method="compare_all"),
        start.shape[0] - 1).astype(jnp.int32)
    block = first[tile] + jnp.minimum(item - (ends - count)[tile],
                                      count[tile] - 1)
    every_block = jnp.minimum(block + jnp.maximum(item - (ends[-1] - 1), 0),
                              blocks - 1)
    return dict(tile=tile, block=block.astype(jnp.int32),
                every_block=every_block.astype(jnp.int32),
                count=ends[-1:].astype(jnp.int32))


def _bf16_pieces(x, n: int):
    """float32 x as a sum of n bfloat16 arrays (3 hold all 24 bits)."""
    parts = []
    for _ in range(n):
        parts.append(x.astype(jnp.bfloat16))
        x = x - parts[-1].astype(jnp.float32)
    return parts


def _moe_sum_rows_kernel(tile_ref, block_ref, count_ref, token_ref, *refs,
                         weighted: bool):
    """One item: add the rows of this block that belong to this tile's
    tokens into the tile's accumulator, by a [tokens, rows] matrix on the
    MXU that holds a row's weight (or 1) where the row is the token's and
    0 elsewhere. bfloat16 rows are exact MXU operands, and a float32
    weight goes in as three bfloat16 pieces, each product accumulated in
    float32: w * row to float32's rounding, nothing rounded to bfloat16.
    float32 rows take the MXU's float32 passes."""
    if weighted:
        weight_ref, x_ref, o_ref, acc_ref = refs
    else:
        x_ref, o_ref, acc_ref = refs
    n = pl.program_id(0)
    tile = tile_ref[n]

    @pl.when((n == 0) | (tile_ref[jnp.maximum(n - 1, 0)] != tile))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n < count_ref[0])
    def _():
        local = token_ref[0] - tile * SUM_TOKENS                 # [1, rows]
        mine = jax.lax.broadcasted_iota(
            jnp.int32, (SUM_TOKENS, SUM_ROWS), 0) == local
        x = x_ref[...]
        if weighted:
            hot = jnp.where(mine, weight_ref[0], 0.0)
        else:
            hot = mine.astype(jnp.float32)
        if x.dtype == jnp.bfloat16:
            acc = acc_ref[...]
            for piece in _bf16_pieces(hot, 3 if weighted else 1):
                acc = acc + jax.lax.dot_general(
                    piece, x, _NN, preferred_element_type=jnp.float32)
            acc_ref[...] = acc
        else:
            acc_ref[...] += jax.lax.dot_general(
                hot, x.astype(jnp.float32), _NN,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)

    last = pl.num_programs(0) - 1

    @pl.when((n == last) | (tile_ref[jnp.minimum(n + 1, last)] != tile))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_sum_rows(x, token, weight, items, tokens: int, dtype,
                 interpret: bool, name: str = "moe_sum_rows"):
    """out[t] = sum of weight[i] * x[i] over the rows i with token[i] == t.

    x [rows, d] the rows IN TOKEN ORDER, token [rows] int32 ascending
    (`tokens` for a row that holds nothing: it matches no tile), weight
    [rows] float32 or None for 1, `items` what `moe_sum_rows_items` made
    of the tiles' starts -> [tokens, d] in `dtype`: products and sums in
    float32, rounded once. Every row is read once, and the block two
    tiles share twice; the cost follows `rows`, whatever share of the
    tokens' pairs they are. `moe_sum_rows_shape_legal` says which shapes;
    `tokens` may also end inside a tile (`sum_rows_blocks_legal` alone:
    the embedding's table, PR 57): the last output block is then ragged,
    written as far as the output goes, and `items` has that tile as any
    other (`ceil(tokens / SUM_TOKENS)` tiles). `name` is the kernel's in
    the device trace, for a caller that is not the expert layer.
    A row's 0 in another token's sum is a product, not a select: a row
    that is not finite reaches its whole tile."""
    rows, d = x.shape
    blocks = rows // SUM_ROWS

    def lanes(v):
        return v.reshape(blocks, 1, SUM_ROWS)

    of_block = pl.BlockSpec((1, 1, SUM_ROWS),
                            lambda n, tile, block, count: (block[n], 0, 0))
    operands = [lanes(token)] + ([] if weight is None else [lanes(weight)])
    return pl.pallas_call(
        functools.partial(_moe_sum_rows_kernel, weighted=weight is not None),
        name=name,
        out_shape=jax.ShapeDtypeStruct((tokens, d), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(items["tile"].shape[0],),
            in_specs=[of_block] * len(operands) + [pl.BlockSpec(
                (SUM_ROWS, d), lambda n, tile, block, count: (block[n], 0))],
            out_specs=pl.BlockSpec(
                (SUM_TOKENS, d), lambda n, tile, block, count: (tile[n], 0)),
            scratch_shapes=[pltpu.VMEM((SUM_TOKENS, d), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(items["tile"], items["block"], items["count"], *operands, x)


def _spread_chunk(width: int) -> int:
    """Lanes of a row that `moe_spread_rows` takes at a time: the widest
    of these that divides the row (a [128, 512] float32 is the vector
    registers' whole file)."""
    return next(c for c in (512, 384, 256, 128) if width % c == 0)


def _moe_spread_rows_kernel(tile_ref, _, block_ref, count_ref, token_ref,
                            slot_ref, weight_ref, dy_ref, x_ref, dx_ref,
                            dw_ref, dx_acc, dw_acc, *, k: int):
    """One item of `moe_sum_rows`' grid, the product the other way round:
    the [rows, tokens] 0/1 matrix of this block's rows and this tile's
    tokens times the tile's dY gives each row of the tile its token's dY
    (three bfloat16 pieces of it, each picked by a 1 and added in
    float32: the float32 value to the bit) and the other rows of the
    block exact zeros, so a block two tiles share accumulates. On that
    [rows, lanes] tile, a chunk of lanes at a time: w * dY into the
    block's accumulator and <dY, x> summed over the lanes; the latter,
    spread over [slots, tokens] by the same 0/1 matrix and the rows'
    slots, into the tile's `d weights`. `block_ref` is the items'
    `every_block` (what is written); their `block`, unnamed here, only
    steers what is fetched."""
    n = pl.program_id(0)
    last = pl.num_programs(0) - 1
    tile, block = tile_ref[n], block_ref[n]
    before, after = jnp.maximum(n - 1, 0), jnp.minimum(n + 1, last)

    @pl.when((n == 0) | (block_ref[before] != block))
    def _():
        dx_acc[...] = jnp.zeros_like(dx_acc)

    @pl.when((n == 0) | (tile_ref[before] != tile))
    def _():
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(n < count_ref[0])
    def _():
        square = (SUM_ROWS, SUM_TOKENS)
        lane = jax.lax.broadcasted_iota(jnp.int32, square, 1)
        diagonal = jax.lax.broadcasted_iota(jnp.int32, square, 0) == lane

        def column(v):      # [1, rows] along the lanes -> [rows, 1]
            return jnp.sum(jnp.where(diagonal, v, 0), axis=1, keepdims=True)

        mine = column(token_ref[0]) - tile * SUM_TOKENS == lane
        hot = mine.astype(jnp.bfloat16)                   # [rows, tokens]
        w = column(weight_ref[0])
        d_w = jnp.zeros((SUM_ROWS, 1), jnp.float32)
        chunk = _spread_chunk(dy_ref.shape[1])
        for at in range(0, dy_ref.shape[1], chunk):
            lanes = slice(at, at + chunk)
            own = None
            for piece in _bf16_pieces(dy_ref[:, lanes], 3):
                part = jax.lax.dot_general(
                    hot, piece, _NN, preferred_element_type=jnp.float32)
                own = part if own is None else own + part
            d_w = d_w + jnp.sum(own * x_ref[:, lanes].astype(jnp.float32),
                                axis=1, keepdims=True)
            dx_acc[:, lanes] += w * own
        # a (token, slot) pair has at most one row: sums of one term
        of_token = jnp.where(mine, d_w, 0.0)              # [rows, tokens]
        slot = column(slot_ref[0])
        at_slot = jax.lax.broadcasted_iota(jnp.int32, dw_acc.shape, 0)
        d_weights = dw_acc[...]
        for j in range(k):
            d_weights = d_weights + jnp.where(
                at_slot == j,
                jnp.sum(jnp.where(slot == j, of_token, 0.0), axis=0,
                        keepdims=True), 0.0)
        dw_acc[...] = d_weights

    @pl.when((n == last) | (block_ref[after] != block))
    def _():
        dx_ref[...] = dx_acc[...].astype(dx_ref.dtype)

    @pl.when((n == last) | (tile_ref[after] != tile))
    def _():
        dw_ref[0] = dw_acc[...]


def moe_spread_rows(d_y, x, token, slot, weight, items, k: int,
                    interpret: bool):
    """The transpose of `moe_sum_rows` (weighted), for the rows IN TOKEN
    ORDER: of out[t] = sum of weight[i] * x[i] over the rows i of token t,

        d x[i]               = weight[i] * d_y[token[i]]
        d weights[t, slot[i]] = <d_y[t], x[i]>   (t = token[i])

    d_y [tokens, d] float32, x [rows, d], token / slot / weight [rows]
    (`token` ascending, `tokens` for a row that holds nothing; `slot` in
    [0, k)), `items` the grid `moe_sum_rows_items` made -> (d x [rows, d]
    in x's dtype, rounded once; d weights [tokens, k] float32, 0 for a
    (token, slot) no row holds). d_y and x are read once and d x written
    once (a block two tiles share, its tiles' d_y twice): d_y's rows
    never exist in memory, and no cost follows the tokens * k pairs. Rows
    that hold nothing come out 0. As in `moe_sum_rows`, a 0 is a product:
    a d_y row that is not finite reaches the rows of its tile's blocks."""
    rows, d = x.shape
    tokens = d_y.shape[0]
    blocks, tiles = rows // SUM_ROWS, tokens // SUM_TOKENS
    slots = -(-k // 8) * 8          # whole sublanes

    def lanes(v):
        return v.reshape(blocks, 1, SUM_ROWS)

    def at(which, *rest):
        """The block index a prefetched map gives an item: 0 `tile`, 1
        `block` (what is read: nothing is fetched past `count`), 2
        `every_block` (what is written)."""
        return lambda n, *maps: (maps[which][n], *rest)

    of_block = pl.BlockSpec((1, 1, SUM_ROWS), at(1, 0, 0))
    d_x, d_w = pl.pallas_call(
        functools.partial(_moe_spread_rows_kernel, k=k),
        name="moe_spread_rows",
        out_shape=(jax.ShapeDtypeStruct((rows, d), x.dtype),
                   jax.ShapeDtypeStruct((tiles, slots, SUM_TOKENS),
                                        jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(items["tile"].shape[0],),
            in_specs=[of_block] * 3 + [
                pl.BlockSpec((SUM_TOKENS, d), at(0, 0)),
                pl.BlockSpec((SUM_ROWS, d), at(1, 0))],
            out_specs=(pl.BlockSpec((SUM_ROWS, d), at(2, 0)),
                       pl.BlockSpec((1, slots, SUM_TOKENS), at(0, 0, 0))),
            scratch_shapes=[pltpu.VMEM((SUM_ROWS, d), jnp.float32),
                            pltpu.VMEM((slots, SUM_TOKENS), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(items["tile"], items["block"], items["every_block"], items["count"],
      lanes(token), lanes(slot), lanes(weight), d_y, x)
    return d_x, d_w.swapaxes(1, 2).reshape(tokens, slots)[:, :k]


# ---------------------------------------------------------------------------
# the per-head pass between a projection and a flash kernel (PR 42): the
# heads' RMS norm and rotary on the projection's float32 [B, S, H*128]
# (PR 47: or [B, S, H*64]) result as it lies. Through a [B, S, H, D] view
# XLA tiles (H, D), and
# every crossing between that and the [B, S, H*D] the products write and
# the kernels take is a copy of a float32 array of S x H*D elements.

ROTARY_ROWS = 512   # rows a block of `rotary_lanes`, where S allows
ROTARY_HEADS = 4    # heads (128-lane columns) a block, where H allows


def rotary_lanes_shape_legal(seq_len: int, head_dim: int,
                             num_heads: int = 1) -> bool:
    """What `rotary_lanes` takes: heads that are whole 128-lane columns,
    one of 128 or (PR 47) two of 64 side by side, so an even number of
    those, and whole blocks of rows (the flash kernels' tile)."""
    return (head_dim in (LANES, LANES // 2)
            and num_heads * head_dim % LANES == 0
            and seq_len > 0 and seq_len % BLK_Q == 0)


def _rotary_block(s: int, heads: int):
    """(rows, heads) of a block: the most up to ROTARY_ROWS x
    ROTARY_HEADS that divide the operand."""
    rows = next(r for r in (ROTARY_ROWS, 256, BLK_Q) if s % r == 0)
    return rows, next(h for h in range(ROTARY_HEADS, 0, -1)
                      if heads % h == 0)


def _partner(x, half: int, head_dim: int = LANES):
    """x [rows, 128], one head of 128 or two of 64: lane j < half OF ITS
    HEAD gets x[j + half], lane half <= j < 2 half gets x[j - half] (the
    sign is the sine table's); past the rotated lanes what comes meets a
    sine of 0. Neither roll leaves the head on a lane that takes it."""
    if 2 * half == LANES:
        return pltpu.roll(x, half, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    if head_dim < LANES:
        lane = lane % head_dim
    return jnp.where(lane < half, pltpu.roll(x, LANES - half, 1),
                     pltpu.roll(x, half, 1))


def _head_mean(x, head_dim: int):
    """The mean of x [rows, 128] over a head's lanes, float32: [rows, 1]
    for one head of 128; for two heads of 64 two masked sums, each laid
    over its own head's lanes, [rows, 128]."""
    if head_dim == LANES:
        return jnp.mean(x, axis=-1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    first = lane < head_dim
    sums = [jnp.sum(jnp.where(mine, x, 0.0), axis=-1, keepdims=True)
            for mine in (first, lane >= head_dim)]
    return jnp.where(first, *sums) / head_dim


def _rotary_lanes_kernel(*refs, heads: int, half: int, transposed: bool,
                         eps, head_dim: int = LANES):
    """One block of rows of ``heads`` 128-lane columns, each one head of
    128 or two of 64 (``head_dim``; the tables and the scale are then
    one head's laid twice, the partner lane and the norm's mean stay
    inside a 64-lane half, and d scale's two halves are added by the
    caller). Forward: y = n cos +
    partner(n) sin, n = x, or with ``eps`` the head's RMS norm x
    rsqrt(mean(x^2) + eps) scale; float32, rounded once into the output.
    ``transposed``: the backward. dn = g cos - partner(g) sin (the two
    halves of a table are equal bit for bit and the sine's sign is the
    partner's, so this is autodiff's sum term for term); with the norm
    dx = r u - x r^3 mean(u x), u = dn scale, and the block's rows of
    d scale = sum dn x r added into the one output block every step
    revisits."""
    normed = eps is not None
    # a: what is rotated, x forward and the cotangent backward
    if transposed and normed:
        x_ref, a_ref, cos_ref, sin_ref, scale_ref, o_ref, dscale_ref = refs

        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0)
                 & (pl.program_id(2) == 0))
        def _():
            dscale_ref[...] = jnp.zeros_like(dscale_ref)
    elif normed:
        a_ref, cos_ref, sin_ref, scale_ref, o_ref = refs
    else:
        a_ref, cos_ref, sin_ref, o_ref = refs
    cos, sin = cos_ref[...], sin_ref[...]
    for n in range(heads):
        lanes = slice(n * LANES, (n + 1) * LANES)
        a = a_ref[0, :, lanes].astype(jnp.float32)
        if not transposed:
            if normed:
                a = a * jax.lax.rsqrt(_head_mean(a * a, head_dim)
                                      + eps) * scale_ref[...]
            o_ref[0, :, lanes] = (a * cos + _partner(a, half, head_dim) * sin
                                  ).astype(o_ref.dtype)
            continue
        dn = a * cos - _partner(a, half, head_dim) * sin
        if normed:
            x = x_ref[0, :, lanes]
            r = jax.lax.rsqrt(_head_mean(x * x, head_dim) + eps)
            u = dn * scale_ref[...]
            dscale_ref[...] += (dn * x * r).reshape(
                -1, 8, LANES).sum(axis=0)
            dn = r * u - x * (r * r * r * _head_mean(u * x, head_dim))
        o_ref[0, :, lanes] = dn.astype(o_ref.dtype)


def _rotary_lanes_call(operands, cos, sin, scale, half, eps, dtype,
                       interpret, transposed, block=None):
    """`_rotary_lanes_kernel` over ``operands`` (x, or x and g) [B, S,
    H*D]: grid (batch, row block, column block), the column block last,
    so that a row block's tables are fetched once. ``cos``, ``sin``
    [S, D] and ``scale`` [D] are one head's, D 128 or 64: at 64 they are
    laid twice side by side here, a 128-lane column's. The operands allow
    input fusion: the cast XLA would run ahead of the call as a pass of
    its own (the flash backward's float32 dQ rounded to the cotangent's
    bfloat16: 0.76 ms a 64-head op on the v5e) rides in the kernel's
    fetch."""
    b, s, width = operands[-1].shape
    d = cos.shape[-1]
    if d < LANES:
        cos, sin = (jnp.tile(t, (1, LANES // d)) for t in (cos, sin))
    rows, heads = block or _rotary_block(s, width // LANES)
    of_block = pl.BlockSpec((1, rows, heads * LANES),
                            lambda i, r, c: (i, r, c))
    table = pl.BlockSpec((rows, LANES), lambda i, r, c: (r, 0))
    out_shape = [jax.ShapeDtypeStruct((b, s, width), dtype)]
    out_specs = [of_block]
    normed = eps is not None
    if normed:
        scale = (jnp.tile(scale.astype(jnp.float32), LANES // d)
                 if d < LANES else scale.astype(jnp.float32))
        scale = (scale.reshape(1, LANES),)
        if transposed:
            out_shape.append(jax.ShapeDtypeStruct((8, LANES), jnp.float32))
            out_specs.append(pl.BlockSpec((8, LANES), lambda i, r, c: (0, 0)))
    else:
        scale = ()
    out = pl.pallas_call(
        functools.partial(_rotary_lanes_kernel, heads=heads, half=half,
                          transposed=transposed, eps=eps, head_dim=d),
        name="rotary_lanes_bwd" if transposed else "rotary_lanes",
        out_shape=out_shape,
        grid=(b, s // rows, width // (heads * LANES)),
        in_specs=[of_block] * len(operands) + [table, table] + [
            pl.BlockSpec((1, LANES), lambda i, r, c: (0, 0))] * len(scale),
        out_specs=out_specs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(("arbitrary",) if transposed and normed
                                 else ("parallel",)) * 3,
            allow_input_fusion=[True] * len(operands)
            + [False] * (2 + len(scale))),
        interpret=interpret,
    )(*operands, cos, sin, *scale)
    return out if transposed and normed else out[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _rotary_lanes(x, cos, sin, scale, half, eps, dtype, interpret):
    return _rotary_lanes_fwd(x, cos, sin, scale, half, eps, dtype,
                             interpret)[0]


def _rotary_lanes_fwd(x, cos, sin, scale, half, eps, dtype, interpret):
    y = _rotary_lanes_call((x,), cos, sin, scale, half, eps, dtype,
                           interpret, False)
    # the plain rotation's backward reads the cotangent alone
    return y, (x if eps is not None else None, cos, sin, scale)


def _rotary_lanes_bwd(half, eps, dtype, interpret, res, g):
    x, cos, sin, scale = res
    back = _rotary_lanes_call((g,) if x is None else (x, g), cos, sin, scale,
                              half, eps, jnp.float32, interpret, True)
    if x is None:
        return back, None, None, None
    dx, dscale = back
    # the block's 8 rows, and at heads of 64 a column's two heads
    dscale = dscale.reshape(-1, scale.shape[0]).sum(axis=0)
    return dx, None, None, dscale.astype(scale.dtype)


_rotary_lanes.defvjp(_rotary_lanes_fwd, _rotary_lanes_bwd)


def rotary_lanes(x, cos, sin, half: int, dtype, norm=None):
    """The heads' RMS norm (``norm`` = (scale [D], eps), or None) and
    rotary of x [B, S, H*D] float32, the heads side by side as a
    projection's product leaves them, D 128 (a head a 128-lane column)
    or 64 (two a column, PR 47) -> the same shape in ``dtype``, what a
    flash kernel takes: one pass, float32 inside, rounded once, no
    [B, S, H, D] view. ``cos``, ``sin`` [S, D] float32, whose width says
    what D is: a row's tables
    for one head, the sine with the partner's sign (minus on the first
    ``half`` lanes), 1 and 0 on the lanes a partial rotary leaves alone;
    positions, wrapping and YaRN's factor are the tables'. Its own
    backward is the same kernel transposed: dx in float32 from the
    cotangent as it comes (and x under the norm), d scale summed over
    the rows in the kernel; no table has a gradient. Caller checks
    `rotary_lanes_shape_legal` and `pallas_mode` first."""
    scale, eps = norm or (None, None)
    return _rotary_lanes(x, cos, sin, scale, half, eps, dtype,
                         pallas_mode() == "interpret")


# ---------------------------------------------------------------------------
# the gated short convolution's pass between its two products (PR 45):
# y = C * conv(B * x), a causal depthwise convolution of K taps a lane.
# XLA writes B * x out in float32, reads it back once a tap and splits
# the backward into five fusions: 2.8 GB an op, forward and backward, at
# 16,384 x 2048 where the operands and results are 0.74 GB (deviceless
# count, scripts/conv_lab.py). Here every operand is read once and every
# result written once; the K - 1 rows a block needs of its neighbour
# come as a second, 16-row block of the same array.

CONV_ROWS = 256     # rows a block of `gated_conv_lanes`, where S allows
CONV_LANES = 512    # lanes worked through at a time inside a block
CONV_HALO = 16      # rows of the neighbouring block fetched (a bf16 tile)
MAX_CONV_TAPS = 8   # the taps' gradient is one [8, E] block
MAX_CONV_WIDTH = 4096   # E: a block holds rows x 3 E of the projection


def gated_conv_shape_legal(seq_len: int, width: int, taps: int) -> bool:
    """What `gated_conv_lanes` takes: whole blocks of rows (the flash
    kernels' tile), whole 128-lane columns a third, taps a halo's rows
    cover."""
    return (seq_len > 0 and seq_len % BLK_Q == 0 and width % LANES == 0
            and width <= MAX_CONV_WIDTH and 1 <= taps <= MAX_CONV_TAPS)


def _rows_from_before(x, before, shift: int):
    """x [rows, W] moved ``shift`` rows down: row t holds x[t - shift],
    its first ``shift`` rows the last ones of ``before`` [8, W] (the 8
    rows ahead of the block; zeros at a sample's start)."""
    if not shift:
        return x
    row = jax.lax.broadcasted_iota(jnp.int32, before.shape, 0)
    head = jnp.where(row < shift, pltpu.roll(before, shift, 0),
                     pltpu.roll(x[:8], shift, 0))
    return jnp.concatenate([head, pltpu.roll(x, shift, 0)[8:]], axis=0)


def _rows_from_after(x, after, shift: int):
    """x [rows, W] moved ``shift`` rows up: row t holds x[t + shift], its
    last ``shift`` rows the first ones of ``after`` [8, W] (the 8 rows
    past the block; zeros at a sample's end)."""
    if not shift:
        return x
    rows = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, after.shape, 0)
    tail = jnp.where(row >= 8 - shift, pltpu.roll(after, 8 - shift, 0),
                     pltpu.roll(x[rows - 8:], 8 - shift, 0))
    return jnp.concatenate([pltpu.roll(x, rows - shift, 0)[:rows - 8], tail],
                           axis=0)


def _gated_conv_kernel(*refs, width: int, taps: int, gate: bool,
                       transposed: bool):
    """One block of rows of the projection [B ; C ; x], all 3 E lanes of
    it, worked through CONV_LANES at a time; float32 inside, every
    result rounded once. Forward: u = B x (and the 8 rows ahead of the
    block, zeros in a sample's first block), conv_t = sum_j w_j
    u_{t-(K-1)+j}, y = C conv. ``transposed``: the backward, which forms
    u and conv again: dC = dy conv, e = dy C, d_t = sum_j w_j e_{t+(K-1)-j}
    (the 8 rows past the block, zeros in a sample's last), dB = d x,
    dx = d B, and the block's rows of dw_j = sum_t e_t u_{t-(K-1)+j}
    added into the one [8, E] block that every step revisits."""
    f32 = jnp.float32
    if transposed:
        p_ref, dy_ref, p0_ref, p1_ref, dy1_ref, w_ref, dp_ref, dw_ref = refs

        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)
    else:
        p_ref, p0_ref, w_ref, y_ref = refs
    r, blocks = pl.program_id(1), pl.num_programs(1)
    started = (r > 0).astype(f32)
    goes_on = (r < blocks - 1).astype(f32)
    chunk = next(n for n in (CONV_LANES, 256, LANES) if width % n == 0)
    for at in range(0, width, chunk):
        of_b, of_c, of_x = (slice(k * width + at, k * width + at + chunk)
                            for k in range(3))
        lanes = slice(at, at + chunk)
        b, x = p_ref[0, :, of_b].astype(f32), p_ref[0, :, of_x].astype(f32)
        u = b * x
        # the last 8 rows of the 16 fetched ahead of the block
        before = (p0_ref[0, :, of_b].astype(f32)
                  * p0_ref[0, :, of_x].astype(f32))[8:] * started
        moved = [_rows_from_before(u, before, taps - 1 - j)
                 for j in range(taps)]
        conv = sum(w_ref[j:j + 1, lanes] * moved[j] for j in range(taps))
        if not transposed:
            if gate:
                conv = p_ref[0, :, of_c].astype(f32) * conv
            y_ref[0, :, lanes] = conv.astype(y_ref.dtype)
            continue
        e = dy_ref[0, :, lanes].astype(f32)
        after = dy1_ref[0, :, lanes].astype(f32)[:8] * goes_on
        if gate:
            dp_ref[0, :, of_c] = (e * conv).astype(dp_ref.dtype)
            e = e * p_ref[0, :, of_c].astype(f32)
            after = after * p1_ref[0, :, of_c].astype(f32)[:8]
        else:
            dp_ref[0, :, of_c] = jnp.zeros((e.shape[0], chunk), dp_ref.dtype)
        d = sum(w_ref[j:j + 1, lanes]
                * _rows_from_after(e, after, taps - 1 - j)
                for j in range(taps))
        dp_ref[0, :, of_b] = (d * x).astype(dp_ref.dtype)
        dp_ref[0, :, of_x] = (d * b).astype(dp_ref.dtype)
        for j in range(taps):
            dw_ref[j:j + 1, lanes] += jnp.sum(e * moved[j], axis=0,
                                              keepdims=True)


def _gated_conv_call(proj, w, dy, gate, interpret):
    """`_gated_conv_kernel` over proj [B, S, 3 E] (and the cotangent
    ``dy`` [B, S, E] for the backward): grid (batch, row block); the
    K - 1 rows a block needs of its neighbour come as a second, 16-row
    block of the same array."""
    n, s, width3 = proj.shape
    width, taps = width3 // 3, w.shape[0]
    rows = next(r for r in (CONV_ROWS, BLK_Q) if s % r == 0)
    per, halos = rows // CONV_HALO, s // CONV_HALO

    def block(lanes):
        return pl.BlockSpec((1, rows, lanes), lambda i, r: (i, r, 0))

    def before(lanes):
        return pl.BlockSpec((1, CONV_HALO, lanes), lambda i, r: (
            i, jnp.maximum(r * per - 1, 0), 0))

    def after(lanes):
        return pl.BlockSpec((1, CONV_HALO, lanes), lambda i, r: (
            i, jnp.minimum((r + 1) * per, halos - 1), 0))

    tap_block = pl.BlockSpec((MAX_CONV_TAPS, width), lambda i, r: (0, 0))
    w8 = jnp.pad(w.astype(jnp.float32), ((0, MAX_CONV_TAPS - taps), (0, 0)))
    kernel = functools.partial(_gated_conv_kernel, width=width, taps=taps,
                               gate=gate, transposed=dy is not None)
    if dy is None:
        return pl.pallas_call(
            kernel, name="gated_conv",
            out_shape=jax.ShapeDtypeStruct((n, s, width), proj.dtype),
            grid=(n, s // rows),
            in_specs=[block(width3), before(width3), tap_block],
            out_specs=block(width),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=64 << 20),
            interpret=interpret)(proj, proj, w8)
    dproj, dw = pl.pallas_call(
        kernel, name="gated_conv_bwd",
        out_shape=(jax.ShapeDtypeStruct(proj.shape, proj.dtype),
                   jax.ShapeDtypeStruct((MAX_CONV_TAPS, width),
                                        jnp.float32)),
        grid=(n, s // rows),
        in_specs=[block(width3), block(width), before(width3),
                  after(width3), after(width), tap_block],
        out_specs=(block(width3), tap_block),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret)(proj, dy, proj, proj, dy, w8)
    return dproj, dw[:taps].astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gated_conv_lanes(proj, w, gate, interpret):
    return _gated_conv_call(proj, w, None, gate, interpret)


def _gated_conv_lanes_fwd(proj, w, gate, interpret):
    return _gated_conv_call(proj, w, None, gate, interpret), (proj, w)


def _gated_conv_lanes_bwd(gate, interpret, kept, dy):
    return _gated_conv_call(*kept, dy, gate, interpret)


_gated_conv_lanes.defvjp(_gated_conv_lanes_fwd, _gated_conv_lanes_bwd)


def gated_conv_lanes(proj, w, gate: bool = True):
    """y = C * conv(B * x) (``gate`` False: conv(B * x)) [B, S, E] for
    proj [B, S, 3 E] = [B ; C ; x] as the projection stored it and taps
    w [K, E]: the causal depthwise convolution conv(u)_t = sum_j w_j
    u_{t-(K-1)+j} with zeros ahead of a sample's start, in one pass,
    float32 inside, rounded once into proj's dtype. Its own backward is
    one pass too: it keeps proj alone, forms u and conv again, and
    returns d proj in proj's dtype and the taps' gradient summed over
    the rows in the kernel. Caller checks `gated_conv_shape_legal` and
    `pallas_mode` first."""
    return _gated_conv_lanes(proj, w, gate, pallas_mode() == "interpret")


# ---------------------------------------------------------------------------
# The selective scan of a Mamba-1 mixer (PR 52):
#     h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
#     y_t[c]    = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]
# The decay differs by channel AND state, so there is no chunked-product
# form (Mamba-2's, whose decay is one scalar a head, has one: `ssd_scan`
# below is its home, `ops/ssm.py` `ssd_chunked` the same in `jax.numpy`),
# and the state [N, C] a position is never written to HBM: time is walked
# inside the kernel, the state stays in VMEM. A vreg holds 8 x 128 =
# 1024 CHANNELS of one position
# (operands [B, S, C / 128, 128], whose (8, 128) tiles XLA lays out in
# one pass over [B, S, C]), the N states are N such arrays, and B_t[n],
# C_t[n] are scalars out of SMEM: every sum over the states is a
# vreg-wise add, and the forward holds no cross-lane operation at all.
# The backward walks a chunk forward again from the state kept at the
# chunk's start, then backward; its sums over the CHANNELS (dB_t[n],
# dC_t[n]) are the one cross-lane work: added over the channel vregs
# first, one sublane reduce a (position, state), the lanes once a chunk.

SCAN_CHUNK = 64       # positions a grid step; the backward keeps their states
SCAN_CHANNELS = 1024  # channels a vreg: 8 sublanes x 128 lanes
_SCAN_COMPILER_PARAMS = dict(vmem_limit_bytes=100 << 20)


def _scan_fwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, y_ref,
                     hs_ref, h_scr, *, states: int, chunk: int):
    """One chunk of positions, all channels: the state entering the chunk
    goes out (the backward starts from it), then the recurrence a
    position a loop step."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    hs_ref[0, 0] = h_scr[...]

    def step(t, carry):
        dt, x = dt_ref[0, t], x_ref[0, t]
        u = dt * x
        y = d_ref[...] * x
        for n in range(states):
            h = jnp.exp(dt * a_ref[n]) * h_scr[n] + u * b_ref[0, 0, t * states + n]
            h_scr[n] = h
            y = y + h * c_ref[0, 0, t * states + n]
        y_ref[0, t] = y
        return carry

    jax.lax.fori_loop(0, chunk, step, None)


def _scan_bwd_kernel(b_ref, c_ref, x_ref, dt_ref, dy_ref, a_ref, d_ref,
                     hs_ref, dx_ref, ddt_ref, dbc_ref, da_ref, dd_ref,
                     h_all, g_scr, rows, *, states: int, chunk: int):
    """One chunk, the chunks in REVERSE order. The chunk's states once
    more from the one kept at its start (``h_all[t + 1]`` = h_t), then
    from its last position down: g_t = dy_t C_t + a_{t+1} g_{t+1} (the
    cotangent of h_t, carried across chunks in ``g_scr``);
    dC_t[n] = sum_c h_t dy_t, dB_t[n] = sum_c g_t u_t (``rows`` holds
    their sublane sums, the lanes are added once a chunk);
    du_t = sum_n g_t B_t[n]; with e = g_t h_{t-1} a_t (= dL/d(dt_t A)):
    d dt_t = sum_n e A + du_t x_t, dA += e dt_t; dx_t = du_t dt_t +
    D dy_t, dD += dy_t x_t."""
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first)
    def _():
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    @pl.when(pl.program_id(1) == 0)
    def _():
        g_scr[...] = jnp.zeros_like(g_scr)

    h_all[0] = hs_ref[0, 0]

    def forward(t, carry):
        dt = dt_ref[0, t]
        u = dt * x_ref[0, t]
        for n in range(states):
            h_all[t + 1, n] = (jnp.exp(dt * a_ref[n]) * h_all[t, n]
                               + u * b_ref[0, 0, t * states + n])
        return carry

    jax.lax.fori_loop(0, chunk, forward, None)

    def backward(i, carry):
        t = chunk - 1 - i
        dt, x, dy = dt_ref[0, t], x_ref[0, t], dy_ref[0, t]
        u = dt * x
        du = jnp.zeros_like(u)
        ddt = jnp.zeros_like(u)
        for n in range(states):
            a_n = a_ref[n]
            decay = jnp.exp(dt * a_n)
            g = g_scr[n] + dy * c_ref[0, 0, t * states + n]
            rows[t, n:n + 1, :] = jnp.sum(
                jnp.sum(h_all[t + 1, n] * dy, axis=0), axis=0, keepdims=True)
            rows[t, states + n:states + n + 1, :] = jnp.sum(
                jnp.sum(g * u, axis=0), axis=0, keepdims=True)
            du = du + g * b_ref[0, 0, t * states + n]
            e = g * h_all[t, n] * decay
            ddt = ddt + e * a_n
            da_ref[n] += e * dt
            g_scr[n] = g * decay
        ddt_ref[0, t] = ddt + du * x
        dx_ref[0, t] = du * dt + d_ref[...] * dy
        dd_ref[...] += dy * x
        return carry

    jax.lax.fori_loop(0, chunk, backward, None)
    dbc_ref[0] = jnp.sum(rows[...], axis=-1)


def _scan_blocks(chunks: int, vregs: int, states: int, chunk: int,
                 reverse: bool):
    """The BlockSpecs both kernels share; ``reverse``: the grid's second
    index counts the chunks from the last."""
    def at(j):
        return chunks - 1 - j if reverse else j

    scalars = pl.BlockSpec((1, 1, chunk * states),
                           lambda b, j: (b * chunks + at(j), 0, 0),
                           memory_space=pltpu.SMEM)
    rows = pl.BlockSpec((1, chunk, vregs, 8, LANES),
                        lambda b, j: (b, at(j), 0, 0, 0))
    a = pl.BlockSpec((states, vregs, 8, LANES), lambda b, j: (0, 0, 0, 0))
    d = pl.BlockSpec((vregs, 8, LANES), lambda b, j: (0, 0, 0))
    kept = pl.BlockSpec((1, 1, states, vregs, 8, LANES),
                        lambda b, j: (b, at(j), 0, 0, 0, 0))
    return scalars, rows, a, d, kept


def _scan_rows(t):
    """t [B, S, C] as the kernels read it, float32 [B, S', V, 8, 128]:
    S' whole chunks and V whole vregs of channels, zeros past the end
    (dt = 0 there: decay 1, no input)."""
    batch, s, c = t.shape
    s_pad, c_pad = (-s) % SCAN_CHUNK, (-c) % SCAN_CHANNELS
    t = jnp.pad(t.astype(jnp.float32), ((0, 0), (0, s_pad), (0, c_pad)))
    return t.reshape(batch, s + s_pad, (c + c_pad) // SCAN_CHANNELS, 8, LANES)


def _scan_operands(x, dt, bm, cm, a, d):
    """The kernels' operand forms, float32: x and dt by `_scan_rows`, B
    and C [B * chunks, 1, chunk * N] (SMEM), A [N, V, 8, 128], D [V, 8,
    128]."""
    f32 = jnp.float32
    batch, s, c = x.shape
    n = bm.shape[-1]
    s_pad, c_pad = (-s) % SCAN_CHUNK, (-c) % SCAN_CHANNELS

    def scalars(t):
        t = jnp.pad(t.astype(f32), ((0, 0), (0, s_pad), (0, 0)))
        return t.reshape(batch * (s + s_pad) // SCAN_CHUNK, 1, SCAN_CHUNK * n)

    a = jnp.pad(a.astype(f32).T, ((0, 0), (0, c_pad))).reshape(
        n, -1, 8, LANES)
    d = jnp.pad(d.astype(f32), (0, c_pad)).reshape(-1, 8, LANES)
    return _scan_rows(x), _scan_rows(dt), scalars(bm), scalars(cm), a, d


def _scan_forward(x, dt, bm, cm, a, d, interpret):
    batch, s, c = x.shape
    n = bm.shape[-1]
    xs, dts, bs, cs, a4, d3 = _scan_operands(x, dt, bm, cm, a, d)
    chunks, vregs = xs.shape[1] // SCAN_CHUNK, xs.shape[2]
    scalars, rows, a_spec, d_spec, kept = _scan_blocks(
        chunks, vregs, n, SCAN_CHUNK, False)
    y, hs = pl.pallas_call(
        functools.partial(_scan_fwd_kernel, states=n, chunk=SCAN_CHUNK),
        name="selective_scan_fwd",
        out_shape=(jax.ShapeDtypeStruct(xs.shape, jnp.float32),
                   jax.ShapeDtypeStruct((batch, chunks, n, vregs, 8, LANES),
                                        jnp.float32)),
        grid=(batch, chunks),
        in_specs=[scalars, scalars, rows, rows, a_spec, d_spec],
        out_specs=(rows, kept),
        scratch_shapes=[pltpu.VMEM((n, vregs, 8, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            **_SCAN_COMPILER_PARAMS),
        interpret=interpret)(bs, cs, xs, dts, a4, d3)
    return y.reshape(batch, -1, vregs * SCAN_CHANNELS)[:, :s, :c], hs


def _scan_backward(x, dt, bm, cm, a, d, hs, dy, interpret):
    batch, s, c = x.shape
    n = bm.shape[-1]
    xs, dts, bs, cs, a4, d3 = _scan_operands(x, dt, bm, cm, a, d)
    dys = _scan_rows(dy)
    chunks, vregs = xs.shape[1] // SCAN_CHUNK, xs.shape[2]
    scalars, rows, a_spec, d_spec, kept = _scan_blocks(
        chunks, vregs, n, SCAN_CHUNK, True)
    sums = pl.BlockSpec((1, SCAN_CHUNK, 2 * n),
                        lambda b, j: (b * chunks + chunks - 1 - j, 0, 0))
    dx, ddt, dbc, da, dd = pl.pallas_call(
        functools.partial(_scan_bwd_kernel, states=n, chunk=SCAN_CHUNK),
        name="selective_scan_bwd",
        out_shape=(jax.ShapeDtypeStruct(xs.shape, jnp.float32),
                   jax.ShapeDtypeStruct(xs.shape, jnp.float32),
                   jax.ShapeDtypeStruct((batch * chunks, SCAN_CHUNK, 2 * n),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(a4.shape, jnp.float32),
                   jax.ShapeDtypeStruct(d3.shape, jnp.float32)),
        grid=(batch, chunks),
        in_specs=[scalars, scalars, rows, rows, rows, a_spec, d_spec, kept],
        out_specs=(rows, rows, sums, a_spec, d_spec),
        scratch_shapes=[
            pltpu.VMEM((SCAN_CHUNK + 1, n, vregs, 8, LANES), jnp.float32),
            pltpu.VMEM((n, vregs, 8, LANES), jnp.float32),
            pltpu.VMEM((SCAN_CHUNK, 2 * n, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            **_SCAN_COMPILER_PARAMS),
        interpret=interpret)(bs, cs, xs, dts, dys, a4, d3, hs)

    def unrowed(t):
        return t.reshape(batch, -1, vregs * SCAN_CHANNELS)[:, :s, :c]

    dbc = dbc.reshape(batch, -1, 2 * n)[:, :s]
    return (unrowed(dx), unrowed(ddt), dbc[..., n:], dbc[..., :n],
            da.reshape(n, -1)[:, :c].T, dd.reshape(-1)[:c])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _selective_scan(x, dt, bm, cm, a, d, interpret):
    return _scan_forward(x, dt, bm, cm, a, d, interpret)[0]


def _selective_scan_fwd(x, dt, bm, cm, a, d, interpret):
    y, hs = _scan_forward(x, dt, bm, cm, a, d, interpret)
    return y, (x, dt, bm, cm, a, d, hs)


def _selective_scan_bwd(interpret, kept, dy):
    # a cotangent in its operand's dtype (x comes in the compute dtype)
    return tuple(g.astype(t.dtype) for g, t in
                 zip(_scan_backward(*kept, dy, interpret), kept))


_selective_scan.defvjp(_selective_scan_fwd, _selective_scan_bwd)


def selective_scan(x, dt, bm, cm, a, d):
    """y [B, S, C] float32 of the recurrence above for x, dt [B, S, C]
    (dt after its softplus), bm, cm [B, S, N], a [C, N] (negative), d
    [C]; the state is zero at a sample's start. ONE kernel forward and
    ONE backward (which keeps the state entering every chunk of
    SCAN_CHUNK positions, [B, S / 64, N, C] float32, and forms the rest
    again); everything inside is float32. Any S and C: the operands are
    padded to whole chunks and whole vregs of channels. Caller checks
    `pallas_mode` first; `ops.ssm.selective_scan_stepwise` is the same
    recurrence in `jax.numpy`."""
    return _selective_scan(x, dt, bm, cm, a, d, pallas_mode() == "interpret")


# ---------------------------------------------------------------------------
# the gated delta rule in chunks of 128 positions (PR 58), EVERYTHING of a
# chunk in VMEM: the decay matrix exp(G_t - G_s), K K^T, the inverse of
# the unit lower-triangular I + A, W = T (K exp(G)), U = T V, the masked
# Q K^T and, with the state S a [128, 128] float32 scratch carried from
# chunk to chunk and from grid step to grid step,
#     V' = U - W S;   O = (Q exp(G)) S + P V';   S <- exp(G_C) S + Kg^T V'
# from q, k, v [128, 128] and the chunk's rows of the decays' running sum
# and of beta; around it, in the same pass, the convolution's SiLU, the
# heads' L2 norms of q and k (a row's sum over its 128 lanes) and the
# gated head norm of the output.
# Nothing of a chunk but its output and the state that entered it (kept
# for the backward: a state a CHUNK, 1 / 128 of a state a position) goes
# to HBM. The backward is the SAME functions' `jax.vjp`,
# taken inside the kernel a chunk at a time in reverse with dS carried;
# the inverse has a backward of its own (-T^T dT T^T).
#
# A grid step is DELTA_ROWS rows of one KEY head (PR 60), and the chunk
# loop walks the key head's value heads TOGETHER, one loop body a chunk:
# q and k arrive, take their SiLU and norm and form K K^T and Q K^T once
# (`_delta_heads`, `_delta_scores`: nothing in them differs by value
# head); the heads' inverses stand in one basic block as independent
# chains, a doubling of every chain at a time (`_unit_lower_inverse_tiles`);
# the backward adds the heads' dq, dk, d(K K^T) and d(Q K^T) in float32
# and pulls them through the scores and the norm once, so dq and dk leave
# at key-head width.
#
# v5e, bf16, 16,384 positions, 16 key and 32 value heads of 128, one
# layer's rule alone, forward / forward and backward device ms
# (`scripts/delta_lab.py`). The batched form (my chip runs, PR 58;
# `ops.delta_rule._chunk_operands` in XLA: a dozen [4096, 128, 128]
# float32 arrays written and read, the inverse 17.4 of it at `highest`,
# 12.4 at one bfloat16 pass, 44.3 by `lax.linalg.triangular_solve`) with
# the walk as a `lax.scan`: 25.8 / 82.5; the same with the walk as a
# kernel pair that read those operands: 32.8 / 87.6 (the walk itself 1.7
# + 3.6; the relayout to [B, S, H*128] cost what it saved).
# One kernel each way (my chip runs, PR 60; the three forms side by side
# in the lab): a grid step a value head, as PR 58 shipped it, 13.7 / 26.8
# (forward 13.7, backward 8.9, XLA's sums over a key head's two value
# heads 2.6, its relayouts of g and beta 1.6; PR 58's own kernels 13.5
# and 9.1); a key head a step with each head's whole inverse after the
# other's, 11.9 / 20.8 (backward 7.3); **as shipped, a doubling of both
# chains at a time: 9.7 / 18.5** (forward 9.7, backward 7.2, the
# relayouts 1.6).
DELTA_CHUNK = 128     # rows a chunk: one MXU tile, as the heads' 128 lanes
DELTA_ROWS = 1024     # rows a grid step, where S allows
MAX_DELTA_WHOLE = 4096    # longest S taken as ONE block of rows
MAX_DELTA_HEADS_A_STEP = 4    # value heads one loop body walks
_DELTA_COMPILER_PARAMS = dict(vmem_limit_bytes=64 << 20)
_TN = (((0,), (0,)), ((), ()))  # a[c, m] . b[c, n] -> [m, n]


def _delta_rows(s: int) -> int:
    return DELTA_ROWS if s % DELTA_ROWS == 0 else s


def delta_rule_shape_legal(seq_len: int, key_dim: int, value_dim: int,
                           chunk: int) -> bool:
    """The shapes `delta_rule_fused` takes: heads of 128 lanes (keys and
    values), chunks of 128 rows, and a sequence of whole blocks of
    DELTA_ROWS rows, or of whole chunks up to MAX_DELTA_WHOLE rows (one
    block)."""
    return (key_dim == value_dim == LANES and chunk == DELTA_CHUNK
            and seq_len % DELTA_CHUNK == 0
            and (seq_len % DELTA_ROWS == 0 or seq_len <= MAX_DELTA_WHOLE))


def delta_heads_a_step(rep: int) -> int:
    """The value heads one grid step of the kernel pair walks, of the
    ``rep`` a key head serves: all of them up to MAX_DELTA_HEADS_A_STEP
    (a block of rows of every operand for each, doubled, in VMEM: 7.5 MB
    forward and 13 backward at four; the loop body unrolled over them),
    else the largest divisor of ``rep`` under it: a key head then takes
    ``rep`` over that many steps, and XLA adds the steps' dq and dk."""
    return max(n for n in range(1, min(rep, MAX_DELTA_HEADS_A_STEP) + 1)
               if rep % n == 0)


def _chunk_rows(c):
    return pl.ds(pl.multiple_of(c * DELTA_CHUNK, DELTA_CHUNK), DELTA_CHUNK)


def _head_lanes(h: int):
    return slice(h * LANES, (h + 1) * LANES)


def _dot32(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _unit_lower_inverse_tiles(tiles):
    """(I + A)^-1 of each strictly lower-triangular [C, C] float32 tile by
    log2(C) - 1 doublings, float32 products. The tiles' chains share no
    value: every chain's squaring, then every chain's multiply, a
    doubling at a time, so that one chain's product can fill the MXU
    while another's drains."""
    c = tiles[0].shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    invs = [jnp.where(rows == cols, 1.0, 0.0) - a for a in tiles]
    powers, reach = list(tiles), 2
    while reach < c:
        powers = [_dot32(p, p, _NN) for p in powers]
        invs = [inv + _dot32(inv, p, _NN) for inv, p in zip(invs, powers)]
        reach *= 2
    return invs


def _unit_lower_inverse_pullbacks(invs, dinvs):
    """d A = -T^T dT T^T of each tile, the chains side by side as above."""
    halves = [_dot32(inv, dinv, _TN) for inv, dinv in zip(invs, dinvs)]
    return [-_dot32(half, inv, _NT) for half, inv in zip(halves, invs)]


def _delta_heads(q, k, vs):
    """A chunk's q, k [C, 128] of a key head and the v [C, 128] of each
    of its value heads as the convolution left them -> after its SiLU, q
    and k L2-normed a row (q to length Dk^-1/2, k to 1), in the operands'
    dtype."""
    f32, cd = jnp.float32, q.dtype

    def unit(x, scale):
        x = jax.nn.silu(x.astype(f32))
        return (x * (jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True)
                                   + 1e-6) * scale)).astype(cd)

    return (unit(q, float(q.shape[1]) ** -0.5), unit(k, 1.0),
            tuple(jax.nn.silu(v.astype(f32)).astype(cd) for v in vs))


def _delta_scores(q, k):
    """(K K^T, Q K^T) [C, C] float32 of a key head's chunk, before any
    decay, beta or mask: what its value heads share."""
    return _dot(k, k, _NT), _dot(q, k, _NT)


def _delta_decays(g_row):
    """(row index, column index, G down a column, G_C down a column, the
    decay matrix exp(G_t - G_s) on and under the diagonal) of one chunk
    from its row [1, C] of running sums."""
    c = g_row.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    g_col = _column(g_row, rows, cols)
    g_end = jnp.sum(jnp.where(cols == c - 1, g_row, 0.0), axis=1,
                    keepdims=True)
    return rows, cols, g_col, g_end, jnp.exp(
        jnp.where(rows >= cols, g_col - g_row, _MASKED))


def _column(row, rows, cols):    # [1, C] -> [C, 1]
    return jnp.sum(jnp.where(rows == cols, row, 0.0), axis=1, keepdims=True)


def _delta_a(kk, g_row, b_row):
    """A = strict_tril(diag(beta) (K K^T) * exp(G_t - G_s)) of one value
    head's chunk, float32; kk = K K^T of the normed k."""
    rows, cols, _, _, decay = _delta_decays(g_row)
    return jnp.where(rows > cols, kk * decay * _column(b_row, rows, cols),
                     0.0)


def _delta_walk(s, q, k, v, z, g_row, b_row, w_n, inv, qk, *, eps: float):
    """One chunk of one value head past the inverse: (y [C, Dv] float32,
    the state after it). s [Dk, Dv] float32 the state before; q, k, v as
    `_delta_heads` leaves them; z [C, Dv] the gate; g_row, b_row [1, C]
    float32: the running sum of the log-decays inside the chunk, and
    beta; w_n [1, Dv] float32 the head norm's scale; inv = (I + A)^-1
    float32; qk = Q K^T float32. The gated head norm is a row's mean over
    its 128 lanes, so it is here. C = Dk (the columns serve both)."""
    f32, cd = jnp.float32, q.dtype
    rows, cols, g_col, g_end, decay = _delta_decays(g_row)
    t = (inv * b_row).astype(cd)
    into = jnp.exp(g_col)
    w = _dot(t, (k.astype(f32) * into).astype(cd), _NN).astype(cd)
    u = _dot(t, v, _NN).astype(cd)
    p = jnp.where(rows >= cols, qk * decay, 0.0).astype(cd)
    qg = (q.astype(f32) * into).astype(cd)
    kg = (k.astype(f32) * jnp.exp(g_end - g_col)).astype(cd)
    sb = s.astype(cd)
    vp = u.astype(f32) - _dot(w, sb, _NN)
    vb = vp.astype(cd)
    o = _dot(qg, sb, _NN) + _dot(p, vb, _NN)
    y = (o * jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
         * w_n * jax.nn.silu(z.astype(f32)))
    return y, jnp.exp(g_end) * s + _dot(kg, vb, _TN)


def _delta_fused_fwd_kernel(*refs, heads: int, chunks: int, eps: float):
    q_ref, k_ref = refs[:2]
    v_refs = refs[2:2 + heads]
    z_ref, g_ref, b_ref, w_ref, y_ref, kept_ref, inv_ref, state = \
        refs[2 + heads:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def chunk(c, carry):
        rows = _chunk_rows(c)
        q, k, vs = _delta_heads(q_ref[0, rows, :], k_ref[0, rows, :],
                                tuple(v[0, rows, :] for v in v_refs))
        kk, qk = _delta_scores(q, k)
        g_rows = [g_ref[0, h, :, rows] for h in range(heads)]
        b_rows = [b_ref[0, h, :, rows] for h in range(heads)]
        invs = _unit_lower_inverse_tiles(
            [_delta_a(kk, g, b) for g, b in zip(g_rows, b_rows)])
        for h, inv in enumerate(invs):
            at = _head_lanes(h)
            s = state[h]
            kept_ref[0, rows, at] = s
            inv_ref[0, rows, at] = inv
            y, state[h] = _delta_walk(s, q, k, vs[h], z_ref[0, rows, at],
                                      g_rows[h], b_rows[h], w_ref[...], inv,
                                      qk, eps=eps)
            y_ref[0, rows, at] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, None)


def _delta_fused_bwd_kernel(*refs, heads: int, chunks: int, eps: float):
    """A chunk's functions differentiated where they stand, the chunks in
    reverse with dS carried. The inverse is not formed again: the forward
    kept it, and its own backward is -T^T dT T^T. What the value heads
    send back to their key head's q, k and scores is added in float32
    and pulled through the scores and the norm once."""
    q_ref, k_ref = refs[:2]
    v_refs = refs[2:2 + heads]
    (z_ref, g_ref, b_ref, w_ref, kept_ref, inv_ref, dy_ref, dq_ref, dk_ref,
     dv_ref, dz_ref, dg_ref, db_ref, dw_ref, dstate) = refs[2 + heads:]
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def chunk(j, carry):
        rows = _chunk_rows(chunks - 1 - j)
        (q, k, vs), pull_heads = jax.vjp(
            _delta_heads, q_ref[0, rows, :], k_ref[0, rows, :],
            tuple(v[0, rows, :] for v in v_refs))
        (kk, qk), pull_scores = jax.vjp(_delta_scores, q, k)
        g_rows = [g_ref[0, h, :, rows] for h in range(heads)]
        b_rows = [b_ref[0, h, :, rows] for h in range(heads)]
        invs = [inv_ref[0, rows, _head_lanes(h)] for h in range(heads)]
        walked = []
        for h in range(heads):
            at = _head_lanes(h)
            _, pull_walk = jax.vjp(
                functools.partial(_delta_walk, eps=eps), kept_ref[0, rows, at],
                q, k, vs[h], z_ref[0, rows, at], g_rows[h], b_rows[h],
                w_ref[...], invs[h], qk)
            walked.append(pull_walk(
                (dy_ref[0, rows, at].astype(f32), dstate[h])))
        das = _unit_lower_inverse_pullbacks(invs, [w[8] for w in walked])
        dq = dk = dkk = dqk = 0.0
        dvs = []
        for h, (ds, dq_h, dk_h, dv, dz, dg, db, dw, _, dqk_h) in enumerate(
                walked):
            at = _head_lanes(h)
            _, pull_a = jax.vjp(_delta_a, kk, g_rows[h], b_rows[h])
            dkk_h, dg_a, db_a = pull_a(das[h])
            dq, dk = dq + dq_h.astype(f32), dk + dk_h.astype(f32)
            dkk, dqk = dkk + dkk_h, dqk + dqk_h
            dvs.append(dv)
            dstate[h] = ds
            dz_ref[0, rows, at] = dz
            dg_ref[0, h, :, rows] = dg + dg_a
            db_ref[0, h, :, rows] = db + db_a
            dw_ref[0, h] += dw
        dq_s, dk_s = pull_scores((dkk, dqk))
        dq, dk, dvs = pull_heads(((dq + dq_s.astype(f32)).astype(q.dtype),
                                  (dk + dk_s.astype(f32)).astype(k.dtype),
                                  tuple(dvs)))
        dq_ref[0, rows, :] = dq
        dk_ref[0, rows, :] = dk
        for h, dv in enumerate(dvs):
            dv_ref[0, rows, _head_lanes(h)] = dv
        return carry

    jax.lax.fori_loop(0, chunks, chunk, None)


def _delta_fused_specs(s: int, key_heads: int, rep: int, reverse: bool):
    """(rows, the value heads a step walks; their [rows, heads * 128]
    block of an array of their own; a step's own [rows, 128] block (dq,
    dk); q's and k's blocks and the heads' v blocks, 128 lanes each, of
    the ONE [B, S, (2 Hk + Hv) * 128] array the convolution wrote; the
    heads' [1, rows] rows; the norm's scale; the heads' [1, 128] sums).
    The grid's second axis is a step's value heads, ``rep`` over the
    heads a step of such steps to a key head. ``reverse``: the last axis
    walks the blocks of rows from the last to the first."""
    rows = _delta_rows(s)
    blocks = s // rows
    heads = delta_heads_a_step(rep)
    steps_a_key_head = rep // heads

    def at(i):
        return blocks - 1 - i if reverse else i

    def lanes(width, of_step):
        return pl.BlockSpec((1, rows, width),
                            lambda b, h, i: (b, at(i), of_step(h)))

    return (rows, heads, lanes(heads * LANES, lambda h: h),
            lanes(LANES, lambda h: h),
            (lanes(LANES, lambda h: h // steps_a_key_head),
             lanes(LANES, lambda h: key_heads + h // steps_a_key_head),
             *(lanes(LANES, lambda h, j=j: 2 * key_heads + h * heads + j)
               for j in range(heads))),
            pl.BlockSpec((1, heads, 1, rows),
                         lambda b, h, i: (b, h, 0, at(i))),
            pl.BlockSpec((1, LANES), lambda b, h, i: (0, 0)),
            pl.BlockSpec((1, heads, 1, LANES), lambda b, h, i: (b, h, 0, 0)))


def _delta_fused_forward(qkv, z, g, beta, w_n, key_heads, eps, interpret):
    b, s, width = z.shape
    value_heads = width // LANES
    rows, heads, own, _, qkv_specs, row, scale, _ = _delta_fused_specs(
        s, key_heads, value_heads // key_heads, False)
    return pl.pallas_call(
        functools.partial(_delta_fused_fwd_kernel, heads=heads,
                          chunks=rows // DELTA_CHUNK, eps=eps),
        name="delta_rule_fwd",
        out_shape=(jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct(z.shape, jnp.float32),
                   jax.ShapeDtypeStruct(z.shape, jnp.float32)),
        grid=(b, value_heads // heads, s // rows),
        in_specs=[*qkv_specs, own, row, row, scale],
        out_specs=(own, own, own),
        scratch_shapes=[pltpu.VMEM((heads, LANES, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            **_DELTA_COMPILER_PARAMS),
        interpret=interpret)(*[qkv] * len(qkv_specs), z, g, beta, w_n)


def _delta_fused_backward(qkv, z, g, beta, w_n, kept, inv, dy, key_heads,
                          eps, interpret):
    b, s, width = z.shape
    value_heads = width // LANES
    rows, heads, own, step, qkv_specs, row, scale, total = \
        _delta_fused_specs(s, key_heads, value_heads // key_heads, True)
    steps = value_heads // heads
    like = jax.ShapeDtypeStruct(z.shape, z.dtype)
    a_step = jax.ShapeDtypeStruct((b, s, steps * LANES), z.dtype)
    a_row = jax.ShapeDtypeStruct(g.shape, jnp.float32)
    dq, dk, dv, dz, dg, db, dw = pl.pallas_call(
        functools.partial(_delta_fused_bwd_kernel, heads=heads,
                          chunks=rows // DELTA_CHUNK, eps=eps),
        name="delta_rule_bwd",
        out_shape=(a_step, a_step, like, like, a_row, a_row,
                   jax.ShapeDtypeStruct((b, value_heads, 1, LANES),
                                        jnp.float32)),
        grid=(b, steps, s // rows),
        in_specs=[*qkv_specs, own, row, row, scale, own, own, own],
        out_specs=(step, step, own, own, row, row, total),
        scratch_shapes=[pltpu.VMEM((heads, LANES, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            **_DELTA_COMPILER_PARAMS),
        interpret=interpret)(*[qkv] * len(qkv_specs), z, g, beta, w_n, kept,
                             inv, dy)
    if steps > key_heads:   # a key head's value heads took several steps
        dq, dk = (jnp.sum(t.astype(jnp.float32).reshape(
            b, s, key_heads, steps // key_heads, LANES), axis=3).reshape(
                b, s, key_heads * LANES).astype(qkv.dtype) for t in (dq, dk))
    return (jnp.concatenate([dq, dk, dv], axis=-1), dz, dg, db,
            jnp.sum(dw, axis=(0, 1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _delta_rule_fused(qkv, z, g, beta, w_n, key_heads, eps, interpret):
    return _delta_fused_forward(qkv, z, g, beta, w_n, key_heads, eps,
                                interpret)[0]


def _delta_rule_fused_fwd(qkv, z, g, beta, w_n, key_heads, eps, interpret):
    y, kept, inv = _delta_fused_forward(qkv, z, g, beta, w_n, key_heads, eps,
                                        interpret)
    return y, (qkv, z, g, beta, w_n, kept, inv)


def _delta_rule_fused_bwd(key_heads, eps, interpret, res, dy):
    return _delta_fused_backward(*res, dy, key_heads, eps, interpret)


_delta_rule_fused.defvjp(_delta_rule_fused_fwd, _delta_rule_fused_bwd)


def delta_rule_fused(qkv, z, g, beta, w_n, key_heads: int, eps: float):
    """y [B, S, Hv*128]: the heads' L2 norms, the gated delta rule in
    chunks of 128 and the gated head norm, (o * rsqrt(mean(o^2) + eps) *
    w_n) * silu(z). qkv [B, S, (2 Hk + Hv) * 128]: q, k at the key heads
    and v at the value heads as the convolution left them (its SiLU is
    taken here), side by side along the lanes (the kernels' BlockSpecs pick a head's
    128 lanes of each: nothing is sliced or laid out again in HBM); z
    [B, S, Hv*128], in qkv's dtype, the products' operands'; g, beta
    [B, Hv, 1, S] float32: the RUNNING SUM of the log-decays inside each
    chunk of 128 positions, and beta; w_n [1, 128] float32. ONE kernel
    forward (`delta_rule_fwd`, which also keeps the state that entered
    every chunk and the chunk's inverse, [B, S, Hv*128] float32 each)
    and ONE backward (`delta_rule_bwd`). Caller checks `pallas_mode` and
    `delta_rule_shape_legal`; `ops.delta_rule` has the same in
    `jax.numpy`."""
    return _delta_rule_fused(qkv, z, g, beta, w_n, key_heads, eps,
                             pallas_mode() == "interpret")


# ---------------------------------------------------------------------------
# The chunked scan of a Mamba-2 mixer (PR 62): a head's decay is ONE
# scalar a position, so a chunk of Q positions is four matrix products
# (`ops/ssm.py` `ssd_chunked`, the same rule in `jax.numpy`): with cs the
# running sum of dt A inside the chunk and S the state that enters it,
#     Y = ((C B^T) * exp(cs_l - cs_s) [s <= l]) (dt x) + exp(cs) * (C S) + D x
#     S <- exp(cs_Q) S + (B * exp(cs_Q - cs))^T (dt x)
# EVERYTHING of a chunk stays in VMEM: the running sums (a product with a
# triangle of ones, exact in float32: `_by_pieces`), a head's [Q, Q]
# decay tile, the scores, dt x
# and the state, a [N, H*P] float32 scratch carried from chunk to chunk
# and from grid step to grid step: the state's lanes are the heads' lanes
# of x, so C S and the state's update are ONE product each for the heads
# of a 128-lane tile (one head of 128 or two of 64, whose score tiles
# meet the tile's dt x with the other head's lanes zeroed). C B^T is
# formed once a group. Nothing of a chunk but its output and the state
# that entered it (kept for the backward) goes to HBM. The backward is the
# same function's `jax.vjp`, taken inside the kernel a chunk at a time in
# reverse with dS carried (PR 58's form); a product's backward rounds the
# cotangent to the operands' dtype, as the flash kernels round dS.
SSD_ROWS = 1024       # rows a grid step, where S allows
MAX_SSD_LANES = 1024  # H * P: a block holds its rows of every head
_SSD_COMPILER_PARAMS = dict(vmem_limit_bytes=64 << 20)


def ssd_shape_legal(seq_len: int, heads: int, head_dim: int, groups: int,
                    state: int, chunk: int) -> bool:
    """The shapes `ssd_scan` takes: heads of 64 or 128 lanes that fill
    whole 128-lane tiles, up to MAX_SSD_LANES lanes of them, on groups
    that divide them, a state of 128, chunks of 128 or 256 positions;
    any length (padded to whole blocks of rows with dt = 0)."""
    return (seq_len >= 1 and head_dim in (64, LANES)
            and (heads * head_dim) % LANES == 0
            and heads * head_dim <= MAX_SSD_LANES
            and groups >= 1 and heads % groups == 0
            and state == LANES and chunk in (128, 256))


def _ssd_rows(s: int, chunk: int) -> int:
    """Rows a grid step: SSD_ROWS, or the whole (padded) sequence where
    that is shorter."""
    return min(SSD_ROWS, -(-s // chunk) * chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dot_rounding_back(a, b, dims):
    """`_dot` whose backward rounds the cotangent to the operands' dtype
    before the two products it enters (one MXU pass each in bfloat16, as
    XLA's default precision multiplies a float32 cotangent on the chip)."""
    return _dot(a, b, dims)


def _dot_rounding_back_fwd(a, b, dims):
    return _dot(a, b, dims), (a, b)


# dims of out -> (dims of d a from (g, b) or (b, g), ... of d b)
_DOT_PULLBACKS = {_NN: (lambda g, a, b: (_dot(g, b, _NT), _dot(a, g, _TN))),
                  _NT: (lambda g, a, b: (_dot(g, b, _NN), _dot(g, a, _TN))),
                  _TN: (lambda g, a, b: (_dot(b, g, _NT), _dot(a, g, _NN)))}


def _dot_rounding_back_bwd(dims, kept, g):
    a, b = kept
    da, db = _DOT_PULLBACKS[dims](g.astype(a.dtype), a, b)
    return da.astype(a.dtype), db.astype(b.dtype)


_dot_rounding_back.defvjp(_dot_rounding_back_fwd, _dot_rounding_back_bwd)


def _by_pieces(x, axis: int, product, out_axis: int):
    """``product`` of a float32 x with a matrix of zeros and ones, exact:
    x goes in as its three bfloat16 pieces side by side along ``axis``
    (they hold all 24 bits, and a product with 0 or 1 rounds nothing) and
    the three results, side by side along ``out_axis``, are added: ONE
    MXU pass where a float32 product takes six."""
    n = x.shape[axis]
    out = product(jnp.concatenate(_bf16_pieces(x, 3), axis=axis))
    return sum(jax.lax.slice_in_dim(out, i * n, (i + 1) * n, axis=out_axis)
               for i in range(3))


def _ones(q: int, relation):
    """[Q, Q] bfloat16: 1 where ``relation(row, column)``, else 0."""
    return jnp.where(relation(
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 0),
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)), 1.0, 0.0).astype(
            jnp.bfloat16)


@jax.custom_vjp
def _running_sums(x):
    """x [Q, H] float32 -> its running sums down the rows, float32."""
    return _by_pieces(x, 1, lambda p: _dot(
        _ones(x.shape[0], operator.ge), p, _NN), 1)


def _sums_from_the_end(g):
    return _by_pieces(g, 1, lambda p: _dot(
        _ones(g.shape[0], operator.le), p, _NN), 1)


_running_sums.defvjp(lambda x: (_running_sums(x), None),
                     lambda _, g: (_sums_from_the_end(g),))


@jax.custom_vjp
def _rows_along_lanes(x):
    """x [Q, H] float32 -> [H, Q], the same numbers."""
    return _by_pieces(x, 1, lambda p: _dot(
        p, _ones(x.shape[0], operator.eq), _TN), 0)


def _lanes_down_rows(g):    # [H, Q] -> [Q, H]
    return _by_pieces(g, 0, lambda p: _dot(
        _ones(g.shape[1], operator.eq), p, _NT), 1)


_rows_along_lanes.defvjp(lambda x: (_rows_along_lanes(x), None),
                         lambda _, g: (_lanes_down_rows(g),))


def _ssd_chunk(st, x, dt, a, d, bm, cm, *, heads: int, groups: int):
    """One chunk of every head: (y [Q, H*P] float32 with D x, the state
    after it). st [N, H*P] float32 the state before (a head's P lanes
    where x has them); x [Q, H*P], bm, cm [Q, G*N] in the products' dtype;
    dt [Q, H], a [1, H] (negative), d [1, H*P] float32."""
    f32, cd = jnp.float32, x.dtype
    q, width = x.shape
    p, n, rep = width // heads, st.shape[0], heads // groups
    a_tile = LANES // p     # heads a 128-lane tile
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower = rows >= cols
    da = dt * a
    # the running sum down the rows [Q, H], and the same numbers along
    # the lanes [H, Q] (what a decay tile's diagonal sends back to them
    # cancels before the sum's pullback)
    cs = _running_sums(da)
    cs_row = _rows_along_lanes(cs)
    end = cs[q - 1:q]
    since_start, to_end, whole = jnp.exp(cs), jnp.exp(end - cs), jnp.exp(end)
    xf, bf, cf = x.astype(f32), bm.astype(f32), cm.astype(f32)
    skip = d * xf

    def of_group(t, g):
        return t[:, g * n:(g + 1) * n].astype(cd)

    cb = [_dot_rounding_back(of_group(cf, g), of_group(bf, g), _NT)
          for g in range(groups)]

    def first(t):   # the lanes of a tile's first head
        return jax.lax.broadcasted_iota(jnp.int32, t.shape, 1) < p

    def head_lanes(v, tile):    # [R, H] -> [R, 128]: a head's value its lanes
        h = tile * a_tile
        shape = (v.shape[0], LANES)
        own = jnp.broadcast_to(v[:, h:h + 1], shape)
        if a_tile == 1:
            return own
        return jnp.where(first(own), own,
                         jnp.broadcast_to(v[:, h + 1:h + 2], shape))

    ys, states = [], []
    for tile in range(width // LANES):
        at = slice(tile * LANES, (tile + 1) * LANES)
        tile_heads = range(tile * a_tile, (tile + 1) * a_tile)
        xd = xf[:, at] * head_lanes(dt, tile)
        xe = xd * head_lanes(to_end, tile)
        s_in = st[:, at]

        def only(t, members):
            """t with the lanes of the tile's other heads zeroed."""
            if len(members) == a_tile:
                return t
            if members[0] == tile_heads[0]:
                return jnp.where(first(t), t, 0.0)
            return jnp.where(first(t), 0.0, t)

        y = skip[:, at]
        for h in tile_heads:
            decay = jnp.exp(jnp.where(
                lower, cs[:, h:h + 1] - cs_row[h:h + 1], _MASKED))
            y = y + _dot_rounding_back(
                (cb[h // rep] * decay).astype(cd),
                only(xd, [h]).astype(cd), _NN)
        entered, own = 0.0, 0.0
        for g in sorted({h // rep for h in tile_heads}):
            members = [h for h in tile_heads if h // rep == g]
            entered = entered + _dot_rounding_back(
                of_group(cf, g), only(s_in, members).astype(cd), _NN)
            own = own + _dot_rounding_back(
                of_group(bf, g), only(xe, members).astype(cd), _TN)
        ys.append(y + entered * head_lanes(since_start, tile))
        states.append(s_in * head_lanes(whole, tile) + own)
    return jnp.concatenate(ys, axis=1), jnp.concatenate(states, axis=1)


def _ssd_chunk_rows(c, chunk: int):
    return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)


def _ssd_operands(xbc_ref, rows, width: int):
    """x, B, C of a chunk: lane ranges of the convolution's one array."""
    gn = (xbc_ref.shape[2] - width) // 2
    return (xbc_ref[0, rows, :width], xbc_ref[0, rows, width:width + gn],
            xbc_ref[0, rows, width + gn:])


def _ssd_fwd_kernel(xbc_ref, dt_ref, a_ref, d_ref, y_ref, kept_ref, state, *,
                    heads: int, groups: int, chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def walk(c, carry):
        rows = _ssd_chunk_rows(c, chunk)
        x, bm, cm = _ssd_operands(xbc_ref, rows, state.shape[1])
        kept_ref[0, c] = state[...]
        y_ref[0, rows, :], state[...] = _ssd_chunk(
            state[...], x, dt_ref[0, rows, :], a_ref[...], d_ref[...], bm, cm,
            heads=heads, groups=groups)
        return carry

    jax.lax.fori_loop(0, xbc_ref.shape[1] // chunk, walk, None)


def _ssd_bwd_kernel(xbc_ref, dt_ref, a_ref, d_ref, kept_ref, dy_ref,
                    dxbc_ref, ddt_ref, da_ref, dd_ref, dstate, *, heads: int,
                    groups: int, chunk: int):
    """A chunk's function differentiated where it stands, the chunks in
    reverse with dS carried; dx, dB and dC leave as the lane ranges of ONE
    array, as x, B and C came; dA and dD add up over a sample's rows."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    chunks = xbc_ref.shape[1] // chunk
    width = dstate.shape[1]

    def walk(j, carry):
        c = chunks - 1 - j
        rows = _ssd_chunk_rows(c, chunk)
        x, bm, cm = _ssd_operands(xbc_ref, rows, width)
        _, pull = jax.vjp(
            functools.partial(_ssd_chunk, heads=heads, groups=groups),
            kept_ref[0, c], x, dt_ref[0, rows, :], a_ref[...], d_ref[...],
            bm, cm)
        dstate[...], dx, ddt_ref[0, rows, :], da, dd, db, dc = pull(
            (dy_ref[0, rows, :], dstate[...]))
        gn = db.shape[1]
        dxbc_ref[0, rows, :width] = dx
        dxbc_ref[0, rows, width:width + gn] = db
        dxbc_ref[0, rows, width + gn:] = dc
        da_ref[0] += da
        dd_ref[0] += dd
        return carry

    jax.lax.fori_loop(0, chunks, walk, None)


def _ssd_call(xbc, dt, a, d, groups, state, chunk, interpret, kept=None,
              dy=None):
    """The forward kernel, or with ``kept`` and ``dy`` the backward: the
    operands padded to whole blocks of rows (dt = 0: decay 1, no input)
    and the per-head rates laid as rows."""
    f32 = jnp.float32
    batch, s, lanes_in = xbc.shape
    heads, width = dt.shape[-1], lanes_in - 2 * groups * state
    rows = _ssd_rows(s, chunk)
    pad = (-s) % rows
    if pad:
        xbc, dt, dy = (t if t is None else jnp.pad(
            t, ((0, 0), (0, pad), (0, 0))) for t in (xbc, dt, dy))
    blocks, a_block = (s + pad) // rows, rows // chunk
    reverse = kept is not None

    def at(i):
        return blocks - 1 - i if reverse else i

    def lanes(w):
        return pl.BlockSpec((1, rows, w), lambda b, i: (b, at(i), 0))

    def whole(w):
        return pl.BlockSpec((1, w), lambda b, i: (0, 0))

    def summed(w):
        return pl.BlockSpec((1, 1, w), lambda b, i: (b, 0, 0))

    states = pl.BlockSpec((1, a_block, state, width),
                          lambda b, i: (b, at(i), 0, 0))
    operands = (xbc, dt.astype(f32), a.astype(f32)[None],
                jnp.repeat(d.astype(f32), width // heads)[None])
    in_specs = [lanes(lanes_in), lanes(heads), whole(heads), whole(width)]
    params = dict(heads=heads, groups=groups, chunk=chunk)
    shaped = jax.ShapeDtypeStruct
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"), **_SSD_COMPILER_PARAMS)
    scratch = [pltpu.VMEM((state, width), f32)]
    if not reverse:
        y, kept = pl.pallas_call(
            functools.partial(_ssd_fwd_kernel, **params),
            name="ssd_scan_fwd",
            out_shape=(shaped((batch, s + pad, width), f32),
                       shaped((batch, blocks * a_block, state, width), f32)),
            grid=(batch, blocks), in_specs=in_specs,
            out_specs=(lanes(width), states), scratch_shapes=scratch,
            compiler_params=compiler_params, interpret=interpret)(*operands)
        return y[:, :s], kept
    dxbc, ddt, da, dd = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, **params),
        name="ssd_scan_bwd",
        out_shape=(shaped(xbc.shape, xbc.dtype), shaped(dt.shape, f32),
                   shaped((batch, 1, heads), f32),
                   shaped((batch, 1, width), f32)),
        grid=(batch, blocks),
        in_specs=in_specs + [states, lanes(width)],
        out_specs=(lanes(lanes_in), lanes(heads), summed(heads),
                   summed(width)),
        scratch_shapes=scratch, compiler_params=compiler_params,
        interpret=interpret)(*operands, kept, dy.astype(f32))
    return (dxbc[:, :s], ddt[:, :s], jnp.sum(da, axis=(0, 1)),
            jnp.sum(dd.reshape(batch, heads, -1), axis=(0, 2)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ssd_scan(xbc, dt, a, d, groups, state, chunk, interpret):
    return _ssd_call(xbc, dt, a, d, groups, state, chunk, interpret)[0]


def _ssd_scan_fwd(xbc, dt, a, d, groups, state, chunk, interpret):
    y, kept = _ssd_call(xbc, dt, a, d, groups, state, chunk, interpret)
    return y, (xbc, dt, a, d, kept)


def _ssd_scan_bwd(groups, state, chunk, interpret, res, dy):
    *operands, kept = res
    # a cotangent in its operand's dtype
    return tuple(g.astype(t.dtype) for g, t in zip(
        _ssd_call(*operands, groups, state, chunk, interpret, kept, dy),
        operands))


_ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)


def ssd_scan(xbc, dt, a, d, groups: int, state: int, chunk: int):
    """y [B, S, H*P] float32 of the Mamba-2 recurrence above, D x
    included. xbc [B, S, H*P + 2 G*N]: x, B and C side by side along the
    lanes in the products' dtype, the ONE array the convolution left (the
    kernels take their lane ranges of a block: nothing is sliced or laid
    out again in HBM, no [B, S, H, P] view, and the backward writes
    their gradients as one array of the same form); dt [B, S, H] float32
    (after its softplus), a [H] (negative) and d [H]. The state is zero
    at a sample's start and the recurrence runs in chunks of ``chunk``
    positions. Decays, their running sums, every `exp` and the carried
    state are float32; the products take their operands in xbc's dtype
    and accumulate in float32. ONE kernel forward (`ssd_scan_fwd`, which
    keeps the state that entered every chunk, [B, S / chunk, N, H*P]
    float32) and ONE backward (`ssd_scan_bwd`). Caller checks
    `pallas_mode` and `ssd_shape_legal`; `ops.ssm.ssd_chunked` is the
    same in `jax.numpy`."""
    return _ssd_scan(xbc, dt, a, d, groups, state, chunk,
                     pallas_mode() == "interpret")


# ---------------------------------------------------------------------------
# The two passes of a hyper-connection around a sublayer (PR 64): the
# residual stream is n copies of the hidden width side by side along the
# lanes, x [T, n*C], and a sublayer reads its branch's input out of them
# and writes the branch's output back through three maps a position
# (`ops/hyper_connection.py` has the mathematics and the same passes in
# `jax.numpy`). Both are bound by bytes: 24 multiply-adds a lane of C
# against a stream that is read and written whole. `hc_read`: ONE pass
# over a block of rows gives the sum of squares, the n (n + 2) products
# with phi (one MXU pass: phi's float32 columns come as three bfloat16
# terms in three groups of 32 lanes, so the product is float32-exact for
# an x stored in bfloat16), the read map sigmoid(a r z + b) and
# h = sum_i H_pre[i] x_i; the RMS division is applied to the products,
# not to the stream. `hc_write`: x'_i = sum_j H_res[i, j] x_j + H_post[i] y
# in one pass, the maps a [rows, 128] float32 block whose columns are
# broadcast along the lanes. Each has ONE backward kernel; the read's
# takes the cotangent of the stream that the write's backward made
# (`hc_read_lanes` hands x through as an output, so that the two uses of
# one stream meet inside this kernel and not in a pass of XLA's), the
# branch's, and the products' (from the maps' `jax.vjp` outside).

HC_ROWS = 128         # positions a grid step
HC_GROUP = 32         # lanes a term of phi's three takes in the operand
MAX_HC_LANES = 32768  # n * C: a block holds its rows of every stream
_HC_COMPILER_PARAMS = dict(dimension_semantics=("parallel",),
                           vmem_limit_bytes=100 << 20)


def hc_shape_legal(rows: int, streams: int, width: int) -> bool:
    """What `hc_read_lanes` / `hc_write_lanes` take: whole blocks of
    rows, streams of whole 128-lane columns, and maps that with their
    witness fit a group of 32 lanes (n <= 4)."""
    return (rows > 0 and rows % HC_ROWS == 0 and width % LANES == 0
            and 1 <= streams and streams * (streams + 4) <= HC_GROUP
            and streams * width <= MAX_HC_LANES)


def _hc_chunk(width: int) -> int:
    return next(n for n in (512, 256, LANES) if width % n == 0)


def _fold(x):
    """[rows, k * 128] -> [rows, 128]: the 128-lane columns added up."""
    return functools.reduce(operator.add, (
        x[:, at:at + LANES] for at in range(0, x.shape[1], LANES)))


def _lane_sum(x):
    return jnp.sum(x, axis=-1, keepdims=True)


def _lane_iota(rows: int):
    return jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def hc_phi_operand(phi, dtype, transposed: bool = False):
    """phi [n*C, K] float32 as the kernels' MXU operand [n*C, 128] in
    ``dtype``: float32 as it is, its K columns first; bfloat16 as three
    terms (phi = hi + mid + lo to 2^-24) in the lane groups 0, 32, 64.
    ``transposed`` [128, n*C], for the backward's dz phi^T: the groups
    hold hi, hi and mid, against a dz laid as hi, lo, hi."""
    k = phi.shape[1]
    phi = phi.astype(jnp.float32)

    def group(term):
        return jnp.pad(term.astype(dtype), ((0, 0), (0, HC_GROUP - k)))

    if dtype == jnp.float32:
        terms = [phi]
    else:
        hi = phi.astype(dtype)
        rest = phi - hi.astype(jnp.float32)
        mid = rest.astype(dtype)
        lo = rest - mid.astype(jnp.float32)
        terms = [hi, hi, mid] if transposed else [hi, mid, lo]
    operand = jnp.concatenate([group(t) for t in terms], axis=1)
    operand = jnp.pad(operand, ((0, 0), (0, LANES - operand.shape[1])))
    return operand.T if transposed else operand


def _hc_coefficients(a, b):
    """[8, 128] float32: row 0 the products' scales a, row 1 the biases."""
    k = a.shape[0]
    return jnp.pad(jnp.stack([a, b]).astype(jnp.float32),
                   ((0, 6), (0, LANES - k)))


def _hc_read_map(zr, ab_ref, k: int):
    """(z, r, H_pre over the first lanes) of a block's products."""
    lane = _lane_iota(zr.shape[0])
    r = zr[:, LANES - 1:]
    z = jnp.where(lane < k, zr, 0.0)
    return z, r, _sigmoid(ab_ref[0:1, :] * r * z + ab_ref[1:2, :])


def _hc_read_kernel(x_ref, phi_ref, ab_ref, h_ref, zr_ref, *, n: int,
                    c: int, k: int, eps: float):
    """A block of rows of the stream, all n*C lanes: the sum of squares
    and the products with phi CHUNK lanes at a time, then h."""
    f32 = jnp.float32
    rows, chunk = x_ref.shape[0], _hc_chunk(c)
    squares = jnp.zeros((rows, LANES), f32)
    products = jnp.zeros((rows, LANES), f32)
    for at in range(0, n * c, chunk):
        xc = x_ref[:, at:at + chunk]
        products += _dot(xc, phi_ref[at:at + chunk, :], _NN)
        xf = xc.astype(f32)
        squares += _fold(xf * xf)
    r = jax.lax.rsqrt(_lane_sum(squares) / (n * c) + eps)
    lane = _lane_iota(rows)
    z = (products + pltpu.roll(products, LANES - HC_GROUP, 1)
         + pltpu.roll(products, LANES - 2 * HC_GROUP, 1))
    zr = jnp.where(lane == LANES - 1, r, jnp.where(lane < k, z, 0.0))
    zr_ref[...] = zr
    pre = _hc_read_map(zr, ab_ref, k)[2]
    for at in range(0, c, chunk):
        h_ref[:, at:at + chunk] = sum(
            pre[:, i:i + 1] * x_ref[:, i * c + at:i * c + at + chunk
                                    ].astype(f32)
            for i in range(n)).astype(h_ref.dtype)


def _hc_read_bwd_kernel(x_ref, dh_ref, dxo_ref, zr_ref, dzr_ref, ab_ref,
                        phit_ref, dx_ref, dl_ref, *, n: int, c: int,
                        k: int):
    """dx = dx_out + H_pre[i] dh + dz phi^T + (dr dr/dx) x and the read
    map's logits' gradient dl (its first n lanes), where dz, dr are the
    products' and the statistic's cotangents: what came in (dzr: the
    other two maps') plus the read map's own, dl a r and sum dl a z."""
    f32 = jnp.float32
    rows, chunk = x_ref.shape[0], _hc_chunk(c)
    lane = _lane_iota(rows)
    z, r, pre = _hc_read_map(zr_ref[...], ab_ref, k)
    dots = [jnp.zeros((rows, LANES), f32) for _ in range(n)]
    for at in range(0, c, chunk):
        dh = dh_ref[:, at:at + chunk].astype(f32)
        for i in range(n):
            dots[i] += _fold(dh * x_ref[:, i * c + at:i * c + at + chunk
                                        ].astype(f32))
    d_pre = sum(jnp.where(lane == i, _lane_sum(dots[i]), 0.0)
                for i in range(n))
    dl = d_pre * pre * (1.0 - pre)
    dl_ref[...] = dl
    a = ab_ref[0:1, :]
    dzr = dzr_ref[...]
    dz = jnp.where(lane < k, dzr, 0.0) + dl * a * r
    dr = dzr[:, LANES - 1:] + _lane_sum(dl * a * z)
    of_x = -dr * r * r * r / (n * c)
    if x_ref.dtype == f32:
        packed = dz
    else:
        hi = dz.astype(x_ref.dtype).astype(f32)
        packed = (hi + pltpu.roll(dz - hi, HC_GROUP, 1)
                  + pltpu.roll(hi, 2 * HC_GROUP, 1))
    packed = packed.astype(x_ref.dtype)
    for i in range(n):
        for at in range(0, c, chunk):
            lanes = slice(i * c + at, i * c + at + chunk)
            dx_ref[:, lanes] = (
                dxo_ref[:, lanes].astype(f32)
                + pre[:, i:i + 1] * dh_ref[:, at:at + chunk].astype(f32)
                + of_x * x_ref[:, lanes].astype(f32)
                + _dot(packed, phit_ref[:, lanes], _NN)
            ).astype(dx_ref.dtype)


def _hc_maps_columns(m_ref, n: int):
    """(H_post[i], H_res[i][j]) as [rows, 1] columns of the maps' block."""
    m = m_ref[...]
    post = [m[:, n + i:n + i + 1] for i in range(n)]
    res = [[m[:, 2 * n + i * n + j:2 * n + i * n + j + 1] for j in range(n)]
           for i in range(n)]
    return post, res


def _hc_write_kernel(x_ref, y_ref, m_ref, o_ref, *, n: int, c: int):
    f32 = jnp.float32
    chunk = _hc_chunk(c)
    post, res = _hc_maps_columns(m_ref, n)
    for at in range(0, c, chunk):
        y = y_ref[:, at:at + chunk].astype(f32)
        xs = [x_ref[:, j * c + at:j * c + at + chunk].astype(f32)
              for j in range(n)]
        for i in range(n):
            o_ref[:, i * c + at:i * c + at + chunk] = (
                post[i] * y + sum(res[i][j] * xs[j] for j in range(n))
            ).astype(o_ref.dtype)


def _hc_write_bwd_kernel(x_ref, y_ref, m_ref, g_ref, dx_ref, dy_ref, dm_ref,
                         *, n: int, c: int):
    """With g the cotangent of the new stream: dx_j = sum_i H_res[i, j]
    g_i, dy = sum_i H_post[i] g_i, and a position's d H_post[i] = <g_i,
    y>, d H_res[i, j] = <g_i, x_j>, summed over the lanes here."""
    f32 = jnp.float32
    rows, chunk = x_ref.shape[0], _hc_chunk(c)
    post, res = _hc_maps_columns(m_ref, n)
    d_post = [jnp.zeros((rows, LANES), f32) for _ in range(n)]
    d_res = [[jnp.zeros((rows, LANES), f32) for _ in range(n)]
             for _ in range(n)]
    for at in range(0, c, chunk):
        y = y_ref[:, at:at + chunk].astype(f32)
        xs = [x_ref[:, j * c + at:j * c + at + chunk].astype(f32)
              for j in range(n)]
        gs = [g_ref[:, i * c + at:i * c + at + chunk].astype(f32)
              for i in range(n)]
        for j in range(n):
            dx_ref[:, j * c + at:j * c + at + chunk] = sum(
                res[i][j] * gs[i] for i in range(n)).astype(dx_ref.dtype)
        dy_ref[:, at:at + chunk] = sum(
            post[i] * gs[i] for i in range(n)).astype(dy_ref.dtype)
        for i in range(n):
            d_post[i] += _fold(gs[i] * y)
            for j in range(n):
                d_res[i][j] += _fold(gs[i] * xs[j])
    lane = _lane_iota(rows)
    dm = jnp.zeros((rows, LANES), f32)
    for i in range(n):
        dm = jnp.where(lane == n + i, _lane_sum(d_post[i]), dm)
        for j in range(n):
            dm = jnp.where(lane == 2 * n + i * n + j,
                           _lane_sum(d_res[i][j]), dm)
    dm_ref[...] = dm


def _hc_maps_steps(lt, n: int, iters: int, eps: float, clamp):
    """The three maps of a block of positions laid along the LANES: lt
    [K.., R] float32 logits (row k the k-th product's) -> [K, R], rows
    H_pre, H_post and H_res row-major. A Sinkhorn step is elementwise
    work on n arrays [n, R] (row i of the matrix: its columns on the
    sublanes) and sums over them (a column's) or over the sublanes (a
    row's)."""
    pre = _sigmoid(lt[0:n])
    post = 2.0 * _sigmoid(lt[n:2 * n])
    ms = [jnp.exp(jnp.clip(lt[(2 + i) * n:(3 + i) * n], *clamp))
          for i in range(n)]
    for _ in range(iters):
        columns = functools.reduce(operator.add, ms) + eps
        ms = [m / columns for m in ms]
        ms = [m / (jnp.sum(m, axis=0, keepdims=True) + eps) for m in ms]
    return jnp.concatenate([pre, post] + ms, axis=0)


def _hc_maps_kernel(l_ref, *refs, n: int, iters: int, eps: float, clamp,
                    transposed: bool):
    """A block [R, 128] of logits -> the maps [R, 128], through the
    transposed form [128, R] (positions along the lanes). ``transposed``:
    the backward, the steps' own `jax.vjp` formed again from the logits."""
    k = n * (n + 2)
    rows = l_ref.shape[0]
    lt = l_ref[...].T[:HC_GROUP]

    def steps(t):
        return _hc_maps_steps(t, n, iters, eps, clamp)

    if transposed:
        g_ref, o_ref = refs
        (out,) = jax.vjp(steps, lt)[1](g_ref[...].T[:k])
    else:
        (o_ref,) = refs
        out = steps(lt)
        # the witness, in the 2 n lanes after the maps: how far a
        # position's rows and columns of H_res sum from one
        ms = [out[(2 + i) * n:(3 + i) * n] for i in range(n)]
        out = jnp.concatenate(
            [out] + [jnp.abs(jnp.sum(m, axis=0, keepdims=True) - 1.0)
                     for m in ms]
            + [jnp.abs(functools.reduce(operator.add, ms) - 1.0)], axis=0)
    o_ref[...] = jnp.concatenate(
        [out, jnp.zeros((LANES - out.shape[0], rows), jnp.float32)],
        axis=0).T


def _hc_maps_call(logits, g, n, iters, eps, clamp, interpret):
    rows = logits.shape[0]
    block = next(r for r in (512, 256, HC_ROWS) if rows % r == 0)
    spec = pl.BlockSpec((block, LANES), lambda t: (t, 0))
    return pl.pallas_call(
        functools.partial(_hc_maps_kernel, n=n, iters=iters, eps=eps,
                          clamp=clamp, transposed=g is not None),
        name="hc_maps" if g is None else "hc_maps_bwd",
        out_shape=jax.ShapeDtypeStruct(logits.shape, jnp.float32),
        grid=(rows // block,),
        in_specs=[spec] * (1 if g is None else 2), out_specs=spec,
        compiler_params=pltpu.CompilerParams(**_HC_COMPILER_PARAMS),
        interpret=interpret)(*((logits,) if g is None else (logits, g)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _hc_maps(logits, n, iters, eps, clamp, interpret):
    return _hc_maps_call(logits, None, n, iters, eps, clamp, interpret)


def _hc_maps_fwd(logits, n, iters, eps, clamp, interpret):
    return _hc_maps_call(logits, None, n, iters, eps, clamp,
                         interpret), logits


def _hc_maps_bwd(n, iters, eps, clamp, interpret, logits, g):
    return (_hc_maps_call(logits, g.astype(jnp.float32), n, iters, eps,
                          clamp, interpret),)


_hc_maps.defvjp(_hc_maps_fwd, _hc_maps_bwd)


def hc_maps_lanes(logits, n: int, iters: int, eps: float, clamp):
    """maps [T, 128] float32 for logits [T, 128] float32 whose first K =
    n (n + 2) lanes hold a position's products scaled and biased: H_pre
    = sigmoid, H_post = 2 sigmoid, H_res = ``iters`` Sinkhorn steps
    (columns, then rows; ``eps`` in every division) on exp(clip(.,
    *clamp)); the next 2 n lanes hold |a row's sum - 1| and |a column's
    sum - 1| of H_res (a witness, no gradient), the rest zero. One kernel
    each way: XLA
    makes a fusion of every half step and of its gradient, a hundred and
    more launches a sublayer over arrays of a few hundred kilobytes.
    Caller checks `pallas_mode` and `hc_shape_legal`."""
    return _hc_maps(logits, n, iters, eps, tuple(clamp),
                    pallas_mode() == "interpret")


def _hc_rows(lanes: int):
    return pl.BlockSpec((HC_ROWS, lanes), lambda t: (t, 0))


def _hc_whole(rows: int, lanes: int):
    return pl.BlockSpec((rows, lanes), lambda t: (0, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _hc_read(x, phi, a, b, n, eps, interpret):
    return _hc_read_fwd(x, phi, a, b, n, eps, interpret)[0]


def _hc_read_fwd(x, phi, a, b, n, eps, interpret):
    rows, width = x.shape
    c, k = width // n, phi.shape[1]
    h, zr = pl.pallas_call(
        functools.partial(_hc_read_kernel, n=n, c=c, k=k, eps=eps),
        name="hc_read",
        out_shape=(jax.ShapeDtypeStruct((rows, c), x.dtype),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.float32)),
        grid=(rows // HC_ROWS,),
        in_specs=[_hc_rows(width), _hc_whole(width, LANES),
                  _hc_whole(8, LANES)],
        out_specs=(_hc_rows(c), _hc_rows(LANES)),
        compiler_params=pltpu.CompilerParams(**_HC_COMPILER_PARAMS),
        interpret=interpret)(x, hc_phi_operand(phi, x.dtype),
                             _hc_coefficients(a, b))
    return (h, zr, x), (x, zr, phi, a, b)


def _hc_read_bwd(n, eps, interpret, kept, cotangents):
    x, zr, phi, a, b = kept
    dh, dzr, dxo = cotangents
    rows, width = x.shape
    c, k = width // n, phi.shape[1]
    dx, dl = pl.pallas_call(
        functools.partial(_hc_read_bwd_kernel, n=n, c=c, k=k),
        name="hc_read_bwd",
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.float32)),
        grid=(rows // HC_ROWS,),
        in_specs=[_hc_rows(width), _hc_rows(c), _hc_rows(width),
                  _hc_rows(LANES), _hc_rows(LANES), _hc_whole(8, LANES),
                  _hc_whole(LANES, width)],
        out_specs=(_hc_rows(width), _hc_rows(LANES)),
        compiler_params=pltpu.CompilerParams(**_HC_COMPILER_PARAMS),
        interpret=interpret)(
            x, dh, dxo.astype(x.dtype), zr, dzr.astype(jnp.float32),
            _hc_coefficients(a, b),
            hc_phi_operand(phi, x.dtype, transposed=True))
    # the leaves' gradients, over the rows: phi's is a product of the
    # stream with the products' whole cotangent (its float32 as two terms
    # of the stream's dtype, side by side), the read map's scale and bias
    # take the sums of its logits' gradient
    r, z, dl = zr[:, LANES - 1:], zr[:, :k], dl[:, :k]
    dz = dzr[:, :k].astype(jnp.float32) + dl * a * r
    hi = dz.astype(x.dtype)
    terms = jnp.concatenate(
        [hi, (dz - hi.astype(jnp.float32)).astype(x.dtype)], axis=1)
    both = jax.lax.dot_general(x, terms, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    return (dx, (both[:, :k] + both[:, k:]).astype(phi.dtype),
            jnp.sum(dl * r * z, axis=0).astype(a.dtype),
            jnp.sum(dl, axis=0).astype(b.dtype))


_hc_read.defvjp(_hc_read_fwd, _hc_read_bwd)


def hc_read_lanes(x, phi, a, b, n: int, eps: float):
    """(h [T, C], zr [T, 128] float32, x) for the stream x [T, n*C]: with
    r = (mean(x^2) + eps)^-1/2 and z = x phi [T, K] (phi float32; K = n (n
    + 2) columns: the read map's n, the write map's n, the mixing map's
    n * n), h = sum_i sigmoid(a_i r z_i + b_i) x_i. zr holds z in its
    first K lanes and r in its last; the caller forms the other maps
    from them. x comes back as it went in: who reads THAT output hands
    its cotangent to this function's backward kernel, which adds it
    inside. Caller checks `pallas_mode` and `hc_shape_legal`."""
    return _hc_read(x, phi, a, b, n, eps, pallas_mode() == "interpret")


def _hc_write_call(x, y, maps, g, n, interpret):
    rows, width = x.shape
    c = width // n
    specs = [_hc_rows(width), _hc_rows(c), _hc_rows(LANES)]
    if g is None:
        return pl.pallas_call(
            functools.partial(_hc_write_kernel, n=n, c=c), name="hc_write",
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(rows // HC_ROWS,), in_specs=specs,
            out_specs=_hc_rows(width),
            compiler_params=pltpu.CompilerParams(**_HC_COMPILER_PARAMS),
            interpret=interpret)(x, y, maps)
    return pl.pallas_call(
        functools.partial(_hc_write_bwd_kernel, n=n, c=c),
        name="hc_write_bwd",
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(maps.shape, jnp.float32)),
        grid=(rows // HC_ROWS,), in_specs=specs + [_hc_rows(width)],
        out_specs=(_hc_rows(width), _hc_rows(c), _hc_rows(LANES)),
        compiler_params=pltpu.CompilerParams(**_HC_COMPILER_PARAMS),
        interpret=interpret)(x, y, maps, g.astype(x.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _hc_write(x, y, maps, n, interpret):
    return _hc_write_call(x, y, maps, None, n, interpret)


def _hc_write_fwd(x, y, maps, n, interpret):
    return _hc_write_call(x, y, maps, None, n, interpret), (x, y, maps)


def _hc_write_bwd(n, interpret, kept, g):
    return _hc_write_call(*kept, g, n, interpret)


_hc_write.defvjp(_hc_write_fwd, _hc_write_bwd)


def hc_write_lanes(x, y, maps, n: int):
    """x' [T, n*C], x'_i = sum_j H_res[i, j] x_j + H_post[i] y, for the
    stream x, the branch's output y [T, C] and maps [T, 128] float32
    (lanes n..2n-1 H_post, lanes 2n + i n + j H_res[i, j]; the first n,
    H_pre, are not read). Its backward is one kernel too. Caller checks
    `pallas_mode` and `hc_shape_legal`."""
    return _hc_write(x, y, maps.astype(jnp.float32), n,
                     pallas_mode() == "interpret")


def pallas_mode() -> str:
    """'tpu' (compile), 'interpret' (CPU emulation for tests), or 'off'."""
    env = os.environ.get("FLEXFLOW_TPU_PALLAS", "auto")
    if env in ("interpret", "off"):
        return env
    return "tpu" if jax.default_backend() == "tpu" else "off"


# From S = 512 up the search may choose flash on hardware. The bound is
# from a chip round older than the benchmark (flash forward+backward
# 601 us against the einsum path's 1124 us at b8 h16 s512 d64, and the
# einsum path out of memory at S = 8192) and was not re-measured in PR
# 28, whose kernels take 54% of the time of that round's at S = 512;
# whether shorter sequences would gain now is open.
MIN_SEQ_FOR_FLASH = 512


def flash_shape_legal(seq_len: int, head_dim: int, num_heads: int,
                      rope_dim: int = 0) -> bool:
    """The shape half of the flash gate, platform aside: Q-block tile
    divisibility, sublane-aligned head dim, the VMEM-budget upper bounds
    past which the compiler refuses the kernels, and heads that tile the
    [B, S, H*D] operands' lanes in column blocks. The rule for the last
    is the BlockSpec's own: a block's lanes are a multiple of 128 or the
    whole row, so the heads of a block (``_heads_per_block``) have to
    divide the heads and fill 128 lanes exactly, unless one block is the
    whole row (H*D <= 128, or a single head). 16 heads of 64 and 4 of
    128 pass; 3 heads of 64 or 4 of 96 are refused and run the einsum
    path. With ``rope_dim`` (the two-part score: a head's query and key
    are ``head_dim`` lanes and ``rope_dim`` more, its value ``head_dim``)
    a head is one block of 128 lanes and the heads' rotated parts tile
    128-lane blocks among themselves: 32 heads of 128 + 64 pass, a
    192-wide head as ONE width does not. A head wider than 128 lanes
    is 256 exactly, two lane blocks (PR 58: 16 heads of 256 pass, at any
    number of heads; 192 or 384 do not); the kernels for it take a
    causal or plain mask and a window and nothing else, which the
    attention op's `route` holds. The native ``kernel_gate``
    (native/ffs_strategy.hpp) admits the same shapes."""
    if num_heads <= 0:
        return False
    if rope_dim and (head_dim != LANES or rope_dim % 8 or LANES % rope_dim
                     or num_heads % (LANES // rope_dim)):
        return False
    if head_dim > LANES and head_dim != 2 * LANES:
        return False    # past one lane block a head is exactly two
    hpb = _heads_per_block(num_heads, head_dim)
    return (seq_len % BLK_Q == 0 and head_dim % 8 == 0
            and seq_len <= MAX_FLASH_SEQ and head_dim <= MAX_FLASH_HEAD_DIM
            and num_heads % hpb == 0
            and ((hpb * head_dim) % LANES == 0 or hpb == num_heads))


def flash_attention_available(seq_len: int, head_dim: int,
                              num_heads: int, rope_dim: int = 0) -> bool:
    mode = pallas_mode()
    if mode == "off" or not flash_shape_legal(seq_len, head_dim, num_heads,
                                              rope_dim):
        return False
    # interpret mode (tests) exercises any legal shape; on hardware only
    # take over where the kernel beats XLA
    return mode == "interpret" or seq_len >= MIN_SEQ_FOR_FLASH


def grouped_kv_shape_legal(num_heads: int, num_kv_heads: int,
                           head_dim: int) -> bool:
    """Whether the flash kernels take grouped-query keys and values as
    [B, S, Hk*D] (PR 43), the shape half of the rule: whole groups, and
    heads that fill 128-lane column blocks such that every query column
    block reads ONE KV head of the K / V lane block its BlockSpec picks
    by ``j // rep``. A head of 128 is a column block of its own. At
    heads of 64 (PR 47) a column block holds two query heads and a K / V
    lane block two KV heads: the two query heads share a KV head where
    the group's size is even, and k and v are whole lane blocks where
    the KV heads are (32 : 8 and 4 : 2 pass; 6 : 2, a group of 3, and
    14 : 7, an odd Hk, repeat K and V as before). The kernels then take
    that head's half of the lane block (``_own_kv_head``,
    ``_half_moved``).

    v5e, lfm2's op whole (32 : 8 heads of 64, 16,384 positions, the
    heads' norm, whole rotary, causal; bf16; forward and backward of one
    attention op, device ms; PR 47, `scripts/flash_lab.py --only
    grouped.lfm2`), the flash forward / backward kernel and what else
    the form runs. As shipped until PR 47 (`repeated_view`: the norm and
    rotary over a [B, S, H, 64] view, K and V repeated): **53.14**,
    kernels 16.64 / 22.48, the projections' fusions 5.63, XLA's passes
    between them 7.9 (copy 2.75, multiply_reduce 0.90, add_convert 0.83,
    broadcast_multiply 0.80, slice_negate 0.68, add_add 0.48,
    convert_convert 0.42, broadcast 0.41, reduce 0.36, pad_maximum
    0.30). The pass in lanes, K and V still repeated (`repeated`):
    **47.29**, kernels 16.64 / 22.47, `rotary_lanes` 0.41 + 0.77, the
    repeat and its sum 1.24 (copy 0.67, reduce 0.36, bitcast_convert
    0.21). K and V at the KV heads with the KV head laid twice a
    fetched CHUNK in the forward: **47.52**, kernels 40.36 together
    (+1.25: a roll and a select of a [1024, 128] K and V tile 8,704
    times an op do not hide under the MXU's passes). **As shipped**
    (`grouped`: the forward moves the query block's heads once a grid
    step, the backward lays the K block's head twice once a grid step):
    **46.40**, kernels 16.68 / 22.56, fusions 5.71, `rotary_lanes`
    0.42 + 0.82, everything else 0.2: 6.74 ms an op under the shipped
    form, of which the view forms 5.85 and the repeat 0.89."""
    hpb = (1 if head_dim == 2 * LANES   # a column block of its own (PR 58)
           else LANES // head_dim if head_dim in (LANES, LANES // 2) else 0)
    return (0 < num_kv_heads < num_heads and num_heads % num_kv_heads == 0
            and hpb > 0 and (num_heads // num_kv_heads) % hpb == 0
            and num_kv_heads % hpb == 0)


def flash_attention(q, k, v, num_heads: int, causal: bool = False,
                    window: int = 0, block_diffusion=None, rope=None,
                    num_kv_heads=None):
    """q, k, v: [B, S, H*D] -> [B, S, H*D], the heads side by side along
    the lanes as the projections' plain 2-D products leave them, so that
    no layout change sits between a projection and a kernel and no
    operand's minor dimension is narrower than a vreg. Caller checks
    flash_attention_available first; self-attention only (Sq == Sk).
    Who holds [B, H, S, D] converts with ``merge_heads`` /
    ``split_heads`` at its own boundary. ``rope`` = (q_rope [B, S, H*R],
    k_rope [B, S, R]): the two-part score (``_flash_fwd``); the gradient
    of k_rope is the sum over the heads.

    ``num_kv_heads`` < H (PR 43; caller checks ``grouped_kv_shape_legal``):
    k and v are [B, S, Hk*D], a K/V head shared by H / Hk consecutive
    query heads, and are never repeated: the kernels read a group's
    block for each of its heads (at heads of 64 a half of a lane block
    for each of its column blocks, PR 47). Their gradients are the
    groups' sums in float32 (whatever dtype k and v come in, the kernels
    read them rounded to q's); ``num_kv_heads`` None or H is the call
    without it, jaxpr and all."""
    static = (num_heads, causal, pallas_mode() == "interpret", window,
              tuple(block_diffusion) if block_diffusion else None,
              tuple(rope) if rope is not None else None)
    if num_kv_heads in (None, num_heads):
        return _flash(q, k, v, *static)
    return _flash(q, k.astype(jnp.float32), v.astype(jnp.float32), *static,
                  num_kv_heads)


def flash_attention_sharded(q, k, v, num_heads: int, mesh, batch_axis=None,
                            head_axis=None, causal: bool = False,
                            window: int = 0, block_diffusion=None,
                            rope=None, num_kv_heads=None):
    """Flash attention inside a GSPMD-sharded jit: a bare ``pallas_call``
    is an unpartitionable custom call to the partitioner, so wrap it in
    ``shard_map`` over the mesh axes the batch/head dims are sharded on —
    each device runs the kernel on its local [B/dp, S, (H/mp)*D] block
    (scores never cross shards; no collectives needed; the caller keeps
    a head axis only where the local heads still tile the lanes, and
    under grouped keys, ``num_kv_heads``, only where it divides the KV
    heads: a shard then holds whole groups). Axes not named stay
    replicated, which GSPMD enforces on entry."""
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axis, None, head_axis)
    shards = mesh.shape[head_axis] if head_axis else 1
    assert not num_kv_heads or num_kv_heads % shards == 0, num_kv_heads
    fn = functools.partial(flash_attention, num_heads=num_heads // shards,
                           causal=causal, window=window,
                           block_diffusion=block_diffusion,
                           num_kv_heads=num_kv_heads and num_kv_heads // shards)
    if rope is not None:
        # the one rotated key has no head axis to shard: batch axes only
        assert head_axis is None, "latent attention: no head axis here"
        return jax.shard_map(
            lambda q, k, v, qr, kr: fn(q, k, v, rope=(qr, kr)), mesh=mesh,
            in_specs=(spec,) * 5, out_specs=spec, check_vma=False)(
                q, k, v, *rope)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
