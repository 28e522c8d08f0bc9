"""`MultiHeadAttention.route` (PR 44): the ONE function that decides which
core an attention op runs and in which operand form. For every kind of op
the forward's recorded route, the route asked cold of a fresh op,
`selected_impl` and `traced_gauges` say the same thing.

Forwards are traced (`jax.eval_shape`), never run: the kernels are
interpreted on the CPU and only the decision is under test. Small shapes.
"""

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.ffconst import DataType, OperatorType
from flexflow_tpu.layer import Layer
from flexflow_tpu.machine import make_mesh
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops.attention import AttentionRoute
from flexflow_tpu.ops.base import OpContext, OpRegistry

LATENT = dict(q_lora_rank=24, kv_lora_rank=16, causal=True, bias=False)

# name: (op properties, (batch, Sq, Sk, hidden), mesh axes or None, Pallas
# mode, training, the fields of the route that this kind of op is about)
OPS = {
    "plain_12_heads_of_64_at_512": (
        dict(num_heads=12), (1, 512, 512, 768), None, "interpret", True,
        dict(core="flash", scope="plain", blocked=None, shard_axes=None,
             grouped_kv=False, rotary_in_lanes=False, kv_blocks=(1, 1, 0),
             window_pairs=None)),
    "causal_window": (
        dict(num_heads=4, causal=True, window=128, rope=True),
        (1, 512, 512, 256), None, "interpret", True,
        # four heads of 64: two whole 128-lane columns (PR 47)
        dict(core="flash", scope="window", rotary_in_lanes=True,
             one_span=False,            # the whole-tile kernels
             super_block=False,
             kv_blocks=(*pk.kv_blocks(512, True, 128),
                        pk.kv_blocks_masked(512, True, 128)),
             window_pairs=(pk.visited_pairs(512, True, 128),
                           2 * pk.visible_pairs(512, True, 128)))),
    # PR 46: past the whole-tile kernels a window narrower than a tile
    # takes the one-span form, and the counts are that form's
    "narrow_window_one_span": (
        dict(num_heads=4, causal=True, window=128, rope=True),
        (1, 1536, 1536, 64), None, "interpret", True,
        dict(core="flash", scope="window", one_span=True,
             super_block=False,
             kv_blocks=(6, 6 * 4, 6),
             window_pairs=(1536 * (384 + 256),
                           2 * pk.visible_pairs(1536, True, 128)))),
    "wide_window_chunk_loop": (
        dict(num_heads=4, causal=True, window=800, rope=True),
        (1, 1536, 1536, 64), None, "interpret", True,
        # a window that ends inside a K chunk: one block a grid step
        dict(core="flash", scope="window", one_span=False,
             super_block=False,
             kv_blocks=(*pk.kv_blocks(1536, True, 800),
                        pk.kv_blocks_masked(1536, True, 800)))),
    # PR 51: the chunk loop takes a super-block of Q blocks a grid step
    # where that is a K chunk (512 of 1536: two blocks of 256)
    "causal_chunk_loop_super_block": (
        dict(num_heads=4, causal=True, rope=True),
        (1, 1536, 1536, 64), None, "interpret", True,
        dict(core="flash", scope="full", one_span=False, super_block=True,
             kv_blocks=(*pk.kv_blocks(1536, True),
                        pk.kv_blocks_masked(1536, True)))),
    "window_of_whole_chunks_super_block": (
        dict(num_heads=1, head_dim=128, causal=True, window=1024),
        (1, 2048, 2048, 64), None, "interpret", True,
        dict(core="flash", scope="window", one_span=False,
             super_block=True)),
    "latent_chunk_loop_super_block": (
        dict(LATENT, num_heads=2, head_dim=128, qk_rope_head_dim=64,
             rope=True), (1, 2048, 2048, 64), None, "interpret", True,
        dict(core="flash", scope="latent", one_span=False,
             super_block=True)),
    # blocks of 256 against chunks of 256 (S = 1280): nothing to gather
    "chunk_of_one_block": (
        dict(num_heads=4, causal=True), (1, 1280, 1280, 64), None,
        "interpret", True,
        dict(core="flash", one_span=False, super_block=False)),
    "block_diffusion": (
        dict(num_heads=2, head_dim=128, block_diffusion=(128, 4), rope=True,
             rope_wrap=128, qk_norm=True), (1, 256, 256, 64), None,
        "interpret", True,
        dict(core="flash", scope="block_diffusion", rotary_in_lanes=True,
             window_pairs=None)),
    "gqa_8_of_32_heads_of_128_grouped": (
        dict(num_heads=32, num_kv_heads=8, head_dim=128, causal=True,
             rope=True), (1, 128, 128, 64), None, "interpret", True,
        dict(core="flash", scope="full", grouped_kv=True,
             rotary_in_lanes=True)),
    # PR 47: lfm2's op. Two heads of 64 a 128-lane column, both of one
    # KV head, which is half a K / V lane block: the lane-dense route
    "gqa_8_of_32_heads_of_64_grouped": (
        dict(num_heads=32, num_kv_heads=8, head_dim=64, causal=True,
             rope=True, qk_norm=True), (1, 128, 128, 64), None, "interpret",
        True,
        dict(core="flash", scope="full", grouped_kv=True,
             rotary_in_lanes=True)),
    # a group of 3 heads of 64: a column block would meet two KV heads,
    # the repeat stays; the pass does not ask about groups
    "gqa_2_of_6_heads_of_64_repeated": (
        dict(num_heads=6, num_kv_heads=2, head_dim=64, causal=True,
             rope=True), (1, 128, 128, 64), None, "interpret", True,
        dict(core="flash", scope="full", grouped_kv=False,
             rotary_in_lanes=True)),
    # three KV heads of 64 are one and a half lane blocks: neither form
    "gqa_3_of_6_heads_of_64_view_and_repeat": (
        dict(num_heads=6, num_kv_heads=3, head_dim=64, causal=True,
             rope=True), (1, 128, 128, 64), None, "interpret", True,
        dict(core="flash", scope="full", grouped_kv=False,
             rotary_in_lanes=False)),
    # under a head axis a shard holds whole K / V lane blocks or repeats
    "head_axis_keeps_whole_lane_blocks_of_64": (
        dict(num_heads=16, num_kv_heads=8, head_dim=64, causal=True,
             head_parallel="model"), (2, 128, 128, 64),
        {"data": 2, "model": 4}, "interpret", True,
        dict(core="flash", shard_axes=("data", "model"), grouped_kv=True)),
    "head_axis_splits_a_lane_block_of_64": (
        dict(num_heads=16, num_kv_heads=4, head_dim=64, causal=True,
             head_parallel="model"), (2, 128, 128, 64),
        {"data": 2, "model": 4}, "interpret", True,
        dict(core="flash", shard_axes=("data", "model"), grouped_kv=False)),
    "latent_32_heads_of_128_and_64": (
        dict(LATENT, num_heads=32, head_dim=128, qk_rope_head_dim=64,
             rope=True), (1, 128, 128, 64), None, "interpret", True,
        dict(core="flash", scope="latent", blocked=None, grouped_kv=False,
             rotary_in_lanes=False)),
    "head_axis_splits_a_group": (
        dict(num_heads=8, num_kv_heads=2, head_dim=128, causal=True,
             head_parallel="model"), (2, 128, 128, 64),
        {"data": 2, "model": 4}, "interpret", True,
        dict(core="flash", shard_axes=("data", "model"), grouped_kv=False)),
    "head_axis_keeps_whole_groups": (
        dict(num_heads=8, num_kv_heads=4, head_dim=128, causal=True,
             head_parallel="model"), (2, 128, 128, 64),
        {"data": 2, "model": 4}, "interpret", True,
        dict(core="flash", shard_axes=("data", "model"), grouped_kv=True)),
    "pinned_einsum": (
        dict(num_heads=4, kernel_impl="einsum"), (1, 128, 128, 64), None,
        "interpret", True,
        dict(core="einsum", blocked=None, fallback=None, kv_blocks=None)),
    "training_dropout": (
        dict(num_heads=4, dropout=0.1), (1, 128, 128, 64), None,
        "interpret", True,
        dict(core="einsum", blocked="dropout", fallback=None)),
    "dropout_at_inference": (
        dict(num_heads=4, dropout=0.1), (1, 128, 128, 64), None,
        "interpret", False, dict(core="flash", blocked=None)),
    "searched_flash_with_pallas_off": (
        dict(num_heads=4, kernel_impl="flash"), (1, 128, 128, 64), None,
        "off", True,
        dict(core="einsum", blocked=None,
             fallback="flash unavailable at runtime (seq=128, head_dim=16, "
                      "heads=4) — einsum executed instead")),
    "searched_flash_on_cross_attention": (
        dict(num_heads=4, kernel_impl="flash"), (1, 128, 256, 64), None,
        "interpret", True,
        dict(core="einsum", blocked="cross_attention",
             fallback="flash has no lowering for this forward "
                      "(dropout_rate=0.0, Sq=128, Sk=256) — einsum executed "
                      "instead")),
    "latent_rotated_width_the_kernels_refuse": (
        dict(LATENT, num_heads=2, head_dim=128, qk_rope_head_dim=48),
        (1, 128, 128, 64), None, "interpret", True,
        dict(core="einsum", blocked="shape")),
    "ring_over_a_seq_axis": (
        dict(num_heads=4, causal=True, seq_parallel="seq"),
        (1, 128, 128, 64), {"seq": 2}, "interpret", True,
        dict(core="ring", blocked=None, kv_blocks=None)),
    # FAILS AT THE PARENT (a086560): `selected_impl` answered `ring`
    # wherever the seq axis is larger than 1, forward also asked Sq == Sk
    # and ran einsum
    "cross_attention_on_a_seq_mesh": (
        dict(num_heads=4, seq_parallel="seq"), (1, 128, 256, 64),
        {"seq": 2}, "interpret", True,
        dict(core="einsum", blocked="cross_attention", fallback=None)),
}


def make_op(props, shapes):
    b, sq, sk, e = shapes
    layer = Layer(OperatorType.MULTIHEAD_ATTENTION, "attn", [],
                  data_type=DataType.FLOAT)
    layer.properties.update(dict(embed_dim=e), **props)
    return OpRegistry.create(layer, [(b, sq, e), (b, sk, e), (b, sk, e)])


@pytest.mark.parametrize("case", list(OPS))
def test_forward_route_selected_impl_and_gauges_agree(case, monkeypatch):
    props, shapes, axes, mode, training, want = OPS[case]
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
    b, sq, sk, e = shapes
    op, cold = make_op(props, shapes), make_op(props, shapes)
    assert op._route is None
    untraced = op.traced_gauges()
    assert not any(v for k, v in untraced.items()
                   if not k.endswith("_attention_ops")), untraced

    mesh = make_mesh(8 if len(axes) > 1 else 2, axes) if axes else None
    ctx = OpContext(training=training, mesh=mesh, rng=jax.random.PRNGKey(0),
                    compute_dtype=jnp.bfloat16)
    params = jax.eval_shape(op.init_params, jax.random.PRNGKey(0))
    q = jax.ShapeDtypeStruct((b, sq, e), jnp.float32)
    kv = jax.ShapeDtypeStruct((b, sk, e), jnp.float32)
    out = jax.eval_shape(lambda p, q, kv: op.forward(p, [q, kv, kv], ctx),
                         params, q, kv)
    assert out[0].shape == (b, sq, e)

    # one decision: traced, asked cold of another instance, and its core
    route = op._route
    assert isinstance(route, AttentionRoute)
    assert route == cold.route(axes or {}, training)
    assert cold._route is None          # asking records nothing
    assert cold.selected_impl(axes, training=training) == route.core
    assert {k: getattr(route, k) for k in want} == want
    assert op._kernel_fallback == route.fallback

    # the gauges are a view of it
    flash = route.core == "flash"
    assert (route.kv_blocks is not None) == flash
    assert op.traced_gauges() == {
        "executor.flash_lane_dense_ops": int(flash),
        "executor.rotary_lane_dense_ops": int(route.rotary_in_lanes),
        "executor.flash_grouped_kv_ops": int(route.grouped_kv),
        "executor.flash_one_span_ops": int(route.one_span),
        "executor.flash_super_block_ops": int(route.super_block),
        "executor.window_attention_ops": int(route.scope == "window"),
        "executor.block_diffusion_attention_ops": int(
            route.scope == "block_diffusion"),
        "executor.latent_attention_ops": int(route.scope == "latent"),
        **dict(zip(("attention/kv_blocks_visited", "attention/kv_blocks_total",
                    "attention/kv_blocks_masked"),
                   route.kv_blocks or (0, 0, 0))),
        **dict(zip(("attention/window_keys_visited",
                    "attention/window_keys_visible"),
                   route.window_pairs or (0, 0))),
    }


def test_the_shapes_a_forward_sees_override_the_static_ones():
    """A forward under a pipeline's microbatches or a bucket of another
    length asks the route at the operands' shapes, not `input_shapes`."""
    op = make_op(dict(num_heads=4, seq_parallel="seq"), (2, 128, 128, 64))
    assert op.route({"seq": 2}, True).core == "ring"
    assert op.route({"seq": 2}, True, sk=256).core == "einsum"
    assert op.route({"seq": 2}, True, sq=256, sk=256).core == "ring"
    assert op.route({"seq": 1}, True).core == "einsum"      # Pallas off
