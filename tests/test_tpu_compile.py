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

Three files, so that three workers take them (`tests/tpu_compile.py`
holds what they share): the flash kernels in
`tests/test_tpu_compile_flash.py`, the decoder cells' ops in
`tests/test_tpu_compile_decoder.py`, and here the embedding's backward,
the fused update, the head and loss, the lane-dense rotary and the whole
train steps. Tier-1 keeps the kernels of the main path at real widths
plus one whole train step at depth 2; the full-depth steps of every
`chip_smoke.py` arm (30-90 s each) are `-m slow` and are the rehearsal to
run before a four-chip call.
"""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from flexflow_tpu import AdamOptimizer, FFConfig, LossType, MetricsType
from flexflow_tpu.machine import MachineSpec, make_mesh
from flexflow_tpu.models import TransformerConfig, create_transformer
from flexflow_tpu.obs.inspect import pallas_kernel_count
from flexflow_tpu.obs.step_scopes import table_of
from flexflow_tpu.ops import pallas_kernels as pk
from tpu_compile import (_compile, _no_compilation_cache,  # noqa: F401
                         described_mesh, layout_faults, on_tpu, topo)


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

    _HLO = {}

    def _hlo(self, topo, kind, lanes):
        """The compiled step of one of `OPS`, with the pass or steered to
        the view: one compile a (kind, form) for the class."""
        if (kind, lanes) not in self._HLO:
            seq, hidden, props, _, _ = self.OPS[kind]
            self._HLO[kind, lanes] = self._compile_op(
                topo, seq, hidden, props, lanes)
        return self._HLO[kind, lanes]

    def _compile_op(self, topo, seq, hidden, props, lanes):
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
        seq, _, props, with_the_pass, on_the_view = self.OPS[kind]
        heads, kv_heads = props["num_heads"], props["num_kv_heads"]
        d = props.get("head_dim", 128)
        hlo = self._hlo(topo, kind, True)
        left = self.passes_over(hlo, seq * heads * d)
        # nothing: the repeat's backward is gone too
        assert len(left) == with_the_pass, left
        self.assert_keys_stay_at_the_kv_heads(hlo, seq, heads, kv_heads, d)
        view = self.passes_over(self._hlo(topo, kind, False),
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
        hlo = self._hlo(topo, "laguna_window_64_8", True)
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
