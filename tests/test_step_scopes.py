"""Every instruction of a train step under a name (obs/step_scopes.py).

The executor runs the loss, the optimizer update, the op that produces
the model's output and every op kind as nested calls
(`ops.base.scoped`); the table read back from the compiled step's text
gives each instruction one part and a direction. Nothing that runs
changes: with `scoped` patched to the identity the same model trains to
the same bits. A session with `device=True` writes the table itself.
"""

import base64
import collections
import json
import re

import numpy as np
import pytest

import jax

from flexflow_tpu import (AdamOptimizer, FFConfig, FFModel, LossType,
                          MetricsType, obs)
from flexflow_tpu.dataloader import block_diffusion_batch
from flexflow_tpu.ffconst import ActiMode
from flexflow_tpu.models import (DecoderConfig, TransformerConfig,
                                 create_decoder, create_transformer)
from flexflow_tpu.obs import step_scopes as ss


def transformer():
    tc = TransformerConfig(num_layers=2, hidden_size=32, num_heads=4,
                           seq_length=16, batch_size=8)
    ff = create_transformer(tc, FFConfig(batch_size=8))
    ff.compile(AdamOptimizer(alpha=1e-3),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [MetricsType.MEAN_SQUARED_ERROR])
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 16, 32)).astype(np.float32)
    y = rng.standard_normal((16, 16, 1)).astype(np.float32)
    return ff, [x], y


def decoder(pattern):
    cfg = DecoderConfig(hybrid_override_pattern=pattern, batch_size=2,
                        seq_length=16, sliding_window_size=8)
    ff = create_decoder(cfg, FFConfig(batch_size=2))
    weighted = "D" in pattern
    ff.compile(AdamOptimizer(alpha=1e-3),
               LossType.WEIGHTED_SPARSE_CATEGORICAL_CROSSENTROPY if weighted
               else LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
    rng = np.random.default_rng(0)
    if weighted:
        x0 = rng.integers(0, cfg.vocab_size - 1, (4, 8)).astype(np.int32)
        ids, labels = block_diffusion_batch(x0, cfg.block_length,
                                            cfg.vocab_size - 1, rng)
        return ff, [ids], labels
    ids = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
    return ff, [ids], np.roll(ids, -1, axis=1)


def conv_model():
    ff = FFModel(FFConfig(batch_size=8))
    t = ff.create_tensor((8, 3, 16, 16))
    t = ff.conv2d(t, 8, 3, 3, 1, 1, 1, 1, activation=ActiMode.AC_MODE_RELU)
    a = ff.pool2d(t, 2, 2, 2, 2, 0, 0)
    b = ff.conv2d(a, 8, 1, 1, 1, 1, 0, 0)
    t = ff.concat([a, b], axis=1)
    t = ff.flat(t)
    t = ff.dense(t, 10)
    t = ff.softmax(t)
    ff.compile(AdamOptimizer(alpha=1e-3),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.ACCURACY])
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 3, 16, 16)).astype(np.float32)
    y = rng.integers(0, 10, (16, 1)).astype(np.int32)
    return ff, [x], y


MODELS = {
    "transformer": transformer,
    "decoder_MEMEM*": lambda: decoder("MEMEM*"),
    "decoder_GWWW": lambda: decoder("GWWW"),
    "decoder_D": lambda: decoder("D"),
    "conv": conv_model,
}
# every op kind of the model, beside head, loss and optimizer_update
PARTS = {
    "transformer": {"op_linear", "op_layernorm", "op_ew_add", "attention"},
    "decoder_MEMEM*": {"op_embedding", "op_rmsnorm", "op_ew_add", "ssm",
                       "experts", "attention"},
    "decoder_GWWW": {"op_embedding", "op_rmsnorm", "op_ew_add", "experts",
                     "attention"},
    "decoder_D": {"op_embedding", "op_rmsnorm", "op_ew_add", "op_split",
                  "experts", "attention"},
    # (the flat is a reshape the compiler folds away)
    "conv": {"op_conv2d", "op_pool2d", "op_concat", "op_linear"},
}


# the parts whose backward is work of its own
BOTH_WAYS = {"head", "loss", "op_linear", "op_layernorm", "op_rmsnorm",
             "op_conv2d", "op_pool2d", "op_embedding", "attention",
             "experts", "ssm"}


def step_text(ff, xs, y):
    batch = ff.config.batch_size
    step = ff.executor.make_train_step()
    return step.lower(
        ff.params, ff.opt_state, ff.state,
        ff._stage_inputs([x[:batch] for x in xs]),
        ff._shard_batch(y[:batch]), jax.random.PRNGKey(0)).compile().as_text()


def train(ff, xs, y, steps=3):
    """The losses of ``steps`` steps on the first batch."""
    batch = ff.config.batch_size
    losses = []
    for _ in range(steps):
        ff.fit([x[:batch] for x in xs], y[:batch], epochs=1, verbose=False)
        losses.append(ff._last_loss)
    return losses


_TRAINED = {}


def trained(model):
    """`MODELS[model]()` and its first three steps, ONE model and one
    compiled step a module for every test that reads it (`lower` and
    `compile` of a step that has run find the program it ran): (ff, xs,
    y, the three losses, `loss_own_vjp` as `obs` gave it before the step
    was traced, the registry's gauge right after the first step)."""
    if model not in _TRAINED:
        ff, xs, y = MODELS[model]()
        untraced = obs.model_context(ff)["loss_own_vjp"]
        losses = train(ff, xs, y, 1)
        gauge = obs.get_registry().to_dict()["gauges"][
            "executor.loss_own_vjp"]
        losses += train(ff, xs, y, 2)
        _TRAINED[model] = ff, xs, y, losses, untraced, gauge
    return _TRAINED[model]


@pytest.fixture(scope="module", params=list(MODELS))
def lowered(request):
    ff, xs, y, *_ = trained(request.param)
    return request.param, ss.table_of(step_text(ff, xs, y))


def test_every_instruction_has_exactly_one_part(lowered):
    _, table = lowered
    assert len(table) > 100
    for name, row in table.items():
        parts = {ss.scope_part(s) for s in re.findall(
            r"jit\(([\w.\-]+)\)", row["op_name"].split(";")[0])} - {None}
        assert len(parts) <= 1, (name, row)
        assert row["part"] == (parts.pop() if parts else None)
        assert row["direction"] in ss.DIRECTIONS
        if row["direction"] in ("forward", "backward"):
            # nothing the model's forward or backward computes lies
            # outside a part but what autodiff adds between two ops
            assert row["part"] is not None or "add_any" in row["op_name"] \
                or not row["op_name"].rstrip(")").endswith(
                    ("dot_general", "conv_general_dilated")), (name, row)


def test_head_loss_and_every_op_kind_run_both_ways(lowered):
    model, table = lowered
    seen = collections.defaultdict(set)
    for row in table.values():
        seen[row["part"]].add(row["direction"])
    assert PARTS[model] <= set(seen), set(seen)
    for part in PARTS[model] | {"head", "loss"}:
        if part in BOTH_WAYS:
            assert {"forward", "backward"} <= seen[part], (part, seen[part])
        else:
            # an add's backward is the identity, a split, a reshape or a
            # concatenation may fuse away in one direction
            assert seen[part] & {"forward", "backward"}, part
    assert seen["optimizer_update"] == {"optimizer"}
    for row in table.values():
        if row["part"] == "optimizer_update":
            assert "jvp(" not in row["op_name"]
            assert "transpose(" not in row["op_name"]
    # the update's instructions are there at all, and named
    assert sum(r["part"] == "optimizer_update" for r in table.values()) > 10


def test_a_fusion_lists_the_parts_of_its_body(lowered):
    _, table = lowered
    fusions = [r for r in table.values() if "parts" in r]
    assert fusions
    for row in fusions:
        assert all(isinstance(n, int) and n > 0
                   for n in row["parts"].values())
        if row["part"] is not None and row["parts"]:
            # the root's part is one of the body's
            assert row["part"] in row["parts"], row


def test_the_loss_scope_holds_the_own_backward_of_the_cross_entropy(lowered):
    """`losses.target_log_probs` is a `custom_vjp` INSIDE the nested call
    `loss` (PR 40): its forward's instructions still read
    `jvp(jit(loss))`, its backward's `transpose(jvp(jit(loss)))`, and
    where it runs no scatter and no `log_softmax` is left in the scope."""
    model, table = lowered
    names = collections.defaultdict(list)
    for row in table.values():
        if row["part"] == "loss":
            names[row["direction"]].append(row["op_name"])
    assert any("jvp(jit(loss))" in n for n in names["forward"])
    assert any("transpose(jvp(jit(loss)))" in n for n in names["backward"])
    if not model.startswith("decoder"):
        return
    assert any(n.endswith("/exp") for n in names["forward"])
    assert any(n.endswith("/exp") for n in names["backward"])
    every = names["forward"] + names["backward"]
    assert not any("scatter" in n or "log_softmax" in n for n in every), [
        n for n in every if "scatter" in n or "log_softmax" in n]


@pytest.mark.parametrize("model,want", [
    ("decoder_GWWW", 1), ("decoder_D", 1), ("transformer", 0), ("conv", 0)])
def test_the_gauge_says_whether_the_loss_took_its_own_backward(model, want):
    """`executor.loss_own_vjp`: 1 for a sparse or weighted sparse
    cross-entropy on logits, 0 for MSE and for the `final_is_softmax`
    branch (probabilities in); set when the train step is traced, in the
    registry, `op_counters` and every trace header."""
    ff, _, _, _, untraced, gauge = trained(model)
    assert untraced == 0
    assert ff.executor.traced_gauges()["executor.loss_own_vjp"] == want
    assert obs.model_context(ff)["loss_own_vjp"] == want
    assert gauge == want


HLO = """HloModule jit_train_step

%fused_computation.1 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %mul.3 = f32[8]{0} multiply(%p.1, %p.1), metadata={op_name="jit(train_step)/transpose(jvp(jit(op_linear)))/mul"}
  ROOT %add.4 = f32[8]{0} add(%mul.3, %p.1), metadata={op_name="jit(train_step)/jit(optimizer_update)/add"}
}

ENTRY %main.9 (w.1: f32[8]) -> f32[8] {
  %w.1 = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %copy-start.2 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(%w.1)
  %copy-done.2 = f32[8]{0:S(1)} copy-done(%copy-start.2)
  %fusion.5 = f32[8]{0} fusion(%copy-done.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jit(optimizer_update)/add"}
  ROOT %copy.7 = f32[8]{0} copy(%fusion.5)
}
"""


def test_a_fusions_body_and_the_compilers_own_copies():
    table = ss.table_of(HLO)
    assert table["fusion.5"]["part"] == "optimizer_update"
    assert table["fusion.5"]["parts"] == {"op_linear": 1,
                                          "optimizer_update": 1}
    assert table["fusion.5"]["directions"] == {"backward": 1,
                                               "optimizer": 1}
    # the prefetch of `w` carries no `op_name`: it feeds the update, two
    # part-less instructions on; the copy of the result is fed by it
    for name in ("copy-start.2", "copy-done.2"):
        assert table[name]["part"] is None
        assert table[name]["feeds"] == "optimizer_update"
    assert table["copy.7"]["part"] is None
    assert "feeds" not in table["copy.7"]
    assert table["copy.7"]["fed_by"] == "optimizer_update"
    assert "feeds" not in table["fusion.5"]


SIX_RESULTS = """HloModule jit_train_step

%fused_computation.1 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%p.1, %p.1), metadata={op_name="jit(train_step)/jvp(jit(op_linear))/mul"}
}

%fused_computation.2 (p.2: f32[8]) -> (f32[8], f32[8], f32[8], f32[8], f32[8], /*index=5*/f32[8]) {
  %p.2 = f32[8]{0} parameter(0)
  %add.4 = f32[8]{0} add(%p.2, %p.2), metadata={op_name="jit(train_step)/jit(optimizer_update)/add"}
  ROOT %tuple.5 = (f32[8]{0}, f32[8]{0}, f32[8]{0}, f32[8]{0}, f32[8]{0}, /*index=5*/f32[8]{0}) tuple(%add.4, %add.4, %add.4, %add.4, %add.4, /*index=5*/%add.4)
}

ENTRY %main.9 (w.1: f32[8]) -> (f32[8], f32[8], f32[8], f32[8], f32[8], /*index=5*/f32[8]) {
  %w.1 = f32[8]{0} parameter(0)
  %fusion.6 = f32[8]{0} fusion(%w.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(jit(op_linear))/mul"}
  ROOT %fusion.7 = (f32[8]{0}, f32[8]{0}, f32[8]{0}, f32[8]{0}, f32[8]{0}, /*index=5*/f32[8]{0}) fusion(%fusion.6), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(train_step)/jit(optimizer_update)/add"}
}
"""


def test_a_header_of_six_results_opens_its_computation():
    """A tuple of six results or more holds `/*index=5*/`: a header
    pattern that stops at the first `=` files the computation's
    instructions under the one before (38 of the 1,403 headers of
    nemotron's step, ENTRY among them, PR 50), and a fusion's `parts`
    then list another body."""
    for line, name in (
            ("%fused_computation.2 (p.2: f32[8]) -> (f32[8], /*index=5*/"
             "f32[8]) {", "fused_computation.2"),
            ("ENTRY %main.9 (w.1: f32[8]) -> f32[8] {", "main.9"),
            ("region_0.5 (a: f32[], b: f32[]) -> f32[] { ", "region_0.5")):
        assert ss.COMPUTATION.match(line).group(1) == name
    assert ss.COMPUTATION.match(
        "  %add.4 = f32[8]{0} add(%p.2, %p.2)") is None
    table = ss.table_of(SIX_RESULTS)
    assert table["fusion.6"]["parts"] == {"op_linear": 1}
    assert table["fusion.7"]["parts"] == {"optimizer_update": 1}
    assert table["fusion.7"]["directions"] == {"optimizer": 1}


class TestTheRule:
    def test_outermost_program_scope_never_a_bare_substring(self):
        assert ss.part_of(
            "jit(train_step)/jvp(jit(op_linear))/dot_general") == "op_linear"
        assert ss.part_of("jit(train_step)/transpose(jvp(jit(moe_layer)))/"
                          "jit(moe_combine)/gather") == "experts"
        assert ss.part_of("jit(train_step)/jvp(jit(attention_window))/"
                          "jit(flash_window)/pallas_call") == "attention"
        assert ss.part_of("jit(train_step)/jvp(jit(attention_plain))/"
                          "dot_general") == "attention"
        assert ss.part_of("jit(train_step)/jvp(jit(ssm_mixer))/"
                          "jit(ssd_scan)/dot_general") == "ssm"
        # `loss` as a substring of another function's name is no scope
        assert ss.part_of("jit(train_step)/jit(cross_entropy_loss)/exp") \
            is None
        assert ss.part_of("jit(train_step)/jvp(loss)/exp") is None
        assert ss.part_of("") is None

    def test_directions(self):
        assert ss.classify("jit(train_step)/jvp(jit(head))/dot_general") == (
            "head", "forward")
        assert ss.classify("jit(train_step)/transpose(jvp(jit(loss)))/mul") \
            == ("loss", "backward")
        assert ss.classify("jit(train_step)/jit(optimizer_update)/sqrt") == (
            "optimizer_update", "optimizer")
        assert ss.classify("jit(train_step)/transpose(jvp(checkpoint))/"
                           "rematted_computation/jit(op_linear)/dot_general"
                           ) == ("op_linear", "backward")
        assert ss.classify("jit(train_step)/convert_element_type") == (
            None, "none")
        # several names joined by the compiler: the first one counts
        assert ss.classify("jit(train_step)/jit(optimizer_update)/mul;"
                           "jit(train_step)/jvp(jit(head))/mul") == (
                               "optimizer_update", "optimizer")

    @pytest.mark.parametrize("line,want", [
        ('%tpu_custom_call.3 = bf16[8,512,1024] custom-call(%a), '
         'custom_call_target="tpu_custom_call", metadata={op_name='
         '"jit(train_step)/jvp(tpu_custom_call_flash_fwd_whole)/pallas_call"}',
         ("attention", "forward")),
        # as the chip's compiled step has it: `pallas_call` alone in
        # `op_name`, the kernel's name in the payload's MLIR bytecode
        ('%tpu_custom_call.4 = bf16[8,512,1024] custom-call(%a), '
         'custom_call_target="tpu_custom_call", backend_config={'
         '"custom_call_config":{"body":"' + base64.b64encode(
             b"ML\xefR\x01MLIR\x00flash_fwd_kernel\x00flash_bwd_blocked\x00"
         ).decode() + '"}}, metadata={op_name="pallas_call"}',
         ("attention", "backward")),
        ('%shard_map.7 = f32[1024] custom-call(%a), custom_call_target='
         '"tpu_custom_call", metadata={op_name="jit(train_step)/shard_map/'
         'tpu_custom_call_fused_adam/pallas_call"}',
         ("optimizer_update", "optimizer")),
        ('%custom-call.9 = f32[4] custom-call(%a), custom_call_target='
         '"ConcatBitcast"', (None, "none")),
        ('%gmm.9 = f32[4] custom-call(%a), custom_call_target='
         '"tpu_custom_call", metadata={op_name="jit(train_step)/jvp(jit('
         'moe_layer))/jit(moe_grouped_matmul)/jit(gmm)/pallas_call"}',
         ("experts", "forward")),
    ])
    def test_a_top_level_kernel_goes_by_its_own_name(self, line, want):
        op_name = re.search(r'op_name="([^"]*)"', line)
        assert ss.classify(op_name.group(1) if op_name else "", line) == want


def _identity_scoped(monkeypatch):
    import flexflow_tpu.executor as executor
    from flexflow_tpu.ops import attention, base, experts, ssm
    for module in (base, executor, attention, experts, ssm):
        monkeypatch.setattr(module, "scoped", lambda name, fn: fn)


@pytest.mark.parametrize("model", list(MODELS))
def test_three_steps_are_those_of_the_unscoped_model(model, monkeypatch):
    """The first step's loss to the bit (the forward pass is the same
    arithmetic), three steps' losses and parameters to a few units in
    the last place: a nested call sums a value's cotangents inside the
    call before they join the others, so the backward pass adds the same
    float32 terms in another order. (The cells' steps compiled for the
    chip are compared with the parent's instruction by instruction in
    PERF.md section 6, PR 36: the three decoders' are the same program.)"""
    def leaves(ff):
        # (a layer's name carries a counter of the process, and a dict's
        # leaves come in the names' STRING order: `conv2d_98`, `conv2d_100`
        # swap places where the two models' counters straddle a power of
        # ten, so the layers go by the numbers in their names)
        def by_number(name):
            return [int(t) if t.isdigit() else t
                    for t in re.split(r"(\d+)", name)]
        return [np.asarray(p) for name in sorted(ff.params, key=by_number)
                for p in jax.tree.leaves(ff.params[name])]

    ff, _, _, losses, _, _ = trained(model)
    params = leaves(ff)
    _identity_scoped(monkeypatch)
    plain, xs, y = MODELS[model]()
    plain_losses, plain_params = train(plain, xs, y), leaves(plain)
    assert losses[0] == plain_losses[0]
    np.testing.assert_allclose(losses, plain_losses, rtol=1e-6)
    assert len(params) == len(plain_params)
    for a, b in zip(params, plain_params):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-6)


@pytest.fixture
def no_open_session():
    yield
    if obs.session_tracer() is not None:
        obs.stop_trace()


def _thunks_between(xplane, first, last):
    """Names of the CPU profile's executed instructions (a thunk's event
    has an `end: <name>` twin) that start between the two marker
    annotations."""
    from jax.profiler import ProfileData
    events = [ev for plane in ProfileData.from_file(xplane).planes
              for line in plane.lines for ev in line.events]
    t0 = min(ev.start_ns for ev in events if ev.name == first)
    t1 = max(ev.start_ns for ev in events if ev.name == last)
    ended = {ev.name[len("end: "):] for ev in events
             if ev.name.startswith("end: ")}
    return {ev.name for ev in events
            if ev.name in ended and t0 <= ev.start_ns <= t1}


def test_a_device_session_writes_the_table_of_the_step_that_ran(
        tmp_path, no_open_session):
    ff, xs, y = transformer()
    ff.fit(xs, y, epochs=1, verbose=False)     # compiles the step
    jax.block_until_ready(ff.params)
    staged = ff._stage_inputs([x[:8] for x in xs]), ff._shard_batch(y[:8])
    obs.start_trace(str(tmp_path), device=True)
    # what a `fit` call runs beside the train step, alone between two
    # markers: the other programs' instruction names
    with jax.profiler.TraceAnnotation("others_begin"):
        pass
    jax.block_until_ready((ff._stage_inputs([x[:8] for x in xs]),
                           ff._shard_batch(y[:8]),
                           jax.random.split(jax.random.PRNGKey(1)),
                           jax.tree.map(jax.numpy.add, staged, staged)))
    with jax.profiler.TraceAnnotation("others_end"):
        pass
    ff.fit(xs, y, epochs=1, verbose=False)
    ff.fit(xs, y, epochs=1, verbose=False)
    jax.block_until_ready(ff.params)
    with jax.profiler.TraceAnnotation("steps_end"):
        pass
    paths = obs.stop_trace()
    assert paths["step_scopes"].endswith(".step_scopes.json")
    with open(paths["step_scopes"]) as f:
        artifact = json.load(f)
    table = artifact["instructions"]
    header = artifact["header"]
    assert header["kind"] == "step_scopes"
    assert header["step_scopes_instructions"] == len(table) > 100
    assert header["step_scopes_s"] > 0
    with open(paths["events"]) as f:
        session_header = json.loads(f.readline())
    assert session_header["step_scopes"] == paths["step_scopes"].rsplit(
        "/", 1)[1]
    assert session_header["step_scopes_s"] == header["step_scopes_s"]
    assert session_header["step_scopes_instructions"] == len(table)
    # the table is that of the step as this process compiled it
    assert table == json.loads(json.dumps(ss.table_of(step_text(ff, xs, y))))
    # and its names cover what the profile saw the train steps run: every
    # executed instruction of the two `fit` calls that is no instruction
    # of the other programs is in the table, and most of them are
    others = _thunks_between(paths["xplane"], "others_begin", "others_end")
    ran = _thunks_between(paths["xplane"], "others_end", "steps_end")
    assert ran - others, "the profile holds no event of the train steps"
    assert ran - others <= set(table), sorted(ran - others - set(table))
    assert len((ran - others) & set(table)) > 20
