"""The seam between search and executor (flexflow_tpu/parallel/choice.py):
``Choice`` parses and composes the searched choice's name, and
``plan_execution`` turns a strategy, the ``FFConfig`` switches and the
``FFS_NO_*`` variables into the ``ExecPlan`` an executor runs.

The plans expected below were written down from the parent of the PR
that added the module (PR 29): the same hand-built strategies went
through its ``FFModel.compile`` (``graph_optimize`` replaced by a
function returning them) and the executor's attributes were read."""

import dataclasses
import json
import math
import os

import pytest
from jax.sharding import PartitionSpec as P

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.ffconst import CompMode, OperatorType as OT
from flexflow_tpu.models.transformer import (TransformerConfig,
                                             create_transformer)
from flexflow_tpu.parallel.choice import Choice, ExecPlan, plan_execution
from flexflow_tpu.parallel.strategy import (OpStrategy,
                                            data_parallel_strategy)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- (a) the grammar ------------------------------------------------------
def _trace_names():
    """Every distinct candidate name in the committed search traces."""
    names = set()
    for rel, key in (("SEARCH_TRACE.json", "search_trace"),
                     ("tests/fixtures/obs_report_dir/"
                      "demo_r00_host00.searchtrace.json", None)):
        with open(os.path.join(ROOT, rel)) as f:
            trace = json.load(f)
        for op in (trace[key] if key else trace)["ops"]:
            names.update(c["choice"] for c in op["candidates"])
    return sorted(names)


TRACE_NAMES = _trace_names()
# what the four cells' strategies hold (PERF.md section 4), and the
# bases whose properties code reads
CELL_NAMES = ["rep", "rep_k:flash", "dp_wus_ovl_k:flash",
              "dp_wus_ovl_k:fused", "rep_r", "rep_k:flash_r",
              "dp_head_ring", "head_ring_wus", "dp_ep", "dp_ep_sp_wus_ovl",
              "dp_head", "dp_sp", "dp_k:conv_bn_fused_r", "dp_wus_ovl_r",
              "dp_col_wus_ovl_k:fused_r", "dp_k:einsum"]


def test_trace_names_are_the_25_the_issue_counted():
    assert len(TRACE_NAMES) == 25
    assert {"dp_row", "dp_col", "dp_head", "sample2",
            "dp_mp_last"} <= {Choice.parse(n).base for n in TRACE_NAMES}


@pytest.mark.parametrize("name", sorted(set(TRACE_NAMES + CELL_NAMES)))
def test_round_trip_and_parts(name):
    c = Choice.parse(name)
    assert str(c) == name
    # the parts, by an oracle that shares nothing with the parser: peel
    # the suffixes off the end in the reverse of the canonical order
    rest = name
    remat = rest.endswith("_r")
    rest = rest[:-2] if remat else rest
    rest, _, kernel = rest.partition("_k:")
    ovl = rest.endswith("_ovl")
    rest = rest[:-4] if ovl else rest
    wus = rest.endswith("_wus")
    rest = rest[:-4] if wus else rest
    assert (c.base, c.wus, c.ovl, c.kernel, c.remat) == \
        (rest, wus, ovl, kernel or None, remat)
    assert c.ring == rest.endswith("_ring")
    assert c.head == ("head" in rest.split("_"))
    assert c.expert == ("ep" in rest.split("_"))


@pytest.mark.parametrize("name", [None, ""])
def test_no_choice_parses_to_nothing_engaged(name):
    c = Choice.parse(name)
    assert c == Choice() and str(c) == ""
    assert OpStrategy(output_specs=[]).parsed == c


def test_strategy_reparses_when_the_name_is_set():
    st = OpStrategy(output_specs=[], choice="dp")
    assert st.parsed.kernel is None
    st.choice = "dp_k:flash"  # as tests and strategy import do
    assert st.parsed.kernel == "flash"


# ---- (b), (c) the plan ----------------------------------------------------
def _bert(layers, cfg=None):
    return create_transformer(
        TransformerConfig(num_layers=layers, hidden_size=32, num_heads=2,
                          seq_length=128, batch_size=8), cfg)


def _hybrid(_layers, cfg=None):
    """30 ops, one of them attention: the shape of the Nemotron cell."""
    ff = FFModel(cfg or FFConfig(batch_size=8))
    t = ff.create_tensor((8, 128, 32), name="input")
    for i in range(7):
        t = ff.relu(ff.dense(t, 32, name=f"up_{i}"))
    t = ff.multihead_attention(t, t, t, 32, 2, name="attn")
    for i in range(7, 14):
        t = ff.relu(ff.dense(t, 32, name=f"up_{i}"))
    ff.dense(t, 1, name="head")
    return ff


def _strategy(nodes, pick):
    out = {}
    for n in nodes:
        name = pick(n)
        nd = len(n.op.output_shapes[0])
        spec = (P("data", *([None] * (nd - 1)))
                if name.startswith("dp") else None)
        out[n.op.guid] = OpStrategy(output_specs=[spec], choice=name)
    return out


def _pick_1chip(n):
    return "rep_k:flash" if n.op.op_type == OT.MULTIHEAD_ATTENTION else "rep"


def _pick_4chip(n):
    t = n.op.op_type
    if t == OT.MULTIHEAD_ATTENTION:
        return "dp_wus_ovl_k:flash"
    if t == OT.LINEAR and n.op.name != "head":
        return "dp_wus_ovl_k:fused"
    return "dp_wus_ovl" if t == OT.LAYERNORM else "dp"


def _pick_nemo(n):
    return "rep_k:flash" if n.op.name == "attn" else "rep"


def _pick_remat(n):
    if n.op.op_type == OT.MULTIHEAD_ATTENTION:
        return "rep_k:flash_r"
    return "rep_r" if n.op.name.startswith("ffn1") else "rep"


def _pick_mixed(n):
    if n.op.op_type == OT.MULTIHEAD_ATTENTION:
        return "dp_wus_ovl" if n.op.name == "attn_0" else "dp"
    if n.op.name.startswith("ffn1"):
        return "dp_wus_ovl_k:fused_r"
    return "dp_wus" if n.op.name.startswith("ffn2") else "dp"


def _names(*prefixes, n):
    return {f"{p}_{i}" for p in prefixes for i in range(n)}


def _impls(n=2, **impl_of_prefix):
    return {f"{p}_{i}": impl for p, impl in impl_of_prefix.items()
            for i in range(n)}


INFO4 = {"overlap": {"bucket_mb": 16}, "objective": "step_time"}
OFF = dict(wus=False, wus_ops=None, overlap=False, bucket_bytes=4000000,
           kernel_choices=None, remat_ops=None, body_remat=False)
WUS2 = _names("attn", "ffn1", "ffn2", "ln1", "ln2", n=2)
KC2 = _impls(attn="flash", ffn1="fused", ffn2="fused")
ON4 = dict(wus=True, wus_ops=WUS2, overlap=True, bucket_bytes=16000000,
           kernel_choices=KC2, remat_ops=None, body_remat=False)
FLASH2 = dict(OFF, kernel_choices=_impls(attn="flash"))

# id: (build, layers, pick, mesh axes, search_info, cfg switches, env,
#      comp_mode, the plan the parent gave, kernel_impl left on attention)
T, I = CompMode.TRAINING, CompMode.INFERENCE
PLANS = {
    # (b) the cells
    "cell_bert_1chip": (
        _bert, 12, _pick_1chip, {"data": 1}, {}, {}, {}, T,
        dict(OFF, kernel_choices=_impls(12, attn="flash")), "flash"),
    "cell_bert_4chip": (
        _bert, 12, _pick_4chip, {"data": 4}, INFO4, {}, {}, T,
        dict(ON4, wus_ops=_names("attn", "ffn1", "ffn2", "ln1", "ln2", n=12),
             kernel_choices=_impls(12, attn="flash", ffn1="fused",
                                   ffn2="fused")), "flash"),
    "cell_nemotron_1chip": (
        _hybrid, 0, _pick_nemo, {"data": 1}, {}, {}, {}, T,
        dict(OFF, kernel_choices={"attn": "flash"}), "flash"),
    "remat_twins_1chip": (
        _bert, 2, _pick_remat, {"data": 1}, {}, {}, {}, T,
        dict(FLASH2, remat_ops=_names("attn", "ffn1", n=2)), "flash"),
    "mixed_wus_4chip": (
        _bert, 2, _pick_mixed, {"data": 4}, INFO4, {}, {}, T,
        dict(ON4, wus_ops={"attn_0"} | _names("ffn1", "ffn2", n=2),
             kernel_choices=_impls(attn="einsum", ffn1="fused"),
             remat_ops=_names("ffn1", n=2)), "einsum"),
    "default_attention_is_pinned": (
        _bert, 2, lambda n: "rep", {"data": 1}, {}, {}, {}, T,
        dict(OFF, kernel_choices=_impls(attn="einsum")), "einsum"),
    "flat_mesh_ignores_pipeline_info": (
        _bert, 2, _pick_1chip, {"data": 2}, {"pipeline": "detect"}, {}, {},
        T, FLASH2, "flash"),
    # (c) each switch in its off position, and the forced ones
    "wus_off": (
        _bert, 2, _pick_4chip, {"data": 4}, INFO4,
        dict(weight_update_sharding="off"), {}, T,
        dict(OFF, bucket_bytes=16000000, kernel_choices=KC2), "flash"),
    "wus_on_shards_every_op": (
        _bert, 2, _pick_mixed, {"data": 4}, INFO4,
        dict(weight_update_sharding="on"), {}, T,
        dict(ON4, wus_ops=None,
             kernel_choices=_impls(attn="einsum", ffn1="fused"),
             remat_ops=_names("ffn1", n=2)), "einsum"),
    "wus_on_needs_a_data_degree": (
        _bert, 2, _pick_1chip, {"data": 1}, {},
        dict(weight_update_sharding="on"), {}, T, FLASH2, "flash"),
    "overlap_off": (
        _bert, 2, _pick_4chip, {"data": 4}, INFO4,
        dict(overlap_bucket_mb="off"), {}, T,
        dict(ON4, overlap=False, bucket_bytes=4000000), "flash"),
    "overlap_0": (
        _bert, 2, _pick_4chip, {"data": 4}, INFO4,
        dict(overlap_bucket_mb="0"), {}, T,
        dict(ON4, overlap=False, bucket_bytes=4000000), "flash"),
    "overlap_8mb": (
        _bert, 2, _pick_4chip, {"data": 4}, INFO4,
        dict(overlap_bucket_mb="8"), {}, T,
        dict(ON4, bucket_bytes=8000000), "flash"),
    "overlap_needs_wus": (
        _bert, 2, _pick_1chip, {"data": 1}, {},
        dict(overlap_bucket_mb="8"), {}, T,
        dict(FLASH2, bucket_bytes=8000000), "flash"),
    "kernel_search_off": (
        _bert, 2, _pick_4chip, {"data": 4}, INFO4,
        dict(kernel_search="off"), {}, T,
        dict(ON4, kernel_choices=None), None),
    "FFS_NO_KERNEL_SEARCH": (
        _bert, 2, _pick_4chip, {"data": 4}, INFO4, {},
        {"FFS_NO_KERNEL_SEARCH": "1"}, T,
        dict(ON4, kernel_choices=None), None),
    "remat_search_off": (
        _bert, 2, _pick_remat, {"data": 1}, {},
        dict(remat_search="off"), {}, T, FLASH2, "flash"),
    "FFS_NO_REMAT": (
        _bert, 2, _pick_remat, {"data": 1}, {}, {}, {"FFS_NO_REMAT": "1"},
        T, FLASH2, "flash"),
    "inference_has_no_wus": (
        _bert, 2, _pick_4chip, {"data": 4}, INFO4, {}, {}, I,
        dict(OFF, bucket_bytes=16000000, kernel_choices=KC2), "flash"),
    # heuristic strategies (no search ran)
    "heuristic_data4": (
        _bert, 2, None, {"data": 4}, None, {}, {}, T,
        dict(OFF, wus=True, overlap=True), None),
    "heuristic_data2": (
        _bert, 2, None, {"data": 2}, None, {}, {}, T, OFF, None),
    "heuristic_data4_overlap_off": (
        _bert, 2, None, {"data": 4}, None,
        dict(overlap_bucket_mb="off"), {}, T, dict(OFF, wus=True), None),
    # the pipe-mesh gates
    "pipe_has_no_kernel_or_op_remat": (
        _bert, 4, _pick_remat, {"pipe": 2, "data": 2}, {"pipeline": None},
        {}, {}, T, OFF, None),
    "pipe_body_remat": (
        _bert, 4, _pick_1chip, {"pipe": 2, "data": 2},
        {"pipeline": "detect"}, {}, {}, T, dict(OFF, body_remat=True), None),
    "pipe_body_remat_search_off": (
        _bert, 4, _pick_1chip, {"pipe": 2, "data": 2},
        {"pipeline": "detect"}, dict(remat_search="off"), {}, T, OFF, None),
    "pipe_body_FFS_NO_REMAT": (
        _bert, 4, _pick_1chip, {"pipe": 2, "data": 2},
        {"pipeline": "detect"}, {}, {"FFS_NO_REMAT": "1"}, T, OFF, None),
}


def _fields(plan):
    return {f.name: getattr(plan, f.name) for f in dataclasses.fields(plan)}


def _inputs(build, layers, pick, axes, info, switches):
    from flexflow_tpu.machine import make_mesh
    from flexflow_tpu.parallel.pipeline_detect import detect_repeated_blocks
    from flexflow_tpu.parallel.strategy import apply_strategy
    cfg = FFConfig(batch_size=8)
    for k, v in switches.items():
        assert hasattr(cfg, k)
        setattr(cfg, k, v)
    nodes = build(layers, cfg)._materialize_nodes()[0]
    mesh = make_mesh(math.prod(axes.values()), axes)
    strategy = (_strategy(nodes, pick) if pick is not None
                else data_parallel_strategy(nodes, mesh))
    if info is not None and info.get("pipeline") == "detect":
        info = dict(info, pipeline=dict(
            blocks=detect_repeated_blocks(nodes), remat=True,
            microbatches=2))
    apply_strategy(nodes, strategy, mesh)  # as compile does before
    return nodes, strategy, info, cfg


@pytest.mark.parametrize("case", sorted(PLANS))
def test_plan_is_the_parents(case, monkeypatch):
    build, layers, pick, axes, info, switches, env, mode, want, pin = \
        PLANS[case]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    nodes, strategy, info, cfg = _inputs(build, layers, pick, axes, info,
                                         switches)
    plan = plan_execution(nodes, strategy, axes, info, cfg, mode)
    assert _fields(plan) == want
    pins = {n.op.kernel_impl for n in nodes
            if n.op.op_type == OT.MULTIHEAD_ATTENTION}
    assert pins == {pin}


def test_bad_switch_value_is_refused():
    cfg = FFConfig(batch_size=8)
    cfg.weight_update_sharding = "sometimes"
    with pytest.raises(ValueError, match="auto|on|off"):
        plan_execution([], {}, {"data": 4}, None, cfg, T)


# ---- the executors take the plan ------------------------------------------
def _compiled(pick, axes, info=None, layers=2, **switches):
    """FFModel.compile on a hand-built searched strategy."""
    from flexflow_tpu.search import unity
    cfg = FFConfig(batch_size=8)
    cfg.search_budget = 5
    for k, v in switches.items():
        setattr(cfg, k, v)
    ff = _bert(layers, cfg)
    real = unity.graph_optimize
    unity.graph_optimize = lambda nodes, *a, **k: (
        dict(axes), _strategy(nodes, pick), dict(info or {}))
    try:
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
    finally:
        unity.graph_optimize = real
    return ff


@pytest.fixture(scope="module")
def ff_4chip():
    return _compiled(_pick_4chip, {"data": 4}, INFO4)


@pytest.fixture(scope="module")
def ff_remat():
    return _compiled(_pick_remat, {"data": 1})


def test_compile_hands_the_plan_to_the_executor(ff_4chip):
    ff, ex = ff_4chip, ff_4chip.executor
    assert _fields(ex.plan) == ON4
    # the names other code reads, with the plan's values
    assert (ff.wus_enabled, ff.overlap_enabled) == (True, True)
    assert ff.kernel_choices == KC2 and ff.remat_ops is None
    assert (ex.weight_update_sharding, ex.grad_overlap) == (True, True)
    assert ex.wus_ops == WUS2 and ex.overlap_bucket_bytes == 16000000
    assert ex.kernel_choices == KC2 and ex.remat_ops is None
    assert ex.fused_update_ops == _names("ffn1", "ffn2", n=2)
    assert {s.choice for s in ff.strategy.values()} == {
        "dp", "dp_wus_ovl", "dp_wus_ovl_k:flash", "dp_wus_ovl_k:fused"}


def test_plan_follows_the_executors_state(ff_remat):
    ex = ff_remat.executor
    assert ex.plan.remat_ops == _names("attn", "ffn1", n=2)
    kept = ex.remat_ops
    try:
        ex.remat_ops = {"attn_0"}  # as tests and bench.py set it
        assert ex.plan.remat_ops == {"attn_0"}
    finally:
        ex.remat_ops = kept


def test_seq_bucket_executor_runs_the_full_plan_less_remat(ff_remat):
    ff = ff_remat
    ex = ff._bucket_executor(64)
    assert ex is not ff.executor
    assert ex.plan == dataclasses.replace(ff.executor.plan, remat_ops=None)
    assert ex.kernel_choices == _impls(attn="flash")


def test_executor_takes_no_loose_choice_arguments():
    import inspect
    from flexflow_tpu.executor import GraphExecutor
    params = set(inspect.signature(GraphExecutor.__init__).parameters)
    assert "plan" in params
    assert not params & {"weight_update_sharding", "wus_ops",
                         "overlap_grad_sync", "overlap_bucket_bytes",
                         "kernel_choices", "remat_ops"}


# ---- the replay asks the plan ---------------------------------------------
class _Node:
    def __init__(self, name, params=1):
        self.op = type("Op", (), dict(
            name=name, params_elems=lambda self: params))()


EXECUTED = [
    # searched, plan, node has params -> what the simulator prices
    ("rep_k:flash_r", dict(kernel_choices={"a": "flash"},
                           remat_ops=frozenset({"a"})), 1, "rep_k:flash_r"),
    ("rep_k:flash_r", dict(kernel_choices={"a": "flash"}), 1, "rep_k:flash"),
    ("rep_k:flash_r", dict(remat_ops=frozenset({"a"})), 1, "rep_r"),
    ("rep_k:flash", dict(kernel_choices={"a": "einsum"}), 1, "rep"),
    ("rep_r", dict(remat_ops=frozenset({"b"})), 1, "rep"),
    ("dp_wus_ovl_k:fused", dict(), 1, "dp"),
    ("dp_wus_ovl_k:fused_r",
     dict(wus=True, overlap=True, kernel_choices={"a": "fused"},
          remat_ops=frozenset({"a"})), 1, "dp_wus_ovl_k:fused_r"),
    ("dp", dict(wus=True, overlap=True), 1, "dp_wus_ovl"),
    ("dp", dict(wus=True, overlap=True), 0, "dp"),
    ("dp_wus_ovl", dict(wus=True), 1, "dp_wus"),
    ("dp_wus", dict(wus=True, wus_ops=frozenset({"b"})), 1, "dp"),
    ("dp_head_ring", dict(wus=True, wus_ops=frozenset({"a"})), 1,
     "dp_head_ring_wus"),
]


@pytest.mark.parametrize("searched,plan,params,want", EXECUTED)
def test_executed_choice(searched, plan, params, want):
    got = ExecPlan(**plan).executed_choice(_Node("a", params),
                                           Choice.parse(searched))
    assert str(got) == want


def _replayed(ff):
    import flexflow_tpu.search.native as native
    from flexflow_tpu.search.validate import simulate_strategy
    seen = {}
    real = native.native_simulate

    def spy(req):
        seen.update(req["assignment"])
        return real(req)

    native.native_simulate = spy
    try:
        resp = simulate_strategy(ff)
    finally:
        native.native_simulate = real
    assert resp["iteration_time"] > 0
    return {n.op.name: seen[str(n.op.guid)] for n in ff.executor.nodes}


def test_replay_prices_flash_remat_as_itself(ff_remat):
    """Fails at the parent of PR 29: the replay compared "flash" with
    "flash_r", dropped the kernel and the remat with it, and priced
    plain ``rep``."""
    assert ff_remat.executor.kernel_choices["attn_0"] == "flash"
    assert "attn_0" in ff_remat.executor.remat_ops
    replayed = _replayed(ff_remat)
    assert replayed["attn_0"] == "rep_k:flash_r"
    assert replayed["ffn1_0"] == "rep_r" and replayed["ffn2_0"] == "rep"


def test_replay_of_the_4chip_cell_is_what_was_searched(ff_4chip):
    by_guid = {n.op.guid: n.op.name for n in ff_4chip.executor.nodes}
    searched = {by_guid[g]: s.choice for g, s in ff_4chip.strategy.items()}
    assert _replayed(ff_4chip) == searched
