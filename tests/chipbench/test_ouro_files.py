"""The data files, FLOP and byte functions and readers that the
`ouro_2_6b` configuration adds: the configuration against the catalog's
row, the cell's files found by name and its entries after the accepted
ones, hand counts of ISSUE 48's numbers, and the four new readers on a
made-up trace and join table and on the trace the v5e recorded."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks import harness as hs  # noqa: E402
from benchmarks import manifest as mf  # noqa: E402
from benchmarks import session_reduce as sr  # noqa: E402
from benchmarks import trace_reduce as tr  # noqa: E402
from rehearse import send_output_to  # noqa: E402

CONFIG = "ouro_2_6b"
CELL = "ouro_2_6b.s4096_b1.1chip"
FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "devtrace_tpu_v5e.trace.json.gz")
# the catalog's row Ouro-2.6B (model-configs guide, architectures.jsonl),
# as published
PUBLISHED = {
    "early_exit_threshold": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5632,
    "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "use_sliding_window": False, "vocab_size": 49152}
REDUCED = {"num_hidden_layers": 6, "vocab_size": 6144}
# never cut: hidden, head and feed-forward widths, the heads, the passes
WIDTHS = ("hidden_size", "head_dim", "intermediate_size",
          "num_attention_heads", "num_key_value_heads", "total_ut_steps")
NEW_METRICS = ("layers.looped_stack_share_pct",
               "layers.loop_pass_max_over_min",
               "layers.exit_heads_share_pct",
               "kernels.causal_flash_roofline")
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632       # a layer's matrices
PAIRS = 4096 * 4097 // 2                         # causal, a sample


@pytest.fixture(scope="module")
def cell():
    manifest = mf.load_manifest()
    entry, config, traffic = mf.find_cell(manifest, CELL)
    family = hs.load_by_path("families", config["family"])
    return manifest, entry, config, traffic, family


def test_configuration_holds_the_published_numbers(cell):
    manifest, _, config, _, _ = cell
    (listed,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert listed["reduced"] == config["reduced"] == list(REDUCED)
    assert listed["source"] == config["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    assert "one pipeline stage of 8" in listed["why"]
    assert "ONE set of leaves" in listed["why"] and len(listed["why"]) <= 200
    assert not set(REDUCED) & set(WIDTHS)
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):   # where the guide is installed
        with open(catalog_file) as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows if r["name"] == "Ouro-2.6B"]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == config["source"]
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config[key] == REDUCED[key], key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert set(config["published"]) == set(REDUCED)
    # an eighth of the layers and an eighth of the rows: one stage of the
    # assumed eight; the floors: four layers and more, an eighth of the rows
    assert 48 // 8 == 6 >= 4 and 49152 // 8 == 6144
    for key in ("source", "deployment", "departures", "assumed", "adam",
                "parameters", "loss_positions", "looping"):
        assert config[key], key
    assert "eight pipeline stages of six layers" in config["deployment"]
    assert "rows 0-6,143" in config["deployment"]
    assumed = " ".join(config["assumed"])
    for said in ("sandwich norms", "closes EVERY pass", "early_exit_gate",
                 "lambda^(T) is not read", "exit_entropy_beta 0.1",
                 "sequence 4,096", "ByteDance's layout is not public",
                 "NO depth scaling", "embedding_std 1.0",
                 "p = 1/2, 1/4, 1/8, 1/8", "NOT sharpened",
                 "pairs (j, j + 64)"):
        assert said in assumed, said
    assert (config["exit_entropy_beta"], config["initializer_range"],
            config["embedding_std"]) == (0.1, 0.02, 1.0)
    assert config["adam"]["alpha"] == 1e-7
    assert config["adam"]["state_dtype"] == "bfloat16"
    assert "333,500,417" in config["parameters"]
    assert "WHY SIX LAYERS AND NOT NINE" in config["parameters"]


def test_the_cells_files_are_found_by_name(cell):
    manifest, entry, config, traffic, family = cell
    assert entry == dict(name=CELL, config=CONFIG, traffic="s4096_b1",
                         chips=1, why=traffic["why"])
    assert len(entry["why"]) <= 200
    for said in ("6 layers x 4 passes", "ONE set of leaves", "36.5 TFLOP",
                 "the 4 heads 3.4"):
        assert said in entry["why"], said
    assert (traffic["seq"], traffic["batch"], traffic["steps_per_epoch"],
            traffic["reference_chunk"], traffic["part_a_share"]) == (
        4096, 1, 4, 1, 0.5)
    assert config["family"] == "ouro"
    assert family.reference(family.sizes(config, traffic), traffic)[0] \
        .__name__ == "benchmarks.references.ouro"
    names = [m["name"] for m in manifest["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput"
        assert by_name[name]["source"] == "device_trace"
        assert hasattr(hs.load_by_path("layer_metrics", name), "read")
        # new entries come after everything the benchmark had (PR 45's)
        assert names.index(name) > names.index(
            "layers.unshared_experts_share_pct")
    assert names[-4:] == list(NEW_METRICS)
    assert {n: (by_name[n]["unit"], by_name[n]["better"], by_name[n]["layer"])
            for n in NEW_METRICS} == {
        "layers.looped_stack_share_pct": ("%", "lower", "model ops"),
        "layers.loop_pass_max_over_min": ("ratio", "lower",
                                          "executor step"),
        "layers.exit_heads_share_pct": ("%", "lower", "model ops"),
        "kernels.causal_flash_roofline": ("%", "higher", "kernels")}
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index("lfm2_8b_a1b.s16384_b1.1chip")
    assert cells[-1] == CELL
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index(CONFIG) > configs.index("lfm2_8b_a1b")
    assert configs[-1] == CONFIG
    reported = {m["name"] for m in mf.metrics_of(manifest, "per_layer",
                                                 CELL)}
    assert set(NEW_METRICS) <= reported
    assert {"device.mfu_pct", "device.idle_pct", "search.search_s",
            "compile.model_compile_s"} <= reported
    # the accepted readers keep to their own cells, and no accepted
    # metric's list of cells holds the new one
    assert not reported & {"layers.moe_share_pct",
                           "layers.full_attention_share_pct",
                           "layers.short_conv_share_pct",
                           "kernels.flash_roofline"}
    for m in manifest["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", ()), m["name"]
    # one four-chip cell of the quarter the benchmark may have
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_parameters_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    import numpy as np
    count = {name: sum(int(np.prod(shape)) for _, shape in leaves.values())
             for name, leaves in family.weight_shapes(s).items()}
    assert LAYER == 51_380_224
    for i in range(6):
        assert count[f"b{i}_attn"] == 4 * 2048 * 2048
        assert count[f"b{i}_gate_up_proj"] == 2 * 2048 * 5632 == 23_068_672
        assert count[f"b{i}_down_proj"] == 2048 * 5632 == 11_534_336
        for norm in ("norm", "attn_out_norm", "post_norm", "mlp_out_norm"):
            assert count[f"b{i}_{norm}"] == 2048
    assert "b6_attn" not in count
    # no pass has leaves of its own: every leaf ONCE whatever T is
    assert not any(name.startswith("ut") for name in count)
    assert count["embed_tokens"] == count["lm_head"] == 6144 * 2048 \
        == 12_582_912
    assert count["final_ln"] == 2048 and count["exit_gate"] == 2049
    assert sum(count.values()) == family.parameters(s) == (
        6 * (LAYER + 4 * 2048) + 2 * 12_582_912 + 2048 + 2049
    ) == 333_500_417
    assert family.parameters(dict(s, total_ut_steps=1)) == 333_500_417
    # 10 bytes a parameter resident, 28 at the peak of the reference's
    # Adam step
    assert 10 * family.parameters(s) / 1e9 == pytest.approx(3.34, abs=0.01)
    assert 28 * family.parameters(s) / 1e9 == pytest.approx(9.34, abs=0.01)
    # nine layers would pass the reference's cap too (ISSUE 48: 488M)
    assert 333_500_417 + 3 * (LAYER + 4 * 2048) == 487_665_665 < 511e6


def test_flops_and_bytes_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    per = family.forward_flops_per_token(s)
    # T passes of every layer and T heads (with the gate's column)
    assert per["layer_products"] == 24 * 2 * LAYER
    assert per["scores"] == 24 * 4 * 16 * 128 * 4097 / 2
    assert per["heads"] == 4 * 2 * 2048 * (6144 + 1)
    token = sum(per.values())
    step = family.train_flops_per_sample(s)
    assert step == 3 * 4096 * token
    # ISSUE 48: an application 1.263 TFLOP of products and 0.206 of
    # scores; x 24 = 35.25; the four heads 1.24; 36.5 in all
    assert 6 * LAYER * 4096 == pytest.approx(1.263e12, rel=1e-3)
    assert 12 * PAIRS * 2048 == pytest.approx(0.206e12, rel=2e-3)
    assert PAIRS == 8_390_656
    assert 3 * 4096 * (per["layer_products"] + per["scores"]) == \
        pytest.approx(35.25e12, rel=1e-3)
    assert 3 * 4096 * per["heads"] == pytest.approx(1.24e12, rel=3e-3)
    assert step == pytest.approx(36.5e12, rel=1e-3)
    shares = {k: v / token for k, v in per.items()}
    assert shares["layer_products"] == pytest.approx(0.830, abs=0.002)
    assert shares["scores"] == pytest.approx(0.136, abs=0.001)
    assert shares["heads"] == pytest.approx(0.034, abs=0.001)
    # one pass is a quarter of the layers' work and a quarter of the heads'
    once = family.forward_flops_per_token(dict(s, total_ut_steps=1))
    assert {k: 4 * v for k, v in once.items()} == per
    # the 24 ops' flash kernels over the VISIBLE pairs: 4.95 TFLOP, 25.1 ms
    # at the peak; bfloat16 q, k, v, o and their gradients beside them
    flops, nbytes = family.causal_flash_step_flops_and_bytes(s)
    assert flops == 24 * 12 * PAIRS * 2048 == pytest.approx(4.95e12,
                                                           rel=1e-3)
    assert flops / 197e12 == pytest.approx(25.1e-3, rel=2e-3)
    assert nbytes == 24 * 12 * 2 * 4096 * 2048 == 4_831_838_208
    assert nbytes / 819e9 < flops / 197e12             # FLOPs, not bytes


STEP = "jit(train_step)/"
FULL = "jit(attention_full))/"


def pass_rows(ut, at):
    """Three instructions of pass `ut`: a product of the MLP, the flash
    kernel forward, the flash kernel backward."""
    scope = f"jit(ut{ut}))/"
    return {
        f"fusion.{at}": dict(
            op_name=STEP + "jvp(" + scope + "jit(op_linear)/dot_general",
            part=f"ut{ut}", direction="forward"),
        f"flash.{at + 1}": dict(
            op_name=STEP + "jvp(" + scope + FULL[:-2]
            + ")/jit(flash_full)/pallas_call",
            part=f"ut{ut}", direction="forward"),
        f"flash.{at + 2}": dict(
            op_name=STEP + "transpose(jvp(" + scope[:-2] + "))/" + FULL[:-2]
            + ")/jit(flash_full)/pallas_call",
            part=f"ut{ut}", direction="backward")}


TABLE = dict(
    **pass_rows(0, 1), **pass_rows(1, 4),
    **{"fusion.7": dict(op_name=STEP + "jvp(jit(exit))/jit(op_concat)/"
                        "concatenate", part="exit", direction="forward"),
       "fusion.8": dict(op_name=STEP + "jvp(jit(exit))/jit(head)/"
                        "dot_general", part="exit", direction="forward"),
       "fusion.9": dict(op_name=STEP + "transpose(jvp(jit(loss)))/mul",
                        part="loss", direction="backward"),
       "fusion.10": dict(op_name=STEP + "jit(optimizer_update)/add",
                         part="optimizer_update", direction="optimizer")})


def fake_device():
    """One train step of 10 ms: pass ut0 1 + 0.5 + 1 ms, pass ut1 1 + 0.5
    + 1.5 ms (its flash backward the dearer); 0.5 ms of the passes'
    concatenation and 0.5 of the head under `exit`; 0.5 of the loss; 1 of
    the update; 2 idle."""
    return tr.Device("/device:TPU:0", {
        tr.MODULES: [(tr.STEP_MODULE + "(1)", 0.0, 10e-3)],
        tr.OPS: [("fusion.1", 0.0, 1e-3), ("flash.2", 1e-3, 0.5e-3),
                 ("flash.3", 1.5e-3, 1e-3), ("fusion.4", 2.5e-3, 1e-3),
                 ("flash.5", 3.5e-3, 0.5e-3), ("flash.6", 4e-3, 1.5e-3),
                 ("fusion.7", 5.5e-3, 0.5e-3), ("fusion.8", 6e-3, 0.5e-3),
                 ("fusion.9", 6.5e-3, 0.5e-3), ("fusion.10", 7e-3, 1e-3)]})


class FakeFamily:
    observed = {}

    @staticmethod
    def causal_flash_step_flops_and_bytes(sizes):
        return 197e12 * 1.4e-3, 1.0     # 1.4 ms at the bf16 peak


def context(family=FakeFamily, devices=None):
    manifest = mf.load_manifest()
    entry, config, traffic = mf.find_cell(manifest, CELL)
    return dict(devices=devices or [fake_device()], cell=entry,
                config=config, traffic=traffic, family=family,
                counters=dict(sizes={}, peaks=dict(
                    bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)))


def write_table(table):
    where = sr.out_dir(ROOT, CELL)
    os.makedirs(where, exist_ok=True)
    with open(os.path.join(where, "session_r00_host00.step_scopes.json"),
              "w") as f:
        json.dump(dict(header=dict(kind="step_scopes"), instructions=table),
                  f)


def test_new_readers_on_a_made_up_trace(tmp_path, monkeypatch):
    send_output_to(monkeypatch, tmp_path)
    write_table(TABLE)
    read = {name: hs.load_by_path("layer_metrics", name).read(context())
            for name in NEW_METRICS}
    # of the 8 busy ms, 2.5 lie in pass ut0 and 3 in ut1; 1 under `exit`
    # and 0.5 in the loss; the four flash events take 3.5 ms for 1.4 at
    # the peak
    assert read["layers.looped_stack_share_pct"] == pytest.approx(
        100 * 5.5 / 8)
    assert read["layers.loop_pass_max_over_min"] == pytest.approx(3 / 2.5)
    assert read["layers.exit_heads_share_pct"] == pytest.approx(
        100 * 1.5 / 8)
    assert read["kernels.causal_flash_roofline"] == pytest.approx(40.0)
    # the first reader left the whole breakdown beside the session
    with open(os.path.join(sr.out_dir(ROOT, CELL), "step_parts.json")) as f:
        parts = {(p, d): ms for p, d, ms in
                 json.load(f)["part_direction_ms_a_step"]}
    assert parts[("ut0", "forward")] == pytest.approx(1.5)
    assert parts[("ut1", "backward")] == pytest.approx(1.5)
    assert parts[("exit", "forward")] == pytest.approx(1.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(
        name, tmp_path, monkeypatch):
    """A run without a table, a program without the scopes (the parent
    commit's: one pass, no `exit`), a family without the count, the trace
    the v5e recorded of another program: None, no raise."""
    class Bare:
        pass
    send_output_to(monkeypatch, tmp_path)
    reader = hs.load_by_path("layer_metrics", name)
    assert reader.read(context()) is None              # no table
    write_table({"fusion.1": dict(
        op_name=STEP + "jvp(jit(attention_latent))/dot_general",
        part="attention", direction="forward"),
                 "fusion.8": dict(op_name=STEP + "jvp(jit(head))/dot_general",
                                  part="head", direction="forward")})
    assert reader.read(context()) is None              # no such scope
    if name == "kernels.causal_flash_roofline":
        write_table(TABLE)
        assert reader.read(context(Bare)) is None      # no count
    if name == "layers.loop_pass_max_over_min":
        write_table(dict(pass_rows(0, 1)))
        assert reader.read(context()) is None          # one pass alone
    ctx = context(Bare)
    ctx["devices"] = []
    assert reader.read(ctx) is None
    # the recorded trace: its instructions are another program's
    write_table(TABLE)
    recorded = tr.load_chrome(FIXTURE)
    assert recorded and tr.step_spans(recorded[0])
    assert reader.read(context(devices=recorded)) is None
