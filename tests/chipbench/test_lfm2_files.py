"""The data files, FLOP and byte functions and readers that the
`lfm2_8b_a1b` configuration adds: the configuration against the catalog's
row, the cell's files found by name, hand counts, and the new readers on
a made-up trace and join table and on the trace the v5e recorded."""

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

CONFIG = "lfm2_8b_a1b"
CELL = "lfm2_8b_a1b.s16384_b1.1chip"
FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "devtrace_tpu_v5e.trace.json.gz")
PERIOD = ["conv", "conv", "full_attention", "conv"]
# the catalog's row LFM2-8B-A1B (model-configs guide, architectures.jsonl),
# as published
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": PERIOD * 4 + ["conv", "conv", "full_attention", "conv",
                                 "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
REDUCED = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 8192}
# never cut: hidden, head, feed-forward and expert widths, the taps,
# experts a token, the heads (the mixers are whole on every chip)
WIDTHS = ("hidden_size", "head_dim", "intermediate_size",
          "moe_intermediate_size", "num_experts_per_tok", "conv_L_cache",
          "num_attention_heads", "num_key_value_heads")
NEW_METRICS = ("layers.short_conv_share_pct", "kernels.gated_conv_roofline",
               "layers.head64_attention_share_pct",
               "layers.unshared_experts_share_pct")


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
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert "one chip of 8" in listed["why"] and len(listed["why"]) <= 200
    assert "ONE tied table" in listed["why"]
    assert not set(REDUCED) & set(WIDTHS)
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):   # where the guide is installed
        with open(catalog_file) as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows if r["name"] == "LFM2-8B-A1B"]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == config["source"]
    assert len(PUBLISHED["layer_types"]) == 24
    assert PUBLISHED["layer_types"].count("full_attention") == 6
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config[key] == REDUCED[key], key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert set(config["published"]) == set(REDUCED)
    assert config["num_experts_published"] == 32
    # the cut keeps the deployment's ratios: a quarter of the experts, an
    # eighth of the table; the floors: a whole period and four layers
    # after the leading dense one, 8 experts, an eighth of the rows
    assert 32 // 4 == 8 >= 8 and 65536 // 8 == 8192
    first = config["first_layer"]
    assert config["layer_types"][first:first + 5] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert first < config["num_dense_layers"] <= first + 1
    for key in ("source", "deployment", "departures", "assumed", "adam",
                "census", "parameters", "loss_positions"):
        assert config[key], key
    assert "8 chips share each layer" in config["deployment"]
    assumed = " ".join(config["assumed"])
    for said in ("TIED", "8.34B", "8.47B", "head_dim 64", "standard deviation "
                 "0.02", "1/sqrt(3)", "[B ; C ; x]", "pairs (j, j + 32)",
                 "1e-20", "sequence 16,384", "0.02 / sqrt(24)",
                 "balanced state", "slot_slack",
                 "LiquidAI's layout is not public"):
        assert said in assumed, said
    assert any("no auxiliary" in d for d in config["departures"])
    assert any("2,048 (token, slot) pairs" in d
               for d in config["departures"])
    assert "491,043,072" in config["parameters"]


def test_the_cells_files_are_found_by_name(cell):
    manifest, entry, config, traffic, family = cell
    assert entry == dict(name=CELL, config=CONFIG, traffic="s16384_b1",
                         chips=1, why=traffic["why"])
    assert len(entry["why"]) <= 200
    for said in ("conv mixers' products 31%", "2,048 pairs",
                 "8,192 deployed"):
        assert said in entry["why"], said
    assert (traffic["seq"], traffic["batch"], traffic["steps_per_epoch"],
            traffic["reference_chunk"], traffic["part_a_share"]) == (
        16384, 1, 4, 1, 0.5)
    assert config["family"] == "lfm2"
    assert family.reference(family.sizes(config, traffic), traffic)[0] \
        .__name__ == "benchmarks.references.lfm2"
    names = [m["name"] for m in manifest["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput"
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["unit"] == "%"
        assert hasattr(hs.load_by_path("layer_metrics", name), "read")
        # new entries come after everything the benchmark had (PR 41's)
        assert names.index(name) > names.index(
            "kernels.window_keys_visited_ratio")
    assert by_name["kernels.gated_conv_roofline"]["better"] == "higher"
    assert by_name["kernels.gated_conv_roofline"]["layer"] == "kernels"
    assert {by_name[n]["layer"] for n in NEW_METRICS} == {"model ops",
                                                          "kernels"}
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index("laguna_xs2.s8192_b1.1chip")
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index(CONFIG) > configs.index("laguna_xs2")
    reported = {m["name"] for m in mf.metrics_of(manifest, "per_layer",
                                                 CELL)}
    assert set(NEW_METRICS) <= reported
    assert {"device.mfu_pct", "device.idle_pct",
            "compile.model_compile_s"} <= reported
    # the accepted readers keep to their own cells
    assert not reported & {"layers.moe_share_pct",
                           "layers.gated_full_attention_share_pct",
                           "layers.ssm_share_pct", "kernels.flash_roofline"}
    # one four-chip cell of the quarter the benchmark may have
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_parameters_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    import numpy as np
    count = {name: sum(int(np.prod(shape)) for _, shape in leaves.values())
             for name, leaves in family.weight_shapes(s).items()}
    conv = 2048 * 3 * 2048 + 3 * 2048 + 2048 * 2048
    attention = 2 * 2048 * 32 * 64 + 2 * 2048 * 8 * 64 + 2 * 64
    experts = 8 * 3 * 2048 * 1792 + 2048 * 32 + 32
    assert count["b0_conv"] == count["b4_conv"] == conv == 16_783_360
    assert count["b1_attn"] == attention == 10_485_888
    assert count["b1_mixer"] == count["b4_mixer"] == experts == 88_145_952
    assert count["b0_gate_up_proj"] + count["b0_down_proj"] == \
        3 * 2048 * 7168 == 44_040_192
    assert "b0_mixer" not in count and "b1_gate_up_proj" not in count
    assert "b1_conv" not in count and "b0_attn" not in count
    assert count["embed_tokens"] == 8192 * 2048 and "lm_head" not in count
    norms = 2 * 2048
    assert (conv + 44_040_192 + norms, attention + experts + norms,
            conv + experts + norms) == (60_827_648, 98_635_936, 104_933_408)
    assert sum(count.values()) == family.parameters(s) == (
        60_827_648 + 98_635_936 + 3 * 104_933_408 + 8192 * 2048 + 2048
    ) == 491_043_072
    # 10 bytes a parameter resident, 28 at the peak of the reference's
    # Adam step
    assert 10 * family.parameters(s) / 1e9 == pytest.approx(4.91, abs=0.01)
    assert 28 * family.parameters(s) / 1e9 == pytest.approx(13.75, abs=0.01)


def test_flops_and_bytes_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    per = family.forward_flops_per_token(s)
    assert per["conv_products"] == 4 * 2 * 2048 * 4 * 2048 == 134_217_728
    assert per["gated_conv"] == 4 * 2048 * 8
    assert per["projections"] == 2 * 2048 * 64 * (2 * 32 + 2 * 8)
    assert per["scores"] == 4 * 32 * 64 * 16385 / 2
    assert per["dense_mlp"] == 6 * 2048 * 7168
    assert per["experts"] == 4 * (6 * 2048 * 1792 * 4 * 8 / 32
                                  + 2 * 2048 * 32)
    assert per["head"] == 2 * 2048 * 8192
    token = sum(per.values())
    assert token == pytest.approx(432.6e6, rel=1e-3)
    assert family.train_flops_per_sample(s) == 3 * 16384 * token
    assert family.train_flops_per_sample(s) == pytest.approx(21.26e12,
                                                             rel=1e-3)
    # the convolution mixers and the experts they feed are half the step
    assert (per["conv_products"] + per["experts"]) / token == pytest.approx(
        0.515, abs=0.005)
    assert family.expected_held_slots(s) == 16384      # 2,048 an expert
    # the gate-convolution-gate of the four ops, forward and backward:
    # 4 + 7 arrays of T x 2048 bfloat16 an op
    flops, nbytes = family.gated_conv_step_flops_and_bytes(s)
    elements = 16384 * 2048
    assert nbytes == 4 * 22 * elements == 2_952_790_016
    assert flops == 4 * elements * (3 * 8 + 6)
    assert nbytes / 819e9 == pytest.approx(3.605e-3, rel=1e-3)
    assert flops / 197e12 < 0.01 * nbytes / 819e9      # bytes, not FLOPs


STEP = "jit(train_step)/"
CONV = "jit(op_short_conv))/"
TABLE = {
    "fusion.1": dict(op_name=STEP + "jvp(" + CONV + "dot_general",
                     part="op_short_conv", direction="forward"),
    "fusion.2": dict(op_name=STEP + "jvp(" + CONV + "jit(gated_conv)/mul",
                     part="op_short_conv", direction="forward"),
    "fusion.3": dict(op_name=STEP + "transpose(jvp(" + CONV[:-1]
                     + ")/jit(gated_conv)/mul", part="op_short_conv",
                     direction="backward"),
    "fusion.4": dict(op_name=STEP + "jvp(jit(attention_full))/"
                     "jit(rotary_whole)/mul", part="attention",
                     direction="forward"),
    "flash.5": dict(op_name=STEP + "transpose(jvp(jit(attention_full)))/"
                    "jit(flash_full)/pallas_call", part="attention",
                    direction="backward"),
    "fusion.6": dict(op_name=STEP + "jvp(jit(moe_layer))/jit(moe_route)/sort",
                     part="experts", direction="forward"),
    "fusion.7": dict(op_name=STEP + "jvp(jit(head))/dot_general",
                     part="head", direction="forward"),
}


def fake_device():
    """One train step of 10 ms: under the convolution ops 1 ms of a
    product and 0.5 + 1 ms of the gate-convolution-gate; under the
    attention op 0.5 ms of rotary and 2 ms of kernel; 1.5 ms of the
    experts; 1.5 ms of the head; 2 idle."""
    return tr.Device("/device:TPU:0", {
        tr.MODULES: [(tr.STEP_MODULE + "(1)", 0.0, 10e-3)],
        tr.OPS: [("fusion.1", 0.0, 1e-3), ("fusion.2", 1e-3, 0.5e-3),
                 ("fusion.3", 1.5e-3, 1e-3), ("fusion.4", 2.5e-3, 0.5e-3),
                 ("flash.5", 3e-3, 2e-3), ("fusion.6", 5e-3, 1.5e-3),
                 ("fusion.7", 6.5e-3, 1.5e-3)]})


class FakeFamily:
    observed = {}

    @staticmethod
    def gated_conv_step_flops_and_bytes(sizes):
        return 1.0, 819e9 * 0.6e-3       # 0.6 ms at the HBM peak


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
    # of the 8 busy ms, 2.5 lie under `op_short_conv`, 1.5 of them in
    # `gated_conv` for 0.6 at the peak; 2.5 under `attention_full`; 1.5
    # under `moe_layer`
    assert read["layers.short_conv_share_pct"] == pytest.approx(
        100 * 2.5 / 8)
    assert read["kernels.gated_conv_roofline"] == pytest.approx(40.0)
    assert read["layers.head64_attention_share_pct"] == pytest.approx(
        100 * 2.5 / 8)
    assert read["layers.unshared_experts_share_pct"] == pytest.approx(
        100 * 1.5 / 8)
    # the first reader left the whole breakdown beside the session
    with open(os.path.join(sr.out_dir(ROOT, CELL), "step_parts.json")) as f:
        parts = {(p, d): ms for p, d, ms in
                 json.load(f)["part_direction_ms_a_step"]}
    assert parts[("op_short_conv", "forward")] == pytest.approx(1.5)
    assert parts[("op_short_conv", "backward")] == pytest.approx(1.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(
        name, tmp_path, monkeypatch):
    """A run without a table, a program without the scopes (the parent
    commit's), a family without the count, the trace the v5e recorded of
    another program: None, no raise."""
    class Bare:
        pass
    send_output_to(monkeypatch, tmp_path)
    reader = hs.load_by_path("layer_metrics", name)
    assert reader.read(context()) is None              # no table
    write_table({"fusion.1": dict(
        op_name=STEP + "jvp(jit(attention_latent))/dot_general",
        part="attention", direction="forward")})
    assert reader.read(context()) is None              # no such scope
    if name == "kernels.gated_conv_roofline":
        write_table(TABLE)
        assert reader.read(context(Bare)) is None      # no count
    ctx = context(Bare)
    ctx["devices"] = []
    assert reader.read(ctx) is None
    # the recorded trace: its instructions are another program's
    write_table(TABLE)
    recorded = tr.load_chrome(FIXTURE)
    assert recorded and tr.step_spans(recorded[0])
    assert reader.read(context(devices=recorded)) is None


def test_an_older_program_ends_at_once(cell, monkeypatch):
    """Under these files a program whose decoder has no convolution mixer
    (the parent commit's) is refused by `sizes`, before any weight is
    made: a clean exit, soon."""
    import dataclasses

    from flexflow_tpu import models
    _, _, config, traffic, family = cell

    @dataclasses.dataclass
    class Older:
        hidden_size: int = 64
    monkeypatch.setattr(models, "DecoderConfig", Older)
    with pytest.raises(SystemExit, match="gated short convolution"):
        family.sizes(config, traffic)
