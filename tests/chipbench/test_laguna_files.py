"""The data files, FLOP and byte functions and readers that the
`laguna_xs2` configuration adds: the configuration against the catalog's
row, the cell's files found by name, hand counts, and the new readers on
a made-up trace and join table."""

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

CONFIG = "laguna_xs2"
CELL = "laguna_xs2.s8192_b1.1chip"
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# the catalog's row Laguna-XS.2 (model-configs guide, architectures.jsonl),
# as published
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": PERIOD * 10,
    "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}
REDUCED = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 12544}
# never cut: hidden, head, feed-forward and expert widths, the window,
# experts a token, the heads (attention is whole on every chip)
WIDTHS = ("hidden_size", "head_dim", "intermediate_size",
          "moe_intermediate_size", "shared_expert_intermediate_size",
          "num_experts_per_tok", "sliding_window", "num_attention_heads",
          "num_key_value_heads", "num_attention_heads_per_layer",
          "rope_parameters", "partial_rotary_factor")
NEW_METRICS = ("layers.gated_window_attention_share_pct",
               "layers.gated_full_attention_share_pct",
               "layers.attention_gate_share_pct",
               "kernels.narrow_window_flash_roofline",
               "kernels.window_keys_visited_ratio")
TRACED = NEW_METRICS[:4]


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
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")
    assert "one chip of 16" in listed["why"] and len(listed["why"]) <= 200
    assert "48 / 64 query heads" in listed["why"]
    assert not set(REDUCED) & set(WIDTHS)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    catalog = [r for r in rows if r["name"] == "Laguna-XS.2"]
    if catalog:        # the catalog itself, where the guide is installed
        assert catalog[0]["config"] == PUBLISHED
        assert catalog[0]["source_url"] == config["source"]
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config[key] == REDUCED[key], key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert set(config["published"]) == set(REDUCED)
    assert config["num_experts_published"] == 256
    # the cut keeps the deployment's ratios: a 16th of the experts, an
    # eighth of the vocabulary; the floors: a whole period and four
    # layers after the dense one, 8 experts, an eighth of the rows
    assert 256 // 16 == 16 >= 8 and 100352 // 8 == 12544
    assert config["layer_types"][1:5] == PERIOD[1:] + PERIOD[:1]
    for key in ("source", "deployment", "departures", "assumed", "adam",
                "census", "parameters", "loss_positions"):
        assert config[key], key
    assert "16 chips share each layer" in config["deployment"]
    assumed = " ".join(config["assumed"])
    for said in ("softplus(h W_g)", "sigmoid scores", "low = floor",
                 "rotate_half", "0 <= i - j < 512", "sequence 8,192",
                 "0.02 / sqrt(40)", "balanced state", "slot_slack",
                 "poolside's layout is not public"):
        assert said in assumed, said
    assert any("no auxiliary" in d for d in config["departures"])
    assert any("256 (token, slot) pairs" in d for d in config["departures"])
    assert "490.3M" in config["parameters"]


def test_the_cells_files_are_found_by_name(cell):
    manifest, entry, config, traffic, family = cell
    assert entry == dict(name=CELL, config=CONFIG, traffic="s8192_b1",
                         chips=1, why=traffic["why"])
    assert len(entry["why"]) <= 200
    for said in ("gated attention 75%", "window-512", "256 tokens",
                 "4,096 deployed"):
        assert said in entry["why"], said
    assert (traffic["seq"], traffic["batch"], traffic["steps_per_epoch"],
            traffic["reference_chunk"], traffic["part_a_share"]) == (
        8192, 1, 4, 1, 0.5)
    assert config["family"] == "laguna"
    assert family.reference(family.sizes(config, traffic), traffic)[0] \
        .__name__ == "benchmarks.references.laguna"
    names = [m["name"] for m in manifest["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput"
        assert hasattr(hs.load_by_path("layer_metrics", name), "read")
        # new entries come after everything the benchmark had (PR 39's)
        assert names.index(name) > names.index("layers.mtp_share_pct")
    assert {by_name[n]["source"] for n in TRACED} == {"device_trace"}
    assert {by_name[n]["unit"] for n in TRACED} == {"%"}
    assert by_name[NEW_METRICS[4]]["source"] == "program_counter"
    assert by_name[NEW_METRICS[4]]["better"] == "lower"
    assert {by_name[n]["layer"] for n in NEW_METRICS} == {"model ops",
                                                          "kernels"}
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index("joyai_llm_flash.s4096_b1.1chip")
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index(CONFIG) > configs.index("joyai_llm_flash")
    reported = {m["name"] for m in mf.metrics_of(manifest, "per_layer",
                                                 CELL)}
    assert set(NEW_METRICS) <= reported
    assert {"device.mfu_pct", "device.idle_pct",
            "compile.model_compile_s"} <= reported
    # the accepted readers keep to their own cells
    assert not reported & {"layers.moe_share_pct",
                           "layers.window_attention_share_pct",
                           "kernels.window_flash_roofline",
                           "kernels.latent_flash_roofline",
                           "kernels.flash_roofline"}
    # one four-chip cell of the quarter the benchmark may have
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_parameters_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    import numpy as np
    count = {name: sum(int(np.prod(shape)) for _, shape in leaves.values())
             for name, leaves in family.weight_shapes(s).items()}
    full = 2 * 2048 * 48 * 128 + 2 * 2048 * 8 * 128 + 2048 * 48
    window = 2 * 2048 * 64 * 128 + 2 * 2048 * 8 * 128 + 2048 * 64
    experts = 2048 * 256 + 256 + 17 * 3 * 2048 * 512
    assert count["b0_attn"] == count["b4_attn"] == full == 29_458_432
    assert count["b1_attn"] == count["b3_attn"] == window == 37_879_808
    assert count["b1_mixer"] == count["b4_mixer"] == experts == 54_001_920
    assert count["b0_gate_up_proj"] + count["b0_down_proj"] == \
        3 * 2048 * 8192 == 50_331_648
    assert "b0_mixer" not in count and "b1_gate_up_proj" not in count
    assert count["embed_tokens"] == count["lm_head"] == 12544 * 2048
    norms = 2 * 2048
    assert (full + 50_331_648 + norms, window + experts + norms,
            full + experts + norms) == (79_794_176, 91_885_824, 83_464_448)
    assert sum(count.values()) == family.parameters(s) == (
        79_794_176 + 3 * 91_885_824 + 83_464_448 + 2 * 12544 * 2048 + 2048
    ) == 490_298_368
    # 10 bytes a parameter resident, 28 at the peak of the reference's
    # Adam step
    assert 10 * family.parameters(s) / 1e9 == pytest.approx(4.90, abs=0.01)
    assert 28 * family.parameters(s) / 1e9 == pytest.approx(13.73, abs=0.01)


def test_flops_and_bytes_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    causal = 8192 * 8193 // 2
    seen = 8192 * 512 - 512 * 511 // 2
    assert family.visible_pairs(8192) == causal
    assert family.visible_pairs(8192, 512) == seen == 4_063_488
    per = family.forward_flops_per_token(s)
    a_full = 2 * 2048 * 128 * (2 * 48 + 16) + 2 * 2048 * 48
    a_window = 2 * 2048 * 128 * (2 * 64 + 16) + 2 * 2048 * 64
    assert (a_full, a_window) == (58_916_864, 75_759_616)
    assert per["projections"] == 2 * a_full + 3 * a_window
    assert per["full_scores"] == 2 * 4 * 48 * 128 * causal / 8192
    assert per["window_scores"] == 3 * 4 * 64 * 128 * seen / 8192
    assert per["dense_mlp"] == 6 * 2048 * 8192
    assert per["experts"] == 4 * (6 * 2048 * 512 * 8 * 16 / 256
                                  + 6 * 2048 * 512 + 2 * 2048 * 256)
    assert per["head"] == 2 * 2048 * 12544
    token = sum(per.values())
    assert token == pytest.approx(789.2e6, rel=1e-3)
    assert family.train_flops_per_sample(s) == 3 * 8192 * token
    assert family.train_flops_per_sample(s) == pytest.approx(19.40e12,
                                                             rel=1e-3)
    # gated attention of the two kinds is three quarters of it
    attention = per["projections"] + per["full_scores"] + per["window_scores"]
    assert attention / token == pytest.approx(0.754, abs=0.002)
    assert family.expected_held_slots(s) == 4096      # 256 an expert
    # the window kernels of the three 64-head ops, forward and backward,
    # over the visible pairs alone
    flops, nbytes = family.narrow_window_flash_step_flops_and_bytes(s)
    assert flops == 3 * 64 * seen * (4 * 128 + 8 * 128)
    assert nbytes == 3 * 12 * 2 * 8192 * 64 * 128
    assert flops / 197e12 == pytest.approx(6.083e-3, rel=1e-3)
    assert nbytes / 819e9 == pytest.approx(5.900e-3, rel=1e-3)


STEP = "jit(train_step)/"
TABLE = {
    "fusion.1": dict(op_name=STEP + "jvp(jit(attention_window))/dot_general",
                     part="attention", direction="forward"),
    "flash.2": dict(op_name=STEP + "jvp(jit(attention_window))/"
                    "jit(flash_window)/pallas_call", part="attention",
                    direction="forward"),
    "flash.3": dict(op_name=STEP + "transpose(jvp(jit(attention_window)))/"
                    "jit(flash_window)/pallas_call", part="attention",
                    direction="backward"),
    "fusion.4": dict(op_name=STEP + "jvp(jit(attention_full))/"
                     "jit(attention_gate)/mul", part="attention",
                     direction="forward"),
    "fusion.5": dict(op_name=STEP + "transpose(jvp(jit(attention_window)))/"
                     "jit(attention_gate)/reduce_sum", part="attention",
                     direction="backward"),
    "flash.6": dict(op_name=STEP + "jvp(jit(attention_full))/"
                    "jit(flash_full)/pallas_call", part="attention",
                    direction="forward"),
    "fusion.7": dict(op_name=STEP + "jvp(jit(head))/dot_general",
                     part="head", direction="forward"),
}


def fake_device():
    """One train step of 10 ms: under the window ops 1 ms of projections,
    1 + 2 ms of kernels and 0.5 ms of the gate's backward; under the full
    ops 0.5 ms of the gate and 2 ms of kernel; 1 ms of the head; 2 idle."""
    return tr.Device("/device:TPU:0", {
        tr.MODULES: [(tr.STEP_MODULE + "(1)", 0.0, 10e-3)],
        tr.OPS: [("fusion.1", 0.0, 1e-3), ("flash.2", 1e-3, 1e-3),
                 ("flash.3", 2e-3, 2e-3), ("fusion.4", 4e-3, 0.5e-3),
                 ("fusion.5", 4.5e-3, 0.5e-3), ("flash.6", 5e-3, 2e-3),
                 ("fusion.7", 7e-3, 1e-3)]})


class FakeFamily:
    observed = {"op_counters": {"attention/window_keys_visited": 300.0,
                                "attention/window_keys_visible": 120.0}}

    @staticmethod
    def narrow_window_flash_step_flops_and_bytes(sizes):
        return 197e12 * 1.5e-3, 1.0       # 1.5 ms at the peak


def context(family=FakeFamily):
    manifest = mf.load_manifest()
    entry, config, traffic = mf.find_cell(manifest, CELL)
    return dict(devices=[fake_device()], cell=entry, config=config,
                traffic=traffic, family=family, counters=dict(
                    sizes={}, peaks=dict(bf16_flops_per_s=197e12,
                                         hbm_bytes_per_s=819e9)))


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
    # of the 8 busy ms, 4.5 lie under `attention_window` and 2.5 under
    # `attention_full`, the gate's 0.5 + 0.5 among them; the window
    # kernels take 3 ms for 1.5 at the peak
    assert read["layers.gated_window_attention_share_pct"] == pytest.approx(
        100 * 4.5 / 8)
    assert read["layers.gated_full_attention_share_pct"] == pytest.approx(
        100 * 2.5 / 8)
    assert read["layers.attention_gate_share_pct"] == pytest.approx(
        100 * 1.0 / 8)
    assert read["kernels.narrow_window_flash_roofline"] == pytest.approx(50.0)
    assert read["kernels.window_keys_visited_ratio"] == pytest.approx(2.5)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(
        name, tmp_path, monkeypatch):
    """A run without a table, a program without the scopes or the gauges
    (the parent commit's), a family without the count: None, no raise."""
    class Bare:
        pass
    send_output_to(monkeypatch, tmp_path)
    reader = hs.load_by_path("layer_metrics", name)
    if name in TRACED:
        assert reader.read(context()) is None              # no table
    write_table({"fusion.1": dict(
        op_name=STEP + "jvp(jit(attention_latent))/dot_general",
        part="attention", direction="forward")})
    if name in TRACED:
        assert reader.read(context()) is None              # no such scope
    assert reader.read(context(Bare)) is None or name in TRACED[:3]
    ctx = context(Bare)
    ctx["devices"] = []
    assert reader.read(ctx) is None
