"""The data files, FLOP and byte functions and readers that the
`joyai_llm_flash` configuration adds: the configuration against the
catalog's row, the cell's files found by name, hand counts, and the new
readers on a made-up trace and join table."""

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

CONFIG = "joyai_llm_flash"
CELL = "joyai_llm_flash.s4096_b1.1chip"
# the numbers of the catalog's row JoyAI-LLM-Flash (model-configs guide,
# architectures.jsonl), as published
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 8,
           "vocab_size": 16160}
# never cut: hidden, latent, head and expert widths, experts a token
WIDTHS = ("hidden_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "qk_head_dim", "v_head_dim", "head_dim",
          "intermediate_size", "moe_intermediate_size",
          "num_experts_per_tok", "num_attention_heads")
NEW_METRICS = ("layers.latent_attention_share_pct",
               "kernels.latent_flash_roofline", "layers.mtp_share_pct")


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
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
        "config.json")
    assert "one chip of 32 that share each layer" in listed["why"]
    assert "latent attention" in listed["why"] and len(listed["why"]) <= 200
    assert len(listed["source"]) <= 200
    assert not set(REDUCED) & set(WIDTHS)
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config[key] == REDUCED[key], key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert set(config["published"]) == set(REDUCED)
    assert config["n_routed_experts_published"] == 256
    # the cut keeps the deployment's ratios: a 32nd of the experts, an
    # eighth of the vocabulary
    assert 256 // 32 == 8 and 129280 // 8 == 16160
    for key in ("source", "deployment", "departures", "assumed", "adam",
                "census"):
        assert config[key]
    assert "32 chips share each layer" in config["deployment"]
    assert config["mtp_loss_weight"] == 0.3
    assumed = " ".join(config["assumed"])
    for said in ("lambda 0.3", "BEFORE the final norm", "in that order",
                 "sequence 4,096", "e_score_correction_bias",
                 "0.02 / sqrt(40)", "slot_slack"):
        assert said in assumed, said
    assert any("no auxiliary" in d for d in config["departures"])


def test_the_cells_files_are_found_by_name(cell):
    manifest, entry, config, traffic, family = cell
    assert entry == dict(name=CELL, config=CONFIG, traffic="s4096_b1",
                         chips=1, why=traffic["why"])
    assert len(entry["why"]) <= 200
    for said in ("latent attention 65%", "MTP 22%", "128 tokens",
                 "4,096 deployed"):
        assert said in entry["why"], said
    assert (traffic["seq"], traffic["batch"], traffic["steps_per_epoch"],
            traffic["reference_chunk"], traffic["part_a_share"]) == (
        4096, 1, 4, 1, 0.5)
    assert config["family"] == "joyai_flash"
    assert family.reference(family.sizes(config, traffic), traffic)[0] \
        .__name__ == "benchmarks.references.joyai_flash"
    names = [m["name"] for m in manifest["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput"
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["unit"] == "%"
        assert hasattr(hs.load_by_path("layer_metrics", name), "read")
        # new entries come after everything the benchmark had (PR 36's)
        assert names.index(name) > names.index(
            "layers.moe_combine_share_pct")
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index("sdar_30b_a3b.s8192_b1.1chip")
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index(CONFIG) > configs.index("sdar_30b_a3b")
    reported = {m["name"] for m in mf.metrics_of(manifest, "per_layer",
                                                 CELL)}
    assert set(NEW_METRICS) <= reported
    # the accepted readers keep to their own cells
    assert not reported & {"layers.moe_share_pct",
                           "kernels.grouped_matmul_roofline",
                           "kernels.block_diffusion_flash_roofline",
                           "kernels.flash_roofline"}
    # one four-chip cell of the quarter the benchmark may have
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_parameters_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    import numpy as np
    count = {name: sum(int(np.prod(shape)) for _, shape in leaves.values())
             for name, leaves in family.weight_shapes(s).items()}
    attention = (2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512
                 + 512 * 32 * 256 + 32 * 128 * 2048)
    experts = 2048 * 256 + 256 + 9 * 3 * 2048 * 768
    assert count["b0_attn"] == count["mtp_attn"] == attention == 26_347_520
    assert count["b1_mixer"] == count["mtp_mixer"] == experts == 42_991_872
    assert count["b0_gate_up_proj"] + count["b0_down_proj"] == \
        3 * 2048 * 7168
    assert count["embed_tokens"] == count["lm_head"] == 16160 * 2048
    expert_layer = attention + experts + 2 * 2048
    dense_layer = attention + 3 * 2048 * 7168 + 2 * 2048
    module = 4096 * 2048 + expert_layer + 3 * 2048
    assert (expert_layer, dense_layer, module) == (
        69_343_488, 70_391_808, 77_738_240)
    assert sum(count.values()) == family.parameters(s) == (
        dense_layer + 4 * expert_layer + module + 2 * 16160 * 2048 + 2048
    ) == 491_697_408
    assert family.decoder_pattern(s) == "AXXXX"
    assert family.expert_prefixes(s) == ["b1", "b2", "b3", "b4", "mtp"]
    # 28 bytes a parameter at the peak of the reference's Adam step
    assert 28 * family.parameters(s) / 1e9 == pytest.approx(13.77, abs=0.01)


def test_flops_and_bytes_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    pairs = 4096 * 4097 // 2
    assert family.visible_pairs(s) == pairs == 8_390_656
    per = family.forward_flops_per_token(s)
    assert per["projections"] == 2 * (26_347_520 - 1536 - 512) == 52_690_944
    assert per["scores"] == 2 * 32 * 320 * pairs / 4096 == 41_953_280
    assert per["dense_mlp"] == 6 * 2048 * 7168
    assert per["experts"] == (6 * 2048 * 768 * 8 * 8 / 256
                              + 6 * 2048 * 768 + 2 * 2048 * 256)
    assert per["mtp_projection"] == 2 * 4096 * 2048
    assert per["head"] == 2 * 2048 * 16160
    token = (6 * (per["projections"] + per["scores"]) + per["dense_mlp"]
             + 5 * per["experts"] + per["mtp_projection"] + 2 * per["head"])
    assert token == pytest.approx(869.3e6, rel=1e-3)
    assert family.train_flops_per_sample(s) == 3 * 4096 * token
    assert family.train_flops_per_sample(s) == pytest.approx(10.68e12,
                                                             rel=1e-3)
    assert family.expected_held_slots(s) == 1024      # 128 an expert
    # the flash kernels of the six ops, forward and backward
    flops, nbytes = family.latent_flash_step_flops_and_bytes(s)
    assert flops == 6 * 32 * pairs * (2 * 320 + 2 * 640) == \
        pytest.approx(3.093e12, rel=1e-3)
    row = 2 * 4096                                    # bytes a lane
    forward = row * (32 * 192 + 32 * 128 + 64 + 2 * 32 * 128)
    backward = forward + row * (32 * 128 + 32 * 192 + 32 * 128 + 32 * 128
                                + 32 * 128)
    assert nbytes == 6 * (forward + backward)
    assert flops / 197e12 > nbytes / 819e9            # FLOP-bound: 15.7 ms
    assert flops / 197e12 == pytest.approx(15.70e-3, rel=1e-3)


STEP = "jit(train_step)/"
TABLE = {
    "fusion.1": dict(op_name=STEP + "jvp(jit(attention_latent))/dot_general",
                     part="attention", direction="forward"),
    "flash.2": dict(op_name=STEP + "jvp(jit(attention_latent))/"
                    "jit(flash_latent)/pallas_call", part="attention",
                    direction="forward"),
    "flash.3": dict(op_name=STEP + "transpose(jvp(jit(mtp)))/"
                    "jit(attention_latent)/jit(flash_latent)/pallas_call",
                    part="mtp", direction="backward"),
    "fusion.4": dict(op_name=STEP + "jvp(jit(mtp))/jit(moe_layer)/"
                     "dot_general", part="mtp", direction="forward"),
    "fusion.5": dict(op_name=STEP + "jvp(jit(head))/dot_general",
                     part="head", direction="forward"),
}


def fake_device():
    """One train step of 10 ms: 2 + 1 ms of the trunk's attention (1 in
    its kernel), 3 ms of the module's kernel, 2 of its experts, 1 of the
    head, 1 idle."""
    return tr.Device("/device:TPU:0", {
        tr.MODULES: [(tr.STEP_MODULE + "(1)", 0.0, 10e-3)],
        tr.OPS: [("fusion.1", 0.0, 2e-3), ("flash.2", 2e-3, 1e-3),
                 ("flash.3", 3e-3, 3e-3), ("fusion.4", 6e-3, 2e-3),
                 ("fusion.5", 8e-3, 1e-3)]})


class FakeFamily:
    @staticmethod
    def latent_flash_step_flops_and_bytes(sizes):
        return 197e12 * 3e-3, 1.0       # 3 ms at the peak


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
    # 6 of the 9 busy ms lie under `attention_latent`, the module's among
    # them; 5 are the module's own; the kernels take 4 ms for 3 at the peak
    assert read["layers.latent_attention_share_pct"] == pytest.approx(
        100 * 6 / 9)
    assert read["layers.mtp_share_pct"] == pytest.approx(100 * 5 / 9)
    assert read["kernels.latent_flash_roofline"] == pytest.approx(75.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(
        name, tmp_path, monkeypatch):
    """A run without a table, a program without the scopes (the parent
    commit's), a family without the count: None, no raise."""
    class Bare:
        pass
    send_output_to(monkeypatch, tmp_path)
    reader = hs.load_by_path("layer_metrics", name)
    assert reader.read(context()) is None                  # no table
    write_table({"fusion.1": dict(
        op_name=STEP + "jvp(jit(attention_full))/dot_general",
        part="attention", direction="forward")})
    assert reader.read(context()) is None                  # no such scope
    assert reader.read(context(Bare)) is None
    ctx = context()
    ctx["devices"] = []
    assert reader.read(ctx) is None
