"""The data files, FLOP and byte functions and readers that the
`sdar_30b_a3b` configuration adds: the configuration against the catalog's
row, the cell's files found by name, hand counts, and the new scope
readers on a made-up trace."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness as hs  # noqa: E402
from benchmarks import manifest as mf  # noqa: E402
from benchmarks import trace_reduce as tr  # noqa: E402

CONFIG = "sdar_30b_a3b"
CELL = "sdar_30b_a3b.s8192_b1.1chip"
# the numbers of the catalog's row SDAR-30B-A3B-Chat (model-configs guide,
# architectures.jsonl), as published
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992,
           "num_attention_heads": 8, "num_key_value_heads": 1}
# never cut: hidden, head and expert widths, experts a token
WIDTHS = ("hidden_size", "head_dim", "moe_intermediate_size",
          "intermediate_size", "num_experts_per_tok")
NEW_METRICS = ("layers.block_diffusion_attention_share_pct",
               "kernels.block_diffusion_flash_roofline")


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
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
        "config.json")
    assert "one chip of 8 that share each layer" in listed["why"]
    assert "block-diffusion" in listed["why"] and len(listed["why"]) <= 200
    assert not set(REDUCED) & set(WIDTHS)
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config[key] == REDUCED[key], key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert set(config["published"]) == set(REDUCED)
    assert config["num_experts_published"] == 128
    # the cut keeps the deployment's ratios: 8 query heads a key/value
    # head, an eighth of the experts and of the vocabulary
    assert 32 // 4 == 8 // 1 and 128 // 8 == 16 and 151936 // 8 == 18992
    for key in ("source", "deployment", "departures", "assumed", "adam"):
        assert config[key]
    assert "8 chips share each layer" in config["deployment"]
    # what the catalog does not give is assumed, in the file
    assert config["block_length"] == 4 and config["noise_t_min"] == 1e-3
    assumed = " ".join(config["assumed"])
    for said in ("block length B = 4", "noise schedule", "uniform on "
                 "[0.001, 1]", "1/t", "row 18,991", "i mod L", "half-split",
                 "RMS norm of every query and key head", "mask_embedding_std",
                 "qk_norm_scale", "slot_slack"):
        assert said in assumed, said
    assert any("no auxiliary" in d for d in config["departures"])


def test_the_cells_files_are_found_by_name(cell):
    manifest, entry, config, traffic, family = cell
    assert entry == dict(name=CELL, config=CONFIG, traffic="s8192_b1",
                         chips=1, why=traffic["why"])
    assert len(entry["why"]) <= 200
    assert (traffic["seq"], traffic["batch"], traffic["steps_per_epoch"],
            traffic["reference_chunk"], traffic["part_a_share"]) == (
        8192, 1, 4, 1, 0.5)
    assert config["family"] == "sdar"
    assert family.reference(family.sizes(config, traffic), traffic)[0] \
        .__name__ == "benchmarks.references.sdar"
    names = [m["name"] for m in manifest["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput"
        assert by_name[name]["source"] == "device_trace"
        assert hasattr(hs.load_by_path("layer_metrics", name), "read")
        # new entries come after everything the benchmark had (PR 31's)
        assert names.index(name) > names.index(
            "kernels.window_flash_roofline")
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index(
        "smallthinker_21b_a3b.s16384_b1.1chip")
    reported = {m["name"] for m in mf.metrics_of(manifest, "per_layer",
                                                 CELL)}
    assert set(NEW_METRICS) <= reported
    # the accepted expert-layer readers keep to their own cells
    assert not reported & {"layers.moe_share_pct",
                           "kernels.grouped_matmul_roofline",
                           "kernels.window_flash_roofline",
                           "kernels.flash_roofline"}
    # one four-chip cell of the quarter the benchmark may have
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_parameters_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    import numpy as np
    count = {name: sum(int(np.prod(shape)) for _, shape in leaves.values())
             for name, leaves in family.weight_shapes(s).items()}
    attention = 2 * 2048 * 8 * 128 + 2 * 2048 * 128 + 2 * 128
    experts = 2048 * 128 + 16 * 3 * 2048 * 768
    assert count["b0_attn"] == attention == 4_718_848
    assert count["b1_mixer"] == experts == 75_759_616
    assert count["embed_tokens"] == count["lm_head"] == 18992 * 2048
    layer = attention + experts + 2 * 2048
    assert layer == 80_482_560
    assert sum(count.values()) == 4 * layer + 2 * 18992 * 2048 + 2048 \
        == 399_723_520
    assert family.decoder_pattern(s) == "DDDD"
    assert family.pattern_of(s).count("E") == 4
    assert family.mask_id(s) == 18991


def test_flops_and_bytes_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    # 2,048 blocks of 4 in each half
    pairs = 16 * 2048 + 16 * (2048 * 2047 // 2) + 16 * (2048 * 2049 // 2)
    assert family.visible_pairs(s) == pairs == 67_141_632
    assert pairs / 16384 ** 2 == pytest.approx(0.2501, abs=1e-4)
    per = family.forward_flops_per_position(s)
    assert per["projections"] == 2 * 2048 * 128 * 18 == 9_437_184
    assert per["scores"] == 4 * 1024 * pairs / 16384 == 4 * 1024 * 4098
    assert per["experts"] == 6 * 2048 * 768 * 8 * 16 / 128 == 9_437_184
    assert per["router"] == 2 * 2048 * 128
    position = sum(per.values())
    assert position == pytest.approx(36.18e6, rel=1e-3)
    head = 2 * 2048 * 18992
    assert family.train_flops_per_sample(s) == 3 * 8192 * (
        2 * 4 * position + head)
    assert family.train_flops_per_sample(s) == pytest.approx(9.025e12,
                                                             rel=1e-3)
    # the flash kernels of the four layers, forward and backward
    flops, nbytes = family.block_diffusion_flash_step_flops_and_bytes(s)
    assert flops == 4 * 12 * pairs * 1024 == pytest.approx(3.300e12,
                                                           rel=1e-3)
    assert nbytes == 4 * 12 * 2 * 16384 * 1024 == pytest.approx(1.61e9,
                                                                rel=1e-2)
    assert flops / 197e12 > nbytes / 819e9       # FLOP-bound: 16.75 ms
    assert flops / 197e12 == pytest.approx(16.75e-3, rel=1e-3)
    # the grouped products: three matrices, forward and two backward each
    assert family.expected_held_slots(s) == 16384
    flops, nbytes = family.grouped_matmul_step_flops_and_bytes(s)
    assert flops == 4 * 18 * 16384 * 2048 * 768
    assert nbytes == 4 * 9 * (2 * 16 * 2048 * 768
                              + 2 * 16384 * (2048 + 768))
    half, _ = family.grouped_matmul_step_flops_and_bytes(s, slots=8192)
    assert half == flops / 2


class FakeFamily:
    """What the readers ask of a family, with made-up scopes."""
    observed = {"scopes": {
        "fusion.1": "jit(train_step)/jvp(jit(attention_block_diffusion))/"
                    "dot_general",
        "custom.2": "jit(train_step)/jvp(jit(attention_block_diffusion))/"
                    "jit(flash_block_diffusion)/pallas_call",
        "custom.3": "jit(train_step)/transpose(jvp(jit("
                    "attention_block_diffusion)))/"
                    "jit(flash_block_diffusion)/pallas_call",
        "fusion.5": "jit(train_step)/jvp(jit(moe_layer))/dot_general"}}

    @staticmethod
    def block_diffusion_flash_step_flops_and_bytes(sizes):
        return 197e12 * 3e-3, 1.0       # 3 ms at the peak


def fake_device():
    """One train step of 10 ms: 6 ms of attention, 4 of them in its
    kernels, 3 ms of experts, 1 ms idle."""
    return tr.Device("/device:TPU:0", {
        tr.MODULES: [(tr.STEP_MODULE + "(1)", 0.0, 10e-3)],
        tr.OPS: [("fusion.1", 0.0, 2e-3), ("custom.2", 2e-3, 1e-3),
                 ("custom.3", 3e-3, 3e-3), ("fusion.5", 6e-3, 3e-3)]})


def test_new_readers_on_a_made_up_trace():
    ctx = dict(devices=[fake_device()], family=FakeFamily, counters=dict(
        sizes={}, peaks=dict(bf16_flops_per_s=197e12,
                             hbm_bytes_per_s=819e9)))
    read = {name: hs.load_by_path("layer_metrics", name).read(ctx)
            for name in NEW_METRICS}
    assert read["layers.block_diffusion_attention_share_pct"] == \
        pytest.approx(100 * 6 / 9)
    assert read["kernels.block_diffusion_flash_roofline"] == pytest.approx(
        75.0)
    # the accepted attention readers find nothing under these scopes
    for other in ("layers.window_attention_share_pct",
                  "layers.full_attention_share_pct",
                  "kernels.window_flash_roofline"):
        assert hs.load_by_path("layer_metrics", other).read(ctx) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(name):
    """A program without the scopes (the parent commit's), a family
    without the byte function, a trace without devices: None, no raise."""
    class Bare:
        pass
    reader = hs.load_by_path("layer_metrics", name)
    counters = dict(sizes={}, peaks=dict(bf16_flops_per_s=1.0,
                                         hbm_bytes_per_s=1.0))
    assert reader.read(dict(devices=[], family=Bare,
                            counters=counters)) is None
    assert reader.read(dict(devices=[fake_device()], family=Bare,
                            counters=counters)) is None
    no_scope = type("F", (), {
        "observed": {"scopes": {"fusion.1": "x"}},
        "block_diffusion_flash_step_flops_and_bytes":
        staticmethod(lambda s: (1.0, 1.0))})
    assert reader.read(dict(devices=[fake_device()], family=no_scope,
                            counters=counters)) is None
