"""The data files, FLOP and byte functions and readers that the
`smallthinker_21b_a3b` configuration adds: the configuration against the
catalog's row, the cell's files found by name, hand counts, and the new
scope readers on a made-up trace."""

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

CONFIG = "smallthinker_21b_a3b"
CELL = "smallthinker_21b_a3b.s16384_b1.1chip"
LAYOUT = [0, 1, 1, 1] * 13
# the numbers of the catalog's row SmallThinker-21BA3B-Instruct
# (model-configs guide, architectures.jsonl), as published
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "moe_num_primary_experts": 8,
           "vocab_size": 18992, "num_attention_heads": 7,
           "num_key_value_heads": 1}
# never cut: hidden, head and expert widths, experts a token, the window
WIDTHS = ("hidden_size", "head_dim", "moe_ffn_hidden_size",
          "moe_num_active_primary_experts", "sliding_window_size")
NEW_METRICS = ("layers.window_attention_share_pct",
               "layers.full_attention_share_pct",
               "kernels.window_flash_roofline")


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
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    assert "8 that share each layer" in listed["why"]
    assert not set(REDUCED) & set(WIDTHS)
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config[key] == REDUCED[key], key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert set(config["published"]) == set(REDUCED)
    assert config["moe_num_primary_experts_published"] == 64
    # the cut keeps the deployment's ratios: 7 query heads a key/value
    # head, an eighth of the experts and of the vocabulary
    assert 28 // 4 == 7 // 1 and 64 // 8 == 8 and 151936 // 8 == 18992
    for key in ("source", "deployment", "departures", "assumed", "adam"):
        assert config[key]
    assert "8 chips share each layer" in config["deployment"]
    assert any("secondary experts" in d and "NOT built" in d
               for d in config["departures"])
    assert any("i - j < 4096" in a for a in config["assumed"])


def test_the_cells_files_are_found_by_name(cell):
    manifest, entry, config, traffic, family = cell
    assert entry == dict(name=CELL, config=CONFIG, traffic="s16384_b1",
                         chips=1, why=traffic["why"])
    assert len(entry["why"]) <= 200
    assert (traffic["seq"], traffic["batch"], traffic["steps_per_epoch"],
            traffic["reference_chunk"], traffic["part_a_share"]) == (
        16384, 1, 4, 1, 0.5)
    assert config["family"] == "smallthinker"
    assert family.reference(family.sizes(config, traffic), traffic)[0] \
        .__name__ == "benchmarks.references.smallthinker"
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput"
        assert by_name[name]["source"] == "device_trace"
        assert hasattr(hs.load_by_path("layer_metrics", name), "read")
    # new entries come last, after everything the benchmark had
    assert [m["name"] for m in manifest["per_layer"]][-3:] == list(
        NEW_METRICS)
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == CONFIG
    reported = {m["name"] for m in mf.metrics_of(manifest, "per_layer",
                                                 CELL)}
    assert set(NEW_METRICS) <= reported
    assert "kernels.flash_roofline" not in reported   # non-causal MHA's


def test_parameters_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    import numpy as np
    count = {name: sum(int(np.prod(shape)) for _, shape in leaves.values())
             for name, leaves in family.weight_shapes(s).items()}
    attention = 2 * 2560 * 7 * 128 + 2 * 2560 * 128
    experts = 2560 * 64 + 8 * 3 * 2560 * 768
    assert count["b0_attn"] == attention == 5_242_880
    assert count["b1_mixer"] == experts == 47_349_760
    assert count["embed_tokens"] == count["lm_head"] == 18992 * 2560
    layer = attention + experts + 2 * 2560
    assert layer == 52_597_760
    assert sum(count.values()) == 4 * layer + 2 * 18992 * 2560 + 2560 \
        == 307_632_640
    assert family.decoder_pattern(s) == "GWWW"
    assert family.pattern_of(s).count("E") == 4


def test_flops_and_bytes_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    full, window = 16384 * 16385 // 2, 4096 * 4097 // 2 + 12288 * 4096
    assert family.visible_pairs(16384) == full == 134_225_920
    assert family.visible_pairs(16384, 4096) == window == 58_722_304
    assert family.visible_pairs(2048, 4096) == 2048 * 2049 // 2
    per = family.forward_flops_per_token(s)
    assert per["projections"] == 2 * 2560 * 128 * 16 == 10_485_760
    assert per["G"] == 4 * 896 * full / 16384
    assert per["W"] == 4 * 896 * window / 16384
    assert per["experts"] == 6 * 2560 * 768 * 6 * 8 / 64 == 8_847_360
    assert per["router"] == 2 * 2560 * 64
    assert per["head"] == 2 * 2560 * 18992 == 97_239_040
    token = (4 * (per["projections"] + per["experts"] + per["router"])
             + per["G"] + 3 * per["W"] + per["head"])
    assert token == pytest.approx(243.7e6, rel=1e-3)
    assert family.train_flops_per_sample(s) == 3 * 16384 * token
    assert family.train_flops_per_sample(s) == pytest.approx(11.98e12,
                                                             rel=1e-3)
    # the window layers' flash kernels, forward and backward
    flops, nbytes = family.window_flash_step_flops_and_bytes(s)
    assert flops == 3 * 12 * window * 896 == pytest.approx(1.894e12,
                                                           rel=1e-3)
    assert nbytes == 3 * 12 * 2 * 16384 * 896
    assert flops / 197e12 > nbytes / 819e9       # FLOP-bound: 9.6 ms
    # the grouped products: three matrices, forward and two backward each
    assert family.expected_held_slots(s) == 12288
    flops, nbytes = family.grouped_matmul_step_flops_and_bytes(s)
    assert flops == 4 * 18 * 12288 * 2560 * 768
    assert nbytes == 4 * 9 * (2 * 8 * 2560 * 768 + 2 * 12288 * (2560 + 768))
    half, _ = family.grouped_matmul_step_flops_and_bytes(s, slots=6144)
    assert half == flops / 2


class FakeFamily:
    """What the readers ask of a family, with made-up scopes."""
    observed = {"scopes": {
        "fusion.1": "jit(train_step)/jvp(jit(attention_window))/dot_general",
        "custom.2": "jit(train_step)/jvp(jit(attention_window))/"
                    "jit(flash_window)/pallas_call",
        "custom.3": "jit(train_step)/transpose(jvp(jit(attention_window)))/"
                    "jit(flash_window)/pallas_call",
        "custom.4": "jit(train_step)/jvp(jit(attention_full))/"
                    "jit(flash_full)/pallas_call",
        "fusion.5": "jit(train_step)/jvp(jit(moe_layer))/dot_general"}}

    @staticmethod
    def window_flash_step_flops_and_bytes(sizes):
        return 197e12 * 3e-3, 1.0       # 3 ms at the peak


def fake_device():
    """One train step of 10 ms: 6 ms of window attention, 4 of them in its
    kernels, 1 ms of full attention, 2 ms of experts, 1 ms idle."""
    return tr.Device("/device:TPU:0", {
        tr.MODULES: [(tr.STEP_MODULE + "(1)", 0.0, 10e-3)],
        tr.OPS: [("fusion.1", 0.0, 2e-3), ("custom.2", 2e-3, 1e-3),
                 ("custom.3", 3e-3, 3e-3), ("custom.4", 6e-3, 1e-3),
                 ("fusion.5", 7e-3, 2e-3)]})


def test_new_readers_on_a_made_up_trace():
    ctx = dict(devices=[fake_device()], family=FakeFamily, counters=dict(
        sizes={}, peaks=dict(bf16_flops_per_s=197e12,
                             hbm_bytes_per_s=819e9)))
    read = {name: hs.load_by_path("layer_metrics", name).read(ctx)
            for name in NEW_METRICS}
    assert read["layers.window_attention_share_pct"] == pytest.approx(
        100 * 6 / 9)
    assert read["layers.full_attention_share_pct"] == pytest.approx(
        100 * 1 / 9)
    assert read["kernels.window_flash_roofline"] == pytest.approx(75.0)


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
    no_scope = type("F", (), {"observed": {"scopes": {"fusion.1": "x"}},
                              "window_flash_step_flops_and_bytes":
                              staticmethod(lambda s: (1.0, 1.0))})
    assert reader.read(dict(devices=[fake_device()], family=no_scope,
                            counters=counters)) is None
