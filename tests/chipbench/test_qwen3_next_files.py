"""The data files, FLOP and byte functions and readers that the
`qwen3_next_80b_a3b` configuration adds (PR 58): the configuration against
the catalog's row, the cell's files found by name, the issue's hand
counts, the five new readers on a made-up trace and join table and on
the trace the v5e recorded of another program, and the refusal of an
older program. The cell end to end at a tiny size is
`test_rehearsal_qwen3_next.py`'s."""

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

CONFIG = "qwen3_next_80b_a3b"
CELL = "qwen3_next_80b_a3b.s16384_b1.1chip"
FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "devtrace_tpu_v5e.trace.json.gz")
# the catalog's row Qwen3-Next-80B-A3B-Instruct (model-configs guide,
# architectures.jsonl), as published
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992}
NEW_METRICS = ("layers.delta_mixer_share_pct", "kernels.delta_rule_roofline",
               "layers.gated_attention256_share_pct",
               "kernels.head256_flash_roofline",
               "layers.top10_experts_share_pct")


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
    assert listed["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert listed["source"] == config["source"]
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config[key] == REDUCED[key], key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert set(config["published"]) == set(REDUCED)
    # no width among the reduced keys
    for key in REDUCED:
        assert not key.endswith(("_dim", "_rank", "_size")) or \
            key == "vocab_size"
    assert config["parameters_count"] == 424_340_544
    assert "424,340,544" in config["parameters"]
    assert config["adam"]["alpha"] == 1e-6
    assert config["router_dtype"] == "float32"
    said = " ".join(config["assumed"] + config["departures"])
    for item in ("ZERO-CENTRED", "NOT zero-centred", "A_log = log(U(0, 16))",
                 "Mamba's rule", "rotate_half", "lax.top_k",
                 "multi-token-prediction", "no auxiliary", "partial sums",
                 "ROUTERS DRIFT",
                 "column layout", "interleaves", "one position a step"):
        assert item in said, item
    for item in ("32 chips", "32-way", "8-way", "whole on every chip",
                 "17.5 GB"):
        assert item in config["deployment"], item
    for item in ("0.00264", "delta correction", "rotary over all 256",
                 "NOT visible"):
        assert item in config["census"], item


def test_the_cells_files_are_found_by_name(cell):
    manifest, entry, config, traffic, family = cell
    assert entry == dict(name=CELL, config=CONFIG, traffic="s16384_b1",
                         chips=1, why=traffic["why"])
    assert len(entry["why"]) <= 200
    for said in ("320 pairs", "10,240 deployed", "TFLOP", "delta mixers"):
        assert said in entry["why"], said
    assert (traffic["seq"], traffic["batch"], traffic["steps_per_epoch"],
            traffic["reference_chunk"], traffic["part_a_share"]) == (
        16384, 1, 4, 1, 0.5)
    assert config["family"] == "qwen3_next"
    s = family.sizes(config, traffic)
    assert s["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
    assert family.reference(s, traffic)[0].__name__ == \
        "benchmarks.references.qwen3_next"
    # a `reference_*` size alters the reference, a `program_*` one does not
    altered = family.sizes(config, traffic, dict(reference_decay=False,
                                                 program_rope_theta=1e4))
    assert family.reference_kw(altered)["decay"] is False
    assert family.reference_kw(altered)["rope_theta"] == 1e7
    assert "decay" not in family.reference_kw(s)
    names = [m["name"] for m in manifest["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput"
        assert by_name[name]["source"] == "device_trace"
        assert hasattr(hs.load_by_path("layer_metrics", name), "read")
        # new entries come after what the benchmark had (PR 54's)
        assert names.index(name) > names.index(
            "kernels.selected_keys_visited_ratio")
        assert by_name[name]["better"] == (
            "higher" if "roofline" in name else "lower")
    assert {by_name[n]["layer"] for n in NEW_METRICS} == {"model ops",
                                                          "kernels"}
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index("keye_vl2_30b_a3b.s16384_b1.1chip")
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index(CONFIG) > configs.index("keye_vl2_30b_a3b")
    reported = {m["name"] for m in mf.metrics_of(manifest, "per_layer",
                                                 CELL)}
    assert set(NEW_METRICS) <= reported
    assert {"device.mfu_pct", "device.idle_pct",
            "compile.model_compile_s"} <= reported
    # the accepted readers keep to their own cells
    assert not reported & {"layers.short_conv_share_pct",
                           "kernels.causal_flash_roofline",
                           "layers.moe_share_pct", "kernels.ssd_roofline"}


def test_parameters_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    shapes = family.weight_shapes(s)

    def count(name):
        import math
        return sum(math.prod(shape) for _, shape in shapes[name].values())

    assert count("b0_delta") == (25_165_824 + 131_072 + 32_768 + 32 + 32
                                 + 128 + 8_388_608) == 33_718_464
    assert count("b3_attn") == (16_777_216 + 2 * 1_048_576 + 8_388_608
                                + 512) == 27_263_488
    assert count("b0_mixer") == (1_048_576 + 3_145_728 + 2_048
                                 + 16 * 3_145_728) == 54_528_000
    assert count("b0_norm") + count("b0_post_norm") == 4_096
    linear = sum(count(f"b0_{n}") for n in ("norm", "delta", "post_norm",
                                            "mixer"))
    full = sum(count(f"b3_{n}") for n in ("norm", "attn", "post_norm",
                                          "mixer"))
    assert (linear, full) == (88_250_560, 81_795_584)
    assert count("embed_tokens") + count("lm_head") == 77_791_232
    assert family.parameters(s) == 3 * linear + full + 77_791_232 + 2_048 \
        == config["parameters_count"]


def test_flops_and_bytes_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    per = family.forward_flops_per_token(s)
    # a delta mixer's three products: 2048 x (12,288 + 64) in, 4,096 out
    assert per["delta_products"] == 3 * 2 * 2048 * (12_288 + 64 + 4_096)
    # a value head and chunk of 128: K K^T, Q K^T, 12 products of the
    # inverse, T K, T V, W S, Q S, P V', K^T V': 20 products of 128^3
    assert family.delta_rule_flops_a_chunk(s) == 20 * 2 * 128 ** 3
    assert per["delta_rule"] == 3 * 32 * 20 * 2 * 128 ** 2
    assert per["projections"] == 2 * 2048 * 256 * (3 * 16 + 2 * 2)
    assert per["scores"] == 4 * 16 * 256 * 16385 / 2
    assert per["experts"] == pytest.approx(4 * (
        6 * 2048 * 512 * 10 / 32 + 2 * 2048 * 512 + 6 * 2048 * 512
        + 2 * 2048))
    assert per["head"] == 2 * 2048 * 18992
    total = family.train_flops_per_sample(s)
    assert 27.5e12 < total < 28.5e12
    shares = {k: 3 * 16384 * v / total for k, v in per.items()}
    assert shares["delta_products"] == pytest.approx(0.353, abs=0.005)
    assert shares["delta_rule"] == pytest.approx(0.110, abs=0.005)
    assert shares["scores"] == pytest.approx(0.234, abs=0.005)
    assert shares["projections"] == pytest.approx(0.095, abs=0.005)
    assert shares["experts"] == pytest.approx(0.072, abs=0.005)
    assert shares["head"] == pytest.approx(0.136, abs=0.005)
    flops, nbytes = family.delta_rule_step_flops_and_bytes(s)
    assert flops == 3 * 3 * 16384 * 32 * 20 * 2 * 128 ** 2
    assert nbytes == 3 * 16384 * (2 * (3 * 12_288 + 4_096) + 16 * 32)
    flops, nbytes = family.flash_step_flops_and_bytes(s)
    assert flops == 14 * 16 * 256 * 16384 * 16385 / 2
    assert nbytes == 2 * 16384 * 256 * (5 * 16 + 6 * 2)
    assert family.expected_held_slots(s) == 5120
    assert family.expected_chunks(s) == 3 * 32 * 128


# ---------------------------------------------------------------------------
# the readers on a made-up trace

STEP = "jit(train_step)/"
TABLE = {
    "fusion.1": dict(op_name=STEP + "jvp(jit(delta_mixer))/dot_general",
                     part="delta_mixer", direction="forward"),
    "fusion.2": dict(op_name=STEP + "jvp(jit(delta_mixer))/jit(delta_rule)/"
                     "dot_general", part="delta_mixer", direction="forward"),
    "delta_rule_fwd.3": dict(
        op_name=STEP + "jvp(jit(delta_mixer))/jit(delta_rule)/pallas_call",
        part="delta_mixer", direction="forward"),
    "delta_rule_bwd.4": dict(
        op_name=STEP + "transpose(jvp(jit(delta_mixer)))/jit(delta_rule)/"
        "pallas_call", part="delta_mixer", direction="backward"),
    "fusion.5": dict(op_name=STEP + "jvp(jit(attention_full))/dot_general",
                     part="attention", direction="forward"),
    "flash.6": dict(op_name=STEP + "transpose(jvp(jit(attention_full)))/"
                    "jit(flash_full)/pallas_call", part="attention",
                    direction="backward"),
    "fusion.7": dict(op_name=STEP + "jvp(jit(moe_layer))/jit(moe_shared)/"
                     "dot_general", part="experts", direction="forward"),
    "fusion.8": dict(op_name=STEP + "jvp(jit(head))/dot_general",
                     part="head", direction="forward"),
}


def fake_device():
    """One train step of 10 ms: under the delta mixer 0.5 ms of a
    projection, 0.5 ms of the rule's batched part and 1 + 1.5 ms of its
    two kernels; under the attention op 0.5 ms of a projection and 2 ms
    of kernel; 1 ms of the shared expert; 1 ms of the head; 2 idle."""
    return tr.Device("/device:TPU:0", {
        tr.MODULES: [(tr.STEP_MODULE + "(1)", 0.0, 10e-3)],
        tr.OPS: [("fusion.1", 0.0, 0.5e-3), ("fusion.2", 0.5e-3, 0.5e-3),
                 ("delta_rule_fwd.3", 1e-3, 1e-3),
                 ("delta_rule_bwd.4", 2e-3, 1.5e-3),
                 ("fusion.5", 3.5e-3, 0.5e-3), ("flash.6", 4e-3, 2e-3),
                 ("fusion.7", 6e-3, 1e-3), ("fusion.8", 7e-3, 1e-3)]})


class FakeFamily:
    observed = {}

    @staticmethod
    def delta_rule_step_flops_and_bytes(sizes):
        return 197e12 * 0.3e-3, 1.0      # 0.3 ms at the bf16 peak

    @staticmethod
    def flash_step_flops_and_bytes(sizes):
        return 1.0, 819e9 * 0.5e-3       # bytes alone: 0.5 ms at the peak


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
    # of the 8 busy ms 3.5 lie under `delta_mixer`, 3 of them in
    # `delta_rule` for 0.3 at the peak; 2.5 under `attention_full`, 2 of
    # them in `flash_full` for 0.5 of bytes at the peak; 1 under
    # `moe_layer`
    assert read["layers.delta_mixer_share_pct"] == pytest.approx(
        100 * 3.5 / 8)
    assert read["kernels.delta_rule_roofline"] == pytest.approx(10.0)
    assert read["layers.gated_attention256_share_pct"] == pytest.approx(
        100 * 2.5 / 8)
    assert read["kernels.head256_flash_roofline"] == pytest.approx(25.0)
    assert read["layers.top10_experts_share_pct"] == pytest.approx(
        100 * 1 / 8)


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
    assert reader.read(context(Bare)) is None          # no table
    write_table({"fusion.1": dict(
        op_name=STEP + "jvp(jit(attention_window))/jit(flash_window)/"
        "pallas_call", part="attention", direction="forward")})
    assert reader.read(context(Bare)) is None          # no such scope
    write_table(TABLE)
    if name.startswith("kernels."):
        assert reader.read(context(Bare)) is None      # no count
    ctx = context(Bare)
    ctx["devices"] = []
    assert reader.read(ctx) is None
    # the recorded trace: its instructions are another program's
    recorded = tr.load_chrome(FIXTURE)
    assert recorded and tr.step_spans(recorded[0])
    assert reader.read(context(devices=recorded)) is None


def test_the_new_scopes_are_parts_of_the_step():
    """`obs.step_scopes` reads the new names back: the delta mixer is a
    part of its own, its rule lies in it."""
    from flexflow_tpu.obs.step_scopes import classify
    for op_name, want in (
            (STEP + "jvp(jit(delta_mixer))/dot_general",
             ("delta_mixer", "forward")),
            (STEP + "jvp(jit(delta_mixer))/jit(delta_rule)/pallas_call",
             ("delta_mixer", "forward")),
            (STEP + "transpose(jvp(jit(delta_mixer)))/jit(delta_rule)/"
             "pallas_call", ("delta_mixer", "backward")),
            (STEP + "jvp(jit(attention_full))/jit(attention_gate)/mul",
             ("attention", "forward"))):
        assert classify(op_name) == want, op_name
    # no new name holds one of the scopes the accepted readers match as
    # bare substrings
    for new in ("delta_mixer", "delta_rule"):
        for old in ("ssm_mixer", "ssd_scan", "moe_layer", "attention_full",
                    "attention_window", "flash_full", "flash_window",
                    "attention_latent", "flash_latent", "gated_conv",
                    "attention_block_diffusion", "flash_block_diffusion",
                    "mamba_mixer", "selective_scan", "flash_diff",
                    "attention_sparse", "flash_sparse", "sparse_indexer"):
            assert old not in new, (old, new)


def test_an_older_program_ends_at_once(cell, monkeypatch):
    """Under these files a program whose decoder has no such family (the
    parent commit's) is refused by `sizes`, before any weight is made: a
    clean exit, soon."""
    import dataclasses

    from flexflow_tpu import models
    _, _, config, traffic, family = cell

    @dataclasses.dataclass
    class Older:
        hidden_size: int = 64
    monkeypatch.setattr(models, "DecoderConfig", Older)
    with pytest.raises(SystemExit, match="gated delta-rule mixer"):
        family.sizes(config, traffic)
