"""The data files, FLOP and byte functions and readers that the
`keye_vl2_30b_a3b` configuration adds (PR 54): the configuration against
the catalog's row, the cell's files found by name, the issue's hand
counts, the five new readers on a made-up trace and join table and on
the trace the v5e recorded of another program, and the refusal of an
older program. The cell end to end at a tiny size is
`test_rehearsal_keye.py`'s."""

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

CONFIG = "keye_vl2_30b_a3b"
CELL = "keye_vl2_30b_a3b.s16384_b1.1chip"
FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "devtrace_tpu_v5e.trace.json.gz")
# the catalog's row Keye-VL-2.0-30B-A3B (model-configs guide,
# architectures.jsonl), as published
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992,
           "num_attention_heads": 8, "num_key_value_heads": 1}
NEW_METRICS = ("layers.sparse_attention_share_pct",
               "layers.sparse_indexer_share_pct",
               "kernels.sparse_flash_roofline",
               "kernels.index_select_roofline",
               "kernels.selected_keys_visited_ratio")


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
    assert config["indexer_dtype"] == "float32"
    assert config["parameters"] == 408_768_000
    assert config["mask_bytes_a_layer"] == 16384 ** 2
    said = " ".join(config["assumed"] + config["departures"])
    for item in ("CONTIGUOUS", "lightning indexer", "WHOLE 64 lanes",
                 "lax.top_k", "q_chunk_size", "SPARSE TRAINING STAGE",
                 "coefficient 1", "Hadamard", "vision tower"):
        assert item in said, item
    assert "all-reduce" in config["deployment"]


def test_the_cells_files_are_found_by_name(cell):
    manifest, entry, config, traffic, family = cell
    assert entry == dict(name=CELL, config=CONFIG, traffic="s16384_b1",
                         chips=1, why=traffic["why"])
    assert len(entry["why"]) <= 200
    for said in ("head 32%", "indexer 23", "12 TFLOP", "all of the indexer"):
        assert said in entry["why"], said
    assert (traffic["seq"], traffic["batch"], traffic["steps_per_epoch"],
            traffic["reference_chunk"], traffic["part_a_share"]) == (
        16384, 1, 4, 1, 0.5)
    assert config["family"] == "keye"
    s = family.sizes(config, traffic)
    assert family.indexer(s) == (16, 64, 2048)
    assert family.reference(s, traffic)[0].__name__ == \
        "benchmarks.references.keye"
    names = [m["name"] for m in manifest["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput"
        assert hasattr(hs.load_by_path("layer_metrics", name), "read")
        # new entries come after what the benchmark had (PR 52's)
        assert names.index(name) > names.index("kernels.diff_flash_roofline")
        assert by_name[name]["better"] == (
            "higher" if "roofline" in name else "lower")
    assert by_name["kernels.selected_keys_visited_ratio"]["source"] == \
        "program_counter"
    assert {by_name[n]["layer"] for n in NEW_METRICS} == {"model ops",
                                                          "kernels"}
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index("phi4_mini_flash.s8192_b1.1chip")
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index(CONFIG) > configs.index("phi4_mini_flash")
    reported = {m["name"] for m in mf.metrics_of(manifest, "per_layer",
                                                 CELL)}
    assert set(NEW_METRICS) <= reported
    assert {"device.mfu_pct", "device.idle_pct",
            "compile.model_compile_s"} <= reported
    # the accepted readers keep to their own cells
    assert not reported & {"layers.block_diffusion_attention_share_pct",
                           "kernels.block_diffusion_flash_roofline",
                           "layers.moe_share_pct"}


def test_parameters_and_pairs_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    shapes = family.weight_shapes(s)

    def count(name):
        import math
        return sum(math.prod(shape) for _, shape in shapes[name].values())

    assert count("b0_attn") == 4_718_592 + 256 + 2_260_992 + 128
    assert count("b0_mixer") == 262_144 + 16 * 4_718_592
    assert count("b0_norm") + count("b0_post_norm") == 4_096
    layer = sum(count(f"b0_{n}") for n in ("norm", "attn", "post_norm",
                                           "mixer"))
    assert layer == 82_743_680
    assert count("embed_tokens") + count("lm_head") == 77_791_232
    assert family.parameters(s) == 4 * layer + 77_791_232 + 2_048 \
        == config["parameters"]
    assert family.selected_pairs(s) == 31_458_304
    assert family.causal_pairs(s) == 134_225_920


def test_flops_and_bytes_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    per = family.forward_flops_per_position(s)
    assert per["projections"] == 2 * 2048 * 128 * 18
    assert per["scores"] == pytest.approx(4 * 1024 * 31_458_304 / 16384)
    assert per["indexer"] == pytest.approx(
        2 * 2048 * 1104 + 2 * 1024 * 134_225_920 / 16384)
    total = family.train_flops_per_sample(s)
    assert 11.9e12 < total < 12.0e12
    head = 3 * 16384 * 2 * 2048 * 18992
    assert head / total == pytest.approx(0.32, abs=0.005)
    flops, nbytes = family.sparse_flash_step_flops_and_bytes(s)
    assert flops == 12 * 31_458_304 * 1024 * 4      # the issue's count
    assert nbytes == 12 * 2 * 16384 * 1024 * 4
    flops, nbytes = family.index_select_step_flops_and_bytes(s)
    assert flops == 4 * (2 * 1024 * 134_225_920
                         + (6 * 1024 + 2 * 1024) * 31_458_304)
    assert nbytes > 4 * 2 * 16384 ** 2     # the mask written and read


# ---------------------------------------------------------------------------
# the readers on a made-up trace

STEP = "jit(train_step)/"
TABLE = {
    "fusion.1": dict(op_name=STEP + "jvp(jit(sparse_indexer))/dot_general",
                     part="sparse_indexer", direction="forward"),
    "index_select.2": dict(
        op_name=STEP + "jvp(jit(sparse_indexer))/pallas_call",
        part="sparse_indexer", direction="forward"),
    "index_kl.3": dict(op_name=STEP + "jvp(jit(sparse_indexer))/pallas_call",
                       part="sparse_indexer", direction="forward"),
    "fusion.4": dict(op_name=STEP + "jvp(jit(attention_sparse))/dot_general",
                     part="attention", direction="forward"),
    "flash.5": dict(op_name=STEP + "transpose(jvp(jit(attention_sparse)))/"
                    "jit(flash_sparse)/pallas_call", part="attention",
                    direction="backward"),
    "fusion.6": dict(op_name=STEP + "transpose(jvp(jit(sparse_indexer)))/"
                     "dot_general", part="sparse_indexer",
                     direction="backward"),
    "fusion.7": dict(op_name=STEP + "jvp(jit(head))/dot_general",
                     part="head", direction="forward"),
}


def fake_device():
    """One train step of 10 ms: under the indexer 0.5 ms of a product,
    1 + 1.5 ms of its two kernels and 0.5 ms of a product's backward;
    under the attention op 0.5 ms of a projection and 2 ms of kernel;
    2 ms of the head; 2 idle."""
    return tr.Device("/device:TPU:0", {
        tr.MODULES: [(tr.STEP_MODULE + "(1)", 0.0, 10e-3)],
        tr.OPS: [("fusion.1", 0.0, 0.5e-3), ("index_select.2", 0.5e-3, 1e-3),
                 ("index_kl.3", 1.5e-3, 1.5e-3), ("fusion.4", 3e-3, 0.5e-3),
                 ("flash.5", 3.5e-3, 2e-3), ("fusion.6", 5.5e-3, 0.5e-3),
                 ("fusion.7", 6e-3, 2e-3)]})


class FakeFamily:
    observed = {"op_counters": {"attention/visited_pairs": 86.0,
                                "attention/selected_pairs": 10.0}}

    @staticmethod
    def sparse_flash_step_flops_and_bytes(sizes):
        return 197e12 * 0.3e-3, 1.0      # 0.3 ms at the bf16 peak

    @staticmethod
    def index_select_step_flops_and_bytes(sizes):
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
    # of the 8 busy ms 3.5 lie under `sparse_indexer`, 2.5 of them in its
    # kernels for 0.5 of bytes at the peak; 2.5 under `attention_sparse`,
    # 2 of them in `flash_sparse` for 0.3 at the peak
    assert read["layers.sparse_indexer_share_pct"] == pytest.approx(
        100 * 3.5 / 8)
    assert read["layers.sparse_attention_share_pct"] == pytest.approx(
        100 * 2.5 / 8)
    assert read["kernels.sparse_flash_roofline"] == pytest.approx(15.0)
    assert read["kernels.index_select_roofline"] == pytest.approx(20.0)
    assert read["kernels.selected_keys_visited_ratio"] == pytest.approx(4.3)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(
        name, tmp_path, monkeypatch):
    """A run without a table, a program without the scopes or counters
    (the parent commit's), a family without the count, the trace the v5e
    recorded of another program: None, no raise."""
    class Bare:
        pass
    send_output_to(monkeypatch, tmp_path)
    reader = hs.load_by_path("layer_metrics", name)
    assert reader.read(context(Bare)) is None          # no table, no counter
    write_table({"fusion.1": dict(
        op_name=STEP + "jvp(jit(attention_full))/jit(flash_full)/"
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
    if name != "kernels.selected_keys_visited_ratio":
        assert reader.read(context(devices=recorded)) is None


def test_the_new_scopes_are_parts_of_the_step():
    """`obs.step_scopes` reads the new names back: the indexer is a part
    of its own, the op's main attention lies in `attention`."""
    from flexflow_tpu.obs.step_scopes import classify
    for op_name, want in (
            (STEP + "jvp(jit(sparse_indexer))/pallas_call",
             ("sparse_indexer", "forward")),
            (STEP + "transpose(jvp(jit(sparse_indexer)))/dot_general",
             ("sparse_indexer", "backward")),
            (STEP + "jvp(jit(attention_sparse))/jit(flash_sparse)/"
             "pallas_call", ("attention", "forward")),
            (STEP + "transpose(jvp(jit(attention_sparse)))/jit(flash_sparse)"
             "/pallas_call", ("attention", "backward"))):
        assert classify(op_name) == want, op_name
    # no new name holds one of the scopes the accepted readers match as
    # bare substrings
    for new in ("attention_sparse", "flash_sparse", "sparse_indexer"):
        for old in ("ssm_mixer", "ssd_scan", "moe_layer", "attention_full",
                    "attention_window", "flash_full", "flash_window",
                    "attention_latent", "flash_latent", "gated_conv",
                    "attention_block_diffusion", "flash_block_diffusion",
                    "mamba_mixer", "selective_scan", "flash_diff"):
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
    with pytest.raises(SystemExit, match="learned sparse attention"):
        family.sizes(config, traffic)
