"""The data files, counts and readers that the `xing4_0_29b_a4b`
configuration adds: the configuration against the catalog's row, the
cell's files found by name, hand counts of the parameters and of the
bytes the hyper-connections' passes must move, and the two new readers
and joyai's three on a made-up trace and join table."""

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

CONFIG = "xing4_0_29b_a4b"
CELL = "xing4_0_29b_a4b.s4096_b1.1chip"
# the numbers of the catalog's row Xing4.0-29B-A4B (model-configs guide,
# architectures.jsonl), as published
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 8,
           "vocab_size": 16384, "num_attention_heads": 4,
           "num_key_value_heads": 4}
# never cut: hidden, latent, head and expert widths, experts a token, the
# streams and their steps
WIDTHS = ("hidden_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "intermediate_size",
          "moe_intermediate_size", "num_experts_per_tok", "hc_mult",
          "hc_sinkhorn_iters")
NEW_METRICS = ("layers.hyper_connection_share_pct",
               "kernels.hyper_connection_roofline")
# accepted readers of layers this cell runs too (joyai's), the cell
# appended to their lists
SHARED_METRICS = ("layers.latent_attention_share_pct",
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
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
        "config.json")
    assert listed["file"] == "benchmarks/configs/xing4_0_29b_a4b.json"
    assert "one chip of 8" in listed["why"] and len(listed["why"]) <= 200
    assert not set(REDUCED) & set(WIDTHS)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Xing4.0-29B-A4B"] or [None]
    if row is not None:     # the catalog beside the guide, where it is
        assert row["config"] == PUBLISHED
        assert row["source_url"] == config["source"]
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config[key] == REDUCED[key], key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert set(config["published"]) == set(REDUCED)
    # the cut keeps the deployment's ratios: an eighth of the heads, of
    # the experts and of the vocabulary, at the guide's floors
    assert (32 // 8, 64 // 8, 131072 // 8) == (4, 8, 16384)
    assert config["n_routed_experts_published"] == 64
    assert config["dense_layers_held"] == 1 and config["published_depth"] == 40
    for key in ("source", "deployment", "departures", "assumed", "adam",
                "census", "hc_init"):
        assert config[key]
    # ONE set of numbers: an op's own draw (`HyperConnectionPre
    # .init_params`) is the configuration's
    from flexflow_tpu.ops.hyper_connection import INIT
    assert INIT == tuple(config["hc_init"][k] for k in (
        "alpha", "phi_std", "res_diagonal", "bias_std", "res_bias_std"))
    assert "8 chips share each layer" in config["deployment"]
    assert "789,610,628" in config["deployment"]
    assumed = " ".join(config["assumed"])
    for said in ("born by replication", "SUMMED x_L", "lambda 0.3",
                 "ADJACENT pairs", "no learned scale", "then the rows",
                 "alpha 0.01", "N(0, 0.3)", "0.02 / sqrt(40)",
                 "slot_slack", "ungated"):
        assert said in assumed, said
    assert any("no auxiliary" in d for d in config["departures"])
    assert any("three bfloat16 terms" in d for d in config["departures"])


def test_the_cells_files_are_found_by_name(cell):
    manifest, entry, config, traffic, family = cell
    assert entry == dict(name=CELL, config=CONFIG, traffic="s4096_b1",
                         chips=1, why=traffic["why"])
    assert len(entry["why"]) <= 200
    assert (traffic["seq"], traffic["batch"], traffic["steps_per_epoch"],
            traffic["reference_chunk"], traffic["part_a_share"]) == (
        4096, 1, 4, 1, 0.5)
    assert config["family"] == "xing4"
    assert family.reference(family.sizes(config, traffic), traffic)[0] \
        .__name__ == "benchmarks.references.xing4"
    names = [m["name"] for m in manifest["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput"
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["unit"] == "%"
        assert hasattr(hs.load_by_path("layer_metrics", name), "read")
        # new entries come after everything the benchmark had (PR 58's)
        assert names.index(name) > names.index(
            "layers.top10_experts_share_pct")
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == CONFIG
    reported = {m["name"] for m in mf.metrics_of(manifest, "per_layer",
                                                 CELL)}
    assert set(NEW_METRICS) | set(SHARED_METRICS) <= reported
    for name in SHARED_METRICS:
        assert by_name[name]["workloads"] == [
            "joyai_llm_flash.s4096_b1.1chip", CELL]
    # the two readers that take their scopes from a second lowering of
    # the step (`scope_reduce`) keep to their own cells, as for joyai
    assert not reported & {"layers.moe_share_pct",
                           "kernels.grouped_matmul_roofline"}
    # one four-chip cell of the quarter the benchmark may have
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(manifest["workloads"]) == 14 and len(manifest["configs"]) == 13


def test_parameters_and_bytes_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    attention = (3584 * 768 + 768 + 768 * 4 * 192 + 3584 * 576 + 512
                 + 512 * 4 * 256 + 512 * 3584)
    experts = 3584 * 64 + 64 + 9 * 3 * 3584 * 1024
    hyper = 2 * (2 * 14336 * 4 + 14336 * 16 + 4 + 4 + 16 + 3)
    expert_block = attention + experts + hyper + 2 * 3584
    dense_block = attention + 3 * 3584 * 9216 + hyper + 2 * 3584
    module = 2 * 3584 + 7168 * 3584 + expert_block + 3584
    assert (attention, experts, hyper) == (7_767_296, 99_319_872, 688_182)
    assert (expert_block, dense_block, module) == (
        107_782_518, 107_553_078, 133_483_382)
    assert family.parameters(s) == (dense_block + 4 * expert_block
                                    + 2 * 16384 * 3584 + module + 3584) \
        == 789_610_628
    # 14 bytes a parameter resident, 16 in the reference's Adam step
    assert family.parameters(s) * 14 / 1e9 == pytest.approx(11.05, abs=0.01)
    assert family.parameters(s) * 16 / 1e9 == pytest.approx(12.63, abs=0.01)
    assert family.expected_held_slots(s) == 2048
    per = family.forward_flops_per_token(s)
    assert per["hyper_connection"] == 2 * 3584 * (4 * 24 + 4 + 20)
    # the passes' bytes: (9 n + 5) C elements a position and sublayer
    reader = hs.load_by_path("layer_metrics",
                             "kernels.hyper_connection_roofline")
    assert reader.step_bytes(s) == 12 * 4096 * 293_888
    assert reader.step_bytes(s) / 819e9 == pytest.approx(17.64e-3, rel=1e-3)


STEP = "jit(train_step)/"
HC = "jit(hyper_connection)/"
TABLE = {
    "hc_read.1": dict(op_name=STEP + "jvp(" + HC[:-1] + ")/jit(hc_read)/"
                      "pallas_call", part="hyper_connection",
                      direction="forward"),
    "hc_maps.2": dict(op_name=STEP + "jvp(" + HC[:-1] + ")/jit(hc_maps)/"
                      "pallas_call", part="hyper_connection",
                      direction="forward"),
    "hc_write_bwd.3": dict(op_name=STEP + "transpose(jvp(jit(mtp)))/" + HC
                           + "jit(hc_write)/pallas_call", part="mtp",
                           direction="backward"),
    "fusion.4": dict(op_name=STEP + "jvp(jit(attention_latent))/dot_general",
                     part="attention", direction="forward"),
    "fusion.5": dict(op_name=STEP + "jvp(jit(mtp))/jit(moe_layer)/"
                     "dot_general", part="mtp", direction="forward"),
    "fusion.6": dict(op_name=STEP + "jvp(jit(head))/dot_general",
                     part="head", direction="forward"),
}


def fake_device():
    """One train step of 10 ms: 2 ms in the read kernel, 1 in the maps',
    2 in the module's write kernel's backward, 2 of attention, 1 of the
    module's experts, 1 of the head, 1 idle."""
    return tr.Device("/device:TPU:0", {
        tr.MODULES: [(tr.STEP_MODULE + "(1)", 0.0, 10e-3)],
        tr.OPS: [("hc_read.1", 0.0, 2e-3), ("hc_maps.2", 2e-3, 1e-3),
                 ("hc_write_bwd.3", 3e-3, 2e-3), ("fusion.4", 5e-3, 2e-3),
                 ("fusion.5", 7e-3, 1e-3), ("fusion.6", 8e-3, 1e-3)]})


def context(sizes=None):
    manifest = mf.load_manifest()
    entry, config, traffic = mf.find_cell(manifest, CELL)
    if sizes is None:
        # one sublayer's worth: 4 ms at the HBM peak
        sizes = dict(hc_mult=4, hidden_size=3584, num_hidden_layers=0,
                     num_nextn_predict_layers=1, batch=1,
                     seq=int(819e9 * 4e-3 / (2 * 2 * 41 * 3584)))
    return dict(devices=[fake_device()], cell=entry, config=config,
                traffic=traffic, family=object(), counters=dict(
                    sizes=sizes, peaks=dict(bf16_flops_per_s=197e12,
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
    # 5 of the 9 busy ms lie under `hyper_connection` (the module's
    # among them), 4 of them in the read and write passes, which the
    # roofline reads alone: 4 ms for what takes 4 at the peak
    assert read["layers.hyper_connection_share_pct"] == pytest.approx(
        100 * 5 / 9)
    assert read["kernels.hyper_connection_roofline"] == pytest.approx(
        100.0, rel=2e-3)


def test_accepted_readers_on_the_same_made_up_trace(cell, tmp_path,
                                                    monkeypatch):
    """The latent attention's share, the module's and the flash kernels'
    roofline by the readers joyai's cell brought, with this family's
    count of the kernels' work at 4 heads."""
    family = cell[-1]
    send_output_to(monkeypatch, tmp_path)
    table = dict(TABLE, **{"fusion.4": dict(
        op_name=STEP + "jvp(jit(attention_latent))/jit(flash_latent)/"
        "pallas_call", part="attention", direction="forward")})
    write_table(table)
    s = family.sizes(cell[2], cell[3])
    ctx = dict(context(), family=family)
    ctx["counters"]["sizes"] = s
    read = {name: hs.load_by_path("layer_metrics", name).read(ctx)
            for name in SHARED_METRICS}
    assert read["layers.latent_attention_share_pct"] == pytest.approx(
        100 * 2 / 9)
    assert read["layers.mtp_share_pct"] == pytest.approx(100 * 3 / 9)
    # six ops over the causal pairs of 4,096 positions at 4 heads: 3.5
    # times the forward's 2 (192 + 128) FLOPs a pair, FLOP-bound
    pairs = 4096 * 4097 // 2 * 4
    flops, nbytes = family.latent_flash_step_flops_and_bytes(s)
    assert flops == 6 * pairs * (2 * 320 + 2 * (2 * 128 + 2 * 192))
    assert flops / 197e12 > nbytes / 819e9
    assert read["kernels.latent_flash_roofline"] == pytest.approx(
        100 * flops / 197e12 / 2e-3)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_nothing_where_there_is_nothing_to_read(
        name, tmp_path, monkeypatch):
    """A run without a table, a program without the scopes (the parent
    commit's), sizes without streams: None, no raise."""
    send_output_to(monkeypatch, tmp_path)
    reader = hs.load_by_path("layer_metrics", name)
    assert reader.read(context()) is None                  # no table
    write_table({"fusion.1": dict(
        op_name=STEP + "jvp(jit(attention_full))/dot_general",
        part="attention", direction="forward")})
    assert reader.read(context()) is None                  # no such scope
    assert reader.read(context(sizes={})) is None
    ctx = context()
    ctx["devices"] = []
    assert reader.read(ctx) is None
