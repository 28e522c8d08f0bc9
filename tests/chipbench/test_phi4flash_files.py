"""The data files, FLOP and byte functions and readers that the
`phi4_mini_flash` configuration adds (PR 52): the configuration against
the catalog's row, the cell's files found by name, the issue's hand
counts, the five new readers on a made-up trace and join table and on
the trace the v5e recorded, and the cell end to end on the CPU at a tiny
size (`--trace 2`; the sizes are here because `rehearse.py`'s table is
PR 24's file)."""

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

CONFIG = "phi4_mini_flash"
CELL = "phi4_mini_flash.s8192_b1.1chip"
FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "devtrace_tpu_v5e.trace.json.gz")
# the catalog's row Phi-4-mini-flash-reasoning (model-configs guide,
# architectures.jsonl), as published
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
REDUCED = {"num_hidden_layers": 4, "vocab_size": 25008}
NEW_METRICS = ("layers.mamba1_mixer_share_pct",
               "layers.gated_memory_share_pct",
               "layers.diff_attention_share_pct",
               "kernels.selective_scan_roofline",
               "kernels.diff_flash_roofline")


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
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
        "blob/main/config.json")
    assert listed["file"] == "benchmarks/configs/phi4_mini_flash.json"
    assert "one pipeline stage of 8" in listed["why"]
    assert len(listed["why"]) <= 200
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):   # where the guide is installed
        with open(catalog_file) as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows
                  if r["name"] == "Phi-4-mini-flash-reasoning"]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == config["source"]
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config[key] == REDUCED[key], key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert set(config["published"]) == set(REDUCED)
    # the floors: a whole period (2 layers) and four layers, an eighth of
    # the rows; no width among the reduced keys
    assert 200064 // 8 == 25008 and config["num_hidden_layers"] >= 4
    assert (config["first_layer_index"],
            config["published_num_hidden_layers"]) == (16, 32)
    assert (config["head_dim"], config["mamba_d_state"],
            config["mamba_d_conv"], config["mamba_expand"],
            config["mamba_dt_rank"]) == (64, 16, 4, 2, 160)
    for key in ("source", "deployment", "departures", "assumed", "adam",
                "parameters", "loss_positions"):
        assert config[key], key
    assert "eight pipeline stages" in config["deployment"]
    assert "LEFT OUT on the chip: the window-512 kind" in \
        config["deployment"]
    assumed = " ".join(config["assumed"])
    for said in ("from memory without network access", "d_state 16",
                 "dt_rank 160", "head size 64", "no rotary",
                 "0.8 - 0.6 exp(-0.3 i)", "0.79634", "0.79799",
                 "LayerNorm with scale and bias", "log(n + 1)",
                 "+-160^-1/2", "+-1/2", "normal 0.1", "sequence 8,192"):
        assert said in assumed, said
    assert any("hands stage 4 the output of stage 3" in d
               for d in config["departures"])
    assert "478,876,928" in config["parameters"]


def test_the_cells_files_are_found_by_name(cell):
    manifest, entry, config, traffic, family = cell
    assert entry == dict(name=CELL, config=CONFIG, traffic="s8192_b1",
                         chips=1, why=traffic["why"])
    assert len(entry["why"]) <= 200
    for said in ("MLPs 58%", "head 11.8", "scores 11.6", "26.6 TFLOP"):
        assert said in entry["why"], said
    assert (traffic["seq"], traffic["batch"], traffic["steps_per_epoch"],
            traffic["reference_chunk"], traffic["part_a_share"]) == (
        8192, 1, 4, 1, 0.5)
    assert config["family"] == "phi4flash"
    s = family.sizes(config, traffic)
    assert s["kinds"] == ["mamba", "full", "gated_memory", "cross"]
    assert family.reference(s, traffic)[0].__name__ == \
        "benchmarks.references.phi4flash"
    names = [m["name"] for m in manifest["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput"
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["unit"] == "%"
        assert hasattr(hs.load_by_path("layer_metrics", name), "read")
        # new entries come after what the benchmark had (PR 50's)
        assert names.index(name) > names.index(
            "search.priced_within_2x_share_pct")
        assert by_name[name]["better"] == (
            "higher" if "roofline" in name else "lower")
    assert {by_name[n]["layer"] for n in NEW_METRICS} == {"model ops",
                                                          "kernels"}
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) > cells.index("ouro_2_6b.s4096_b1.1chip")
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index(CONFIG) > configs.index("ouro_2_6b")
    reported = {m["name"] for m in mf.metrics_of(manifest, "per_layer",
                                                 CELL)}
    assert set(NEW_METRICS) <= reported
    assert {"device.mfu_pct", "device.idle_pct",
            "compile.model_compile_s"} <= reported
    # the accepted readers keep to their own cells
    assert not reported & {"layers.ssm_share_pct", "kernels.ssd_roofline",
                           "layers.full_attention_share_pct",
                           "kernels.causal_flash_roofline"}
    # one four-chip cell of the quarter the benchmark may have
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_parameters_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    import numpy as np
    count = {name: sum(int(np.prod(shape)) for _, shape in leaves.values())
             for name, leaves in family.weight_shapes(s).items()}
    mlp = 2560 * 2 * 10240 + 10240 * 2560
    assert mlp == 78_643_200
    assert count["b0_gate_up_proj"] + count["b0_down_proj"] == mlp
    mamba = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 5120 * 16 + 5120 + 5120 * 2560)
    assert count["b0_mixer"] == mamba == 41_241_600
    full = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    assert count["b1_attn"] == full == 19_668_864
    unit = 2 * 2560 * 5120
    assert count["b2_memory_in_proj"] + count["b2_memory_out_proj"] == \
        unit == 26_214_400
    cross = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
    assert count["b3_attn"] == cross == 13_112_704
    norms = 2 * 2 * 2560
    assert [kind + mlp + norms for kind in (mamba, full, unit, cross)] == [
        119_895_040, 98_322_304, 104_867_840, 91_766_144]
    assert count["embed_tokens"] == 25008 * 2560 == 64_020_480
    assert "lm_head" not in count and count["final_ln"] == 5120
    assert sum(count.values()) == family.parameters(s) == 478_876_928
    # 10 bytes a parameter resident, 28 at the peak of the reference's
    # Adam step; a fifth kind of layer (window attention) would not fit
    assert 10 * family.parameters(s) / 1e9 == pytest.approx(4.79, abs=0.01)
    assert 28 * family.parameters(s) / 1e9 == pytest.approx(13.41, abs=0.01)
    assert 28 * (family.parameters(s) + 98_322_304) / 1e9 > 16.1


def test_flops_and_bytes_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    per = family.forward_flops_per_token(s)
    assert per["mlp"] == 4 * 6 * 2560 * 10240
    assert per["mamba_products"] == 2 * (2560 * 10240 + 5120 * 192
                                         + 160 * 5120 + 5120 * 2560)
    assert per["projections"] == (2 * 2560 * (2560 + 1280 + 1280)
                                  + 2 * 2560 * 2560) + 2 * 2 * 2560 * 2560
    pairs = 8192 * 8193 // 2
    assert family.visible_pairs(s, "full") == \
        family.visible_pairs(s, "cross") == pairs == 33_558_528
    # a visible pair: 2 * 40 * 64 for the scores, 2 * 40 * 128 the values
    assert per["scores"] == 2 * (2 * 40 * 64 + 2 * 40 * 128) * pairs / 8192
    assert per["gated_memory"] == 2 * 2 * 2560 * 5120
    assert per["head"] == 2 * 2560 * 25008
    token = sum(per.values())
    assert token == pytest.approx(1083.2e6, rel=1e-3)
    shares = {k: round(100 * v / token, 1) for k, v in per.items()}
    assert shares == {"mlp": 58.1, "mamba_products": 7.6,
                      "projections": 6.0, "scores": 11.6,
                      "gated_memory": 4.8, "head": 11.8}
    assert family.train_flops_per_sample(s) == 3 * 8192 * token
    assert family.train_flops_per_sample(s) == pytest.approx(26.62e12,
                                                             rel=1e-3)
    # under a window of 512 a query sees at most 512 keys
    assert family.visible_pairs(s, "window") == \
        512 * 513 // 2 + (8192 - 512) * 512
    # the selective scan: 671M state updates forward; its bytes bind
    # (they are what the roofline counts), 1.34 ms a step
    flops, nbytes = family.selective_scan_step_flops_and_bytes(s)
    assert 8192 * 5120 * 16 == 671_088_640
    assert flops == 28 * 671_088_640
    assert nbytes == 26 * 8192 * 5120 + 24 * 8192 * 16 == 1_093_664_768
    assert nbytes / 819e9 == pytest.approx(1.335e-3, rel=1e-3)
    assert flops / 197e12 < 0.1 * nbytes / 819e9
    # both attention ops' cores: 12 * pairs * 40 * 64 * 1.5 FLOPs an op
    flops, nbytes = family.diff_flash_step_flops_and_bytes(s)
    assert flops == 2 * 12 * pairs * 40 * 64 * 1.5
    assert flops / 197e12 == pytest.approx(15.70e-3, rel=1e-3)
    assert nbytes / 819e9 < 0.1 * flops / 197e12       # FLOPs, not bytes


STEP = "jit(train_step)/"
MAMBA = "jit(mamba_mixer))/"
TABLE = {
    "fusion.1": dict(op_name=STEP + "jvp(" + MAMBA + "dot_general",
                     part="mamba", direction="forward"),
    "scan.2": dict(op_name=STEP + "jvp(" + MAMBA
                   + "jit(selective_scan)/pallas_call", part="mamba",
                   direction="forward"),
    "scan.3": dict(op_name=STEP + "transpose(jvp(" + MAMBA[:-1]
                   + ")/jit(selective_scan)/pallas_call", part="mamba",
                   direction="backward"),
    "fusion.4": dict(op_name=STEP + "jvp(jit(attention_diff_full))/"
                     "dot_general", part="attention", direction="forward"),
    "flash.5": dict(op_name=STEP + "transpose(jvp(jit(attention_diff_cross"
                    ")))/jit(flash_diff)/pallas_call", part="attention",
                    direction="backward"),
    "fusion.6": dict(op_name=STEP + "jvp(jit(gated_memory))/jit(op_linear)/"
                     "dot_general", part="gated_memory",
                     direction="forward"),
    "fusion.7": dict(op_name=STEP + "jvp(jit(head))/dot_general",
                     part="head", direction="forward"),
}


def fake_device():
    """One train step of 10 ms: under the Mamba mixer 1 ms of a product
    and 0.5 + 1 ms of the scan; under the attention ops 0.5 ms of a
    projection and 2 ms of kernel; 1.5 ms of the gated memory unit;
    1.5 ms of the head; 2 idle."""
    return tr.Device("/device:TPU:0", {
        tr.MODULES: [(tr.STEP_MODULE + "(1)", 0.0, 10e-3)],
        tr.OPS: [("fusion.1", 0.0, 1e-3), ("scan.2", 1e-3, 0.5e-3),
                 ("scan.3", 1.5e-3, 1e-3), ("fusion.4", 2.5e-3, 0.5e-3),
                 ("flash.5", 3e-3, 2e-3), ("fusion.6", 5e-3, 1.5e-3),
                 ("fusion.7", 6.5e-3, 1.5e-3)]})


class FakeFamily:
    observed = {}

    @staticmethod
    def selective_scan_step_flops_and_bytes(sizes):
        return 1e30, 819e9 * 0.3e-3      # bytes alone: 0.3 ms at the peak

    @staticmethod
    def diff_flash_step_flops_and_bytes(sizes):
        return 197e12 * 0.8e-3, 1.0      # 0.8 ms at the bf16 peak


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
    # of the 8 busy ms, 2.5 lie under `mamba_mixer`, 1.5 of them in
    # `selective_scan` for 0.3 of bytes at the peak; 2.5 under the two
    # `attention_diff_*`, 2 of them in `flash_diff` for 0.8 at the peak;
    # 1.5 under `gated_memory`
    assert read["layers.mamba1_mixer_share_pct"] == pytest.approx(
        100 * 2.5 / 8)
    assert read["kernels.selective_scan_roofline"] == pytest.approx(20.0)
    assert read["layers.diff_attention_share_pct"] == pytest.approx(
        100 * 2.5 / 8)
    assert read["kernels.diff_flash_roofline"] == pytest.approx(40.0)
    assert read["layers.gated_memory_share_pct"] == pytest.approx(
        100 * 1.5 / 8)
    # the first reader left the whole breakdown beside the session
    with open(os.path.join(sr.out_dir(ROOT, CELL), "step_parts.json")) as f:
        parts = {(p, d): ms for p, d, ms in
                 json.load(f)["part_direction_ms_a_step"]}
    assert parts[("mamba", "forward")] == pytest.approx(1.5)
    assert parts[("mamba", "backward")] == pytest.approx(1.0)
    assert parts[("gated_memory", "forward")] == pytest.approx(1.5)


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
        op_name=STEP + "jvp(jit(attention_full))/jit(flash_full)/"
        "pallas_call", part="attention", direction="forward"),
        "fusion.6": dict(op_name=STEP + "jvp(jit(ssm_mixer))/jit(ssd_scan)/"
                         "dot_general", part="ssm", direction="forward")})
    assert reader.read(context()) is None              # no such scope
    if name.startswith("kernels."):
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


def test_the_new_scopes_are_parts_of_the_step():
    """`obs.step_scopes` reads the new names back: the Mamba-1 mixer and
    the gated memory unit are parts of their own, the differential ops
    lie in `attention`, in both directions."""
    from flexflow_tpu.obs.step_scopes import classify
    for op_name, want in (
            (STEP + "jvp(jit(mamba_mixer))/jit(selective_scan)/pallas_call",
             ("mamba", "forward")),
            (STEP + "transpose(jvp(jit(mamba_mixer)))/jit(selective_scan)/"
             "pallas_call", ("mamba", "backward")),
            (STEP + "jvp(jit(gated_memory))/jit(op_ew_mul)/mul",
             ("gated_memory", "forward")),
            (STEP + "transpose(jvp(jit(gated_memory)))/jit(op_linear)/"
             "dot_general", ("gated_memory", "backward")),
            (STEP + "jvp(jit(attention_diff_full))/jit(flash_diff)/"
             "pallas_call", ("attention", "forward")),
            (STEP + "transpose(jvp(jit(attention_diff_cross)))/"
             "jit(flash_diff)/pallas_call", ("attention", "backward")),
            (STEP + "jvp(jit(attention_diff_window))/dot_general",
             ("attention", "forward"))):
        assert classify(op_name) == want, op_name
    # no new name holds one of the scopes the accepted readers match as
    # bare substrings
    for new in ("mamba_mixer", "selective_scan", "gated_memory",
                "attention_diff_full", "attention_diff_cross",
                "attention_diff_window", "flash_diff"):
        for old in ("ssm_mixer", "ssd_scan", "moe_layer", "attention_full",
                    "attention_window", "flash_full", "flash_window",
                    "attention_latent", "flash_latent", "gated_conv"):
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
    with pytest.raises(SystemExit, match="Mamba-1 mixer"):
        family.sizes(config, traffic)


TINY = dict(num_hidden_layers=4, first_layer_index=4,
            published_num_hidden_layers=8, vocab_size=64, hidden_size=32,
            num_attention_heads=8, num_key_value_heads=4, head_dim=8,
            intermediate_size=48, sliding_window=8, mamba_d_state=4,
            mamba_dt_rank=4, initializer_range=0.2, embedding_std=0.2,
            seq=32, batch=2, steps_per_epoch=2)


def test_the_cell_end_to_end_at_a_tiny_size(tmp_path, monkeypatch, capsys):
    """The cell through `harness.run_cell` on the CPU (`--trace 2`, which
    is `--trace 0` until the window has closed): the family's functions,
    the check against the reference, the counters, and the join table
    that names the new scopes. Nothing here is a device number."""
    import time
    cell = CELL
    send_output_to(monkeypatch, tmp_path)
    result = hs.run_cell(cell, 2147483777, 0.5, 2,
                         t_start=time.perf_counter(),
                         rehearsal=dict(sizes=TINY))
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    names = set(result["metrics"])
    end_to_end = {m["name"] for m in mf.load_manifest()["end_to_end"]}
    assert end_to_end <= names
    # a CPU trace has no TPU lane: the device-trace readers find nothing
    assert not names & set(NEW_METRICS)
    assert result["metrics"]["compile.window_compiles"]["value"] == 0
    assert "executor.dispatch_ms" in names
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    checks = {ln["name"]: ln for ln in lines if ln.get("phase") == "check"}
    assert checks["no_kernel_fallback"]["detail"] == {}
    assert checks["mixers_by_layer"]["detail"] == ["mamba", "attention",
                                                   "attention"]
    assert checks["parameters_as_counted"]["ok"] is True
    assert checks["shared_tensor_readers"]["detail"] == 3
    assert checks["weights_installed"]["ok"] is True
    assert checks["pred_nrmse"]["value"] < 1e-4
    counters = next(ln for ln in lines
                    if ln.get("phase") == "observed")["op_counters"]
    assert counters["ssm/selective_scan_ops"] == 1
    assert counters["executor.shared_tensors"] == 3
    assert counters["executor.shared_tensor_readers"] == 3
    assert counters["executor.tied_head_ops"] == 1
    assert 0.2 < counters["attention/diff_lambda_b1"] < 1.4
    assert 0.2 < counters["attention/diff_lambda_b3"] < 1.4
    # the join table the session wrote names the new scopes and their
    # parts in both directions, and the five readers, given a TPU lane,
    # would find their rows in it
    from benchmarks import session_reduce as sr
    where = sr.out_dir(ROOT, cell)
    table = next(f for f in os.listdir(where)
                 if f.endswith(".step_scopes.json"))
    with open(os.path.join(where, table)) as f:
        rows = list(json.load(f)["instructions"].values())
    for scope in ("mamba_mixer", "selective_scan", "gated_memory",
                  "attention_diff_full", "attention_diff_cross", "head"):
        assert any(f"jit({scope})" in r["op_name"] for r in rows), scope
    for scope, part in (("mamba_mixer", "mamba"),
                        ("selective_scan", "mamba"),
                        ("gated_memory", "gated_memory"),
                        ("attention_diff_cross", "attention")):
        inside = [r for r in rows if f"jit({scope})" in r["op_name"]]
        assert {r["part"] for r in inside} == {part}, scope
        assert {"forward", "backward"} <= {r["direction"] for r in inside}, \
            scope
    with open(os.path.join(where, next(
            f for f in os.listdir(where)
            if f.endswith(".events.jsonl")))) as f:
        header = json.loads(f.readline())
    meta = header.get("meta", header)
    assert meta["shared_tensor_readers"] == 3
    assert meta["ssm_selective_scan_ops"] == 1
