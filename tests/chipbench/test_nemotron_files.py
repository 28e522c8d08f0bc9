"""The data files, FLOP and byte functions and readers that the
`nemotron3_nano_30b_a3b` configuration and the four-chip `bert_ae` cell
add: the configuration against the catalog's row, hand counts, and the
scope readers on a made-up trace."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness as hs  # noqa: E402
from benchmarks import manifest as mf  # noqa: E402
from benchmarks import scope_reduce  # noqa: E402
from benchmarks import trace_reduce as tr  # noqa: E402

CELL = "nemotron3_nano_30b_a3b.s8192_b1.1chip"
FOUR = "bert_ae.s512_b128.4chip"
# the numbers of the catalog's row NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
# (model-configs guide, architectures.jsonl), as published
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 9, "n_routed_experts": 8,
           "vocab_size": 16384, "mamba_num_heads": 8, "n_groups": 1,
           "num_attention_heads": 4, "num_key_value_heads": 1}
# never cut: hidden, head, state and expert widths, experts a token
WIDTHS = ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
          "conv_kernel", "chunk_size", "moe_intermediate_size",
          "moe_shared_expert_intermediate_size", "intermediate_size",
          "num_experts_per_tok", "expand")


@pytest.fixture(scope="module")
def cell():
    manifest = mf.load_manifest()
    entry, config, traffic = mf.find_cell(manifest, CELL)
    family = hs.load_by_path("families", config["family"])
    return manifest, entry, config, traffic, family


def test_configuration_holds_the_published_numbers(cell):
    manifest, _, config, _, _ = cell
    (listed,) = [c for c in manifest["configs"]
                 if c["name"] == "nemotron3_nano_30b_a3b"]
    assert listed["reduced"] == config["reduced"] == list(REDUCED)
    assert not set(REDUCED) & set(WIDTHS)
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config[key] == REDUCED[key], key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["n_routed_experts_published"] == 128
    assert config["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert len(config["hybrid_override_pattern"]) == 52
    for key in ("source", "deployment", "departures", "assumed", "adam"):
        assert config[key]
    assert "16 chips share each layer" in config["deployment"]


def test_cell_and_metric_entries(cell):
    manifest, entry, _, traffic, _ = cell
    assert entry["chips"] == 1 and entry["traffic"] == "s8192_b1"
    assert (traffic["seq"], traffic["batch"], traffic["steps_per_epoch"],
            traffic["reference_chunk"], traffic["part_a_share"]) == (
        8192, 1, 4, 1, 0.5)
    four, _, four_traffic = mf.find_cell(manifest, FOUR)
    assert four["chips"] == 4 and four["config"] == "bert_ae"
    assert (four_traffic["seq"], four_traffic["batch"],
            four_traffic["steps_per_epoch"]) == (512, 128, 4)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("layers.ssm_share_pct", "layers.moe_share_pct",
                 "kernels.ssd_roofline", "kernels.grouped_matmul_roofline"):
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "throughput"
        assert by_name[name]["source"] == "device_trace"
    assert by_name["collectives.exposed_ms"]["workloads"] == [FOUR]
    # flash's formula is for non-causal MHA: its list is as it was
    assert by_name["kernels.flash_roofline"]["workloads"] == [
        "bert_ae.s512_b32.1chip"]
    # what the benchmark had comes first, unchanged in order
    assert [w["name"] for w in manifest["workloads"]][:2] == [
        "bert_ae.s512_b32.1chip", "inception_v3_ae.b256.1chip"]


def test_parameters_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    import numpy as np
    count = {name: sum(int(np.prod(shape)) for _, shape in leaves.values())
             for name, leaves in family.weight_shapes(s).items()}
    mamba = (2688 * (512 + 768 + 8) + 4 * 768 + 768 + 3 * 8 + 512
             + 512 * 2688)
    experts = (2688 * 128 + 128 + 8 * 2 * 2688 * 1856
               + 2 * 2688 * 3712)
    attention = 2688 * 128 * (4 + 1 + 1) + 4 * 128 * 2688
    assert count["b0_mixer"] == mamba == 4_842_776
    assert count["b1_mixer"] == experts == 100_122_752
    assert count["b5_mixer"] == attention == 3_440_640
    assert count["embed_tokens"] == count["lm_head"] == 16384 * 2688
    total = sum(count.values())
    assert total == (4 * mamba + 4 * experts + attention
                     + 2 * 16384 * 2688 + 10 * 2688) == 511_410_016


def test_flops_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    per = family.forward_flops_per_token(s)
    # the four SSD products a token: C B^T and scores x inside a chunk of
    # 128, the chunk's state and its read
    ssd = 2 * (128 * 128 + 128 * 512 + 2 * 128 * 512)
    assert family.ssd_forward_flops_per_token(s) == ssd == 425_984
    assert per["M"] == (2 * 2688 * 1288 + 2 * 768 * 4 + ssd
                        + 2 * 512 * 2688)
    assert per["*"] == 2 * 2688 * 128 * (8 + 2) + 4 * 4 * 128 * 8192 / 2
    routed = 4 * 2688 * 1856 * 6 * 8 / 128
    assert per["E"] == routed + 4 * 2688 * 3712 + 2 * 2688 * 128
    assert per["head"] == 2 * 2688 * 16384
    a_token = 4 * per["M"] + 4 * per["E"] + per["*"] + per["head"]
    assert family.train_flops_per_sample(s) == 3 * 8192 * a_token
    assert 8.2e12 < family.train_flops_per_sample(s) < 8.3e12
    shares = {k: 100 * v / a_token for k, v in (
        ("E", 4 * per["E"]), ("head", per["head"]), ("M", 4 * per["M"]),
        ("*", per["*"]))}
    assert [round(shares[k]) for k in ("E", "head", "M", "*")] == [
        57, 26, 12, 5]


def test_kernel_flops_and_bytes_by_hand(cell):
    _, _, config, traffic, family = cell
    s = family.sizes(config, traffic)
    flops, nbytes = family.ssd_step_flops_and_bytes(s)
    assert flops == 3 * 425_984 * 8192 * 4
    x, bc = 2 * 8192 * 512, 2 * 8192 * 256
    assert nbytes == 4 * ((2 * x + bc) + (3 * x + 2 * bc))
    assert family.expected_held_slots(s) == 3072
    flops, nbytes = family.grouped_matmul_step_flops_and_bytes(s)
    assert flops == 3 * 4 * 3072 * 2688 * 1856 * 4
    assert nbytes == 4 * 6 * (2 * 8 * 2688 * 1856 + 2 * 3072 * (2688 + 1856))
    # counted for the pairs that landed on held experts
    more, _ = family.grouped_matmul_step_flops_and_bytes(s, 4000)
    assert more == pytest.approx(flops * 4000 / 3072)


def test_balanced_routers_give_the_held_experts_their_share(cell):
    """The benchmark sets the routers' bias from the seed by the
    published balancing rule: on the seed's batch every expert then gets
    its share, so the held ones get theirs."""
    import jax
    import numpy as np
    _, _, _, _, family = cell
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_rehearsal_nemotron import TINY
    from benchmarks.references import nemotron_h as ref
    s = dict(TINY, hybrid_override_pattern="MEMEM*EME", seq=64,
             layer_norm_epsilon=1e-5, conv_kernel=4, time_step_min=1e-3,
             time_step_max=1e-1, time_step_floor=1e-4, expert_offset=0,
             routed_scaling_factor=2.5, norm_topk_prob=True, embedding_std=1.0)
    w = jax.device_get(family.make_weights(s, 5))
    assert np.any(w["b1_mixer"]["e_bias"] != 0)
    (ids,), _ = family.make_data(dict(s, steps_per_epoch=1), 5)
    chosen = np.asarray(ref.routed_experts(w, ids, 1,
                                           **family.reference_kw(s)))
    load = np.bincount(chosen.reshape(-1), minlength=16)
    mean = chosen.size / 16
    # 24 pairs an expert here: the sampling alone spreads them by a fifth
    assert load.max() <= 1.6 * mean and load.min() >= 0.5 * mean
    unbalanced = dict(w, b1_mixer=dict(w["b1_mixer"], e_bias=0 * w[
        "b1_mixer"]["e_bias"]))
    before = np.bincount(np.asarray(ref.routed_experts(
        unbalanced, ids, 1, **family.reference_kw(s))).reshape(-1),
        minlength=16)
    assert before.max() - before.min() > load.max() - load.min()


def test_hlo_scopes_parse():
    _, _, config, _, family = (None, None) + tuple(
        mf.find_cell(mf.load_manifest(), CELL)[1:]) + (
        hs.load_by_path("families", "nemotron_h"),)
    text = """
ENTRY %main {
  %fusion.7 = bf16[8,16]{1,0} fusion(%p0), kind=kLoop, calls=%f, metadata={op_name="jit(train_step)/jvp(ssm_mixer)/ssd_scan/dot_general" source_file="x.py"}
  ROOT %gmm.2 = bf16[8,16]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(moe_layer))/moe_grouped_matmul/jit(tgmm)/pallas_call"}
  %copy.1 = bf16[8,16]{1,0} copy(%fusion.7)
}
"""
    assert family.hlo_scopes(text) == {
        "fusion.7": "jit(train_step)/jvp(ssm_mixer)/ssd_scan/dot_general",
        "gmm.2": "jit(train_step)/transpose(jvp(moe_layer))/"
                 "moe_grouped_matmul/jit(tgmm)/pallas_call"}


class Family:
    observed = {"scopes": {
        "fusion.1": "jit(train_step)/jvp(ssm_mixer)/ssd_scan/dot_general",
        "fusion.2": "jit(train_step)/transpose(jvp(ssm_mixer))/mul",
        "gmm.1": "jit(train_step)/jvp(moe_layer)/moe_grouped_matmul/x",
        "fusion.3": "jit(train_step)/jvp(moe_layer)/moe_shared/dot_general",
        "fusion.4": "jit(train_step)/optimizer_update/add"}}


def made_up_trace():
    """Two train steps of 10 ms; 2 + 1 ms a step under ssm_mixer (2 of
    them under ssd_scan), 3 + 1 under moe_layer, 1 outside, 2 idle."""
    ops, modules = [], []
    for k in range(2):
        t0 = 0.1 + 0.010 * k
        modules.append((f"jit_train_step({k})", t0, 0.010))
        at = t0
        for name, dur in (("fusion.1", 0.002), ("fusion.2", 0.001),
                          ("gmm.1", 0.003), ("fusion.3", 0.001),
                          ("fusion.4", 0.001)):
            ops.append((name, at, dur))
            at += dur
    ops.append(("while.1", 0.1, 0.004))        # enclosing: never counted
    return [tr.Device("/device:TPU:0", {tr.OPS: ops, tr.MODULES: modules})]


def test_scope_readers_on_a_made_up_trace():
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    ctx = dict(devices=made_up_trace(), family=Family,
               counters=dict(peaks=peaks))
    # busy is the union of the spans: `while.1` lies over counted ops
    assert scope_reduce.share_pct(ctx, "ssm_mixer") == pytest.approx(
        100 * 3 / 8)
    assert scope_reduce.share_pct(ctx, "moe_layer") == pytest.approx(
        100 * 4 / 8)
    # 1e9 FLOPs are 1 ms at the peak, 5e7 bytes 0.5 ms: 1 ms over 2 ms
    assert scope_reduce.roofline_pct(ctx, "ssd_scan", 1e9, 5e7) \
        == pytest.approx(50.0)
    assert scope_reduce.roofline_pct(ctx, "moe_grouped_matmul", 1e9, 6e8) \
        == pytest.approx(100 * 6 / 3)     # the bytes bound: 6 ms over 3 ms


@pytest.mark.parametrize("metric", [
    "layers.ssm_share_pct", "layers.moe_share_pct", "kernels.ssd_roofline",
    "kernels.grouped_matmul_roofline", "collectives.exposed_ms"])
def test_readers_return_nothing_where_there_is_nothing_to_read(metric, cell):
    """A program without the scopes (the parent), another family, a CPU
    trace: None, and no exception."""
    reader = hs.load_by_path("layer_metrics", metric)
    _, _, config, traffic, family = cell
    sizes = family.sizes(config, traffic)
    bert = hs.load_by_path("families", "bert_ae")
    for fam, devices in ((bert, made_up_trace()), (family, []),
                         (bert, [])):
        if hasattr(fam, "observed"):
            fam.observed.clear()
        ctx = dict(devices=devices, family=fam,
                   counters=dict(peaks=None, sizes=sizes))
        if metric == "collectives.exposed_ms" and devices:
            assert reader.read(ctx) == 0.0    # one chip: no collective
        else:
            assert reader.read(ctx) is None


def test_exposed_collectives_reader_on_a_made_up_trace():
    reader = hs.load_by_path("layer_metrics", "collectives.exposed_ms")
    dev = made_up_trace()[0]
    # 2 ms of all-reduce a step, 1 ms of it under a compute op
    extra = [("all-reduce.1", 0.1085 + 0.010 * k, 0.002) for k in range(2)]
    devices = [tr.Device(dev.name, {**dev.lines,
                                    tr.OPS: dev.lines[tr.OPS] + extra})]
    assert reader.read(dict(devices=devices)) == pytest.approx(1.5)
