#!/usr/bin/env python
"""fflint CLI — static strategy & graph verifier over the model zoo.

Builds + compiles a zoo model (CPU-sized configs by default; no training
step runs) and runs the fflint pass pipeline (flexflow_tpu/analysis)
over the materialized PCG, the chosen strategy, and — with ``--hlo`` —
the optimized HLO of the compiled train step. Exit code is nonzero when
any ERROR-severity diagnostic fires.

    python scripts/fflint.py --model mlp
    python scripts/fflint.py --model transformer --budget 4 --hlo
    python scripts/fflint.py --all --json > fflint.json
    python scripts/fflint.py --model resnet --layout nhwc --lint-out out.json
    python scripts/fflint.py --model llama --budget 4 --edges

``--edges`` additionally renders the per-edge reshard table
(analysis/dataflow.py): every producer→consumer spec disagreement with
the collective it implies — kind, per-device bytes, mesh axes, fabric
(ici|dcn) — plus the generalized tiny-batch weight-movement edges. With
``--json`` the table lands under ``edge_reshards``; the exit code is
nonzero whenever an unpriced edge fires FFL205/FFL210 (ERROR).

``--model all`` / ``--all`` sweeps every zoo model and merges the
reports into one JSON document keyed by model name (the artifact the
run_t1.sh lint stage commits next to the bench output).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# lint against a virtual 8-device mesh on CPU (the tests' fake TPU
# slice) — a 1-device mesh has no sharding for the passes to verify
if os.environ.get("JAX_PLATFORMS", "") in ("", "cpu") \
        and "xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

ZOO = ["mlp", "alexnet", "resnet", "resnext", "inception", "dlrm", "xdl",
       "candle_uno", "moe", "moe_encoder", "transformer", "llama", "lfm2",
       "ouro", "phi4flash", "keye", "qwen3_next", "xing4"]


def build_model(name: str, ff_config):
    """CPU-sized zoo configs (the tests' sizes): build only — compile is
    the caller's job so search/mesh flags apply uniformly."""
    if name == "mlp":
        from flexflow_tpu.models.mlp import create_mlp
        return create_mlp(batch_size=16, in_dim=64, hidden_dims=(128, 128),
                          out_dim=10, ff_config=ff_config), "cat"
    if name == "alexnet":
        from flexflow_tpu.models.alexnet import create_alexnet
        return create_alexnet(batch_size=8, num_classes=10,
                              ff_config=ff_config), "cat"
    if name == "resnet":
        from flexflow_tpu.models.resnet import ResNetConfig, create_resnet
        return create_resnet(
            ResNetConfig(batch_size=8, image_size=64, stages=(1, 1, 1, 1)),
            ff_config), "cat"
    if name == "resnext":
        from flexflow_tpu.models.resnext import (ResNeXtConfig,
                                                 create_resnext50)
        return create_resnext50(
            ResNeXtConfig(batch_size=8, image_size=64, stages=(1, 1, 1, 1),
                          cardinality=8), ff_config), "cat"
    if name == "inception":
        from flexflow_tpu.models.inception import (InceptionConfig,
                                                   create_inception_v3)
        return create_inception_v3(
            InceptionConfig(batch_size=8, image_size=75, num_classes=10),
            ff_config), "cat"
    if name == "dlrm":
        from flexflow_tpu.models.dlrm import DLRMConfig, create_dlrm
        return create_dlrm(
            DLRMConfig(batch_size=8, vocab_size=1000, num_sparse_features=4),
            ff_config), "mse"
    if name == "xdl":
        from flexflow_tpu.models.xdl import XDLConfig, create_xdl
        return create_xdl(XDLConfig(batch_size=8,
                                    embedding_size=(1000, 1000)),
                          ff_config), "cat"
    if name == "candle_uno":
        from flexflow_tpu.models.candle_uno import (CandleUnoConfig,
                                                    create_candle_uno)
        return create_candle_uno(
            CandleUnoConfig(batch_size=8, dense_layers=(32,) * 2,
                            dense_feature_layers=(32,) * 2,
                            input_features={"dose1": 1, "cell": 24,
                                            "drug_desc": 40}),
            ff_config), "mse"
    if name == "moe":
        from flexflow_tpu.models.moe_model import MoEConfig, create_moe
        return create_moe(
            MoEConfig(batch_size=16, input_dim=32, num_exp=4, num_select=2,
                      hidden_size=16), ff_config), "cat"
    if name == "moe_encoder":
        from flexflow_tpu.models.moe_model import (MoEConfig,
                                                   create_moe_encoder)
        return create_moe_encoder(
            MoEConfig(batch_size=4, num_encoder_layers=2, hidden_size=16,
                      num_exp=2, num_select=1, seq_length=8, num_classes=5),
            ff_config), "mse"
    if name == "transformer":
        from flexflow_tpu.models.transformer import (TransformerConfig,
                                                     create_transformer)
        return create_transformer(
            TransformerConfig(num_layers=2, hidden_size=128, num_heads=4,
                              seq_length=64, batch_size=16),
            ff_config), "mse"
    if name == "llama":
        from flexflow_tpu.models.llama import (LlamaModelConfig,
                                               create_llama)
        return create_llama(LlamaModelConfig(), ff_config), "cat"
    if name == "lfm2":
        # gated short convolutions 3 : 1 with grouped-query attention, a
        # dense layer, then experts; the head reads the embedding's table
        from flexflow_tpu.models import DecoderConfig, create_decoder
        return create_decoder(DecoderConfig(
            layer_types=["conv", "full_attention", "conv", "conv"],
            num_dense_layers=1, qk_layernorm=True, head_dim=16,
            moe_shared_expert_intermediate_size=0,
            tie_word_embeddings=True, batch_size=8, seq_length=16),
            ff_config), "cat"
    if name == "ouro":
        # a looped model: two sandwich-norm blocks applied three times
        # with one set of leaves (the readers name their owners), an
        # exit gate beside the head
        from flexflow_tpu.models import DecoderConfig, create_decoder
        return create_decoder(DecoderConfig(
            hybrid_override_pattern="UU", total_ut_steps=3,
            num_attention_heads=4, num_key_value_heads=4, batch_size=8,
            seq_length=16), ff_config), "exit"
    if name == "phi4flash":
        # a decoder-hybrid-decoder model, all five kinds of layer by the
        # published rule: a Mamba-1 scan whose output two later layers
        # read, keys and values that a later layer cross-attends (second
        # and third OUTPUTS of their ops), differential attention
        from flexflow_tpu.models import DecoderConfig, create_decoder
        return create_decoder(DecoderConfig(
            mb_per_layer=2, num_hidden_layers=8, sliding_window=8,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            tie_word_embeddings=True, batch_size=8, seq_length=16),
            ff_config), "cat"
    if name == "keye":
        # learned sparse attention: an indexer keeps 6 keys a query, the
        # main attention runs over them, the indexer's loss leaves on
        # the executor's side channel; then experts. (The op refuses
        # `sparse_index` with a window, a block-diffusion mask,
        # differential pairs or given keys/values with a sentence of its
        # own, so no such model reaches a lint pass.)
        from flexflow_tpu.models import DecoderConfig, create_decoder
        return create_decoder(DecoderConfig(
            hybrid_override_pattern="KK", num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, experts_held=4,
            sa_config=dict(indexer_num_heads=4, indexer_head_dim=8,
                           indexer_num_kv_heads=1, topk=6),
            mrope_section=(2, 3, 3), batch_size=8, seq_length=16),
            ff_config), "cat"
    if name == "qwen3_next":
        # gated delta-rule mixers in three layers of four, then gated
        # attention whose gate comes a lane out of the query projection;
        # zero-centred norms; softmax top-k experts with a gated shared
        # expert (the delta mixer's chunk counts and decays leave on the
        # executor's side channel: no remat twin)
        from flexflow_tpu.models import DecoderConfig, create_decoder
        return create_decoder(DecoderConfig(
            layer_types=["linear_attention"] * 3 + ["full_attention"],
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            qk_layernorm=True, attn_output_gate=True,
            zero_centered_norms=True, partial_rotary_factor=0.25,
            router_scoring="softmax", shared_expert_gate=True,
            experts_held=4, delta_chunk_size=8, batch_size=8,
            seq_length=16), ff_config), "cat"
    if name == "xing4":
        # every residual connection a hyper-connection over 4 streams
        # (two exported tensors a sublayer: the maps and the stream
        # handed through); latent attention under YaRN, a dense layer,
        # then experts, the multi-token-prediction module
        from flexflow_tpu.models import DecoderConfig, create_decoder
        return create_decoder(DecoderConfig(
            hybrid_override_pattern="AX", hc_mult=4,
            num_attention_heads=4, experts_held=4,
            num_nextn_predict_layers=1,
            rope_scaling=dict(type="yarn", factor=64, beta_fast=32,
                              beta_slow=1, mscale=1, mscale_all_dim=1,
                              original_max_position_embeddings=8),
            batch_size=8, seq_length=16), ff_config), "cat"
    raise SystemExit(f"unknown --model {name!r} (zoo: {', '.join(ZOO)})")


def compile_model(ff, loss_kind: str):
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.optimizers import SGDOptimizer
    loss = {"mse": LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
            "exit": LossType.EXPECTED_EXIT_SPARSE_CATEGORICAL_CROSSENTROPY,
            }.get(loss_kind, LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    ff.compile(SGDOptimizer(lr=0.01), loss)
    return ff


def edge_table_json(ff) -> list:
    """The per-edge reshard table of the compiled model, as JSON rows —
    implicit GSPMD insertions first, then explicit boundaries, then the
    generalized tiny-batch weight-movement edges."""
    from flexflow_tpu.analysis import (LintContext, edge_reshard_table,
                                       weight_movement_edges)
    ctx = LintContext(
        nodes=ff.executor.nodes, mesh=ff.mesh, strategy=ff.strategy,
        machine_spec=ff.machine_spec, config=ff.config,
        final_ref=ff.executor.final_ref, ff=ff)
    rows = [e.to_json() for e in
            sorted(edge_reshard_table(ctx),
                   key=lambda e: (e.explicit, -e.bytes))]
    rows += [dict(e.to_json(), weight_movement=True)
             for e in weight_movement_edges(ctx)]
    return rows


def format_edges(rows: list) -> str:
    lines = []
    for r in rows:
        tag = ("wmove" if r.get("weight_movement")
               else "explicit" if r["explicit"] else "implicit")
        lines.append(
            f"  {tag:<8} {r['edge']}  {r['src_spec']} -> {r['dst_spec']}"
            f"  {r['kind']} {r['bytes'] / 1e6:.3f} MB"
            f" [{'+'.join(r['axes']) or '-'}/{r['fabric']}]"
            + (f" ({r['reason']})" if r.get("reason") else ""))
    return "\n".join(lines) if lines else "  (no edge reshards)"


def lint_one(name: str, args) -> "LintReport":
    from flexflow_tpu.analysis import lint_model
    from flexflow_tpu.config import FFConfig

    cfg = FFConfig(conv_compute_layout=args.layout)
    if args.budget:
        cfg.search_budget = args.budget
        cfg.enable_parameter_parallel = True
        cfg.enable_pipeline_parallel = False
    ff, loss_kind = build_model(name, cfg)
    compile_model(ff, loss_kind)
    report = lint_model(ff, hlo=True if args.hlo else None)
    report.context["model"] = name
    if getattr(args, "edges", False):
        report.context["edge_reshards"] = edge_table_json(ff)
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default=None,
                    help=f"zoo model ({', '.join(ZOO)}) or 'all'")
    ap.add_argument("--all", action="store_true",
                    help="lint every zoo model")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--hlo", action="store_true",
                    help="also compile the train step and run the "
                         "emitted-HLO checks (slow)")
    ap.add_argument("--budget", type=int, default=0,
                    help="search budget: lint the SEARCHED strategy "
                         "instead of the data-parallel default")
    ap.add_argument("--edges", action="store_true",
                    help="include the per-edge reshard table (kind, "
                         "bytes, axes, fabric per producer->consumer "
                         "spec disagreement)")
    ap.add_argument("--layout", default="auto",
                    choices=["auto", "nhwc", "nchw"],
                    help="conv compute layout for the layout pass")
    ap.add_argument("--lint-out", default=None,
                    help="also write the JSON report to this path")
    args = ap.parse_args()

    models = ZOO if (args.all or args.model in (None, "all")) \
        else [args.model]
    merged = {}
    rc = 0
    for name in models:
        try:
            report = lint_one(name, args)
        except Exception as e:
            merged[name] = dict(error=f"build/compile failed: {e!r}")
            print(f"== {name}: build/compile failed: {e!r}",
                  file=sys.stderr)
            rc = 2
            continue
        merged[name] = report.to_json()
        if report.has_errors():
            rc = rc or 1
        if not args.json:
            edges = report.context.pop("edge_reshards", None)
            print(f"== {name}")
            print(report.format_human())
            if edges is not None:
                print(f"-- edge reshard table ({len(edges)} edges)")
                print(format_edges(edges))
    doc = merged if len(models) > 1 else merged[models[0]]
    if args.json:
        print(json.dumps(doc, indent=1))
    if args.lint_out:
        with open(args.lint_out, "w") as f:
            json.dump(doc, f, indent=1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
