#!/usr/bin/env python
"""Per-layer roofline attribution + layout/batch A/B harness.

The evidence channel for conv-family optimization decisions (ISSUE 2):
times every materialized op of a zoo model standalone (slope-timed, the
BENCH_NOTES methodology), computes flops/bytes against the chip's peaks,
and names each layer compute-bound vs bandwidth-bound. Writes
``<out>.json`` (machine-readable rows + per-class aggregates) and
``<out>.md`` (the table for BENCH_NOTES).

    python scripts/roofline.py --model inception --batch 16 --layout nhwc
    python scripts/roofline.py --model inception --ab --batches 8,64

``--ab`` additionally measures FULL-STEP training throughput (bench.py's
``time_train`` protocol) for every (layout, batch) cell — the
same-session A/B the chip-weather volatility rules require
(BENCH_NOTES.md: only same-session A/Bs are trustworthy).

The conv-class ``efficiency`` aggregate printed at the end is the number
to feed ``MachineSpec.conv_efficiency`` (native cost-model calibration).
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_model(name, batch, layout, on_cpu, image_size=None):
    import jax.numpy as jnp

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.ffconst import LossType
    from flexflow_tpu.optimizers import AdamOptimizer, SGDOptimizer
    import numpy as np

    rs = np.random.RandomState(0)
    cfg_kw = dict(conv_compute_layout=layout)
    if name == "inception":
        from flexflow_tpu.models.inception import (InceptionConfig,
                                                   create_inception_v3)
        # CPU default mirrors bench.py's reduced proxy; TPU the AE protocol
        mc = InceptionConfig(
            batch_size=batch,
            image_size=image_size or (75 if on_cpu else 299),
            num_classes=10 if on_cpu else 1000,
            reduced=on_cpu)
        ff = create_inception_v3(mc, FFConfig(batch_size=batch, **cfg_kw))
        ff.compile(AdamOptimizer(alpha=1e-4, state_dtype=jnp.bfloat16),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
        x = rs.randn(batch, 3, mc.image_size, mc.image_size).astype(np.float32)
        y = rs.randint(0, mc.num_classes, (batch, 1)).astype(np.int32)
        return ff, [x], y
    if name == "bert":
        from flexflow_tpu.models.transformer import (TransformerConfig,
                                                     create_transformer)
        mc = (TransformerConfig(num_layers=2, hidden_size=128, num_heads=4,
                                seq_length=64, batch_size=batch)
              if on_cpu else TransformerConfig(batch_size=batch))
        ff = create_transformer(mc, FFConfig(batch_size=batch, **cfg_kw))
        ff.compile(AdamOptimizer(alpha=1e-4, state_dtype=jnp.bfloat16),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
        x = rs.randn(batch, mc.seq_length, mc.hidden_size).astype(np.float32)
        y = rs.randn(batch, mc.seq_length, 1).astype(np.float32)
        return ff, [x], y
    if name == "dlrm":
        from flexflow_tpu.models.dlrm import DLRMConfig, create_dlrm
        mc = (DLRMConfig(batch_size=batch, num_sparse_features=4,
                         vocab_size=1000, embedding_dim=16) if on_cpu else
              DLRMConfig(batch_size=batch, num_sparse_features=8,
                         vocab_size=1000000, embedding_dim=64))
        ff = create_dlrm(mc, FFConfig(batch_size=batch, **cfg_kw))
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
        xs = []
        for n in ff.executor.input_names:
            if n.startswith("sparse"):
                xs.append(rs.randint(0, mc.vocab_size,
                                     (batch, mc.indices_per_feature))
                          .astype(np.int32))
            else:
                xs.append(rs.randn(batch, mc.dense_dim).astype(np.float32))
        y = rs.randint(0, 2, (batch, 1)).astype(np.float32)
        return ff, xs, y
    if name == "moe":
        from flexflow_tpu.models.moe_model import MoEConfig, create_moe
        mc = (MoEConfig(batch_size=batch, input_dim=64, num_exp=4,
                        num_select=2, hidden_size=32) if on_cpu else
              MoEConfig(batch_size=batch, input_dim=1024, num_exp=16,
                        num_select=2, hidden_size=1024, num_classes=1000))
        ff = create_moe(mc, FFConfig(batch_size=batch, **cfg_kw))
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
        x = rs.randn(batch, mc.input_dim).astype(np.float32)
        y = rs.randint(0, mc.num_classes, (batch, 1)).astype(np.int32)
        return ff, [x], y
    raise SystemExit(f"unknown --model {name!r}")


def step_throughput(ff, xs, y, iters, windows):
    from bench import time_train
    sps, _ = time_train(ff, xs, y, iters=iters, windows=windows)
    return sps


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="inception",
                    choices=["inception", "bert", "dlrm", "moe"])
    ap.add_argument("--batch", type=int, default=None,
                    help="batch size (default: 8 CPU / 16 TPU)")
    ap.add_argument("--image-size", type=int, default=None)
    ap.add_argument("--layout", default="auto",
                    choices=["auto", "nhwc", "nchw"])
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--no-bwd", action="store_true",
                    help="skip backward timing (faster)")
    ap.add_argument("--ab", action="store_true",
                    help="also run full-step layout x batch A/Bs")
    ap.add_argument("--batches", default="8,64",
                    help="comma list of batch sizes for --ab")
    ap.add_argument("--iters", type=int, default=None,
                    help="A/B steps per timing window")
    ap.add_argument("--out", default=None,
                    help="output stem (default roofline_<model>_<layout>)")
    args = ap.parse_args()

    import jax

    from flexflow_tpu import __version__
    from flexflow_tpu.machine import detect_machine_spec
    from flexflow_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    from flexflow_tpu.obs.roofline import (finish_aggregates,
                                           format_markdown, roofline_report)

    on_cpu = jax.devices()[0].platform == "cpu"
    batch = args.batch or (8 if on_cpu else 16)
    print(f"[roofline] building {args.model} batch={batch} "
          f"layout={args.layout} on {jax.devices()[0].platform}",
          file=sys.stderr)
    ff, xs, y = build_model(args.model, batch, args.layout, on_cpu,
                            args.image_size)
    spec = ff.machine_spec or detect_machine_spec()
    report = roofline_report(ff.executor.nodes, spec,
                             repeats=args.repeats,
                             include_bwd=not args.no_bwd)
    report["meta"] = dict(model=args.model, batch=batch,
                          layout=args.layout,
                          layout_info=dict(ff.layout_info,
                                           boundaries=None),
                          platform=jax.devices()[0].platform,
                          version=__version__)
    finish_aggregates(report["classes"], report["machine"]["peak_flops"])

    if args.ab:
        iters = args.iters or (3 if on_cpu else 10)
        ab = []
        del ff
        for layout in ("nchw", "nhwc"):
            for b in [int(s) for s in args.batches.split(",")]:
                try:
                    m, mxs, my = build_model(args.model, b, layout, on_cpu,
                                             args.image_size)
                    sps = step_throughput(m, mxs, my, iters=iters, windows=2)
                    cell = dict(layout=layout, batch=b,
                                samples_per_s=round(sps, 3),
                                steps_per_s=round(sps / b, 4))
                    del m
                except Exception as e:
                    cell = dict(layout=layout, batch=b,
                                error=f"{type(e).__name__}: {e}")
                print(f"[roofline] A/B {cell}", file=sys.stderr)
                ab.append(cell)
        report["ab"] = ab

    out = args.out or f"roofline_{args.model}_{args.layout}"
    with open(out + ".json", "w") as f:
        json.dump(report, f, indent=1)
    md = format_markdown(report)
    if args.ab:
        md += "\n\nFull-step A/B (samples/s, same session):\n\n" \
              "| layout | batch | samples/s | steps/s |\n|---|---|---|---|\n"
        for c in report["ab"]:
            md += (f"| {c['layout']} | {c['batch']} "
                   f"| {c.get('samples_per_s', c.get('error'))} "
                   f"| {c.get('steps_per_s', '')} |\n")
    with open(out + ".md", "w") as f:
        f.write(f"# Roofline: {args.model} (batch {batch}, "
                f"layout {args.layout}, "
                f"{report['meta']['platform']})\n\n" + md + "\n")
    print(f"[roofline] wrote {out}.json {out}.md", file=sys.stderr)
    # one machine-readable stdout line, bench.py-style
    conv = report["classes"].get("conv") or {}
    print(json.dumps(dict(
        model=args.model, batch=batch, layout=args.layout,
        conv_efficiency=conv.get("efficiency"),
        classes={k: dict(ops=v["ops"], efficiency=v.get("efficiency"))
                 for k, v in report["classes"].items()},
        ab=report.get("ab"))))


if __name__ == "__main__":
    main()
