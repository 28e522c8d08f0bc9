#!/usr/bin/env python3
"""The output check over many seeds in one process, with its control.

    python3 benchmarks/seeds_check.py --workload <cell> --seeds 1 2 3 ...

For every seed: fresh weights and data go into the cell's compiled
model, the check's system side runs (predictions, three one-step
losses), and the plain float32 reference judges it, exactly as in
`run.py`. Then the control: the reference itself, with the operands of
every matrix multiplication rounded to float8 (the nearest precision
below the configuration's bfloat16), is put in the program's place and
judged by the same comparison; it has to come out as not correct. The
reference with bfloat16 operands is read too, for scale. The limits in
the family files were set from this tool's readings (PERF.md).

The model is compiled once and reused: between seeds its weights are
replaced through `set_parameter` and Adam's state is zeroed.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def check_seeds(cell_name, seeds, root=ROOT, rehearsal=None,
                operands=("fp8", "bf16"), control_seeds=None):
    from benchmarks import harness as hs
    from benchmarks import manifest as mf
    manifest = mf.load_manifest(root)
    cell, config, traffic = mf.find_cell(manifest, cell_name, root)
    family = hs.load_by_path("families", config["family"], root)
    hs.build_native(root)
    import jax
    if rehearsal is None:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("seeds_check: needs a TPU")
        from flexflow_tpu.utils.compile_cache import configure_compile_cache
        configure_compile_cache()
    s = family.sizes(config, traffic, (rehearsal or {}).get("sizes"))
    batch = s["batch"]
    one = dict(s, steps_per_epoch=1)     # the check reads the first batch
    records = _system_sides(hs, family, config, s, one, cell, seeds, batch)
    rows_out = []
    if control_seeds is None:
        control_seeds = len(records)
    log_space = getattr(family, "PREDICTIONS_ARE_PROBABILITIES", False)

    def judged(got, want):
        rows = hs.compare(got, want, family.TOLERANCES, log_space)
        out = {r["name"]: r["value"] for r in rows}
        out.update(hs.prediction_errors(got["preds"], want["preds"],
                                        log_space))
        return out, all(r["ok"] for r in rows)

    for n, (seed, xs, y, weights, system) in enumerate(records):
        t0 = time.perf_counter()
        want = hs.reference_side(family, weights, s, traffic, config, xs, y,
                                 batch)
        row = dict(seed=seed, cell=cell_name)
        row["program"], row["program_correct"] = judged(system, want)
        first = dict(want, losses=want["losses"][:1])
        for operand in (operands if n < control_seeds else ()):
            low = hs.reference_side(family, weights, s, traffic, config, xs,
                                    y, batch, operand=operand, steps=1)
            row[operand], row[operand + "_correct"] = judged(low, first)
        if len(want["losses"]) > 1 and n < control_seeds:
            # the control of the later losses: Adam without bias correction
            wrong = hs.reference_side(
                family, weights, s, traffic, config, xs, y, batch,
                adam=dict(config["adam"], bias_correction=False))
            row["wrong_adam"], row["wrong_adam_correct"] = judged(
                dict(wrong, preds=want["preds"]), want)
        row["reference_losses"] = want["losses"]
        row["system_losses"] = system["losses"]
        row["seconds"] = time.perf_counter() - t0
        hs.emit(**row)
        rows_out.append(row)
    return rows_out


def _system_sides(hs, family, config, s, one, cell, seeds, batch):
    import jax
    import jax.numpy as jnp
    ff = family.build(config, s, cell["chips"], seeds[0])
    zero_opt = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    records = []
    for seed in seeds:
        t0 = time.perf_counter()
        xs, y = family.make_data(one, seed)
        weights = jax.device_get(family.make_weights(s, seed))
        family.install_weights(ff, weights)
        ff.opt_state = zero_opt(ff.opt_state)
        system, _ = hs.system_side(ff, xs, y, batch)
        records.append((seed, xs, y, weights, system))
        hs.emit(seed=seed, phase="system", losses=system["losses"],
                seconds=time.perf_counter() - t0,
                memory_stats=jax.devices()[0].memory_stats())
    hs.release(ff)
    return records


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the controls on the first N seeds only")
    args = ap.parse_args()
    rows = check_seeds(args.workload, args.seeds,
                       control_seeds=args.control_seeds)
    with_control = [r for r in rows if "fp8" in r]
    def extreme(fn, key):
        use = rows if key == "program" else with_control
        return {k: fn(r[key][k] for r in use) for k in use[0][key]}

    print(json.dumps(dict(
        summary=args.workload, seeds=len(rows),
        program_largest=extreme(max, "program"),
        control_fp8_smallest=extreme(min, "fp8"),
        bf16_reference_largest=extreme(max, "bf16"),
        wrong_adam_smallest=(extreme(min, "wrong_adam")
                             if "wrong_adam" in with_control[0] else None),
        program_all_correct=all(r["program_correct"] for r in rows),
        control_all_incorrect=not any(r["fp8_correct"]
                                      for r in with_control))))


if __name__ == "__main__":
    main()
