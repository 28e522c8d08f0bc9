#!/usr/bin/env python3
"""A cell's mechanism controls in one process: the PROGRAM built
otherwise, through the `program_*` size overrides its family reads, and
judged by the output check against the reference as the cell states it.

    python scripts/program_controls.py --workload <cell> --seed <n> \\
        --control program_gating=false --control program_sliding_window=4096

`benchmarks/seeds_check.check_seeds(cell, seeds, rehearsal=dict(sizes=...))`
does the same one control a call and computes the reference each time (two
minutes of a decoder cell's five); here the weights and the data of the
seed are made once, every control's program gives its predictions and
three losses and is released, and the reference runs once at the end with
the device to itself. A first row, `as_stated`, is the program as the
cell states it and has to come out correct; every control has to come
out NOT correct, by the comparison or by a row of the family's
`extra_checks` (named in `failed_by` alike). One JSON line a row; the
last line sums up. Not part of a benchmark run.

A control named `reference_*` (PR 58) is of the other kind, for a
mechanism that no published key switches: it alters the REFERENCE (the
family's `reference_kw` passes the key on to its reference's forward) and
the program as the cell states it is judged against that reference, its
predictions and first loss (no Adam steps: one forward of the reference
a control). It too has to come out NOT correct.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", action="append", default=[],
                    help="a size override, name=<JSON value>")
    ap.add_argument("--tiny", default=None,
                    help="JSON of size overrides for a CPU rehearsal")
    args = ap.parse_args()

    from benchmarks import harness as hs
    from benchmarks import manifest as mf
    manifest = mf.load_manifest(ROOT)
    cell, config, traffic = mf.find_cell(manifest, args.workload, ROOT)
    family = hs.load_by_path("families", config["family"], ROOT)
    hs.build_native(ROOT)
    import jax
    tiny = json.loads(args.tiny) if args.tiny else None
    if tiny is None:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("program_controls: needs a TPU (--tiny "
                             "rehearses)")
        from flexflow_tpu.utils.compile_cache import configure_compile_cache
        configure_compile_cache()
    stated = family.sizes(config, traffic, tiny)
    batch = stated["batch"]
    xs, y = family.make_data(dict(stated, steps_per_epoch=1), args.seed)
    weights = jax.device_get(family.make_weights(stated, args.seed))
    given = [(c, {c.split("=", 1)[0]: json.loads(c.split("=", 1)[1])})
             for c in args.control]
    controls = [("as_stated", {})] + [
        c for c in given if not c[0].startswith("reference_")]
    systems = []
    for name, override in controls:
        s = family.sizes(config, traffic, dict(tiny or {}, **override))
        ff = family.build(config, s, cell["chips"], args.seed)
        family.install_weights(ff, weights)
        faults = [c for c, ok, _ in family.extra_checks(
            ff, s, cell["chips"], tiny is None) if not ok]
        system, _ = hs.system_side(ff, xs, y, batch)
        hs.release(ff)
        systems.append((name, system, faults))
        hs.emit(phase="system", control=name, losses=system["losses"],
                extra_checks_failed=faults)
    want = hs.reference_side(family, weights, stated, traffic, config, xs, y,
                             batch)
    rows = []
    for name, system, faults in systems:
        judged = hs.compare(system, want, family.TOLERANCES)
        row = dict(control=name, seed=args.seed,
                   correct=all(r["ok"] for r in judged) and not faults,
                   **{r["name"]: r["value"] for r in judged},
                   failed_by=[r["name"] for r in judged
                              if not r["ok"]] + faults)
        hs.emit(**row)
        rows.append(row)
    for name, override in given:
        if not name.startswith("reference_"):
            continue
        altered = hs.reference_side(
            family, weights, family.sizes(config, traffic,
                                          dict(tiny or {}, **override)),
            traffic, config, xs, y, batch, steps=1)
        judged = hs.compare(systems[0][1], altered, family.TOLERANCES)
        row = dict(control=name, seed=args.seed,
                   correct=all(r["ok"] for r in judged),
                   **{r["name"]: r["value"] for r in judged},
                   failed_by=[r["name"] for r in judged if not r["ok"]])
        hs.emit(**row)
        rows.append(row)
    family.sizes(config, traffic, tiny)     # what the family keeps of them
    print(json.dumps(dict(
        summary=args.workload, seed=args.seed,
        limits=family.TOLERANCES, as_stated_correct=rows[0]["correct"],
        controls_all_incorrect=not any(r["correct"] for r in rows[1:]))))


if __name__ == "__main__":
    main()
