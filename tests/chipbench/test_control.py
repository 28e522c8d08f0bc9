"""The output check's control, at a size a test run can hold: the
reference with float8 operands, put in the program's place, has to read
far above the reference with the configuration's bfloat16 operands, which
has to pass. On the chip the same tool (`benchmarks/seeds_check.py`) ran at the
cells' own sizes; PERF.md has those readings."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from rehearse import TINY  # noqa: E402


@pytest.mark.parametrize("cell,family,seeds", [
    ("bert_ae.s512_b32.1chip", "bert_ae", [11, 2 ** 31 + 12, 13]),
    ("inception_v3_ae.b256.1chip", "inception_v3_ae", [14]),
])
def test_fp8_control_fails_and_bf16_passes(cell, family, seeds):
    from benchmarks.harness import load_by_path
    from benchmarks.seeds_check import check_seeds
    rows = check_seeds(cell, seeds, rehearsal=dict(sizes=TINY[family]))
    assert len(rows) == len(seeds)
    fam = load_by_path("families", family)
    key = "log_nrmse" if getattr(
        fam, "PREDICTIONS_ARE_PROBABILITIES", False) else "nrmse"
    for row in rows:
        assert row["program_correct"], row
        # the limit was placed between the two readings at the cell's own
        # size on the chip; at this size the control has to stand as far
        # from the stated precision as it does there
        assert row["bf16"][key] < fam.TOLERANCES["pred_" + key]
        assert row["fp8"][key] > 5 * row["bf16"][key]
        assert row["fp8"][key] > 5 * row["program"][key]
