"""Share of the traced train steps' device-busy time in the expert layers
of a model WITHOUT a shared expert (router, the routing sort, the
grouped products over the held experts' rows, the combine; forward and
backward), by `layers.moe_share_pct`'s rule: its reader, loaded from the
file beside this one, run on this cell (that metric's own list of cells
is the accepted benchmark's and stays as it is). That reader takes an
instruction's `op_name` from what the family kept of a second lowering
of the step (`observed["scopes"]`); this cell's family lowers nothing a
second time, so the names come from the join table the program itself
wrote (`benchmarks/step_parts.py`), which holds the same pairs. Where
there is no table, or that reader finds nothing, this one returns
nothing."""

import importlib.util
import os
import types

from benchmarks import step_parts


def _moe_share_reader():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "layers.moe_share_pct.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics.layers_moe_share_pct", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read(ctx):
    table = step_parts.find_table(ctx, __file__)
    if not table:
        return None
    names = {n: row["op_name"] for n, row in table.items()}
    return _moe_share_reader()(dict(ctx, family=types.SimpleNamespace(
        observed={"scopes": names})))
