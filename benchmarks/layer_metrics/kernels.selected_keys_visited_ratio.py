"""(query, key) pairs that the main attention's flash kernels work
through over those the indexers kept: the program's counters
`attention/visited_pairs` (a head, forward and backward added, from the
kernels' own tile ranges less the tiles their per-tile summaries let
them skip) over twice `attention/selected_pairs` (counted on the device
from the mask itself), as the family kept them after the window
(`observed["op_counters"]`). 1 would be a kernel that visits no pair a
query did not keep; with seeded weights the kept keys lie scattered, no
tile is empty and the ratio is that of the causal triangle to the kept
pairs. `kernels.window_keys_visited_ratio` is the model. Where the
program publishes no such counters the reader returns nothing."""


def read(ctx):
    counters = (getattr(ctx["family"], "observed", None) or {}).get(
        "op_counters") or {}
    visited = counters.get("attention/visited_pairs")
    selected = counters.get("attention/selected_pairs")
    if not visited or not selected:
        return None
    return visited / (2.0 * selected)
