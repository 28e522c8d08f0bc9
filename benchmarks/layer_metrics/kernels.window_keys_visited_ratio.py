"""(query, key) pairs that the window ops' flash kernels work through
over those the mask leaves visible: the program's gauges
`attention/window_keys_visited` over `attention/window_keys_visible`
(both a head and sequence, forward and backward added, over the window
ops that ran flash; the first from the kernels' own tile ranges), as the
family kept them after the window (`observed["op_counters"]`). 1 would
be a kernel that visits no hidden key; a window narrower than the tiles
reads well above it. Where the program publishes no such gauges (an
older program, no window op) the reader returns nothing."""


def read(ctx):
    counters = (getattr(ctx["family"], "observed", None) or {}).get(
        "op_counters") or {}
    visited = counters.get("attention/window_keys_visited")
    visible = counters.get("attention/window_keys_visible")
    if not visited or not visible:
        return None
    return visited / visible
