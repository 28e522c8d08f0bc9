"""Share of its roofline that the read and write passes of the
hyper-connections reach: the least time for a step's passes over the
residual streams, forward and backward, over the device time a step of
the events whose `op_name` holds `jit(hc_read)` or `jit(hc_write)`, read
through the join table the program writes (`benchmarks/step_parts.py`).

The count is this file's own, from the cell's shapes, of what the two
passes MUST move, whatever implements them (T positions a step, n
streams of C lanes, bfloat16; a sublayer's maps and products are a few
hundred bytes a position and are left out, as are the FLOPs, 24
multiply-adds a lane of C):
    read, forward    the stream in, the branch's input out   (n + 1) C
    write, forward   the stream and the branch's output in,
                     the new stream out                      (2 n + 1) C
    write, backward  the new stream's cotangent, the stream and the
                     output in (the maps' gradients are their inner
                     products), the stream's and the output's
                     cotangents out                          (3 n + 2) C
    read, backward   the stream, the two cotangents of it and of the
                     branch's input in, the stream's cotangent
                     out                                     (3 n + 1) C
(9 n + 5) C elements a position and sublayer, 2 bytes each: 293,888
bytes at n = 4, C = 3584. A pass that reads an operand twice (phi's
gradient is a product that reads the stream once more), keeps a float32
copy or splits into several fusions costs time and adds no work, so it
lowers the share and can never lift it over 100. Where the program has
no such scopes or the chip's peaks are unknown the reader returns
nothing."""

from benchmarks import step_parts

SCOPES = ("hc_read", "hc_write")


def step_bytes(s):
    """Bytes the read and write passes of one train step must move."""
    n, c = s["hc_mult"], s["hidden_size"]
    sublayers = 2 * (s["num_hidden_layers"] + s["num_nextn_predict_layers"])
    return 2 * s["batch"] * s["seq"] * sublayers * (9 * n + 5) * c


def read(ctx):
    table = step_parts.find_table(ctx, __file__)
    peaks = ctx["counters"]["peaks"]
    sizes = ctx["counters"].get("sizes") or {}
    if not table or not peaks or not sizes.get("hc_mult"):
        return None
    inside = {n: dict(part="hc_passes", direction=row["direction"])
              for n, row in table.items()
              if any(f"jit({scope})" in row["op_name"] for scope in SCOPES)}
    got = step_parts.reduce(ctx["devices"], inside) if inside else None
    seconds = sum(got["ms_a_step"].values()) / 1e3 if got else 0.0
    if not seconds:
        return None
    return 100.0 * step_bytes(sizes) / peaks["hbm_bytes_per_s"] / seconds
