#!/usr/bin/env python3
"""The hand look behind where the attention op's per-head gate lives
(PR 41): the gate ALONE, forward and backward, at the laguna cell's two
shapes (8,192 positions, hidden 2048, 64 and 48 heads of 128), and the
two rotary forms beside it.

On the chip every piece is one jitted program, run five times under the
profiler; `<piece>_device_ms` is the median device time of its program
and `<piece>_device_ops` its ops by stem (`moe_combine_lab.device_ms`).
Each piece is `value_and_grad` over its inputs of `sum(f(...) * g)`, what
a train step runs of it:

- `gate.broadcast`: a = softplus(x w_gate) [B, S, H] float32, broadcast
  over the head's 128 lanes through a `[B, S, H, D]` view of the core's
  output (XLA writes the float32 broadcast and the view's copy);
- `gate.lanes_by_product`: what ships (`ops/attention.py` `_gated`): the
  same a laid along the lanes by a product with the 0/1 matrix
  `[H, H * D]` at precision `highest` (exact: every sum has one term),
  so that the multiply is one pass over `[B, S, H * D]` and the
  backward's 128-lane sum a product with the matrix's transpose;
- `no_gate`: the cast of the core's output that either form replaces;
- `rotary_whole` / `rotary_partial_yarn`: the two rotary forms on a
  `[B, S, H, D]` float32 query (`rotary_embedding`; `rotary_partial`
  over the first 64 lanes with YaRN's table), and two other bodies of
  the partial form: `rotary_partial.by_slices` (the rotated halves cut
  out of the head and laid end to end again) and
  `rotary_partial.by_product` (a lane's partner by a product with a
  signed permutation of the head's lanes at precision `highest`).

Those rotary pieces are the rotation ALONE, a `[B, S, H, D]` array in
and out. A train step pays more: the projection writes `[B, S, H*D]`
and the flash kernel reads `[B, S, H*D]`, XLA lays the 4-D view out
with its tile over (H, D), and every crossing is a copy of the whole
float32 array (PR 41's 1.90 ms for the product form was true alone; by
the bytes of the compiled op the full layer paid 7.4 ms for it). So the
pieces that decide anything are IN CONTEXT (PR 42), one whole attention
op, forward and backward, at a decoder cell's shape:

- `rotary.in_context.{whole,partial,norm_whole,whole_7_1}.view`: the
  projections, the heads' norm and the rotation over the `[B, S, H, D]`
  view (`rotary_embedding` / `rotary_partial`: the shipped form until PR
  42, and still every shape's the pass does not take), the K/V repeat,
  the flash kernels, the gate, the output projection;
- `....lanes`: the same op with norm and rotation as the one lane-dense
  pass `pallas_kernels.rotary_lanes` (what ships where the shapes
  allow);
- `....none`: the op with neither (the floor: what no pass could beat).

- `rotary_lanes.blocks.{plain_64,normed_8}.<rows>x<heads>`: the kernel
  ALONE, forward and backward, over other blocks than
  `pallas_kernels._rotary_block`'s (64 heads at 8,192 positions: 1.72 ms
  at 512 x 1, 1.245 at 512 x 4, which ships, 1.21 at 1024 x 8; my chip
  runs, PR 42); a block the scoped VMEM refuses is listed `refused`.

`whole` is the laguna cell's window layer (64 + 8 heads of 128, 8,192
positions, window 512), `partial` its full layer (48 + 8 heads, rotary
over 64 lanes with YaRN's table), `norm_whole` the sdar cell's op (8 + 1
heads, 16,384 positions under the block-diffusion mask, per-head RMS
norm, wrapped positions), `whole_7_1` the smallthinker cell's window
layer (7 + 1 heads, 16,384 positions, window 4096).

Prints one JSON line a shape and writes them to
`chiprun_out/gate_lab.json`. `--deviceless` compiles the in-context
pieces for a described v5e and reports `sync_gb`, the bytes (operands +
result) of the compiled program's synchronous top-level instructions,
and `f32_between_fusions`, the float32 arrays of S x H*D elements it
writes outside a fusion's body (`obs.inspect.arrays_between_fusions`).
`--tiny` runs small shapes wherever it is (a rehearsal: its times mean
nothing). Nothing here is a benchmark metric.

    python scripts/gate_lab.py [--tiny | --deviceless] [--only in_context]
"""

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from moe_combine_lab import device_ms   # noqa: E402  (this directory's)

YARN = dict(rope_type="yarn", factor=64, beta_fast=64, beta_slow=1,
            original_max_position_embeddings=4096,
            attention_factor=1.4158883083359672)


def pieces(seq, hidden, heads, d):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.attention import (gate_lanes, rotary_embedding,
                                            rotary_frequencies,
                                            rotary_partial)

    rs = np.random.RandomState(0)
    bf16 = jnp.bfloat16
    x = jnp.asarray(rs.randn(1, seq, hidden), bf16)
    w = jnp.asarray(0.02 * rs.randn(hidden, heads), jnp.float32)
    o, g = (jnp.asarray(rs.randn(1, seq, heads * d), bf16) for _ in range(2))

    def values(w, x):
        return jax.nn.softplus(jnp.dot(
            x.astype(jnp.float32), w, precision=jax.lax.Precision.HIGHEST))

    def broadcast(w, x, o):
        a = values(w, x)
        return (o.reshape(1, seq, heads, d).astype(jnp.float32)
                * a[..., None]).reshape(o.shape).astype(bf16)

    def by_product(w, x, o):
        return (o.astype(jnp.float32)
                * gate_lanes(values(w, x), d)).astype(bf16)

    def no_gate(w, x, o):
        return o.astype(jnp.float32).astype(bf16)

    def whole(q):
        return rotary_embedding(q, theta=10000.0, seq_axis=1)

    def partial(q):
        inv_freq, factor = rotary_frequencies(d // 2, 500000.0, YARN)
        return rotary_partial(q, inv_freq, rotary_dim=d // 2,
                              attention_factor=factor)

    def tables(r):
        inv_freq, factor = rotary_frequencies(r, 500000.0, YARN)
        angles = (jnp.arange(seq, dtype=jnp.float32)[:, None]
                  * inv_freq[None, :])
        return (jnp.cos(angles) * factor)[:, None, :], (
            jnp.sin(angles) * factor)[:, None, :]

    def partial_by_slices(q):
        r = d // 2
        cos, sin = tables(r)
        x1, x2, rest = q[..., :r // 2], q[..., r // 2:r], q[..., r:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                                rest], axis=-1)

    def partial_by_product(q):
        r = d // 2
        cos, sin = tables(r)
        one, zero = jnp.ones((seq, 1, d - r)), jnp.zeros((seq, 1, d - r))
        cos = jnp.concatenate([cos, cos, one], axis=-1)
        sin = jnp.concatenate([sin, sin, zero], axis=-1)
        turn = np.zeros((d, d), np.float32)      # partner = q @ turn
        for j in range(r // 2):
            turn[j + r // 2, j] = -1.0
            turn[j, j + r // 2] = 1.0
        partner = jnp.dot(q, jnp.asarray(turn),
                          precision=jax.lax.Precision.HIGHEST)
        return q * cos + partner * sin

    def graded(name, fn, args, cotangent):
        def run(*args):
            return jax.value_and_grad(
                lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cotangent),
                argnums=tuple(range(len(args))))(*args)
        run.__name__ = run.__qualname__ = name.replace(".", "_")
        return jax.jit(run), args

    q = jnp.asarray(rs.randn(1, seq, heads, d), jnp.float32)
    return {
        "gate.broadcast": graded("gate.broadcast", broadcast, (w, x, o), g),
        "gate.lanes_by_product": graded("gate.lanes_by_product", by_product,
                                        (w, x, o), g),
        "no_gate": graded("no_gate", no_gate, (w, x, o), g),
        "rotary_whole": graded("rotary_whole", whole, (q,),
                               g.reshape(q.shape)),
        "rotary_partial_yarn": graded("rotary_partial_yarn", partial, (q,),
                                      g.reshape(q.shape)),
        "rotary_partial.by_slices": graded(
            "rotary_partial.by_slices", partial_by_slices, (q,),
            g.reshape(q.shape)),
        "rotary_partial.by_product": graded(
            "rotary_partial.by_product", partial_by_product, (q,),
            g.reshape(q.shape)),
    }


# the decoder cells' rotary ops: sequence, model width, the op's properties
IN_CONTEXT = {
    "whole": (8192, 2048, dict(
        num_heads=64, num_kv_heads=8, causal=True, window=512, gate=True,
        rope_theta=10000.0)),
    "partial": (8192, 2048, dict(
        num_heads=48, num_kv_heads=8, causal=True, gate=True,
        rope_theta=500000.0, partial_rotary_factor=0.5, rope_scaling=YARN)),
    "norm_whole": (16384, 2048, dict(
        num_heads=8, num_kv_heads=1, block_diffusion=(8192, 4),
        rope_wrap=8192, qk_norm=True, rope_theta=1000000.0)),
    "whole_7_1": (16384, 2560, dict(
        num_heads=7, num_kv_heads=1, causal=True, window=4096,
        rope_theta=1500000.0)),
}


def _tiny(props):
    """The same op at the smallest sizes the flash kernels and the pass
    take (S a multiple of 128)."""
    small = dict(props, num_heads=4,
                 num_kv_heads=min(2, props["num_kv_heads"]))
    if "window" in props:
        small["window"] = 128
    if "block_diffusion" in props:
        small.update(block_diffusion=(128, 4), rope_wrap=128)
    return 256, 64, small


def in_context_pieces(name, seq, hidden, props):
    """name -> (function of (params, x, g), abstract arguments): one
    attention op's forward and backward in its three forms."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ffconst import OperatorType
    from flexflow_tpu.layer import Layer
    from flexflow_tpu.ops.base import OpContext, OpRegistry

    def op_of(rope, lanes):
        layer = Layer(OperatorType.MULTIHEAD_ATTENTION, "op", [])
        layer.properties.update(dict(props, embed_dim=hidden, head_dim=128,
                                     bias=False, rope=rope))
        if not rope:
            layer.properties.update(qk_norm=False, rope_wrap=0)
        op = OpRegistry.create(layer, [(1, seq, hidden)] * 3)
        if not lanes:
            # the lab steers the op to the form it compares against
            route = op.route
            op.route = lambda *a, **k: dataclasses.replace(
                route(*a, **k), rotary_in_lanes=False)
        return op

    out = {}
    for form, op in (("view", op_of(True, False)),
                     ("lanes", op_of(True, True)),
                     ("none", op_of(False, False))):
        def run(params, x, g, op=op):
            ctx = OpContext(training=True, compute_dtype=jnp.bfloat16)
            return jax.value_and_grad(lambda p, x: jnp.sum(
                op.forward(p, [x], ctx)[0].astype(jnp.float32) * g),
                argnums=(0, 1))(params, x)
        piece = f"rotary.in_context.{name}.{form}"
        run.__name__ = run.__qualname__ = piece.replace(".", "_")
        shapes = jax.eval_shape(op.init_params, jax.random.PRNGKey(0))
        x = jax.ShapeDtypeStruct((1, seq, hidden), jnp.bfloat16)
        out[piece] = (run, (shapes, x, x))
    return out


def block_pieces(tiny):
    """name -> (jitted function, arguments): the pass ALONE, forward and
    backward as two calls of one program, over blocks of rows x heads
    other than `pallas_kernels._rotary_block`'s: 64 heads at 8,192
    positions (plain), 8 heads at 16,384 with the norm."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops import pallas_kernels as pk

    rs = np.random.RandomState(0)
    interpret = pk.pallas_mode() == "interpret"
    out = {}
    for tag, seq, heads, normed in (("plain_64", 8192, 64, False),
                                    ("normed_8", 16384, 8, True)):
        if tiny:
            seq, heads = 256, 4
        x = jnp.asarray(rs.randn(1, seq, heads * 128), jnp.float32)
        g = jnp.asarray(rs.randn(1, seq, heads * 128), jnp.bfloat16)
        cos, sin = (jnp.asarray(rs.randn(seq, 128), jnp.float32)
                    for _ in range(2))
        scale = jnp.asarray(1 + 0.1 * rs.randn(128), jnp.float32)
        for rows in (256, 512, 1024, 2048):
            for per in (1, 2, 4, 8):
                if seq % rows or heads % per:
                    continue

                def run(x, g, cos, sin, scale, block=(rows, per),
                        normed=normed):
                    scale, eps = (scale, 1e-6) if normed else (None, None)
                    y = pk._rotary_lanes_call(
                        (x,), cos, sin, scale, 64, eps, jnp.bfloat16,
                        interpret, False, block)
                    back = pk._rotary_lanes_call(
                        (x, g) if normed else (g,), cos, sin, scale, 64,
                        eps, jnp.float32, interpret, True, block)
                    return y, back

                name = f"rotary_lanes.blocks.{tag}.{rows}x{per}"
                run.__name__ = run.__qualname__ = name.replace(".", "_")
                out[name] = (jax.jit(run), (x, g, cos, sin, scale))
    return out


_FREE = ("parameter", "tuple", "get-tuple-element", "bitcast", "constant")


def synchronous_bytes(hlo):
    """Bytes, operands + result, of the synchronous instructions of an
    optimized HLO text's ENTRY computation: what the program moves
    through memory outside its asynchronous copies, a kernel or fusion
    counted by what it reads and writes -> (total, the instructions
    (bytes, name, opcode), largest first)."""
    import re

    from flexflow_tpu.obs.inspect import _INSTRUCTION, shape_bytes

    sizes, rows, entry = {}, [], False
    for line in hlo.splitlines():
        if line.startswith("ENTRY "):
            entry = True
        elif entry and line.startswith("}"):
            break
        m = _INSTRUCTION.match(line) if entry else None
        if not m:
            continue
        name, result, opcode = m.groups()
        sizes[name] = shape_bytes(result)
        if opcode in _FREE or opcode.endswith(("-start", "-done")):
            continue
        operands = re.findall(r"%[\w.\-]+", line[m.end():].split(")")[0])
        rows.append((sizes[name] + sum(sizes.get(o, 0.0) for o in operands),
                     name, opcode))
    return sum(r[0] for r in rows), sorted(rows, reverse=True)


def in_context(opts):
    """One line a shape: the three forms of each, timed on the chip or
    (`--deviceless`) compiled for a described one and counted."""
    import jax
    import numpy as np

    from flexflow_tpu.obs.inspect import arrays_between_fusions
    from flexflow_tpu.ops import pallas_kernels as pk

    chip = None
    if opts.deviceless:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        pk.pallas_mode = lambda: "tpu"      # the live backend is the CPU
    lines = []
    for name, (seq, hidden, props) in IN_CONTEXT.items():
        if opts.tiny:
            seq, hidden, props = _tiny(props)
        line = dict(piece="rotary.in_context." + name, seq=seq,
                    hidden=hidden, heads=props["num_heads"],
                    kv_heads=props["num_kv_heads"],
                    device=("deviceless v5e" if chip
                            else jax.devices()[0].device_kind))
        jitted, outs = {}, {}
        for piece, (fn, shapes) in in_context_pieces(
                name, seq, hidden, props).items():
            if opts.only not in piece:
                continue
            if chip:
                hlo = jax.jit(fn).lower(*jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=chip),
                    shapes)).compile().as_text()
                total, rows = synchronous_bytes(hlo)
                written = {n for heads in {props["num_heads"],
                                           props["num_kv_heads"]}
                           for n in arrays_between_fusions(
                               hlo, "f32", seq * heads * 128)}
                line[piece] = dict(
                    sync_gb=round(total / 1e9, 3),
                    f32_between_fusions=[
                        f"{name} {opcode} {size / 1e6:.0f} MB"
                        for size, name, opcode in rows if name in written])
                continue
            rs = np.random.RandomState(0)
            args = jax.tree.map(lambda a: jax.numpy.asarray(
                0.02 * rs.randn(*a.shape), a.dtype), shapes)
            jitted[piece] = (jax.jit(fn), args)
            outs[piece.rsplit(".", 1)[1]] = jax.block_until_ready(
                jitted[piece][0](*args))
        if "lanes" in outs and "view" in outs:
            # the two forms are one mathematics: the value, and every
            # gradient's largest difference over its largest entry
            (a, da), (b, db) = outs["lanes"], outs["view"]
            line["lanes_vs_view"] = dict(
                value_rel=float(abs(a - b) / abs(b)),
                grads_rel={jax.tree_util.keystr(path): float(
                    abs(x.astype("float32") - y.astype("float32")).max()
                    / abs(y.astype("float32")).max())
                    for (path, x), y in zip(
                        jax.tree_util.tree_leaves_with_path(da),
                        jax.tree.leaves(db))})
        # a CPU trace has no device lane to read
        timed = {} if opts.tiny or chip else device_ms(jitted, stems=14)
        for piece, (ms, ops) in timed.items():
            line[piece + "_device_ms"] = round(ms, 3)
            line[piece + "_device_ops"] = ops
        print(json.dumps(line), flush=True)
        lines.append(line)
    return lines


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--deviceless", action="store_true")
    ap.add_argument("--only", default="", help="pieces whose name holds this")
    opts = ap.parse_args()
    if opts.deviceless:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if opts.deviceless:
        return in_context(opts)     # nothing else has a count to report
    if not opts.tiny and jax.default_backend() != "tpu":
        sys.exit("gate_lab.py times the pieces on a TPU; --tiny rehearses, "
                 "--deviceless compiles and counts")
    lines = in_context(opts)
    blocks = {name: piece for name, piece in block_pieces(opts.tiny).items()
              if opts.only in name}
    if blocks:
        line = dict(piece="rotary_lanes.blocks",
                    device=jax.devices()[0].device_kind, refused=[])
        for name, (fn, args) in list(blocks.items()):
            try:
                jax.block_until_ready(fn(*args))
            except Exception:      # the block outgrows the scoped VMEM
                line["refused"].append(name)
                del blocks[name]
        for name, (ms, ops) in ({} if opts.tiny
                                else device_ms(blocks)).items():
            line[name + "_device_ms"] = round(ms, 3)
        print(json.dumps(line), flush=True)
        lines.append(line)
    for heads in (64, 48):
        seq, hidden, d = (256, 64, 16) if opts.tiny else (8192, 2048, 128)
        jitted = {name: piece
                  for name, piece in pieces(seq, hidden, heads, d).items()
                  if opts.only in name}
        if not jitted:
            continue
        for fn, args in jitted.values():
            jax.block_until_ready(fn(*args))
        line = dict(seq=seq, hidden=hidden, heads=heads, head_dim=d,
                    device=jax.devices()[0].device_kind)
        # a CPU trace has no device lane to read
        timed = {} if opts.tiny else device_ms(jitted)
        line["pieces"] = sorted(jitted)
        for name, (ms, ops) in timed.items():
            line[name + "_device_ms"] = ms
            line[name + "_device_ops"] = ops
        print(json.dumps(line), flush=True)
        lines.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gate_lab.json", "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
