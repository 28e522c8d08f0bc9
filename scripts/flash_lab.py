#!/usr/bin/env python3
"""The blocked flash kernels alone, on the chip (PR 35).

At the shapes of the three decoder cells, forward (`flash_fwd`) and
K-blocked backward (`flash_bwd_blocked`) each in a program of its own,
bf16, ten calls after a warm-up inside one profiler trace; a line gives
the kernel's device time a call from the trace's longest op. Three
programs a shape:

- `split`: what ships: only the tiles that hold a hidden pair run the
  masked body (`pallas_kernels._k_split` / `_q_split`);
- `every_tile_masked`: the kernels before PR 35 (but for the backward's
  sums in scratch), every visited tile masked and each range one loop,
  made here by classing every sub-range edge;
- `every_tile_masked+peel`: the same with the forward's last chunk in
  straight-line code after the loop, which tells what the unmasked body
  is worth from what the peeled chunk is;

and under the block-diffusion mask both at K blocks of 512 and of 1024
(`_seq_block` caps them at 512 there). The latent-attention shape (PR 39:
32 heads of 128 + 64 / 128 at 4,096 positions, the two-part score with the
ONE rotated key a position) runs `split` twice: as it ships, and
`split, no rotated part` (the same kernels on the 128-wide parts alone),
so that the difference is what the second product and its operands cost.
The narrow-window shape (PR 41: 64 heads of 128 at 8,192 positions under
a window of 512, half of a K chunk of 1024) runs `split`, the chunk loop
(`one_span` held to None), at K blocks of 1024 (what `_seq_block` gave
before it took the block from the window), 512 (what shipped until PR
46) and 256, and then `one_span` (PR 46: a block's reachable positions
as ONE tile, no loop and no running softmax) at blocks of 256, 128 and
512 rows (forward and backward alike in a line), the span following from
the window (768, 640, 1024), over tiles a grid step x tiles a loop
iteration (`_span_tiles`; a whole head a step and four an iteration
ship: 32 x 4 at 256 rows forward, 64 x 4 at 128 backward), and last
`one_span as shipped`, nothing held. The same at a window of 768
(`laguna.narrow_window_768`), the upper end of the rule: the chunk loop
as `_seq_block` cuts it (chunks of 512) against spans of 1024 and 896.

Every chunk-loop shape (all but the narrow windows; since PR 51 also
`ouro.full`, 16 heads of 128 at 4,096 positions, and `lfm2.full`, 32 : 8
heads of 64 at 16,384) then runs the `super_block` axis (PR 51;
`pallas_kernels.super_block` held): the forward at Q blocks a grid step x
Q blocks a loop iteration, 1 x 1 the parent's kernel, the sub-tiles whole
or `trimmed` (a sub-tile takes the part of a chunk it can see any of),
against the parent's backward; the backward with the chunks at a K block's
own positions (and a whole window ahead) in 1, 2 or 4 sub-blocks, each
against the queries that see it, against the parent's forward; and
`super_block as shipped`, nothing held. A line of that axis times the
direction under test alone. `--programs super_block` runs that axis alone
(any comma-separated parts of programs' names). A form the chip's compiler
refuses is a line with an `error`.

`--only grouped` (PR 43) times ONE WHOLE ATTENTION OP instead, forward
and backward (`value_and_grad` over its parameters and input, as a train
step runs it), at the six grouped-query shapes of the decoder cells
(laguna's 64 : 8 window and 48 : 8 full ops, sdar's 8 : 1, smallthinker's
7 : 1, nemotron's 4 : 1, and since PR 47 lfm2's 32 : 8 at heads of 64
with the heads' norm), in two forms: `repeated` (K and V repeated to
[B, S, H*D] ahead of the kernels, the shipped form until PR 43, made
here by holding the op's route to `grouped_kv=False`) and `grouped` (the
kernels read K and V at the KV heads and add a group's dK / dV up in
their resident float32 panel: what ships), and where the shipped kernels
take the one-span form (laguna's window op) a third, `grouped_chunks`:
grouped keys with `one_span` held to None, the chunk loop of PR 41. At
heads of 64 (lfm2) one more, `repeated_view`: the repeat AND the heads'
norm and rotary over the [B, S, H, 64] view, what shipped until PR 47
(`grouped_kv` and `rotary_in_lanes` both held False). A
line holds the forms'
device ms, their ops by stem, and the largest difference of the value
and of every gradient between them (`grouped_vs_repeated`).

Prints one JSON line a measurement and writes them to
`chiprun_out/flash_lab.json`. Nothing here is a benchmark metric.

    python scripts/flash_lab.py [--tiny] [--only <part of a shape's name>]
                                [--programs <part of a program's name>,...]
    python scripts/flash_lab.py --only grouped[.<part of an op's name>]

`--tiny` is the CPU rehearsal (short sequences, the kernels interpreted,
no device in the trace, so `device_ms` is null).
"""

import argparse
import dataclasses
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# heads of 128, positions, causal, window, block diffusion
SHAPES = {
    "sdar.block_diffusion": (8, 16384, False, 0, (8192, 4)),
    "smallthinker.window": (7, 16384, True, 4096, None),
    "smallthinker.full": (7, 16384, True, 0, None),
    "nemotron.full": (4, 8192, True, 0, None),
    "joyai.latent": (32, 4096, True, 0, None),
    "laguna.narrow_window": (64, 8192, True, 512, None),
    "laguna.narrow_window_768": (64, 8192, True, 768, None),
    "laguna.full": (48, 8192, True, 0, None),
    "ouro.full": (16, 4096, True, 0, None),
    # heads of 64, two a lane block, four query heads a KV head
    "lfm2.full": (32, 16384, True, 0, None, dict(head_dim=64, kv_heads=8)),
}
ROPE_DIM = 64      # the rotated lanes of a `latent` shape's query and key
REPS = 10


def every_tile_masked(ranges, forward, peel=False):
    """The split of the kernels before PR 35 (every visited tile masked,
    each range one loop); with ``peel`` the forward's last chunk is cut
    off its loop into straight-line code, as the shipped split has it."""
    def split(x0, blk_a, blk_b, s, causal, window, block_diffusion=None):
        masked = causal or block_diffusion is not None
        cut = [(lo, hi, masked) for lo, hi in ranges(
            x0, blk_a, blk_b, s, causal, window, block_diffusion)]
        if peel and masked:
            lo, hi, _ = cut.pop()
            cut += [(lo, hi - 1, True), (hi - 1, hi, True)]
        return (tuple(cut), peel and masked) if forward else tuple(cut)
    return split


# `super_block` (PR 51), the chunk-loop kernels' blocks a grid step. The
# forward: (Q blocks a grid step, Q blocks a loop iteration, whether the
# peeled last chunk's sub-tiles stop at their own last query); 1 x 1 is
# the parent's kernel.
FORWARD_FORMS = ((1, 1, False), (2, 2, False), (4, 4, False), (4, 2, False),
                 (4, 1, False), (8, 4, False), (16, 4, False),
                 (16, 1, False), ("head", 4, False), (4, 4, True),
                 (16, 4, True))
# The backward: the sub-blocks of a K block at its own chunk (the
# diagonal), each against the queries from its first key on; 1 is the
# parent's kernel.
BACKWARD_FORMS = (1, 2, 4)


def super_block_variants(seq, bd, tiny):
    """(program, forward form | None, backward form | None) a line: each
    direction's forms against the other's parent, then what ships."""
    from flexflow_tpu.ops import pallas_kernels as pk

    rows = pk._q_block(seq, bd)
    most = pk._seq_block(seq, bd) // rows
    out = []
    for tiles, chains, trim in FORWARD_FORMS:
        if tiles == "head":
            tiles = (bd[0] if bd else seq) // rows
        if chains > most or (trim and chains < most) or (tiny and tiles > 8):
            continue
        line = (f"super_block forward {tiles} x {chains}"
                + (" trimmed" if trim else ""), (tiles, chains, trim), None)
        if line not in out:
            out.append(line)
    out += [(f"super_block backward, diagonal x {subs}", None, subs)
            for subs in BACKWARD_FORMS]
    return out + [("super_block as shipped", None, None)]


def variants(name, window, bd, tiny):
    """(program, K block held | None as `_seq_block` gives it, (block,
    span, tiles a grid step) of the one-span form | None) a line of the
    shape ``name``."""
    if ".narrow_window" in name:
        blocks = (1024, 512, 256) if name.endswith(".narrow_window") else (
            None,)
        # rows a tile -> tiles a grid step
        # rows a tile -> (tiles a grid step, tiles a loop iteration)
        rows = {256: ((1, 1), (8, 1), (16, 1), (8, 2), (16, 2), (16, 4),
                      (32, 4), (8, 8)),
                128: ((1, 1), (32, 2), (16, 4), (32, 4), (64, 4), (16, 8)),
                512: ((1, 1), (16, 2))}
        if tiny:
            blocks = (b and b // 2 for b in blocks)
            rows = {256: ((1, 1), (4, 2), (8, 1)), 128: ((4, 4),)}
        spans = [(b, -(-(window + b - 1) // 128) * 128, n)
                 for b, steps in rows.items() for n in steps]
        return [("split", b, None) for b in blocks] + [
            ("one_span", None, one) for one in spans if one[1] <= 1024] + [
            ("one_span as shipped", None, None)]
    if name.endswith(".latent"):
        return [("split", None, None), ("split, no rotated part", None, None)]
    return [(program, blk, None) for blk in ((512, 1024) if bd else (None,))
            for program in ("every_tile_masked", "every_tile_masked+peel",
                            "split")]


def kernel_ms(fn, args, interpret):
    """Device ms a call of the flash kernel in ``fn``, from a trace of
    REPS calls (None where the trace holds no device)."""
    import jax
    from benchmarks import trace_reduce as tr

    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(REPS):
                out = fn(*args)
            jax.block_until_ready(out)
        if interpret:
            return None
        dev = tr.load_xplane(tr.newest_xplane(d))[0]
    # alone in its program the kernel's event carries the program's name,
    # not the kernel's: the op that took the longest is the kernel (the
    # backward's other op is dQ's cast, a fortieth of it)
    by_op = {}
    for name, _, dur in dev.lines["XLA Ops"]:
        by_op.setdefault(name, []).append(dur)
    found = max(by_op.values(), key=sum)
    assert len(found) == REPS, {n: len(d) for n, d in by_op.items()}
    return 1e3 * sum(found) / REPS


def grouped_op_lines(tiny, only=""):
    """`--only grouped`: one line a grouped-query attention op of the
    decoder cells, repeated against grouped keys (`--only grouped.lfm2`:
    the ops whose name holds what follows the dot)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from gate_lab import IN_CONTEXT, _tiny
    from moe_combine_lab import device_ms

    from flexflow_tpu.ffconst import OperatorType
    from flexflow_tpu.layer import Layer
    from flexflow_tpu.ops import pallas_kernels as pk
    from flexflow_tpu.ops.base import OpContext, OpRegistry

    shipped = pk.one_span
    ops = {"laguna.window_64_8": IN_CONTEXT["whole"],
           "laguna.full_48_8": IN_CONTEXT["partial"],
           "sdar.block_diffusion_8_1": IN_CONTEXT["norm_whole"],
           "smallthinker.window_7_1": IN_CONTEXT["whole_7_1"],
           "nemotron.full_4_1": (8192, 2688, dict(
               num_heads=4, num_kv_heads=1, causal=True, rope=False)),
           "lfm2.full_32_8": (16384, 2048, dict(
               num_heads=32, num_kv_heads=8, head_dim=64, causal=True,
               qk_norm=True, rope_theta=1000000.0))}
    lines = []
    for name, (seq, hidden, props) in ops.items():
        if only not in name:
            continue
        if tiny:
            seq, hidden, props = _tiny(props)
            if "window" in props:   # past the whole-tile kernels: one span
                seq = 1536
            elif props.get("head_dim") == 64:   # the chunk loop, as the cell
                seq = 1280
        jitted, outs = {}, {}
        for form, held in FORMS.items():
            if form == "repeated_view" and props.get("head_dim") != 64:
                continue    # at 128 lanes the pass has no shape rule to hold
            layer = Layer(OperatorType.MULTIHEAD_ATTENTION, "op", [])
            layer.properties.update(dict(
                dict(rope=True, head_dim=128), **props, embed_dim=hidden,
                bias=False))
            op = OpRegistry.create(layer, [(1, seq, hidden)] * 3)
            if held:
                route = op.route
                op.route = lambda *a, route=route, held=held, **k: (
                    dataclasses.replace(route(*a, **k), **held))

            def run(params, x, g, op=op):
                ctx = OpContext(training=True, compute_dtype=jnp.bfloat16)
                return jax.value_and_grad(lambda p, x: jnp.sum(
                    op.forward(p, [x], ctx)[0].astype(jnp.float32) * g),
                    argnums=(0, 1))(params, x)
            run.__name__ = run.__qualname__ = (
                f"grouped_{name}_{form}".replace(".", "_"))
            rs = np.random.RandomState(0)
            shapes = jax.eval_shape(op.init_params, jax.random.PRNGKey(0))
            x = jax.ShapeDtypeStruct((1, seq, hidden), jnp.bfloat16)
            args = jax.tree.map(lambda a: jnp.asarray(
                0.02 * rs.randn(*a.shape), a.dtype), (shapes, x, x))
            if form == "grouped_chunks":
                if not op.route({}, True).one_span:
                    continue    # the shipped kernels are the chunk loop
                pk.one_span = lambda *a, **k: None
            try:     # the first call traces: the form is held around it
                jitted[form] = (jax.jit(run), args)
                outs[form] = jax.block_until_ready(jitted[form][0](*args))
            finally:
                pk.one_span = shipped
            assert op._route.grouped_kv == ("repeated" not in form) and (
                op._route.rotary_in_lanes == (
                    props.get("rope", True) and form != "repeated_view")), (
                        name, form, op._route)
        def against(other):
            """The largest difference of the shipped form's value and of
            each of its gradients from ``other``'s."""
            (a, da), (b, db) = outs["grouped"], outs[other]
            return dict(
                value_rel=float(abs(a - b) / abs(b)),
                grads_rel={jax.tree_util.keystr(path): float(
                    abs(x.astype("float32") - y.astype("float32")).max()
                    / abs(y.astype("float32")).max())
                    for (path, x), y in zip(
                        jax.tree_util.tree_leaves_with_path(da),
                        jax.tree.leaves(db))})

        line = dict(
            shape="grouped." + name, seq=seq, hidden=hidden,
            heads=props["num_heads"], kv_heads=props["num_kv_heads"],
            device=jax.devices()[0].device_kind,
            grouped_vs_repeated=against("repeated"))
        if "grouped_chunks" in outs:
            line["one_span_vs_chunks"] = against("grouped_chunks")
        if "repeated_view" in outs:
            line["grouped_vs_repeated_view"] = against("repeated_view")
        # a CPU trace has no device lane to read
        # the forward's kernel apart from the backward's where causal
        for form, (ms, by_stem) in ({} if tiny else device_ms(
                jitted, stems=13, whole=("flash_full",))).items():
            line[form + "_device_ms"] = round(ms, 3)
            line[form + "_device_ops"] = by_stem
        print(json.dumps(line), flush=True)
        lines.append(line)
    return lines


# form -> the fields of the op's route held (nothing: what ships)
FORMS = {"repeated": dict(grouped_kv=False), "grouped": {},
         "grouped_chunks": {},
         "repeated_view": dict(grouped_kv=False, rotary_in_lanes=False)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--programs", default="",
                    help="only the programs whose name holds one of these, "
                    "comma-separated")
    args = ap.parse_args()
    tiny, only, programs = args.tiny, args.only, args.programs
    if tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # the whole op of `--only grouped` asks `pallas_mode()` for its core
        os.environ.setdefault("FLEXFLOW_TPU_PALLAS", "interpret")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flexflow_tpu.ops import pallas_kernels as pk

    if not tiny and jax.default_backend() != "tpu":
        sys.exit("flash_lab.py times the kernels on a TPU; --tiny rehearses")
    shipped = {name: getattr(pk, name) for name in (
        "_k_split", "_q_split", "_seq_block", "one_span", "_span_tiles",
        "super_block")}

    def restore():
        for name, fn in shipped.items():
            setattr(pk, name, fn)

    lines = []
    if only and (only in "grouped" or only.startswith("grouped.")):
        lines = grouped_op_lines(tiny, only[len("grouped."):])
    for name, (heads, seq, causal, window, bd, *wide) in SHAPES.items():
        if only not in name:
            continue
        head_dim, kv_heads = 128, None
        if wide:
            head_dim, kv_heads = wide[0]["head_dim"], wide[0]["kv_heads"]
        if tiny:
            seq, window, bd = 2048, window // 8, bd and (1024, 4)
            heads = min(heads, 4)
            kv_heads = kv_heads and 2
        rs = np.random.RandomState(0)
        q, k, v, do = (jnp.asarray(rs.randn(1, seq, n * head_dim),
                                   jnp.bfloat16)
                       for n in (heads, kv_heads or heads,
                                 kv_heads or heads, heads))
        rope = (jnp.asarray(rs.randn(1, seq, heads * ROPE_DIM), jnp.bfloat16),
                jnp.asarray(rs.randn(1, seq, ROPE_DIM), jnp.bfloat16))
        latent = name.endswith(".latent")
        lines_of = [(program, blk, one, None, None)
                    for program, blk, one in variants(name, window, bd, tiny)
                    if not wide]     # PR 35's programs: heads of 128
        if ".narrow_window" not in name:
            lines_of += [(program, None, None, forward, backward)
                         for program, forward, backward
                         in super_block_variants(seq, bd, tiny)]
        for program, blk, one, forward, backward in lines_of:
            if not any(part in program for part in programs.split(",")):
                continue
            restore()
            if program.startswith("every_tile_masked"):
                pk._k_split = every_tile_masked(
                    pk._k_ranges, True, peel="peel" in program)
                pk._q_split = every_tile_masked(pk._q_ranges, False)
                # PR 35's kernels: one block a grid step, whole tiles
                # (a sub-tile's part of a chunk goes by the split's order)
                pk.super_block = lambda *a, **k: ((1, 1, False), 1)
            if blk:
                pk._seq_block = (lambda s, block_diffusion=None,
                                 window=0, b=blk: b)
            # the form under test, held (`one` None: the chunk loop)
            if ".narrow_window" in name and program != "one_span as shipped":
                pk.one_span = lambda *a, held=one and (one[:2],) * 2, **k: held
                pk._span_tiles = lambda s, blk, n=one and one[2]: n
            if program.startswith("super_block") and (forward or backward):
                # one direction's form against the other's parent
                pk.super_block = lambda *a, held=(
                    forward or (1, 1, False), backward or 1), **k: held
            mask = dict(window=window, block_diffusion=bd,
                        num_kv_heads=kv_heads)
            if latent and program != "split, no rotated part":
                mask["rope"] = rope
            fwd = jax.jit(lambda q, k, v: pk._flash_fwd(
                q, k, v, heads, causal, tiny, **mask))
            bwd = jax.jit(lambda q, k, v, o, lse, do: pk._flash_bwd(
                q, k, v, o, lse, do, heads, causal, tiny, **mask))
            line = dict(
                shape=name, heads=heads, seq=seq, window=window,
                program=program,
                k_block=pk._seq_block(seq, bd, window),
                one_span=pk.one_span(seq, causal, window, bd),
                tiles_a_step=one[2] if one else pk.one_span(
                    seq, causal, window, bd) and pk._span_tiles(seq, 128),
                super_block=pk.super_block(seq, window, bd),
                device=jax.devices()[0].device_kind)
            try:    # a form the chip's compiler refuses is a row too
                o, lse = fwd(q, k, v)
                line.update(
                    pairs_visited=pk.visited_pairs(seq, causal, window, bd),
                    pairs_visible=2 * pk.visible_pairs(seq, causal, window)
                    if causal else None,
                    tiles_visited=pk.kv_blocks(seq, causal, window, bd)[0],
                    tiles_masked=pk.kv_blocks_masked(seq, causal, window, bd),
                    last_chunk_peeled=pk._k_split(
                        0, pk._q_block(seq, bd),
                        pk._seq_block(seq, bd, window), seq,
                        causal, window, bd)[1])
                if not backward:
                    line["forward_device_ms"] = kernel_ms(fwd, (q, k, v), tiny)
                if not forward:
                    line["backward_device_ms"] = kernel_ms(
                        bwd, (q, k, v, o, lse, do), tiny)
            except Exception as e:  # noqa: BLE001: the compiler's, any kind
                line["error"] = f"{type(e).__name__}: {e}"[-600:]
            print(json.dumps(line), flush=True)
            lines.append(line)
    restore()
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_lab.json", "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
