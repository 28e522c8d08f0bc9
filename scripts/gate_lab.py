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

Prints one JSON line a shape and writes them to
`chiprun_out/gate_lab.json`. `--tiny` runs small shapes wherever it is (a
rehearsal: its times mean nothing). Nothing here is a benchmark metric.

    python scripts/gate_lab.py [--tiny]
"""

import argparse
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


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    opts = ap.parse_args()
    import jax

    if not opts.tiny and jax.default_backend() != "tpu":
        sys.exit("gate_lab.py times the pieces on a TPU; --tiny rehearses")
    lines = []
    for heads in (64, 48):
        seq, hidden, d = (256, 64, 16) if opts.tiny else (8192, 2048, 128)
        jitted = pieces(seq, hidden, heads, d)
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
