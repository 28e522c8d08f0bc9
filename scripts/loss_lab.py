#!/usr/bin/env python3
"""The hand look behind `losses.target_log_probs` (PR 40): the head and
the loss ALONE, forward and backward, at the four decoder cells' shapes.

On the chip every piece is one jitted program, run `calls` times under
the profiler; `<piece>_device_ms` is the median device time of its
program and `<piece>_device_ops` its ops by stem. The head is
`ops/linear.py`'s forward (a bf16 product accumulated in float32, stored
as bf16), the weights the step's bf16 compute copy. The bodies of the
loss:

- `today`: what `losses.py` did until PR 40, kept HERE as the yardstick:
  `take_along_axis(log_softmax(logits.astype(float32)), ids)`, its
  backward by autodiff (a scatter-add into a zero-filled float32
  [rows, V], `log_softmax`'s transpose, a cast);
- `own_vjp`: what ships, `losses.target_log_probs`;
- `own_vjp_blocks`: the same function over blocks of `BLOCK_ROWS` rows
  by `lax.map` (ROADMAP S1b's wording);
- `own_vjp_select`: the target's logit as `sum(where(iota == id, l, 0))`
  beside the sum of the exponentials in place of the gather.

Each as `head+<body>.fwd` (x, w -> loss), `head+<body>.fwd_bwd`
(`value_and_grad` over x and w: what a train step runs) and
`<body>.loss_only.fwd_bwd` (the logits handed over, their gradient
out); `head.fwd` alone; and the head's backward alone, handed its
cotangent as stored: `head_bwd.as_shipped` (the transpose of
`y.astype(bf16)` makes both products take a float32 [rows, V] operand)
against `head_bwd.bf16_operand` (the cotangent as bf16 into both). The
chip's compiler folds that cast into the product's operand fetch: the
two compile to ONE program (`--deviceless`: equal but for an
instruction's number) and the profiler reports both under the first's
name, 7.73 / 16.89 / 6.69 / 5.71 ms at the four shapes, 2.03-2.07 times
`head.fwd` (my chip runs, PR 40).

Prints one JSON line a shape with `logits_pass_ms` (the bf16 logits read
once at the HBM's peak) and writes them to `chiprun_out/loss_lab.json`.
`--deviceless` compiles every piece for a described v5e and reports the
compiler's temporary bytes, scatters and float32 arrays of the logits'
shape between fusions; `--tiny` runs small shapes wherever it is (a
rehearsal: its times mean nothing). Nothing here is a benchmark metric.

    python scripts/loss_lab.py [--deviceless | --tiny] [--only own_vjp]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from moe_combine_lab import device_ms   # noqa: E402  (this directory's)

# hidden width, vocabulary rows held, rows of a step ([B, S] flattened as
# [1, rows]); `weighted`: labels [1, rows, 2] (id, weight) with a quarter
# of the weights zero; `parts`: the counters of `part_nll_sums` as well
SHAPES = {
    "nemotron3_nano_30b_a3b.s8192_b1": dict(E=2688, V=16384, rows=8192),
    "smallthinker_21b_a3b.s16384_b1": dict(E=2560, V=18992, rows=16384),
    "sdar_30b_a3b.s8192_b1": dict(E=2048, V=18992, rows=8192, weighted=True),
    "joyai_llm_flash.s4096_b1": dict(E=2048, V=16160, rows=8192,
                                     weighted=True, parts=("main", "mtp")),
}
TINY = {
    "tiny.sparse": dict(E=128, V=1000, rows=256),
    "tiny.weighted": dict(E=128, V=1000, rows=256, weighted=True,
                          parts=("main", "mtp")),
}
BLOCK_ROWS = 2048
HBM_BYTES_PER_S = 819e9   # v5e, as benchmarks/peaks.json has it


def pieces(s, block_rows):
    """name -> (function, argument names): every piece takes arrays only."""
    import jax
    import jax.numpy as jnp
    from flexflow_tpu import losses

    f32 = jnp.float32
    weighted, parts = s.get("weighted"), s.get("parts")

    def head(x, w):
        # ops/linear.py `Linear.forward` without bias or activation
        return jnp.dot(x, w, preferred_element_type=f32).astype(x.dtype)

    def today_logp(logits, ids):
        logp = jax.nn.log_softmax(logits.astype(f32), axis=-1)
        return jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]

    def blocks_logp(logits, ids):
        lead = ids.shape
        n = ids.size // block_rows
        out = jax.lax.map(
            lambda a: losses.target_log_probs(*a),
            (logits.reshape(n, block_rows, logits.shape[-1]),
             ids.reshape(n, block_rows)))
        return out.reshape(lead)

    @jax.custom_vjp
    def select_logp(logits, ids):
        return _select_fwd(logits, ids)[0]

    def _select_fwd(logits, ids):
        l32 = logits.astype(f32)
        m = jnp.max(logits, axis=-1, keepdims=True).astype(f32)
        hit = jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, logits.ndim - 1) == ids[..., None]
        lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(l32 - m), axis=-1))
        target = jnp.sum(jnp.where(hit, l32, 0.0), axis=-1)
        return target - lse, (logits, ids, lse)

    select_logp.defvjp(_select_fwd, losses._target_log_probs_bwd)

    def loss_of(logp_fn, once=True):
        """(logits, labels) -> (loss, the counters that leave the step);
        not ``once``: the part sums evaluate the log-probabilities again,
        two calls the compiler was trusted to merge (until PR 40)."""
        def loss(logits, labels):
            ids = losses.class_ids(logits, labels)
            logp = logp_fn(logits, ids)
            if not weighted:
                return -jnp.mean(logp), {}
            counted = {"target_positions": losses.target_positions(labels)}
            if parts:
                counted.update(losses.part_nll_sums(
                    logp if once else logp_fn(logits, ids), labels, parts))
            return losses.weighted_nll_mean(logp, labels), counted
        return loss

    bodies = {"today": loss_of(today_logp, once=False),
              "own_vjp": loss_of(losses.target_log_probs),
              "own_vjp_blocks": loss_of(blocks_logp),
              "own_vjp_select": loss_of(select_logp)}

    def head_bwd_as_shipped(x, w, ct):
        return jax.vjp(head, x, w)[1](ct)

    def head_bwd_bf16_operand(x, w, ct):
        return (jnp.dot(ct, w.T, preferred_element_type=f32).astype(x.dtype),
                jnp.dot(x[0].T, ct[0],
                        preferred_element_type=f32).astype(w.dtype))

    head.__name__ = "head_fwd"      # the profiler's program name
    out = {"head.fwd": (head, ("x", "w")),
           "head_bwd.as_shipped": (head_bwd_as_shipped, ("x", "w", "ct")),
           "head_bwd.bf16_operand": (head_bwd_bf16_operand,
                                     ("x", "w", "ct"))}
    for name, body in bodies.items():
        def fwd(x, w, labels, body=body):
            return body(head(x, w), labels)

        def fwd_bwd(x, w, labels, body=body):
            return jax.value_and_grad(
                lambda x, w: body(head(x, w), labels),
                (0, 1), has_aux=True)(x, w)

        def loss_only(logits, labels, body=body):
            return jax.value_and_grad(
                lambda l: body(l, labels), has_aux=True)(logits)

        # the profiler's program names come from `__name__`
        tag = name.replace(".", "_")
        fwd.__name__ = f"{tag}_fwd"
        fwd_bwd.__name__ = f"{tag}_fwd_bwd"
        loss_only.__name__ = f"{tag}_loss_only"
        out[f"head+{name}.fwd"] = (fwd, ("x", "w", "labels"))
        out[f"head+{name}.fwd_bwd"] = (fwd_bwd, ("x", "w", "labels"))
        out[f"{name}.loss_only.fwd_bwd"] = (loss_only, ("logits", "labels"))
    return out


def make_arguments(s):
    """The stream N(0, 1) and the head N(0, 0.02) as the cells make them
    (logits of standard deviation about 1), ids uniform; a weighted
    shape's weights uniform on (0, 2) with a quarter of them zero."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    rows, bf16 = s["rows"], jnp.bfloat16
    x = jax.random.normal(ks[0], (1, rows, s["E"]), bf16)
    w = (0.02 * jax.random.normal(ks[1], (s["E"], s["V"]))).astype(bf16)
    ids = jax.random.randint(ks[2], (1, rows), 0, s["V"])
    labels = ids
    if s.get("weighted"):
        weight = jnp.where(jax.random.uniform(ks[3], (1, rows)) < 0.25, 0.0,
                           2 * jax.random.uniform(ks[4], (1, rows)))
        labels = jnp.stack([ids.astype(jnp.float32), weight], axis=-1)
    logits = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(bf16)
    return dict(x=x, w=w, labels=labels, logits=logits,
                ct=(logits * 1e-4).astype(bf16))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--deviceless", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", default="", help="pieces whose name holds this")
    ap.add_argument("--calls", type=int, default=5)
    opts = ap.parse_args()
    if opts.deviceless:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from flexflow_tpu.obs.inspect import arrays_between_fusions, scatters_in

    if opts.deviceless:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif not opts.tiny and jax.devices()[0].platform != "tpu":
        sys.exit("loss_lab: no TPU here (try --deviceless or --tiny)")

    out = {}
    for cell, s in (TINY if opts.tiny else SHAPES).items():
        line = dict(cell=cell, device=("deviceless v5e" if opts.deviceless
                                       else jax.devices()[0].device_kind),
                    logits_pass_ms=round(
                        2 * s["rows"] * s["V"] / HBM_BYTES_PER_S * 1e3, 3))
        arrays = (jax.eval_shape(lambda: make_arguments(s))
                  if opts.deviceless else make_arguments(s))
        jitted = {}
        for name, (fn, names) in pieces(
                s, 64 if opts.tiny else BLOCK_ROWS).items():
            if opts.only not in name:
                continue
            if opts.deviceless:
                compiled = jax.jit(fn).lower(*(
                    jax.ShapeDtypeStruct(arrays[n].shape, arrays[n].dtype,
                                         sharding=chip)
                    for n in names)).compile()
                hlo = compiled.as_text()
                line[name] = dict(
                    temp_mb=round(
                        compiled.memory_analysis().temp_size_in_bytes / 1e6),
                    scatters=len(scatters_in(hlo)),
                    f32_logits_between_fusions=len(arrays_between_fusions(
                        hlo, "f32", s["rows"] * s["V"])))
            else:
                jitted[name] = (jax.jit(fn), [arrays[n] for n in names])
                jax.block_until_ready(jitted[name][0](*jitted[name][1]))
        if jitted and "head+today.fwd_bwd" in jitted:
            # the bodies agree: the loss to a float32 unit, the gradients
            # to a unit of their dtype (tests/test_losses.py holds them
            # to it; here only that the lab times the same mathematics)
            want = jitted["head+today.fwd_bwd"][0](
                *jitted["head+today.fwd_bwd"][1])
            for name, (fn, args) in jitted.items():
                if name.startswith("head+own") and name.endswith("fwd_bwd"):
                    got = fn(*args)
                    line[name + "_loss_rel_to_today"] = float(
                        abs(got[0][0] - want[0][0]) / abs(want[0][0]))
        # (the CPU's profiler has no device plane: `--tiny` times nothing)
        timed = (device_ms(jitted, opts.calls)
                 if jitted and jax.devices()[0].platform == "tpu" else {})
        for name, (ms, ops) in timed.items():
            line[name + "_device_ms"] = round(ms, 3)
            line[name + "_device_ops"] = ops
        print(json.dumps(line), flush=True)
        out[cell] = line
    if not opts.deviceless and not opts.tiny:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/loss_lab.json", "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
