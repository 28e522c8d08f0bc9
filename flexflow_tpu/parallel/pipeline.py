"""Pipeline parallelism: SPMD GPipe / circular pipelines over a 'pipe' axis.

New executing scope vs the reference, where pipeline parallelism exists
only as an enum value (`/root/reference/include/flexflow/ffconst.h:153`
OP_PIPELINE, with no runtime behind it).

TPU-native design (the MaxText/praxis recipe): a model whose body is R
identical repeated blocks stacks each block's parameters on a leading
[R, ...] axis sharded over the 'pipe' mesh axis. Under ``shard_map``
every device holds R/S blocks' weights; microbatch activations flow
stage-to-stage with ``jax.lax.ppermute`` over the pipe ring.

Two schedules:

* ``gpipe`` — each stage holds k = R/S *consecutive* blocks and runs all
  of them per tick. T = M + S - 1 ticks for M microbatches; bubble
  fraction (S-1)/T.
* ``circular`` — blocks are assigned round-robin (stage s holds blocks
  s, s+S, s+2S, ...) and each stage runs ONE block per tick; a
  microbatch circulates the ring k times, re-entering stage 0 from a
  recirculation buffer. T = kM + S - 1 ticks, shrinking the bubble to
  (S-1)/(kM+S-1) — the MaxText circular-pipeline schedule.

The microbatch queue and output buffer shard over the pipe axis
(``shard_queue``): stage s holds only its M/S microbatches, and two
single-microbatch ppermute streams carry inputs down to stage 0 and
finished outputs back to their owning stage — per-device queue memory
drops by ~S vs the replicated-queue lowering (kept as the fallback when
S does not divide M).

Backward is ordinary JAX autodiff through the shard_map — the transpose
of ppermute is the reverse-ring ppermute, so the returning gradient
pipeline falls out of jax.grad.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


SCHEDULES = ("gpipe", "circular")


def circular_block_order(num_blocks: int, num_stages: int):
    """Storage-row order for ``schedule='circular'``: returns the list
    ``order`` with ``order[row] = block index stored at that row``, such
    that sharding the leading dim over S stages gives stage s the
    round-robin blocks {s, s+S, s+2S, ...} with local slice r = round r's
    block. Row s*k + r holds block r*S + s."""
    k = num_blocks // num_stages
    return [r * num_stages + s for s in range(num_stages) for r in range(k)]


def pipeline_spmd(stage_fn, stacked_params, x, mesh, *, num_microbatches,
                  axis: str = "pipe", data_axis: str = "data",
                  stage_leading_dim: bool = False,
                  schedule: str = "gpipe", shard_queue: bool = True):
    """Run ``stage_fn`` as an S-stage SPMD pipeline.

    stage_fn(params_slice, x) -> y: one stage's computation; input and
        output must share shape/dtype (repeated-block models).
    stacked_params: pytree with leading dim R (a multiple of the ``axis``
        mesh size S), sharded over ``axis``. With R == S each stage holds
        one slice; ``stage_leading_dim=True`` keeps the local [R/S, ...]
        leading dim. Under ``schedule='gpipe'`` stage_fn then receives
        the whole local tree (a stage running R/S consecutive blocks);
        under ``schedule='circular'`` the rows must be in
        ``circular_block_order`` and stage_fn receives ONE block's
        squeezed slice per call (the round's block).
    x: [B, ...] global batch; B % num_microbatches == 0, and the
        microbatch size is the unit each stage processes per tick. When
        ``data_axis`` names a mesh axis, each microbatch additionally
        shards over it (pipeline x data composition).
    shard_queue: shard the microbatch queue and output buffer over the
        pipe axis (each stage holds M/S microbatches; per-tick ppermute
        streams feed stage 0 and scatter finished outputs back). Falls
        back to the replicated queue when S does not divide M.
    Returns y of x's shape: the last stage's outputs, gathered.
    """
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}, "
                         f"got {schedule!r}")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    S = sizes[axis]
    R = None
    for leaf in jax.tree.leaves(stacked_params):
        bad = (leaf.shape[0] % S != 0) if stage_leading_dim \
            else (leaf.shape[0] != S)
        if bad:
            raise ValueError(
                f"stacked param dim 0 is {leaf.shape[0]} but the '{axis}' "
                f"mesh axis has {S} stages — a mismatch would silently "
                f"drop stages")
        R = leaf.shape[0] if R is None else R
    M = num_microbatches
    if x.shape[0] % M:
        raise ValueError(f"batch {x.shape[0]} % microbatches {M} != 0")
    data_axis = data_axis if sizes.get(data_axis, 1) > 1 else None
    if data_axis and (x.shape[0] // M) % sizes[data_axis]:
        raise ValueError(
            f"microbatch size {x.shape[0] // M} % '{data_axis}' axis "
            f"({sizes[data_axis]}) != 0")
    # circular: one block per tick, k rounds around the ring; without a
    # stage-leading dim there is exactly one round and the schedules
    # coincide
    circular = schedule == "circular" and stage_leading_dim
    rounds = (R // S) if circular else 1
    use_circ = rounds > 1  # recirculation buffer needed
    if use_circ and M < S:
        raise ValueError(
            f"circular schedule needs microbatches >= stages "
            f"({M} < {S}): a returning microbatch would overtake the "
            f"recirculation buffer")
    qsharded = shard_queue and M % S == 0
    q = M // S if qsharded else M
    ticks = rounds * M + S - 1
    # the sharded output stream needs S-1 more hops to land the last
    # microbatches on their owners — a separate compute-free drain loop
    # (running stage_fn on garbage there would cost real backward
    # residual memory for nothing)

    down = [(i, (i - 1) % S) for i in range(S)]  # toward stage 0
    up = [(i, (i + 1) % S) for i in range(S)]    # the pipeline direction

    def body(params, xs):
        # params: this device's block slices; xs: [q, mb, ...] local
        # queue slice (the full [M, ...] queue when replicated)
        idx = jax.lax.axis_index(axis)
        outs = jnp.zeros_like(xs)
        z = jnp.zeros(xs.shape[1:], xs.dtype)

        def block_params(r):
            if circular:
                return jax.tree.map(
                    lambda w: jax.lax.dynamic_index_in_dim(
                        w, r, 0, keepdims=False), params)
            return params if stage_leading_dim \
                else jax.tree.map(lambda w: w[0], params)

        def tick(t, carry):
            state, outs, circ, in_stream, out_stream = carry
            # ---- input side: the microbatch entering stage 0 ----------
            if qsharded:
                # double-buffered input stream (ISSUE 9): consume the
                # value staged at the END of the previous tick, then
                # advance the stream for tick t+1 — the hop's ppermute
                # has no consumer inside this tick, so XLA's async
                # collective scheduling overlaps it with the block
                # compute instead of gating stage 0's feed on it (the
                # simulator already priced the streams as bandwidth-only
                # prefetch traffic; this makes the runtime match).
                # Protocol: owner h(m) = m // q injects m at the end of
                # tick m - h - 1 (h == 0 and m == 0 come from the
                # pre-loop staging); stage 0 reads microbatch t at tick
                # t, exactly as the synchronous stream delivered.
                queue_feed = in_stream
                nxt = jax.lax.ppermute(in_stream, axis, down)
                m_in = t + 1 + idx
                owned = jnp.logical_and(m_in >= idx * q,
                                        m_in < (idx + 1) * q)
                li = jnp.clip(m_in - idx * q, 0, q - 1)
                mine = jax.lax.dynamic_index_in_dim(xs, li, 0,
                                                    keepdims=False)
                in_stream = jnp.where(owned, mine, nxt)
            else:
                feed = jnp.clip(t, 0, M - 1)
                queue_feed = jax.lax.dynamic_index_in_dim(
                    xs, feed, 0, keepdims=False)
            if use_circ:
                # rounds >= 1 re-enter from the recirculation buffer —
                # a W = M-S+1 slot ring in BOTH queue lowerings (a value
                # u lives from its bank tick u+S-1 to its consume tick
                # u+M, so at most W slots are ever live); the value fed
                # at global step u0 is microbatch u0-M of the previous
                # round, parked in slot (u0-M) % W. Round 0 feeds from
                # the queue directly (ISSUE 20 satellite: the replicated
                # fallback no longer keeps a full M-slot ring).
                u0 = jnp.clip(t, 0, rounds * M - 1)
                cslot = (u0 - M) % (M - S + 1)
                circ_feed = jax.lax.dynamic_index_in_dim(
                    circ, cslot, 0, keepdims=False)
                feed_val = jnp.where(t < M, queue_feed, circ_feed)
            else:
                feed_val = queue_feed
            cur = jnp.where(idx == 0, feed_val, state)
            # ---- compute: this stage's block for the current round ----
            u = t - idx  # global step of the microbatch at this stage
            r = jnp.clip(u, 0, rounds * M - 1) // M
            y = stage_fn(block_params(r), cur)
            # ---- output side: microbatch leaving its final round ------
            u_last = t - (S - 1)                 # last stage's step
            fin = u_last - (rounds - 1) * M      # finished microbatch
            finished = jnp.logical_and(fin >= 0, fin < M)
            if qsharded:
                # out stream rides the ring away from the last stage;
                # each stage captures the finished microbatches it owns
                out_stream = jax.lax.ppermute(out_stream, axis, up)
                out_stream = jnp.where(
                    jnp.logical_and(idx == S - 1, finished), y, out_stream)
                m_out = fin - ((idx + 1) % S)
                owned_out = jnp.logical_and(m_out >= idx * q,
                                            m_out < (idx + 1) * q)
                lo = jnp.clip(m_out - idx * q, 0, q - 1)
                prev = jax.lax.dynamic_index_in_dim(outs, lo, 0,
                                                    keepdims=False)
                outs = jax.lax.dynamic_update_index_in_dim(
                    outs, jnp.where(owned_out, out_stream, prev), lo, 0)
            else:
                slot = jnp.clip(fin, 0, M - 1)
                valid = jnp.logical_and(idx == S - 1, finished)
                prev = jax.lax.dynamic_index_in_dim(outs, slot, 0,
                                                    keepdims=False)
                outs = jax.lax.dynamic_update_index_in_dim(
                    outs, jnp.where(valid, y, prev), slot, 0)
            # ---- forward the activation one hop around the pipe ring --
            state = jax.lax.ppermute(y, axis, up)
            if use_circ:
                # stage 0 banks the returning activation for its next
                # round (consumed M-S+1 ticks later — safe: M >= S)
                u_arr = jnp.clip(t - (S - 1), 0, rounds * M - 1)
                ok = jnp.logical_and(
                    jnp.logical_and(t - (S - 1) >= 0,
                                    u_arr // M < rounds - 1),
                    idx == 0)
                # the ring write at tick t lands on the slot whose value
                # was consumed THIS tick ((t-S+1) - (t-M) = W) — safe
                # because circ_feed above read the pre-update buffer
                s_arr = u_arr % (M - S + 1)
                prevc = jax.lax.dynamic_index_in_dim(circ, s_arr, 0,
                                                     keepdims=False)
                circ = jax.lax.dynamic_update_index_in_dim(
                    circ, jnp.where(ok, state, prevc), s_arr, 0)
            return state, outs, circ, in_stream, out_stream

        if use_circ:
            # windowed to the M-S+1 in-flight slots in BOTH queue
            # lowerings: the HBM win the simulator's queue-memory term
            # prices with the same (M-pp+1)/M factor
            circ0 = jnp.zeros((M - S + 1,) + xs.shape[1:], xs.dtype)
        else:
            circ0 = jnp.zeros((1,) + xs.shape[1:], xs.dtype)  # unused
        if qsharded:
            # pre-loop staging of the double-buffered input stream: the
            # "end of tick -1" injection — stage 0 stages microbatch 0
            # (and with q == 1, stage h stages its own microbatch h,
            # which then rides h hops to arrive at tick h)
            m0 = idx
            owned0 = jnp.logical_and(m0 >= idx * q, m0 < (idx + 1) * q)
            li0 = jnp.clip(m0 - idx * q, 0, q - 1)
            in0 = jnp.where(owned0,
                            jax.lax.dynamic_index_in_dim(xs, li0, 0,
                                                         keepdims=False), z)
        else:
            in0 = z
        carry = (z, outs, circ0, in0, z)
        _, outs, _, _, out_stream = jax.lax.fori_loop(0, ticks, tick, carry)
        if qsharded:
            def drain_tick(j, carry):
                outs, out_stream = carry
                t = ticks + j
                out_stream = jax.lax.ppermute(out_stream, axis, up)
                fin = t - (S - 1) - (rounds - 1) * M
                m_out = fin - ((idx + 1) % S)
                owned_out = jnp.logical_and(m_out >= idx * q,
                                            m_out < (idx + 1) * q)
                lo = jnp.clip(m_out - idx * q, 0, q - 1)
                prev = jax.lax.dynamic_index_in_dim(outs, lo, 0,
                                                    keepdims=False)
                outs = jax.lax.dynamic_update_index_in_dim(
                    outs, jnp.where(owned_out, out_stream, prev), lo, 0)
                return outs, out_stream

            outs, _ = jax.lax.fori_loop(0, S - 1, drain_tick,
                                        (outs, out_stream))
            # each stage returns the finished microbatches it owns — the
            # out_specs sharding assembles the global [M, ...] result
            return outs
        # every device returns outs; only the last stage's is real — psum
        # after zeroing the others yields the replicated result
        outs = jnp.where(idx == S - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    pipe_spec = P(axis)
    # queue layout: microbatch dim sharded over pipe (or replicated in
    # the fallback); the batch-within-microbatch dim shards over the data
    # axis so pipeline x data composes (each data shard pipelines its
    # slice of every microbatch)
    x_spec = P(axis if qsharded else None, data_axis) if data_axis \
        else (P(axis) if qsharded else P())
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: pipe_spec, stacked_params), x_spec),
        out_specs=x_spec, check_vma=False)
    mb = x.shape[0] // M
    xs = x.reshape((M, mb) + x.shape[1:])
    return fn(stacked_params, xs).reshape(x.shape)


def transformer_block_stage(embed_dim: int, num_heads: int, seq_length: int,
                            batch_per_microbatch: int, ffn_mult: int = 4):
    """(init_fn, stage_fn) for one pre-norm transformer block built from
    the framework's own op implementations — the repeated stage a
    pipelined transformer runs on each 'pipe' shard.

    init_fn(rng) -> params pytree for one stage;
    stage_fn(params, x[Bmb, S, E]) -> same shape.

    ``seq_length``/``batch_per_microbatch`` are construction-time shape
    metadata only (Op instances are built against concrete shapes); the
    returned stage_fn itself is shape-polymorphic, so running it on a
    differently-sized (e.g. data-sharded) block is fine.
    """
    from flexflow_tpu.ffconst import ActiMode, DataType, OperatorType
    from flexflow_tpu.layer import Layer
    from flexflow_tpu.ops import OpRegistry
    from flexflow_tpu.ops.base import OpContext

    b, s, e = batch_per_microbatch, seq_length, embed_dim

    def make(op_type, props, shapes):
        lyr = Layer(op_type, None, [], data_type=DataType.FLOAT)
        lyr.properties.update(props)
        return OpRegistry.create(lyr, shapes)

    ln1 = make(OperatorType.LAYERNORM, dict(axes=(-1,)), [(b, s, e)])
    attn = make(OperatorType.MULTIHEAD_ATTENTION,
                dict(embed_dim=e, num_heads=num_heads, dropout=0.0),
                [(b, s, e)] * 3)
    ln2 = make(OperatorType.LAYERNORM, dict(axes=(-1,)), [(b, s, e)])
    ff1 = make(OperatorType.LINEAR,
               dict(out_dim=e * ffn_mult,
                    activation=ActiMode.AC_MODE_RELU), [(b, s, e)])
    ff2 = make(OperatorType.LINEAR, dict(out_dim=e), [(b, s, e * ffn_mult)])

    def init_fn(rng):
        ks = jax.random.split(rng, 5)
        return {"ln1": ln1.init_params(ks[0]),
                "attn": attn.init_params(ks[1]),
                "ln2": ln2.init_params(ks[2]),
                "ff1": ff1.init_params(ks[3]),
                "ff2": ff2.init_params(ks[4])}

    def stage_fn(p, x):
        ctx = OpContext(training=True, compute_dtype=jnp.float32)
        h = ln1.forward(p["ln1"], [x], ctx)[0]
        a = attn.forward(p["attn"], [h, h, h], ctx)[0]
        x = x + a
        h = ln2.forward(p["ln2"], [x], ctx)[0]
        h = ff1.forward(p["ff1"], [h], ctx)[0]
        h = ff2.forward(p["ff2"], [h], ctx)[0]
        return x + h

    return init_fn, stage_fn


def stack_stage_params(per_stage_params, order=None):
    """[params_block0, ..., params_blockR-1] (identical trees) -> one tree
    with a leading [R, ...] axis, ready to shard over 'pipe'. ``order``
    permutes the storage rows (``circular_block_order`` for the circular
    schedule: row i holds block order[i])."""
    if order is not None:
        per_stage_params = [per_stage_params[b] for b in order]
    return jax.tree.map(lambda *ws: jnp.stack(ws), *per_stage_params)


def shard_stacked(stacked_params, mesh, axis: str = "pipe"):
    """Place the stacked tree with dim 0 sharded over the pipe axis."""
    def put(w):
        spec = P(axis, *([None] * (w.ndim - 1)))
        return jax.device_put(w, NamedSharding(mesh, spec))

    return jax.tree.map(put, stacked_params)
