"""Sharded KV-cache incremental decode for the causal attention family.

Full-sequence ``predict`` recomputes every prior token's K/V at every
generation step — O(S^2) projection work per emitted token. Here the
K/V of already-seen positions live in a first-class *sharded* cache
tensor per attention op:

* shape ``[B, Hk, S_max, D]`` (kv heads, so GQA caches the small side);
* the **head axis shards under model parallelism** exactly where the
  searched strategy put the attention weights' head axis;
* the **sequence axis shards over the ring-attention 'seq' mesh axis**
  when the mesh carries one — the same layout
  ``parallel/ring_attention`` uses for K/V blocks, so long-context
  caches scale with the ring, and GSPMD partitions the decode
  attention over the sharded cache length;
* the batch axis follows the data axes.

The decode path reuses the model's OWN graph: the layer graph is
re-materialized at the new-token block length (prefill: the prompt
length; decode: 1) via ``FFModel._materialize_nodes`` — the seq-bucket
machinery applied to serving — and executed node by node, with
``MultiHeadAttention.decode_forward`` splicing the cache in. Everything
outside attention is position-wise in a decoder transformer, so the
composition is numerically the full-sequence forward restricted to the
new rows: ``tests/test_serve.py`` parity-tests prefill + N decode steps
against full recompute.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.ffconst import OperatorType


def _attention_nodes(ff) -> List[Any]:
    return [n for n in ff.executor.nodes
            if n.op.op_type == OperatorType.MULTIHEAD_ATTENTION]


def cache_partition_spec(ff, node, batch: int, max_len: int):
    """PartitionSpec for one attention op's ``[B, Hk, S_max, D]`` cache.

    Head axis: wherever the searched strategy sharded the attention
    weights' head dim (``wq`` param spec, dim 0) — model parallelism
    keeps each chip's cache to its own heads. Seq axis: the mesh's
    'seq' (ring attention) axis when present. Batch: the data axes.
    Every entry engages only when the extent divides — an indivisible
    dim stays replicated rather than failing the placement.
    """
    from jax.sharding import PartitionSpec as P

    axes = dict(zip(ff.mesh.axis_names, ff.mesh.devices.shape))

    def fits(entry, extent) -> bool:
        if entry is None:
            return False
        names = entry if isinstance(entry, tuple) else (entry,)
        deg = 1
        for a in names:
            if axes.get(a, 1) <= 1:
                return False
            deg *= axes[a]
        return extent % deg == 0

    data_axes = tuple(a for a in ("data", "replica") if axes.get(a, 1) > 1)
    b_entry = (data_axes if len(data_axes) > 1 else
               (data_axes[0] if data_axes else None))
    if not fits(b_entry, batch):
        b_entry = None
    h_entry = None
    st = (ff.strategy or {}).get(node.op.guid)
    if st is not None:
        wq = st.param_specs.get("wq")
        if wq is not None and len(wq) > 0 and fits(wq[0],
                                                   node.op.num_kv_heads):
            h_entry = wq[0]
    s_entry = "seq" if fits("seq", max_len) else None
    return P(b_entry, h_entry, s_entry, None)


def init_kv_cache(ff, batch: Optional[int] = None,
                  max_len: Optional[int] = None, dtype=None
                  ) -> Dict[str, Dict[str, Any]]:
    """Zero-initialized sharded caches, one ``{"k","v"}`` pair per
    causal attention op, placed on their partition specs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    batch = int(batch or ff.input_tensors[0].shape[0])
    max_len = int(max_len or ff._declared_seq() or 0)
    if max_len <= 0:
        raise ValueError("model has no sequence dim to cache")
    dtype = dtype or ff.executor.compute_dtype
    for node in ff.executor.nodes:
        if node.op.op_type in (OperatorType.HC_PRE, OperatorType.HC_POST):
            raise NotImplementedError(
                f"'{node.op.name}' is a hyper-connection: a decode step "
                f"would carry the position's n residual streams from "
                f"layer to layer and mix them by maps formed from all of "
                f"them, where `decode_forward` carries one hidden row; "
                f"serving several residual streams a position is not "
                f"built")
        if node.op.op_type == OperatorType.MAMBA_MIXER or getattr(
                node.op, "exports", 0) or getattr(node.op, "kv_given",
                                                  False):
            raise NotImplementedError(
                f"'{node.op.name}' is a Mamba-1 mixer, or makes or reads "
                f"keys/values or a memory shared between layers: a new "
                f"token needs the scan's state, and ONE cache entry that "
                f"the producer writes and every reader reads; this cache "
                f"holds one {{k, v}} pair a causal attention op. Serving "
                f"them is not built")
        if getattr(node.op, "sparse_index", None):
            raise NotImplementedError(
                f"'{node.op.name}' is learned sparse attention: a new "
                f"token's query would score every cached position with "
                f"the indexer (whose key this cache does not hold) and "
                f"attend over the kept ones; the selection over a cache "
                f"is not built")
        if node.op.op_type == OperatorType.DELTA_MIXER:
            raise NotImplementedError(
                f"'{node.op.name}' is a gated delta-rule mixer: a new "
                f"token needs the value heads' [Dk, Dv] states and the "
                f"convolution's last K - 1 rows, which this cache (one "
                f"{{k, v}} pair a causal attention op) does not hold; "
                f"serving a linear-attention layer's state is not built")
        if getattr(node.op, "lane_gate", False):
            raise NotImplementedError(
                f"'{node.op.name}' gates its output a lane from the query "
                f"projection: `decode_forward` does not apply that gate; "
                f"serving the family is not built")
        if node.op.op_type == OperatorType.SHORT_CONV:
            raise NotImplementedError(
                f"'{node.op.name}' is a short convolution: a new token "
                f"reads the K - 1 positions before it, which this cache "
                f"does not hold; serving a convolution layer's state is "
                f"not built")
    caches: Dict[str, Dict[str, Any]] = {}
    for node in _attention_nodes(ff):
        op = node.op
        if not op.causal:
            raise NotImplementedError(
                f"attention '{op.name}' is not causal — KV-cache decode "
                f"only decomposes causal attention incrementally")
        if getattr(op, "latent", None):
            raise NotImplementedError(
                f"attention '{op.name}' is latent attention — this cache "
                f"holds whole key and value heads; serving it from the "
                f"compressed latent (the absorbed form) is not built")
        if (getattr(op, "gate", False) or getattr(op, "rope_scaling", None)
                or getattr(op, "rotary_dim", op.head_dim) != op.head_dim):
            raise NotImplementedError(
                f"attention '{op.name}' has a per-head output gate "
                f"(`gate`), partial rotary (`partial_rotary_factor`) or "
                f"scaled frequencies (`rope_scaling`) — the cached decode "
                f"path applies none of them; serving such an op is not "
                f"built")
        spec = cache_partition_spec(ff, node, batch, max_len)
        sharding = NamedSharding(ff.mesh, spec)
        shape = (batch, op.num_kv_heads, max_len, op.head_dim)
        # distinct buffers per entry: the decode step donates the cache
        # tree, and donation rejects aliased buffers
        caches[op.name] = dict(
            k=jax.device_put(jnp.zeros(shape, dtype), sharding),
            v=jax.device_put(jnp.zeros(shape, dtype), sharding))
    if not caches:
        raise ValueError("model has no attention ops — nothing to cache")
    return caches


def _seq_overrides(ff, new_len: int, batch: Optional[int]
                   ) -> Dict[str, Tuple[int, ...]]:
    """INPUT-shape overrides materializing the graph at ``new_len``
    new-token rows (and optionally ``batch`` rows): dim 1 of every
    seq-carrying input becomes ``new_len`` — the seq-bucket override
    discipline of ``FFModel._bucket_executor``."""
    declared = ff._declared_seq()
    overrides: Dict[str, Tuple[int, ...]] = {}
    for layer in ff.layers:
        if layer.op_type != OperatorType.INPUT:
            continue
        shp = list(layer.outputs[0].shape)
        changed = False
        if declared is not None and len(shp) >= 2 and shp[1] == declared:
            shp[1] = new_len
            changed = True
        if batch is not None and shp and shp[0] != batch:
            shp[0] = batch
            changed = True
        if changed:
            overrides[layer.name] = tuple(shp)
    return overrides


class DecodeSession:
    """Prefill + incremental-decode over the sharded KV cache.

    One session = one in-flight batch of sequences decoding in
    lockstep. ``prefill(inputs)`` consumes the prompt block (absolute
    positions 0..S0-1), ``decode(inputs)`` one token block at the
    running position; both return the logits for the rows they
    consumed. Two jitted executables total (one per block length),
    cached across calls; caches are donated through each step so the
    update is in-place on device.
    """

    def __init__(self, ff, batch: Optional[int] = None,
                 max_len: Optional[int] = None):
        from flexflow_tpu.executor import GraphExecutor
        if type(ff.executor) is not GraphExecutor:
            raise NotImplementedError(
                "KV-cache decode drives the plain GraphExecutor graph "
                "(pipeline-lowered models are not supported)")
        self.ff = ff
        self.batch = int(batch or ff.input_tensors[0].shape[0])
        self.max_len = int(max_len or ff._declared_seq() or 0)
        self.caches = init_kv_cache(ff, self.batch, self.max_len)
        self.pos = 0
        self._steps: Dict[int, Any] = {}  # block length -> jitted step
        # attention kernel provenance (ISSUE 15 defect fix): the decode
        # path runs ``decode_forward`` — ALWAYS the cached einsum; flash
        # has no incremental decomposition over a KV cache, so the
        # module-level flash availability check is irrelevant here. The
        # impl is RECORDED at session build and the report replays it,
        # instead of re-deriving availability at report time and
        # claiming a kernel this path can never run.
        self.kernel_choices = {
            n.op.name: "cached_einsum" for n in _attention_nodes(ff)}

    def report(self) -> Dict[str, Any]:
        """Session provenance for serve observability: the recorded
        per-op attention impls (always ``cached_einsum`` on the decode
        path) plus geometry — agrees with training provenance by
        construction, never by re-derivation."""
        return dict(batch=self.batch, max_len=self.max_len, pos=self.pos,
                    kernel_choices=dict(self.kernel_choices))

    # ---- step construction -------------------------------------------------
    def _make_step(self, t: int):
        import jax

        ff = self.ff
        nodes, input_names, tensor_ref = ff._materialize_nodes(
            _seq_overrides(ff, t, self.batch))
        final_ref = ff._select_final_ref(nodes, tensor_ref)
        by_guid = {n.op.guid: n for n in nodes}
        attn_guids = {n.op.guid for n in nodes
                      if n.op.op_type == OperatorType.MULTIHEAD_ATTENTION}

        def step(params, state, caches, inputs, pos):
            from flexflow_tpu.executor import op_params
            from flexflow_tpu.ops.base import OpContext
            ctx = OpContext(training=False,
                            compute_dtype=ff.executor.compute_dtype,
                            mesh=ff.mesh)
            values: Dict[Tuple[int, int], Any] = {}

            def fetch(ref):
                if ref[0] == "op":
                    return values[(ref[1], ref[2])]
                return inputs[ref[1]]

            new_caches = {k: dict(v) for k, v in caches.items()}
            for node in nodes:
                op = node.op
                args = [fetch(r) for r in node.input_refs]
                if op.guid in attn_guids:
                    c = caches[op.name]
                    y, k_new, v_new = op.decode_forward(
                        params.get(op.name, {}), args, ctx,
                        c["k"], c["v"], pos)
                    new_caches[op.name] = dict(k=k_new, v=v_new)
                    outs = [y]
                elif hasattr(op, "init_state"):
                    outs = op.forward(params.get(op.name, {}), args, ctx,
                                      state=state.get(op.name))
                    op._new_state = None  # eval mode: stats don't advance
                else:
                    outs = op.forward(op_params(op, params), args, ctx)
                if getattr(op, "_aux_loss", None) is not None:
                    op._aux_loss = None  # inference: no objective
                for i, o in enumerate(outs):
                    values[(op.guid, i)] = o
            return values[final_ref], new_caches

        return jax.jit(step, donate_argnums=(2,)), input_names, by_guid

    def _step_for(self, t: int):
        if t not in self._steps:
            self._steps[t] = self._make_step(t)
        return self._steps[t]

    # ---- public API --------------------------------------------------------
    def _run(self, inputs: Sequence[np.ndarray], t: int) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        if self.pos + t > self.max_len:
            raise ValueError(
                f"decode past max_len: pos {self.pos} + block {t} > "
                f"{self.max_len}")
        step, input_names, _ = self._step_for(t)
        if len(inputs) != len(input_names):
            raise ValueError(f"model has {len(input_names)} inputs, got "
                             f"{len(inputs)}")
        feed = {}
        for name, arr in zip(input_names, inputs):
            arr = jnp.asarray(arr)
            if jnp.issubdtype(arr.dtype, jnp.floating):
                arr = arr.astype(self.ff.executor.compute_dtype)
            feed[name] = arr
        logits, self.caches = step(self.ff.params, self.ff.state,
                                   self.caches, feed,
                                   jnp.int32(self.pos))
        self.pos += t
        return np.asarray(jax.device_get(logits))

    def prefill(self, inputs: Sequence[np.ndarray]) -> np.ndarray:
        """Consume the prompt block (seq-carrying inputs shaped
        ``[B, S0, ...]``); returns logits for every prompt row."""
        if self.pos != 0:
            raise ValueError("prefill must be the session's first call")
        seqful = [np.asarray(x) for x in
                  (inputs if isinstance(inputs, (list, tuple))
                   else [inputs])]
        t = int(seqful[0].shape[1])
        return self._run(seqful, t)

    def decode(self, inputs: Sequence[np.ndarray]) -> np.ndarray:
        """One incremental block (usually ``[B, 1, ...]``) at the
        running position; returns its logits."""
        seqful = [np.asarray(x) for x in
                  (inputs if isinstance(inputs, (list, tuple))
                   else [inputs])]
        return self._run(seqful, int(seqful[0].shape[1]))

    def generate(self, input_ids: np.ndarray, steps: int) -> np.ndarray:
        """Greedy generation for single-input token models: prefill the
        prompt, then emit ``steps`` argmax tokens. Returns
        ``[B, S0 + steps]`` token ids."""
        ids = np.asarray(input_ids)
        logits = self.prefill([ids])
        toks = [ids]
        for i in range(steps):
            nxt = np.argmax(logits[:, -1, :], axis=-1).astype(ids.dtype)
            toks.append(nxt[:, None])
            if i + 1 < steps:
                logits = self.decode([nxt[:, None]])
        return np.concatenate(toks, axis=1)
