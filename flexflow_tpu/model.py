"""FFModel: the user-facing model builder + training runtime.

TPU re-design of the reference FFModel (include/flexflow/model.h:326,
src/runtime/model.cc): the same deferred layer-building API (dense, conv2d,
multihead_attention, ..., model.h:380-520), a ``compile()`` that
materializes operators from layers (create_operators_from_layers,
model.cc:2784), picks a parallelization strategy, and builds the
executable — here a single jitted train-step over a device mesh rather
than Legion task launches. ``fit/eval`` mirror the Python frontend's loop
(flexflow_cffi.py:2073-2086) and print the same
``ELAPSED TIME / THROUGHPUT`` lines as the reference examples
(examples/cpp/Transformer/transformer.cc:209-211).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from flexflow_tpu.config import FFConfig
from flexflow_tpu.executor import GraphExecutor, OpNode
from flexflow_tpu.ffconst import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OperatorType,
    PoolType,
)
from flexflow_tpu.layer import Layer
from flexflow_tpu.machine import MachineSpec, detect_machine_spec, make_mesh
from flexflow_tpu.metrics import Metrics, PerfMetrics
from flexflow_tpu.ops import OpRegistry
from flexflow_tpu.ops.base import shared_leaves_error
from flexflow_tpu.optimizers import Optimizer, SGDOptimizer
from flexflow_tpu.tensor import Tensor


# About the bytes of a staged batch's raw form that one host-to-device
# transfer carries (`FFModel._shard_batch`). Small pieces keep a batch's
# copy from being held up by those of the batches `fit` stages right
# behind it: Inception's 275 MB batch in pieces of 16/8/4/2 MiB left a
# v5e waiting 45/31/23/34 ms at the start of an epoch, 67-143 ms whole
# (PERF.md section 6, PR 26). Under 4 MiB the host thread's enqueues
# (0.24 ms each) take longer than the wire.
_RAW_PIECE_BYTES = 4 << 20


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        self.config = config or FFConfig()
        self.layers: List[Layer] = []
        self._layer_named: Dict[str, Layer] = {}
        self.input_tensors: List[Tensor] = []
        self.label_tensor: Optional[Tensor] = None
        self.optimizer: Optional[Optimizer] = None
        self.executor: Optional[GraphExecutor] = None
        self.params: Dict[str, Dict[str, jax.Array]] = {}
        self.opt_state: Any = None
        self.state: Dict[str, Any] = {}
        self.machine_spec: Optional[MachineSpec] = None
        self.mesh = None
        self.strategy = None
        self._rng = jax.random.PRNGKey(self.config.seed)
        self._iter = 0
        self._metrics_acc = PerfMetrics()
        # host seconds: compile()'s phases, and set_parameter since then
        self.compile_phases: Optional[Dict[str, float]] = None
        self.set_parameter_s = 0.0
        # parity loop state (forward/backward/update protocol)
        self._current_batch = None
        self._pending = None

    # ======================= tensor/layer construction =====================
    def create_tensor(self, dims: Sequence[int], dtype: DataType = DataType.FLOAT,
                      create_grad: bool = True, name: Optional[str] = None) -> Tensor:
        layer = Layer(OperatorType.INPUT, name or f"input_{len(self.input_tensors)}",
                      [], data_type=dtype)
        # input names key the feed dict — must be unique too
        if not hasattr(self, "_used_names"):
            self._used_names = set()
        if layer.name in self._used_names:
            layer.name = f"{layer.name}_{layer.guid}"
        self._used_names.add(layer.name)
        t = Tensor(dims, dtype, owner_layer=layer, name=layer.name)
        layer.outputs = [t]
        self.layers.append(layer)
        self.input_tensors.append(t)
        return t

    def _add_layer(self, op_type: OperatorType, inputs: List[Tensor],
                   props: Dict[str, Any], name: Optional[str] = None,
                   dtype: Optional[DataType] = None,
                   shared_op: Optional[Tensor] = None) -> Layer:
        """``shared_op`` (upstream's argument of that name): the output
        of the layer whose leaves this one reads in place of its own,
        ALL of them. The model then holds each leaf once, under the
        owner's name, with one optimizer state; its gradient is the sum
        over every reader (``Op.tied_params``). ``compile`` raises where
        the owner is not a layer of the reader's kind with leaves of the
        reader's shapes."""
        owner = shared_op.owner_layer if shared_op is not None else None
        again = getattr(self, "_again", None)
        if owner is None and again and name:
            # a further application of the layer called `name`
            owner = self._layer_named.get(name) if again[1] else None
            name = again[0] + name
        layer = Layer(op_type, name, inputs,
                      data_type=dtype or (inputs[0].dtype if inputs else DataType.FLOAT))
        # parameters are keyed by layer name — names must be unique
        if not hasattr(self, "_used_names"):
            self._used_names = set()
        if layer.name in self._used_names:
            base = layer.name
            layer.name = f"{base}_{layer.guid}"
        self._used_names.add(layer.name)
        layer.properties.update(props)
        if getattr(self, "_scope", None):
            layer.properties["scope"] = self._scope
        if owner is not None:
            layer.properties["shared_op"] = owner.name
        self.layers.append(layer)
        self._layer_named[layer.name] = layer
        return layer

    @contextlib.contextmanager
    def scope(self, name: str):
        """The layers added inside run under the nested call ``name`` in
        the device trace, around their own scopes (``ops.base.scoped``;
        `obs/step_scopes.py` makes ``mtp``, ``ut<t>`` and ``exit`` parts
        of the step)."""
        prev, self._scope = getattr(self, "_scope", None), name
        try:
            yield
        finally:
            self._scope = prev

    @contextlib.contextmanager
    def applied_again(self, prefix: str, share_leaves: bool = True):
        """The layers added inside are further applications of layers
        the model has: one added as ``name`` is called ``prefix + name``
        and reads ALL its leaves out of the layer ``name``
        (``_add_layer(shared_op=)``), so that a builder runs the same
        code for every pass of a looped stack. With ``share_leaves``
        False only the names change (every pass leaves of its own: a
        control)."""
        prev, self._again = getattr(self, "_again", None), (prefix,
                                                            share_leaves)
        try:
            yield
        finally:
            self._again = prev

    def _finish(self, layer: Layer) -> Tensor:
        op = OpRegistry.create(layer, [t.shape for t in layer.inputs])
        if "shared_op" in layer.properties and not op.tied_params:
            # an op without leaves (an add, a split) shares nothing
            del layer.properties["shared_op"]
        outs = [
            Tensor(s, layer.data_type, owner_layer=layer, owner_idx=i,
                   name=f"{layer.name}_out{i}")
            for i, s in enumerate(op.output_shapes)
        ]
        layer.outputs = outs
        return outs[0] if len(outs) == 1 else tuple(outs)

    # ---- dense / conv stack (model.h:380-520 API parity) -------------------
    def dense(self, input: Tensor, out_dim: int,
              activation: ActiMode = ActiMode.AC_MODE_NONE, use_bias: bool = True,
              datatype: Optional[DataType] = None, kernel_initializer=None,
              bias_initializer=None, tied_to: Optional[Tensor] = None,
              shared_op: Optional[Tensor] = None,
              full_precision: bool = False,
              name: Optional[str] = None) -> Tensor:
        """``shared_op``: the output of another ``dense`` whose kernel
        and bias this one reads (``_add_layer``). ``tied_to``: the
        one-leaf, transposed case of it: the output of an ``embedding``
        whose table [out_dim, in_dim] this product reads, y = x E^T, in
        place of a kernel of its own (a tied head): the model then holds
        ONE leaf, the embedding's, with one optimizer state, and its
        gradient is the sum over both uses (ops/linear.py).
        ``full_precision``: the product in float32, whatever the
        compute dtype (a gate of one column)."""
        extra = {}
        if tied_to is not None:
            source = tied_to.owner_layer
            if (source is None or source.op_type != OperatorType.EMBEDDING
                    or use_bias or shared_op is not None):
                raise ValueError(
                    f"dense '{name}': tied_to takes the output of an "
                    f"embedding layer, and no bias")
            extra["tied_to"] = True
        if full_precision:
            extra["full_precision"] = True
        layer = self._add_layer(OperatorType.LINEAR, [input], dict(
            out_dim=out_dim, activation=activation, use_bias=use_bias,
            kernel_initializer=kernel_initializer, bias_initializer=bias_initializer,
            **extra), name, datatype,
            shared_op=tied_to if tied_to is not None else shared_op)
        return self._finish(layer)

    def conv2d(self, input: Tensor, out_channels: int, kernel_h: int, kernel_w: int,
               stride_h: int, stride_w: int, padding_h: int, padding_w: int,
               activation: ActiMode = ActiMode.AC_MODE_NONE, groups: int = 1,
               use_bias: bool = True, kernel_initializer=None,
               bias_initializer=None, shared_op: Optional[Tensor] = None,
               name: Optional[str] = None) -> Tensor:
        layer = self._add_layer(OperatorType.CONV2D, [input], dict(
            out_channels=out_channels, kernel_h=kernel_h, kernel_w=kernel_w,
            stride_h=stride_h, stride_w=stride_w, padding_h=padding_h,
            padding_w=padding_w, activation=activation, groups=groups,
            use_bias=use_bias, kernel_initializer=kernel_initializer,
            bias_initializer=bias_initializer), name, shared_op=shared_op)
        return self._finish(layer)

    def pool2d(self, input: Tensor, kernel_h: int, kernel_w: int, stride_h: int,
               stride_w: int, padding_h: int, padding_w: int,
               pool_type: PoolType = PoolType.POOL_MAX,
               activation: ActiMode = ActiMode.AC_MODE_NONE,
               name: Optional[str] = None) -> Tensor:
        layer = self._add_layer(OperatorType.POOL2D, [input], dict(
            kernel_h=kernel_h, kernel_w=kernel_w, stride_h=stride_h,
            stride_w=stride_w, padding_h=padding_h, padding_w=padding_w,
            pool_type=pool_type, activation=activation), name)
        return self._finish(layer)

    def batch_norm(self, input: Tensor, relu: bool = True,
                   name: Optional[str] = None) -> Tensor:
        layer = self._add_layer(OperatorType.BATCHNORM, [input],
                                dict(relu=relu), name)
        return self._finish(layer)

    def layer_norm(self, input: Tensor, axes: Sequence[int] = (-1,),
                   elementwise_affine: bool = True, eps: float = 1e-5,
                   name: Optional[str] = None) -> Tensor:
        layer = self._add_layer(OperatorType.LAYERNORM, [input], dict(
            axes=tuple(axes), elementwise_affine=elementwise_affine, eps=eps), name)
        return self._finish(layer)

    def group_norm(self, input: Tensor, groups: int, eps: float = 1e-5,
                   affine: bool = True, name: Optional[str] = None) -> Tensor:
        """nn.GroupNorm analog (r4): per-group channel normalization."""
        layer = self._add_layer(OperatorType.GROUPNORM, [input],
                                dict(groups=groups, eps=eps, affine=affine),
                                name)
        return self._finish(layer)

    def rms_norm(self, input: Tensor, eps: float = 1e-6,
                 zero_centered: bool = False,
                 name: Optional[str] = None) -> Tensor:
        """RMSNorm over the last dim (Llama/T5 family; new scope vs the
        reference); ``zero_centered``: the scale is 1 + the leaf."""
        layer = self._add_layer(OperatorType.RMSNORM, [input], dict(
            eps=eps, **({"zero_centered": True} if zero_centered else {})),
            name)
        return self._finish(layer)

    def embedding(self, input: Tensor, num_entries: int, out_dim: int,
                  aggr: AggrMode = AggrMode.AGGR_MODE_NONE,
                  kernel_initializer=None,
                  shared_op: Optional[Tensor] = None,
                  name: Optional[str] = None) -> Tensor:
        layer = self._add_layer(OperatorType.EMBEDDING, [input], dict(
            num_entries=num_entries, out_dim=out_dim, aggr=aggr,
            kernel_initializer=kernel_initializer), name, DataType.FLOAT,
            shared_op=shared_op)
        return self._finish(layer)

    def multihead_attention(self, query: Tensor, key: Tensor, value: Tensor,
                            embed_dim: int, num_heads: int, kdim: int = 0,
                            vdim: int = 0, dropout: float = 0.0, bias: bool = True,
                            qkv_bias: bool = False,
                            add_bias_kv: bool = False, add_zero_attn: bool = False,
                            causal: bool = False, num_kv_heads: int = 0,
                            rope: bool = False, rope_theta: float = 10000.0,
                            kernel_initializer=None,
                            seq_parallel: Optional[str] = None,
                            head_dim: int = 0, window: int = 0,
                            block_diffusion=None, rope_wrap: int = 0,
                            qk_norm: bool = False, qk_norm_eps: float = 1e-6,
                            q_lora_rank: int = 0, kv_lora_rank: int = 0,
                            qk_rope_head_dim: int = 0,
                            latent_norm_eps: float = 1e-6,
                            rope_whole_head: bool = False,
                            gate: bool = False,
                            gate_activation: str = "softplus",
                            partial_rotary_factor: float = 1.0,
                            rope_scaling: Optional[dict] = None,
                            differential: bool = False,
                            lambda_init: float = 0.8,
                            lambda_scale: float = 1.0,
                            diff_norm_eps: float = 1e-5,
                            kv_given: bool = False,
                            export_kv: bool = False,
                            sparse_index=None,
                            indexer_dtype: str = "float32",
                            mrope_section=None, mrope_positions=None,
                            index_loss: bool = True,
                            lane_gate: bool = False,
                            qk_norm_zero_centered: bool = False,
                            name: Optional[str] = None) -> Tensor:
        """``seq_parallel='seq'`` runs the attention core as ring attention
        over that mesh axis (context parallelism for long sequences).
        ``head_dim`` is the width of a head where it is not
        ``embed_dim // num_heads`` (a few wide heads on a model width they
        do not divide; the softmax scale is ``head_dim ** -0.5``).
        ``window`` (with ``causal``): a query sees its last ``window``
        keys, itself among them, and no key at distance ``window`` or
        more; the blocked flash kernels skip the blocks that leaves
        empty. ``block_diffusion=(L, B)`` (not with ``causal``): the
        sequence is a noised copy of an L-token sample and then the clean
        one, in blocks of B; a noised block sees itself and the clean
        blocks before it, a clean block the clean ones up to itself.
        ``rope_wrap``: rotary positions repeat with this period (both
        copies at positions 0..L-1). ``qk_norm``: RMS norm of every query
        and key head over ``head_dim``, with a learned scale each, ahead
        of the rotary embedding. ``kv_lora_rank`` (with ``q_lora_rank``,
        ``qk_rope_head_dim``; causal self-attention, no bias): latent
        attention. Queries and keys/values come out of low-rank latents
        with an RMS norm (``latent_norm_eps``) on each; a head's query
        and key are ``head_dim`` lanes that are not rotated and
        ``qk_rope_head_dim`` that are (``rope_theta``, over adjacent
        pairs), the rotated key ONE vector a position
        for all heads; values are ``head_dim`` wide (``rope_whole_head``
        is a control: every lane of a head rotated). ``gate``: one
        scalar a head and position, softplus(x w_gate) in float32 (leaf
        ``w_gate`` [embed_dim, num_heads]; ``gate_activation`` "sigmoid"
        is a control), times the head's output ahead of the output
        projection. ``partial_rotary_factor``: rotary over the first
        ``head_dim * factor`` lanes of every head, the rest pass.
        ``rope_scaling``: a public config's ``rope_type`` "yarn" keys
        (``factor``, ``original_max_position_embeddings``, ``beta_fast``,
        ``beta_slow``, ``attention_factor``) for the frequency table
        (``ops.attention.rotary_frequencies``). ``differential``: the
        heads in pairs, two softmax maps a pair, A1 - lambda A2 on the
        pair's values, a norm of the pair's 2 * head_dim lanes
        (``diff_norm_eps``), lambda from four learned vectors and
        ``lambda_init`` (`ops/attention.py`; ``lambda_scale`` 0 is a
        control: the second map weighs nothing). ``kv_given``: ``key`` and
        ``value`` are [B, S, num_kv_heads * head_dim], ALREADY projected
        by another op (the op holds wq and wo alone). ``export_kv``: the
        op's projected keys and values are outputs too; the call then
        returns (out, k, v), for ``kv_given`` readers.
        ``sparse_index`` = (index heads, their width, keys a query keeps):
        learned sparse attention (causal self-attention): an indexer
        (leaves ``w_iq``, ``w_ik``, ``w_iw``, a LayerNorm of its one key)
        scores every causal pair from a DETACHED copy of the input, a
        query keeps its best keys, the main softmax runs over them alone,
        and the indexer's loss, the KL divergence from the main
        attention's head-summed probabilities to its own softmax over
        the kept keys, joins the step's loss (``ops/attention.py``
        `_forward_sparse`). ``indexer_dtype``: the operands of the
        index products ("float32" at `highest`, or "bfloat16").
        ``mrope_section``: the rotary pairs each of three position
        streams turns; ``mrope_positions`` [3][S]: the streams where
        they differ (static; None: all three the token's index, which is
        the plain rotation). ``index_loss`` False leaves the indexer's
        loss out of the step's."""
        shared = {k: v for k, v in (("differential", differential),
                                    ("kv_given", kv_given),
                                    ("export_kv", export_kv)) if v}
        if sparse_index:
            shared.update(
                sparse_index=tuple(int(n) for n in sparse_index),
                **({"indexer_dtype": indexer_dtype}
                   if indexer_dtype != "float32" else {}),
                **({"index_loss": False} if not index_loss else {}))
        if mrope_section:
            shared.update(mrope_section=tuple(mrope_section), **(
                {"mrope_positions": tuple(tuple(int(p) for p in row)
                                          for row in mrope_positions)}
                if mrope_positions is not None else {}))
        if differential:
            shared.update(lambda_init=lambda_init,
                          diff_norm_eps=diff_norm_eps,
                          **({"lambda_scale": lambda_scale}
                             if lambda_scale != 1.0 else {}))
        latent = dict(q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
                      qk_rope_head_dim=qk_rope_head_dim,
                      latent_norm_eps=latent_norm_eps,
                      **({"rope_whole_head": True} if rope_whole_head
                         else {})) if kv_lora_rank else {}
        layer = self._add_layer(OperatorType.MULTIHEAD_ATTENTION,
                                [query, key, value], dict(
            embed_dim=embed_dim, num_heads=num_heads, kdim=kdim or embed_dim,
            vdim=vdim or embed_dim, dropout=dropout, bias=bias,
            qkv_bias=qkv_bias, causal=causal,
            num_kv_heads=num_kv_heads or num_heads, rope=rope,
            rope_theta=rope_theta,
            kernel_initializer=kernel_initializer, seq_parallel=seq_parallel,
            **({"head_dim": head_dim} if head_dim else {}),
            **({"window": window} if window else {}),
            **({"block_diffusion": tuple(block_diffusion)}
               if block_diffusion else {}),
            **({"rope_wrap": rope_wrap} if rope_wrap else {}),
            **({"qk_norm": True, "qk_norm_eps": qk_norm_eps}
               if qk_norm else {}),
            **({"gate": True} if gate else {}),
            **({"lane_gate": True} if lane_gate else {}),
            **({"qk_norm_zero_centered": True}
               if qk_norm and qk_norm_zero_centered else {}),
            **({"gate_activation": gate_activation}
               if gate_activation != "softplus" else {}),
            **({"partial_rotary_factor": partial_rotary_factor}
               if partial_rotary_factor != 1.0 else {}),
            **({"rope_scaling": dict(rope_scaling)}
               if rope_scaling else {}), **shared, **latent), name)
        return self._finish(layer)

    def ssm_mixer(self, input: Tensor, num_heads: int, head_dim: int,
                  state_size: int, n_groups: int = 1, conv_kernel: int = 4,
                  chunk_size: int = 128, eps: float = 1e-5,
                  time_step_min: float = 1e-3, time_step_max: float = 1e-1,
                  time_step_floor: float = 1e-4, kernel_initializer=None,
                  name: Optional[str] = None) -> Tensor:
        """Mamba-2 mixer over [B, S, E] (ops/ssm.py): input projection,
        causal depthwise convolution, the chunked state-space scan, the
        gated group RMSNorm, output projection."""
        layer = self._add_layer(OperatorType.SSM_MIXER, [input], dict(
            num_heads=num_heads, head_dim=head_dim, state_size=state_size,
            n_groups=n_groups, conv_kernel=conv_kernel,
            chunk_size=chunk_size, eps=eps, time_step_min=time_step_min,
            time_step_max=time_step_max, time_step_floor=time_step_floor,
            kernel_initializer=kernel_initializer), name)
        return self._finish(layer)

    def mamba_mixer(self, input: Tensor, state_size: int = 16,
                    conv_kernel: int = 4, expand: int = 2, dt_rank: int = 0,
                    export_memory: bool = False, export_gated: bool = False,
                    time_step_min: float = 1e-3, time_step_max: float = 1e-1,
                    kernel_initializer=None, name: Optional[str] = None):
        """Mamba-1 mixer over [B, S, E] (ops/ssm.py `MambaMixer`): input
        projection to ``expand * E`` channels and their gate, causal
        depthwise convolution, the selective scan (a decay a channel AND
        state: one Pallas kernel each way), the gate, output projection.
        ``dt_rank`` 0 is ceil(E / 16). With ``export_memory`` the call
        returns (out, memory): the scan's output before its gate, [B, S,
        expand * E], for the layers that read it (``export_gated`` is a
        control: the output AFTER the gate)."""
        layer = self._add_layer(OperatorType.MAMBA_MIXER, [input], dict(
            d_inner=expand * input.shape[-1], state_size=state_size,
            conv_kernel=conv_kernel, dt_rank=dt_rank,
            time_step_min=time_step_min, time_step_max=time_step_max,
            kernel_initializer=kernel_initializer,
            **({"export_memory": True} if export_memory else {}),
            **({"export_gated": True} if export_gated else {})), name)
        return self._finish(layer)

    def delta_mixer(self, input: Tensor, num_key_heads: int,
                    num_value_heads: int, key_head_dim: int,
                    value_head_dim: int, conv_kernel: int = 4,
                    chunk_size: int = 128, eps: float = 1e-6,
                    kernel_initializer=None,
                    name: Optional[str] = None) -> Tensor:
        """Gated delta-rule mixer over [B, S, E] (ops/delta_rule.py):
        the projections to q, k, v, the output gate z and the rates, a
        causal depthwise convolution with SiLU over q, k, v, the heads'
        L2 norms, the chunked delta rule (a key head serves
        ``num_value_heads / num_key_heads`` value heads), the gated head
        norm, output projection."""
        layer = self._add_layer(OperatorType.DELTA_MIXER, [input], dict(
            num_key_heads=num_key_heads, num_value_heads=num_value_heads,
            key_head_dim=key_head_dim, value_head_dim=value_head_dim,
            conv_kernel=conv_kernel, chunk_size=chunk_size, eps=eps,
            kernel_initializer=kernel_initializer), name)
        return self._finish(layer)

    def short_conv(self, input: Tensor, kernel: int = 3,
                   output_gate: bool = True, kernel_initializer=None,
                   name: Optional[str] = None) -> Tensor:
        """Gated short convolution over [B, S, E] (ops/short_conv.py):
        [B ; C ; x] = h W_in, a causal depthwise convolution of
        ``kernel`` taps over B * x, the gate C, then W_out; no bias, no
        activation. ``output_gate=False`` leaves C out (a control)."""
        layer = self._add_layer(OperatorType.SHORT_CONV, [input], dict(
            kernel=kernel, kernel_initializer=kernel_initializer,
            **({} if output_gate else {"output_gate": False})), name)
        return self._finish(layer)

    def hc_pre(self, stream: Tensor, streams: int,
               sinkhorn_iters: int = 20, eps: float = 1e-6,
               clamp=(-30.0, 30.0), name: Optional[str] = None):
        """The read half of a hyper-connection (ops/hyper_connection.py)
        over a residual stream [B, S, n*C] of ``streams`` = n copies:
        returns (h [B, S, C], the branch's input; maps [B, S, 128], the
        three mixing maps a position; the stream, handed through), for
        ``hc_post`` to read the last two."""
        layer = self._add_layer(OperatorType.HC_PRE, [stream], dict(
            streams=streams, sinkhorn_iters=sinkhorn_iters, eps=eps,
            clamp_min=clamp[0], clamp_max=clamp[1]), name)
        return self._finish(layer)

    def hc_post(self, stream: Tensor, output: Tensor, maps: Tensor,
                streams: int, name: Optional[str] = None) -> Tensor:
        """The write half: the new stream X'[i] = sum_j H_res[i, j] X[j]
        + H_post[i] y from ``hc_pre``'s stream and maps and the branch's
        output y [B, S, C]."""
        layer = self._add_layer(OperatorType.HC_POST, [stream, output, maps],
                                dict(streams=streams), name)
        return self._finish(layer)

    def moe_layer(self, input: Tensor, n_experts: int, k: int,
                  hidden_size: int, shared_width: int = 0,
                  experts_held: int = 0, expert_offset: int = 0,
                  routed_scaling: float = 1.0, norm_topk: bool = True,
                  slot_slack: float = 0.5, kernel_initializer=None,
                  scoring: str = "sigmoid", gated: bool = False,
                  router_input: Optional[Tensor] = None,
                  activation: str = "relu", shared_gate: bool = False,
                  name: Optional[str] = None) -> Tensor:
        """Dropless mixture-of-experts layer over [B, S, D] with top-k
        routing over all ``n_experts``, computing the part of the
        ``experts_held`` experts from ``expert_offset`` (all of them by
        default) and a shared expert (ops/experts.py ``MoELayer``).
        ``scoring``: "sigmoid" scores with a score-correction bias, or
        "softmax" over the chosen logits; ``gated``: experts of three
        matrices, down(act(gate(x)) * up(x)) with ``activation`` "relu"
        or "silu"; ``router_input``: a second tensor of the input's shape
        that the router reads instead; ``shared_gate``: the shared
        expert's output times sigmoid(x w), one scalar a position (leaf
        ``w_shared_gate`` [D, 1])."""
        extra = {k_: v for k_, v in (("scoring", scoring), ("gated", gated),
                                     ("activation", activation),
                                     ("shared_gate", shared_gate))
                 if v not in ("sigmoid", False, "relu")}
        layer = self._add_layer(
            OperatorType.MOE_LAYER,
            [input] + ([router_input] if router_input is not None else []),
            dict(n_experts=n_experts, k=k, hidden_size=hidden_size,
                 shared_width=shared_width,
                 experts_held=experts_held or n_experts,
                 expert_offset=expert_offset, routed_scaling=routed_scaling,
                 norm_topk=norm_topk, slot_slack=slot_slack,
                 kernel_initializer=kernel_initializer, **extra), name)
        return self._finish(layer)

    # ---- elementwise -------------------------------------------------------
    def _unary(self, op_type, x, name=None, scalar=None, inplace=False):
        layer = self._add_layer(op_type, [x], dict(scalar=scalar, inplace=inplace), name)
        return self._finish(layer)

    def _binary(self, op_type, a, b, name=None):
        layer = self._add_layer(op_type, [a, b], {}, name)
        return self._finish(layer)

    def exp(self, x, name=None): return self._unary(OperatorType.EXP, x, name)
    def sin(self, x, name=None): return self._unary(OperatorType.SIN, x, name)
    def cos(self, x, name=None): return self._unary(OperatorType.COS, x, name)
    def relu(self, x, inplace=True, name=None): return self._unary(OperatorType.RELU, x, name, inplace=inplace)
    def gelu(self, x, name=None): return self._unary(OperatorType.GELU, x, name)
    def sigmoid(self, x, name=None): return self._unary(OperatorType.SIGMOID, x, name)
    def tanh(self, x, name=None): return self._unary(OperatorType.TANH, x, name)
    def elu(self, x, inplace=True, name=None): return self._unary(OperatorType.ELU, x, name, inplace=inplace)
    def rsqrt(self, x, name=None): return self._unary(OperatorType.RSQRT, x, name)
    def identity(self, x, name=None): return self._unary(OperatorType.IDENTITY, x, name)
    def pow(self, x, exponent, name=None): return self._unary(OperatorType.POW, x, name, scalar=exponent)
    def scalar_multiply(self, x, scalar, inplace=True, name=None):
        return self._unary(OperatorType.SCALAR_MULTIPLY, x, name, scalar=scalar, inplace=inplace)
    def scalar_add(self, x, scalar, inplace=True, name=None):
        return self._unary(OperatorType.SCALAR_ADD, x, name, scalar=scalar, inplace=inplace)
    def scalar_sub(self, x, scalar, inplace=True, name=None):
        return self._unary(OperatorType.SCALAR_SUB, x, name, scalar=scalar, inplace=inplace)
    def scalar_true_divide(self, x, scalar, inplace=True, name=None):
        return self._unary(OperatorType.SCALAR_TRUE_DIV, x, name, scalar=scalar, inplace=inplace)

    def add(self, a, b, name=None): return self._binary(OperatorType.EW_ADD, a, b, name)
    def subtract(self, a, b, name=None): return self._binary(OperatorType.EW_SUB, a, b, name)
    def multiply(self, a, b, name=None): return self._binary(OperatorType.EW_MUL, a, b, name)
    def divide(self, a, b, name=None): return self._binary(OperatorType.EW_DIV, a, b, name)
    def max(self, a, b, name=None): return self._binary(OperatorType.EW_MAX, a, b, name)
    def min(self, a, b, name=None): return self._binary(OperatorType.EW_MIN, a, b, name)

    # ---- shape / misc ------------------------------------------------------
    def concat(self, tensors: Sequence[Tensor], axis: int, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.CONCAT, list(tensors), dict(axis=axis), name)
        return self._finish(layer)

    def split(self, input: Tensor, sizes, axis: int, name=None):
        if isinstance(sizes, int):
            sizes = [input.shape[axis] // sizes] * sizes
        layer = self._add_layer(OperatorType.SPLIT, [input],
                                dict(sizes=tuple(sizes), axis=axis), name)
        return self._finish(layer)

    def reshape(self, input: Tensor, shape, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.RESHAPE, [input], dict(shape=tuple(shape)), name)
        return self._finish(layer)

    def transpose(self, input: Tensor, perm, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.TRANSPOSE, [input], dict(perm=tuple(perm)), name)
        return self._finish(layer)

    def flat(self, input: Tensor, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.FLAT, [input], {}, name)
        return self._finish(layer)

    def reverse(self, input: Tensor, axis: int, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.REVERSE, [input], dict(axis=axis), name)
        return self._finish(layer)

    def cast(self, input: Tensor, dtype: DataType, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.CAST, [input], dict(dtype=dtype), name, dtype)
        return self._finish(layer)

    def dropout(self, input: Tensor, rate: float = 0.5, seed: int = 0, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.DROPOUT, [input], dict(rate=rate, seed=seed), name)
        return self._finish(layer)

    def softmax(self, input: Tensor, axis: int = -1, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.SOFTMAX, [input], dict(axis=axis), name)
        return self._finish(layer)

    def gather(self, input: Tensor, index: Tensor, axis: int = 0, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.GATHER, [input, index], dict(axis=axis), name)
        return self._finish(layer)

    def batch_matmul(self, a: Tensor, b: Tensor, a_seq_length_dim: int = -1,
                     b_seq_length_dim: int = -1, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.BATCHMATMUL, [a, b], dict(
            a_seq_length_dim=a_seq_length_dim, b_seq_length_dim=b_seq_length_dim), name)
        return self._finish(layer)

    def reduce_sum(self, input: Tensor, axes, keepdims: bool = False, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.REDUCE_SUM, [input],
                                dict(axes=tuple(axes), keepdims=keepdims), name)
        return self._finish(layer)

    def reduce_max(self, input: Tensor, axes, keepdims: bool = False, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.REDUCE_MAX, [input],
                                dict(axes=tuple(axes), keepdims=keepdims), name)
        return self._finish(layer)

    def log(self, x, name=None):
        return self._unary(OperatorType.LOG, x, name)

    def constant(self, value, name=None, trainable=False) -> Tensor:
        """Embedded constant tensor (fx get_attr buffers, masks, tables).

        trainable=True makes it a leaf parameter (a bare learned tensor
        used directly in forward, e.g. a positional embedding) that the
        optimizer updates, with `value` as the initial value."""
        import numpy as _np
        layer = self._add_layer(OperatorType.CONST, [],
                                dict(value=_np.asarray(value),
                                     trainable=bool(trainable)), name)
        return self._finish(layer)

    def where(self, cond: Tensor, a: Tensor, b: Tensor, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.WHERE, [cond, a, b], {}, name)
        return self._finish(layer)

    def expand(self, input: Tensor, shape, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.EXPAND, [input],
                                dict(shape=tuple(shape)), name)
        return self._finish(layer)

    def einsum(self, equation: str, tensors: Sequence[Tensor], name=None) -> Tensor:
        layer = self._add_layer(OperatorType.EINSUM, list(tensors),
                                dict(equation=equation), name)
        return self._finish(layer)

    def mean(self, input: Tensor, dims, keepdims: bool = False, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.MEAN, [input],
                                dict(axes=tuple(dims), keepdims=keepdims), name)
        return self._finish(layer)

    def top_k(self, input: Tensor, k: int, sorted: bool = True, name=None):
        layer = self._add_layer(OperatorType.TOPK, [input], dict(k=k, sorted=sorted), name)
        return self._finish(layer)

    def arg_top_k(self, input: Tensor, k: int, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.ARG_TOPK, [input], dict(k=k), name)
        return self._finish(layer)

    # ---- MoE ---------------------------------------------------------------
    def group_by(self, input: Tensor, assign: Tensor, n: int, alpha: float = 1.0,
                 name=None):
        layer = self._add_layer(OperatorType.GROUP_BY, [input, assign],
                                dict(n=n, alpha=alpha), name)
        return self._finish(layer)

    def aggregate(self, inputs: Sequence[Tensor], n: int, lambda_bal: float = 0.0,
                  name=None) -> Tensor:
        layer = self._add_layer(OperatorType.AGGREGATE, list(inputs),
                                dict(n=n, lambda_bal=lambda_bal), name)
        return self._finish(layer)

    def aggregate_spec(self, inputs: Sequence[Tensor], n: int,
                       lambda_bal: float = 0.0, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.AGGREGATE_SPEC, list(inputs),
                                dict(n=n, lambda_bal=lambda_bal), name)
        return self._finish(layer)

    def cache(self, input: Tensor, num_batches: int = 1, score_fn=None, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.CACHE, [input],
                                dict(num_batches=num_batches, score_fn=score_fn), name)
        return self._finish(layer)

    def experts(self, input: Tensor, gate: Tensor, n: int, k: int,
                hidden_size: int, alpha: float = 2.0,
                lambda_bal: float = 0.0, expert_parallel=None,
                name=None) -> Tensor:
        """Fused MoE experts op: top-k dispatch -> stacked expert FFN ->
        gate-weighted combine. Stacked weights [E, ...] shard over an
        'expert' mesh axis (ops/experts.py; the TPU fusion of the
        reference's per-expert Linear placement, moe.cc:65-83)."""
        layer = self._add_layer(
            OperatorType.EXPERTS, [input, gate],
            dict(n=n, k=k, hidden_size=hidden_size, alpha=alpha,
                 lambda_bal=lambda_bal, expert_parallel=expert_parallel),
            name)
        return self._finish(layer)

    def moe(self, input: Tensor, num_exp: int, num_select: int,
            expert_hidden_size: int, alpha: float = 2.0,
            lambda_bal: float = 0.04, fused: bool = True, name=None) -> Tensor:
        """MoE sugar layer (model.h:507-512): softmax gate -> topk ->
        group_by -> per-expert dense -> aggregate. With ``fused=True``
        (default) the dispatch/experts/combine run as the single Experts op
        the search can expert-shard; ``fused=False`` builds the reference's
        literal subgraph. Note the two forms have different parameter trees
        (stacked [E, ...] weights vs per-expert dense layers), so
        checkpoints are not interchangeable between them."""
        gate = self.dense(input, num_exp, name=f"{name or 'moe'}_gate")
        gate = self.softmax(gate)
        if fused:
            return self.experts(input, gate, num_exp, num_select,
                                expert_hidden_size, alpha, lambda_bal,
                                name=f"{name or 'moe'}_experts")
        topk_out = self.top_k(gate, num_select)
        topk_values, topk_assign = topk_out
        grouped = self.group_by(input, topk_assign, num_exp, alpha,
                                name=f"{name or 'moe'}_group_by")
        if num_exp == 1:
            grouped = (grouped,)
        expert_outs = []
        for e in range(num_exp):
            h = self.dense(grouped[e], expert_hidden_size,
                           activation=ActiMode.AC_MODE_RELU,
                           name=f"{name or 'moe'}_expert{e}_h")
            o = self.dense(h, input.shape[-1], name=f"{name or 'moe'}_expert{e}_o")
            expert_outs.append(o)
        return self.aggregate(
            [topk_values, topk_assign, topk_assign, gate] + expert_outs,
            num_exp, lambda_bal, name=f"{name or 'moe'}_aggregate")

    # ---- parallel (resharding) ops — explicit PCG API ---------------------
    # (src/parallel_ops/*.cc; under XLA these become sharding-constraint
    # boundaries — see flexflow_tpu/ops/parallel_ops.py)
    def repartition(self, input: Tensor, dim: int, degree: int,
                    axis: Optional[str] = None, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.REPARTITION, [input], dict(
            dim=dim, degree=degree,
            axis=axis or ("data" if dim == 0 else "model")), name)
        return self._finish(layer)

    def combine(self, input: Tensor, dim: int, degree: int, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.COMBINE, [input],
                                dict(dim=dim, degree=degree), name)
        return self._finish(layer)

    def replicate(self, input: Tensor, degree: int, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.REPLICATE, [input],
                                dict(degree=degree), name)
        return self._finish(layer)

    def reduction(self, input: Tensor, dim: int, degree: int, name=None) -> Tensor:
        layer = self._add_layer(OperatorType.REDUCTION, [input],
                                dict(dim=dim, degree=degree), name)
        return self._finish(layer)

    # ======================= compile ========================================
    def _materialize_nodes(self, input_shape_overrides=None):
        """Layer -> Op materialization (create_operators_from_layers,
        model.cc:2784). With `input_shape_overrides` ({input layer name ->
        shape}) every intermediate shape is re-derived from the overridden
        INPUT shapes — the seq-length bucket path (FFIterationConfig
        analog, reference config.h:162-167) materializes the same layer
        graph at a shorter sequence this way.

        Returns (nodes, input_names, tensor_ref)."""
        nodes: List[OpNode] = []
        tensor_ref: Dict[int, Tuple] = {}  # Tensor.guid -> ref
        input_names: List[str] = []
        shape_of: Dict[int, Tuple[int, ...]] = {}
        for layer in self.layers:
            if layer.op_type == OperatorType.INPUT:
                t = layer.outputs[0]
                shape_of[t.guid] = tuple(
                    (input_shape_overrides or {}).get(layer.name, t.shape))
                tensor_ref[t.guid] = ("input", layer.name)
                input_names.append(layer.name)
                continue
            op = OpRegistry.create(
                layer, [shape_of.get(t.guid, t.shape) for t in layer.inputs])
            refs = [tensor_ref[t.guid] for t in layer.inputs]
            nodes.append(OpNode(op, refs))
            for i, t in enumerate(layer.outputs):
                tensor_ref[t.guid] = ("op", op.guid, i)
                shape_of[t.guid] = op.output_shapes[i]
        ops = {n.op.name: n.op for n in nodes}
        for op in ops.values():
            error = shared_leaves_error(op, ops)
            if error:
                raise ValueError(f"layer '{op.name}': {error}")
        return nodes, input_names, tensor_ref

    def _select_final_ref(self, nodes, tensor_ref):
        """Output selection (get_final_operator, model.cc:2476): the
        user-designated tensor, else the sole unconsumed output of the
        final node."""
        out_t = getattr(self, "outputs", None)
        if out_t is not None:
            ref = tensor_ref.get(out_t.guid)
            if ref is None or ref[0] != "op":
                raise ValueError("outputs= must be a tensor produced by a layer")
            return (ref[1], ref[2])
        final_node = nodes[-1]
        consumed = {
            tensor_ref[t.guid][1:]
            for layer in self.layers
            for t in layer.inputs
            if tensor_ref.get(t.guid, ("x",))[0] == "op"
        }
        free = [i for i in range(len(final_node.op.output_shapes))
                if (final_node.guid, i) not in consumed]
        return (final_node.guid, free[0] if len(free) == 1 else 0)

    def compile(self, optimizer: Optional[Optimizer] = None,
                loss_type: LossType = LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                metrics: Sequence[MetricsType] = (),
                comp_mode: CompMode = CompMode.TRAINING,
                machine_spec: Optional[MachineSpec] = None,
                mesh=None, outputs=None,
                lint: Optional[str] = None) -> None:
        """Materialize ops, choose a strategy, build jitted executables.

        Mirrors FFModel::compile (model.cc:2802): Layer->Op materialization,
        strategy search (or data-parallel default), then instead of Legion
        region allocation + NCCL bootstrap, mesh construction + sharding
        assignment + jit.

        ``lint`` runs the fflint static verifier (flexflow_tpu/analysis)
        over the materialized PCG + chosen strategy before parameters
        are allocated: "warn" prints the report, "error" raises on any
        ERROR-severity diagnostic. None defers to ``FFConfig.lint``
        (the ``--lint`` flag); the report lands in ``self.lint_report``.
        """
        t_compile = time.perf_counter()
        cfg = self.config
        cfg.computation_mode = comp_mode
        self.optimizer = optimizer or SGDOptimizer(
            lr=cfg.learning_rate, weight_decay=cfg.weight_decay)
        self.loss_type = loss_type

        # --- create_operators_from_layers (model.cc:2784) ---
        nodes, input_names, tensor_ref = self._materialize_nodes()
        if not nodes:
            raise ValueError("model has no layers")
        # --- output selection (get_final_operator, model.cc:2476) ---
        # The model output is the user-designated tensor (compile(outputs=...)
        # or the Tensor marked via self.outputs), falling back to the sole
        # unconsumed output of the final node.
        out_t = outputs if outputs is not None else getattr(self, "outputs", None)
        if isinstance(out_t, (list, tuple)):
            if len(out_t) != 1:
                raise ValueError("exactly one output tensor is supported")
            out_t = out_t[0]
        # persist so recompile_on_condition's re-compile keeps the selection
        self.outputs = out_t
        final_ref = self._select_final_ref(nodes, tensor_ref)
        final_node = next(n for n in nodes if n.guid == final_ref[0])
        self._final_is_softmax = final_node.op.op_type == OperatorType.SOFTMAX
        self.metrics = Metrics(loss_type, list(metrics),
                               preds_are_probs=self._final_is_softmax)

        # --- machine + mesh + strategy -----------------------------------
        # Mirrors the GRAPH_OPTIMIZE task boundary (model.cc:2825): the
        # search owns the mesh factorization (MachineView enumeration
        # analog); without a search budget we take the data-parallel
        # default, optionally with tensor-parallel overrides.
        avail = len(jax.devices())
        # num_devices == 0 means "auto: use every visible device"
        n_dev = min(cfg.num_devices, avail) if cfg.num_devices > 0 else avail
        batch0 = self.input_tensors[0].shape[0] if self.input_tensors else 1
        if machine_spec is None and cfg.machine_model_file:
            # --machine-model-file / --machine-model-version (reference
            # model.cc:3640): version >= 1 selects the file-based model
            from flexflow_tpu.machine import MachineSpec
            machine_spec = MachineSpec.from_file(cfg.machine_model_file)
        elif cfg.machine_model_version > 0 and not cfg.machine_model_file:
            raise ValueError(
                "--machine-model-version > 0 requires --machine-model-file")
        self.machine_spec = machine_spec or detect_machine_spec(
            n_dev, slices=getattr(cfg, "slices", 1))
        self.search_info = None
        self.search_seconds = None  # wall time of the native search, if run
        # search-objective provenance: "step_time" (TRAINING search),
        # "latency" (INFERENCE search), None (no search ran) — recorded
        # in exported strategy files and checkpoint manifests
        self.search_objective = None

        import math as _math
        from flexflow_tpu.parallel.choice import DATA_AXES, plan_execution
        from flexflow_tpu.parallel.strategy import (
            data_parallel_strategy, apply_strategy, tensor_parallel_overrides)
        from flexflow_tpu.search import unity as _unity

        def _heuristic_mesh():
            if cfg.enable_parameter_parallel and not cfg.only_data_parallel:
                mp = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
            else:
                mp = 1
            dp = n_dev // mp
            while dp > 1 and batch0 % dp != 0:
                dp //= 2
            if dp * mp < n_dev:
                warnings.warn(
                    f"compile(): batch size {batch0} does not divide over "
                    f"the {n_dev // mp}-way data axis of {n_dev} devices; "
                    f"running on {dp * mp} devices and leaving "
                    f"{n_dev - dp * mp} idle", RuntimeWarning, stacklevel=3)
            axes = {"data": dp}
            if mp > 1:
                axes["model"] = mp
            return make_mesh(dp * mp, axes)

        def _heuristic_strategy():
            st = data_parallel_strategy(nodes, self.mesh)
            if cfg.enable_parameter_parallel:
                st = tensor_parallel_overrides(nodes, self.mesh, st)
            return st

        self.mesh = mesh
        self.strategy = None
        if cfg.import_strategy_file:
            mesh_axes, self.strategy = _unity.import_strategy_file(
                cfg.import_strategy_file, nodes)
            if self.mesh is None:
                need = _math.prod(mesh_axes.values())
                if need > avail:
                    raise ValueError(
                        f"strategy file {cfg.import_strategy_file} needs a "
                        f"{mesh_axes} mesh ({need} devices) but only {avail} "
                        f"are visible")
                self.mesh = make_mesh(need, mesh_axes)
            # drop spec axes the actual mesh doesn't carry (file may come
            # from a differently-shaped machine)
            valid = set(self.mesh.axis_names)
            for st in self.strategy.values():
                st.output_specs = [
                    (P(*(e if e in valid else None for e in s))
                     if s is not None else None)
                    for s in st.output_specs
                ]
                st.param_specs = {
                    k: P(*(e if e in valid else None for e in v))
                    for k, v in st.param_specs.items()
                }
        elif (cfg.search_budget > 0 and not cfg.only_data_parallel
              and mesh is None):
            try:
                # optimizer-state copies for the simulator's memory/update
                # model: 0 plain SGD, 1 momentum, 2 Adam-family
                from flexflow_tpu.optimizers import SGDOptimizer as _SGD
                if comp_mode == CompMode.INFERENCE:
                    cfg.opt_state_factor = 0.0  # no optimizer state at all
                elif isinstance(self.optimizer, _SGD):
                    cfg.opt_state_factor = (
                        1.0 if self.optimizer.momentum else 0.0)
                else:
                    cfg.opt_state_factor = 2.0
                measured = None
                if cfg.search_measure_ops:
                    # calibrate the cost model with real-device op timings
                    # (analog of the reference's measure_operator_cost pass)
                    from flexflow_tpu.search.profile import microbenchmark
                    measured = microbenchmark(
                        nodes, cache_file=cfg.measured_cache_file)
                t_search = time.perf_counter()
                mesh_axes, self.strategy, self.search_info = _unity.graph_optimize(
                    nodes, self.machine_spec, cfg, n_dev, batch=batch0,
                    measured=measured, final_ref=final_ref)
                self.search_seconds = time.perf_counter() - t_search
                self.search_objective = self.search_info.get("objective")
                self.mesh = make_mesh(_math.prod(mesh_axes.values()), mesh_axes)
                # the substitution engine may have rewritten the graph —
                # run the rewritten node list (strategy is keyed to it)
                if self.search_info.get("rewritten_nodes") is not None:
                    nodes = self.search_info["rewritten_nodes"]
                    if self.search_info.get("final_ref") is not None:
                        final_ref = tuple(self.search_info["final_ref"])
                    fnode = next(n for n in nodes if n.guid == final_ref[0])
                    was_softmax = self._final_is_softmax
                    self._final_is_softmax = (
                        fnode.op.op_type == OperatorType.SOFTMAX)
                    if was_softmax != self._final_is_softmax:
                        self.metrics = Metrics(
                            loss_type, list(metrics),
                            preds_are_probs=self._final_is_softmax)
            except (RuntimeError, ImportError, OSError) as e:
                # a requested search (--budget N) must never silently
                # degrade to data-parallel — a broken libffsearch.so on a
                # bench run would otherwise measure DP as "searched"
                # (VERDICT r4 Weak #6)
                raise RuntimeError(
                    f"auto-parallelization search was requested "
                    f"(search_budget={cfg.search_budget}) but failed: {e}. "
                    f"Rebuild native/libffsearch.so (cd native && make) or "
                    f"drop --budget to run data-parallel.") from e
        if self.mesh is None:
            self.mesh = _heuristic_mesh()
        if self.strategy is None:
            self.strategy = _heuristic_strategy()
        if cfg.export_strategy_file:
            axes_now = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
            _unity.export_strategy_file(cfg.export_strategy_file, axes_now,
                                        self.strategy, nodes,
                                        objective=self.search_objective)
        # multi-slice runtime axis (flexflow_tpu/multislice): --slices N
        # splits the searched 'data' extent into an OUTER 'slice' axis
        # times the within-slice remainder, and extends every
        # 'data'-sharded PartitionSpec across both. The split happens
        # AFTER strategy export (strategy files stay flat/portable) and
        # before apply_strategy. The cross-slice axis carries data
        # parallelism only — matching the native search's
        # inner_axes_cross_slice mesh gate — so its gradient sync rides
        # the WUS bucketed-RS chaining like any other data axis.
        n_slices = max(1, int(getattr(cfg, "slices", 1) or 1))
        if n_slices > 1 and "slice" not in self.mesh.axis_names:
            axes_flat = dict(zip(self.mesh.axis_names,
                                 self.mesh.devices.shape))
            if axes_flat.get("pipe", 1) > 1:
                raise ValueError(
                    "--slices > 1 does not compose with a 'pipe' mesh: "
                    "the cross-slice axis must carry data parallelism only "
                    "(pass --disable-pipeline-parallel, or --slices 1)")
            from flexflow_tpu.multislice import (remap_strategy_for_slices,
                                                 slice_axes)
            sliced_axes = slice_axes(axes_flat, n_slices)
            self.mesh = make_mesh(_math.prod(sliced_axes.values()),
                                  sliced_axes)
            remap_strategy_for_slices(self.strategy)
        apply_strategy(nodes, self.strategy, self.mesh)
        self.op_profile = None
        if cfg.profiling:
            # --profiling (reference model.cc profiling mode wraps every
            # task with timers): microbenchmark each op on the device and
            # report the per-op fwd/bwd table through the RecursiveLogger
            from flexflow_tpu.search.profile import microbenchmark
            from flexflow_tpu.utils.logger import RecursiveLogger
            plog = RecursiveLogger("profiling")
            with plog.enter(f"per-op device microbenchmarks "
                            f"({len(nodes)} ops)"):
                prof = microbenchmark(nodes,
                                      cache_file=cfg.measured_cache_file)
                for node in nodes:
                    f_s = prof.get(f"{node.guid}:fwd")
                    b_s = prof.get(f"{node.guid}:bwd")
                    if f_s is not None:
                        plog.info(f"{node.op.name}: fwd {f_s * 1e6:9.1f}us  "
                                  f"bwd {b_s * 1e6:9.1f}us")
            self.op_profile = prof
        if cfg.export_strategy_computation_graph_file:
            from flexflow_tpu.utils.dot import export_strategy_dot
            export_strategy_dot(nodes, self.mesh,
                                cfg.export_strategy_computation_graph_file,
                                include_costs=cfg.include_costs_dot_graph,
                                search_info=self.search_info)

        compute_dtype = (
            jnp.bfloat16 if (cfg.allow_mixed_precision and
                             self.machine_spec.chip != "cpu-sim")
            else jnp.float32
        )
        # 'slice' is a data axis to the executor: batch sharding, the
        # WUS/optimizer-state sharding, and the bucketed-RS gradient sync
        # all extend across it (the cross-slice sync is the slow DCN leg
        # the '_ovl' pricing hides under backward compute)
        data_axes = tuple(a for a in self.mesh.axis_names if a in DATA_AXES)
        axes_now = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        # what the executor runs of the searched choice dimensions (WUS,
        # overlap, kernel impls, remat), every switch applied
        plan = plan_execution(nodes, self.strategy, axes_now,
                              self.search_info, cfg, comp_mode)
        self.wus_enabled = plan.wus
        self.overlap_enabled = plan.overlap
        self.kernel_choices = plan.kernel_choices
        self.remat_ops = set(plan.remat_ops) if plan.remat_ops else None
        exec_kwargs = dict(compute_dtype=compute_dtype, data_axes=data_axes,
                           final_is_softmax=self._final_is_softmax,
                           fold_conv_bn=cfg.fold_conv_bn, plan=plan)
        # conv-family execution layout (flexflow_tpu/layout.py): NCHW stays
        # the API/PCG boundary, but on TPU the conv family computes
        # channels-last with boundary transposes hoisted to chain edges.
        # The pipeline executor keeps NCHW (its shard_map'd body stacks
        # block params; conv graphs don't pipeline today).
        from flexflow_tpu.layout import propagate_layouts
        self._layout_args = dict(
            mode=getattr(cfg, "conv_compute_layout", "auto"),
            on_tpu=self.machine_spec.chip != "cpu-sim")
        if axes_now.get("pipe", 1) > 1:
            self.layout_info = dict(enabled=False, nhwc_ops=0, transposes=0)
            # GPipe lowering: the search picked a pipe mesh (or the user
            # passed one explicitly) — the repeated-block body executes as
            # an SPMD pipeline (parallel/pipeline_exec.py)
            from flexflow_tpu.parallel.pipeline_exec import (
                PipelineGraphExecutor)
            pinfo = (self.search_info or {}).get("pipeline") \
                if isinstance(self.search_info, dict) else None
            if pinfo is None or pinfo.get("blocks") is None:
                from flexflow_tpu.parallel.pipeline_detect import (
                    detect_repeated_blocks)
                pb = detect_repeated_blocks(nodes)
                if pb is None:
                    raise ValueError(
                        "mesh has a 'pipe' axis but the graph has no "
                        "repeated-block body to pipeline")
                pinfo = dict(blocks=pb,
                             microbatches=cfg.pipeline_microbatches
                             or 2 * axes_now["pipe"])
            # precedence: explicit flags > searched values > auto
            schedule = getattr(cfg, "pipeline_schedule", "auto")
            if schedule == "auto" and pinfo.get("schedule"):
                schedule = pinfo["schedule"]
            microbatches = (cfg.pipeline_microbatches
                            or int(pinfo.get("microbatches") or 0))
            self.executor = PipelineGraphExecutor(
                nodes, input_names, final_ref, self.mesh, loss_type,
                self.metrics, self.optimizer,
                pipe_blocks=pinfo["blocks"],
                microbatches=microbatches,
                schedule=schedule,
                shard_queue=getattr(cfg, "pipeline_shard_queue", True),
                **exec_kwargs)
        else:
            self.layout_info = propagate_layouts(nodes, **self._layout_args)
            self.executor = GraphExecutor(
                nodes, input_names, final_ref, self.mesh, loss_type,
                self.metrics, self.optimizer, **exec_kwargs)
        self.executor.comp_mode = comp_mode
        # names of the equal parts along the sequence that the logits of
        # a weighted loss consist of (a builder sets `loss_parts`)
        self.executor.loss_parts = getattr(self, "loss_parts", None)
        self.executor.exit_entropy_beta = getattr(
            self, "exit_entropy_beta", 0.0)
        t_built = time.perf_counter()
        # --- fflint static verification (flexflow_tpu/analysis) ----------
        # runs BEFORE parameter allocation so an illegal strategy fails
        # fast instead of deep inside jit
        self.lint_report = None
        lint_mode = (lint if lint is not None
                     else getattr(cfg, "lint", "off")) or "off"
        if lint_mode not in ("off", "warn", "error"):
            raise ValueError(
                f"lint expects off|warn|error, got {lint_mode!r}")
        if lint_mode != "off":
            from flexflow_tpu.analysis import lint_model
            self.lint_report = lint_model(self)
            if self.lint_report.diagnostics:
                print(self.lint_report.format_human())
            if lint_mode == "error" and self.lint_report.has_errors():
                raise ValueError(
                    f"fflint: {len(self.lint_report.errors)} error-"
                    f"severity diagnostic(s) — see report above "
                    f"(compile with lint='warn' to proceed anyway)")
        t_linted = time.perf_counter()
        self._rng, sub = jax.random.split(self._rng)
        self.params, self.state = self.executor.init_params_and_state(sub)
        # INFERENCE (ffconst.h:46 CompMode): forward-only executable — no
        # optimizer state is ever allocated
        self.opt_state = (None if comp_mode == CompMode.INFERENCE
                          else self.optimizer.init(self.params))
        # every leaf starts where the train step will leave it, committed
        # to the mesh. An uncommitted scalar (Adam's step count, a
        # BatchNorm statistic) comes back from the first step as a
        # mesh-replicated array; jit takes that for a new input type and
        # answers with a second trace and a second full compile of the
        # step (34 s for the BERT-proxy on a v5e).
        t_init = time.perf_counter()
        replicated = NamedSharding(self.mesh, P())
        self.state, self.opt_state = jax.tree.map(
            lambda a: a if isinstance(a.sharding, NamedSharding)
            else jax.device_put(a, replicated),
            (self.state, self.opt_state))
        # host seconds of this call by phase (nothing is fenced: a phase
        # holds its programs' compile and dispatch, not the device's run).
        # `executor_build_s` is everything up to the built executor less
        # the native search: materialization, strategy, layout, executor
        search_s = self.search_seconds or 0.0
        self.compile_phases = dict(
            search_s=search_s,
            executor_build_s=t_built - t_compile - search_s,
            lint_s=t_linted - t_built,
            param_init_s=t_init - t_linted,
            state_placement_s=time.perf_counter() - t_init)
        self.set_parameter_s = 0.0
        # what the search believes of the strategy it chose, beside the
        # counters an operator reads (a session's header holds the
        # allocator's peak: obs/session.py); nothing without a search
        from flexflow_tpu.obs.inspect import search_predictions
        from flexflow_tpu.obs.registry import get_registry
        for key, value in search_predictions(self).items():
            if value is not None:
                get_registry().gauge(key.replace("search_", "search/", 1),
                                     value)
        self._iter = 0
        self._seq_execs: Dict[int, Any] = {}  # seq-length bucket executors
        self._unpackers: Dict[Tuple, Any] = {}  # staging programs, by shape
        self._declared_seq_cache = -1  # lazily derived (-1 = not yet)

    # ======================= data staging ==================================
    @staticmethod
    def _stages_raw(arr) -> bool:
        """Whether `_shard_batch` hands `arr` to the runtime as its raw
        bytes: every host array of a single-controller run. A device
        array has no host-side conversion to spare, and multi-controller
        staging assembles per-process rows from host arrays."""
        return jax.process_count() == 1 and not isinstance(arr, jax.Array)

    def _unpacker(self, shape: Tuple[int, ...], raw_dtype, dtype, sharding):
        """How a host batch of `shape` and `raw_dtype` is handed over and
        the jitted program that makes of it the array of `dtype` on
        `sharding`: (shards, bounds, unpack), one per key, compiled by the
        first call that stages the shape. Every shard's rows are one run
        of the batch's row-major elements; `bounds` cuts a run into the
        pieces the host hands over one by one."""
        key = (shape, np.dtype(raw_dtype), np.dtype(dtype), sharding)
        plan = self._unpackers.get(key)
        if plan is None:
            shard_shape = sharding.shard_shape(shape)
            rows = shard_shape[0] if shape else 1
            shards = shape[0] // rows if shape else 1
            row = int(np.prod(shape[1:]))
            # whole rows to a piece: the device then shapes each piece on
            # its own (1.6 against 3.4 ms for Inception's batch on a v5e)
            per = max(1, round(_RAW_PIECE_BYTES / max(
                1, row * np.dtype(raw_dtype).itemsize)))
            bounds = [min(r, rows) * row
                      for r in range(0, rows + per, per)]
            k = len(bounds) - 1
            # a batch layout shards dim 0 only: as 1-D, on the same axes
            flat_spec = P(*sharding.spec[:1])

            def unpack_batch(*pieces):
                def local(*mine):   # a device's stretches, in order
                    return (jnp.concatenate(mine).reshape(shard_shape)
                            .astype(dtype))
                return jax.shard_map(
                    local, mesh=sharding.mesh, in_specs=(flat_spec,) * k,
                    out_specs=sharding.spec)(*pieces)

            # one piece can be donated (JAX matches it to the output by
            # size); several cannot, and are freed as the call returns
            plan = self._unpackers[key] = (
                NamedSharding(sharding.mesh, flat_spec), shards, bounds,
                jax.jit(unpack_batch, out_shardings=sharding,
                        donate_argnums=(0,) if k == 1 else ()))
        return plan

    def _shard_batch(self, arr: np.ndarray, cast: bool = False,
                     inputs: bool = False) -> jax.Array:
        # inputs stage on the executor's batch layout (pipe-sharded under
        # the pipeline's sharded microbatch queue); labels stay on the
        # data-sharded loss layout
        sharding = (self.executor.batch_sharding() if inputs
                    else self.executor.label_sharding())
        if not self._stages_raw(arr):
            arr = jnp.asarray(arr)
            if cast and jnp.issubdtype(arr.dtype, jnp.floating):
                arr = arr.astype(self.executor.compute_dtype)
            if jax.process_count() > 1:
                # multi-controller SPMD: `arr` is the rows THIS host feeds;
                # assemble the global batch from per-process shards
                from flexflow_tpu import distributed as _dist
                return _dist.stage_local_batch(np.asarray(arr), sharding)
            return jax.device_put(arr, sharding)
        # The host hands over the batch's row-major bytes as 1-D views
        # (no copy of a contiguous slice), straight onto the target
        # devices. A 1-D array's device layout IS its byte order, so the
        # runtime's host threads copy where a shaped array has them tile,
        # pad and transpose (six threads x 25 ms for a float32
        # [256, 3, 299, 299] on a v5e host, 120 thread-ms against 34); in
        # pieces, because one piece is copied by one thread before its
        # DMA starts (24 ms, then the wire's 20). Shape, cast and tiled
        # layout are made on the device, at HBM speed.
        arr = np.asarray(arr)
        raw_dtype = jax.dtypes.canonicalize_dtype(arr.dtype)
        flat = np.ascontiguousarray(arr, dtype=raw_dtype).reshape(-1)
        dtype = raw_dtype
        if cast and jnp.issubdtype(dtype, jnp.floating):
            # activations flow in the compute dtype end-to-end (bf16 on
            # TPU): ops emit outputs in their input dtype, so casting once
            # at the graph boundary halves every activation's HBM traffic.
            # Labels are staged without cast (loss math is f32).
            dtype = self.executor.compute_dtype
        flat_sharding, shards, bounds, unpack = self._unpacker(
            arr.shape, raw_dtype, dtype, sharding)
        run = bounds[-1]

        def piece(lo, hi):
            def of_shard(index):    # the slice of the piece a device holds
                at = (index[0].start or 0) // (hi - lo) * run
                return flat[at + lo:at + hi]
            return jax.make_array_from_callback(
                (shards * (hi - lo),), flat_sharding, of_shard)

        return unpack(*(piece(lo, hi) for lo, hi in zip(bounds, bounds[1:])))

    def _local_batch_size(self, global_bs: int) -> int:
        """Rows of a `global_bs` batch this process feeds (== global_bs
        single-process)."""
        if jax.process_count() <= 1:
            return global_bs
        from flexflow_tpu import distributed as _dist
        rows, _ = _dist.local_batch_rows(self.executor.batch_sharding(),
                                         global_bs)
        return rows

    def _stage_inputs(self, xs) -> Dict[str, jax.Array]:
        if not isinstance(xs, (list, tuple)):
            xs = [xs]
        names = self.executor.input_names
        if len(xs) != len(names):
            raise ValueError(f"model has {len(names)} inputs, got {len(xs)} arrays")
        return {n: self._shard_batch(x, cast=True, inputs=True)
                for n, x in zip(names, xs)}

    # ======================= train / eval loops ============================
    def _make_tracer(self, trace_dir, run_name: str):
        """Tracer for one fit/evaluate call. An open trace session
        (``obs.start_trace``) wins: the call records into the session's
        tracer and makes none of its own. Otherwise the explicit
        ``trace_dir`` argument wins over ``Config --trace-dir``; both
        unset returns the shared no-op (flexflow_tpu/obs — zero overhead
        path)."""
        from flexflow_tpu.obs import (make_tracer, model_context,
                                      session_tracer)
        tracer = session_tracer() or make_tracer(
            trace_dir or self.config.trace_dir, run_name=run_name)
        if tracer.active:
            tracer.set_meta(**model_context(self))
        return tracer

    def _make_capture(self, tracer, profile_steps):
        """Windowed jax.profiler device-trace capture (obs/devtrace):
        the explicit ``profile_steps`` argument wins over ``Config
        --profile-steps``; both unset (or no active tracer, or a tracer
        that belongs to an open session, which runs the profiler itself)
        returns the shared no-op capture."""
        from flexflow_tpu.obs import NULL_CAPTURE, make_capture
        if tracer.active and not tracer.fence:
            return NULL_CAPTURE
        return make_capture(tracer,
                            profile_steps or self.config.profile_steps)

    def _finalize_trace(self, tracer, success: bool = True,
                        devtrace=None) -> None:
        """Export the trace + the compiled-step summary (XLA cost/memory
        analysis, collective census) + the search-drift calibration
        report. Observability failures warn instead of killing the
        training run that produced the data.

        ``devtrace`` (an obs DeviceTraceCapture) is finalized FIRST so
        its device lanes and per-step attribution counters land in the
        exported Perfetto trace, and its measured per-collective times
        join the drift report's census-priced predictions.

        ``success=False`` (the run raised) flushes only the trace and
        counters: the summary/drift reports need a fresh lower+compile
        of the step (AOT inspection cannot reuse the executor's cached
        executable), which is minutes of XLA on TPU and — after an OOM
        — likely to fail again; the trace alone is the diagnosis.

        A session's tracer (``obs.start_trace``) is not finalized here:
        ``obs.stop_trace`` writes it, and writes nothing else."""
        if not tracer.fence:
            return
        import os
        import sys
        from flexflow_tpu.obs import (drift_report, export_step_summary,
                                      get_registry, record_step_metrics,
                                      write_artifact)
        devrep = None
        if devtrace is not None and devtrace.active:
            try:
                devrep = devtrace.finalize(self, tracer)
            except Exception as e:
                print(f"[obs] device-trace attribution failed: {e!r}",
                      file=sys.stderr)
        step_metrics = None
        try:
            step_metrics = record_step_metrics(self, tracer)
        except Exception as e:
            print(f"[obs] step metrics failed: {e!r}", file=sys.stderr)
        if success:
            # predicted-schedule lanes (obs/simtrace): replay the
            # strategy through the native simulator and inject the
            # sim:compute / sim:comms Perfetto lanes BEFORE export so
            # the predicted step sits next to the measured device lanes
            try:
                from flexflow_tpu.obs import write_simtrace
                write_simtrace(self, tracer)
            except Exception as e:
                print(f"[obs] simulated-schedule trace failed: {e!r}",
                      file=sys.stderr)
        try:
            tracer.export()
        except Exception as e:
            print(f"[obs] trace export failed: {e!r}", file=sys.stderr)
        stem = os.path.join(tracer.trace_dir, tracer.file_stem)
        extra = dict(run_name=tracer.run_name, run_seq=tracer.run_seq)
        if (isinstance(self.search_info, dict)
                and self.search_info.get("search_trace")):
            # search provenance (--search-trace): the native trace rides
            # along as its own artifact so calibrate/explain tooling can
            # consume it without re-running the search
            try:
                write_artifact(stem + ".searchtrace.json",
                               dict(self.search_info["search_trace"]),
                               host_id=tracer.host_id, kind="searchtrace",
                               header_extra=extra)
            except Exception as e:
                print(f"[obs] search-trace artifact failed: {e!r}",
                      file=sys.stderr)
        if success:
            summary = None
            try:
                summary = export_step_summary(self, tracer)
            except Exception as e:
                print(f"[obs] step inspection failed: {e!r}",
                      file=sys.stderr)
            try:
                rep = drift_report(
                    self, tracer.step_time_s(),
                    census=(summary or {}).get("collectives"),
                    phase_summary=tracer.phase_summary(),
                    measured_collectives=(devrep or {}).get("collectives"),
                    step_metrics=step_metrics)
                write_artifact(stem + ".drift.json", rep,
                               host_id=tracer.host_id, kind="drift",
                               header_extra=extra)
            except Exception as e:
                print(f"[obs] drift report failed: {e!r}", file=sys.stderr)
        else:
            print(f"[obs] run failed: wrote trace/counters only "
                  f"({tracer.file_stem})", file=sys.stderr)
        try:
            get_registry().export(stem + ".counters.json",
                                  host_id=tracer.host_id)
        except Exception as e:
            print(f"[obs] counter export failed: {e!r}", file=sys.stderr)

    def _make_health(self, tracer, devtrace, run_name: str = "fit"):
        """RuntimeHealth for one fit call (None when supervision is
        off). ``--grace-window`` turns SIGTERM/SIGINT into a graceful
        stop the step loop honors (final checkpoint + trace flush +
        ``PREEMPTED_EXIT``); ``--watchdog-timeout`` starts the
        hung-collective watchdog, whose trip flushes this run's trace
        from the watchdog thread before ``HUNG_EXIT`` — the main
        thread is wedged and will never reach its own finalizer."""
        cfg = self.config
        if cfg.grace_window_s <= 0 and cfg.watchdog_timeout_s <= 0:
            return None
        from flexflow_tpu.runtime_health import RuntimeHealth

        def _flush_trace():
            self._finalize_trace(tracer, success=False, devtrace=devtrace)

        return RuntimeHealth(grace_window_s=cfg.grace_window_s,
                             watchdog_timeout_s=cfg.watchdog_timeout_s,
                             run_name=run_name, finalize_fn=_flush_trace)

    def _make_checkpointer(self, checkpoint_dir, checkpoint_every, resume,
                           run_name: str = "fit", heartbeat=None,
                           state_provider=None):
        """CheckpointManager for one fit call (None when checkpointing
        is off). Explicit arguments win over the ``--checkpoint-*`` /
        ``--resume`` config flags. With resume on, the newest COMPLETE
        checkpoint restores (fail-fast on every rank when the directory
        holds only partial ones) and the returned start step tells the
        epoch loop how many step slots to skip; an empty directory is a
        fresh launch — the same command line serves first start and
        every restart."""
        cfg = self.config
        cdir = checkpoint_dir or cfg.checkpoint_dir
        do_resume = resume if resume is not None else cfg.resume
        every = (checkpoint_every if checkpoint_every is not None
                 else cfg.checkpoint_every)
        if not cdir:
            if do_resume:
                raise ValueError(
                    "resume requested but no checkpoint directory — pass "
                    "fit(checkpoint_dir=...) or --checkpoint-dir")
            if every:
                # a cadence with nowhere to write would train for hours
                # saving nothing — the silent-data-loss launch typo
                raise ValueError(
                    f"checkpoint_every={every} requested but no checkpoint "
                    f"directory — pass fit(checkpoint_dir=...) or "
                    f"--checkpoint-dir")
            return None, 0
        from flexflow_tpu.ckpt import CheckpointManager
        mgr = CheckpointManager(self, cdir, every=every,
                                retain=cfg.checkpoint_retain,
                                async_write=cfg.checkpoint_async,
                                run_name=run_name, heartbeat=heartbeat,
                                state_provider=state_provider)
        start = mgr.resume() if do_resume else 0
        return mgr, start

    def _run_epochs(self, next_batch, num_batches: int, bs: int, epochs: int,
                    verbose: bool, on_epoch_start=None, tracer=None,
                    devtrace=None, ckpt_mgr=None, start_step: int = 0,
                    on_resume=None, health=None) -> float:
        """Shared epoch loop: per-batch jitted step, on-device metric
        accumulation (one host sync per epoch), ELAPSED TIME / THROUGHPUT
        report. ``next_batch(epoch, b)`` -> (inputs dict, labels).

        With an active tracer each step is a span with rng_split /
        dispatch / metric_accumulate phases plus whatever phases the
        ``next_batch`` closure records (fit: sibling data_load /
        device_put spans — disjoint, so phase totals sum to step time
        instead of double-booking H2D under data_load), and each epoch
        ends with a metrics_sync span (the one host fetch of the
        accumulated metrics). A per-call tracer (``fit(trace_dir=...)``,
        ``tracer.fence``) also ends every step in a device_wait phase
        that fences it on the loss — an observer effect that form
        accepts so per-step times mean device time, not async dispatch
        time. A session's tracer (``obs.start_trace``) fences nothing:
        the traced loop is the untraced one.

        ``ckpt_mgr`` (a flexflow_tpu.ckpt.CheckpointManager) saves every
        ``checkpoint_every`` iterations (blocking only for the local
        device→host shard snapshot; file writes and the manifest commit
        run on its writer thread) and once more at the end. A resumed
        run passes ``start_step``: the first ``start_step`` step slots
        of the epoch grid are skipped — the slots the checkpoint already
        covers — so epochs/batch indices line up with the uninterrupted
        schedule. Skipped slots cost NOTHING: loaders with positional
        state are repositioned by the one-shot ``on_resume(start_step)``
        callback (fit_loader seeks its loaders there) instead of
        fetching-and-discarding every covered batch.

        ``health`` (flexflow_tpu.runtime_health.RuntimeHealth) is fed
        once per finished step: the watchdog heartbeat, plus the
        preemption check — a pending SIGTERM/maintenance notice raises
        ``Preempted`` AFTER the in-flight step, at which point this
        loop cuts the grace-window checkpoint (``ckpt_mgr.finalize``)
        and lets the exception carry ``PREEMPTED_EXIT`` out."""
        from flexflow_tpu.ckpt import faults as _faults
        from flexflow_tpu.obs import NULL_CAPTURE, NULL_TRACER
        from flexflow_tpu.obs.session import step_keeper
        tracer = tracer or NULL_TRACER
        devtrace = devtrace or NULL_CAPTURE
        train_step = self.executor.make_train_step()
        # None unless a session with the profiler is open and has not
        # seen this executor's step yet
        keep_step = step_keeper(self)
        self._refresh_compute_params()
        tracer.setup_done()
        start = time.time()
        loss = None
        executed = 0
        step_idx = -1  # global step index, the --profile-steps coordinate
        for epoch in range(epochs):
            if on_epoch_start is not None:
                on_epoch_start()
            self._metrics_acc = PerfMetrics()
            mtotals, step_counts = None, []
            epoch_executed = 0
            for b in range(num_batches):
                step_idx += 1
                if step_idx < start_step:
                    # this step slot is inside the restored checkpoint
                    continue
                if step_idx == start_step and start_step and on_resume:
                    # one-shot loader reposition: runs after this
                    # epoch's on_epoch_start reset, right before the
                    # first post-resume fetch
                    on_resume(start_step)
                # devtrace OUTSIDE tracer.step: the profiler session
                # start/stop at the window edges costs whole seconds on
                # some backends — observability overhead, not step time,
                # so it must not land in the step span the percentile
                # reservoir observes (ISSUE 8 satellite: the 17 s p99)
                with devtrace.step(step_idx), tracer.step():
                    inputs, labels = next_batch(epoch, b)
                    with tracer.phase("rng_split"):
                        self._rng, sub = jax.random.split(self._rng)
                    with tracer.phase("dispatch"):
                        if keep_step is not None:
                            # shapes and shardings of this call, for the
                            # session's join table (obs/step_scopes.py)
                            keep_step(train_step, (
                                self.params, self.opt_state, self.state,
                                inputs, labels, sub))
                            keep_step = None
                        (self.params, self.opt_state, self.state, loss,
                         mvals) = train_step(
                            self.params, self.opt_state, self.state,
                            inputs, labels, sub)
                    self._iter += 1
                    with tracer.phase("metric_accumulate"):
                        # the ops' integer counts stay a step's own
                        # until the epoch's fetch (an epoch's sum of
                        # pairs passes what int32 holds)
                        counts = {k: mvals.pop(k) for k in [
                            k for k, v in mvals.items() if "/" in k
                            and jnp.issubdtype(v.dtype, jnp.integer)]}
                        if counts:
                            step_counts.append(counts)
                        largest = self.executor.max_counters
                        mtotals = (mvals if mtotals is None else {
                            k: (jnp.maximum if k in largest else jnp.add)(
                                mtotals[k], v) for k, v in mvals.items()})
                    if tracer.fence:
                        with tracer.phase("device_wait"):
                            jax.block_until_ready(loss)
                executed += 1
                epoch_executed += 1
                # fault-injection seam (FFS_FAULT kill_host / sigterm /
                # hang); no-op when the env is unset
                _faults.step_hook(step_idx)
                if health is not None:
                    # watchdog heartbeat + preemption check. A pending
                    # notice surfaces HERE — after the in-flight step —
                    # so the grace checkpoint is a consistent post-step
                    # state the auto-resumed run continues bit-exactly.
                    try:
                        health.step_done(step_idx)
                    except BaseException:
                        if ckpt_mgr is not None:
                            t_grace = time.perf_counter()
                            with tracer.phase("grace_checkpoint"):
                                ckpt_mgr.finalize(
                                    elapsed_s=time.time() - start,
                                    steps=executed)
                            from flexflow_tpu.obs.registry import \
                                get_registry
                            get_registry().gauge(
                                f"{ckpt_mgr.run_name}/grace_checkpoint_s",
                                time.perf_counter() - t_grace)
                        raise
                if ckpt_mgr is not None:
                    if ckpt_mgr.should_save(self._iter):
                        with tracer.phase("checkpoint"):
                            ckpt_mgr.save(self._iter)
                    else:
                        ckpt_mgr.note_step(self._iter)
            with tracer.phase("metrics_sync", epoch=epoch):
                if epoch_executed:
                    # a resumed run's partial epoch accumulated only the
                    # EXECUTED steps' totals — average over those, not
                    # the full grid
                    totals = dict(mtotals or {})
                    # what the ops counted (executor counters, named
                    # "<layer>/<what>") came with the metrics: one fetch
                    counted = {k: totals.pop(k) for k in list(totals)
                               if "/" in k}
                    for step in jax.device_get(step_counts):
                        for k, v in step.items():   # as Python ints
                            counted[k] = counted.get(k, 0) + sum(
                                int(n) for n in v)
                    self._metrics_acc.update(totals, bs * epoch_executed)
                    self._last_loss = float(loss)
                    if counted:
                        self._publish_op_counters(counted)
            if verbose and epoch_executed:
                # fully-skipped epochs (inside the restored checkpoint)
                # have nothing to report
                rep = self._metrics_acc.report()
                print(f"epoch {epoch}: loss={self._last_loss:.4f} " +
                      " ".join(f"{k}={v:.4f}" for k, v in rep.items()))
        elapsed = time.time() - start
        if ckpt_mgr is not None:
            # final save + durability barrier + goodput gauge: the run
            # must not be reported done while a commit is still in flight
            ckpt_mgr.finalize(elapsed_s=elapsed, steps=executed)
        tracer.annotate(steps=executed)
        # throughput counts only the samples this run actually processed
        # (a resume skips the checkpoint-covered step slots in ~0 time)
        thr = bs * executed / elapsed
        if verbose:
            print(f"ELAPSED TIME = {elapsed:.4f}s, THROUGHPUT = {thr:.2f} samples/s")
        return thr

    def _publish_op_counters(self, counted):
        """An epoch's op counters to the registry's gauges and to
        ``self.op_counters``: sums as they are, means over the ops and
        steps that were added up."""
        from flexflow_tpu.executor import COUNT_SUFFIX
        from flexflow_tpu.obs.registry import get_registry
        host = {k: float(v) for k, v in jax.device_get(counted).items()}
        self.op_counters = {}
        for k, v in host.items():
            if k.endswith(COUNT_SUFFIX):
                continue
            n = host.get(k + COUNT_SUFFIX)
            self.op_counters[k] = v / n if n else v
            get_registry().gauge(k, self.op_counters[k])
        # beside what the ops counted on the device, what the trace of
        # their forwards recorded on the host
        self.op_counters.update(
            (k, float(v)) for k, v in self.executor.traced_gauges().items())

    def fit(self, x=None, y=None, batch_size: Optional[int] = None,
            epochs: Optional[int] = None, verbose: bool = True,
            trace_dir: Optional[str] = None,
            profile_steps: Optional[str] = None,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: Optional[int] = None,
            resume: Optional[bool] = None):
        """Keras-style whole-dataset training loop, streaming batches from
        host (base_model.py:376-430 / flexflow_cffi.py:2073-2086).

        ``trace_dir`` (or ``Config --trace-dir``) activates the runtime
        observability subsystem: per-step Chrome-trace/JSONL artifacts,
        a compiled-step summary (XLA FLOPs/bytes/peak memory +
        collective census), and a search-drift calibration report land
        in that directory when the loop finishes.

        ``profile_steps`` (or ``Config --profile-steps``, e.g. "2:4")
        additionally wraps that step window in a ``jax.profiler``
        capture: device compute/collective lanes and per-step
        compute/comms/exposed-comms attribution merge into the same
        trace dir (obs/devtrace).

        ``checkpoint_dir`` + ``checkpoint_every`` (or the
        ``--checkpoint-*`` flags) turn on v2 per-shard async
        checkpointing (flexflow_tpu/ckpt): every N iterations each host
        snapshots its addressable shards (the only blocking cost) and a
        writer thread commits them manifest-last, retaining the newest
        ``--checkpoint-retain`` checkpoints. ``resume`` (or
        ``--resume``) restores the newest complete checkpoint first and
        skips the step slots it covers, so ``epochs`` keeps meaning the
        TOTAL schedule — an interrupted and an uninterrupted run of the
        same command line end bit-identically."""
        epochs = epochs or self.config.epochs
        xs = x if isinstance(x, (list, tuple)) else [x]
        n = xs[0].shape[0]
        bs = batch_size or self.input_tensors[0].shape[0]
        # multi-host: x/y hold this process's rows; each batch takes the
        # local block of the global batch (multi-controller SPMD)
        lbs = self._local_batch_size(bs)
        num_batches = n // lbs
        if num_batches == 0:
            raise ValueError(
                f"dataset of {n} samples is smaller than batch size {lbs}")
        tracer = self._make_tracer(trace_dir, "fit")
        devtrace = self._make_capture(tracer, profile_steps)

        def next_batch(epoch, b):
            sl = slice(b * lbs, (b + 1) * lbs)
            with tracer.phase("data_load"):
                xs_np = [xx[sl] for xx in xs]
                y_np = y[sl]
            with tracer.phase("device_put") as span:
                if span is not None:   # None on the no-op tracer
                    staged = xs_np + [y_np]
                    span.args = dict(
                        bytes=sum(a.nbytes for a in staged),
                        # of them, handed to the runtime unconverted
                        raw_bytes=sum(a.nbytes for a in staged
                                      if self._stages_raw(a)))
                return (self._stage_inputs(xs_np),
                        self._shard_batch(y_np))

        # a traced run that dies mid-training (OOM, NaN assert, ^C,
        # preemption) — or at resume, against a missing/corrupt
        # checkpoint — still flushes its trace: that trace is the
        # diagnosis
        run_name = tracer.run_name if tracer.fence else "fit"
        health = None
        try:
            with tracer.call("fit", epochs=epochs):
                health = self._make_health(tracer, devtrace,
                                           run_name=run_name)
                if health is not None:
                    health.install()
                ckpt_mgr, start_step = self._make_checkpointer(
                    checkpoint_dir, checkpoint_every, resume,
                    run_name=run_name,
                    heartbeat=(health.heartbeat if health is not None
                               else None))
                out = self._run_epochs(next_batch, num_batches, bs, epochs,
                                       verbose, tracer=tracer,
                                       devtrace=devtrace, ckpt_mgr=ckpt_mgr,
                                       start_step=start_step, health=health)
        except BaseException:
            self._finalize_trace(tracer, success=False, devtrace=devtrace)
            raise
        finally:
            if health is not None:
                health.close()
        self._finalize_trace(tracer, devtrace=devtrace)
        return out

    def fit_loader(self, loaders, epochs: Optional[int] = None,
                   verbose: bool = True, trace_dir: Optional[str] = None,
                   profile_steps: Optional[str] = None,
                   checkpoint_dir: Optional[str] = None,
                   checkpoint_every: Optional[int] = None,
                   resume: Optional[bool] = None):
        """Steady-state training from staged on-device loaders
        (flexflow_tpu.dataloader) — no host→device traffic per step."""
        epochs = epochs or self.config.epochs
        bs = loaders.input_loaders[0].batch_size
        tracer = self._make_tracer(trace_dir, "fit")
        devtrace = self._make_capture(tracer, profile_steps)

        def next_batch(e, b):
            with tracer.phase("data_load"):
                return loaders.next_batch()

        def cursor():
            # the dataloader position, recorded in every manifest: a
            # resume seeks straight here instead of fetching-and-
            # discarding every covered batch (ROADMAP elastic (c))
            nb = loaders.num_batches
            return dict(loader=dict(iteration=int(self._iter),
                                    epoch=int(self._iter // nb),
                                    batch=int(self._iter % nb),
                                    num_batches=int(nb)))

        run_name = tracer.run_name if tracer.fence else "fit"
        health = None
        try:
            with tracer.call("fit", epochs=epochs):
                health = self._make_health(tracer, devtrace,
                                           run_name=run_name)
                if health is not None:
                    health.install()
                ckpt_mgr, start_step = self._make_checkpointer(
                    checkpoint_dir, checkpoint_every, resume,
                    run_name=run_name,
                    heartbeat=(health.heartbeat if health is not None
                               else None),
                    state_provider=cursor)
                # the staged loader advances positional state — a resumed
                # run repositions it once (seek) at the first post-resume
                # slot, paying zero fetches for the covered ones
                out = self._run_epochs(next_batch, loaders.num_batches, bs,
                                       epochs, verbose,
                                       on_epoch_start=loaders.reset,
                                       tracer=tracer, devtrace=devtrace,
                                       ckpt_mgr=ckpt_mgr,
                                       start_step=start_step,
                                       on_resume=lambda s: loaders.seek(
                                           s % loaders.num_batches),
                                       health=health)
        except BaseException:
            self._finalize_trace(tracer, success=False, devtrace=devtrace)
            raise
        finally:
            if health is not None:
                health.close()
        self._finalize_trace(tracer, devtrace=devtrace)
        return out

    # ---- checkpoint / resume (new scope vs reference — SURVEY §5.4) -------
    def save_checkpoint(self, path: str) -> None:
        from flexflow_tpu.checkpoint import save_checkpoint
        save_checkpoint(path, self)

    def load_checkpoint(self, path: str) -> int:
        from flexflow_tpu.checkpoint import load_checkpoint
        return load_checkpoint(path, self)

    def recompile_on_condition(self, recompile_state) -> bool:
        from flexflow_tpu.recompile import recompile_on_condition
        return recompile_on_condition(self, recompile_state)

    def evaluate(self, x=None, y=None, batch_size: Optional[int] = None,
                 trace_dir: Optional[str] = None):
        xs = x if isinstance(x, (list, tuple)) else [x]
        n = xs[0].shape[0]
        bs_report = batch_size or self.input_tensors[0].shape[0]
        bs = self._local_batch_size(bs_report)  # multi-host: x/y are local rows
        if n // bs == 0:
            raise ValueError(
                f"dataset of {n} samples is smaller than batch size {bs}")
        eval_step = self.executor.make_eval_step()
        tracer = self._make_tracer(trace_dir, "evaluate")
        acc = PerfMetrics()
        loss_sum, batches = 0.0, 0
        try:
            with tracer.call("evaluate"):
                for b in range(n // bs):
                    with tracer.step():
                        sl = slice(b * bs, (b + 1) * bs)
                        with tracer.phase("device_put"):
                            inputs = self._stage_inputs(
                                [xx[sl] for xx in xs])
                            labels = self._shard_batch(y[sl])
                        with tracer.phase("dispatch"):
                            loss, logits, mvals = eval_step(
                                self.params, self.state, inputs, labels)
                        with tracer.phase("metrics_sync"):
                            loss_sum += float(loss)
                            batches += 1
                            acc.update({k: v for k, v in mvals.items()},
                                       bs_report)
        finally:
            if tracer.fence:   # a session's tracer is written by stop_trace
                try:
                    tracer.export()
                except Exception as e:
                    import sys
                    print(f"[obs] trace export failed: {e!r}",
                          file=sys.stderr)
        rep = acc.report()
        rep["loss"] = loss_sum / max(batches, 1)
        return rep

    def serve(self, batch_buckets=None, max_wait_ms: float = 5.0,
              search_budget: Optional[int] = None, start: bool = False,
              verbose: bool = False):
        """Production inference serving over this compiled model
        (flexflow_tpu/serve): continuous/dynamic batching into per-
        batch-bucket executors, each with its OWN latency-objective
        searched sharding when ``search_budget`` (default: the
        compile-time ``--budget``) is nonzero and the native search is
        available. Returns a ``ServingEngine``; ``start=True`` also
        spins its background serving thread —

            engine = model.serve(start=True)
            out = engine.submit(sample).wait()

        p50/p99 request latency, queue depth, and batch occupancy land
        in the obs registry under ``serve/*``; ``scripts/serve_bench.py``
        drives the closed-loop benchmark."""
        if self.executor is None:
            raise ValueError("compile() the model before serve()")
        from flexflow_tpu.serve import ServingEngine
        engine = ServingEngine(self, batch_buckets=batch_buckets,
                               max_wait_ms=max_wait_ms,
                               search_budget=search_budget,
                               verbose=verbose)
        return engine.start() if start else engine

    def predict(self, x):
        fwd = self.executor.make_forward(training=False)
        inputs = self._stage_inputs(x if isinstance(x, (list, tuple)) else [x])
        self._rng, sub = jax.random.split(self._rng)
        out, _ = fwd(self.params, self.state, inputs, sub)
        if jax.process_count() > 1:
            from flexflow_tpu import distributed as _dist
            return _dist.all_gather_host(out)
        return np.asarray(out)

    # ---- reference-parity iteration protocol ------------------------------
    # (forward / backward / update with FFIterationConfig.seq_length —
    # model.cc:2415-2475 + config.h:162-167. Under XLA these are phases of
    # one fused jitted step; we keep the API by staging the batch in
    # set_batch and running the fused step in update(). A seq_length
    # shorter than the model's declared sequence dispatches to a BUCKET
    # executor: the same layer graph re-materialized at the next
    # power-of-two length, so every op — not just BatchMatmul — skips the
    # compute beyond the active length while jit sees only a bounded set
    # of static shapes. begin/end_trace are no-ops: jit IS the trace.)
    def set_batch(self, x, y):
        self._current_batch = (self._stage_inputs(x if isinstance(x, (list, tuple)) else [x]),
                               self._shard_batch(y))

    def forward(self, seq_length: Optional[int] = None):
        if self._current_batch is None:
            raise ValueError("call set_batch(x, y) before forward()")
        self._iter_seq = seq_length
        self._pending = "forward"

    def zero_gradients(self):
        pass

    def backward(self, seq_length: Optional[int] = None):
        if seq_length is not None:
            self._iter_seq = seq_length
        self._pending = "backward"

    def _declared_seq(self) -> Optional[int]:
        """The model's sequence extent: the dim any op marks with the SEQ
        role (attention and friends). None = no sequence dim (MLP/conv),
        in which case seq_length iteration args are ignored — matching
        the reference, where only seq ops consume FFIterationConfig."""
        if self._declared_seq_cache != -1:
            return self._declared_seq_cache
        from flexflow_tpu.ops.base import DimRole
        # collect EVERY SEQ-role extent: a graph whose ops disagree on the
        # sequence length (e.g. encoder/decoder cross-attention) has no
        # single bucketable extent — run full-length rather than slicing
        # against whichever op happened to iterate last (ADVICE r5)
        found = {
            shp[d]
            for node in self.executor.nodes
            for shp, roles in zip(node.op.output_shapes,
                                  node.op.output_dim_roles())
            for d, r in enumerate(roles)
            if r == DimRole.SEQ
        }
        self._declared_seq_cache = found.pop() if len(found) == 1 else None
        return self._declared_seq_cache

    def _seq_bucket(self, seq_length: Optional[int]) -> Optional[int]:
        """Bucketed static length for an iteration's seq_length: the next
        power of two (>=16), None when the full-length step applies."""
        declared = self._declared_seq()
        if not seq_length or declared is None or seq_length >= declared:
            return None
        if isinstance(self.search_info, dict) \
                and self.search_info.get("rewritten_nodes") is not None:
            return None  # strategy is keyed to the rewritten graph
        from flexflow_tpu.executor import GraphExecutor
        if type(self.executor) is not GraphExecutor:
            return None  # pipeline bodies are stacked at full length
        # at least one INPUT must carry the sequence at dim 1, or the
        # bucket graph would equal the full graph while update() slices —
        # degrade to the full-length step instead
        if not any(len(layer.outputs[0].shape) >= 2
                   and layer.outputs[0].shape[1] == declared
                   for layer in self.layers
                   if layer.op_type == OperatorType.INPUT):
            return None
        b = 16
        while b < seq_length:
            b *= 2
        return b if b < declared else None

    def _bucket_executor(self, bucket: int):
        """GraphExecutor for the layer graph re-materialized at `bucket`
        sequence length; params/opt state/op state are shared with the
        full-length executor (layer guids are stable, and no parameter
        shape depends on the sequence extent)."""
        ex = self._seq_execs.get(bucket)
        if ex is not None:
            return ex
        from flexflow_tpu.executor import GraphExecutor
        from flexflow_tpu.parallel.strategy import apply_strategy
        declared = self._declared_seq()
        overrides = {}
        for layer in self.layers:
            if layer.op_type != OperatorType.INPUT:
                continue
            shp = list(layer.outputs[0].shape)
            if sum(1 for e in shp[1:] if e == declared) > 1:
                raise NotImplementedError(
                    f"seq_length buckets: input '{layer.name}' shape "
                    f"{tuple(shp)} carries the sequence extent on more "
                    f"than one dim (e.g. an [B,S,S] mask) — ambiguous "
                    f"to slice")
            if len(shp) >= 2 and shp[1] == declared:
                shp[1] = bucket
                overrides[layer.name] = tuple(shp)
        nodes, input_names, tensor_ref = self._materialize_nodes(overrides)
        final_ref = self._select_final_ref(nodes, tensor_ref)
        # parameter SHAPES must be sequence-independent; a mismatch means
        # dim 1 of some input was NOT the sequence (e.g. an auxiliary
        # (B, S)-shaped feature input whose extent coincides) and slicing
        # it would silently corrupt training — refuse instead. Shapes via
        # eval_shape, not element counts: a parameter that reshapes at the
        # bucketed length while keeping its element count must still trip
        # the guard (ADVICE r5).
        def _shapes(op):
            # None (not {}) when init_params cannot be abstractly
            # evaluated, so an eval_shape failure falls back to the
            # element-count guard instead of silently comparing {} == {}
            try:
                tree = jax.eval_shape(op.init_params, jax.random.PRNGKey(0))
            except Exception:
                return None
            return {k: tuple(v.shape) for k, v in tree.items()}

        full_shapes = {n.op.guid: _shapes(n.op)
                       for n in self.executor.nodes}
        for n in nodes:
            mine = _shapes(n.op)
            ref = full_shapes.get(n.op.guid, mine)
            if ref is None or mine is None:
                full_node = self.executor.by_guid.get(n.op.guid)
                mismatch = (full_node is not None and
                            full_node.op.params_elems()
                            != n.op.params_elems())
            else:
                mismatch = ref != mine
            if mismatch:
                raise NotImplementedError(
                    f"seq_length buckets: op '{n.op.name}' changes "
                    f"parameter shape at the bucketed length — an input "
                    f"whose dim 1 coincides with the sequence extent is "
                    f"not actually a sequence; run full-length instead")
        apply_strategy(nodes, self.strategy, self.mesh)
        from flexflow_tpu.layout import propagate_layouts
        propagate_layouts(nodes, **getattr(
            self, "_layout_args", dict(mode="nchw", on_tpu=False)))
        full = self.executor
        ex = GraphExecutor(nodes, input_names, final_ref, self.mesh,
                           self.loss_type, self.metrics, self.optimizer,
                           compute_dtype=full.compute_dtype,
                           data_axes=full.data_axes,
                           final_is_softmax=self._final_is_softmax,
                           fold_conv_bn=full.fold_conv_bn,
                           # a shorter bucket holds fewer activations than
                           # the length the search fitted: it does not pay
                           # the recompute
                           plan=dataclasses.replace(full.plan,
                                                    remat_ops=None))
        ex.comp_mode = full.comp_mode
        self._seq_execs[bucket] = ex
        return ex

    def _slice_seq(self, arr, bucket: int):
        declared = self._declared_seq()
        if arr.ndim >= 2 and arr.shape[1] == declared:
            return arr[:, :bucket]
        return arr

    def _final_output_has_seq(self) -> bool:
        """Token-level model (output carries a SEQ dim) => labels slice
        with the sequence; pooled heads (e.g. an S-class classifier whose
        label dim coincidentally equals S) keep full labels."""
        from flexflow_tpu.ops.base import DimRole
        guid, idx = self.executor.final_ref
        node = next(n for n in self.executor.nodes if n.op.guid == guid)
        return DimRole.SEQ in node.op.output_dim_roles()[idx]

    def update(self):
        inputs, labels = self._current_batch
        ex = self.executor
        bucket = self._seq_bucket(getattr(self, "_iter_seq", None))
        if bucket is not None:
            ex = self._bucket_executor(bucket)
            inputs = {k: self._slice_seq(v, bucket)
                      for k, v in inputs.items()}
            if self._final_output_has_seq():
                labels = self._slice_seq(labels, bucket)
        train_step = ex.make_train_step()
        self._refresh_compute_params()
        self._rng, sub = jax.random.split(self._rng)
        (self.params, self.opt_state, self.state, self._last_loss, self._last_metrics) = \
            train_step(self.params, self.opt_state, self.state, inputs, labels, sub)
        self._iter += 1
        self._pending = None

    def begin_trace(self, trace_id: int = 0):
        pass

    def end_trace(self, trace_id: int = 0):
        pass

    # ---- weight I/O (parallel_tensor.h:164-169 set_tensor/get_tensor) -----
    def _body_ref(self, layer_name: str):
        """(template_key, block_idx) when layer_name is a pipelined body op."""
        m = getattr(self.executor, "body_param_map", None)
        return m.get(layer_name) if m else None

    def get_parameter(self, layer_name: str, param_name: str = "kernel") -> np.ndarray:
        ref = self._body_ref(layer_name)
        if ref is not None:
            from flexflow_tpu.parallel.pipeline_exec import BODY_KEY
            key, b = ref
            return np.asarray(self.params[BODY_KEY][key][param_name][b])
        return np.asarray(self.params[layer_name][param_name])

    def set_parameter(self, layer_name: str, value: np.ndarray,
                      param_name: str = "kernel") -> None:
        t0 = time.perf_counter()
        try:
            self._set_parameter(layer_name, value, param_name)
        finally:
            # host seconds spent here since compile(), beside
            # `compile_phases` in every trace header (obs.model_context)
            self.set_parameter_s += time.perf_counter() - t0

    def _set_parameter(self, layer_name, value, param_name) -> None:
        ref = self._body_ref(layer_name)
        if ref is not None:
            from flexflow_tpu.parallel.pipeline_exec import BODY_KEY
            key, b = ref
            old = self.params[BODY_KEY][key][param_name]
            if tuple(old.shape[1:]) != tuple(value.shape):
                raise ValueError(
                    f"shape mismatch {old.shape[1:]} vs {value.shape}")
            # device-side slice update: keeps the pipe sharding and avoids
            # a full host round-trip of the stacked [R, ...] array per call
            self.params[BODY_KEY][key][param_name] = old.at[b].set(
                jnp.asarray(value, old.dtype))
            self._compute_params_dirty = True
            return
        old = self.params[layer_name][param_name]
        if tuple(old.shape) != tuple(value.shape):
            raise ValueError(f"shape mismatch {old.shape} vs {value.shape}")
        self.params[layer_name][param_name] = jax.device_put(
            jnp.asarray(value, old.dtype), old.sharding)
        # defer the bf16 working-copy re-cast: per-weight import loops
        # (torch/onnx/keras frontends) would otherwise cast the whole tree
        # once per weight
        self._compute_params_dirty = True

    def _refresh_compute_params(self) -> None:
        """Re-derive the bf16 working copy after direct params mutations
        (set_parameter / checkpoint load / recompile carry-over) so the
        next jitted step sees the new weights. Lazy: runs once before the
        next use, however many mutations happened."""
        from flexflow_tpu.executor import COMPUTE_PARAMS_KEY
        if not getattr(self, "_compute_params_dirty", False):
            return
        self._compute_params_dirty = False
        if self.executor is not None and self.executor.use_master_copy:
            self.state[COMPUTE_PARAMS_KEY] = \
                self.executor.cast_compute_copy(self.params)

    def get_layer_names(self) -> List[str]:
        return [n.op.name for n in (self.executor.nodes if self.executor else [])]
