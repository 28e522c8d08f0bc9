"""LayerNorm, Softmax, Dropout.

Analogs of src/ops/layer_norm.cc/.cu, softmax.cc (cuDNN softmax),
dropout.cc (cuDNN dropout). All are single fused XLA computations; the
reference's custom Welford CUDA kernels are unnecessary — XLA fuses the
mean/var reductions with the affine apply.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops.base import DimRole, Op, OpContext, register_op


@register_op(OperatorType.LAYERNORM)
class LayerNorm(Op):
    def __init__(self, layer, input_shapes):
        self.axes = tuple(layer.get_property("axes", (-1,)))
        self.elementwise_affine = layer.get_property("elementwise_affine", True)
        self.eps = layer.get_property("eps", 1e-5)
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        return [self.input_shapes[0]]

    def _norm_shape(self):
        shp = self.input_shapes[0]
        axes = tuple(a % len(shp) for a in self.axes)
        return tuple(shp[a] for a in sorted(axes))

    def init_params(self, rng):
        if not self.elementwise_affine:
            return {}
        ns = self._norm_shape()
        return {"scale": jnp.ones(ns), "bias": jnp.zeros(ns)}

    def forward(self, params, inputs, ctx: OpContext):
        (x,) = inputs
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=self.axes, keepdims=True)
        var = jnp.var(xf, axis=self.axes, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        if self.elementwise_affine:
            y = y * params["scale"] + params["bias"]
        return [y.astype(x.dtype)]

    def output_dim_roles(self):
        shp = self.output_shapes[0]
        roles = [DimRole.SAMPLE] + [DimRole.OTHER] * (len(shp) - 1)
        # dim1 of a rank-3 tensor is a position dim (normalization is per
        # position when it is not a normalized axis) — seq-shardable
        norm_axes = {a % len(shp) for a in self.axes}
        if len(shp) == 3 and 1 not in norm_axes:
            roles[1] = DimRole.SEQ
        return [tuple(roles)]

    def params_elems(self):
        return 2 * int(np.prod(self._norm_shape())) if self.elementwise_affine else 0


@register_op(OperatorType.GROUPNORM)
class GroupNorm(Op):
    """nn.GroupNorm for NCHW/NC inputs: normalize each of ``groups``
    channel groups over (C/G, *spatial), per-channel affine (r4 torch.fx
    frontend parity; reference table python/flexflow/torch/model.py)."""

    def __init__(self, layer, input_shapes):
        self.groups = layer.get_property("groups", 1)
        self.eps = layer.get_property("eps", 1e-5)
        self.affine = layer.get_property("affine", True)
        c = input_shapes[0][1]
        if c % self.groups:
            raise ValueError(
                f"group_norm: {c} channels not divisible by "
                f"{self.groups} groups")
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        return [self.input_shapes[0]]

    def init_params(self, rng):
        if not self.affine:
            return {}
        c = self.input_shapes[0][1]
        return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}

    def forward(self, params, inputs, ctx: OpContext):
        (x,) = inputs
        g = self.groups
        nhwc = getattr(self, "exec_layout", "NCHW") == "NHWC"
        if nhwc:
            # channels-last: split the minor dim into (g, c/g); each
            # group normalizes over (*spatial, c/g)
            n, c = x.shape[0], x.shape[-1]
            xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (g, c // g))
            axes = tuple(range(1, xf.ndim - 2)) + (xf.ndim - 1,)
        else:
            n, c = x.shape[0], x.shape[1]
            xf = x.astype(jnp.float32).reshape((n, g, c // g) + x.shape[2:])
            axes = tuple(range(2, xf.ndim))
        mean = jnp.mean(xf, axis=axes, keepdims=True)
        var = jnp.mean((xf - mean) ** 2, axis=axes, keepdims=True)
        y = ((xf - mean) * jax.lax.rsqrt(var + self.eps)).reshape(x.shape)
        if self.affine:
            shape = ((1,) * (x.ndim - 1) + (c,) if nhwc
                     else (1, c) + (1,) * (x.ndim - 2))
            y = y * params["scale"].reshape(shape) \
                + params["bias"].reshape(shape)
        return [y.astype(x.dtype)]

    def params_elems(self):
        return 2 * int(self.input_shapes[0][1]) if self.affine else 0


@register_op(OperatorType.RMSNORM)
class RMSNorm(Op):
    """Root-mean-square normalization over the last dim (Llama/T5 family;
    new scope vs the reference). y = x / rms(x) * scale, computed in f32.
    ``zero_centered`` (PR 58): y = x / rms(x) * (1 + scale), the leaf
    drawn at zero, so that weight decay pulls the scale toward one."""

    def __init__(self, layer, input_shapes):
        self.eps = layer.get_property("eps", 1e-6)
        self.zero_centered = bool(layer.get_property("zero_centered", False))
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        return [self.input_shapes[0]]

    def init_params(self, rng):
        return {"scale": jnp.full((self.input_shapes[0][-1],),
                                  0.0 if self.zero_centered else 1.0)}

    def forward(self, params, inputs, ctx: OpContext):
        (x,) = inputs
        xf = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                            + self.eps)
        scale = params["scale"]
        if self.zero_centered:
            scale = 1.0 + scale.astype(jnp.float32)
        return [(xf * rms * scale).astype(x.dtype)]

    def output_dim_roles(self):
        shp = self.output_shapes[0]
        roles = [DimRole.SAMPLE] + [DimRole.OTHER] * (len(shp) - 1)
        if len(shp) == 3:
            roles[1] = DimRole.SEQ  # per-position norm: seq-shardable
        return [tuple(roles)]

    def params_elems(self):
        return int(self.input_shapes[0][-1])


@register_op(OperatorType.SOFTMAX)
class Softmax(Op):
    def __init__(self, layer, input_shapes):
        self.axis = layer.get_property("axis", -1)
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        return [self.input_shapes[0]]

    def forward(self, params, inputs, ctx: OpContext):
        (x,) = inputs
        return [jax.nn.softmax(x.astype(jnp.float32), axis=self.axis).astype(x.dtype)]

    def output_dim_roles(self):
        shp = self.output_shapes[0]
        roles = [DimRole.SAMPLE] + [DimRole.OTHER] * (len(shp) - 1)
        if len(shp) == 3 and self.axis % len(shp) != 1:
            roles[1] = DimRole.SEQ
        return [tuple(roles)]


@register_op(OperatorType.DROPOUT)
class Dropout(Op):
    def __init__(self, layer, input_shapes):
        self.rate = layer.get_property("rate", 0.5)
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        return [self.input_shapes[0]]

    def forward(self, params, inputs, ctx: OpContext):
        (x,) = inputs
        if not ctx.training or self.rate <= 0.0:
            return [x]
        keep = jax.random.bernoulli(ctx.next_rng(), 1.0 - self.rate, x.shape)
        return [jnp.where(keep, x / (1.0 - self.rate), 0).astype(x.dtype)]

    def output_dim_roles(self):
        from flexflow_tpu.ops.elementwise import _elementwise_roles
        return [_elementwise_roles(self.output_shapes[0])]
