"""Gated short convolution (the LFM2 family's sequence mixer): a causal
depthwise convolution of a few taps between two elementwise gates, all
three read out of one input projection.

No analog in the reference's src/ops. The op is the whole mixer, as
SSMMixer and MultiHeadAttention are theirs, so that the search prices and
places it as one node:

    [B ; C ; x] = h W_in                       E -> 3 E, no bias
    u   = B * x
    c_t = sum_{j < K} w_j * u_{t - (K-1) + j}  depthwise, causal, zeros
                                               ahead of a sample's start
    y   = (C * c) W_out                        E -> E, no bias

No activation anywhere in it. The two products are the op's FLOPs; the
gate-convolution-gate between them is elementwise, so its cost is its
bytes: a nested call of its own inside the op's (`jit(gated_conv)` under
the executor's `jit(op_short_conv)`), with a backward of its own that
keeps the projection alone and forms u and c again, so that no float32
[B, S, E] array is held between the passes. Two bodies, one
mathematics (`ShortConv.in_one_pass` picks from the static shapes):
`pallas_kernels.gated_conv_lanes`, one pass over memory each way, where
Pallas is on, the shape is whole blocks and the mesh one device; else
`gated_conv` here, jax.numpy, which XLA splits into a few fusions
around a float32 copy of B * x. The convolution of the second is
`ops.ssm.causal_depthwise_conv1d`, the function the Mamba-2 mixer runs
at four taps (float32 arithmetic, operands as stored).

``output_gate=False`` leaves C out (y = c W_out; the projection keeps
its 3 E columns): a control of the benchmark, not a model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.initializers import DefaultWeightInitializer
from flexflow_tpu.ops.base import (DimRole, Op, OpContext, register_op,
                                   scoped)
from flexflow_tpu.ops.ssm import causal_depthwise_conv1d


def _parts(proj):
    """[B ; C ; x] of a projection [.., 3 E], float32."""
    return (t.astype(jnp.float32) for t in jnp.split(proj, 3, axis=-1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def gated_conv(proj, w, output_gate=True):
    """proj [B, S, 3 E] = [B ; C ; x], w [K, E] -> C * conv(B * x)
    [B, S, E] in proj's dtype; float32 arithmetic."""
    b, c, x = _parts(proj)
    conv = causal_depthwise_conv1d(b * x, w)
    return (c * conv if output_gate else conv).astype(proj.dtype)


def _gated_conv_fwd(proj, w, output_gate):
    return gated_conv(proj, w, output_gate), (proj, w)


def _gated_conv_bwd(output_gate, kept, dy):
    proj, w = kept
    b, c, x = _parts(proj)
    dy = dy.astype(jnp.float32)
    u = b * x
    if output_gate:
        dc, dconv = dy * causal_depthwise_conv1d(u, w), dy * c
    else:
        dc, dconv = jnp.zeros_like(dy), dy
    du = causal_depthwise_conv1d(dconv, w, reverse=True)
    k, length = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    dw = jnp.stack([jnp.sum(dconv * up[:, j:j + length], axis=(0, 1))
                    for j in range(k)])
    dproj = jnp.concatenate([du * x, dc, du * b], axis=-1)
    return dproj.astype(proj.dtype), dw.astype(w.dtype)


gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


@register_op(OperatorType.SHORT_CONV)
class ShortConv(Op):
    """input [B, S, E] -> [B, S, E]. Weights: w_in [E, 3 E], conv_w
    [K, E], w_out [E, E]; no bias."""

    def __init__(self, layer, input_shapes):
        p = layer.properties
        self.kernel = p.get("kernel", 3)
        self.output_gate = p.get("output_gate", True)
        self.kernel_init = (p.get("kernel_initializer")
                            or DefaultWeightInitializer())
        self._traced = self._one_pass = False
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        return [tuple(self.input_shapes[0])]

    def init_params(self, rng):
        e = self.input_shapes[0][-1]
        ks = jax.random.split(rng, 3)
        bound = 1.0 / self.kernel ** 0.5
        return {"w_in": self.kernel_init(ks[0], (e, 3 * e)),
                "conv_w": jax.random.uniform(ks[1], (self.kernel, e),
                                             jnp.float32, -bound, bound),
                "w_out": self.kernel_init(ks[2], (e, e))}

    def in_one_pass(self, mesh, shape=None) -> bool:
        """Whether the gate-convolution-gate over an input of ``shape``
        (the op's own by default) runs as the one-pass kernel
        (`pallas_kernels.gated_conv_lanes`): Pallas on, whole blocks of
        rows and lanes, and one device (a bare kernel call has no
        partitioning); else XLA's fusions of `gated_conv`."""
        from flexflow_tpu.ops import pallas_kernels as pk
        _, s, e = shape or self.input_shapes[0]
        return bool(pk.pallas_mode() != "off"
                    and pk.gated_conv_shape_legal(s, e, self.kernel)
                    and (mesh is None or mesh.devices.size == 1))

    def forward(self, params, inputs, ctx: OpContext):
        (x,) = inputs
        cd = ctx.compute_dtype
        self._traced = True
        proj = jnp.dot(x.astype(cd), params["w_in"].astype(cd),
                       preferred_element_type=jnp.float32).astype(cd)
        self._one_pass = self.in_one_pass(ctx.mesh, x.shape)
        if self._one_pass:
            from flexflow_tpu.ops.pallas_kernels import gated_conv_lanes
            form = gated_conv_lanes
        else:
            form = gated_conv
        y = scoped("gated_conv", lambda proj, w: form(
            proj, w, self.output_gate))(proj, params["conv_w"])
        out = jnp.dot(y, params["w_out"].astype(cd),
                      preferred_element_type=jnp.float32)
        return [out.astype(x.dtype)]

    def traced_gauges(self):
        """`executor.short_conv_ops`: the op's forward has been traced;
        `executor.gated_conv_kernel_ops`: its gate-convolution-gate ran
        as the one-pass kernel when it was."""
        return {"executor.short_conv_ops": int(self._traced),
                "executor.gated_conv_kernel_ops": int(
                    self._traced and self._one_pass)}

    def output_dim_roles(self):
        # a position reads the K - 1 before it: not position-independent,
        # so no SEQ role
        return [(DimRole.SAMPLE, DimRole.OTHER, DimRole.CHANNEL)]

    def flops(self):
        b, s, e = self.input_shapes[0]
        # the two products; u, K multiply-adds and the gate an element
        return 2 * b * s * e * 4 * e + b * s * e * (2 * self.kernel + 2)

    def interior_bytes(self):
        """Bytes the op keeps for its backward pass besides its output:
        the projection [B ; C ; x] and the gated convolution that the
        output product reads."""
        b, s, e = self.input_shapes[0]
        return b * s * 4 * e * self.dtype.size

    def params_elems(self):
        e = self.input_shapes[0][-1]
        return 4 * e * e + self.kernel * e
