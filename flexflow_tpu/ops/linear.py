"""Linear (dense) operator.

Analog of src/ops/linear.cc + kernels/linear_kernels.cu: y = act(x W + b).
The reference's cuBLAS GemmEx maps to a single jnp.dot lowered onto the
MXU; inputs are cast to the compute dtype (bf16 by default) with f32
accumulation (preferred_element_type), parameters stay f32.

Sharding surface (search): weight [in, out] may shard 'out' on the model
axis (column-parallel → Combine on output) or 'in' (row-parallel →
Replicate input / Reduction output), matching
create_partition_linear_combine / create_replicate_linear_combine
(src/runtime/substitution.cc:1756,1809).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from flexflow_tpu.ffconst import ActiMode, OperatorType
from flexflow_tpu.initializers import DefaultBiasInitializer, DefaultWeightInitializer
from flexflow_tpu.ops.base import DimRole, Op, OpContext, register_op
import jax


def apply_activation(x, act: ActiMode):
    if act == ActiMode.AC_MODE_RELU:
        return jax.nn.relu(x)
    if act == ActiMode.AC_MODE_SIGMOID:
        return jax.nn.sigmoid(x)
    if act == ActiMode.AC_MODE_TANH:
        return jnp.tanh(x)
    if act == ActiMode.AC_MODE_GELU:
        return jax.nn.gelu(x)
    return x


@register_op(OperatorType.LINEAR)
class Linear(Op):
    def __init__(self, layer, input_shapes):
        self.out_dim = layer.get_property("out_dim")
        self.activation = layer.get_property("activation", ActiMode.AC_MODE_NONE)
        self.use_bias = layer.get_property("use_bias", True)
        self.kernel_init = layer.get_property("kernel_initializer") or DefaultWeightInitializer()
        self.bias_init = layer.get_property("bias_initializer") or DefaultBiasInitializer()
        # a tied product reads the leaf of ANOTHER KIND of op, a table
        # [out_dim, in_dim] (an embedding's), as y = x E^T: the one-leaf,
        # transposed case of `shared_op` (`Op.tied_params`)
        self.transposed = bool(layer.get_property("tied_to"))
        self._tied_traced = False
        # the product in float32 at full precision, its leaves kept
        # float32 in the compute copy (an exit gate: one column)
        self.full_precision = bool(layer.get_property("full_precision"))
        if self.full_precision:
            self.full_precision_params = ("kernel", "bias")
        super().__init__(layer, input_shapes)
        self.in_dim = self.input_shapes[0][-1]

    def compute_output_shapes(self):
        (in_shape,) = self.input_shapes
        return [tuple(in_shape[:-1]) + (self.out_dim,)]

    def shared_leaves(self):
        if self.transposed:
            return OperatorType.EMBEDDING, {
                "kernel": (self.out_dim, self.input_shapes[0][-1])}
        return super().shared_leaves()

    def init_params(self, rng):
        in_dim = self.input_shapes[0][-1]
        k1, k2 = jax.random.split(rng)
        params = {"kernel": self.kernel_init(k1, (in_dim, self.out_dim))}
        if self.use_bias:
            params["bias"] = self.bias_init(k2, (self.out_dim,))
        return params

    def forward(self, params, inputs, ctx: OpContext):
        (x,) = inputs
        w = params["kernel"].astype(ctx.compute_dtype)
        if self.transposed:
            self._tied_traced = True
            y = jnp.einsum("...e,ve->...v", x.astype(ctx.compute_dtype), w,
                           preferred_element_type=jnp.float32)
        elif self.full_precision:
            y = jnp.dot(x.astype(jnp.float32),
                        params["kernel"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
        else:
            y = jnp.dot(x.astype(ctx.compute_dtype), w,
                        preferred_element_type=jnp.float32)
        if self.use_bias:
            y = y + params["bias"]
        y = apply_activation(y, self.activation)
        return [y.astype(x.dtype)]

    def output_dim_roles(self):
        # dim1 of a rank-3 input is a position dim the matmul treats
        # independently — a sequence dim the search may context-shard
        shp = self.output_shapes[0]
        mid = DimRole.SEQ if len(shp) == 3 else DimRole.OTHER
        roles = [DimRole.SAMPLE] + [mid] * (len(shp) - 2) + [DimRole.CHANNEL]
        return [tuple(roles)]

    def flops(self):
        batch = int(np.prod(self.input_shapes[0][:-1]))
        return 2 * batch * self.in_dim * self.out_dim

    def traced_gauges(self):
        """`executor.tied_head_ops`: this product read its table out of
        another op's leaf when last traced (only a tied op says so)."""
        if not self.transposed:
            return {}
        return {"executor.tied_head_ops": int(self._tied_traced)}

    def params_elems(self):
        return self.in_dim * self.out_dim + (self.out_dim if self.use_bias else 0)
