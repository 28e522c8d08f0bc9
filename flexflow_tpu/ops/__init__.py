"""Operator library: pure-JAX compute ops + metadata for the search.

Analog of the reference's src/ops/*.cc + kernels (SURVEY §2.2), with the
CUDA kernels replaced by XLA HLO lowerings (and Pallas where XLA
underperforms). There are no hand-written backward kernels: autodiff over
the composed forward provides every *_BWD task of the reference.
"""

from flexflow_tpu.ops.base import Op, OpRegistry, register_op
import flexflow_tpu.ops.linear  # noqa: F401
import flexflow_tpu.ops.conv  # noqa: F401
import flexflow_tpu.ops.attention  # noqa: F401
import flexflow_tpu.ops.norm  # noqa: F401
import flexflow_tpu.ops.elementwise  # noqa: F401
import flexflow_tpu.ops.tensor_ops  # noqa: F401
import flexflow_tpu.ops.matmul  # noqa: F401
import flexflow_tpu.ops.embedding  # noqa: F401
import flexflow_tpu.ops.reduce  # noqa: F401
import flexflow_tpu.ops.moe  # noqa: F401
import flexflow_tpu.ops.experts  # noqa: F401
import flexflow_tpu.ops.ssm  # noqa: F401
import flexflow_tpu.ops.short_conv  # noqa: F401
import flexflow_tpu.ops.hyper_connection  # noqa: F401
import flexflow_tpu.ops.delta_rule  # noqa: F401
import flexflow_tpu.ops.parallel_ops  # noqa: F401

__all__ = ["Op", "OpRegistry", "register_op"]
