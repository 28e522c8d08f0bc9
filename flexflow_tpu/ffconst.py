"""Framework-wide enums.

TPU-native analog of the reference's ``include/flexflow/ffconst.h`` enum
surface (OperatorType ffconst.h:63-156, DataType, LossType :33-39,
MetricsType :52-60, CompMode, ParameterSyncType :46, ActiMode, AggrMode,
PoolType). Values are our own; names keep API parity so frontends and
strategy files interoperate.
"""

import enum

import jax.numpy as jnp


class DataType(enum.Enum):
    BOOL = "bool"
    INT32 = "int32"
    INT64 = "int64"
    HALF = "float16"
    BFLOAT16 = "bfloat16"
    FLOAT = "float32"
    DOUBLE = "float64"

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.value)

    @property
    def size(self) -> int:
        return self.jnp_dtype.itemsize

    @classmethod
    def from_jnp(cls, dtype) -> "DataType":
        return cls(jnp.dtype(dtype).name)


class ActiMode(enum.Enum):
    AC_MODE_NONE = 0
    AC_MODE_RELU = 1
    AC_MODE_SIGMOID = 2
    AC_MODE_TANH = 3
    AC_MODE_GELU = 4


class AggrMode(enum.Enum):
    AGGR_MODE_NONE = 0
    AGGR_MODE_SUM = 1
    AGGR_MODE_AVG = 2


class PoolType(enum.Enum):
    POOL_MAX = 0
    POOL_AVG = 1


class LossType(enum.Enum):
    CATEGORICAL_CROSSENTROPY = 10
    SPARSE_CATEGORICAL_CROSSENTROPY = 11
    MEAN_SQUARED_ERROR_AVG_REDUCE = 12
    MEAN_SQUARED_ERROR_SUM_REDUCE = 13
    IDENTITY = 14
    # token-level cross-entropy whose labels carry a weight a position:
    # labels [B, S, 2] float32, (token id, weight)
    WEIGHTED_SPARSE_CATEGORICAL_CROSSENTROPY = 15
    # a looped model's objective: the expectation of the T passes'
    # cross-entropies under the exit distribution the model's gate emits,
    # less an entropy bonus; output [B, T*S, V + 1] (the passes' logits
    # laid end to end, the gate's logit the last column), labels [B, S]
    EXPECTED_EXIT_SPARSE_CATEGORICAL_CROSSENTROPY = 16


class MetricsType(enum.Enum):
    ACCURACY = 1001
    CATEGORICAL_CROSSENTROPY = 1002
    SPARSE_CATEGORICAL_CROSSENTROPY = 1003
    MEAN_SQUARED_ERROR = 1004
    ROOT_MEAN_SQUARED_ERROR = 1005
    MEAN_ABSOLUTE_ERROR = 1006


class CompMode(enum.Enum):
    TRAINING = 0
    INFERENCE = 1


class ParameterSyncType(enum.Enum):
    """How gradients are synchronized across data-parallel replicas.

    On TPU both map to a ``psum`` over the data mesh axes inside the jitted
    step (the reference distinguishes a zero-copy parameter server from NCCL
    allreduce — config.h:55-59); we keep the names for config parity.
    """

    NONE = 0
    PS = 1
    NCCL = 2


class OperatorType(enum.Enum):
    # sources
    NOOP = enum.auto()
    INPUT = enum.auto()
    WEIGHT = enum.auto()
    # dense / conv stack
    CONV2D = enum.auto()
    POOL2D = enum.auto()
    BATCHNORM = enum.auto()
    LINEAR = enum.auto()
    EMBEDDING = enum.auto()
    # attention / transformer
    MULTIHEAD_ATTENTION = enum.auto()
    LAYERNORM = enum.auto()
    # RMSNorm: new scope vs the reference (no analog in ffconst.h) — the
    # Llama/T5 model family's normalization
    RMSNORM = enum.auto()
    SOFTMAX = enum.auto()
    # elementwise
    EW_ADD = enum.auto()
    EW_SUB = enum.auto()
    EW_MUL = enum.auto()
    EW_DIV = enum.auto()
    EW_MAX = enum.auto()
    EW_MIN = enum.auto()
    RELU = enum.auto()
    GELU = enum.auto()
    SIGMOID = enum.auto()
    TANH = enum.auto()
    ELU = enum.auto()
    EXP = enum.auto()
    SIN = enum.auto()
    COS = enum.auto()
    POW = enum.auto()
    RSQRT = enum.auto()
    IDENTITY = enum.auto()
    SCALAR_MULTIPLY = enum.auto()
    SCALAR_ADD = enum.auto()
    SCALAR_SUB = enum.auto()
    SCALAR_TRUE_DIV = enum.auto()
    # matmul / shape
    BATCHMATMUL = enum.auto()
    CONCAT = enum.auto()
    SPLIT = enum.auto()
    RESHAPE = enum.auto()
    TRANSPOSE = enum.auto()
    FLAT = enum.auto()
    REVERSE = enum.auto()
    CAST = enum.auto()
    DROPOUT = enum.auto()
    GATHER = enum.auto()
    REDUCE_SUM = enum.auto()
    REDUCE_MAX = enum.auto()
    MEAN = enum.auto()
    TOPK = enum.auto()
    ARG_TOPK = enum.auto()
    # r4 additions for torch.fx frontend depth (reference table
    # python/flexflow/torch/model.py:2408-2496 covers these kinds)
    CONST = enum.auto()      # embedded constant (fx get_attr buffers)
    WHERE = enum.auto()      # select(cond, a, b) — masked_fill/where
    EXPAND = enum.auto()     # broadcast_to (torch expand/repeat)
    EINSUM = enum.auto()     # general einsum contraction
    GROUPNORM = enum.auto()  # nn.GroupNorm
    LOG = enum.auto()        # elementwise natural log
    # MoE quartet (+ gating sugar)
    GROUP_BY = enum.auto()
    AGGREGATE = enum.auto()
    AGGREGATE_SPEC = enum.auto()
    CACHE = enum.auto()
    EXPERTS = enum.auto()
    # fused compute
    FUSED = enum.auto()
    # parallel (resharding) ops — first-class PCG citizens (ffconst.h:149-156)
    REPARTITION = enum.auto()
    COMBINE = enum.auto()
    REPLICATE = enum.auto()
    REDUCTION = enum.auto()
    PIPELINE = enum.auto()
    FUSED_PARALLEL = enum.auto()
    # loss/metrics pseudo-ops (appear in taskgraph simulation)
    LOSS = enum.auto()
    METRICS = enum.auto()
    OPTIMIZER = enum.auto()
    ALLREDUCE = enum.auto()
    # appended (PR 27), so that every earlier member keeps its value:
    # the Mamba-2 mixer (ops/ssm.py) and the dropless mixture-of-experts
    # layer over the experts held here (ops/experts.py)
    SSM_MIXER = enum.auto()
    MOE_LAYER = enum.auto()
    # appended (PR 45): the gated short convolution (ops/short_conv.py)
    SHORT_CONV = enum.auto()
    # appended (PR 52): the Mamba-1 mixer (ops/ssm.py `MambaMixer`)
    MAMBA_MIXER = enum.auto()
    # appended (PR 58): the gated delta-rule mixer (ops/delta_rule.py)
    DELTA_MIXER = enum.auto()
    # appended (PR 64): the two halves of a hyper-connection around a
    # sublayer (ops/hyper_connection.py): the read of the branch's input
    # out of the residual streams with the three mixing maps, and the
    # write of the branch's output back into them
    HC_PRE = enum.auto()
    HC_POST = enum.auto()


PARALLEL_OP_TYPES = frozenset(
    {
        OperatorType.REPARTITION,
        OperatorType.COMBINE,
        OperatorType.REPLICATE,
        OperatorType.REDUCTION,
        OperatorType.PIPELINE,
        OperatorType.FUSED_PARALLEL,
    }
)
