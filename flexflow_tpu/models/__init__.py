"""Model zoo: the reference's example models rebuilt through the FFModel API.

Mirrors examples/cpp/{Transformer,AlexNet,ResNet,InceptionV3,DLRM,XDL,
mixture_of_experts,candle_uno,MLP_Unify,resnext50} with the same
architecture configs, so benchmark protocols carry over (SURVEY §6).
"""

from flexflow_tpu.models.transformer import create_transformer, TransformerConfig
from flexflow_tpu.models.mlp import create_mlp
from flexflow_tpu.models.alexnet import create_alexnet
from flexflow_tpu.models.dlrm import create_dlrm, DLRMConfig
from flexflow_tpu.models.resnet import create_resnet, ResNetConfig
from flexflow_tpu.models.resnext import create_resnext50, ResNeXtConfig
from flexflow_tpu.models.inception import create_inception_v3, InceptionConfig
from flexflow_tpu.models.candle_uno import create_candle_uno, CandleUnoConfig
from flexflow_tpu.models.xdl import create_xdl, XDLConfig
from flexflow_tpu.models.moe_model import create_moe, create_moe_encoder, MoEConfig
from flexflow_tpu.models.llama import (create_llama, import_hf_weights,
                                       LlamaModelConfig)
from flexflow_tpu.models.decoder import create_decoder, DecoderConfig

__all__ = [
    "create_transformer",
    "TransformerConfig",
    "create_mlp",
    "create_alexnet",
    "create_dlrm",
    "DLRMConfig",
    "create_resnet",
    "ResNetConfig",
    "create_resnext50",
    "ResNeXtConfig",
    "create_inception_v3",
    "InceptionConfig",
    "create_candle_uno",
    "CandleUnoConfig",
    "create_xdl",
    "XDLConfig",
    "create_moe",
    "create_moe_encoder",
    "MoEConfig",
    "create_llama", "import_hf_weights", "LlamaModelConfig",
    "create_decoder", "DecoderConfig",
]
