"""Llama-family decoder LM (BASELINE.md stretch target).

No counterpart exists in the reference's example zoo — this is new scope:
RMSNorm, rotary position embeddings, grouped-query attention, and SwiGLU
MLPs, built from the framework's own ops so the auto-parallelization
search sees a normal PCG (attention head axis shardable, seq axis
ring-shardable, batch data-parallel). ``import_hf_weights`` loads a
HuggingFace ``LlamaForCausalLM`` state dict for numerics parity
(tests/test_llama.py checks logits against the HF forward).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from flexflow_tpu.config import FFConfig
from flexflow_tpu.model import FFModel


@dataclasses.dataclass
class LlamaModelConfig:
    # defaults are a test-size model; Llama-3-8B would be
    # hidden 4096 / inter 14336 / 32 layers / 32 heads / 8 kv heads /
    # vocab 128256 / theta 500000
    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 128
    num_hidden_layers: int = 2
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    batch_size: int = 4
    seq_length: int = 16
    seq_parallel: Optional[str] = None  # 'seq' for ring attention
    # the head reads the embedding's table (ONE leaf) and not a copy
    tie_word_embeddings: bool = False


def create_llama(cfg: LlamaModelConfig, ff_config: FFConfig = None) -> FFModel:
    """The pattern ``L`` repeated: models/decoder.py builds it."""
    from flexflow_tpu.models.decoder import DecoderConfig, create_decoder

    return create_decoder(DecoderConfig(
        hybrid_override_pattern="L" * cfg.num_hidden_layers,
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        layer_norm_epsilon=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        batch_size=cfg.batch_size, seq_length=cfg.seq_length,
        seq_parallel=cfg.seq_parallel,
        tie_word_embeddings=cfg.tie_word_embeddings), ff_config)


def import_hf_weights(ff: FFModel, hf_model) -> int:
    """Copy a HuggingFace ``LlamaForCausalLM``'s weights into a compiled
    ``create_llama`` model. Returns the number of tensors copied."""
    import numpy as np

    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    cfg = hf_model.config
    h = cfg.num_attention_heads
    hk = getattr(cfg, "num_key_value_heads", h)
    e = cfg.hidden_size
    d = e // h

    def heads(w, nh):  # HF [nh*D, E] -> ours [nh, E, D]
        return w.reshape(nh, d, -1).transpose(0, 2, 1)

    copied = 0

    def put(layer, value, pname="kernel"):
        nonlocal copied
        ff.set_parameter(layer, np.ascontiguousarray(value, np.float32),
                         pname)
        copied += 1

    put("embed_tokens", sd["model.embed_tokens.weight"])
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}."
        put(f"l{i}_input_ln", sd[p + "input_layernorm.weight"], "scale")
        put(f"l{i}_attn", heads(sd[p + "self_attn.q_proj.weight"], h), "wq")
        put(f"l{i}_attn", heads(sd[p + "self_attn.k_proj.weight"], hk), "wk")
        put(f"l{i}_attn", heads(sd[p + "self_attn.v_proj.weight"], hk), "wv")
        # o_proj [E, H*D] -> wo [H, D, E]
        put(f"l{i}_attn",
            sd[p + "self_attn.o_proj.weight"].transpose(1, 0).reshape(h, d, e),
            "wo")
        put(f"l{i}_post_ln",
            sd[p + "post_attention_layernorm.weight"], "scale")
        put(f"l{i}_gate_proj", sd[p + "mlp.gate_proj.weight"].T)
        put(f"l{i}_up_proj", sd[p + "mlp.up_proj.weight"].T)
        put(f"l{i}_down_proj", sd[p + "mlp.down_proj.weight"].T)
    put("final_ln", sd["model.norm.weight"], "scale")
    if not ff.params.get("lm_head"):
        # built with `tie_word_embeddings`: the head reads the table
        return copied
    lm = sd.get("lm_head.weight")
    if lm is None:  # a tied checkpoint into an untied model: a copy
        lm = sd["model.embed_tokens.weight"]
    put("lm_head", lm.T)
    return copied
