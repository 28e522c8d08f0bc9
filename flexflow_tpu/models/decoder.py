"""One decoder language-model builder, driven by a layer pattern.

A model is an embedding, a stack of blocks and a head; the pattern names
each block by one letter, and the sizes of each kind come from keys that
mirror the public ``config.json`` files (``nemotron_h``'s names where two
families differ). New scope vs the reference, whose zoo has one file a
family.

    M   x + ssm_mixer(rms_norm(x))        Mamba-2 mixer (ops/ssm.py; its
                                          scan one Pallas kernel each
                                          way where the shape allows)
    E   x + moe_layer(rms_norm(x))        sigmoid top-k experts over the
                                          experts held, a shared expert
    *   x + attention(rms_norm(x))        causal GQA, no rotary embedding
    -   x + mlp(rms_norm(x))              down(relu(up(x))^2), no gate
    L   the Llama block: rotary GQA attention, then a SwiGLU MLP, each
        behind its own norm and residual (models/llama.py builds on it)
    G   attention, then experts, each behind its own norm and residual:
        full causal GQA with NO position embedding; the experts' router
        reads the PRE-attention norm (so the choice is known while
        attention runs), softmax over the chosen logits, gated ReLU
        experts down(relu(gate(x)) * up(x)), no shared expert
    W   the same with a sliding window of ``sliding_window_size`` keys
        and rotary embedding (``rope_theta``) over the whole head
    D   the block of a block-diffusion model: attention, then experts,
        each behind its own norm and residual. The sequence is a noised
        copy of a sample and then the clean one (``seq_length`` = 2L);
        attention under the block-diffusion mask of ``block_length``
        (``attention_mask``), rotary positions that repeat after L, an
        RMS norm of every query and key head (``qk_norm``); the router
        reads the POST-attention norm, softmax over all experts with the
        chosen renormalised, experts down(act(gate(x)) * up(x)) of width
        ``moe_intermediate_size`` with act ``hidden_act``, no shared one
    K   learned sparse attention, then experts (PR 54): `D`'s block
        under the causal mask, one sample a sequence, with
        ``sa_config`` (``indexer_num_heads``, ``indexer_head_dim``,
        ``indexer_num_kv_heads`` 1, ``topk``): an indexer scores every
        causal pair from a detached copy of the layer's normed input, a
        query keeps its ``topk`` best keys, the main GQA attention runs
        over them alone, and the indexer's loss (the KL divergence from
        the main attention's head-summed probabilities) joins the
        step's; ``indexer_dtype``; rotary by three position streams
        (``mrope_section``: the pairs each turns; ``mrope_positions``
        [3][S] where they differ, else the token's index);
        ``index_loss`` False leaves the indexer's loss out
    A   latent attention, then a SwiGLU MLP of ``intermediate_size``
        (gate and up as one product, ``b<i>_gate_up_proj``),
        each behind its own norm and residual. Latent attention
        (``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
        ``qk_rope_head_dim``, ``v_head_dim`` = the not-rotated width):
        queries and keys/values out of low-rank latents with an RMS norm
        on each; a head's query and key are [not rotated ; rotated], the
        rotated key ONE vector a position for all heads, rotary
        (``rope_theta``) over adjacent pairs of the rotated lanes only
    X   latent attention, then experts: sigmoid scores with the
        score-correction bias, the chosen renormalised and scaled
        (``routed_scaling_factor``), experts down(act(gate(x)) * up(x))
        of ``moe_intermediate_size``, and a shared expert of the same
        gated form, ``n_shared_experts * moe_intermediate_size`` wide.
        ``rope_scaling`` (DeepSeek-V2's YaRN keys) scales the rotated
        lanes' frequencies and the softmax scale (ops/attention.py)

    F   gated full attention, then a feed-forward, each behind its own
        norm and residual (PR 41). Attention: causal GQA with layer i's
        own number of query heads (``num_attention_heads_per_layer[i]``;
        ``num_key_value_heads`` and ``head_dim`` are the model's), rotary
        by ``rope_parameters["full_attention"]`` (``rope_theta``,
        ``partial_rotary_factor``: the first lanes of every head rotate,
        the rest pass; ``rope_type`` "yarn" with its keys: scaled
        frequencies and ``attention_factor``), and with ``gating`` one
        scalar a head and position, softplus(h w_gate), on the head's
        output ahead of the output projection. Feed-forward by
        ``mlp_layer_types[i]``: "dense" the SwiGLU MLP of
        ``intermediate_size`` (as `A`'s), "sparse" (the default) `X`'s
        experts, the shared expert ``moe_shared_expert_intermediate_size``
        wide
    S   the same under a sliding window of ``sliding_window_size`` keys,
        rotary by ``rope_parameters["sliding_attention"]``

    C   a gated short convolution, then a feed-forward, each behind its
        own norm and residual (PR 45). The mixer (ops/short_conv.py):
        [B ; C ; x] = h W_in, a causal depthwise convolution of
        ``conv_L_cache`` taps over B * x, the gate C, then W_out; no
        bias, no activation. Feed-forward as `F`'s

    R   a gated delta-rule linear-attention mixer, then `F`'s
        feed-forward, each behind its own norm and residual (PR 58). The
        mixer (ops/delta_rule.py; ``linear_num_key_heads`` Hk,
        ``linear_num_value_heads`` Hv, ``linear_key_head_dim``,
        ``linear_value_head_dim``, ``linear_conv_kernel_dim`` taps):
        [q ; k ; v ; z] = h W_qkvz, [b ; a] = h W_ba, q, k, v through a
        causal depthwise convolution and SiLU, beta = sigmoid(b), g =
        -exp(A_log) softplus(a + dt_bias), q and k L2-normed a head, a
        value head's state S <- exp(g) S; S <- S + k (x) beta (v - S^T k);
        o = S^T q, then (rms_norm(o) w_n) * silu(z) a head and W_out;
        no bias. The model's switches, which `F` / `S` read too:
        ``attn_output_gate`` (the attention op's query projection is
        [E, H x 2 x D], a head's columns [query ; gate], and
        sigmoid(gate) a LANE multiplies the core's output ahead of the
        output projection, where ``gating`` is one scalar a head),
        ``zero_centered_norms`` (every norm of the stream, the final norm
        and the q / k head norms are x_hat * (1 + w), w drawn at zero;
        the delta mixer's own gated norm is not), ``partial_rotary_factor``
        (the model-wide short form of ``rope_parameters``' key),
        ``router_scoring`` "softmax" (`D`'s router: softmax over ALL
        outputs, the chosen renormalised, no score-correction bias;
        "sigmoid" is `X`'s) and ``shared_expert_gate`` (the shared
        expert's output times sigmoid(h w_sg), one scalar a position)

    U   the block of a looped (universal-transformer) model (PR 48):
        `L`'s mixers, causal rotary GQA attention and then the SwiGLU
        MLP (gate and up as one product), with SANDWICH norms: a norm
        on each branch's input and another on its OUTPUT, x' = x +
        rms_norm(attention(rms_norm(x))), x'' = x' +
        rms_norm(mlp(rms_norm(x'))) (``sandwich_norm`` False leaves the
        output norms out: a control)

    m w y f g c   the six blocks of a decoder-hybrid-decoder model
        (SambaY, PR 52; `_sambay_block`), each a mixer and then `A`'s
        SwiGLU MLP, behind LayerNorms WITH bias and residuals, no
        position embedding anywhere. ``mb_per_layer`` 2 names them by
        the published rule from a layer's PUBLISHED index i of L
        (``first_layer_index`` + its place here; L =
        ``published_num_hidden_layers`` or ``num_hidden_layers``, the
        layers built): even i below L / 2 `m`, the Mamba-1 mixer
        (``mamba_d_state``, ``mamba_d_conv``, ``mamba_expand``,
        ``mamba_dt_rank``; ops/ssm.py `MambaMixer`); odd i below L / 2
        `w`, DIFFERENTIAL causal attention (ops/attention.py; lambda_init
        = 0.8 - 0.6 exp(-0.3 i), biases on every projection) under a
        window of ``sliding_window`` keys; i = L / 2 `y`, the Mamba-1
        mixer whose scan output (before its gate) is kept as the MEMORY;
        i = L / 2 + 1 `f`, full differential attention whose projected
        keys and values are kept as the SHARED ones; above them even i
        `g`, the gated memory unit (silu(h W_1) * memory) W_2 (scope
        ``gated_memory``), and odd i `c`, differential cross-attention: a
        query projection of its own over the shared keys and values.
        The memory and the shared keys/values are second (and third)
        OUTPUTS of the ops that make them, PCG tensors which the readers
        take as inputs. A stage that holds a reader and not its producer
        is refused. Controls: ``diff_lambda_scale`` 0 (plain attention:
        the second map left out), ``memory_gated`` (the memory taken
        AFTER the scan's gate), ``cross_own_kv`` (a `c` layer projects
        keys and values of its own)

``layer_types`` (a public config's list of "full_attention" /
"sliding_attention" / "conv" / "linear_attention", one entry a layer
that runs) stands for the pattern: `F`, `S`, `C` and `R` in its order. ``num_dense_layers`` is the
short form of ``mlp_layer_types``: the layers below it are "dense", the
others "sparse". ``qk_layernorm`` gives `F` / `S` an RMS norm of every
query and key head ahead of rotary (scales of ``head_dim``, shared by
the heads). ``num_attention_heads_per_layer`` and
``mlp_layer_types`` are read at the layer's index and may be longer than
the pattern (a model cut in depth can keep its published lists); a
model-wide ``num_attention_heads``, ``rope_theta`` and an all-"sparse"
feed-forward are the short form. The three attention properties are the
op's (``FFModel.multihead_attention(..., gate, partial_rotary_factor,
rope_scaling)``), off by default.

``hc_mult`` n > 0 (PR 64) replaces the residual path of every block
that is built on `_attention_ffn_block` (`A X F S C R U`; any other
letter is refused) by manifold-constrained hyper-connections
(ops/hyper_connection.py): the embedding's output is laid n times side
by side, X_0 = [e; e; ...] [B, S, n*E]; each sublayer reads its branch's
input h = sum_i H_pre[i] X[i] through ``<prefix>_hc_attn`` /
``<prefix>_hc_ffn`` (`FFModel.hc_pre`; the norm, the mixer or the
feed-forward are what they were, on h) and writes X'[i] = sum_j
H_res[i, j] X[j] + H_post[i] y through ``<prefix>_res1`` / ``_res2``
(`FFModel.hc_post`, where the plain path has its `add`); the n streams
are summed ahead of the final norm (``hc_merge``). ``hc_sinkhorn_iters``,
``hc_eps``, ``mhc_h_res_clamp_min`` / ``_max`` are the public config's
keys. With 0 (the default) every
block adds its branch to ONE stream, node for node as before.

``num_nextn_predict_layers`` 1 adds the multi-token-prediction module: a
second branch off the last block's output x_L (before the final norm)
that reads the NEXT token's embedding, u_i = [rms_norm(e(t_{i+1})) ;
rms_norm(x_L,i)] W_eh, runs one more `X` block on u with weights of its
own (ops named ``mtp_*``, under the trace scope ``mtp``) and shares the
embedding and the head with the main model: the two normed hidden
sequences are laid end to end and ONE head gives logits [B, 2S, V], the
main model's and then the module's, whose row i predicts t_{i+2}. Train
with ``WEIGHTED_SPARSE_CATEGORICAL_CROSSENTROPY`` on labels [B, 2S, 2]
(token, weight; weight 0 where a target does not exist, the module's
half scaled by the weight between the two losses); ``ff.loss_parts``
then names the halves, and an epoch's ``ff.op_counters`` hold
``loss/main_nll`` and ``loss/mtp_nll``, the sums of the two unweighted
cross-entropies over their targets. Under ``hc_mult`` the module reads
the SUMMED x_L, lays its u n times as streams of its own and sums them
ahead of its final norm.

``total_ut_steps`` T > 1 applies the whole stack T times with ONE set
of leaves (a looped model): pass 1 builds the layers under the trace
scope ``ut0``; pass t + 1 builds them again under ``ut<t>`` as
``ut<t>_<name>``, every one reading ALL its leaves out of pass 1's
(``FFModel.applied_again``: `jax.grad` sums the T uses, the optimizer
holds one state). The final norm closes EVERY pass and its output is
the next pass's input. The T normed sequences are laid end to end,
pass-major, and under the scope ``exit`` ONE head gives logits [B, T*S,
V] and ONE gate of one column (``exit_gate``, with a bias, the product
in float32) the logit of leaving after that pass; the model's output is
[B, T*S, V + 1], the gate's logit last. Train with
``EXPECTED_EXIT_SPARSE_CATEGORICAL_CROSSENTROPY`` on labels [B, S]:
the expectation of the T cross-entropies under the exit distribution
less ``exit_entropy_beta`` times its entropy (losses.py);
``ff.loss_parts`` names the passes and an epoch's ``ff.op_counters``
hold ``loss/exit_nll_ut<t>``, ``loss/exit_mass_ut<t>`` and
``loss/exit_entropy``.

After the last block ``rms_norm`` and the head, ``logits = x W_head``
(untied), or with ``tie_word_embeddings`` ``logits = x E^T`` with E the
table ``embed_tokens`` gathers from: ONE leaf, read by both ops
(``FFModel.dense(..., tied_to=)``); train with ``SPARSE_CATEGORICAL_CROSSENTROPY`` on labels
``[B, S]``. A pattern with ``D`` keeps the noised half alone from there
on (logits ``[B, L, V]``) and trains with
``WEIGHTED_SPARSE_CATEGORICAL_CROSSENTROPY`` on labels ``[B, L, 2]``
(``dataloader.block_diffusion_batch`` makes both).

A chip's share of a layer is a configuration like any other: fewer heads
(``mamba_num_heads`` with ``n_groups``, ``num_attention_heads`` with
``num_key_value_heads``), a slice of the vocabulary (``vocab_size``), and
``experts_held`` of the ``n_routed_experts`` from ``expert_offset``; the
widths (``hidden_size``, the head sizes, the expert widths, the router's
``n_routed_experts`` outputs) stay the model's.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Optional, Sequence

from flexflow_tpu.config import FFConfig
from flexflow_tpu.ffconst import ActiMode, DataType
from flexflow_tpu.model import FFModel


@dataclasses.dataclass
class DecoderConfig:
    # defaults are a test-size model
    hybrid_override_pattern: str = "ME*"
    vocab_size: int = 256
    hidden_size: int = 64
    layer_norm_epsilon: float = 1e-5
    # attention (`*`, `L`)
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 0                       # 0: hidden_size // heads
    rope_theta: float = 10000.0             # `L`, `W`
    sliding_window_size: int = 0            # `W`
    # `D`: the mask ("block_diffusion", or "causal" over the 2L
    # positions), its block, and whether the two copies share positions
    attention_mask: str = "block_diffusion"
    block_length: int = 4
    shared_positions: bool = True
    qk_norm: bool = True
    hidden_act: str = "silu"
    # Mamba-2 mixer (`M`)
    mamba_num_heads: int = 4
    mamba_head_dim: int = 16
    n_groups: int = 1
    ssm_state_size: int = 16
    conv_kernel: int = 4
    chunk_size: int = 8
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    # experts (`E`)
    n_routed_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 32
    moe_shared_expert_intermediate_size: int = 64
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    experts_held: int = 0                   # 0: all of them
    expert_offset: int = 0
    slot_slack: float = 0.5
    moe_ffn_hidden_size: int = 32           # expert width of `G`, `W`
    # dense MLP (`-`, `L`)
    intermediate_size: int = 128
    # latent attention (`A`, `X`); `rope_whole_head` is a control: rotary
    # over all lanes of a head, as a model without the split would
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_whole_head: bool = False
    rope_scaling: Optional[dict] = None     # YaRN on the rotated lanes
    n_shared_experts: int = 1               # `X`
    # hyper-connections (module docstring): streams (0: the plain
    # residual), Sinkhorn steps, eps of the norm and of the steps'
    # divisions, the clamp ahead of exp
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # `F`, `S`: a layer's own query heads, attention kind ("full_attention"
    # / "sliding_attention": the pattern, where given) and feed-forward
    # kind ("dense" / "sparse"), rotary parameters by attention kind, the
    # per-head output gate (`gate_activation` "sigmoid" is a control)
    num_attention_heads_per_layer: Optional[Sequence[int]] = None
    layer_types: Optional[Sequence[str]] = None
    mlp_layer_types: Optional[Sequence[str]] = None
    rope_parameters: Optional[dict] = None
    gating: bool = False
    gate_activation: str = "softplus"
    qk_layernorm: bool = False
    num_dense_layers: Optional[int] = None
    # `C`: the short convolution's taps (`conv_output_gate` False is a
    # control: the gate C left out)
    conv_L_cache: int = 3
    conv_output_gate: bool = True
    # `R`: the gated delta-rule mixer's heads, taps and chunk, and the
    # switches of its model that `F` / `S` and the feed-forward read
    # (module docstring)
    linear_num_key_heads: int = 2
    linear_num_value_heads: int = 4
    linear_key_head_dim: int = 16
    linear_value_head_dim: int = 16
    linear_conv_kernel_dim: int = 4
    delta_chunk_size: int = 128
    attn_output_gate: bool = False
    zero_centered_norms: bool = False
    partial_rotary_factor: float = 1.0
    router_scoring: str = "sigmoid"
    shared_expert_gate: bool = False
    # the head reads the embedding's table (one leaf) in place of its own
    tie_word_embeddings: bool = False
    # a looped model: the stack applied this many times with one set of
    # leaves, an exit gate beside the head, the entropy bonus's weight.
    # Controls: `sandwich_norm` False (`U` without its output norms),
    # `share_ut_leaves` False (every pass leaves of its own),
    # `norm_between_passes` False (the final norm feeds the head alone)
    total_ut_steps: int = 1
    exit_entropy_beta: float = 0.1
    sandwich_norm: bool = True
    share_ut_leaves: bool = True
    norm_between_passes: bool = True
    # the multi-token-prediction module: 0 or 1; `mtp_shift` is the
    # distance of the token whose embedding it reads (1; 0 is a control)
    num_nextn_predict_layers: int = 0
    mtp_shift: int = 1
    # a decoder-hybrid-decoder model (`m w y f g c`): 2 names the blocks
    # by the published rule (0: the pattern does); the layers built, the
    # published index of the first and the published depth (0: the
    # layers built); the window of `w`; the Mamba-1 sizes (rank 0:
    # ceil(hidden / 16)); three controls (module docstring)
    mb_per_layer: int = 0
    num_hidden_layers: int = 0
    first_layer_index: int = 0
    published_num_hidden_layers: int = 0
    sliding_window: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    diff_lambda_scale: float = 1.0
    memory_gated: bool = False
    cross_own_kv: bool = False
    # `K`: learned sparse attention. `sa_config` as the public config
    # names its keys; the three position streams; the indexer's loss
    sa_config: Optional[dict] = None
    indexer_dtype: str = "float32"
    mrope_section: Optional[Sequence[int]] = None
    mrope_positions: Optional[Sequence[Sequence[int]]] = None
    index_loss: bool = True
    batch_size: int = 2
    seq_length: int = 16
    seq_parallel: Optional[str] = None      # 'seq': ring attention


def _attention(ff, h, cfg, name, rope, window=0):
    return ff.multihead_attention(
        h, h, h, cfg.hidden_size, cfg.num_attention_heads, bias=False,
        causal=True, num_kv_heads=cfg.num_key_value_heads, rope=rope,
        rope_theta=cfg.rope_theta, seq_parallel=cfg.seq_parallel,
        head_dim=cfg.head_dim, window=window, name=name)


def _attention_experts_block(ff, t, i, cfg, windowed):
    """`G` / `W`: x' = x + attention(h), x'' = x' + experts(norm(x'))
    with the experts chosen from h = norm(x), the attention's input."""
    eps = cfg.layer_norm_epsilon
    h = ff.rms_norm(t, eps=eps, name=f"b{i}_norm")
    window = cfg.sliding_window_size if windowed else 0
    t = ff.add(t, _attention(ff, h, cfg, f"b{i}_attn", rope=windowed,
                             window=window), name=f"b{i}_res1")
    g = ff.rms_norm(t, eps=eps, name=f"b{i}_post_norm")
    m = ff.moe_layer(
        g, cfg.n_routed_experts, cfg.num_experts_per_tok,
        cfg.moe_ffn_hidden_size, experts_held=cfg.experts_held,
        expert_offset=cfg.expert_offset,
        routed_scaling=cfg.routed_scaling_factor,
        norm_topk=cfg.norm_topk_prob, slot_slack=cfg.slot_slack,
        scoring="softmax", gated=True, router_input=h, name=f"b{i}_mixer")
    return ff.add(t, m, name=f"b{i}_res2")


def _sparse_attention(ff, h, i, cfg):
    """`K`'s attention: causal QK-normed rotary GQA over the keys an
    indexer keeps (``sa_config``)."""
    sa = dict(cfg.sa_config or {})
    if sa.get("indexer_num_kv_heads", 1) != 1 or not sa.get("topk"):
        raise ValueError(f"decoder: sa_config {sa} (an indexer of ONE key "
                         f"head and a topk)")
    return ff.multihead_attention(
        h, h, h, cfg.hidden_size, cfg.num_attention_heads, bias=False,
        causal=True, num_kv_heads=cfg.num_key_value_heads, rope=True,
        rope_theta=cfg.rope_theta, head_dim=cfg.head_dim,
        qk_norm=cfg.qk_norm, qk_norm_eps=cfg.layer_norm_epsilon,
        sparse_index=(sa["indexer_num_heads"], sa["indexer_head_dim"],
                      sa["topk"]),
        indexer_dtype=cfg.indexer_dtype, mrope_section=cfg.mrope_section,
        mrope_positions=cfg.mrope_positions, index_loss=cfg.index_loss,
        name=f"b{i}_attn")


def _block_diffusion_block(ff, t, i, cfg, sparse=False):
    """`D`: x' = x + attention(norm(x)), x'' = x' + experts(norm(x'));
    `K` (``sparse``): the same with learned sparse attention."""
    eps = cfg.layer_norm_epsilon
    if cfg.attention_mask not in ("block_diffusion", "causal"):
        raise ValueError(f"decoder: unknown attention_mask "
                         f"{cfg.attention_mask!r}")
    half = cfg.seq_length // 2
    masked = cfg.attention_mask == "block_diffusion"
    h = ff.rms_norm(t, eps=eps, name=f"b{i}_norm")
    a = _sparse_attention(ff, h, i, cfg) if sparse else ff.multihead_attention(
        h, h, h, cfg.hidden_size, cfg.num_attention_heads, bias=False,
        causal=not masked, num_kv_heads=cfg.num_key_value_heads, rope=True,
        rope_theta=cfg.rope_theta, head_dim=cfg.head_dim,
        block_diffusion=(half, cfg.block_length) if masked else None,
        rope_wrap=half if cfg.shared_positions else 0,
        qk_norm=cfg.qk_norm, qk_norm_eps=eps, name=f"b{i}_attn")
    t = ff.add(t, a, name=f"b{i}_res1")
    g = ff.rms_norm(t, eps=eps, name=f"b{i}_post_norm")
    m = ff.moe_layer(
        g, cfg.n_routed_experts, cfg.num_experts_per_tok,
        cfg.moe_intermediate_size, experts_held=cfg.experts_held,
        expert_offset=cfg.expert_offset,
        routed_scaling=cfg.routed_scaling_factor,
        norm_topk=cfg.norm_topk_prob, slot_slack=cfg.slot_slack,
        scoring="softmax", gated=True, activation=cfg.hidden_act,
        name=f"b{i}_mixer")
    return ff.add(t, m, name=f"b{i}_res2")


def _swiglu_mlp(ff, h, cfg, prefix, one_product=False):
    """down(silu(gate(x)) * up(x)); with ``one_product`` gate and up are
    the two halves of one product's columns (the leaf
    ``<prefix>_gate_up_proj``), which is what the search's rewrite makes
    of the two anyway, under a name the builder knows."""
    if one_product:
        gate, up = ff.split(
            ff.dense(h, 2 * cfg.intermediate_size, use_bias=False,
                     name=f"{prefix}_gate_up_proj"),
            [cfg.intermediate_size] * 2, axis=2, name=f"{prefix}_gate_up")
    else:
        gate = ff.dense(h, cfg.intermediate_size, use_bias=False,
                        name=f"{prefix}_gate_proj")
        up = ff.dense(h, cfg.intermediate_size, use_bias=False,
                      name=f"{prefix}_up_proj")
    silu = ff.multiply(gate, ff.sigmoid(gate, name=f"{prefix}_sig"),
                       name=f"{prefix}_silu")
    h = ff.multiply(silu, up, name=f"{prefix}_swiglu")
    return ff.dense(h, cfg.hidden_size, use_bias=False,
                    name=f"{prefix}_down_proj")


def _llama_block(ff, t, i, cfg):
    h = ff.rms_norm(t, eps=cfg.layer_norm_epsilon, name=f"l{i}_input_ln")
    t = ff.add(t, _attention(ff, h, cfg, f"l{i}_attn", rope=True),
               name=f"l{i}_res1")
    h = ff.rms_norm(t, eps=cfg.layer_norm_epsilon, name=f"l{i}_post_ln")
    return ff.add(t, _swiglu_mlp(ff, h, cfg, f"l{i}"), name=f"l{i}_res2")


def _residual(ff, cfg):
    """The residual rule of a block, (read, write): ``read(t, name)``
    gives (what the branch's norm reads, what ``write`` needs of the
    stream) and ``write(kept, y, name)`` the stream after the branch's
    output y. Plain: the stream itself and `add`. Under ``hc_mult`` the
    two halves of a hyper-connection."""
    n = cfg.hc_mult
    if not n:
        return (lambda t, name: (t, t),
                lambda kept, y, name: ff.add(kept, y, name=name))

    def read(t, name):
        h, maps, stream = ff.hc_pre(
            t, n, sinkhorn_iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
            clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
            name=name)
        return h, (stream, maps)

    def write(kept, y, name):
        return ff.hc_post(kept[0], y, kept[1], n, name=name)

    return read, write


def _as_streams(ff, t, cfg, name):
    """[B, S, E] laid ``hc_mult`` times side by side (as it is at 0)."""
    return (ff.concat([t] * cfg.hc_mult, axis=2, name=name)
            if cfg.hc_mult else t)


def _streams_summed(ff, t, cfg, name):
    """The sum of the ``hc_mult`` streams of [B, S, n*E]."""
    if cfg.hc_mult < 2:
        return t
    parts = ff.split(t, [cfg.hidden_size] * cfg.hc_mult, axis=2, name=name)
    t = parts[0]
    for i, part in enumerate(parts[1:], 1):
        t = ff.add(t, part, name=f"{name}_sum{i}")
    return t


def _attention_ffn_block(ff, t, prefix, cfg, mixer, experts,
                         shared_width, sandwich=False):
    """x' = x + mixer(norm(x)), x'' = x' + f(norm(x')) with f the
    SwiGLU MLP or the sigmoid-scored experts with their gated shared
    expert (none at ``shared_width`` 0): the block of `A` / `X`, of
    `F` / `S` / `C` and of `U`, which differ in ``mixer(h)``; with
    ``sandwich`` each branch's output is normed too. Where `x +` stands
    is the model's residual rule (`_residual`: `add`, or under
    ``hc_mult`` the two halves of a hyper-connection)."""
    eps, zero = cfg.layer_norm_epsilon, cfg.zero_centered_norms
    read, write = _residual(ff, cfg)
    h, kept = read(t, f"{prefix}_hc_attn")
    h = ff.rms_norm(h, eps=eps, zero_centered=zero, name=f"{prefix}_norm")
    a = mixer(h)
    if sandwich:
        a = ff.rms_norm(a, eps=eps, name=f"{prefix}_attn_out_norm")
    t = write(kept, a, f"{prefix}_res1")
    g, kept = read(t, f"{prefix}_hc_ffn")
    g = ff.rms_norm(g, eps=eps, zero_centered=zero,
                    name=f"{prefix}_post_norm")
    if not experts:
        m = _swiglu_mlp(ff, g, cfg, prefix, one_product=True)
        if sandwich:
            m = ff.rms_norm(m, eps=eps, name=f"{prefix}_mlp_out_norm")
        return write(kept, m, f"{prefix}_res2")
    m = ff.moe_layer(
        g, cfg.n_routed_experts, cfg.num_experts_per_tok,
        cfg.moe_intermediate_size, shared_width=shared_width,
        experts_held=cfg.experts_held, expert_offset=cfg.expert_offset,
        routed_scaling=cfg.routed_scaling_factor,
        norm_topk=cfg.norm_topk_prob, slot_slack=cfg.slot_slack,
        scoring=cfg.router_scoring, gated=True, activation=cfg.hidden_act,
        shared_gate=cfg.shared_expert_gate, name=f"{prefix}_mixer")
    return write(kept, m, f"{prefix}_res2")


def _latent_block(ff, t, prefix, cfg, experts):
    """`A` / `X`: latent attention, then the SwiGLU MLP or the experts."""
    if cfg.v_head_dim != cfg.qk_nope_head_dim:
        raise ValueError("decoder: latent attention takes a value head as "
                         "wide as the query/key head's not-rotated part")

    def attention(h):
        return ff.multihead_attention(
            h, h, h, cfg.hidden_size, cfg.num_attention_heads, bias=False,
            causal=True, rope=True, rope_theta=cfg.rope_theta,
            head_dim=cfg.qk_nope_head_dim, q_lora_rank=cfg.q_lora_rank,
            kv_lora_rank=cfg.kv_lora_rank,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            latent_norm_eps=cfg.layer_norm_epsilon,
            rope_whole_head=cfg.rope_whole_head,
            rope_scaling=cfg.rope_scaling, name=f"{prefix}_attn")

    return _attention_ffn_block(
        ff, t, prefix, cfg, attention, experts,
        cfg.n_shared_experts * cfg.moe_intermediate_size)


LAYER_TYPE_LETTERS = {"full_attention": "F", "sliding_attention": "S",
                      "conv": "C", "linear_attention": "R"}
LETTER_LAYER_TYPES = {v: k for k, v in LAYER_TYPE_LETTERS.items()}


def _of_layer(values, i, default):
    """Layer i's entry of a per-layer list, or the model-wide value."""
    return default if values is None else values[i]


def _gated_block(ff, t, i, cfg, letter):
    """`F` / `S`: layer i's own heads, its kind's rotary parameters and
    window, the heads' norm, the gate; `C`: the gated short convolution;
    `R`: the gated delta-rule mixer; then layer i's kind of
    feed-forward."""
    rope = dict((cfg.rope_parameters or {}).get(
        LETTER_LAYER_TYPES[letter]) or {})
    theta = float(rope.pop("rope_theta", cfg.rope_theta))
    partial = float(rope.pop("partial_rotary_factor",
                             cfg.partial_rotary_factor))
    scaled = rope.get("rope_type", "default") != "default"
    feed_forward = _of_layer(
        cfg.mlp_layer_types, i,
        "dense" if i < (cfg.num_dense_layers or 0) else "sparse")
    if feed_forward not in ("dense", "sparse"):
        raise ValueError(f"decoder: mlp_layer_types[{i}] is "
                         f"{feed_forward!r} (known: dense, sparse)")

    def short_conv(h):
        return ff.short_conv(h, kernel=cfg.conv_L_cache,
                             output_gate=cfg.conv_output_gate,
                             name=f"b{i}_conv")

    def delta_rule(h):
        return ff.delta_mixer(
            h, cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            conv_kernel=cfg.linear_conv_kernel_dim,
            chunk_size=cfg.delta_chunk_size, eps=cfg.layer_norm_epsilon,
            name=f"b{i}_delta")

    def attention(h):
        return ff.multihead_attention(
            h, h, h, cfg.hidden_size,
            _of_layer(cfg.num_attention_heads_per_layer, i,
                      cfg.num_attention_heads),
            bias=False, causal=True, num_kv_heads=cfg.num_key_value_heads,
            rope=True, rope_theta=theta, head_dim=cfg.head_dim,
            window=cfg.sliding_window_size if letter == "S" else 0,
            gate=cfg.gating, gate_activation=cfg.gate_activation,
            partial_rotary_factor=partial,
            rope_scaling=rope if scaled else None,
            qk_norm=cfg.qk_layernorm, qk_norm_eps=cfg.layer_norm_epsilon,
            lane_gate=cfg.attn_output_gate,
            qk_norm_zero_centered=cfg.zero_centered_norms,
            name=f"b{i}_attn")

    return _attention_ffn_block(ff, t, f"b{i}", cfg,
                                {"C": short_conv, "R": delta_rule}.get(
                                    letter, attention),
                                feed_forward == "sparse",
                                cfg.moe_shared_expert_intermediate_size)


SAMBAY_LETTERS = "mwyfgc"


def sambay_pattern(cfg) -> str:
    """The blocks of a decoder-hybrid-decoder stage by the published
    rule (module docstring), one letter a layer built."""
    if cfg.mb_per_layer != 2:
        raise ValueError(f"decoder: mb_per_layer {cfg.mb_per_layer} (the "
                         f"decoder-hybrid-decoder rule is written for 2)")
    total = cfg.published_num_hidden_layers or cfg.num_hidden_layers
    first = cfg.first_layer_index
    if not 0 < cfg.num_hidden_layers <= total - first or total % 4:
        # L / 2 is a Mamba layer's index, so it is even
        raise ValueError(
            f"decoder: layers {first}..{first + cfg.num_hidden_layers - 1} "
            f"of a published depth of {total} (a multiple of 4, and the "
            f"stage inside it)")
    half = total // 2
    return "".join(
        ("m" if i < half else "y" if i == half else "g") if i % 2 == 0
        else ("w" if i < half else "f" if i == half + 1 else "c")
        for i in range(first, first + cfg.num_hidden_layers))


def _sambay_block(ff, t, i, letter, cfg, shared):
    """One `m w y f g c` block: x' = x + mixer(LN(x)), x'' = x' +
    mlp(LN(x')). ``shared`` holds the memory and the keys/values the
    stage's layers have made so far; `y` and `f` fill it, `g` and `c`
    read it."""
    eps, depth = cfg.layer_norm_epsilon, cfg.first_layer_index + i
    total = cfg.published_num_hidden_layers or cfg.num_hidden_layers
    half = total // 2
    h = ff.layer_norm(t, eps=eps, name=f"b{i}_norm")

    def missing(what, maker):
        return ValueError(
            f"decoder: layer {depth} reads {what} of layer {maker}, which "
            f"this stage (layers {cfg.first_layer_index}.."
            f"{cfg.first_layer_index + len(shared['pattern']) - 1} of "
            f"{total}) does not hold: a stage keeps a reader with its "
            f"producer (exported tensors do not cross stages)")

    def attention(kv=None, window=0, export=False):
        return ff.multihead_attention(
            h, *(kv or (h, h)), cfg.hidden_size, cfg.num_attention_heads,
            bias=True, qkv_bias=True, causal=True,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            window=window, differential=True,
            lambda_init=0.8 - 0.6 * math.exp(-0.3 * depth),
            lambda_scale=cfg.diff_lambda_scale,
            diff_norm_eps=eps, kv_given=kv is not None, export_kv=export,
            name=f"b{i}_attn")

    if letter in "my":
        a = ff.mamba_mixer(
            h, state_size=cfg.mamba_d_state, conv_kernel=cfg.mamba_d_conv,
            expand=cfg.mamba_expand, dt_rank=cfg.mamba_dt_rank,
            export_memory=letter == "y", export_gated=cfg.memory_gated,
            time_step_min=cfg.time_step_min,
            time_step_max=cfg.time_step_max, name=f"b{i}_mixer")
        if letter == "y":
            a, shared["memory"] = a
    elif letter == "w":
        a = attention(window=cfg.sliding_window)
    elif letter == "f":
        a, *shared["kv"] = attention(export=True)
    elif letter == "g":
        if "memory" not in shared:
            raise missing("the memory (the scan's output)", half)
        with ff.scope("gated_memory"):
            width = shared["memory"].shape[-1]
            gate = ff.dense(h, width, use_bias=False,
                            name=f"b{i}_memory_in_proj")
            silu = ff.multiply(gate, ff.sigmoid(gate, name=f"b{i}_memory_sig"),
                               name=f"b{i}_memory_silu")
            a = ff.dense(ff.multiply(silu, shared["memory"],
                                     name=f"b{i}_memory_gated"),
                         cfg.hidden_size, use_bias=False,
                         name=f"b{i}_memory_out_proj")
    else:
        if cfg.cross_own_kv:
            a = attention()
        elif "kv" not in shared:
            raise missing("the shared keys and values", half + 1)
        else:
            a = attention(kv=shared["kv"])
    t = ff.add(t, a, name=f"b{i}_res1")
    g = ff.layer_norm(t, eps=eps, name=f"b{i}_post_norm")
    return ff.add(t, _swiglu_mlp(ff, g, cfg, f"b{i}", one_product=True),
                  name=f"b{i}_res2")


def _mtp_module(ff, embedded, x_last, cfg):
    """The multi-token-prediction module's hidden states, normed for the
    shared head: [B, S, E] whose row i stands for the token after next."""
    eps, seq = cfg.layer_norm_epsilon, cfg.seq_length
    if cfg.num_nextn_predict_layers != 1:
        raise NotImplementedError("decoder: one multi-token-prediction "
                                  "module (num_nextn_predict_layers 0 or 1)")
    with ff.scope("mtp"):
        e_next = embedded
        if cfg.mtp_shift:
            # row i reads e(t_{i + shift}); the last rows wrap around to
            # the first tokens and carry no target
            first, rest = ff.split(embedded, [cfg.mtp_shift,
                                              seq - cfg.mtp_shift],
                                   axis=1, name="mtp_shift")
            e_next = ff.concat([rest, first], axis=1, name="mtp_next")
        u = ff.concat([ff.rms_norm(e_next, eps=eps, name="mtp_enorm"),
                       ff.rms_norm(x_last, eps=eps, name="mtp_hnorm")],
                      axis=2, name="mtp_eh")
        u = ff.dense(u, cfg.hidden_size, use_bias=False, name="mtp_eh_proj")
        u = _latent_block(ff, _as_streams(ff, u, cfg, "mtp_hc_streams"),
                          "mtp", cfg, experts=True)
        u = _streams_summed(ff, u, cfg, "mtp_hc_merge")
        return ff.rms_norm(u, eps=eps, name="mtp_final_ln")


def _mixer(ff, h, letter, i, cfg):
    name = f"b{i}_mixer"
    if letter == "M":
        return ff.ssm_mixer(
            h, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size,
            n_groups=cfg.n_groups, conv_kernel=cfg.conv_kernel,
            chunk_size=cfg.chunk_size, eps=cfg.layer_norm_epsilon,
            time_step_min=cfg.time_step_min,
            time_step_max=cfg.time_step_max,
            time_step_floor=cfg.time_step_floor, name=name)
    if letter == "E":
        return ff.moe_layer(
            h, cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size,
            shared_width=cfg.moe_shared_expert_intermediate_size,
            experts_held=cfg.experts_held, expert_offset=cfg.expert_offset,
            routed_scaling=cfg.routed_scaling_factor,
            norm_topk=cfg.norm_topk_prob, slot_slack=cfg.slot_slack,
            name=name)
    if letter == "*":
        return _attention(ff, h, cfg, name, rope=False)
    if letter == "-":
        up = ff.dense(h, cfg.intermediate_size, use_bias=False,
                      activation=ActiMode.AC_MODE_RELU, name=f"b{i}_up")
        return ff.dense(ff.multiply(up, up, name=f"b{i}_sq"),
                        cfg.hidden_size, use_bias=False, name=name)
    raise ValueError(f"decoder pattern: unknown block letter {letter!r} "
                     f"(known: M E * - L G W D K A X F S C R U m w y f g c)")


def _stack(ff, t, pattern, cfg):
    """One application of the blocks ``pattern`` names."""
    shared = {"pattern": pattern}   # what `y` and `f` make for `g` and `c`
    for i, letter in enumerate(pattern):
        if letter in SAMBAY_LETTERS:
            t = _sambay_block(ff, t, i, letter, cfg, shared)
            continue
        if letter == "U":
            t = _attention_ffn_block(
                ff, t, f"b{i}", cfg,
                lambda h: _attention(ff, h, cfg, f"b{i}_attn", rope=True),
                False, 0, sandwich=cfg.sandwich_norm)
            continue
        if letter in "FSCR":
            t = _gated_block(ff, t, i, cfg, letter)
            continue
        if letter in "AX":
            t = _latent_block(ff, t, f"b{i}", cfg, experts=letter == "X")
            continue
        if letter == "L":
            t = _llama_block(ff, t, i, cfg)
            continue
        if letter in "GW":
            t = _attention_experts_block(ff, t, i, cfg, letter == "W")
            continue
        if letter in "DK":
            t = _block_diffusion_block(ff, t, i, cfg, sparse=letter == "K")
            continue
        h = ff.rms_norm(t, eps=cfg.layer_norm_epsilon, name=f"b{i}_norm")
        t = ff.add(t, _mixer(ff, h, letter, i, cfg), name=f"b{i}_res")
    return t


def _looped(ff, t, pattern, cfg):
    """A looped model from the embedding on: the stack and the final
    norm ``total_ut_steps`` times, pass 1 the owner of every leaf and
    the passes after it their readers, then the head and the exit gate
    over all passes' normed sequences laid end to end."""
    eps, passes = cfg.layer_norm_epsilon, []
    for ut in range(cfg.total_ut_steps):
        again = (ff.applied_again(f"ut{ut}_", cfg.share_ut_leaves) if ut
                 else contextlib.nullcontext())
        with ff.scope(f"ut{ut}"), again:
            t = _stack(ff, t, pattern, cfg)
            normed = ff.rms_norm(t, eps=eps, name="final_ln")
        passes.append(normed)
        if cfg.norm_between_passes:
            t = normed
    with ff.scope("exit"):
        t = ff.concat(passes, axis=1, name="ut_passes")
        logits = ff.dense(t, cfg.vocab_size, use_bias=False, name="lm_head")
        gate = ff.dense(t, 1, full_precision=True, name="exit_gate")
        ff.concat([logits, gate], axis=2, name="logits_and_exit_gate")
    ff.loss_parts = tuple(f"ut{ut}" for ut in range(cfg.total_ut_steps))
    ff.exit_entropy_beta = cfg.exit_entropy_beta
    return ff


def create_decoder(cfg: DecoderConfig, ff_config: FFConfig = None) -> FFModel:
    ff = FFModel(ff_config or FFConfig(batch_size=cfg.batch_size))
    ids = ff.create_tensor((cfg.batch_size, cfg.seq_length),
                           dtype=DataType.INT32, name="input_ids")
    t = embedded = ff.embedding(ids, cfg.vocab_size, cfg.hidden_size,
                                name="embed_tokens")
    pattern = cfg.hybrid_override_pattern
    if cfg.mb_per_layer:
        pattern = sambay_pattern(cfg)
    if cfg.layer_types is not None:
        unknown = set(cfg.layer_types) - set(LAYER_TYPE_LETTERS)
        if unknown:
            raise ValueError(f"decoder: layer_types holds {sorted(unknown)} "
                             f"(known: {sorted(LAYER_TYPE_LETTERS)})")
        pattern = "".join(LAYER_TYPE_LETTERS[k] for k in cfg.layer_types)
    if cfg.hc_mult:
        others = sorted(set(pattern) - set("AXFSCRU"))
        if others or cfg.hc_mult < 0:
            raise ValueError(
                f"decoder: hc_mult {cfg.hc_mult} with the blocks {others}: "
                f"hyper-connections stand in the residual path of the "
                f"blocks `A X F S C R U` (`_attention_ffn_block`)")
        t = _as_streams(ff, t, cfg, "hc_streams")
    if cfg.total_ut_steps > 1:
        if ("D" in pattern or cfg.num_nextn_predict_layers
                or cfg.tie_word_embeddings or cfg.hc_mult):
            raise NotImplementedError(
                "decoder: a looped model (total_ut_steps > 1) takes an "
                "untied head, no multi-token-prediction module, no "
                "block-diffusion block and no hyper-connections")
        return _looped(ff, t, pattern, cfg)
    t = _streams_summed(ff, _stack(ff, t, pattern, cfg), cfg, "hc_merge")
    if "D" in pattern:
        # the head and the loss read the noised half alone
        half = cfg.seq_length // 2
        t = ff.split(t, [half, half], axis=1, name="noised_half")[0]
    mtp = (_mtp_module(ff, embedded, t, cfg)
           if cfg.num_nextn_predict_layers else None)
    # a decoder-hybrid-decoder model's norms are LayerNorms with bias
    final_norm = (ff.layer_norm if set(pattern) <= set(SAMBAY_LETTERS)
                  else ff.rms_norm)
    t = final_norm(t, eps=cfg.layer_norm_epsilon, name="final_ln",
                   **({"zero_centered": True} if cfg.zero_centered_norms
                      else {}))
    if "K" in pattern:
        # the weighted loss then counts `loss/main_nll` beside the
        # indexers' `loss/index_kl`
        ff.loss_parts = ("main",)
    if mtp is not None:
        # one head over both hidden sequences laid end to end
        t = ff.concat([t, mtp], axis=1, name="main_and_mtp")
        ff.loss_parts = ("main", "mtp")
    t = ff.dense(t, cfg.vocab_size, use_bias=False, name="lm_head",
                 tied_to=embedded if cfg.tie_word_embeddings else None)
    return ff
