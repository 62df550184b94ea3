"""Model-family presets.

Configurations for the MoE families the benchmark matrix targets
(BASELINE.md) and the common public architectures a framework user
expects.  Each returns a full :class:`MoEConfig`; pass ``**overrides`` to
resize (e.g. fewer layers for a smoke run).
"""

from __future__ import annotations

import jax.numpy as jnp

from flashmoe_tpu.config import Activation, MoEConfig


def mixtral_8x7b(**overrides) -> MoEConfig:
    """Mixtral-8x7B: 8 experts, top-2, SwiGLU, GQA 32/8."""
    base = dict(
        num_experts=8, expert_top_k=2, hidden_size=4096,
        intermediate_size=14336, num_layers=32, moe_frequency=1,
        vocab_size=32000, num_heads=32, num_kv_heads=8,
        sequence_len=4096, gated_ffn=True, hidden_act=Activation.SILU,
        rope_theta=1e6, drop_tokens=False, dtype=jnp.bfloat16,
    )
    base.update(overrides)
    return MoEConfig(**base)


def deepseek_moe_16b(**overrides) -> MoEConfig:
    """DeepSeekMoE-16B: 64 routed + 2 shared experts, top-6, fine-grained."""
    base = dict(
        num_experts=64, expert_top_k=6, num_shared_experts=2,
        hidden_size=2048, intermediate_size=1408, num_layers=28,
        moe_frequency=1, vocab_size=102400, num_heads=16,
        sequence_len=4096, gated_ffn=True, hidden_act=Activation.SILU,
        drop_tokens=False, dtype=jnp.bfloat16,
    )
    base.update(overrides)
    return MoEConfig(**base)


def switch_base(**overrides) -> MoEConfig:
    """Switch-Transformer-Base flavour: top-1 routing, capacity + drops."""
    base = dict(
        num_experts=128, expert_top_k=1, hidden_size=768,
        intermediate_size=3072, num_layers=12, moe_frequency=2,
        vocab_size=32128, num_heads=12, sequence_len=512,
        capacity_factor=1.25, drop_tokens=True,
        hidden_act=Activation.RELU, dtype=jnp.bfloat16,
    )
    base.update(overrides)
    return MoEConfig(**base)


def flashmoe_reference(**overrides) -> MoEConfig:
    """The reference repo's benchmark config
    (``csrc/flashmoe_config.json``: E=64 top-2 H=2048 I=2048 S=8192)."""
    base = dict(
        num_experts=64, expert_top_k=2, hidden_size=2048,
        intermediate_size=2048, num_layers=2, moe_frequency=2,
        vocab_size=50257, num_heads=16, sequence_len=8192,
        capacity_factor=1.0, drop_tokens=True, dtype=jnp.bfloat16,
    )
    base.update(overrides)
    return MoEConfig(**base)


def joyai_llm_flash(**overrides) -> MoEConfig:
    """JoyAI-LLM-Flash (48B-A2.7B; huggingface.co/jdopensource/
    JoyAI-LLM-Flash ``config.json``, ``model_type`` joyai_llm_flash): 40
    layers of multi-head latent attention (32 heads), the first a dense
    SwiGLU of width 7168, the rest 256 routed experts top-8 + 1 shared
    of width 768 behind a sigmoid router with a selection bias
    (``noaux_tc``), normalised top-k weights times 2.5; one group, so no
    group-limited selection.  Its multi-token-prediction layer is not
    part of the model this preset builds."""
    base = dict(
        num_experts=256, expert_top_k=8, num_shared_experts=1,
        hidden_size=2048, intermediate_size=768, num_layers=40,
        moe_frequency=1, first_k_dense=1, dense_intermediate_size=7168,
        vocab_size=129280, num_heads=32, attention_kind="mla",
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=3.2e7,
        router_score="sigmoid", router_bias=True, norm_topk_prob=True,
        routed_scaling_factor=2.5, sequence_len=4096, gated_ffn=True,
        hidden_act=Activation.SILU, drop_tokens=False, dtype=jnp.bfloat16,
    )
    base.update(overrides)
    return MoEConfig(**base)


def ling3_flash(**overrides) -> MoEConfig:
    """The language model of Ling-3.0-flash-VL (~125B-A5.5B;
    huggingface.co/inclusionAI/Ling-3.0-flash-VL ``config.json``): 42
    layers in groups of 6 (the source's ``layer_group_size``), five 'kda' layers
    (delta-rule linear attention: 32 heads of a 128 x 128 state, a
    4-tap convolution, decay bounded below at exp(-5)) to one of latent
    attention with NO query rank (``q_lora_rank`` null), RoPE theta 6e6;
    2 leading dense layers of width 6144, then 512 routed experts top-8
    + 1 shared of width 768 behind a sigmoid router with a selection
    bias, limited to the 4 best of 8 groups, normalised weights times
    2.5.  Its vision tower and its multi-token-prediction module are not
    part of the model this preset builds."""
    base = dict(
        num_experts=512, expert_top_k=8, num_shared_experts=1,
        hidden_size=2560, intermediate_size=768, num_layers=42,
        moe_frequency=1, first_k_dense=2, dense_intermediate_size=6144,
        vocab_size=157184, num_heads=32, attention_kind="mla",
        q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=6e6,
        kda_heads=32, kda_head_dim=128, kda_conv=4,
        kda_lower_bound=-5.0, router_score="sigmoid", router_bias=True,
        norm_topk_prob=True, routed_scaling_factor=2.5, n_group=8,
        topk_group=4, sequence_len=4096, gated_ffn=True,
        hidden_act=Activation.SILU, drop_tokens=False, dtype=jnp.bfloat16,
    )
    base.update(overrides)
    # the last layer of every group of 6 is the latent-attention one
    base.setdefault("layer_mixers", tuple(
        "mla" if (li + 1) % 6 == 0 else "kda"
        for li in range(base["num_layers"])))
    return MoEConfig(**base)


def lfm2_24b_a2b(**overrides) -> MoEConfig:
    """LFM2-24B-A2B (huggingface.co/LiquidAI/LFM2-24B-A2B ``config.json``,
    ``model_type`` lfm2_moe): 40 layers of width 2048, three gated short
    convolutions (3 taps) to one grouped-query attention layer (32 heads
    over 8 K/V heads of width 64, RMSNorm on every head of q and k before
    RoPE, theta 1e6): ``layer_types`` has full attention at layers 2, 6,
    ..., 38.  2 leading dense layers of width 11776, then 64 experts top-4
    of width 1536 behind a sigmoid router with a selection bias,
    normalised weights, no shared expert; RMSNorm eps 1e-5."""
    base = dict(
        num_experts=64, expert_top_k=4, num_shared_experts=0,
        hidden_size=2048, intermediate_size=1536, num_layers=40,
        moe_frequency=1, first_k_dense=2, dense_intermediate_size=11776,
        vocab_size=65536, num_heads=32, num_kv_heads=8, qk_norm=True,
        conv_taps=3, norm_eps=1e-5, rope_theta=1e6,
        router_score="sigmoid", router_bias=True, norm_topk_prob=True,
        routed_scaling_factor=1.0, sequence_len=4096, gated_ffn=True,
        hidden_act=Activation.SILU, drop_tokens=False, dtype=jnp.bfloat16,
    )
    base.update(overrides)
    # the published layer_types: full attention at 2, 6, ..., 38
    base.setdefault("layer_mixers", tuple(
        "mha" if li % 4 == 2 else "conv"
        for li in range(base["num_layers"])))
    return MoEConfig(**base)


def nemotron3_nano_30b_a3b(**overrides) -> MoEConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B (31.6B-A3.2B; huggingface.co/nvidia/
    NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``, ``model_type``
    nemotron_h): 52 blocks of width 2688, each ONE thing behind one norm by
    ``hybrid_override_pattern``: a state-space mixer (``M``, 23: Mamba-2,
    64 heads of 64 over a float32 state of 128 a channel, 8 groups, a
    4-tap convolution with a bias, chunks of 128), a mixture of experts
    (``E``, 23: 128 ungated relu^2 experts of width 1856, STORED with 64
    zero columns to 1920 = 15 x 128 lanes, top-6 + 1 shared
    of width 3712 behind a sigmoid router with a selection bias,
    normalised weights times 2.5) or attention (``*``, 6: 32 query heads
    over 2 K/V heads of 128, NO rotary embedding); RMSNorm eps 1e-5,
    untied head.  The shared expert is held as ``num_shared_experts`` 2 x
    1856: for an ungated expert one of width 3712 IS two of 1856 over the
    halves of the same two matrices.  ``pattern`` (an override) replaces
    the published pattern, ``num_layers`` alone keeps its first letters."""
    pattern = overrides.pop(
        "pattern", "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    )[:overrides.get("num_layers")]
    base = dict(
        num_experts=128, expert_top_k=6, num_shared_experts=2,
        hidden_size=2688, intermediate_size=1856, vocab_size=131072,
        num_heads=32, num_kv_heads=2, head_dim=128, use_rope=False,
        ssm_heads=64, ssm_head_dim=64, ssm_groups=8, ssm_state=128,
        ssm_conv=4, ssm_chunk=128, norm_eps=1e-5,
        router_score="sigmoid", router_bias=True, norm_topk_prob=True,
        routed_scaling_factor=2.5, sequence_len=4096, gated_ffn=False,
        hidden_act=Activation.RELU2, drop_tokens=False, dtype=jnp.bfloat16,
    )
    base.update(overrides)
    # the experts stored at whole lanes: 64 zero columns beside 1856
    base.setdefault("intermediate_pad", -base["intermediate_size"] % 128)
    kinds = {"M": ("ssm", None), "E": (None, "moe"), "*": ("mha", None)}
    base.setdefault("num_layers", len(pattern))
    base.setdefault("layer_mixers", tuple(kinds[c][0] for c in pattern))
    base.setdefault("layer_ffns", tuple(kinds[c][1] for c in pattern))
    return MoEConfig(**base)


def longcat_flash(**overrides) -> MoEConfig:
    """The language model of LongCat-Flash-Omni (560B-A27B;
    huggingface.co/meituan-longcat/LongCat-Flash-Omni ``config.json``):
    28 published layers of width 6144, each TWO latent-attention
    sublayers (64 heads, ``q_lora_rank`` 1536 and ``kv_lora_rank`` 512
    with both rank scales, nope 128 / rope 64 / v 128, theta 1e7) and TWO
    dense SwiGLU FFNs of width 12288 around ONE shortcut-connected
    mixture: it reads the first dense FFN's normed input and joins the
    residual stream after the second's (``layer_ffns``: 'dense+moe' then
    'dense+join', so ``num_layers`` counts SUBLAYERS, 56).  The router is
    768 wide, a softmax with a selection bias: 512 FFN experts of width
    2048 and 256 zero-compute identity experts, top-12 over all of them,
    the chosen probabilities NOT normalised, times 6; no shared expert;
    RMSNorm eps 1e-5, untied head.  The audio and vision towers and the
    audio decoder are not part of the model this preset builds."""
    base = dict(
        num_experts=512, zero_experts=256, expert_top_k=12,
        num_shared_experts=0, hidden_size=6144, intermediate_size=2048,
        dense_intermediate_size=12288, num_layers=56, vocab_size=131072,
        num_heads=64, attention_kind="mla", q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, mla_rank_scale=True, rope_theta=1e7, norm_eps=1e-5,
        router_score="softmax", router_bias=True, norm_topk_prob=False,
        routed_scaling_factor=6.0, sequence_len=4096, gated_ffn=True,
        hidden_act=Activation.SILU, drop_tokens=False, dtype=jnp.bfloat16,
    )
    base.update(overrides)
    base.setdefault("layer_ffns", ("dense+moe", "dense+join")
                    * (base["num_layers"] // 2))
    return MoEConfig(**base)


def sdar_30b_a3b(**overrides) -> MoEConfig:
    """SDAR-30B-A3B-Chat (huggingface.co/JetLM/SDAR-30B-A3B-Chat
    ``config.json``, ``model_type`` sdar_moe): 48 layers alike of width
    2048, grouped-query attention (32 heads over 4 K/V heads of width 128:
    the heads' 4096 columns are twice the hidden size; RMSNorm on every
    head of q and k before RoPE, theta 1e6), 128 experts top-8 of width 768
    behind a softmax router with renormalised weights, no shared expert,
    SwiGLU, an untied head over 151936 tokens, RMSNorm eps 1e-6.  The model
    GENERATES BY DIFFUSION OVER BLOCKS: ``block_length`` 4 (the family's
    released default for the -Chat checkpoints; the config states none)
    under block-causal attention, ``mask_token_id`` 151669 (the family's
    ``<|MASK|>``)."""
    base = dict(
        num_experts=128, expert_top_k=8, num_shared_experts=0,
        hidden_size=2048, intermediate_size=768, num_layers=48,
        moe_frequency=1, vocab_size=151936, num_heads=32, num_kv_heads=4,
        head_dim=128, qk_norm=True, norm_eps=1e-6, rope_theta=1e6,
        router_score="softmax", norm_topk_prob=True,
        routed_scaling_factor=1.0, sequence_len=4096, gated_ffn=True,
        hidden_act=Activation.SILU, drop_tokens=False, dtype=jnp.bfloat16,
        block_length=4, mask_token_id=151669,
    )
    base.update(overrides)
    if "mask_token_id" not in overrides:
        # a cut vocabulary keeps a [MASK] id inside it (any id serves:
        # the engine tracks masks by state and never reveals this one)
        base["mask_token_id"] = min(base["mask_token_id"],
                                    base["vocab_size"] - 1)
    return MoEConfig(**base)


def granite4_h_micro(**overrides) -> MoEConfig:
    """granite-4.0-h-micro (3.19B; huggingface.co/ibm-granite/
    granite-4.0-h-micro ``config.json``, ``model_type`` granitemoehybrid):
    40 layers of width 2048, each a mixer AND a dense SwiGLU part of width
    8192 (``shared_intermediate_size``; ``num_local_experts`` 0: no
    mixture anywhere), every part joining the stream times
    ``residual_multiplier`` 0.22.  ``layer_types``: attention at 5, 15, 25
    and 35 (32 query heads over 8 K/V heads of width 64, NO positional
    embedding, scores times ``attention_multiplier`` 1/64), a state-space
    mixer elsewhere (Mamba-2: 64 heads of 64 over a float32 state of 128 a
    channel, ONE group, a 4-tap convolution with a bias, chunks of 256).
    The embedding's rows enter times ``embedding_multiplier`` 12, the
    logits leave divided by ``logits_scaling`` 8 through a head TIED to
    the embedding; RMSNorm eps 1e-5, vocabulary 100352."""
    base = dict(
        num_experts=1, expert_top_k=1, hidden_size=2048,
        intermediate_size=8192, num_layers=40, vocab_size=100352,
        num_heads=32, num_kv_heads=8, head_dim=64, use_rope=False,
        ssm_heads=64, ssm_head_dim=64, ssm_groups=1, ssm_state=128,
        ssm_conv=4, ssm_chunk=256, norm_eps=1e-5,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.015625, logits_scaling=8.0,
        tie_embeddings=True, sequence_len=4096, gated_ffn=True,
        hidden_act=Activation.SILU, drop_tokens=False, dtype=jnp.bfloat16,
    )
    base.update(overrides)
    # the published layer_types: attention at 5, 15, 25, 35
    base.setdefault("layer_mixers", tuple(
        "mha" if li % 10 == 5 else "ssm"
        for li in range(base["num_layers"])))
    base.setdefault("layer_ffns", ("dense",) * base["num_layers"])
    return MoEConfig(**base)


def trinity_large_preview(**overrides) -> MoEConfig:
    """Trinity-Large-Preview (400B-A13B; huggingface.co/arcee-ai/
    Trinity-Large-Preview ``config.json``, ``model_type`` afmoe): 60 layers
    of width 3072, 48 query heads over 8 K/V heads of 128 with an RMSNorm on
    every head of q and k and a sigmoid gate ``u Wg`` on the heads' outputs;
    ``layer_types``: three WINDOW layers (``sliding_window`` 4096 keys,
    RoPE theta 1e4) to one full layer with NO rotation
    (``global_attn_every_n_layers`` 4: full where ``(i + 1) % 4 == 0``);
    four norms a layer (each part's input AND output); 6 leading dense
    SwiGLU layers of width 12288, then 256 experts top-4 of width 3072 + 1
    shared behind a sigmoid router with a selection bias, normalised
    weights (``route_norm``) times ``route_scale`` 2.448, one group; the
    embedding's rows enter times sqrt(3072) (``mup_enabled``); RMSNorm eps
    1e-5, an untied head over 200192 tokens."""
    base = dict(
        num_experts=256, expert_top_k=4, num_shared_experts=1,
        hidden_size=3072, intermediate_size=3072, num_layers=60,
        moe_frequency=1, first_k_dense=6, dense_intermediate_size=12288,
        vocab_size=200192, num_heads=48, num_kv_heads=8, head_dim=128,
        qk_norm=True, attn_window=4096, attn_gate=True, part_out_norm=True,
        norm_eps=1e-5, rope_theta=1e4, embedding_multiplier=3072 ** 0.5,
        router_score="sigmoid", router_bias=True, norm_topk_prob=True,
        routed_scaling_factor=2.448, sequence_len=4096, gated_ffn=True,
        hidden_act=Activation.SILU, drop_tokens=False, dtype=jnp.bfloat16,
    )
    base.update(overrides)
    if "first_k_dense" not in overrides:    # a cut under six layers
        base["first_k_dense"] = min(6, base["num_layers"])
    # the published layer_types: full attention at 3, 7, ..., 59
    base.setdefault("layer_mixers", tuple(
        "mha" if (li + 1) % 4 == 0 else "swa"
        for li in range(base["num_layers"])))
    return MoEConfig(**base)


PRESETS = {
    "mixtral-8x7b": mixtral_8x7b,
    "deepseek-moe-16b": deepseek_moe_16b,
    "switch-base": switch_base,
    "flashmoe-reference": flashmoe_reference,
    "joyai-llm-flash": joyai_llm_flash,
    "ling-3.0-flash": ling3_flash,
    "lfm2-24b-a2b": lfm2_24b_a2b,
    "nemotron-3-nano-30b-a3b": nemotron3_nano_30b_a3b,
    "longcat-flash": longcat_flash,
    "sdar-30b-a3b-chat": sdar_30b_a3b,
    "granite-4.0-h-micro": granite4_h_micro,
    "trinity-large-preview": trinity_large_preview,
}
