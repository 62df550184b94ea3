"""Static configuration system for flashmoe-tpu.

The reference (osayamenja/FlashMoE) bakes its model/job parameters in at
*compile time*: ``csrc/flashmoe_config.json`` is converted to ``-D`` macros by
``setup.py:226-292`` / ``CMakeLists.txt:114-159`` and consumed into the
``ACC`` constexpr struct (``csrc/include/flashmoe/types.cuh:441-512``), which
derives ~40 compile-time constants (token count ``S``, expert capacity ``EC``,
padded capacity ``pEC``, tile counts, gate reduction mode, combine mode, ...).

On TPU we get the same "compile-time specialization" for free from JAX
tracing: a frozen, hashable dataclass passed as a static argument (or closed
over) specializes every ``jit``/Pallas compilation to the exact shapes, with
no rebuild step.  This module is therefore the TPU-native equivalent of the
whole JSON -> macro -> ``ACC`` pipeline, including the schema constraints of
``csrc/flashmoe_config.schema.json:34-63`` (divisibility requirements) and
the derived-quantity formulas of ``types.cuh:497-499``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Any

import jax.numpy as jnp

# TPU-native tile geometry.  The MXU is a 128x128 systolic array and the VPU
# operates on (8, 128) vregs; 128 is the universal lane width.  The reference
# uses BLOCK_M=128 / BLOCK_N=64 CUDA tiles (types.cuh); on TPU the natural
# block is 128x128.
BLOCK_M = 128
BLOCK_N = 128
LANE = 128

#: the mixers that keep a constant-size state a SLOT and cache nothing a
#: token (``MoEConfig.slot_state`` says what each keeps)
STATE_MIXERS = ("kda", "conv", "ssm")

#: the "mha" kind with a WINDOW: a layer of it is an "mha" layer whose
#: queries see the last ``MoEConfig.attn_window`` keys and whose K/V rows
#: live in a page pool of their own, from which the pages behind the window
#: go back as a slot advances (``MoEConfig.window_layers``)
WINDOW_MIXER = "swa"

#: what a layer's feed-forward name (``MoEConfig.layer_ffns``) says:
#: ``(the part whose output joins the residual stream where it is
#: computed, the mixture branch)``; the branch is "moe" (a mixture reads
#: the part's normed input here and its output is carried along), "join"
#: (the carried output joins after this part's) or None
FFN_PARTS = {None: (None, None), "moe": ("moe", None),
             "dense": ("dense", None), "dense+moe": ("dense", "moe"),
             "dense+join": ("dense", "join")}


class Activation:
    """Activation selector, mirroring ``hidden_act`` (0=relu / 1=gelu) in
    ``csrc/flashmoe_config.json`` with TPU-relevant extensions."""

    RELU = "relu"
    GELU = "gelu"
    SILU = "silu"  # used by Mixtral/DeepSeek family (gated FFN)
    RELU2 = "relu2"  # relu(x)^2: ungated experts of two matrices


_DTYPE_MAP = {
    # reference torch_dtype codes: 0=f32 / 1=tf32 / 2=bf16 / 3=fp16
    # (csrc/flashmoe_config.schema.json).  tf32 has no TPU equivalent; the
    # closest MXU mode is bf16 inputs with f32 accumulation, which is what
    # "bf16" here means.  fp16 is not TPU-native; we map it to bf16.
    0: jnp.float32,
    1: jnp.bfloat16,
    2: jnp.bfloat16,
    3: jnp.bfloat16,
    "float32": jnp.float32,
    "f32": jnp.float32,
    "tf32": jnp.bfloat16,
    "bfloat16": jnp.bfloat16,
    "bf16": jnp.bfloat16,
    "float16": jnp.bfloat16,
    "fp16": jnp.bfloat16,
}


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Frozen model/job configuration.

    Field names follow ``csrc/flashmoe_config.json:1-17`` where a counterpart
    exists; everything derived mirrors ``ACC`` (``types.cuh:441-512``).
    Instances are hashable and therefore usable as ``jit`` static arguments.
    """

    # --- core MoE shape (reference names) ---
    num_experts: int = 8
    expert_top_k: int = 2
    hidden_size: int = 1024
    intermediate_size: int = 4096
    sequence_len: int = 128
    mini_batch: int = 1
    global_batch: int = 1
    capacity_factor: float = 1.25
    drop_tokens: bool = True
    is_training: bool = False
    hidden_act: str = Activation.GELU

    # --- full-model shape ---
    num_layers: int = 2
    moe_frequency: int = 1  # every Nth layer is MoE
    vocab_size: int = 32000

    # --- extensions beyond the reference (needed for a full framework) ---
    num_shared_experts: int = 0  # DeepSeekMoE-style always-on experts
    num_heads: int = 8
    num_kv_heads: int = 0  # 0 => = num_heads (MHA); <num_heads => GQA
    head_dim: int = 0  # 0 => hidden_size // num_heads
    gated_ffn: bool = False  # SwiGLU-style expert FFN (Mixtral/DeepSeek)
    router_jitter: float = 0.0
    aux_loss_coef: float = 0.01
    router_z_loss_coef: float = 0.0
    rope_theta: float = 10000.0

    # --- architecture keys a published config.json states and the block
    # above cannot (DeepSeek-V3 family: JoyAI-LLM-Flash).  Every default
    # is the value all earlier presets run at, so their traced graphs do
    # not change.
    # attention: "mha" (q/k/v projections, GQA by num_kv_heads) or "mla"
    # (multi-head latent attention: queries through a q_lora_rank
    # bottleneck, keys and values decompressed from one kv_lora_rank
    # latent a token, a qk_rope_head_dim rotary key shared by all heads;
    # RoPE rotates ADJACENT pairs).  The five sizes are MLA's and must be
    # 0 under "mha".
    attention_kind: str = "mha"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # router: scores are softmax(logits) or sigmoid(logits); with
    # router_bias the layer holds a per-expert `gate_bias` that is added
    # to the scores for the top-k SELECTION only (the combine weights are
    # the scores themselves: `e_score_correction_bias`, "noaux_tc");
    # norm_topk_prob divides the chosen weights by their sum;
    # routed_scaling_factor multiplies them afterwards.
    router_score: str = "softmax"
    router_bias: bool = False
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # the first first_k_dense layers carry a dense FFN of width
    # dense_intermediate_size (0: intermediate_size) whatever
    # moe_frequency says
    first_k_dense: int = 0
    dense_intermediate_size: int = 0
    # group-limited routing (``n_group`` > 1): the experts lie in n_group
    # equal groups, a group scores the sum of its two best selection
    # scores, the topk_group best groups are kept and the top-k is taken
    # inside them.  One group is the plain rule, bit for bit.
    n_group: int = 1
    topk_group: int = 1
    # the token mixer PER LAYER: "mha", "mla", "kda" (Kimi delta
    # attention: the gated delta rule with a per-channel decay over a
    # [kda_head_dim, kda_head_dim] state a head, behind a causal depthwise
    # convolution of kda_conv taps) or "conv" (a gated short convolution:
    # a causal depthwise convolution of conv_taps taps over a gated
    # projection of the input, between two gates).  The last two keep a
    # constant-size state a slot: no positions, nothing cached a token.
    # "ssm" is a selective state-space mixer in its scalar-decay form
    # (Mamba-2: ssm_heads heads of width ssm_head_dim over a float32
    # state [ssm_head_dim, ssm_state] a head, the input and output maps B
    # and C shared by the heads of one of ssm_groups groups, behind a
    # causal depthwise convolution of ssm_conv taps with a bias; its span
    # form works in chunks of ssm_chunk tokens), a state a slot as well.
    # "swa" is an "mha" layer with a WINDOW: a query at position p sees
    # the keys ``p - attn_window < j <= p`` (``attn_window`` keys, itself
    # among them).  In a model that has such layers THEY rotate q and k
    # (``use_rope``) and its "mha" layers, which see every key, do not
    # (``ops/attention.kv_project``: the one place of the rule).
    # ``layer_mixers`` names every layer; empty, every layer is
    # ``attention_kind``.  An entry None is a layer with NO mixer:
    # ``x + ffn(norm(x))`` alone.
    layer_mixers: tuple = ()
    attn_window: int = 0
    # the feed-forward part PER LAYER: "moe", "dense" or None (a layer
    # that is its mixer alone, ``x + mixer(norm(x))``, with ONE norm).
    # Empty: what moe_frequency and first_k_dense say, every layer one.
    # Two more spell a mixture whose output joins the residual stream
    # LATER than it is read (a shortcut-connected mixture): "dense+moe" is
    # a dense FFN whose output joins now AND a mixture over the same
    # normed input whose output is carried along (the layer holds both:
    # ``moe`` the dense part, ``branch`` the mixture); "dense+join" is a
    # dense FFN after whose output the carried one joins:
    # ``x + dense(norm(x)) + carried``.  The layers between the two do not
    # see the mixture's output.  One branch is open at a time and every
    # one joins (:data:`FFN_PARTS` splits the names).
    layer_ffns: tuple = ()
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_lower_bound: float = -5.0  # log of the smallest decay a step
    conv_taps: int = 3
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # an "mha" layer norms every head of q and of k (RMSNorm over the
    # head's width, weights ``q_norm`` / ``k_norm``) before RoPE
    qk_norm: bool = False
    # an "mha" layer rotates q and k by their positions (RoPE); False: it
    # applies none, and positions reach it through the causal mask alone
    use_rope: bool = True
    # an "mha" / "swa" layer multiplies its heads' outputs, before the
    # output product, by ``sigmoid(u Wg)`` elementwise (``wg``: H ->
    # heads x head_dim, over the part's normed input u)
    attn_gate: bool = False
    # every part norms its OUTPUT before it joins the stream, with weights
    # of its own (``attn_out_norm`` / ``ffn_out_norm``): four norms a layer,
    # ``x + norm(part(norm(x)))``
    part_out_norm: bool = False
    # the eps of every RMSNorm of the model (block, final, q/k)
    norm_eps: float = 1e-6
    # the share of a mixture layer's experts THIS chip holds, of a
    # deployment that divides every layer over several: experts
    # expert_first .. expert_first + experts_held - 1 (0 held: all).  The
    # router keeps its num_experts outputs; the layer computes the rows
    # routed to its own experts and leaves the others' part out.
    expert_first: int = 0
    experts_held: int = 0
    # zero-compute experts: the router of a mixture layer is num_experts +
    # zero_experts wide (``gate_w``, ``gate_bias`` and the counts too) and
    # a chosen output e >= num_experts is the IDENTITY: it adds its weight
    # times the layer's input and runs no FFN (the expert weights stay
    # num_experts; expert_top_k may be up to the router's width).  Nobody
    # holds them: a chip with a share of the experts (experts_held counts
    # FFN experts only) computes them for every one of its own tokens.
    zero_experts: int = 0
    # an "mla" layer multiplies its normed query latent by
    # sqrt(hidden_size / q_lora_rank) and its normed key/value latent
    # (not the shared rotary key) by sqrt(hidden_size / kv_lora_rank):
    # ranks that are a fraction of the width keep the scale of the width
    mla_rank_scale: bool = False
    # columns of ZEROS the routed experts' matrices are STORED with beyond
    # intermediate_size (w_up / w_gate / b_up columns, w_down rows; 0:
    # none).  Every activation here maps 0 to 0, so the layer's result is
    # the published one; what it buys is a stored width of whole lanes
    # (1856 + 64 = 15 x 128): the chip keeps an array whose last dimension
    # is no whole lanes in ANOTHER dimension order, and copies it into
    # row-major order before every kernel that takes it (PERF.md section
    # 6, PR 39).  Serving only: a gradient would fill the zeros.
    intermediate_pad: int = 0
    # generation by diffusion over blocks: the model generates
    # ``block_length`` positions together (0: a token at a time, as every
    # other model here).  Attention is then BLOCK-causal in prefill and in
    # generation alike (a position sees every earlier block and its OWN
    # block in both directions: ``attn_block``), a block starts as the
    # ``mask_token_id`` token's embedding wherever the prompt's tail does
    # not open it, and is revealed over denoising forwards, then committed
    # (``models/generate.generate_blocks``; the serving engine's
    # ``_paged_denoise_step`` commits it beside the next block's first
    # forward).  A power of two; every layer a K/V layer.
    block_length: int = 0
    mask_token_id: int = 0
    # scalar factors a published config states (the granitemoehybrid
    # family's): the embedding's rows times ``embedding_multiplier`` as
    # they enter the stream; every part joins it as ``x + residual_multiplier
    # * part(norm(x))``; an "mha" layer's scores are ``q . k *
    # attention_multiplier`` (None: ``head_dim ** -0.5``); the logits are
    # divided by ``logits_scaling``.  ``tie_embeddings``: the head
    # contracts over the embedding's own ``[V, H]`` array and the parameter
    # tree has no ``lm_head``.  Each default writes NOTHING into a program
    # (a Python branch where the factor would be applied, not a multiply
    # by one).  The serving paths of one chip apply them (:attr:`rescaled`).
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None
    logits_scaling: float = 1.0
    tie_embeddings: bool = False

    # --- numerics ---
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    accum_dtype: Any = jnp.float32

    # --- parallelism (mesh axis sizes; 1 = off) ---
    dp: int = 1  # data parallel
    ep: int = 1  # expert parallel
    tp: int = 1  # tensor parallel
    sp: int = 1  # sequence/context parallel
    pp: int = 1  # pipeline parallel

    # distributed MoE transport when ep > 1: "collective" (XLA all-to-all,
    # the robust default), "fused" (in-kernel RDMA, the FlashDMoE path),
    # "ragged" (dropless ragged all-to-all), or "auto" — the analytical
    # planner (flashmoe_tpu/planner/) picks per (config, mesh,
    # generation): predicted-latency winner, measured-winner when
    # tuning-table / bench measurements cover the shape
    moe_backend: str = "collective"

    # Wire-dtype compression of the EP all-to-all payload
    # (flashmoe_tpu/ops/wire.py): tokens are quantized immediately
    # before each exchange and dequantized immediately after, so only
    # the wire sees the narrow dtype — every compute stage stays at
    # `dtype`.  `wire_dtype` covers the dispatch leg (tokens -> expert
    # owners), `wire_dtype_combine` the return leg (expert outputs back
    # to token owners — independent because it carries gate-weighted
    # results that often want to stay high-precision).  Values: "bf16"
    # (plain cast), "e4m3"/"e5m2" (per-token-row scaled fp8, f32 scales
    # ride as a sidecar).  Default None: OFF, the hot path is
    # bit-identical to a compression-free build (the collect_stats /
    # degrade_unhealthy_experts convention; asserted by
    # tests/test_wire.py).  XLA transports only — the fused RDMA kernel
    # moves raw slabs, so `moe_backend='fused'` rejects these knobs.
    wire_dtype: str | None = None
    wire_dtype_combine: str | None = None

    # Per-hop wire dtype for the CROSS-SLICE (DCN) stage of the
    # two-stage hierarchical all-to-all (parallel/ep.py
    # _hierarchical_a2a): when the ep axis spans DCN-connected slices,
    # the exchange decomposes into an intra-slice ICI hop and one
    # aggregated DCN message per slice pair — and the DCN hop, priced
    # ~5x slower per byte than ICI (topology._DCN_SPEC), can carry a
    # narrower wire than the in-slice hop.  Set (e.g. "e4m3") the DCN
    # stage of BOTH legs re-encodes at this dtype while the ICI stage
    # stays at the leg's own wire (`wire_dtype` / `wire_dtype_combine`,
    # raw when those are off).  Default None: INHERIT the leg wire —
    # the whole exchange encodes once and the traced graph is exactly
    # the single-dtype build (bit-identical; proven by the staticcheck
    # invariant engine).  Inert on flat (single-slice) exchanges — there
    # is no DCN hop to re-encode.  XLA transports only, like the other
    # wire knobs (the fused RDMA kernel moves raw slabs).
    wire_dtype_dcn: str | None = None

    # Wire dtype for the serving fabric's KV-page handoff
    # (flashmoe_tpu/fabric/handoff.py): when prefill and decode run in
    # separate pools, a finished prompt's KV run crosses DCN as whole
    # pages — this knob compresses that payload with the same per-row
    # codec as the a2a wires, one scale per (layer, page) block riding
    # a `_qscale` sidecar.  HOST-SIDE only: the codec runs between the
    # prefill jit and the decode-side page store, so no traced graph
    # changes and no collective moves (census-proven; registered in
    # staticcheck/registry.py with changes_graph=False).  Default None:
    # OFF, handed-off pages are the prefill jit's own arrays untouched
    # — a fabric drill is bit-equal to the single-pool engine
    # (tests/test_fabric.py's acceptance drill).
    kv_wire_dtype: str | None = None

    # Chunked double-buffered EP dispatch (Comet-style compute–
    # communication overlap, arXiv 2502.19811): split the [E, C, H]
    # exchange slab along the local-expert axis into this many chunks
    # and software-pipeline the XLA transports so chunk k's expert FFN
    # overlaps chunk k+1's all-to-all, on the dispatch AND combine legs
    # (parallel/ep.py / parallel/ragged_ep.py; priced by the planner,
    # which also picks the best count under moe_backend='auto').
    # Composes with the wire codec: each chunk encodes/decodes inside
    # the pipeline.  Must divide num_experts // ep (validated here; the
    # shard body re-validates against the actual mesh).  Default None:
    # OFF, the serial schedule — bit-identical to a pre-chunking build
    # (the collect_stats / wire_dtype convention, asserted by
    # tests/test_chunked.py).  The fused RDMA kernel ignores the knob:
    # its transport already overlaps in-kernel per-slab (docs/PERF.md).
    a2a_chunks: int | None = None

    # In-graph MoE observability (flashmoe_tpu/ops/stats.py): when True,
    # every MoE layer additionally returns a MoEStats tuple (per-expert
    # load histogram, dropped-token fraction, capacity utilization,
    # imbalance factor, router entropy, top-k confidence) on
    # MoEOutput.stats, and the transformer/trainer thread them into step
    # metrics and the flight recorder.  Default False: the hot path is
    # bit-identical to a stats-free build and the EP layers add no extra
    # collectives (asserted by tests/test_observe.py).
    collect_stats: bool = False

    # Tier-0 fault tolerance (flashmoe_tpu/ops/health.py): when True,
    # every MoE layer checks its per-expert FFN outputs for non-finite
    # values *inside the compiled graph*, zeroes a sick expert's
    # contribution, and renormalizes each token's surviving gate weights
    # (jnp.where only — jit/vmap-safe, no collectives).  A dead or
    # NaN-poisoned expert then degrades quality for its tokens instead of
    # poisoning the whole step.  Masked expert/assignment counts land in
    # MoEStats (masked_experts / masked_fraction) when collect_stats is
    # also set, so the flight recorder sees degradation.  Default False:
    # the hot path is bit-identical to a pre-fault-tolerance build
    # (asserted by tests/test_chaos.py).
    degrade_unhealthy_experts: bool = False

    # Phase-level profiling (flashmoe_tpu/profiler/): when True, the
    # MoE layer bodies fence each phase (gate, dispatch, a2a legs,
    # expert FFN, combine) with block_until_ready so a host-armed
    # PhaseTimeline measures real per-phase wall time on EAGER
    # executions — the xprof-free phase timeline the cost ledger joins.
    # Host-side only: fences block on concrete values and no-op on
    # tracers, so the traced graph is byte-identical with the knob on
    # or off (registered as a graph-neutral knob in the staticcheck
    # registry and proven by the invariant engine).  Default False:
    # the bodies contain no fence calls at all.
    profile_phases: bool = False

    # Static hot-expert replica routing map, written by the self-healing
    # runtime controller (flashmoe_tpu/runtime/controller.py) when it
    # re-places experts under sustained load skew: each (hot, slot) pair
    # splits the traffic of expert ``hot`` between its own slot and the
    # replica ``slot`` (whose FFN weights the controller overwrites with
    # a copy of ``hot``'s — the victim slot must be a ~dead expert, so
    # evicting it costs nothing).  Applied in-graph AFTER top-k
    # (ops/gate.py): tokens routed to ``hot`` alternate between the two
    # physical slots by token parity, so each token is processed by
    # exactly one value-identical replica and the combine merges
    # contributions unchanged — the hot expert's load (and its capacity
    # drops) split in half.  Default (): OFF, bit-identical to a
    # replica-free build (the collect_stats / wire_dtype convention;
    # registered in staticcheck/registry.py, proven by the invariant
    # engine).
    expert_replicas: tuple = ()

    # Serving-phase selector consumed by the analytical planner when
    # ``moe_backend='auto'`` (flashmoe_tpu/planner/select.py and the
    # serving engine, flashmoe_tpu/serving/): None prices the layer at
    # the training shape (B x S tokens per step — the default every
    # training job uses); "decode" prices it at DECODE token counts
    # (per-step tokens = the decode batch, each fanning out top_k
    # exchange rows — a different regime where per-message alphas
    # dominate and the training-shaped a2a schedules are simply wrong,
    # RaMP arXiv 2604.26039); "prefill" prices the full-sequence
    # inference forward (training shape, inference-mode feasibility).
    # Pure selector: the traced graph is identical for every value —
    # only WHICH path 'auto' resolves to changes (registered in
    # staticcheck/registry.py SELECTOR_FIELDS).
    serving_mode: str | None = None

    # Forced FFN schedule of the fused RDMA kernel
    # (parallel/fused.py:_fused_schedule): None = auto (the IO-aware
    # resolution — arrival-batched when the hidden slab fits VMEM,
    # per-source resident when its byte trade wins, row-windowed
    # ('rowwin') when it beats per-row-tile streaming, 'stream'
    # otherwise); or one of 'batched' / 'resident' / 'stream' /
    # 'rowwin' to pin the schedule.  A forced schedule still faces the
    # hard VMEM feasibility gate — the kernel raises a clear ValueError
    # rather than launching an infeasible geometry, and the planner
    # marks the matching fused[<schedule>] row infeasible with the
    # reason.  Pure selector: every value computes the same function
    # (bit-identity across schedules asserted by tests/test_fused.py);
    # only execution geometry changes (registered in
    # staticcheck/registry.py SELECTOR_FIELDS).
    fused_schedule: str | None = None

    # Quantized expert weight storage & compute (flashmoe_tpu/quant/):
    # "int8" or "e4m3" stores the MoE FFN expert weights (w_up /
    # w_gate / w_down) at 1 byte per element with per-output-channel
    # f32 scales, dequantized IN COMPUTE — every matmul still
    # accumulates f32, biases/router stay full-precision.  With
    # pre-quantized params (quant.quantize_state) the weights stream
    # from HBM and live in memory at the narrow width (the planner
    # prices exactly this: analysis.path_costs weight terms, the fused
    # rowwin K-window geometry at 1 B/elem); with ordinary params the
    # layers fake-quant in-graph (round-trip) — same numerics, no
    # storage savings.  Default None: OFF, no quant code runs and the
    # graph is bit-identical to a pre-quant build (the collect_stats /
    # wire_dtype convention; registered in staticcheck/registry.py,
    # proven by the invariant engine).  Inference-only: post-training
    # quantization has no gradient story (jnp.round kills them), so
    # is_training=True rejects the knob — train at full precision and
    # quantize the checkpoint.
    expert_quant: str | None = None

    # Inference-only: fuse the dispatch gather into the FFN kernel
    # (ops/expert.py:grouped_ffn_tokens — no [E, C, H] HBM buffer).
    # None = auto: follow the FLASHMOE_GATHER_FUSED env var, else stay on
    # the explicit-dispatch path, which is hardware-validated.  The gather
    # kernel is opt-in until a measurement on the chip shows it winning
    # (round-2 advisor finding; VERDICT r2 "do this" #2).
    gather_fused: bool | None = None

    def __post_init__(self):
        if self.num_experts < 1:
            raise ValueError("num_experts must be >= 1")
        if self.zero_experts < 0:
            raise ValueError("zero_experts must be >= 0")
        if not (1 <= self.expert_top_k <= self.router_width):
            raise ValueError("expert_top_k must be in [1, num_experts + "
                             "zero_experts]")
        # schema.json:34-63: hidden/intermediate multipleOf 64, seq multipleOf 128.
        if self.hidden_size % 64:
            raise ValueError("hidden_size must be a multiple of 64")
        if self.intermediate_size % 64:
            raise ValueError("intermediate_size must be a multiple of 64")
        if self.num_experts > 1 and self.num_experts % self.ep:
            raise ValueError("num_experts must divide evenly over ep")
        if self.capacity_factor <= 0:
            raise ValueError("capacity_factor must be > 0")
        mla_sizes = (self.q_lora_rank, self.kv_lora_rank,
                     self.qk_nope_head_dim, self.qk_rope_head_dim,
                     self.v_head_dim)
        if self.attention_kind == "mla":
            # q_lora_rank 0: the published null, queries projected direct
            if (min(mla_sizes[1:]) < 1 or self.q_lora_rank < 0
                    or self.qk_rope_head_dim % 2):
                raise ValueError(
                    "attention_kind='mla' needs kv_lora_rank, "
                    "qk_nope_head_dim, qk_rope_head_dim (even) and "
                    "v_head_dim >= 1 and q_lora_rank >= 0, got "
                    f"{mla_sizes}")
            if self.num_kv_heads not in (0, self.num_heads) or self.head_dim:
                raise ValueError(
                    "attention_kind='mla' has no kv-head grouping and no "
                    "single head_dim: leave num_kv_heads and head_dim 0")
            if self.kv_wire_dtype is not None:
                raise NotImplementedError(
                    "kv_wire_dtype with attention_kind='mla': the page "
                    "codec (fabric/handoff.py) packs (layer, page) blocks "
                    "of a K/V pair; a codec for latent rows is missing")
        elif self.attention_kind == "mha":
            if any(mla_sizes):
                raise ValueError(
                    f"MLA sizes {mla_sizes} need attention_kind='mla'")
        else:
            raise ValueError(f"attention_kind {self.attention_kind!r} not "
                             f"in ('mha', 'mla')")
        for name in ("layer_mixers", "layer_ffns"):
            named = tuple(getattr(self, name))
            object.__setattr__(self, name, named)
            if named and len(named) != self.num_layers:
                raise ValueError(f"{name} names {len(named)} layers of "
                                 f"{self.num_layers}")
        if set(self.layer_ffns) - set(FFN_PARTS):
            raise ValueError(f"layer_ffns {self.layer_ffns} not of "
                             f"{tuple(FFN_PARTS)}")
        if self.moe_layer_indices and self.num_experts < 2:
            raise ValueError("a 'moe' layer needs num_experts >= 2")
        carried = None
        for li, (_, ffn) in enumerate(self.layers):
            _, branch = FFN_PARTS[ffn]
            if branch == "moe" and carried is not None:
                raise ValueError(
                    f"layer {li} reads a mixture branch while layer "
                    f"{carried}'s has not joined: one is open at a time")
            if branch == "join" and carried is None:
                raise ValueError(
                    f"layer {li} joins a mixture branch and none is open "
                    f"(a branch joins once, after the layer that read it)")
            carried = {"moe": li, "join": None}.get(branch, carried)
        if carried is not None:
            raise ValueError(
                f"the mixture branch layer {carried} reads never joins: a "
                f"later layer's feed-forward part must be 'dense+join'")
        if (None, None) in self.layers:
            raise ValueError(
                f"layer {self.layers.index((None, None))} has neither a "
                f"mixer nor a feed-forward part")
        if set(self.mixers) - {"mha", "mla", WINDOW_MIXER, *STATE_MIXERS,
                               None}:
            raise ValueError(f"layer_mixers {self.mixers} not of "
                             f"('mha', 'mla', '{WINDOW_MIXER}', None) + "
                             f"{STATE_MIXERS}")
        if set(self.mixers) - {*STATE_MIXERS, self.attention_kind, None,
                               *((WINDOW_MIXER,)
                                 if self.attention_kind == "mha" else ())}:
            raise ValueError(
                f"layer_mixers {self.mixers}: the layers that cache rows "
                f"are all attention_kind={self.attention_kind!r} (an 'mha' "
                f"model's may be '{WINDOW_MIXER}')")
        if bool(self.window_layers) != (self.attn_window > 0):
            raise ValueError(
                f"attn_window={self.attn_window} with "
                f"{len(self.window_layers)} '{WINDOW_MIXER}' layers: the "
                f"window is theirs, and they need one >= 1")
        if (self.attn_gate or self.part_out_norm) and (
                self.attention_kind != "mha"):
            raise ValueError(
                "attn_gate gates an 'mha' layer's heads, and part_out_norm "
                "is held to the reference through such a model alone: an "
                "'mla' model has neither")
        if self.part_out_norm and any(
                FFN_PARTS[ffn][1] for _, ffn in self.layers):
            raise NotImplementedError(
                "part_out_norm with a mixture branch that joins a layer "
                "later: which norm the carried output takes is not stated "
                "by any configuration here")
        if self.windowed and (self.is_training or self.block_length or max(
                self.dp, self.ep, self.tp, self.sp, self.pp) > 1):
            raise NotImplementedError(
                f"'{WINDOW_MIXER}' layers / attn_gate / part_out_norm under "
                f"is_training, block_length > 0 or a mesh axis > 1: "
                f"training's attention (ops/attention.flash_attention and "
                f"its backward kernels, parallel/ringattn.py) has no "
                f"window, the mesh's parameter specs (parallel/mesh.py, "
                f"parallel/pipeline.py) name neither ``wg`` nor the output "
                f"norms, and the block mask's kernels know no window; a "
                f"serving config of one chip runs them")
        if len(set(self.mixers) & set(STATE_MIXERS)) > 1:
            raise ValueError(
                f"layer_mixers {self.mixers}: the layers that keep a "
                f"state a slot are all of one kind of {STATE_MIXERS}")
        if "conv" in self.mixers and self.conv_taps < 2:
            raise ValueError(
                f"a 'conv' layer needs conv_taps >= 2, got {self.conv_taps}")
        if self.qk_norm and self.attention_kind != "mha":
            raise ValueError(
                "qk_norm norms the heads of an 'mha' layer's q and k: an "
                "'mla' layer norms its latent")
        if self.block_length:
            bl = self.block_length
            if bl < 2 or bl & (bl - 1):
                raise ValueError(
                    f"block_length={bl} must be 0 (a token at a time) or a "
                    f"power of two >= 2 (a query sees up to "
                    f"``pos | (block_length - 1)``)")
            other = sorted({m for m in self.mixers if m is not None}
                           - {"mha"})
            if other:
                raise NotImplementedError(
                    f"block_length={bl} with {other} layers: generation by "
                    f"blocks rewrites a block's cached rows in place, "
                    f"forward after forward; a state a slot that can be "
                    f"taken back, or a block-causal latent arm, is missing")
            if not 0 <= self.mask_token_id < self.vocab_size:
                raise ValueError(
                    f"mask_token_id={self.mask_token_id} lies outside the "
                    f"vocabulary of {self.vocab_size}")
        if not self.use_rope and self.attention_kind != "mha":
            raise ValueError(
                "use_rope=False is an 'mha' layer's: an 'mla' layer's "
                "shared key IS its rotary part")
        if "ssm" in self.mixers:
            sizes = (self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
                     self.ssm_state, self.ssm_chunk)
            if (min(sizes) < 1 or self.ssm_conv < 2
                    or self.ssm_heads % self.ssm_groups):
                raise ValueError(
                    "an 'ssm' layer needs ssm_heads, ssm_head_dim, "
                    "ssm_state, ssm_chunk >= 1, ssm_groups dividing "
                    f"ssm_heads and ssm_conv >= 2, got {sizes} and "
                    f"{self.ssm_conv}")
            if self.is_training:
                raise NotImplementedError(
                    "training through an 'ssm' layer: the chunked form's "
                    "gradient is not held against the recurrence's by any "
                    "test; a serving config (is_training=False) runs it")
        if "kda" in self.mixers:
            # the chunkwise form takes exp(16 x |bound|) in float32
            if (self.kda_heads < 1 or self.kda_head_dim < 1
                    or self.kda_conv < 2
                    or not -5.5 <= self.kda_lower_bound < 0):
                raise ValueError(
                    "a 'kda' layer needs kda_heads, kda_head_dim >= 1, "
                    "kda_conv >= 2 and kda_lower_bound in [-5.5, 0), got "
                    f"{(self.kda_heads, self.kda_head_dim, self.kda_conv, self.kda_lower_bound)}")
        if min(self.embedding_multiplier, self.residual_multiplier,
               self.logits_scaling) <= 0 or (
                self.attention_multiplier is not None
                and self.attention_multiplier <= 0):
            raise ValueError(
                "embedding_multiplier, residual_multiplier, logits_scaling "
                "and attention_multiplier (where given) must be > 0")
        if self.attention_multiplier is not None and (
                self.attention_kind != "mha"):
            raise ValueError(
                "attention_multiplier scales an 'mha' layer's scores: an "
                "'mla' layer's scale is its own head widths'")
        if self.rescaled and (self.is_training or max(
                self.dp, self.ep, self.tp, self.sp, self.pp) > 1):
            raise NotImplementedError(
                "embedding_multiplier / residual_multiplier / "
                "attention_multiplier / logits_scaling / tie_embeddings "
                "under is_training or a mesh axis > 1: the trainer, the "
                "pipeline stages and the mesh's parameter specs "
                "(runtime/trainer.py, parallel/pipeline.py, "
                "parallel/mesh.py) name an ``lm_head`` leaf and apply no "
                "factor; a serving config of one chip runs them")
        if self.n_group < 1 or self.num_experts % self.n_group or not (
                1 <= self.topk_group <= self.n_group):
            raise ValueError(
                f"n_group={self.n_group} must divide num_experts="
                f"{self.num_experts} and topk_group={self.topk_group} "
                f"lie in [1, n_group]")
        if self.n_group > 1 and (
                self.expert_top_k
                > self.topk_group * (self.num_experts // self.n_group)):
            raise ValueError("expert_top_k exceeds the experts of "
                             "topk_group groups")
        if not (0 <= self.experts_held and 0 <= self.expert_first
                and self.expert_first + self.experts_held
                <= self.num_experts):
            raise ValueError(
                f"experts {self.expert_first}..+{self.experts_held} are "
                f"not among num_experts={self.num_experts}")
        if self.intermediate_pad < 0 or self.intermediate_pad % 64:
            raise ValueError("intermediate_pad must be a multiple of 64 "
                             ">= 0")
        if self.intermediate_pad and (self.is_training or self.tp > 1):
            raise ValueError(
                "intermediate_pad stores zero columns that only a forward "
                "pass keeps zero and a tensor-parallel split would count "
                "as the model's: is_training=False and tp=1 only")
        if self.experts_held and self.ep > 1:
            raise ValueError("experts_held is one chip's share of a "
                             "layer: it does not compose with ep > 1")
        if self.zero_experts:
            if self.ep > 1:
                raise NotImplementedError(
                    "zero_experts with ep > 1: the expert-parallel layers "
                    "(parallel/ep.py, ragged_ep.py, fused.py) route over "
                    "num_experts outputs and exchange every routed row")
            if self.is_training:
                raise NotImplementedError(
                    "training through zero-compute experts: no test holds "
                    "the identity term's gradient; a serving config "
                    "(is_training=False) runs it")
            if (self.drop_tokens or self.n_group > 1 or self.expert_replicas
                    or self.degrade_unhealthy_experts or self.collect_stats):
                raise ValueError(
                    "zero_experts are computed over the routed rows of a "
                    "dropless config (drop_tokens=False) with one routing "
                    "group: the capacity arm, group-limited routing, "
                    "expert_replicas, degrade_unhealthy_experts and "
                    "collect_stats index num_experts outputs")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score {self.router_score!r} not in "
                             f"('softmax', 'sigmoid')")
        if self.routed_scaling_factor <= 0:
            raise ValueError("routed_scaling_factor must be > 0")
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError("first_k_dense must be in [0, num_layers]")
        if self.dense_intermediate_size % 64:
            raise ValueError(
                "dense_intermediate_size must be a multiple of 64")
        if self.moe_backend not in ("collective", "fused", "ragged",
                                    "auto"):
            raise ValueError(
                f"moe_backend {self.moe_backend!r} not in "
                f"('collective', 'fused', 'ragged', 'auto')"
            )
        if self.fused_schedule not in (None, "batched", "resident",
                                       "stream", "rowwin"):
            raise ValueError(
                f"fused_schedule {self.fused_schedule!r} not in "
                f"(None, 'batched', 'resident', 'stream', 'rowwin')"
            )
        # reject combinations the specialized transports cannot serve
        # rather than silently falling back to the collective path
        if self.moe_backend in ("fused", "ragged") and self.tp > 1:
            raise ValueError(
                f"moe_backend={self.moe_backend!r} does not compose with "
                f"tp>1; use moe_backend='collective'"
            )
        if self.moe_backend == "ragged" and self.num_shared_experts:
            raise ValueError(
                "moe_backend='ragged' does not support shared experts; "
                "use 'collective' or 'fused'"
            )
        # wire-dtype knobs: reject unsupported combinations at config
        # time (unknown name, fp8 on a jax build without float8, wire
        # wider than the compute dtype, fused backend) instead of
        # failing inside shard_map
        from flashmoe_tpu.ops import wire as _wire

        for knob, val in (("wire_dtype", self.wire_dtype),
                          ("wire_dtype_combine", self.wire_dtype_combine),
                          ("wire_dtype_dcn", self.wire_dtype_dcn),
                          ("kv_wire_dtype", self.kv_wire_dtype)):
            if val is None:
                continue
            wd = _wire.resolve(val)  # ValueError on unknown/unsupported
            if jnp.dtype(wd).itemsize > jnp.dtype(self.dtype).itemsize:
                raise ValueError(
                    f"{knob}={val!r} ({jnp.dtype(wd).itemsize} B) is wider "
                    f"than the compute dtype "
                    f"{jnp.dtype(self.dtype).name} "
                    f"({jnp.dtype(self.dtype).itemsize} B); a wire must "
                    f"compress, not inflate")
        # quantized expert storage: reject unsupported combinations at
        # config time (unknown name, e4m3 without float8 support,
        # training jobs, tensor-parallel experts) instead of failing
        # inside a layer trace
        if self.expert_quant is not None:
            from flashmoe_tpu.quant import core as _qcore

            _qcore.resolve(self.expert_quant)  # ValueError on unknown
            if self.is_training:
                raise ValueError(
                    "expert_quant is post-training (inference-only): "
                    "jnp.round has no useful gradient, so a quantized "
                    "training step would silently learn nothing — "
                    "train at full precision and quantize_state() the "
                    "checkpoint")
            if self.tp > 1:
                raise ValueError(
                    "expert_quant does not compose with tp>1 (the "
                    "Megatron intermediate split would shard w_up's "
                    "per-output-channel scales); use tp=1")
        # chunked a2a pipeline: reject impossible chunk counts at config
        # time (clear ValueError) instead of a shape error inside the
        # pipeline loop; the shard body re-checks against the actual
        # mesh width, which may differ from cfg.ep
        if self.a2a_chunks is not None:
            n = self.a2a_chunks
            if not isinstance(n, int) or n < 1:
                raise ValueError(
                    f"a2a_chunks={n!r} must be a positive int (or None "
                    f"for the serial schedule)")
            nlx = self.num_experts // max(self.ep, 1)
            if n > 1 and (nlx == 0 or nlx % n):
                raise ValueError(
                    f"a2a_chunks={n} must divide the local-expert axis "
                    f"(num_experts // ep = {nlx}); pick a divisor or "
                    f"leave a2a_chunks=None for the serial schedule")
        # replica routing map: reject malformed maps at config time so
        # the in-graph remap (ops/gate.py) only ever sees valid static
        # (hot, slot) pairs
        if self.expert_replicas:
            if not isinstance(self.expert_replicas, tuple):
                raise ValueError(
                    f"expert_replicas must be a tuple of (hot, slot) "
                    f"pairs, got {type(self.expert_replicas).__name__}")
            seen_slots: set = set()
            hots = set()
            for pair in self.expert_replicas:
                if (not isinstance(pair, tuple) or len(pair) != 2
                        or not all(isinstance(v, int) for v in pair)):
                    raise ValueError(
                        f"expert_replicas entries must be (hot, slot) "
                        f"int pairs, got {pair!r}")
                hot, slot = pair
                if hot == slot:
                    raise ValueError(
                        f"expert_replicas pair {pair} replicates an "
                        f"expert onto its own slot")
                for v in pair:
                    if not 0 <= v < self.num_experts:
                        raise ValueError(
                            f"expert_replicas id {v} out of range "
                            f"[0, {self.num_experts})")
                if slot in seen_slots:
                    raise ValueError(
                        f"expert_replicas slot {slot} used as a replica "
                        f"target twice")
                if hot in hots:
                    # the in-graph split is a token-parity half/half
                    # between ONE (hot, slot) pair; a second replica of
                    # the same expert would receive zero traffic — its
                    # evicted slot wasted silently
                    raise ValueError(
                        f"expert_replicas replicates expert {hot} "
                        f"twice; the parity split supports exactly one "
                        f"replica per hot expert")
                seen_slots.add(slot)
                hots.add(hot)
            if hots & seen_slots:
                raise ValueError(
                    f"expert_replicas chains a replica "
                    f"({sorted(hots & seen_slots)} appear as both hot "
                    f"expert and replica slot)")
        if self.serving_mode not in (None, "prefill", "decode"):
            raise ValueError(
                f"serving_mode {self.serving_mode!r} not in "
                f"(None, 'prefill', 'decode')")
        if ((self.wire_dtype or self.wire_dtype_combine
                or self.wire_dtype_dcn)
                and self.moe_backend == "fused"):
            raise ValueError(
                "wire-dtype compression rides the XLA transports; "
                "moe_backend='fused' RDMAs raw slabs in-kernel — use "
                "'collective', 'ragged', or 'auto'"
            )

    # ------------------------------------------------------------------
    # Derived quantities (ACC equivalents, types.cuh:441-512)
    # ------------------------------------------------------------------

    @property
    def tokens(self) -> int:
        """S = sequence_len * mini_batch (types.cuh:470)."""
        return self.sequence_len * self.mini_batch

    @property
    def padded_num_experts(self) -> int:
        """PX: experts padded to the lane width (types.cuh ``PX``)."""
        return _round_up(self.num_experts, LANE)

    def capacity_for(self, tokens: int) -> int:
        """EC (types.cuh:497-499): CF * TK * ceil(tokens/E) when dropping,
        else all tokens.  The floor of 8 keeps the capacity buffer aligned to
        the TPU sublane count.  Used for both the global token count and the
        EP layer's per-shard capacity."""
        if not self.drop_tokens:
            return tokens
        return max(
            8,
            int(
                math.ceil(
                    self.capacity_factor
                    * self.expert_top_k
                    * math.ceil(tokens / self.num_experts)
                )
            ),
        )

    @property
    def expert_capacity(self) -> int:
        """EC over the full (unsharded) token count."""
        return self.capacity_for(self.tokens)

    @property
    def padded_expert_capacity(self) -> int:
        """pEC: EC padded to the block size (types.cuh ``pEC``)."""
        return _round_up(self.expert_capacity, 8)

    @property
    def num_local_experts(self) -> int:
        """nLx under the (uniform) EP sharding."""
        return max(1, self.num_experts // self.ep)

    @property
    def resolved_num_kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def rescaled(self) -> bool:
        """Whether a scalar factor or the tied head is set: what the
        training and mesh paths do not apply (``__post_init__`` refuses
        them by name)."""
        return (self.tie_embeddings or self.attention_multiplier is not None
                or (self.embedding_multiplier, self.residual_multiplier,
                    self.logits_scaling) != (1.0, 1.0, 1.0))

    @property
    def windowed(self) -> bool:
        """Whether the model has window layers, an output gate on its
        attention or norms on its parts' outputs: what the training and
        mesh paths do not build (``__post_init__`` refuses them by
        name)."""
        return bool(self.window_layers or self.attn_gate
                    or self.part_out_norm)

    @property
    def attn_block(self) -> int:
        """Positions an attention layer's mask treats as ONE: a query at
        ``pos`` sees keys up to ``pos | (attn_block - 1)``.  1 is causal
        attention; ``block_length`` for a model that generates by blocks."""
        return self.block_length or 1

    @functools.cached_property
    def layers(self) -> tuple:
        """THE description of every layer, ``(mixer, ffn)``: the token
        mixer ("mha", "mla" or one of ``STATE_MIXERS``) and the
        feed-forward part (a name of :data:`FFN_PARTS`), either of which
        may be None: a layer is ``x + mixer(norm(x))``, then
        ``x + ffn(norm(x))``, with one norm for each part it has; a
        feed-forward name may also say that a mixture reads the part's
        normed input and joins after a LATER layer's part (``layer_ffns``).
        Everything that asks what a layer is (the parameter tree, the
        layer loops, the cache's layout, the counts) reads this."""
        mixers = self.layer_mixers or (self.attention_kind,) * self.num_layers
        ffns = self.layer_ffns
        if not ffns:
            f = max(1, self.moe_frequency)
            ffns = tuple(
                "moe" if (self.num_experts > 1 and li >= self.first_k_dense
                          and (li + 1) % f == 0) else "dense"
                for li in range(self.num_layers))
        return tuple(zip(mixers, ffns))

    @property
    def router_width(self) -> int:
        """Outputs of a mixture layer's router: the FFN experts and the
        zero-compute ones behind them."""
        return self.num_experts + self.zero_experts

    @property
    def moe_layer_indices(self) -> tuple[int, ...]:
        """Which transformer layers carry an MoE FFN (vs dense or none),
        as their feed-forward part or as the branch read there."""
        return tuple(li for li, (_, ffn) in enumerate(self.layers)
                     if "moe" in FFN_PARTS[ffn])

    def ffn_config(self, li: int, branch: bool = False) -> "MoEConfig":
        """The config layer ``li``'s feed-forward part runs under (with
        ``branch``: the mixture branch read there): this one for a
        mixture, else one dense expert (no router, no shared experts) of
        the dense width."""
        if FFN_PARTS[self.layers[li][1]][branch] == "moe":
            return self
        return self.dense_config

    @functools.cached_property
    def dense_config(self) -> "MoEConfig":
        """The config a dense feed-forward part runs under: one expert of
        the dense width, no router, nothing of the mixture's."""
        return self.replace(
            num_experts=1, expert_top_k=1, num_shared_experts=0,
            n_group=1, topk_group=1, expert_first=0, experts_held=0,
            zero_experts=0, intermediate_pad=0, layer_ffns=(),
            intermediate_size=(self.dense_intermediate_size
                               or self.intermediate_size))

    @property
    def mixers(self) -> tuple:
        """The token mixer of every layer (None: the layer has none)."""
        return tuple(mixer for mixer, _ in self.layers)

    @property
    def cache_layers(self) -> tuple:
        """The layers that cache a row for EVERY token of a context (every
        layer whose mixer is neither of ``STATE_MIXERS`` nor the window
        kind): layer ``cache_layers[i]`` owns index i of the paged
        pools."""
        return tuple(li for li, m in enumerate(self.mixers)
                     if m is not None and m != WINDOW_MIXER
                     and m not in STATE_MIXERS)

    @property
    def window_layers(self) -> tuple:
        """The layers that cache the rows of the last ``attn_window``
        tokens alone (mixer "swa"): layer ``window_layers[i]`` owns index
        i of the WINDOW pools, whose page ids are a space of their own."""
        return tuple(li for li, m in enumerate(self.mixers)
                     if m == WINDOW_MIXER)

    @property
    def state_layers(self) -> tuple:
        """The layers that keep a constant-size state a slot (a mixer of
        ``STATE_MIXERS``): layer ``state_layers[i]`` owns index i of the
        per-slot arrays (:attr:`slot_state`)."""
        return tuple(li for li, m in enumerate(self.mixers)
                     if m in STATE_MIXERS)

    @property
    def slot_state(self) -> tuple:
        """What ONE state layer keeps of a slot, whatever its context:
        ``((name, shape, dtype), ...)``, the arrays the cache holds by
        slot in this order and the mixer takes and returns.  'kda': the
        float32 delta-rule ``state`` [heads, d, d] and ``conv``, the
        convolution's last kda_conv - 1 inputs of q, k and v side by
        side; 'conv': ``conv``, the convolution's last conv_taps - 1
        inputs (hidden_size each), in the activations' dtype; 'ssm': the
        float32 ``state`` [heads, head_dim, ssm_state] and ``conv``, the
        last ssm_conv - 1 inputs of its convolution (x, B and C side by
        side: :attr:`ssm_conv_width` each)."""
        kinds = set(self.mixers) & set(STATE_MIXERS)
        if kinds == {"ssm"}:
            return (("state", (self.ssm_heads, self.ssm_head_dim,
                               self.ssm_state), jnp.float32),
                    ("conv", ((self.ssm_conv - 1) * self.ssm_conv_width,),
                     self.dtype))
        if kinds == {"kda"}:
            n, d = self.kda_heads, self.kda_head_dim
            return (("state", (n, d, d), jnp.float32),
                    ("conv", ((self.kda_conv - 1) * 3 * n * d,),
                     self.dtype))
        if kinds == {"conv"}:
            return (("conv", ((self.conv_taps - 1) * self.hidden_size,),
                     self.dtype),)
        return ()

    @property
    def ssm_inner(self) -> int:
        """Channels an 'ssm' mixer works on: heads x head width."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels an 'ssm' mixer's convolution runs over: x beside the
        groups' B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def kv_token_elems(self) -> int:
        """Elements ONE caching layer holds for one token: K and V of
        every kv head, or MLA's latent beside its shared rotary key."""
        if self.attention_kind == "mla":
            return self.kv_lora_rank + self.qk_rope_head_dim
        return 2 * self.resolved_num_kv_heads * self.resolved_head_dim

    @property
    def kv_pool_rows(self) -> tuple[int, int, int]:
        """(pools, rows a pool keeps a token, elements of a row) of the
        paged cache as it is STORED: a K and a V pool of every kv head; or
        an MLA model's ONE pool of one latent row a token, padded to whole
        lanes (576 -> 640) so that a page ``[page, row]`` is whole tiles,
        contiguous, and a kernel's DMA can take it
        (``serving/kvcache.LatentPagedCache``).  K/V heads narrower than
        a lane tile lie ``LANE // head_dim`` to a row where they divide
        (8 heads of 64 are 4 rows of 128: heads 2j and 2j + 1 side by
        side), for the same reason: the chip pads a 64-wide minor
        dimension to 128 lanes, twice the pool and twice the bytes a
        step reads, and the decode kernel takes whole lanes
        (``ops/attention.pack_heads``)."""
        if self.attention_kind == "mla":
            return 1, 1, _round_up(self.kv_token_elems, LANE)
        nkv, dh = self.resolved_num_kv_heads, self.resolved_head_dim
        pack = LANE // dh if dh < LANE and LANE % dh == 0 else 1
        if nkv % pack:
            pack = 1
        return 2, nkv // pack, dh * pack

    @property
    def kv_row_elems(self) -> int:
        """Elements the pools store for one token of one caching layer:
        :attr:`kv_token_elems` with an MLA row's padding."""
        pools, heads, row = self.kv_pool_rows
        return pools * heads * row

    @property
    def kv_token_bytes(self) -> int:
        """Bytes one cached token holds over all the layers that cache
        (what the model defines; the pool's padding is not in it), window
        layers included: what a token costs while it is inside the
        window."""
        return ((len(self.cache_layers) + len(self.window_layers))
                * self.kv_token_elems * jnp.dtype(self.dtype).itemsize)

    @property
    def kv_pool_token_bytes(self) -> int:
        """Bytes of pool one cached token takes over all the layers that
        cache: :attr:`kv_token_bytes` with the rows as stored."""
        return ((len(self.cache_layers) + len(self.window_layers))
                * self.kv_row_elems * jnp.dtype(self.dtype).itemsize)

    @property
    def state_slot_bytes(self) -> int:
        """Bytes of state one slot holds over the state layers, whatever
        its context: the arrays of :attr:`slot_state`."""
        return len(self.state_layers) * sum(
            math.prod(shape) * jnp.dtype(dtype).itemsize
            for _, shape, dtype in self.slot_state)

    @property
    def param_count(self) -> int:
        """PC (types.cuh:491-492): Chinchilla-style dense parameter count used
        by the Decider's cost model for gradient-buffer sizing."""
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        mixers = sum(m is not None for m, _ in self.layers)
        ffns = sum((part is not None) + (branch == "moe")
                   for part, branch in (FFN_PARTS[f] for _, f in self.layers))
        return v * h + mixers * 4 * h * h + ffns * 2 * h * i + h * v

    # ------------------------------------------------------------------
    # IO
    # ------------------------------------------------------------------

    @classmethod
    def from_json(cls, path_or_dict) -> "MoEConfig":
        """Load from a reference-style ``flashmoe_config.json`` dict/file."""
        if isinstance(path_or_dict, (str,)):
            with open(path_or_dict) as f:
                raw = json.load(f)
        else:
            raw = dict(path_or_dict)
        act = raw.pop("hidden_act", 1)
        if isinstance(act, int):
            act = Activation.RELU if act == 0 else Activation.GELU
        dtype = _DTYPE_MAP[raw.pop("torch_dtype", 2)]
        known = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in raw.items() if k in known}
        for b in ("drop_tokens", "is_training"):
            if b in kwargs:
                kwargs[b] = bool(kwargs[b])
        return cls(hidden_act=act, dtype=dtype, **kwargs)

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        for k in ("dtype", "param_dtype", "accum_dtype"):
            d[k] = jnp.dtype(d[k]).name
        return json.dumps(d, indent=2)

    def replace(self, **kw) -> "MoEConfig":
        return dataclasses.replace(self, **kw)


# Benchmark configurations from BASELINE.json / BASELINE.md.
BENCH_CONFIGS = {
    # 1. correctness reference
    "tiny": MoEConfig(num_experts=8, expert_top_k=2, hidden_size=1024,
                      intermediate_size=4096, sequence_len=128),
    # 2. single-chip token-scaling bench (reference headline config uses
    #    E=64, H=2048, I=2048, S=8192; BASELINE.json asks d_model=4096, S=4096)
    "token_scaling": MoEConfig(num_experts=64, expert_top_k=2, hidden_size=4096,
                               intermediate_size=4096, sequence_len=4096,
                               capacity_factor=1.0),
    "reference": MoEConfig(num_experts=64, expert_top_k=2, hidden_size=2048,
                           intermediate_size=2048, sequence_len=8192,
                           capacity_factor=1.0),
    # 3. Mixtral-8x7B FFN dims, 8-chip EP
    "mixtral": MoEConfig(num_experts=8, expert_top_k=2, hidden_size=4096,
                         intermediate_size=14336, sequence_len=4096,
                         gated_ffn=True, hidden_act=Activation.SILU, ep=8),
    # 4. DeepSeekMoE-style
    "deepseek": MoEConfig(num_experts=64, expert_top_k=6, hidden_size=2048,
                          intermediate_size=1408, sequence_len=4096,
                          num_shared_experts=2, gated_ffn=True,
                          hidden_act=Activation.SILU, ep=8),
    # 5. 256-expert weak-scaling / payload-skew bench (BASELINE.json
    #    config #5, sized for v5p-256).  ep clamps to the devices actually
    #    present where it runs, so the same name runs
    #    single-chip for latency, on the virtual 8-device mesh for
    #    correctness (tests/test_presets.py), and at full scale when a
    #    v5p pod is reachable.  Per-rank tokens stay constant as ep grows
    #    — the weak-scaling axis of the reference's scaling_gpus_8 plot
    #    (/root/reference/README.md:46).
    "weak_scaling_256": MoEConfig(num_experts=256, expert_top_k=2,
                                  hidden_size=2048, intermediate_size=2048,
                                  sequence_len=8192, capacity_factor=1.0,
                                  ep=256),
}
