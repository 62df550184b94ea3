"""NVIDIA-Nemotron-3-Nano-30B-A3B's block against the plain reference
(``benchmark/lib/reference_nemotron3.py``), at tiny sizes on the CPU,
float32, seeded random weights, the odd sizes kept: an expert width that is
no multiple of 128 (192), 16 query heads to the one K/V head, more
state-space heads (4) than groups (2).  The state-space mixer in its two
forms against the reference's token-by-token recurrence; layers that are a
mixer or a feed-forward part alone, ONE norm each; the by-slot state under
the serving engine beside K/V pages (whole and chunked prefill, decode,
slot reuse, idle rows: ``tests/test_nemotron3_engine.py``; pads here);
ungated relu^2 experts through the grouped kernel at the odd width; the
two shares of the experts.

Tolerances.  As ``tests/test_ling3.py``: the program and the reference
compute the same float32 products in different orders (the chunked form's
masked products against the recurrence's one step a token); ``TIGHT``
(2e-5 of the compared values' scale) has a factor of ten over the largest
reading seen (2.4e-6 on logits, 1.1e-7 on the state), and anything left out
of the mathematics (a tap, the convolution's bias, the skip D, the gate,
the group norm, a norm of a layer, the selection bias) moves a logit by
1e-2 or more.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import Activation, MoEConfig
from flashmoe_tpu.models.generate import generate
from flashmoe_tpu.models.presets import PRESETS
from flashmoe_tpu.models.reference import init_moe_params
from flashmoe_tpu.models.transformer import forward, init_params
from flashmoe_tpu.ops import moe, ssm
from flashmoe_tpu.ops.gate import RouterOutput

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIGHT = 2e-5


def _load(path, name):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


ref = _load(os.path.join(ROOT, "benchmark", "lib", "reference_nemotron3.py"),
            "benchlib_reference_nemotron3")

# the cut's pattern in small: every kind of layer, a mixer beside a mixer
# (``M*``) and the published ``MEM`` runs
PATTERN = "MEM*EM"
TINY = dict(pattern=PATTERN, hidden_size=64, intermediate_size=192,
            num_experts=8, expert_top_k=2, vocab_size=256, num_heads=16,
            num_kv_heads=1, head_dim=8, ssm_heads=4, ssm_head_dim=8,
            ssm_groups=2, ssm_state=16, ssm_chunk=8, dtype=jnp.float32,
            param_dtype=jnp.float32)
CFG = PRESETS["nemotron-3-nano-30b-a3b"](**TINY)
FILE = {  # the same sizes under the published key names
    "hidden_size": 64, "num_hidden_layers": 6,
    "hybrid_override_pattern": PATTERN, "num_attention_heads": 16,
    "num_key_value_heads": 1, "head_dim": 8, "mamba_num_heads": 4,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "use_conv_bias": True, "vocab_size": 256,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 192, "moe_shared_expert_intermediate_size": 384,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "n_group": 1, "layer_norm_epsilon": 1e-5,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "mlp_hidden_act": "relu2",
    "served": {"param_dtype": "float32", "expert_width_stored": 256}}
DIMS = ref.model_dims(FILE)
WIDTH = CFG.ssm_conv_width                       # 4 * 8 + 2 * 2 * 16 = 96
SERVE = dict(max_batch=3, page_size=8, num_pages=40, max_pages_per_slot=12,
             ctx_bucket_pages=3, prompt_bucket=8)
TOKENS = np.random.default_rng(5).integers(1, 256, 200)


@pytest.fixture(scope="module")
def params():
    """The reference's weights (its tree layout IS the program's), norms
    and the skip moved off one so that one left out shows."""
    p = ref.make_params(1234567891011, DIMS)
    key = jax.random.PRNGKey(3)
    for li, layer in enumerate(p["layers"]):
        for j, name in enumerate(("attn_norm", "ffn_norm", "ssm_norm",
                                  "ssm_D")):
            if name in layer:
                k = jax.random.fold_in(key, 10 * li + j)
                layer[name] = 1.0 + 0.1 * jax.random.normal(
                    k, layer[name].shape, jnp.float32)
    return p


def _close(got, want, tol=TIGHT):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * max(np.abs(want).max(), 1e-30)


def _x(t, seed=1, b=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, t, 64),
                             jnp.float32)


# ------------------------------------------------ (a) the layers' description

def test_a_layer_is_one_thing_and_the_tree_says_so(params):
    """``cfg.layers`` is what everything reads: a mixer-only layer has
    ``attn_norm`` and no ``moe``, a mixture-only layer ``ffn_norm`` and no
    mixer; the reference's tree IS the program's."""
    mine = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), mine)
            == jax.tree.map(lambda a: (a.shape, a.dtype), params))
    assert CFG.layers == (("ssm", None), (None, "moe"), ("ssm", None),
                          ("mha", None), (None, "moe"), ("ssm", None))
    assert CFG.mixers == ("ssm", None, "ssm", "mha", None, "ssm")
    assert CFG.moe_layer_indices == (1, 4) and CFG.cache_layers == (3,)
    assert CFG.state_layers == (0, 2, 5)
    for layer, (mixer, ffn) in zip(params["layers"], CFG.layers):
        assert ("attn_norm" in layer) == (mixer is not None)
        assert ("ffn_norm" in layer) == ("moe" in layer) == (ffn is not None)
    assert CFG.slot_state == (("state", (4, 8, 16), jnp.float32),
                              ("conv", (3 * WIDTH,), jnp.float32))
    assert CFG.state_slot_bytes == 3 * (4 * 8 * 16 + 3 * WIDTH) * 4
    assert CFG.kv_token_bytes == 1 * 2 * 1 * 8 * 4      # one '*' layer
    # counts: a part that is absent is not counted
    both = MoEConfig(num_layers=6, hidden_size=64, intermediate_size=192)
    assert both.param_count - CFG.replace(vocab_size=32000).param_count \
        == 2 * 4 * 64 * 64 + 4 * 2 * 64 * 192
    # the published pattern, whole
    full = PRESETS["nemotron-3-nano-30b-a3b"]()
    kinds = [m or f for m, f in full.layers]
    assert (kinds.count("ssm"), kinds.count("moe"), kinds.count("mha")) \
        == (23, 23, 6) and full.num_layers == 52
    assert full.slot_state == (("state", (64, 64, 128), jnp.float32),
                               ("conv", (3 * 6144,), jnp.bfloat16))
    assert full.state_slot_bytes == 23 * (2_097_152 + 36_864)
    assert full.kv_token_bytes == 6 * 1024 and not full.use_rope
    assert full.kv_pool_rows == (2, 2, 128)
    assert (full.hidden_act, full.gated_ffn) == (Activation.RELU2, False)
    # the experts stored at whole lanes: 1856 + 64 = 15 x 128
    assert (full.intermediate_size, full.intermediate_pad) == (1856, 64)
    assert CFG.intermediate_pad == 64 and params["layers"][1]["moe"][
        "w_up"].shape == (8, 64, 256)


def test_earlier_configs_describe_their_layers_as_before():
    """With no ``layer_ffns`` every layer is a mixer AND a feed-forward
    part, mixture or dense by ``moe_frequency`` / ``first_k_dense``."""
    cfg = PRESETS["lfm2-24b-a2b"]()
    assert all(m in ("conv", "mha") for m in cfg.mixers)
    assert [f for _, f in cfg.layers] == ["dense"] * 2 + ["moe"] * 38
    assert cfg.moe_layer_indices == tuple(range(2, 40))
    switch = MoEConfig(num_layers=4, moe_frequency=2)
    assert switch.layers == (("mha", "dense"), ("mha", "moe")) * 2
    assert MoEConfig(num_experts=1, expert_top_k=1).moe_layer_indices == ()


@pytest.mark.parametrize("bad", [
    dict(layer_ffns=("moe",)),                              # not every layer
    dict(layer_ffns=(None,) * 6),               # layer 1 is nothing at all
    dict(layer_ffns=("moe", "swiglu", None, None, "moe", None)),
    dict(layer_mixers=("ssm", None, "kda", "mha", None, "ssm")),  # two kinds
    dict(ssm_heads=3), dict(ssm_state=0), dict(ssm_conv=1),
    dict(intermediate_pad=32), dict(intermediate_pad=64, tp=2),
    dict(attention_kind="mla", kv_lora_rank=8, qk_nope_head_dim=8,
         qk_rope_head_dim=8, v_head_dim=8, num_kv_heads=0, head_dim=0,
         layer_mixers=("ssm", None, "ssm", "mla", None, "ssm")),  # no rope
])
def test_config_validates_the_new_keys(bad):
    with pytest.raises(ValueError):
        CFG.replace(**bad)


def test_training_through_the_mixer_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="'ssm'"):
        CFG.replace(is_training=True)


# ------------------------------- (b) the mixer's two forms and the recurrence

@pytest.mark.parametrize("t", [1, 5, 8, 37])
def test_a_span_equals_its_steps_and_the_reference(params, t):
    """One token, a ragged chunk, one whole chunk and four and a bit: the
    chunked form, the step token by token over a slot's state, and the
    reference's recurrence give the same output and the same state."""
    layer, x = params["layers"][0], _x(t, b=2)
    out, _, _, (s1, c1) = ssm.ssm_attention(layer, x, CFG, None, None, 0)
    state = jnp.zeros((1, 2, 4, 8, 16), jnp.float32)
    conv = jnp.zeros((1, 2, 3 * WIDTH), jnp.float32)
    steps = []
    for i in range(t):
        o, state, conv, _ = ssm.ssm_attention(layer, x[:, i:i + 1], CFG,
                                              state, conv, 0)
        steps.append(o)
    _close(jnp.concatenate(steps, axis=1), out)
    _close(state[0], s1)
    _close(conv[0], c1)
    for b in range(2):
        want, s_ref = ref.ssm(layer, x[b], DIMS)
        _close(out[b], want)
        _close(s1[b], s_ref)


def test_the_skip_the_bias_and_the_gated_norm_show_when_left_out(params):
    layer, x = params["layers"][0], _x(20)
    want = ref.ssm(layer, x[0], DIMS)[0]
    for name, other in (("ssm_D", jnp.zeros((4,))),
                        ("ssm_conv_b", jnp.zeros((WIDTH,))),
                        ("ssm_norm", jnp.ones((32,)))):
        out = ssm.ssm_attention(dict(layer, **{name: other}), x, CFG, None,
                                None, 0)[0]
        assert np.abs(np.asarray(out[0] - want)).max() > 1e-2 * np.abs(
            np.asarray(want)).max()


def test_a_chunk_edge_carries_the_state(params):
    """A prompt in three spans (13 + 16 + 8: ragged, two chunks, one)
    carries state and inputs to the whole prompt's output and state."""
    layer, x = params["layers"][2], _x(37, seed=4)
    whole, _, _, (s_w, c_w) = ssm.ssm_attention(layer, x, CFG, None, None, 0)
    state = jnp.ones((3, 2, 4, 8, 16), jnp.float32)     # stale: slot 1
    conv = jnp.ones((3, 2, 3 * WIDTH), jnp.float32)
    slots, outs, at = jnp.asarray([1]), [], 0
    for n in (13, 16, 8):
        o, state, conv, _ = ssm.ssm_attention(
            layer, x[:, at:at + n], CFG, state, conv, 1, slots=slots,
            fresh=jnp.asarray(at == 0))
        outs.append(o)
        at += n
    _close(jnp.concatenate(outs, axis=1), whole)
    _close(state[1, 1], s_w[0])
    _close(conv[1, 1], c_w[0])
    # the other layers' and the other slot's arrays: to the bit
    assert (np.asarray(state[0]) == 1).all() and (
        np.asarray(state[1, 0]) == 1).all() and (
        np.asarray(conv[2]) == 1).all()


def test_pads_idle_rows_and_a_fresh_start(params):
    """Positions past a row's valid prefix leave state and inputs as the
    valid prefix left them (a step of dt 0: decay 1, input 0); a row with
    nothing valid keeps both TO THE BIT, in the span form and in the step;
    ``fresh`` rows start from zero whatever the slot held."""
    layer = params["layers"][0]
    x = _x(16, seed=6, b=2)
    valid = jnp.arange(16)[None, :] < jnp.asarray([[11], [0]])
    rng = np.random.default_rng(0)
    state = jnp.asarray(rng.normal(size=(1, 2, 4, 8, 16)), jnp.float32)
    conv = jnp.asarray(rng.normal(size=(1, 2, 3 * WIDTH)), jnp.float32)
    _, s1, c1, _ = ssm.ssm_attention(layer, x, CFG, state, conv, 0,
                                     valid=valid)
    _, s11, c11, _ = ssm.ssm_attention(layer, x[:1, :11], CFG, state[:, :1],
                                       conv[:, :1], 0)
    _close(s1[0, 0], s11[0, 0])
    _close(c1[0, 0], c11[0, 0])
    np.testing.assert_array_equal(np.asarray(s1[0, 1]),
                                  np.asarray(state[0, 1]))
    np.testing.assert_array_equal(np.asarray(c1[0, 1]),
                                  np.asarray(conv[0, 1]))
    # the step: row 0 decodes, row 1 is idle
    live = jnp.asarray([[True], [False]])
    _, s2, c2, _ = ssm.ssm_attention(layer, x[:, :1], CFG, state, conv, 0,
                                     valid=live)
    assert np.abs(np.asarray(s2[0, 0] - state[0, 0])).max() > 0
    np.testing.assert_array_equal(np.asarray(s2[0, 1]),
                                  np.asarray(state[0, 1]))
    np.testing.assert_array_equal(np.asarray(c2[0, 1]),
                                  np.asarray(conv[0, 1]))
    # a reset: the stale state is not read
    out_f, s3, _, _ = ssm.ssm_attention(layer, x[:1], CFG, state[:, :1],
                                        conv[:, :1], 0,
                                        fresh=jnp.asarray(True))
    out_0, _, _, (s0, _) = ssm.ssm_attention(layer, x[:1], CFG, None, None,
                                             0)
    np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_0))
    np.testing.assert_array_equal(np.asarray(s3[0]), np.asarray(s0))


def test_the_step_kernel_is_the_plain_step_in_place():
    """``fm_ssm_step`` in interpret mode over ONE layer of a state array
    at a lane-wide state size (heads > groups, an idle row of dt 0): the
    plain step's output and state, the idle row and the other layers to
    the bit; and the rule that picks the arm."""
    rng = np.random.default_rng(0)
    layers, b, n, p, g, ns = 3, 5, 8, 16, 2, 128
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    state = f32(layers, b, n, p, ns)
    xs, bm, cm = f32(b, n, p), f32(b, g, ns), f32(b, g, ns)
    dt = jnp.asarray(rng.uniform(0, 0.2, (b, n)), jnp.float32).at[2].set(0)
    a = -jnp.asarray(rng.uniform(1, 16, (n,)), jnp.float32)
    y0, s0 = ssm.ssm_step(xs, bm, cm, dt, a, jnp.zeros((n,)), state[1])
    y1, got = ssm.ssm_step_pallas(state, 1, xs, bm, cm, dt, a,
                                  interpret=True)
    _close(y1, y0)
    _close(got[1], s0)
    for same in ((got[0], state[0]), (got[2], state[2]),
                 (got[1, 2], state[1, 2])):
        np.testing.assert_array_equal(*map(np.asarray, same))
    # the arm: a TPU, row b IS slot b, whole lanes and sublane tiles
    assert ssm.ssm_step_arm(b, None, state) == "xla"           # the CPU
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        assert ssm.ssm_step_arm(b, None, state) == "step_kernel"
        assert ssm.ssm_step_arm(b, jnp.arange(b), state) == "xla"
        assert ssm.ssm_step_arm(b - 1, None, state) == "xla"
        assert ssm.ssm_step_arm(b, None, None) == "xla"
        assert ssm.ssm_step_arm(b, None, state[..., :16]) == "xla"
        assert ssm.ssm_step_arm(
            256, None, jax.ShapeDtypeStruct((6, 256, 64, 64, 128),
                                            jnp.float32)) == "step_kernel"


# ------------------------------------------------------- (c) the experts

def _route(cfg, s, seed=0):
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.choice(cfg.num_experts, cfg.expert_top_k,
                               replace=False) for _ in range(s)])
    counts = np.bincount(idx.reshape(-1), minlength=cfg.num_experts)
    zero = jnp.zeros((), jnp.float32)
    return RouterOutput(
        jnp.asarray(rng.uniform(0.1, 1.0, idx.shape), jnp.float32),
        jnp.asarray(idx, jnp.int32), jnp.asarray(counts, jnp.int32),
        jnp.zeros((cfg.num_experts,), jnp.float32), zero, zero)


@pytest.mark.parametrize("held", [0, 4], ids=["all", "a_share"])
def test_the_grouped_kernel_takes_relu2_at_a_width_of_no_whole_lanes(
        monkeypatch, held):
    """Ungated relu^2 experts of width 192 (1.5 lanes; the cell's 1856 is
    14.5) STORED with 64 zero columns to whole lanes, through
    ``fm_ffn_fwd`` in interpret mode against the ``ragged_dot`` form over
    the weights as PUBLISHED (the zeros cut off): the padding changes
    nothing; with all experts and with a share of them."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=192, intermediate_pad=64,
                    drop_tokens=False,
                    hidden_act=Activation.RELU2, gated_ffn=False,
                    dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                    experts_held=held, expert_first=2 if held else 0)
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    assert p["w_up"].shape[1:] == (128, 256) and p["w_down"].shape[1:] == (
        256, 128) and p["b_up"].shape[1:] == (256,)
    assert not np.asarray(p["w_up"][:, :, 192:], np.float32).any()
    assert not np.asarray(p["w_down"][:, 192:], np.float32).any()
    bare = dict(p, w_up=p["w_up"][:, :, :192], b_up=p["b_up"][:, :192],
                w_down=p["w_down"][:, :192])
    r = _route(cfg, 40)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 128), jnp.bfloat16)
    assert moe.routed_rows_form(cfg) == "routed_rows"      # the CPU's
    want = jax.jit(lambda x: moe.routed_rows_ffn(
        bare, x, r, cfg.replace(intermediate_pad=0)))(x)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.routed_rows_form(cfg) == "routed_kernel"
    assert moe.expert_arm(cfg, 40) == "routed_kernel"
    assert moe.routed_rows_form(
        cfg.replace(intermediate_pad=0)) == "routed_rows"
    monkeypatch.undo()
    monkeypatch.setattr(moe, "routed_rows_form", lambda c: "routed_kernel")
    got = jax.jit(lambda x: moe.routed_rows_ffn(p, x, r, cfg))(x)
    scale = float(np.abs(np.asarray(want)).max())
    assert scale > 0 and np.abs(np.asarray(got - want)).max() <= 2e-2 * scale


def test_relu2_is_the_square_of_relu_in_every_plain_form(params):
    """The dense arm, the routed rows and the shared expert against the
    reference's mixture layer."""
    layer = params["layers"][1]
    x = _x(24, seed=8)[0]
    want = jax.jit(lambda p, x: ref.ffn(p, x, DIMS))(layer["moe"], x)
    for routed in (False, True):
        out = moe.moe_layer(layer["moe"], x, CFG, use_pallas=False,
                            routed_rows=routed)
        _close(out.out, want)
        assert int(out.expert_counts.sum()) == 24 * 2
    none = ref.ffn(layer["moe"], x, DIMS, shared=False)
    assert np.abs(np.asarray(want - none)).max() > 1e-2


def test_the_two_shares_add_up_to_the_uncut_layer(params):
    """THE SHARE TEST.  Two chips hold experts 0-3 and 4-7, each routes
    over all eight and computes its own experts' rows; the shared expert
    is computed by both alike.  The two partial results, the shared expert
    counted once, are the uncut layer's result, and each share is what the
    reference gives for the same share."""
    whole = params["layers"][4]["moe"]
    x = _x(48, seed=22)[0]
    routed = jax.jit(lambda p, x, cfg: moe.moe_layer(
        p, x, cfg, use_pallas=False, routed_rows=True),
        static_argnames="cfg")
    full = routed(whole, x, CFG).out
    _close(full, ref.ffn(whole, x, DIMS))
    parts = []
    for chip in range(2):
        cfg = CFG.replace(expert_first=4 * chip, experts_held=4)
        mine = {k: (v[4 * chip:4 * chip + 4]
                    if k in ("w_up", "b_up", "w_down", "b_down") else v)
                for k, v in whole.items()}              # stored padded
        out = routed(mine, x, cfg)
        parts.append(out.out)
        _close(out.out, jax.jit(lambda p, x, first=4 * chip: ref.ffn(
            p, x, dict(DIMS, experts=4, expert_first=first)))(mine, x))
        assert int(out.expert_counts.sum()) == 48 * 2   # routed over all
    once = ref._relu2(x, whole["shared_w_up"], whole["shared_w_down"], None)
    _close(sum(parts) - once, full)


# ------------------------- (d) generate and forward against the reference
#     (the engine against the reference: tests/test_nemotron3_engine.py)


def test_generate_and_forward_equal_the_reference(params):
    """The no-cache forward and ``generate``'s dense cache run the same
    one-part layers."""
    toks = jnp.asarray(TOKENS[None, :29], jnp.int32)
    logits, _ = jax.jit(lambda p, t: forward(p, t, CFG))(params, toks)
    _close(logits[0], ref.forward_logits(params, DIMS, toks[0],
                                         jnp.arange(29)))
    out = np.asarray(generate(params, toks[:, :12], CFG, max_new_tokens=8))
    want = ref.forward_logits(params, DIMS, jnp.asarray(out[0, :19]),
                              jnp.arange(11, 19))
    assert list(out[0, 12:]) == [int(t) for t in np.asarray(want).argmax(-1)]


def test_attention_without_positions_is_the_references(params):
    """No rotary embedding: with RoPE on, the same weights give other
    logits; 16 query heads read the one K/V head."""
    toks = jnp.asarray(TOKENS[None, :23], jnp.int32)
    want = ref.forward_logits(params, DIMS, toks[0], jnp.arange(23))
    roped, _ = forward(params, toks, CFG.replace(use_rope=True))
    assert np.abs(np.asarray(roped[0] - want)).max() > 1e-2
