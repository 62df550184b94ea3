"""NVIDIA-Nemotron-3-Nano's block under the serving engine, against the
plain reference: the by-slot state beside K/V pages through whole and
chunked prefill, decode on either arm of the state step, slot reuse, idle
rows, the refusals and the records.  The sizes, the weights, the
tolerances and the ``params`` fixture are ``tests/test_nemotron3.py``'s
(the layer, the mixer and ``generate`` are held there); split from it by
ISSUE 47, the two together held a worker of the tier-1 gate for five
minutes.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.ops import ssm
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import Request, ServeConfig, ServingEngine
from flashmoe_tpu.serving.kvcache import HybridCache, slot_state_fields
from flashmoe_tpu.serving.speculate import SpecConfig
from flashmoe_tpu.utils.telemetry import SPAN_NAMES, FlightRecorder, Metrics

from test_nemotron3 import (  # noqa: F401 — ``params`` is the fixture
    CFG, DIMS, SERVE, TIGHT, TOKENS, WIDTH, _close, params, ref,
)


# ORDER matters in this file, as it did in the one file these cases came
# from: ``test_the_engine_serves..._on_the_step_kernel`` and
# ``test_records_and_names`` both LOWER ``eng._paged_decode_step`` on the
# same shapes, and ``jax.jit`` hands the second its first trace; the one
# that patches the arm comes first, and no other file lowers that function
# at this configuration (a worker keeps its traces from file to file).

def _serve_logits(monkeypatch, params, serve, requests, cfg=CFG, **kw):
    """Run requests and keep the logits the sampler was given at every
    step, by slot: ``rows[rid]`` row j is what output token j of the
    request was sampled from, the prefill's row first."""
    rows, sampler = {}, eng._sample_dynamic
    holder = {}

    def watching(logits, *knobs):
        got = np.asarray(logits)
        for i in holder["engine"]._decoding():
            rows.setdefault(holder["engine"].slots[i].orig.rid,
                            []).append(got[i])
        return sampler(logits, *knobs)

    monkeypatch.setattr(eng, "_sample_dynamic", watching)
    holder["engine"] = engine = ServingEngine(params, cfg, serve, **kw)
    out = engine.run(requests)
    return out, {r: np.stack(v) for r, v in rows.items()}, engine


def _reference_rows(params, out, t0, n):
    toks = jnp.asarray(out[:t0 + n - 1])
    return ref.forward_logits(params, DIMS, toks,
                              jnp.arange(t0 - 1, t0 + n - 1))


def test_the_engine_serves_the_same_logits_on_the_step_kernel(monkeypatch,
                                                              params):
    """The decode program with the kernel's arm forced (interpret mode, a
    state of 16 lanes): the logits of the reference's full forward, idle
    rows and rows between chunks left alone."""
    monkeypatch.setattr(
        ssm, "ssm_step_arm", lambda b, slots, state: (
            "step_kernel" if state is not None and slots is None
            and b == state.shape[1] else "xla"))
    serve = ServeConfig(**SERVE, prefill_chunk=16)
    lens = [(9, 5), (40, 7), (21, 4), (33, 6)]
    reqs = [Request(rid=r, prompt=tuple(int(t) for t in
                                        TOKENS[3 * r:3 * r + t0]),
                    max_new_tokens=n) for r, (t0, n) in enumerate(lens)]
    out, got, engine = _serve_logits(monkeypatch, params, serve, reqs)
    for r, (t0, n) in enumerate(lens):
        _close(got[r], _reference_rows(params, out[r], t0, n))
    text = eng._paged_decode_step.lower(
        params, CFG, engine.cache, jnp.zeros((3,), jnp.int32),
        jnp.zeros((3, 3), jnp.int32),
        jnp.zeros((3,), jnp.int32)).as_text(debug_info=True)
    assert "fm_ssm_step" in text


@pytest.mark.parametrize("chunk,t0", [(None, 21), (16, 21), (16, 70)])
def test_engine_logits_equal_the_references_full_forward(
        monkeypatch, params, chunk, t0):
    """Whole-prompt prefill, and chunked prefill with the state carried
    over two and over five chunks (the last one ragged), then 20 decode
    steps over the by-slot state and the K/V pages: the logits the sampler
    saw against the reference's full forward (no cache, the recurrence
    token by token), to ``TIGHT``: float32 both sides."""
    serve = ServeConfig(**SERVE, prefill_chunk=chunk)
    prompt = [int(t) for t in TOKENS[:t0]]
    mx = Metrics()
    out, got, engine = _serve_logits(
        monkeypatch, params, serve,
        [Request(rid=0, prompt=tuple(prompt), max_new_tokens=20)],
        metrics_obj=mx)
    assert isinstance(engine.cache, HybridCache)
    assert engine.cache._fields == ("k_pages", "v_pages", "state", "conv")
    assert slot_state_fields(engine.cache) == (False, False, True, True)
    assert engine.cache.state.shape == (3, 3, 4, 8, 16)
    assert engine.cache.k_pages.shape == (1, 40, 1, 8, 8)
    assert len(out[0]) == t0 + 20 and out[0][:t0] == prompt
    want = _reference_rows(params, out[0], t0, 20)
    _close(got[0], want)
    assert out[0][t0:] == [int(t) for t in np.asarray(want).argmax(-1)]
    carries = -(-t0 // chunk) - 1 if chunk else 0
    assert mx.counters.get("serve.chunk_carries", 0) == carries
    assert mx.counters["serve.state_resets"] == 1
    assert mx.gauges["serve.state_slot_bytes"] == CFG.state_slot_bytes


@pytest.mark.parametrize("chunk", [None, 16])
def test_slots_in_flight_hold_the_references_state(params, chunk):
    """What the benchmark's ``state_gap`` reads: after some decode steps
    the first state layer of a slot is the reference's recurrence over the
    tokens the slot has consumed."""
    engine = ServingEngine(params, CFG, ServeConfig(**SERVE,
                                                    prefill_chunk=chunk))
    engine.submit(Request(rid=0, prompt=tuple(int(t) for t in TOKENS[:37]),
                          max_new_tokens=30))
    for _ in range(12):
        engine.step()
    slot = next(i for i, s in enumerate(engine.slots) if s is not None)
    s = engine.slots[slot]
    tokens = (tuple(s.req.prompt) + tuple(s.emitted))[:s.length]
    got = [np.asarray(engine.cache.state[li, slot]) for li in range(3)]
    gaps = ref.state_gaps(params, DIMS, [(tokens, got)], 96,
                          layers=6)["per_stream"][0]
    assert len(gaps) == 3 and max(gaps) < TIGHT
    rounded = ref.state_gaps(params, DIMS, [(tokens, got)], 96,
                             control="bfloat16")["widest"]
    assert rounded > 1e-3


def test_a_reused_slot_gives_a_fresh_engines_logits(monkeypatch, params):
    """Three slots, six requests of mixed lengths (whole and chunked
    prefill): every slot is reused after a finished request and each
    request's logits are those of the reference's full forward."""
    serve = ServeConfig(**SERVE, prefill_chunk=16)
    lens = [(9, 5), (40, 7), (21, 4), (33, 6), (8, 9), (17, 3)]
    reqs = [Request(rid=r, prompt=tuple(int(t) for t in
                                        TOKENS[3 * r:3 * r + t0]),
                    max_new_tokens=n) for r, (t0, n) in enumerate(lens)]
    out, got, engine = _serve_logits(monkeypatch, params, serve, reqs)
    assert engine.stats["completed"] == 6 and engine.stats["max_active"] == 3
    for r, (t0, n) in enumerate(lens):
        _close(got[r], _reference_rows(params, out[r], t0, n))


def test_idle_rows_leave_the_state_to_the_bit(params):
    """One request decoding among three slots: the other slots' state and
    inputs, set to a pattern, come through every decode step to the bit."""
    engine = ServingEngine(params, CFG, ServeConfig(**SERVE))
    engine.submit(Request(rid=0, prompt=tuple(int(t) for t in TOKENS[:9]),
                          max_new_tokens=6))
    engine.step()
    slot = next(i for i, s in enumerate(engine.slots) if s is not None)
    idle = jnp.asarray([i for i in range(3) if i != slot])
    rng = np.random.default_rng(1)
    marks = {"state": jnp.asarray(rng.normal(size=(3, 2, 4, 8, 16)),
                                  jnp.float32),
             "conv": jnp.asarray(rng.normal(size=(3, 2, 3 * WIDTH)),
                                 jnp.float32)}
    engine.cache = engine.cache._replace(**{
        name: getattr(engine.cache, name).at[:, idle].set(mark)
        for name, mark in marks.items()})
    while engine.pending():
        engine.step()
    for name, mark in marks.items():
        np.testing.assert_array_equal(
            np.asarray(getattr(engine.cache, name)[:, idle]),
            np.asarray(mark))


def test_refusals_name_the_state_not_the_mixer(params):
    for kw, extra in ((dict(speculate=SpecConfig(draft_tokens=2)), {}),
                      (dict(ep_shards=3, num_pages=42), {}),
                      ({}, dict(prefill_fn=lambda *a, **k: None))):
        with pytest.raises(NotImplementedError, match="recurrent-state"):
            ServingEngine(params, CFG, ServeConfig(**dict(SERVE, **kw)),
                          **extra)


# ------------------------------------------- the records, counters and names

def test_records_and_names(params):
    assert {"attn.ssm_prefill", "attn.ssm_decode"} <= set(SPAN_NAMES)
    rec, mx = FlightRecorder(), Metrics()
    engine = ServingEngine(params, CFG, ServeConfig(**SERVE,
                                                    prefill_chunk=16),
                           recorder=rec, metrics_obj=mx)
    engine.run([Request(rid=r, prompt=tuple(int(t) for t in TOKENS[:t0]),
                        max_new_tokens=4) for r, t0 in enumerate((9, 40))])
    slot = CFG.state_slot_bytes
    decodes = [r for r in rec.records if r["kind"] == "serve_decode"]
    # every row of the program goes through the step, live or not
    assert decodes and all(d["state_rows"] == 3 >= d["slots"]
                           and d["state_bytes"] == 2 * 3 * slot
                           and 1 <= d["experts_touched"] <= 6
                           and d["attn_arm"] == "gather"
                           for d in decodes)
    assert mx.gauges["serve.state_slot_bytes"] == slot
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    lower = lambda fn, *a: fn.lower(params, CFG, init_paged_cache(
        CFG, 40, 8, 3), *a).as_text(debug_info=True)
    text = lower(eng._paged_decode_step, jnp.zeros((3,), jnp.int32),
                 jnp.zeros((3, 3), jnp.int32), jnp.zeros((3,), jnp.int32))
    assert "attn.ssm_decode" in text and "attn.ssm_prefill" not in text
    text = lower(eng._prefill_chunk, jnp.zeros((1, 16), jnp.int32),
                 jnp.zeros((3,), jnp.int32), jnp.zeros((2,), jnp.int32),
                 jnp.int32(0), jnp.int32(3))
    assert "attn.ssm_prefill" in text
