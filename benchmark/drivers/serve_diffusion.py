"""Driver of the serving cells whose model generates by diffusion over
blocks: ``build`` / ``measure`` / ``close`` are ``drivers/serve.py``'s
(through ``drivers/serve_mla.py``: the cell's ``reference`` names the file
of ``lib/`` that describes its block), the check is this file's.

A token of such a model was chosen under a block state that ONE causal
forward over prompt + answer never sees, so ``drivers/serve.py::check``
cannot judge it.  Here the sampled finished requests are handed over with,
beside ``engine.outputs[rid]``, the denoising step that revealed each
answer position (``engine.reveal_steps[rid]``); the reference rebuilds
every block's state before each step from the SERVED tokens and steps
(teacher-forced, so a flipped choice does not compound) and reads
(``lib/reference_sdar.block_gaps``):

* ``served_gap_mean`` / ``served_gap_widest``: the reference's best logit
  at a revealed row, at that step, less its logit of the served token, over
  the largest logit magnitude compared (the other cells' scale);
* ``reveal_gap_mean`` / ``reveal_gap_widest``: by the reference's own
  log-confidences, the best row a step left masked less the least row it
  revealed, 0 where the reference would have revealed the same rows.

In a ``--control 1`` run each name under the check's ``controls`` is put in
the program's place (the reference with that mistake made: ``fp8``,
``causal_block``, ``no_commit``) and its four readings are held to the
cell's limits.  A control that PASSES them is a mistake the cell cannot
tell.
"""

from __future__ import annotations

import gc
import importlib.util
import os
import sys

import numpy as np

GAPS = (("served_gap_widest", "served", "widest"),
        ("served_gap_mean", "served", "mean"),
        ("reveal_gap_widest", "reveal", "widest"),
        ("reveal_gap_mean", "reveal", "mean"))


def _driver(name):
    full = f"benchdriver_{name}"
    if full not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            full, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[full] = mod
        spec.loader.exec_module(mod)
    return sys.modules[full]


def build(run):
    return _driver("serve_mla").build(run)


def measure(state, run):
    return _driver("serve_mla").measure(state, run)


def sample_streams(state, run, n):
    """``drivers/serve.py::sample_streams``' seeded sample of the requests
    finished in the window (the one of most served tokens in it), each
    with its reveal steps: [(prompt, served_tokens, reveal_steps)]."""
    engine, watch = state["engine"], state["watch"]
    w0, _ = state["window"]
    cohort = _driver("serve").COHORT_RID
    done = sorted(rid for rid, t in watch.done.items()
                  if t >= w0 and rid < cohort)
    if not done:
        return []
    rng = np.random.default_rng([run.seed & 0xFFFFFFFF, 7])
    longest = max(done, key=lambda r: (watch.tokens[r], -r))
    rest = [r for r in done if r != longest]
    picks = [longest] + [int(r) for r in rng.choice(
        rest, size=min(n - 1, len(rest)), replace=False)]
    out = []
    for rid in picks:
        full, t0 = engine.outputs[rid], watch.prompt_len[rid]
        out.append((tuple(full[:t0]), tuple(full[t0:]),
                    tuple(engine.reveal_steps[rid])))
    return out


def _readings(got):
    return {name: got[part][stat] for name, part, stat in GAPS}


def check(state, run):
    ref = run.lib(run.cell.spec["reference"])
    spec = run.cell.spec["check"]
    streams = sample_streams(state, run, int(spec["streams"]))
    engine, serve, dims = state["engine"], state["serve"], state["dims"]
    # the program's state goes before the reference's copies come
    engine.close()
    engine.cache = engine._logits = engine._block_state = None
    gc.collect()
    t0 = run.clock()
    if not streams:
        return {"correct": False, "compared": [
            {"name": "served_streams", "value": 0, "limit": 1, "ok": False}],
            "notes": {}}
    bl = dims["block"]
    r_pad = -(-state["means"]["output_max"] // bl) * bl
    controls = tuple(spec.get("controls", ())) if run.control else ()
    got = ref.block_gaps(state["params"], dims, streams,
                         serve.denoise_steps or bl, serve.max_context,
                         r_pad, controls=controls)
    limits = spec["limits"]
    compared = []
    for name, value in _readings(got).items():
        limit = limits.get(name)
        compared.append({"name": name, "value": value, "limit": limit,
                         "ok": limit is None or value <= limit})
    notes = {"streams": got["per_stream"], "tokens": got["tokens"],
             "steps": got["steps"],
             "reference_s": round(run.clock() - t0, 3)}
    if controls:
        notes["controls"] = {}
        for name, low in got["controls"].items():
            told = _readings(low)
            notes["controls"][name] = dict(told, passes_the_limits=all(
                limits.get(k) is None or v <= limits[k]
                for k, v in told.items()))
    return {"correct": all(c["ok"] for c in compared) and got["tokens"] > 0,
            "compared": compared, "notes": notes}


def close(state):
    _driver("serve_mla").close(state)
