"""Driver of the serving cells whose layers differ in what they SEE (window
layers beside full ones): ``drivers/serve_controls.py``'s run to the letter
(the cell's ``reference`` file, one more reading for each name under the
check's ``controls`` in a ``--control 1`` run), with a sample of finished
streams that is SURE to hold the two a window has to be checked on: the
finished request with the SHORTEST prompt (under the window: its context
crosses the window while it decodes) and the one with the LONGEST (its
prompt is several windows, so every chunk but the first gathers a window's
pages alone).  ``drivers/serve.py``'s sample is the one of most served
tokens and a seeded draw of the others, which holds a shortest prompt in
two runs of five; here the draw's last two places go to those two where it
missed them.  Everything else is ``drivers/serve.py``'s.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import sys


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
    return _driver("serve_controls").build(run)


def measure(state, run):
    return _driver("serve_controls").measure(state, run)


def covering_streams(sample, state, run, n):
    """``sample`` (``drivers/serve.sample_streams``) with the shortest and
    the longest finished prompt among the ``n`` streams."""
    serve = _driver("serve")
    engine, watch = state["engine"], state["watch"]
    w0, _ = state["window"]
    picked = sample(state, run, n)
    done = sorted(rid for rid, t in watch.done.items()
                  if t >= w0 and rid < serve.COHORT_RID)
    if not done:
        return picked
    by_len = sorted(done, key=lambda r: (watch.prompt_len[r], r))
    ends = (watch.prompt_len[by_len[0]], watch.prompt_len[by_len[-1]])
    have = {len(p) for p, _ in picked}
    for rid, t0 in zip((by_len[0], by_len[-1]), ends):
        if t0 in have:
            continue
        full = engine.outputs[rid]
        stream = (tuple(full[:t0]), tuple(full[t0:]))
        # the draw's places from the end on; the first is the stream of
        # most served tokens and stays
        at = next((i for i in range(len(picked) - 1, 0, -1)
                   if len(picked[i][0]) not in ends), None)
        if at is None:
            picked.append(stream)
        else:
            picked[at] = stream
        have.add(t0)
    return picked


def check(state, run):
    """``serve_controls.check`` over :func:`covering_streams`: the sample
    is ``drivers/serve.py``'s function, which the other drivers call by
    its module, so it is put in its place for the length of the call."""
    serve = _driver("serve")
    plain = serve.sample_streams
    serve.sample_streams = functools.partial(covering_streams, plain)
    try:
        checked = _driver("serve_controls").check(state, run)
    finally:
        serve.sample_streams = plain
    checked["notes"]["prompt_lengths"] = [
        s["prompt"] for s in checked["notes"].get("streams", [])]
    return checked


def close(state):
    _driver("serve_controls").close(state)
