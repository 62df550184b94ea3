"""Driver of the serving cells whose check names MORE controls than the
lower-precision one: ``drivers/serve_mla.py`` (the cell's ``reference``
names the file of ``lib/`` that describes its block) with, in a
``--control 1`` run, one more reading for each name under the check's
``controls``: the cell's reference is asked for the tokens a program that
made that mistake would have served (``served_token_gaps(...,
control=name)``: a ``quant`` name of the reference file, such as a term of
the layer left out), and their gaps against the sound reference are held
to the cell's limits.  A control that PASSES them is a mistake the cell
cannot tell.  Everything else is ``drivers/serve.py``'s.
"""

from __future__ import annotations

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
    return _driver("serve_mla").build(run)


def measure(state, run):
    return _driver("serve_mla").measure(state, run)


def check(state, run):
    spec = run.cell.spec["check"]
    extra = list(spec.get("controls", [])) if run.control else []
    streams = (_driver("serve").sample_streams(state, run,
                                               int(spec["streams"]))
               if extra else [])
    serve, means = state["serve"], state["means"]
    params, dims = state["params"], state["dims"]
    checked = _driver("serve_mla").check(state, run)
    if not streams:
        return checked
    ref = run.lib(run.cell.spec["reference"])
    limits, told = spec["limits"], {}
    for name in extra:
        low = ref.served_token_gaps(params, dims, streams, serve.max_context,
                                    means["output_max"], control=name)
        got = {"served_gap_widest": low["widest"],
               "served_gap_mean": low["mean"]}
        told[name] = dict(got, passes_the_limits=all(
            limits.get(k) is None or v <= limits[k] for k, v in got.items()))
    checked["notes"]["controls"] = told
    return checked


def close(state):
    _driver("serve_mla").close(state)
