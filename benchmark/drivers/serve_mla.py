"""Driver of the serving cells whose plain reference is not
``lib/reference.py``: the cell's ``reference`` names the file of ``lib/``
that describes its block (``reference_mla`` for latent attention).
Everything else is ``drivers/serve.py``'s: this hands it the run with
``lib("reference")`` answered by the file the cell names.
"""

from __future__ import annotations

import importlib.util
import os
import sys


def _serve():
    name = "benchdriver_serve"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "serve.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


class _Run:
    """The harness's run, with the cell's own reference file."""

    def __init__(self, run):
        self._run = run

    def __getattr__(self, name):
        return getattr(self._run, name)

    def lib(self, name):
        return self._run.lib(self._run.cell.spec["reference"]
                             if name == "reference" else name)


def build(run):
    return _serve().build(_Run(run))


def measure(state, run):
    return _serve().measure(state, _Run(run))


def check(state, run):
    return _serve().check(state, _Run(run))


def close(state):
    _serve().close(state)
