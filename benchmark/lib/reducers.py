"""The few reducers that per-layer metrics are data over.

``benchmark/layer_metrics/<metric>.json`` names one of these functions
(``reducer``) and its arguments (``args``).  Each takes the run's context:
``trace`` (``trace_reduce.summarize``'s result, or None), ``records`` (what
the program's recorder was given), ``harness`` (series the harness timed
itself), ``end_to_end`` (this run's end-to-end values), ``cell``,
``config``, ``peaks``, ``chips`` and ``lib`` (the loader of this directory's
modules).  A reducer that finds nothing to read
returns None and the metric is left out of the line.
"""

from __future__ import annotations

import statistics


def _dims(ctx):
    return ctx["lib"]("reference").model_dims(ctx["config"])


def _device_events(ctx, line):
    tr = ctx.get("trace")
    if not tr:
        return None
    first = tr["per_device"][sorted(tr["per_device"])[0]]
    return first[line]


def harness_median(ctx, series):
    """Median of a series the harness timed with its own clock."""
    vals = ctx["harness"].get(series)
    return statistics.median(vals) if vals else None


def record_mean_share(ctx, kind, field, of_engine):
    """Mean of ``field`` over the program's records of ``kind``, as a
    percentage of the cell's ``engine`` setting ``of_engine``."""
    vals = [r[field] for r in ctx["records"] if r.get("kind") == kind]
    if not vals:
        return None
    return 100.0 * statistics.fmean(vals) / ctx["cell"]["engine"][of_engine]


def module_ms_per_call(ctx, pattern):
    """Device time of the programs matching ``pattern`` per execution."""
    mods = _device_events(ctx, "modules")
    if not mods:
        return None
    total, n = ctx["lib"]("trace_reduce").time_by_pattern(mods, pattern)
    return total / n / 1e6 if n else None


def module_share_of_busy(ctx, pattern):
    """Device time of the programs matching ``pattern`` as a percentage
    of the device's busy time in the traced window."""
    mods = _device_events(ctx, "modules")
    if not mods or not ctx["trace"]["busy_s"]:
        return None
    total, n = ctx["lib"]("trace_reduce").time_by_pattern(mods, pattern)
    return 100.0 * total / 1e9 / ctx["trace"]["busy_s"] if n else None


def _count(ctx, count, count_args):
    args = {k: (ctx["harness"][v[1:]] if isinstance(v, str)
                and v.startswith("$") else v)
            for k, v in (count_args or {}).items()}
    return getattr(ctx["lib"]("counts"), count)(_dims(ctx), **args)


def roofline_share(ctx, line, pattern, count, bound, count_args=None,
                   calls=None):
    """The least time the chip could take for what ``count`` (a function
    of ``lib/counts.py``) says ONE call needs, over the device time one
    call took.  The time is that of the events of ``line`` (``modules``
    or ``ops``) matching ``pattern``; the number of calls is the number of
    programs matching ``calls`` (default: the matched events themselves).
    ``bound`` says which peak binds: ``flops`` or ``hbm_bytes``."""
    evs = _device_events(ctx, line)
    if not evs or not ctx.get("peaks"):
        return None
    red = ctx["lib"]("trace_reduce")
    total_ns, n = red.time_by_pattern(evs, pattern)
    if calls is not None:
        _, n_calls = red.time_by_pattern(
            _device_events(ctx, "modules") or [], calls)
    else:
        n_calls = n
    if not n or not n_calls:
        return None
    need = _count(ctx, count, count_args)
    peak = ctx["peaks"]["bf16_flops_per_s" if bound == "flops"
                        else "hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (total_ns / n_calls / 1e9)


def mfu(ctx, rate, count, count_args, tokens_per_call):
    """End-to-end rate ``rate`` (tokens/s) times the FLOPs a token needs
    over the chips' peak: a model FLOP/s utilization, not a kernel's."""
    if not ctx.get("peaks") or rate not in ctx["end_to_end"]:
        return None
    per_token = _count(ctx, count, count_args) / tokens_per_call
    return (100.0 * ctx["end_to_end"][rate] * per_token
            / (ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]))


def exposed_share(ctx, pattern):
    """Time in the operations matching ``pattern`` (collectives) while no
    other operation runs on that chip, as a percentage of the traced
    window, averaged over the chips."""
    tr = ctx.get("trace")
    if not tr:
        return None
    red = ctx["lib"]("trace_reduce")
    shares = []
    for dev in tr["per_device"].values():
        if not dev["ops"]:
            return None
        shares.append(red.exposed_ns(dev["ops"], pattern)
                      / (dev["t1"] - dev["t0"]))
    return 100.0 * sum(shares) / len(shares)
