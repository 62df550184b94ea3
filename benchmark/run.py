#!/usr/bin/env python3
"""The benchmark's one command: run ONE cell once, in this process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds everything by name: the cell in ``BENCHMARK.json``, its
parameters in ``benchmark/workloads/<cell>.json``, its sizes in
``benchmark/configs/<config>.json``, its driver in
``benchmark/drivers/<driver>.py`` and, in a traced run, each per-layer
metric's reader in ``benchmark/layer_metrics/<metric>.json``.  It refuses
to run without a TPU holding the cell's chips (exit 3, no result line),
keeps the compile cache at a fixed place inside the checkout, counts
everything before the measured window as ``setup_s``, checks the timed
path's outputs against the plain reference after the window, and prints
ONE JSON object as its last line.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` profiles a few seconds inside the window
and reports its per-layer metrics, the device's busy time and a breakdown.

Builder's options, which the driver never passes: ``--seed a,b,c`` runs the
seeds one after another in this process (one result line each);
``--control 1`` also reads the lower-precision control's numbers;
``--root <tree>`` takes the manifest and data files from another tree.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_NO_PROGRAM, EXIT_NO_CHIP = 2, 3


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def _load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def lib(name):
    """A module of ``benchmark/lib`` (no package install needed)."""
    full = f"benchlib_{name}"
    if full in sys.modules:
        return sys.modules[full]
    return _load_module(os.path.join(HERE, "lib", f"{name}.py"), full)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files it names."""

    manifest: dict          # BENCHMARK.json
    entry: dict             # the workloads entry
    spec: dict              # benchmark/workloads/<cell>.json
    config: dict            # benchmark/configs/<config>.json
    data_root: str          # directory that holds the data files

    @property
    def name(self):
        return self.entry["name"]

    @property
    def chips(self):
        return int(self.entry["chips"])

    def end_to_end(self):
        """End-to-end metrics this cell reports."""
        return [m for m in self.manifest["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self):
        """Per-layer metrics whose readers may find something here: those
        that list this cell, and those with no list whose ``moves`` this
        cell reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.manifest["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """Find a cell and its files by name under ``root`` (the checkout;
    tests pass a scratch tree to show that new files need no edit)."""
    manifest = _load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no cell {workload!r} in BENCHMARK.json "
                         f"(cells: {[w['name'] for w in manifest['workloads']]})")
    data = os.path.join(root, manifest["paths"][0])
    spec = _load_json(os.path.join(data, "workloads", f"{workload}.json"))
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    return Cell(manifest, entry, spec, config, data)


@dataclasses.dataclass
class Run:
    """What a driver gets: the cell, the run's arguments, the clock the
    harness times with, and the profiler's switch."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    control: bool
    out_dir: str
    clock: object = time.perf_counter
    t_start: float = T_START
    tracer: object = None

    @staticmethod
    def lib(name):
        """A module of ``benchmark/lib``."""
        return lib(name)

    def say(self, **rec):
        """An earlier line: worth reading, decides nothing."""
        print(json.dumps(rec), flush=True)

    def program_config(self, **extra):
        """The program's ``MoEConfig`` for this cell: the configuration
        file's preset with its overrides, the cell's ``program_overrides``
        and what the driver adds."""
        import jax.numpy as jnp

        from flashmoe_tpu.models.presets import PRESETS

        prog = self.cell.config["program"]
        over = dict(prog.get("overrides", {}),
                    **self.cell.spec.get("program_overrides", {}), **extra)
        for k in ("dtype", "param_dtype"):
            if k in over:
                over[k] = jnp.dtype(over[k]).type
        return PRESETS[prog["preset"]](**over)

    def back_to_back(self, call, span):
        """Call ``call(i)`` (which dispatches one step and returns what to
        wait for) back to back for ``seconds``, one call dispatched ahead
        of the one being waited for.  Returns the window's start, the
        time of the last completion, the seconds between completions and
        the last call's result (waited for)."""
        import jax

        clock = self.clock
        w0 = clock()
        w1 = w0 + self.seconds
        t_done, waiting, out, gaps, i = w0, None, None, [], 0
        while True:
            if self.tracer is not None:
                self.tracer.tick(clock() - w0, clock)
            with jax.profiler.TraceAnnotation(span):
                out = call(i)
            i += 1
            if waiting is not None:
                jax.block_until_ready(waiting)
                now = clock()
                gaps.append(now - t_done)
                t_done = now
                if t_done >= w1:
                    break
            waiting = out
        jax.block_until_ready(out)      # the call dispatched ahead
        if self.tracer is not None:
            self.tracer.finish()
        return w0, t_done, gaps, out


class WindowTracer:
    """Profiles ``span_s`` seconds of the window, starting ``after_s``
    into it.  The driver calls :meth:`tick` between steps."""

    def __init__(self, out_dir, window_s):
        self.dir = os.path.join(out_dir, "trace")
        self.after_s = 0.25 * window_s
        self.span_s = min(3.0, 0.25 * window_s)
        self.state = "idle"
        self.t_on = None

    def tick(self, elapsed_s, clock):
        import jax

        if self.state == "idle" and elapsed_s >= self.after_s:
            jax.profiler.start_trace(self.dir)
            self.state, self.t_on = "on", clock()
        elif self.state == "on" and clock() - self.t_on >= self.span_s:
            self.finish()

    def finish(self):
        import jax

        if self.state == "on":
            jax.profiler.stop_trace()
            self.state = "done"


def device_report(chips: int, require_tpu: bool):
    """The devices as JAX reports them; exit 3 where the cell's chips are
    not there (never a fall back to the CPU)."""
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"benchmark: needs {chips} TPU chip(s), JAX reports "
              f"{len(devs)} x {devs[0].platform}: no result",
              file=sys.stderr)
        raise SystemExit(EXIT_NO_CHIP)
    return devs


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def read_layer_metrics(cell: Cell, ctx: dict) -> dict:
    """Each per-layer metric through its own reader file; a reader that
    finds nothing returns None and the metric is left out."""
    reducers = lib("reducers")
    out = {}
    for m in cell.per_layer():
        path = os.path.join(cell.data_root, "layer_metrics",
                            f"{m['name']}.json")
        reader = _load_json(path)
        fn = getattr(reducers, reader["reducer"])
        value = fn(ctx, **reader.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             control: bool = False, require_tpu: bool = True,
             root: str = ROOT, t_start: float | None = None) -> dict:
    """One run of one cell; returns the result object.  ``require_tpu``
    False is the tests' seam: everything else of a run is driven."""
    t_start = T_START if t_start is None else t_start
    cell = load_cell(workload, root)
    if not os.path.isdir(os.path.join(ROOT, "flashmoe_tpu")):
        print("benchmark: the program (flashmoe_tpu/) is not in this "
              "directory: no result", file=sys.stderr)
        raise SystemExit(EXIT_NO_PROGRAM)
    import jax

    devs = device_report(cell.chips, require_tpu)
    from flashmoe_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    out_dir = os.path.join(ROOT, ".bench_out", workload, str(seed))
    os.makedirs(out_dir, exist_ok=True)
    run = Run(cell, int(seed), float(seconds), bool(trace), bool(control),
              out_dir, t_start=t_start)
    if trace:
        run.tracer = WindowTracer(out_dir, seconds)
    driver = _load_module(
        os.path.join(HERE, "drivers", f"{cell.spec['driver']}.py"),
        f"benchdriver_{cell.spec['driver']}")

    state = driver.build(run)                   # weights, warm-up: set-up
    measured = driver.measure(state, run)       # the window
    peak = memory_peak(devs[:cell.chips])       # the program's, not the check's
    checked = driver.check(state, run)          # after the window

    values = dict(measured["end_to_end"])
    values["setup_s"] = measured["window_start"] - t_start
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(checked["correct"]),
              "attempted": int(measured["attempted"]),
              "failed": int(measured["failed"])}
    for c in checked["compared"]:
        run.say(compared=c["name"], value=c["value"], limit=c["limit"],
                ok=c["ok"])
    run.say(workload=workload, seed=int(seed), seconds=seconds,
            compile_cache=cache_dir, end_to_end=values,
            notes=measured.get("notes", {}), check=checked.get("notes", {}))
    if trace:
        tr = lib("trace_reduce")
        summary = tr.summarize_dir(run.tracer.dir, n_devices=cell.chips)
        peaks = lib("peaks").peaks_for(devs[0].device_kind) \
            if devs[0].platform == "tpu" else None
        ctx = {"trace": summary, "records": measured.get("records", []),
               "harness": measured.get("harness", {}), "end_to_end": values,
               "cell": cell.spec, "config": cell.config, "peaks": peaks,
               "chips": cell.chips, "lib": lib}
        result["metrics"] = read_layer_metrics(cell, ctx)
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            result["breakdown"] = {
                "device_ops": [[n[:160], t] for n, t in summary["top_ops"][:10]],
                "idle_gaps": [[n[:160], t] for n, t in summary["idle_gaps"][:10]]}
            run.say(trace_modules=summary["modules"][:12])
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        result["metrics"] = {k: {"value": float(values[k]), "unit": units[k]}
                             for k in units}
    result["device"] = device
    driver.close(state)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=ROOT,
                    help="tree that holds BENCHMARK.json and the data files "
                         "(to try a cell before the manifest lists it)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in str(args.seed).split(",")]
    sys.path.insert(0, ROOT)
    t_start = T_START
    for seed in seeds:
        result = run_cell(args.workload, seed, args.seconds, bool(args.trace),
                          control=bool(args.control), root=args.root,
                          t_start=t_start)
        print(json.dumps(result), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
