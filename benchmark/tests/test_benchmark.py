"""Rehearsal tests of the benchmark itself (CPU): ``python -m pytest benchmark/tests -q``.

They hold the yardstick still: the trace reduction on a small recorded
trace, the traffic generator, the count functions against numbers worked by
hand, the manifest against the contract's character rules, cells,
configurations and per-layer metrics found by name when dropped in as new
files, the refusal to report without a TPU, each cell kind's control (the
plain reference in the precision below) failing its limit at a toy size,
and a run with the timed path broken underneath coming out not correct.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import re
import statistics
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import tiny_tree  # noqa: E402


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


run = _load(os.path.join(BENCH, "run.py"), "benchrun")
tr = run.lib("trace_reduce")
traffic = run.lib("traffic")
counts = run.lib("counts")
reducers = run.lib("reducers")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny_tree.write_tree(str(tmp_path_factory.mktemp("tree")))


# ---------------------------------------------------------------- trace

HAND = [("a", 0, 10), ("b", 5, 15), ("c", 30, 10), ("all-to-all.1", 50, 10),
        ("d", 55, 10)]


def test_union_and_gaps_by_hand():
    assert tr.union_ns(HAND) == 20 + 10 + 15
    assert tr.gaps(HAND) == [(20, 10), (40, 10)]
    assert tr.gaps(HAND, t0=0, t1=80) == [(20, 10), (40, 10), (65, 15)]


def test_time_by_pattern_and_totals_by_hand():
    evs = [("jit_f(12)", 0, 4), ("jit_f(12)", 10, 6), ("jit_g(3)", 20, 1)]
    assert tr.time_by_pattern(evs, r"^jit_f") == (10, 2)
    assert tr.totals_by_name(evs) == [("jit_f", 10, 2), ("jit_g", 1, 1)]


def test_exposed_share_by_hand():
    # the collective runs 50..60; "d" covers 55..65: 5 ns are exposed
    assert tr.exposed_ns(HAND, r"all-to-all") == 5


def test_gap_attribution_by_hand():
    host = [("wide", 0, 10_000_000), ("serve.decode", 100_000, 50_000),
            ("sampling", 300_000, 40_000)]
    got = tr.attribute_gaps([(100_000, 50_000), (300_000, 40_000),
                             (500_000, 10)], host)
    assert got == [("serve.decode", 50_000 / 1e9), ("sampling", 40_000 / 1e9)]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        raw = json.load(f)
    return {"devices": {p: {ln: [tuple(e) for e in evs]
                            for ln, evs in ls.items()}
                        for p, ls in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def test_recorded_trace_busy_and_idle(recorded):
    """Busy is the union of the device's operations; checked against a
    count of covered nanosecond boundaries made another way."""
    s = tr.summarize(recorded, n_devices=1)
    ops = recorded["devices"]["/device:TPU:0"]["XLA Ops"]
    marks = sorted([(t, 1) for _, t, d in ops] + [(t + d, -1) for _, t, d in ops])
    busy, depth, last = 0, 0, None
    for t, step in marks:
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    assert s["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["modules"][0][0] == "jit__paged_decode_step"


def test_recorded_trace_reducers_read_it(recorded):
    s = tr.summarize(recorded, n_devices=1)
    ctx = {"trace": s, "harness": {}, "records": [], "end_to_end": {},
           "lib": run.lib}
    ms = reducers.module_ms_per_call(ctx, pattern="^jit__paged_decode_step")
    mods = recorded["devices"]["/device:TPU:0"]["XLA Modules"]
    hit = [d for n, _, d in mods if n.startswith("jit__paged_decode_step")]
    assert ms == pytest.approx(sum(hit) / len(hit) / 1e6)
    assert reducers.module_ms_per_call(ctx, pattern="^jit_no_such") is None
    share = reducers.module_share_of_busy(ctx, pattern="^jit__sample")
    assert 0 < share < 100


def test_reader_without_a_trace_returns_nothing():
    ctx = {"trace": None, "harness": {}, "records": [], "end_to_end": {},
           "lib": run.lib}
    assert reducers.module_ms_per_call(ctx, pattern="x") is None
    assert reducers.exposed_share(ctx, pattern="x") is None
    assert reducers.harness_median(ctx, series="engine_step_ms") is None


# -------------------------------------------------------------- traffic

MIX = {"arrivals": {"kind": "poisson", "rate_per_s": 4.0},
       "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 1.0,
                      "min": 32, "max": 2048},
       "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                      "min": 16, "max": 512},
       "block": 64}


def test_traffic_is_a_pure_function_of_the_seed():
    a = traffic.generate(MIX, 2**31 + 11, 1000, 100)
    assert a == traffic.generate(MIX, 2**31 + 11, 1000, 100)
    assert a != traffic.generate(MIX, 2**31 + 12, 1000, 100)
    assert [x.rid for x in a] == list(range(100))


def test_every_seed_gets_the_same_sizes_in_another_order():
    a = traffic.generate(MIX, 1, 1000, 128)
    b = traffic.generate(MIX, 2, 1000, 128)
    for part in (slice(0, 64), slice(64, 128)):
        assert sorted(len(x.prompt) for x in a[part]) == \
            sorted(len(x.prompt) for x in b[part])
        assert sorted(x.max_new_tokens for x in a[part]) == \
            sorted(x.max_new_tokens for x in b[part])
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


def test_traffic_matches_its_stated_distributions():
    reqs = traffic.generate(MIX, 5, 1000, 64)
    prompts = sorted(len(r.prompt) for r in reqs)
    assert prompts[0] >= 32 and prompts[-1] <= 2048
    assert statistics.median(prompts) == pytest.approx(256, rel=0.05)
    outs = [r.max_new_tokens for r in reqs]
    assert min(outs) >= 16 and max(outs) <= 512
    assert statistics.median(outs) == pytest.approx(128, rel=0.05)
    # a block of 64 arrivals at 4/s lasts exactly 16 s
    assert reqs[-1].due_s == pytest.approx(16.0)
    assert all(1 <= t < 1000 for r in reqs for t in r.prompt)


def test_backlog_is_due_at_once_and_pctl_is_nearest_rank():
    mix = dict(MIX, arrivals={"kind": "backlog"})
    assert {r.due_s for r in traffic.generate(mix, 3, 100, 10)} == {0.0}
    assert traffic.pctl(list(range(1, 101)), 0.95) == 95
    assert traffic.pctl([5, 1, 3], 0.5) == 3
    with pytest.raises(ValueError):
        traffic.pctl([], 0.5)


# --------------------------------------------------------------- counts

FM = {"hidden": 2048, "inter": 2048, "experts": 64, "top_k": 2, "shared": 0,
      "gated": False, "drop_tokens": True, "capacity_factor": 1.0,
      "heads": 16, "head_dim": 128, "layers": 2, "moe_every": 2,
      "vocab": 50257, "param_dtype": "float32", "dtype": "bfloat16"}
DS = {"hidden": 2048, "inter": 1408, "experts": 64, "top_k": 6, "shared": 2,
      "gated": True, "drop_tokens": False, "capacity_factor": 1.0,
      "heads": 16, "head_dim": 128, "layers": 6, "moe_every": 1,
      "vocab": 102400, "param_dtype": "bfloat16", "dtype": "bfloat16"}


def test_expert_flops_by_hand():
    # 8192 tokens x 2 choices x 2 products x 2 x 2048 x 2048
    assert counts.capacity(FM, 8192) == 256
    assert counts.expert_rows(FM, 8192) == 16384
    assert counts.expert_gemm_flops(FM, 8192) == 2 * 2 * 16384 * 2048 * 2048
    # the issue's figure: about 275 GFLOP a chip a call
    assert counts.expert_gemm_flops(FM, 8192) == pytest.approx(275e9, rel=0.01)


def test_moe_layer_flops_by_hand():
    s = 4096
    want = (2 * s * 2048 * 64 + 3 * 2 * s * 6 * 2048 * 1408
            + 3 * 2 * s * 2048 * 1408 * 2)
    assert counts.moe_layer_flops(DS, s) == want


def test_train_step_flops_by_hand():
    b, t = 2, 4096
    tok = b * t
    proj = 4 * 2 * tok * 2048 * 2048
    attn = 2 * 2 * b * 2048 * t * (t + 1) / 2
    dense = 2 * 2 * tok * 2048 * 2048
    moe = 2 * tok * 2048 * 64 + 2 * 2 * (tok * 2) * 2048 * 2048
    head = 2 * tok * 2048 * 50257
    assert counts.train_step_flops(FM, b, t) == pytest.approx(
        3 * (2 * (proj + attn) + dense + moe + head))


def test_decode_bytes_by_hand():
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 1408 * 66 + 2048 * 64
    weights = 2 * (6 * per_layer + 2048 * 102400)
    assert counts.weight_bytes(DS) == weights
    touch = 1 - (1 - 6 / 64) ** 32
    routed = 2 * 6 * 3 * 2048 * 1408 * 64
    kv = 2 * 6 * 10000 * 16 * 128 * 2
    assert counts.decode_step_bytes(DS, 32, 10000) == pytest.approx(
        weights - routed * (1 - touch) + kv)
    assert counts.decode_step_bytes(DS, 1, 0) < counts.weight_bytes(DS) / 2


def test_peaks_table_and_unknown_kind():
    peaks = run.lib("peaks")
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


# ------------------------------------------------------------- manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_keeps_to_the_contract(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(manifest["paths"][0] + "/")
        assert any(w["config"] == c["name"] for w in manifest["workloads"])
    assert len(json.dumps(manifest)) < 64 * 1024


def test_every_entry_has_its_file_and_every_cell_its_metrics(manifest):
    for w in manifest["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.spec["name"] == w["name"]
        assert cell.spec["config"] == w["config"] == cell.config["name"]
        assert os.path.exists(os.path.join(
            BENCH, "drivers", cell.spec["driver"] + ".py"))
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end()}
        assert cell.per_layer()
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["reduced"] == c["reduced"] and conf["source"] == c["source"]
    for m in manifest["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics", m["name"] + ".json")) as f:
            reader = json.load(f)
        assert hasattr(reducers, reader["reducer"])


# ------------------------------------------- new files are found by name

def test_new_cell_config_and_metric_are_found_by_name(tree):
    cell = run.load_cell("tiny.serve.backlog", root=tree)
    assert cell.config["name"] == "tiny" and cell.spec["driver"] == "serve"
    assert "engine_step_ms.tiny" in [m["name"] for m in cell.per_layer()]
    assert [m["name"] for m in cell.end_to_end()] == \
        ["serve_tokens_per_s", "setup_s"]
    # nothing of the real tree was edited to get there
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert "tiny" not in f.read()


def test_runner_refuses_without_a_tpu(tree):
    with pytest.raises(SystemExit) as e:
        run.run_cell("tiny.serve.backlog", 1, 1.0, False, root=tree)
    assert e.value.code == run.EXIT_NO_CHIP


def test_runner_gives_no_result_in_a_bare_directory(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"][0]
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode == run.EXIT_NO_PROGRAM
    assert '"correct"' not in p.stdout


# ------------------------------------- whole runs at a toy size, and faults

def _drive(tree, cell, seed=2**31 + 5, trace=False, control=False):
    return run.run_cell(cell, seed, 1.5, trace, control=control,
                        require_tpu=False, root=tree)


def test_serve_cell_runs_and_its_control_fails(tree, capsys):
    res = _drive(tree, "tiny.serve.backlog", control=True)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"   # named, never passed off
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    limit = next(s["limit"] for s in said
                 if s.get("compared") == "served_gap_mean")
    control_gap = next(s for s in said if "check" in s)[
        "check"]["control"]["served_gap_mean"]
    assert control_gap > limit      # the precision below is not correct


def test_chat_cell_times_requests_from_when_they_were_due(tree):
    res = _drive(tree, "tiny.serve.chat")
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert res["metrics"]["ttft_p95_ms"]["value"] > 0


def test_traced_run_reports_layer_metrics_found_by_name(tree):
    res = _drive(tree, "tiny.serve.backlog", trace=True)
    assert "engine_step_ms.tiny" in res["metrics"]
    assert "serve_tokens_per_s" not in res["metrics"]


def test_a_token_altered_where_it_is_produced_is_not_correct(tree, monkeypatch):
    from flashmoe_tpu.serving import engine as eng

    real = eng._sample_dynamic

    def altered(logits, *rest):
        return (real(logits, *rest) + 1) % logits.shape[-1]

    monkeypatch.setattr(eng, "_sample_dynamic", altered)
    res = _drive(tree, "tiny.serve.backlog")
    assert res["correct"] is False


@pytest.fixture
def one_device(monkeypatch):
    """The training cell is a one-chip cell: the trainer's bootstrap
    spreads over every device it finds, so it is shown one."""
    import jax

    from flashmoe_tpu.runtime import bootstrap

    real = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a, **k: real(*a, **k)[:1])
    monkeypatch.setattr(bootstrap, "_runtime", None)
    yield
    bootstrap._runtime = None


def test_train_cell_runs_and_its_control_fails(tree, capsys, one_device):
    res = _drive(tree, "tinyref.train", control=True)
    assert res["correct"] and res["attempted"] > 0
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    limit = next(s["limit"] for s in said
                 if s.get("compared") == "first_grad_gap")
    control = next(s for s in said if "check" in s)["check"]["control"]
    assert control["first_grad_gap"] > limit


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tree, monkeypatch, one_device):
    from flashmoe_tpu.runtime import trainer

    real = trainer.make_train_step

    def frozen(*a, **k):
        step = real(*a, **k)
        return lambda state, batch: (
            jax_tree_copy(state), step(jax_tree_copy(state), batch)[1])

    import jax

    def jax_tree_copy(t):
        return jax.tree_util.tree_map(lambda x: x + 0, t)

    monkeypatch.setattr(trainer, "make_train_step", frozen)
    res = _drive(tree, "tinyref.train")
    assert res["correct"] is False


def test_layer_cell_runs_on_four_devices_and_its_control_fails(tree, capsys):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    res = _drive(tree, "tinyref.layer.ep4", control=True)
    assert res["correct"] and res["device"]["count"] >= 4
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    limit = next(s["limit"] for s in said
                 if s.get("compared") == "worst_row_error")
    control = next(s for s in said if "check" in s)["check"]["control"]
    assert control["worst_row_error"] > limit


def test_lr_schedule_is_the_trainers():
    import optax

    train = _load(os.path.join(BENCH, "drivers", "train.py"), "benchdriver_train_t")
    opt = {"lr": 3e-4, "warmup_steps": 100, "total_steps": 10000}
    sched = optax.warmup_cosine_decay_schedule(0.0, 3e-4, 100, 10000)
    for step in (0, 1, 2, 50, 100, 101, 5000, 9999):
        assert train.lr_at(opt, step) == pytest.approx(float(sched(step)),
                                                       rel=1e-5, abs=1e-9)
    assert math.isclose(train.lr_at(opt, 0), 0.0)
