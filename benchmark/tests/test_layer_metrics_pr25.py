"""Rehearsal of the six per-layer readers PR 25 added (CPU):
``python -m pytest benchmark/tests/test_layer_metrics_pr25.py -q``.

Each ``layer_metrics/<metric>.json`` is found by name, names a reducer
that exists, and computes the value worked out by hand here from a few
synthetic records and a few synthetic ``XLA Ops`` events.  On what the
program of the commit before gives (no ``fm_`` kernel names, no
``serve_decode`` records) a reader finds nothing and says so: the metric
is left out of the line, nothing raises.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


run = _load(os.path.join(BENCH, "run.py"), "benchrun_pr25")
reducers = run.lib("reducers")

TRAIN, BACKLOG = "fmref.train.4k", "dsmoe16b.serve.backlog"
NEW = {
    "expert_ffn_fwd_roofline.train": TRAIN,
    "expert_wgrad_roofline.train": TRAIN,
    "expert_dgrad_roofline.train": TRAIN,
    "decode_ctx_gathered.backlog": BACKLOG,
    "decode_ctx_idle.backlog": BACKLOG,
    "kv_pool_occupancy.backlog": BACKLOG,
}

# two executions of the train step; per step the forward kernel runs
# twice (forward pass and recompute), tgmm and gmm twice each
MS = 1_000_000
OPS = [
    ("%fm_ffn_fwd_res.2 = (bf16[16384,2048]) custom-call(...)", 0, 2 * MS),
    ("%fm_ffn_fwd_res.3 = (bf16[16384,2048]) custom-call(...)", 3 * MS, 1 * MS),
    ("%fm_gmm.2 = bf16[16384,2048] custom-call(...)", 5 * MS, 1 * MS),
    ("%fm_gmm.3 = bf16[16384,2048] custom-call(...)", 7 * MS, 1 * MS),
    ("%fm_tgmm.2 = f32[64,2048,2048] custom-call(...)", 9 * MS, 2 * MS),
    ("%fm_tgmm.3 = f32[64,2048,2048] custom-call(...)", 12 * MS, 3 * MS),
    ("%fusion.77 = f32[64,2048,2048] fusion(...)", 16 * MS, 10 * MS),
    ("%fm_flash_fwd.4 = bf16[32,4096,128] custom-call(...)", 30 * MS, 13 * MS),
    ("%fm_ffn_fwd_res.2 = (bf16[16384,2048]) custom-call(...)", 100 * MS, 2 * MS),
    ("%fm_ffn_fwd_res.3 = (bf16[16384,2048]) custom-call(...)", 103 * MS, 1 * MS),
    ("%fm_gmm.2 = bf16[16384,2048] custom-call(...)", 105 * MS, 1 * MS),
    ("%fm_gmm.3 = bf16[16384,2048] custom-call(...)", 107 * MS, 1 * MS),
    ("%fm_tgmm.2 = f32[64,2048,2048] custom-call(...)", 109 * MS, 2 * MS),
    ("%fm_tgmm.3 = f32[64,2048,2048] custom-call(...)", 112 * MS, 3 * MS),
]
MODULES = [("jit_step_fn(1234)", 0, 90 * MS), ("jit_step_fn(1234)", 100 * MS, 90 * MS),
           ("jit_feed(7)", 95 * MS, 1 * MS)]
# what the commit before shows for the same kernels (PERF.md §3, PR 24)
OLD_OPS = [("%jvp__.1 = (bf16[16384,2048]) custom-call(...)", 0, 2 * MS),
           ("%rematted_computation.1 = (bf16[16384,2048]) custom-call(...)", 3 * MS, MS),
           ("%transpose_jvp_jit_tgmm___.1 = f32[64,2048,2048] custom-call(...)", 5 * MS, MS),
           ("%transpose_jvp_jit_grouped_matmul___.2 = bf16[16384,2048] custom-call(...)", 7 * MS, MS)]

RECORDS = [
    {"kind": "serve_step", "step": 0, "active": 32, "pages_used": 1000,
     "ctx_pages": 64, "ctx_pages_idle": 30.0},
    {"kind": "serve_decode", "step": 0, "slots": 32, "ctx_pages": 64,
     "ctx_pages_idle": 30.0},
    {"kind": "serve_step", "step": 1, "active": 30, "pages_used": 1048,
     "ctx_pages": 0, "ctx_pages_idle": 0.0},      # a step with no decode
    {"kind": "serve_step", "step": 2, "active": 31, "pages_used": 1024,
     "ctx_pages": 96, "ctx_pages_idle": 50.0},
    {"kind": "serve_decode", "step": 2, "slots": 31, "ctx_pages": 96,
     "ctx_pages_idle": 50.0},
    {"kind": "serve_request", "rid": 3, "tokens": 100, "queue_wait_ms": 1.0},
]
# the commit before: serve_step records without the new fields
OLD_RECORDS = [{"kind": "serve_step", "step": i, "active": 32,
                "pages_used": 1024} for i in range(3)]


def _reader(metric):
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json")) as f:
        return json.load(f)


def _ctx(cell_name, ops=(), modules=(), records=()):
    cell = run.load_cell(cell_name)
    dev = {"ops": list(ops), "modules": list(modules), "t0": 0, "t1": 200 * MS}
    return {"trace": {"per_device": {"/device:TPU:0": dev}, "busy_s": 0.1},
            "records": list(records), "harness": {}, "end_to_end": {},
            "cell": cell.spec, "config": cell.config,
            "peaks": run.lib("peaks").peaks_for("TPU v5 lite"),
            "chips": 1, "lib": run.lib}


def _value(metric, ctx):
    reader = _reader(metric)
    return getattr(reducers, reader["reducer"])(ctx, **reader.get("args", {}))


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_is_found_by_name_and_listed_for_its_cell(metric):
    reader = _reader(metric)
    assert callable(getattr(reducers, reader["reducer"]))
    assert reader["what"]
    cell = run.load_cell(NEW[metric])
    listed = [m for m in cell.per_layer() if m["name"] == metric]
    assert len(listed) == 1 and listed[0]["workloads"] == [NEW[metric]]
    other = TRAIN if NEW[metric] == BACKLOG else BACKLOG
    assert metric not in {m["name"]
                          for m in run.load_cell(other).per_layer()}


def test_new_entries_are_the_manifests_last_six():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert sorted(names[-6:]) == sorted(NEW) and len(set(names)) == len(names)


# the routed experts' two products over 64 experts x 256 rows (capacity
# factor 1.0, top-2 of 8192 tokens): 2 x 2 x 16384 x 2048 x 2048 FLOPs,
# 1.3955 ms at 197 TFLOP/s
NEED_S = 2 * 2.0 * 16384 * 2048 * 2048 / 197e12


@pytest.mark.parametrize("metric,per_step_ms", [
    ("expert_ffn_fwd_roofline.train", 3.0),     # 2 + 1: forward + recompute
    ("expert_dgrad_roofline.train", 2.0),       # fm_gmm alone, not fm_tgmm
    ("expert_wgrad_roofline.train", 5.0),
])
def test_kernel_rooflines_by_hand(metric, per_step_ms):
    got = _value(metric, _ctx(TRAIN, OPS, MODULES))
    assert got == pytest.approx(100.0 * NEED_S / (per_step_ms * 1e-3),
                                rel=1e-9)
    assert 0.0 < got < 100.0


@pytest.mark.parametrize("metric,want", [
    # (64 + 96) / 2 pages of 160 a slot: steps without a decode left out
    ("decode_ctx_gathered.backlog", 100.0 * 80.0 / 160.0),
    ("decode_ctx_idle.backlog", 100.0 * 40.0 / 160.0),
    # (1000 + 1048 + 1024) / 3 pages of 2048
    ("kv_pool_occupancy.backlog", 100.0 * 1024.0 / 2048.0),
])
def test_record_shares_by_hand(metric, want):
    assert _value(metric, _ctx(BACKLOG, records=RECORDS)) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_finds_nothing_in_what_the_commit_before_gives(metric):
    """Old kernel names, old records, or no trace at all: None, and no
    exception; the one metric whose field the old records have reads it."""
    ctx = _ctx(NEW[metric], OLD_OPS, MODULES, OLD_RECORDS)
    got = _value(metric, ctx)
    if metric == "kv_pool_occupancy.backlog":
        assert got == pytest.approx(50.0)
    else:
        assert got is None
    bare = dict(ctx, trace=None, records=[])
    assert _value(metric, bare) is None


def test_program_records_feed_the_backlog_readers(tmp_path):
    """The program itself at a toy size (CPU, the scratch tree of
    ``tiny_tree``, the toy backlog cell appended to the three record
    metrics' lists there): a traced run's line carries all three."""
    sys.path.insert(0, HERE)
    import tiny_tree

    tree = tiny_tree.write_tree(str(tmp_path / "tree"))
    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    mine = [m for m in manifest["per_layer"]
            if NEW.get(m["name"]) == BACKLOG]
    assert len(mine) == 3
    for m in mine:
        m["workloads"].append("tiny.serve.backlog")
    with open(path, "w") as f:
        json.dump(manifest, f)
    res = run.run_cell("tiny.serve.backlog", 2**31 + 11, 1.5, True,
                       require_tpu=False, root=tree)
    assert res["correct"]
    got = {m["name"]: res["metrics"][m["name"]]["value"] for m in mine}
    # toy engine: buckets of 4 pages of 12 a slot, 64 pages in the pool
    assert 100.0 * 4 / 12 <= got["decode_ctx_gathered.backlog"] <= 100.0
    assert 0.0 <= got["decode_ctx_idle.backlog"] \
        < got["decode_ctx_gathered.backlog"]
    assert 0.0 < got["kv_pool_occupancy.backlog"] <= 100.0
