"""Rehearsal of the ten per-layer readers PR 35 added (CPU):
``python -m pytest benchmark/tests/test_layer_metrics_pr35.py -q``.

Each ``layer_metrics/<metric>.json`` is found by name, is listed for its one
cell, names ``record_mean_share`` and computes the value worked out by hand
here from a few records.  That reducer INDEXES its field, so the readers
name record kinds only this PR's program writes (``serve_held``: one a step;
``serve_prefill``: one a prefill program): on what the commit before records
(``serve_step`` records without ``held_slots``) each finds nothing and says
so, and nothing raises.  The program itself, at a toy size, writes the
field on every record of the kind.
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


run = _load(os.path.join(BENCH, "run.py"), "benchrun_pr35")
reducers = run.lib("reducers")

CELLS = {"backlog": "dsmoe16b.serve.backlog",
         "longctx": "joyai_flash.serve.longctx",
         "longgen": "ling3_flash.serve.longgen",
         "shortchat": "lfm2_24b.serve.shortchat"}
NEW = {f"host_held_share.{c}": CELLS[c] for c in CELLS}
NEW.update({f"{m}.{c}": CELLS[c]
            for m in ("prefill_tokens_fill", "prefill_pad_rows")
            for c in ("longctx", "longgen", "shortchat")})

# four steps: two starved (30 and 32 decoding slots held), two did not;
# three prefill programs: a whole prompt of 300 tokens in 512 rows, a full
# chunk and a last chunk of 100 tokens
RECORDS = [
    {"kind": "serve_step", "step": 0, "active": 32, "held_slots": 30},
    {"kind": "serve_held", "step": 0, "decoding": 30, "starved": 2,
     "starved_at": "serve.prefill", "held_slots": 30},
    {"kind": "serve_held", "step": 1, "decoding": 32, "starved": 0,
     "starved_at": None, "held_slots": 0},
    {"kind": "serve_held", "step": 2, "decoding": 32, "starved": 1,
     "starved_at": "serve.sample", "held_slots": 32},
    {"kind": "serve_held", "step": 3, "decoding": 31, "starved": 0,
     "starved_at": None, "held_slots": 0},
    {"kind": "serve_prefill", "step": 0, "rid": 1, "slot": 2,
     "form": "whole", "pos": 0, "tokens": 300, "rows": 512, "pad_rows": 212,
     "host_ms": 1.5, "starved": True},
    {"kind": "serve_prefill", "step": 1, "rid": 2, "slot": 3,
     "form": "chunk", "pos": 0, "tokens": 1024, "rows": 1024, "pad_rows": 0,
     "host_ms": 0.9, "starved": False},
    {"kind": "serve_prefill", "step": 2, "rid": 2, "slot": 3,
     "form": "chunk", "pos": 1024, "tokens": 100, "rows": 1024,
     "pad_rows": 924, "host_ms": 0.9, "starved": False},
    {"kind": "serve_stall", "step": 2, "host_ms": 40.0, "between_ms": 1.0},
]
# the commit before: serve_step records without the new field, no record
# of the new kinds
OLD_RECORDS = [{"kind": "serve_step", "step": i, "active": 32,
                "pages_used": 1024} for i in range(3)] + [
    {"kind": "serve_decode", "step": 0, "slots": 32, "ctx_pages": 64}]


def _reader(metric):
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json")) as f:
        return json.load(f)


def _value(metric, records):
    cell = run.load_cell(NEW[metric])
    ctx = {"trace": None, "records": list(records), "harness": {},
           "end_to_end": {}, "cell": cell.spec, "config": cell.config,
           "peaks": None, "chips": 1, "lib": run.lib}
    reader = _reader(metric)
    return getattr(reducers, reader["reducer"])(ctx, **reader.get("args", {}))


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_is_found_by_name_and_listed_for_its_one_cell(metric):
    reader = _reader(metric)
    assert reader["reducer"] == "record_mean_share" and reader["what"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = [m for m in manifest["per_layer"] if m["name"] == metric]
    assert len(entry) == 1
    assert entry[0] == {
        "name": metric, "unit": "%", "source": "program_counter",
        "layer": "server", "moves": "serve_tokens_per_s",
        "better": "higher" if metric.startswith("prefill_tokens_fill")
        else "lower", "workloads": [NEW[metric]]}
    for cell in CELLS.values():
        listed = {m["name"] for m in run.load_cell(cell).per_layer()}
        assert (metric in listed) == (cell == NEW[metric])
    # the setting it is a share of is in the cell's engine block
    assert reader["args"]["of_engine"] in run.load_cell(
        NEW[metric]).spec["engine"]


def test_the_ten_are_appended_and_nothing_else_moved():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert len(NEW) == 10 and set(NEW) <= set(names)
    assert len(set(names)) == len(names)
    first = min(names.index(n) for n in NEW)
    assert set(names[first:first + 10]) == set(NEW)
    # the backlog cell's engine has no prefill_chunk: no fill metric there
    assert "prefill_chunk" not in run.load_cell(
        CELLS["backlog"]).spec["engine"]


@pytest.mark.parametrize("metric", sorted(NEW))
def test_record_shares_by_hand(metric):
    name, cell = metric.split(".")
    slots = {"backlog": 32, "longctx": 32, "longgen": 64, "shortchat": 128}
    want = {
        # (30 + 0 + 32 + 0) / 4 slot-steps a step, of max_batch
        "host_held_share": 100.0 * 15.5 / slots[cell],
        # (300 + 1024 + 100) / 3 tokens a program, of prefill_chunk 1024
        "prefill_tokens_fill": 100.0 * (1424 / 3) / 1024,
        # (212 + 0 + 924) / 3 rows a program
        "prefill_pad_rows": 100.0 * (1136 / 3) / 1024,
    }[name]
    assert _value(metric, RECORDS) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_finds_nothing_in_what_the_commit_before_records(metric):
    assert _value(metric, OLD_RECORDS) is None
    assert _value(metric, []) is None


def test_the_programs_records_feed_the_readers(tmp_path):
    """The program itself at a toy size (CPU, ``tiny_tree``'s scratch
    tree; the toy backlog cell given a ``prefill_chunk`` and appended to
    the shortchat readers' lists there): every record of the kinds the
    readers mean over has the field, and a traced run's line carries all
    three metrics."""
    sys.path.insert(0, HERE)
    import tiny_tree

    tree = tiny_tree.write_tree(str(tmp_path / "tree"))
    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    mine = [m for m in manifest["per_layer"]
            if NEW.get(m["name"]) == CELLS["shortchat"]]
    assert len(mine) == 3
    for m in mine:
        m["workloads"].append("tiny.serve.backlog")
    with open(path, "w") as f:
        json.dump(manifest, f)
    spec_path = os.path.join(tree, "benchmark", "workloads",
                             "tiny.serve.backlog.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["engine"]["prefill_chunk"] = 32        # two prompt buckets
    with open(spec_path, "w") as f:
        json.dump(spec, f)

    kept = {}
    serve = run._load_module(os.path.join(BENCH, "drivers", "serve.py"),
                             "benchdriver_serve_pr35")
    real = serve._Recorder.record

    def record(self, **rec):
        kept.setdefault(rec["kind"], []).append(rec)
        real(self, **rec)

    load = run._load_module

    def load_patched(path, name):
        mod = load(path, name)
        if hasattr(mod, "_Recorder"):
            mod._Recorder.record = record
        return mod

    run._load_module = load_patched
    try:
        res = run.run_cell("tiny.serve.backlog", 2**31 + 35, 1.5, True,
                           require_tpu=False, root=tree)
    finally:
        run._load_module = load
    assert res["correct"]
    steps, held, pre = (kept[k] for k in ("serve_step", "serve_held",
                                          "serve_prefill"))
    assert len(held) == len(steps) > 20 and len(pre) > 5
    assert all("held_slots" in r for r in steps + held)
    assert [h["held_slots"] for h in held] == [s["held_slots"] for s in steps]
    assert all(p["tokens"] >= 1 and p["pad_rows"] == p["rows"] - p["tokens"]
               for p in pre)
    assert {p["form"] for p in pre} == {"whole", "chunk"}
    got = {m["name"]: res["metrics"][m["name"]]["value"] for m in mine}
    assert 0.0 <= got["host_held_share.shortchat"] <= 100.0
    fill, pad = (got["prefill_tokens_fill.shortchat"],
                 got["prefill_pad_rows.shortchat"])
    # of the TOY's chunk of 32 rows, through the shortchat reader's args
    assert 0.0 < fill <= 100.0 and 0.0 <= pad < 100.0
    assert fill + pad == pytest.approx(
        100.0 * sum(p["rows"] for p in pre) / len(pre) / 32)
