"""Rehearsal of what PR 27 added to the benchmark (CPU):
``python -m pytest benchmark/tests/test_joyai_flash.py -q``.

The cell ``joyai_flash.serve.longctx``, its configuration, its driver
(``drivers/serve_mla.py``: ``drivers/serve.py`` with the cell's own
reference file), the plain reference (``lib/reference_mla.py``) and the
seven reader files are found by name through ``run.load_cell`` and
``run.read_layer_metrics``; the counts of ``lib/counts_mla.py`` against
numbers worked by hand from the published sizes; and one whole run of a
toy cell of the same architecture, in which the float8 control fails the
limit and a token altered where it is produced is not correct.
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
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import tiny_tree  # noqa: E402


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


run = _load(os.path.join(BENCH, "run.py"), "benchrun_pr27")
counts = run.lib("counts_mla")
ref = run.lib("reference_mla")

CELL = "joyai_flash.serve.longctx"
READERS = {
    "engine_step_ms.longctx": ("harness_median", "host_clock", "server"),
    "decode_device_ms.longctx": ("module_ms_per_call", "device_trace",
                                 "model step"),
    "prefill_device_share.longctx": ("module_share_of_busy", "device_trace",
                                     "model step"),
    "batch_occupancy.longctx": ("record_mean_share", "program_counter",
                                "server"),
    "kv_pool_occupancy.longctx": ("record_mean_share", "program_counter",
                                  "server"),
    "decode_ctx_gathered.longctx": ("record_mean_share", "program_counter",
                                    "server"),
    "decode_ctx_idle.longctx": ("record_mean_share", "program_counter",
                                "server"),
}


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


@pytest.fixture(scope="module")
def dims(cell):
    return ref.model_dims(cell.config)


# ------------------------------------------------- found by name, as data

def test_cell_config_and_driver_are_found_by_name(cell):
    assert cell.chips == 1 and cell.entry["config"] == "joyai_flash"
    assert cell.spec["driver"] == "serve_mla"
    assert cell.spec["reference"] == "reference_mla"
    assert [m["name"] for m in cell.end_to_end()] == \
        ["serve_tokens_per_s", "setup_s"]
    for name in ("serve_mla", "serve"):
        assert os.path.exists(os.path.join(BENCH, "drivers", name + ".py"))
    driver = _load(os.path.join(BENCH, "drivers", "serve_mla.py"),
                   "benchdriver_serve_mla_t")
    assert all(callable(getattr(driver, f))
               for f in ("build", "measure", "check", "close"))


def test_traffic_and_engine_are_the_issues(cell):
    t, e = cell.spec["traffic"], cell.spec["engine"]
    assert t["arrivals"] == {"kind": "backlog"} and t["queue_floor"] == 64
    assert t["prompt_len"] == {"dist": "lognormal", "median": 2048,
                               "sigma": 0.8, "min": 256, "max": 6144}
    assert t["output_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.7, "min": 32, "max": 1024}
    assert (t["block"], t["strata"], t["ramp_steps"],
            t["ramp_population"]) == (32, 8, 4, 32)
    assert (e["max_batch"], e["page_size"], e["max_pages_per_slot"],
            e["num_pages"]) == (32, 16, 448, 16384)
    # every slot can reach its longest context: no eviction
    assert e["max_batch"] * e["max_pages_per_slot"] <= e["num_pages"] - 1 \
        or e["max_batch"] * e["max_pages_per_slot"] == e["num_pages"] - 2048
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert longest == e["max_pages_per_slot"] * e["page_size"]
    assert e["prefill_chunk"] % e["page_size"] == 0
    assert set(cell.spec["check"]["limits"]) == {"served_gap_mean",
                                                 "served_gap_widest"}
    assert cell.spec["check"]["streams"] == 4


def test_configuration_keeps_every_published_number(cell):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "JoyAI-LLM-Flash")
    conf = cell.config
    assert conf["source"] == row["source_url"]
    assert conf["reduced"] == ["num_hidden_layers"]
    # the published keys lie at the top level of the file, under their
    # own names, beside what the file says about the cut
    changed = {k for k, v in row["config"].items()
               if conf.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"}
    assert conf["num_hidden_layers"] == 5
    assert "num_nextn_predict_layers" in conf["not_run"]
    assert {"weights", "e_score_correction_bias"} <= set(conf["assumed"])
    assert conf["program"]["preset"] == "joyai-llm-flash"
    assert conf["served"]["param_dtype"] == "bfloat16"


def test_program_config_is_the_cut_preset(cell):
    import jax.numpy as jnp

    cfg = run.Run(cell, 1, 1.0, False, False, "").program_config()
    assert (cfg.num_layers, cfg.first_k_dense, cfg.moe_layer_indices) == \
        (5, 1, (1, 2, 3, 4))
    assert cfg.param_dtype == jnp.bfloat16 and cfg.attention_kind == "mla"
    assert cfg.kv_token_bytes == 5760


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_found_by_name_and_listed_for_the_cell_alone(cell, metric):
    reducer, source, layer = READERS[metric]
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json")) as f:
        reader = json.load(f)
    assert reader["reducer"] == reducer and reader["what"]
    assert callable(getattr(run.lib("reducers"), reducer))
    entry = next(m for m in cell.per_layer() if m["name"] == metric)
    assert entry["workloads"] == [CELL] and entry["source"] == source
    assert entry["layer"] == layer and entry["moves"] == "serve_tokens_per_s"
    for other in ("dsmoe16b.serve.backlog", "fmref.train.4k"):
        assert metric not in {m["name"]
                              for m in run.load_cell(other).per_layer()}


def test_new_entries_are_appended_and_nothing_else_moved():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert [p["name"] for p in m["per_layer"]][-7:] == list(READERS)
    assert m["workloads"][-1]["name"] == CELL
    assert m["configs"][-1]["name"] == "joyai_flash"
    rate = next(e for e in m["end_to_end"]
                if e["name"] == "serve_tokens_per_s")
    assert rate["workloads"] == ["dsmoe16b.serve.backlog", CELL]
    assert rate["bound"] == 0.05 and m["run_seconds"] == 50


MS = 1_000_000


def test_readers_read_records_and_a_trace_by_hand(cell):
    """All seven through ``read_layer_metrics``; on what the parent gives
    (no records, no trace) each finds nothing and nothing raises."""
    records = [
        {"kind": "serve_step", "active": 32, "pages_used": 8192},
        {"kind": "serve_decode", "ctx_pages": 448, "ctx_pages_idle": 224.0},
        {"kind": "serve_step", "active": 16, "pages_used": 4096},
        {"kind": "serve_decode", "ctx_pages": 224, "ctx_pages_idle": 112.0},
    ]
    mods = [("jit__paged_decode_step(1)", 0, 30 * MS),
            ("jit__paged_decode_step(1)", 40 * MS, 50 * MS),
            ("jit__prefill_chunk(2)", 100 * MS, 15 * MS),
            ("jit__prefill_padded(3)", 120 * MS, 5 * MS),
            ("jit__sample_dynamic(4)", 130 * MS, 10 * MS)]
    dev = {"ops": [], "modules": mods, "t0": 0, "t1": 200 * MS}
    ctx = {"trace": {"per_device": {"/device:TPU:0": dev}, "busy_s": 0.1},
           "records": records, "harness": {"engine_step_ms": [50.0, 70.0,
                                                              90.0]},
           "end_to_end": {}, "cell": cell.spec, "config": cell.config,
           "peaks": None, "chips": 1, "lib": run.lib}
    got = {k: v["value"] for k, v in run.read_layer_metrics(cell, ctx).items()}
    assert got == pytest.approx({
        "engine_step_ms.longctx": 70.0, "decode_device_ms.longctx": 40.0,
        "prefill_device_share.longctx": 20.0,
        "batch_occupancy.longctx": 75.0,
        "kv_pool_occupancy.longctx": 100.0 * 6144 / 16384,
        "decode_ctx_gathered.longctx": 75.0,
        "decode_ctx_idle.longctx": 37.5})
    empty = dict(ctx, trace=None, records=[], harness={})
    assert run.read_layer_metrics(cell, empty) == {}


# ------------------------------------------------ counts, worked by hand

def test_parameter_counts_by_hand(dims):
    mla = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
           + 4096 * 2048)
    assert counts.mla_params(dims) == mla == 26_345_472
    assert counts.layer_params(dims, 0) == mla + 3 * 2048 * 7168 \
        == 70_385_664
    mixture = mla + 2048 * 256 + 257 * 3 * 2048 * 768
    assert counts.layer_params(dims, 3) == mixture == 1_239_547_904
    assert counts.model_params(dims) == 70_385_664 + 4 * mixture \
        + 2 * 129280 * 2048 == 5_558_108_160             # 11.12 GB in bf16
    assert counts.latent_token_bytes(dims) == 5 * 576 * 2 == 5760


def test_decode_bytes_and_flops_by_hand(dims):
    touch = 1 - (1 - 8 / 256) ** 32
    assert counts.expected_expert_touch(dims, 32) == pytest.approx(touch)
    assert 0.637 < touch < 0.639
    routed = 4 * 256 * 3 * 2048 * 768
    weights = 5_558_108_160 - 129280 * 2048 - routed * (1 - touch)
    ctx = 32 * 2800
    assert counts.decode_step_bytes(dims, 32, ctx) == pytest.approx(
        2 * weights + 5760 * ctx)
    # a lone slot with no context reads the weights with 8 experts a layer
    lone = counts.decode_step_bytes(dims, 1, 0)
    assert lone == pytest.approx(2 * (
        5_558_108_160 - 129280 * 2048 - routed * (1 - 8 / 256)))
    # seven times fewer cache bytes than lib/counts.py's K/V of 32 heads
    # of 192 and 128 would be
    assert (32 * (192 + 128)) / 576 > 17 and 2 * 32 * 128 / 576 > 14
    flops = counts.absorbed_attention_flops(dims, 32, ctx)
    per_slot = 2 * 32 * 512 * (128 + 128)
    per_ctx = 2 * 32 * (512 + 64 + 512)
    assert flops == pytest.approx(5 * (32 * per_slot + ctx * per_ctx))
    assert flops < 0.1e12                   # the issue's "under 0.1 TFLOP"


# ------------------------------------------- one whole run at a toy size

TINY_MODEL = {
    "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "kv_lora_rank": 16,
    "moe_intermediate_size": 64, "n_group": 1, "n_routed_experts": 8,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "q_lora_rank": 32, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-06, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "v_head_dim": 8, "vocab_size": 512,
}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tiny_tree.write_tree(str(tmp_path_factory.mktemp("tree27")))

    def put(rel, obj):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)

    put("configs/tinymla.json", {
        "name": "tinymla", "source": "toy sizes for CPU rehearsal",
        "model": TINY_MODEL, "reduced": [],
        "served": {"param_dtype": "bfloat16"},
        "program": {"preset": "joyai-llm-flash", "overrides": {
            "num_layers": 3, "hidden_size": 64, "intermediate_size": 64,
            "dense_intermediate_size": 128, "num_experts": 8,
            "expert_top_k": 2, "num_heads": 4, "q_lora_rank": 32,
            "kv_lora_rank": 16, "qk_nope_head_dim": 8,
            "qk_rope_head_dim": 8, "v_head_dim": 8, "vocab_size": 512,
            "param_dtype": "bfloat16"}}})
    put("workloads/tinymla.serve.json", {
        "name": "tinymla.serve", "config": "tinymla", "driver": "serve_mla",
        "reference": "reference_mla", "chips": 1,
        "engine": {"max_batch": 4, "page_size": 8, "num_pages": 64,
                   "max_pages_per_slot": 12, "ctx_bucket_pages": 4,
                   "prompt_bucket": 16, "prefill_chunk": 16,
                   "max_steps": 100000000},
        "check": {"streams": 6, "control": "fp8",
                  "limits": {"served_gap_widest": 0.5,
                             "served_gap_mean": 0.006}},
        "traffic": {"prompt_len": {"dist": "lognormal", "median": 16,
                                   "sigma": 0.8, "min": 4, "max": 64},
                    "output_len": {"dist": "lognormal", "median": 8,
                                   "sigma": 0.5, "min": 2, "max": 16},
                    "block": 16, "arrivals": {"kind": "backlog"},
                    "queue_floor": 8, "ramp_steps": 3,
                    "ramp_population": 4}})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tinymla", "source": "toy", "reduced": [], "why": "toy",
        "file": "benchmark/configs/tinymla.json"})
    manifest["workloads"].append({
        "name": "tinymla.serve", "config": "tinymla", "traffic": "serve",
        "chips": 1, "why": "toy"})
    for m in manifest["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("tinymla.serve")
    for m in manifest["per_layer"]:
        if m["name"].endswith(".longctx"):
            m["workloads"].append("tinymla.serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _drive(tree, trace=False, control=False):
    return run.run_cell("tinymla.serve", 2**31 + 27, 1.5, trace,
                        control=control, require_tpu=False, root=tree)


def test_toy_cell_runs_and_its_control_fails(tree, capsys):
    res = _drive(tree, control=True)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    limit = next(s["limit"] for s in said
                 if s.get("compared") == "served_gap_mean")
    control_gap = next(s for s in said if "check" in s)[
        "check"]["control"]["served_gap_mean"]
    assert control_gap > limit      # the precision below is not correct


def test_toy_traced_run_reports_the_program_counter_readers(tree):
    res = _drive(tree, trace=True)
    assert {"engine_step_ms.longctx", "batch_occupancy.longctx",
            "kv_pool_occupancy.longctx", "decode_ctx_gathered.longctx",
            "decode_ctx_idle.longctx"} <= set(res["metrics"])
    assert "serve_tokens_per_s" not in res["metrics"]


def test_a_token_altered_where_it_is_produced_is_not_correct(tree,
                                                             monkeypatch):
    from flashmoe_tpu.serving import engine as eng

    real = eng._sample_dynamic
    monkeypatch.setattr(
        eng, "_sample_dynamic",
        lambda logits, *rest: (real(logits, *rest) + 1) % logits.shape[-1])
    assert _drive(tree)["correct"] is False
