"""Rehearsal of what PR 31 added to the benchmark (CPU):
``python -m pytest benchmark/tests/test_ling3_flash.py -q``.

The cell ``ling3_flash.serve.longgen``, its configuration, its driver
(``drivers/serve_state.py``: ``serve_mla.py``'s run and one more comparison,
the slots' recurrent state), the plain
reference (``lib/reference_ling3.py``) and the eight reader files are found
by name through ``run.load_cell`` and ``run.read_layer_metrics``; the
counts of ``lib/counts_ling3.py`` against numbers worked by hand from the
published sizes; and one whole run of a toy cell of the same architecture,
in which the float8 control fails the limit.
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


run = _load(os.path.join(BENCH, "run.py"), "benchrun_pr31")
counts = run.lib("counts_ling3")
ref = run.lib("reference_ling3")

CELL = "ling3_flash.serve.longgen"
READERS = {
    "engine_step_ms.longgen": ("harness_median", "host_clock", "server"),
    "batch_occupancy.longgen": ("record_mean_share", "program_counter",
                                "server"),
    "kv_pool_occupancy.longgen": ("record_mean_share", "program_counter",
                                  "server"),
    "decode_device_ms.longgen": ("module_ms_per_call", "device_trace",
                                 "model step"),
    "prefill_device_share.longgen": ("module_share_of_busy", "device_trace",
                                     "model step"),
    "held_rows_share.longgen": ("record_mean_share", "program_counter",
                                "model step"),
    "decode_ctx_gathered.longgen": ("record_mean_share", "program_counter",
                                    "server"),
    "decode_ctx_idle.longgen": ("record_mean_share", "program_counter",
                                "server"),
}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


@pytest.fixture(scope="module")
def dims(cell):
    return ref.model_dims(cell.config)


# ------------------------------------------------- found by name, as data

def test_cell_config_and_driver_are_found_by_name(cell):
    assert cell.chips == 1 and cell.entry["config"] == "ling3_flash"
    assert cell.spec["driver"] == "serve_state"
    assert cell.spec["reference"] == "reference_ling3"
    assert [m["name"] for m in cell.end_to_end()] == \
        ["serve_tokens_per_s", "setup_s"]
    assert os.path.exists(os.path.join(BENCH, "lib", "reference_ling3.py"))


def test_traffic_and_engine_are_the_issues(cell):
    t, e = cell.spec["traffic"], cell.spec["engine"]
    assert t["arrivals"] == {"kind": "backlog"} and t["queue_floor"] == 128
    assert t["prompt_len"] == {"dist": "lognormal", "median": 1024,
                               "sigma": 1.0, "min": 128, "max": 8192}
    assert t["output_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.7, "min": 64, "max": 2048}
    assert (t["block"], t["strata"], t["ramp_population"]) == (64, 8, 64)
    assert (e["max_batch"], e["page_size"], e["max_pages_per_slot"],
            e["num_pages"], e["prefill_chunk"]) == (64, 16, 640, 40960, 1024)
    # every slot can reach its longest context: no eviction
    assert e["max_batch"] * e["max_pages_per_slot"] == e["num_pages"]
    means = run.lib("traffic").mix_means(t)
    assert means["prompt_max"] + means["output_max"] \
        == e["max_pages_per_slot"] * e["page_size"]
    assert 1400 < means["prompt_mean"] < 1700
    assert 580 < means["output_mean"] < 660
    check = cell.spec["check"]
    assert set(check["limits"]) == {"served_gap_mean", "served_gap_widest",
                                    "state_gap"}
    assert set(check["limits"]) <= set(check["limits_why"])
    assert check["streams"] == 4 and check["state_streams"] == 8
    # the state's control is the precision below the one the file states
    assert cell.config["assumed"]["state_dtype"].startswith("float32")
    assert check["state_control"] == "bfloat16"


def test_configuration_keeps_every_published_number(cell):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Ling-3.0-flash-VL")
    conf = cell.config
    assert conf["source"] == row["source_url"]
    assert conf["reduced"] == REDUCED
    changed = {k for k, v in row["config"].items()
               if conf.get(k, "absent") != v}
    assert changed == set(REDUCED)
    assert {k: conf[k] for k in REDUCED} == {
        "num_hidden_layers": 7, "first_k_dense_replace": 1,
        "num_experts": 128, "vocab_size": 39296}
    assert conf["published"] == {k: row["config"][k] for k in REDUCED}
    assert conf["layer_kinds"] == ["kda"] * 6 + ["mla"]
    assert {"vision_tower", "multi_token_prediction",
            "expert_swiglu_limit_list"} <= set(conf["not_run"])
    assert {"layer_pattern", "kda_gate", "mla_qk_norm", "weights",
            "expert_bias", "kda_A_and_b", "state_dtype"} \
        <= set(conf["assumed"])
    # the SwiGLU limits are off in every layer the cut keeps
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert [conf[key][li] for li in (0, 6, 7, 8, 9, 10, 11)] == [0] * 7


def test_program_config_is_the_cut_preset(cell):
    import jax.numpy as jnp

    cfg = run.Run(cell, 1, 1.0, False, False, "").program_config()
    assert (cfg.num_layers, cfg.first_k_dense, cfg.moe_layer_indices) == \
        (7, 1, (1, 2, 3, 4, 5, 6))
    assert cfg.mixers == ("kda",) * 6 + ("mla",)
    assert cfg.param_dtype == jnp.bfloat16 and cfg.q_lora_rank == 0
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_first,
            cfg.n_group, cfg.topk_group, cfg.expert_top_k) == (
        512, 128, 0, 8, 4, 8)
    assert cfg.kv_token_bytes == 1152 and cfg.vocab_size == 39296
    assert cfg.state_slot_bytes == 6 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)


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
    for other in ("dsmoe16b.serve.backlog", "joyai_flash.serve.longctx",
                  "fmref.train.4k"):
        assert metric not in {m["name"]
                              for m in run.load_cell(other).per_layer()}


def test_new_entries_are_in_the_manifest_and_the_old_ones_as_they_were():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    names = [p["name"] for p in m["per_layer"]]
    assert set(READERS) <= set(names) and len(set(names)) == len(names)
    assert CELL in [w["name"] for w in m["workloads"]]
    rate = next(e for e in m["end_to_end"]
                if e["name"] == "serve_tokens_per_s")
    assert CELL in rate["workloads"] and rate["bound"] == 0.05
    assert m["run_seconds"] == 50
    assert all(w["chips"] == 1 for w in m["workloads"])


MS = 1_000_000


def test_readers_read_records_and_a_trace_by_hand(cell):
    """All eight through ``read_layer_metrics``; with no records and no
    trace each finds nothing and nothing raises."""
    records = [
        {"kind": "serve_step", "active": 64, "pages_used": 8192},
        {"kind": "serve_decode", "held_rows": 140.0, "ctx_pages": 640,
         "ctx_pages_idle": 500.0},
        {"kind": "serve_step", "active": 32, "pages_used": 4096},
        {"kind": "serve_decode", "held_rows": 116.0, "ctx_pages": 512,
         "ctx_pages_idle": 396.0},
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
        "engine_step_ms.longgen": 70.0, "decode_device_ms.longgen": 40.0,
        "prefill_device_share.longgen": 20.0,
        "batch_occupancy.longgen": 75.0,
        "kv_pool_occupancy.longgen": 100.0 * 6144 / 40960,
        "held_rows_share.longgen": 200.0,
        "decode_ctx_gathered.longgen": 90.0,
        "decode_ctx_idle.longgen": 70.0})
    empty = dict(ctx, trace=None, records=[], harness={})
    assert run.read_layer_metrics(cell, empty) == {}


# ------------------------------------------------ counts, worked by hand

def test_parameter_counts_by_hand(dims):
    kda = (3 * 2560 * 4096 + 2560 * 4096 + 2 * 2560 * 32 + 4 * 12288
           + 4096 * 2560)
    assert counts.kda_params(dims) == kda == 52_641_792
    mla = 2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560
    assert counts.mla_params(dims) == mla == 31_883_264
    assert counts.expert_params(dims) == 5_898_240
    assert counts.layer_params(dims, 0) == kda + 3 * 2560 * 6144 \
        == 99_827_712
    mixture = 2560 * 512 + 129 * 5_898_240
    assert counts.layer_params(dims, 3) == kda + mixture == 814_825_472
    assert counts.layer_params(dims, 6) == mla + mixture == 794_066_944
    assert counts.model_params(dims) == 99_827_712 + 5 * 814_825_472 \
        + 794_066_944 + 2 * 39296 * 2560 == 5_169_217_536   # 10.34 GB bf16
    assert counts.latent_token_bytes(dims) == 1152
    assert counts.state_slot_bytes(dims) == 6 * (32 * 128 * 128 * 4
                                                 + 3 * 12288 * 2)
    assert 64 * counts.state_slot_bytes(dims) == pytest.approx(0.8336e9,
                                                               rel=1e-3)


def test_decode_bytes_and_chunk_flops_by_hand(dims):
    assert counts.expected_held_rows(dims, 64) == 128.0
    touch = 1 - (127 / 128) ** 128
    assert counts.expected_expert_touch(dims, 128) == pytest.approx(touch)
    assert 80 < 128 * touch < 82            # the issue's "81 of 128"
    routed = 6 * 128 * 5_898_240
    weights = 5_169_217_536 - 39296 * 2560 - routed * (1 - touch)
    ctx = 64 * 1800
    want = 2 * weights + 1152 * ctx + 2 * 64 * counts.state_slot_bytes(dims)
    assert counts.decode_step_bytes(dims, 64, ctx, 64) == pytest.approx(want)
    assert 8.4e9 < want < 8.8e9             # about 10.5 ms at 819 GB/s
    # the state is a fifth of the step's bytes, the latent rows a sixtieth
    assert 0.18 < 2 * 64 * counts.state_slot_bytes(dims) / want < 0.21
    # a step that measured its held rows passes them
    assert counts.decode_step_bytes(dims, 64, ctx, 64, held_rows=128.0) \
        == pytest.approx(want)
    per_chunk = 64 * 64 * 128 * (4 + 2 + 2) + 6 * 64 * 128 * 128
    assert counts.kda_chunk_flops(dims, 1024) == pytest.approx(
        6 * 32 * 16 * per_chunk)
    assert counts.kda_chunk_flops(dims, 1024) < 0.04e12
    assert counts.kda_chunk_bytes(dims, 1024) == pytest.approx(
        6 * 1024 * 4096 * 4 * 5 + 2 * counts.state_slot_bytes(dims))


def test_the_fitted_bias_balances_the_load():
    """``make_params`` fits the selection bias by the checkpoint's rule:
    on tokens it was not fitted on, the experts' loads lie closer together
    than with no bias, in every mixture layer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    d = ref.model_dims({"model": TINY_MODEL, "layer_kinds": KINDS,
                        "published": {"num_experts": 16},
                        "held": {"expert_first": 4},
                        "served": {"param_dtype": "float32"}})
    params = ref.make_params(31, d)
    toks = jax.random.randint(jax.random.PRNGKey(5), (512,), 1, 512)
    x = params["embed"][toks].astype(jnp.float32)
    for layer, kind in zip(params["layers"], KINDS):
        x, h = ref._ffn_input(layer, x, ref._dims_key(d), kind)
        if "gate_bias" in layer["moe"]:
            bias = layer["moe"]["gate_bias"]
            assert float(jnp.abs(bias).max()) > 0.01
            spread = []
            for b in (bias, 0 * bias):
                idx = ref.chosen_experts(
                    ref.router_scores(h, layer["moe"]["gate_w"]), b, d)
                load = np.bincount(np.asarray(idx).ravel(), minlength=16)
                spread.append(load.std() / load.mean())
            assert spread[0] < 0.7 * spread[1], spread
        x = x + ref.ffn(layer["moe"], h, d)


# ------------------------------------------- one whole run at a toy size

TINY_MODEL = {
    "first_k_dense_replace": 1, "head_dim": 16, "hidden_size": 64,
    "intermediate_size": 128, "kda_lower_bound": -5, "kv_lora_rank": 16,
    "moe_intermediate_size": 64, "n_group": 4, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 3, "q_lora_rank": None, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-06, "rope_theta": 6000000,
    "routed_scaling_factor": 2.5, "score_function": "sigmoid",
    "short_conv_kernel_size": 4, "topk_group": 2, "v_head_dim": 8,
    "vocab_size": 512,
}
KINDS = ["kda", "kda", "mla"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tiny_tree.write_tree(str(tmp_path_factory.mktemp("tree31")))

    def put(rel, obj):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)

    put("configs/tinyling.json", {
        "name": "tinyling", "source": "toy sizes for CPU rehearsal",
        "model": TINY_MODEL, "reduced": [], "layer_kinds": KINDS,
        "published": {"num_experts": 16}, "held": {"expert_first": 4},
        "served": {"param_dtype": "bfloat16"},
        "program": {"preset": "ling-3.0-flash", "overrides": {
            "num_layers": 3, "first_k_dense": 1, "layer_mixers": KINDS,
            "hidden_size": 64, "intermediate_size": 64,
            "dense_intermediate_size": 128, "num_experts": 16,
            "expert_top_k": 3, "n_group": 4, "topk_group": 2,
            "expert_first": 4, "experts_held": 4, "num_heads": 4,
            "kda_heads": 4, "kda_head_dim": 16, "kv_lora_rank": 16,
            "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
            "vocab_size": 512, "param_dtype": "bfloat16"}}})
    put("workloads/tinyling.serve.json", {
        "name": "tinyling.serve", "config": "tinyling",
        "driver": "serve_state", "reference": "reference_ling3", "chips": 1,
        "engine": {"max_batch": 4, "page_size": 8, "num_pages": 64,
                   "max_pages_per_slot": 12, "ctx_bucket_pages": 4,
                   "prompt_bucket": 16, "prefill_chunk": 16,
                   "max_steps": 100000000},
        "check": {"streams": 6, "control": "fp8", "state_streams": 3,
                  "state_control": "bfloat16",
                  "limits": {"served_gap_widest": 0.5,
                             "served_gap_mean": 0.006, "state_gap": 0.02}},
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
        "name": "tinyling", "source": "toy", "reduced": [], "why": "toy",
        "file": "benchmark/configs/tinyling.json"})
    manifest["workloads"].append({
        "name": "tinyling.serve", "config": "tinyling", "traffic": "serve",
        "chips": 1, "why": "toy"})
    for m in manifest["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("tinyling.serve")
    for m in manifest["per_layer"]:
        if m["name"].endswith(".longgen"):
            m["workloads"].append("tinyling.serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _drive(tree, trace=False, control=False):
    return run.run_cell("tinyling.serve", 2**31 + 31, 1.5, trace,
                        control=control, require_tpu=False, root=tree)


def test_toy_cell_runs_and_reads_its_control(tree, capsys):
    res = _drive(tree, control=True)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    gap = next(s["value"] for s in said
               if s.get("compared") == "served_gap_mean")
    control_gap = next(s for s in said if "check" in s)[
        "check"]["control"]["served_gap_mean"]
    assert 0 <= gap < control_gap   # the precision below lies further off
    # the slots in flight against the reference's recurrence (at these
    # sizes and a dozen decode steps a rounded state reads no further off:
    # tests/test_ling3.py holds the comparison itself at float32)
    state = next(s for s in said if s.get("compared") == "state_gap")
    assert state["ok"] and 0 < state["value"] <= state["limit"]
    notes = next(s for s in said if "check" in s)
    # as many slots as were decoding when the window ended, at most 3
    assert 1 <= len(notes["check"]["state"]["first_layer"]) <= 3
    assert len(notes["check"]["state"]["layers_of_first"]) == 2
    assert notes["check"]["control"]["state_gap"] > 0
    slowest = notes["notes"]["slowest_step"]
    assert slowest["step_ms"] > 0 and "serve.decode" in slowest["phase_ms"]


def test_toy_traced_run_reports_the_program_counter_readers(tree):
    res = _drive(tree, trace=True)
    assert {"engine_step_ms.longgen", "batch_occupancy.longgen",
            "kv_pool_occupancy.longgen", "held_rows_share.longgen",
            "decode_ctx_gathered.longgen", "decode_ctx_idle.longgen"} \
        <= set(res["metrics"])
    assert 0 < res["metrics"]["held_rows_share.longgen"]["value"] <= 300
    assert "serve_tokens_per_s" not in res["metrics"]
