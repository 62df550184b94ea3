"""Rehearsal of what PR 33 added to the benchmark (CPU):
``python -m pytest benchmark/tests/test_lfm2_24b.py -q``.

The cell ``lfm2_24b.serve.shortchat``, its configuration, its driver
(``drivers/serve_mla.py`` with the cell's own reference), the plain
reference (``lib/reference_lfm2.py``) and the eight reader files are found
by name through ``run.load_cell`` and ``run.read_layer_metrics``; the
counts of ``lib/counts_lfm2.py`` against numbers worked by hand from the
published sizes; and one whole run of a toy cell of the same architecture,
which is ``correct`` and whose float8 control is not.
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


run = _load(os.path.join(BENCH, "run.py"), "benchrun_pr33")
counts = run.lib("counts_lfm2")
ref = run.lib("reference_lfm2")

CELL = "lfm2_24b.serve.shortchat"
READERS = {
    "engine_step_ms.shortchat": ("harness_median", "host_clock", "server"),
    "batch_occupancy.shortchat": ("record_mean_share", "program_counter",
                                  "server"),
    "kv_pool_occupancy.shortchat": ("record_mean_share", "program_counter",
                                    "server"),
    "decode_device_ms.shortchat": ("module_ms_per_call", "device_trace",
                                   "model step"),
    "prefill_device_share.shortchat": ("module_share_of_busy",
                                       "device_trace", "model step"),
    "experts_touched_share.shortchat": ("record_mean_share",
                                        "program_counter", "model step"),
    "decode_ctx_gathered.shortchat": ("record_mean_share",
                                      "program_counter", "server"),
    "decode_ctx_idle.shortchat": ("record_mean_share", "program_counter",
                                  "server"),
}
REDUCED = ["num_hidden_layers", "num_dense_layers"]
KINDS = ["conv", "full_attention", "conv", "conv", "conv", "full_attention",
         "conv", "conv", "conv"]


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


@pytest.fixture(scope="module")
def dims(cell):
    return ref.model_dims(cell.config)


# ------------------------------------------------- found by name, as data

def test_cell_config_and_driver_are_found_by_name(cell):
    assert cell.chips == 1 and cell.entry["config"] == "lfm2_24b"
    assert cell.spec["driver"] == "serve_mla"
    assert cell.spec["reference"] == "reference_lfm2"
    assert [m["name"] for m in cell.end_to_end()] == \
        ["serve_tokens_per_s", "setup_s"]
    assert os.path.exists(os.path.join(BENCH, "lib", "reference_lfm2.py"))
    assert len(cell.entry["why"]) <= 200


def test_traffic_and_engine_are_the_issues(cell):
    t, e = cell.spec["traffic"], cell.spec["engine"]
    assert t["arrivals"] == {"kind": "backlog"} and t["queue_floor"] == 256
    assert t["prompt_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 1.0, "min": 64, "max": 4096}
    assert t["output_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.7, "min": 32, "max": 1024}
    assert (t["block"], t["strata"], t["ramp_population"]) == (128, 8, 128)
    assert (e["max_batch"], e["page_size"], e["max_pages_per_slot"],
            e["num_pages"], e["prefill_chunk"], e["prompt_bucket"]) == (
        128, 16, 320, 20480, 1024, 256)
    means = run.lib("traffic").mix_means(t)
    assert means["prompt_max"] + means["output_max"] \
        == e["max_pages_per_slot"] * e["page_size"]
    assert 700 < means["prompt_mean"] < 950
    assert 290 < means["output_mean"] < 350
    # the slots' mean context fills a third of the pool or less: eviction
    # stays bypassed
    live = e["max_batch"] * (means["prompt_mean"] + means["output_mean"] / 2)
    assert live < 0.4 * e["num_pages"] * e["page_size"]
    check = cell.spec["check"]
    assert set(check["limits"]) == {"served_gap_mean", "served_gap_widest"}
    assert set(check["limits"]) <= set(check["limits_why"])
    assert check["streams"] == 4 and check["control"] == "fp8"
    assert {"ctx_bucket_pages", "fixed_by_ISSUE_33"} <= set(
        cell.spec["engine_why"])


def test_configuration_keeps_every_published_number(cell):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "LFM2-24B-A2B")
    conf = cell.config
    assert conf["source"] == row["source_url"]
    assert conf["reduced"] == REDUCED
    changed = {k for k, v in row["config"].items()
               if conf.get(k, "absent") != v}
    assert changed == set(REDUCED)
    assert {k: conf[k] for k in REDUCED} == {
        "num_hidden_layers": 9, "num_dense_layers": 1}
    assert conf["published"] == {k: row["config"][k] for k in REDUCED}
    # 64 experts and the whole vocabulary are held
    assert conf["num_experts"] == 64 and conf["vocab_size"] == 65536
    # layers 0 and 2-9 of the published pattern
    assert conf["layer_kinds"] == KINDS == [
        conf["layer_types"][li] for li in (0, 2, 3, 4, 5, 6, 7, 8, 9)]
    assert {"tied_embeddings", "head_dim", "dense_width", "weights",
            "expert_bias", "carried_inputs_dtype"} <= set(conf["assumed"])
    assert conf["deployment"].startswith("the first of five")
    assert conf["memory_analysis"]["copies_of_state_or_pool"] == 0


def test_program_config_is_the_cut_preset(cell):
    import jax.numpy as jnp

    cfg = run.Run(cell, 1, 1.0, False, False, "").program_config()
    assert (cfg.num_layers, cfg.first_k_dense, cfg.moe_layer_indices) == \
        (9, 1, tuple(range(1, 9)))
    assert cfg.mixers == tuple("conv" if k == "conv" else "mha"
                               for k in KINDS)
    assert cfg.param_dtype == jnp.bfloat16 and cfg.qk_norm
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_top_k,
            cfg.num_shared_experts, cfg.router_score, cfg.router_bias) == (
        64, 0, 4, 0, "sigmoid", True)
    assert (cfg.num_heads, cfg.resolved_num_kv_heads,
            cfg.resolved_head_dim, cfg.conv_taps) == (32, 8, 64, 3)
    assert cfg.kv_token_bytes == 4096 and cfg.vocab_size == 65536
    assert cfg.kv_pool_rows == (2, 4, 128)
    assert cfg.state_slot_bytes == 7 * 2 * 2048 * 2     # 57 kB a slot
    assert cfg.norm_eps == 1e-5 and cfg.rope_theta == 1e6


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
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for other in ("dsmoe16b.serve.backlog", "joyai_flash.serve.longctx",
                  "ling3_flash.serve.longgen", "fmref.train.4k"):
        assert metric not in {m["name"]
                              for m in run.load_cell(other).per_layer()}


def test_new_entries_are_appended_and_the_old_ones_as_they_were():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    names = [p["name"] for p in m["per_layer"]]
    assert names[-8:] == [
        "engine_step_ms.shortchat", "batch_occupancy.shortchat",
        "kv_pool_occupancy.shortchat", "decode_device_ms.shortchat",
        "prefill_device_share.shortchat", "decode_ctx_gathered.shortchat",
        "decode_ctx_idle.shortchat", "experts_touched_share.shortchat"]
    assert len(set(names)) == len(names)
    assert m["workloads"][-1]["name"] == CELL
    assert m["configs"][-1]["name"] == "lfm2_24b"
    assert m["configs"][-1]["reduced"] == REDUCED
    rate = next(e for e in m["end_to_end"]
                if e["name"] == "serve_tokens_per_s")
    assert rate["workloads"][-1] == CELL and rate["bound"] == 0.05
    assert m["run_seconds"] == 50
    assert all(w["chips"] == 1 for w in m["workloads"])


MS = 1_000_000


def test_readers_read_records_and_a_trace_by_hand(cell):
    """All eight through ``read_layer_metrics``; with no records and no
    trace each finds nothing and nothing raises."""
    records = [
        {"kind": "serve_step", "active": 128, "pages_used": 8192},
        {"kind": "serve_decode", "experts_touched": 64.0, "ctx_pages": 80,
         "ctx_pages_idle": 6.0},
        {"kind": "serve_step", "active": 64, "pages_used": 4096},
        {"kind": "serve_decode", "experts_touched": 60.0, "ctx_pages": 48,
         "ctx_pages_idle": 10.0},
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
        "engine_step_ms.shortchat": 70.0, "decode_device_ms.shortchat": 40.0,
        "prefill_device_share.shortchat": 20.0,
        "batch_occupancy.shortchat": 75.0,
        "kv_pool_occupancy.shortchat": 100.0 * 6144 / 20480,
        # 62 of 64 experts over 128 slots: 50 % reads "every expert"
        "experts_touched_share.shortchat": 100.0 * 62 / 128,
        "decode_ctx_gathered.shortchat": 20.0,
        "decode_ctx_idle.shortchat": 2.5})
    empty = dict(ctx, trace=None, records=[], harness={})
    assert run.read_layer_metrics(cell, empty) == {}


# ------------------------------------------------ counts, worked by hand

def test_parameter_counts_by_hand(dims):
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    assert counts.conv_params(dims) == conv == 16_783_360
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert counts.attn_params(dims) == attn == 10_485_888
    assert counts.expert_params(dims) == 3 * 2048 * 1536 == 9_437_184
    assert counts.layer_params(dims, 0) == conv + 3 * 2048 * 11776 \
        == 89_135_104
    mixture = 2048 * 64 + 64 * 9_437_184
    assert counts.layer_params(dims, 1) == attn + mixture == 614_596_736
    assert counts.layer_params(dims, 2) == conv + mixture == 620_894_208
    assert counts.model_params(dims) == 89_135_104 + 2 * 614_596_736 \
        + 6 * 620_894_208 + 65536 * 2048 == 5_177_911_552   # 10.36 GB bf16
    assert counts.kv_token_bytes(dims) == 4096
    assert counts.state_slot_bytes(dims) == 7 * 2 * 2048 * 2 == 57_344
    assert 128 * counts.state_slot_bytes(dims) == 7_340_032     # 7 MB


def test_decode_bytes_and_conv_counts_by_hand(dims):
    # 512 rows over 64 experts: 63.98 touched if they fall alike
    touched = 64 * (1 - (63 / 64) ** 512)
    assert counts.expected_experts_touched(dims, 128) == pytest.approx(
        touched)
    assert 63.9 < touched < 64
    ctx = 128 * 900
    want = (2 * (5_177_911_552 - 8 * (64 - touched) * 9_437_184)
            + 4096 * ctx + 2 * 128 * 57_344)
    assert counts.decode_step_bytes(dims, ctx, 128) == pytest.approx(want)
    assert 10.7e9 < want < 10.9e9           # about 13.2 ms at 819 GB/s
    # a step that measured its touched experts passes them: 48 of 64
    less = counts.decode_step_bytes(dims, ctx, 128, experts_touched=48.0)
    assert want - less == pytest.approx(
        2 * 8 * (touched - 48) * 9_437_184)
    # the K/V rows are a twentieth of the step's bytes, the state nothing
    assert 0.04 < 4096 * ctx / want < 0.05
    assert 2 * 128 * 57_344 / want < 0.002
    assert counts.conv_span_flops(dims, 1024) == 7 * 1024 * 2048 * 8
    assert counts.conv_span_bytes(dims, 1024) == \
        7 * 1024 * 4 * 2048 * 2 + 2 * 57_344


def test_the_fitted_bias_balances_the_load():
    """``make_params`` fits the selection bias by the checkpoint's rule:
    on tokens it was not fitted on, the experts' loads lie closer together
    than with no bias, in every mixture layer (at this toy's width a
    router from the seed is near balance already: 0.10-0.15 of the mean
    against 0.05-0.07 fitted, over 4096 tokens whose own noise is 0.03)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    d = ref.model_dims({"model": TINY_MODEL, "layer_kinds": TINY_KINDS,
                        "served": {"param_dtype": "float32"}})
    params = ref.make_params(31, d)
    toks = jax.random.randint(jax.random.PRNGKey(5), (4096,), 1, 512)
    x = params["embed"][toks].astype(jnp.float32)
    for layer, kind in zip(params["layers"], TINY_KINDS):
        x, h = ref._ffn_input(layer, x, ref._dims_key(d), kind)
        if "gate_bias" in layer["moe"]:
            bias = layer["moe"]["gate_bias"]
            assert float(jnp.abs(bias).max()) > 0.01
            spread = []
            for b in (bias, 0 * bias):
                idx = ref.router_weights(h, layer["moe"]["gate_w"], b, d)[1]
                load = np.bincount(np.asarray(idx).ravel(), minlength=8)
                spread.append(load.std() / load.mean())
            assert spread[0] < 0.75 * spread[1], spread
        x = x + ref.ffn(layer["moe"], h, d)


# ------------------------------------------- one whole run at a toy size

TINY_MODEL = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 128,
    "intermediate_size": 128, "moe_intermediate_size": 64, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_dense_layers": 1,
    "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 4,
    "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 512,
}
TINY_KINDS = ["conv", "full_attention", "conv", "conv"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tiny_tree.write_tree(str(tmp_path_factory.mktemp("tree33")))

    def put(rel, obj):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)

    mixers = ["conv", "mha", "conv", "conv"]
    put("configs/tinylfm.json", {
        "name": "tinylfm", "source": "toy sizes for CPU rehearsal",
        "model": TINY_MODEL, "reduced": [], "layer_kinds": TINY_KINDS,
        "served": {"param_dtype": "bfloat16"},
        "program": {"preset": "lfm2-24b-a2b", "overrides": {
            "num_layers": 4, "first_k_dense": 1, "layer_mixers": mixers,
            "hidden_size": 128, "intermediate_size": 64,
            "dense_intermediate_size": 128, "num_experts": 8,
            "expert_top_k": 2, "num_heads": 4, "num_kv_heads": 2,
            "vocab_size": 512, "param_dtype": "bfloat16"}}})
    put("workloads/tinylfm.serve.json", {
        "name": "tinylfm.serve", "config": "tinylfm",
        "driver": "serve_mla", "reference": "reference_lfm2", "chips": 1,
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
        "name": "tinylfm", "source": "toy", "reduced": [], "why": "toy",
        "file": "benchmark/configs/tinylfm.json"})
    manifest["workloads"].append({
        "name": "tinylfm.serve", "config": "tinylfm", "traffic": "serve",
        "chips": 1, "why": "toy"})
    for m in manifest["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("tinylfm.serve")
    for m in manifest["per_layer"]:
        if m["name"].endswith(".shortchat"):
            m["workloads"].append("tinylfm.serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _drive(tree, trace=False, control=False):
    return run.run_cell("tinylfm.serve", 2**31 + 33, 1.5, trace,
                        control=control, require_tpu=False, root=tree)


def test_toy_cell_is_correct_and_its_control_is_not(tree, capsys):
    res = _drive(tree, control=True)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    mean = next(s for s in said if s.get("compared") == "served_gap_mean")
    control_gap = next(s for s in said if "check" in s)[
        "check"]["control"]["served_gap_mean"]
    # the precision below lies past the limit the sound run is under
    assert 0 <= mean["value"] <= mean["limit"] < control_gap
    notes = next(s for s in said if "notes" in s)["notes"]
    assert notes["evictions"] == 0


def test_toy_traced_run_reports_the_program_counter_readers(tree):
    res = _drive(tree, trace=True)
    assert {"engine_step_ms.shortchat", "batch_occupancy.shortchat",
            "kv_pool_occupancy.shortchat", "experts_touched_share.shortchat",
            "decode_ctx_gathered.shortchat", "decode_ctx_idle.shortchat"} \
        <= set(res["metrics"])
    # 1 to 8 experts over 4 slots
    assert 25 <= res["metrics"]["experts_touched_share.shortchat"][
        "value"] <= 200
    assert "serve_tokens_per_s" not in res["metrics"]
