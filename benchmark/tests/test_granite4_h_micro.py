"""Rehearsal of what PR 49 added to the benchmark (CPU):
``python -m pytest benchmark/tests/test_granite4_h_micro.py -q``.

The cell ``granite4_h_micro.serve.ragdocs``, its configuration (the WHOLE
model: ``reduced`` is empty), the plain reference
(``lib/reference_granite4.py``, run by ``drivers/serve_ssm.py`` as it
stands) and the twelve reader files are found by name through
``run.load_cell`` and ``run.read_layer_metrics``; the counts of
``lib/counts_granite4.py`` against the table of ISSUE 49 worked by hand;
and one whole run of a toy cell of the same architecture, which is
``correct`` and whose float8 control is not.
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


run = _load(os.path.join(BENCH, "run.py"), "benchrun_pr49")
counts = run.lib("counts_granite4")
ref = run.lib("reference_granite4")

CELL = "granite4_h_micro.serve.ragdocs"
READERS = {
    "engine_step_ms": ("harness_median", "host_clock", "server"),
    "batch_occupancy": ("record_mean_share", "program_counter", "server"),
    "kv_pool_occupancy": ("record_mean_share", "program_counter", "server"),
    "host_held_share": ("record_mean_share", "program_counter", "server"),
    "decode_ctx_gathered": ("record_mean_share", "program_counter",
                            "server"),
    "decode_ctx_idle": ("record_mean_share", "program_counter", "server"),
    "prefill_tokens_fill": ("record_mean_share", "program_counter",
                            "server"),
    "prefill_pad_rows": ("record_mean_share", "program_counter", "server"),
    "decode_device_ms": ("module_ms_per_call", "device_trace", "model step"),
    "prefill_device_share": ("module_share_of_busy", "device_trace",
                             "model step"),
    "state_rows_share": ("record_mean_share", "program_counter",
                         "model step"),
    "prefill_ctx_gathered": ("record_mean_share", "program_counter",
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
    assert cell.chips == 1 and cell.entry["config"] == "granite4_h_micro"
    assert cell.entry["traffic"] == "ragdocs"
    assert cell.spec["driver"] == "serve_ssm"
    assert cell.spec["reference"] == "reference_granite4"
    assert [m["name"] for m in cell.end_to_end()] == \
        ["serve_tokens_per_s", "setup_s"]
    for rel in ("lib/reference_granite4.py", "lib/counts_granite4.py",
                "drivers/serve_ssm.py"):
        assert os.path.exists(os.path.join(BENCH, rel))
    assert len(cell.entry["why"]) <= 200


def test_traffic_and_engine_are_the_issues(cell):
    t, e = cell.spec["traffic"], cell.spec["engine"]
    assert t["arrivals"] == {"kind": "backlog"} and t["queue_floor"] == 64
    assert t["prompt_len"] == {"dist": "lognormal", "median": 4096,
                               "sigma": 0.6, "min": 1024, "max": 16384}
    assert t["output_len"] == {"dist": "lognormal", "median": 192,
                               "sigma": 0.7, "min": 32, "max": 512}
    # the issue's first form: neither stated fallback is taken
    assert (t["block"], t["strata"], t["ramp_steps"],
            t["ramp_population"]) == (32, 8, 4, 32)
    assert (e["max_batch"], e["page_size"], e["num_pages"],
            e["max_pages_per_slot"], e["prefill_chunk"], e["prompt_bucket"],
            e["ctx_bucket_pages"]) == (32, 16, 24576, 1056, 1024, 256, 264)
    means = run.lib("traffic").mix_means(t)
    assert means["prompt_max"] + means["output_max"] \
        <= e["max_pages_per_slot"] * e["page_size"] == 16896
    assert 4800 < means["prompt_mean"] < 4950       # ISSUE 49: 4.9 k
    assert means["prompt_max"] == 14915
    assert 220 < means["output_mean"] < 235         # 228
    # every prompt is longer than a chunk: each is prefilled in chunks,
    # the state carried from one to the next
    sizes = run.lib("traffic").quantile_sizes(t["prompt_len"], t["block"])
    assert min(sizes) > e["prefill_chunk"]
    # the slots' mean context fills two fifths of the pool: eviction stays
    # bypassed
    live = e["max_batch"] * (means["prompt_mean"] + means["output_mean"] / 2)
    assert live < 0.45 * e["num_pages"] * e["page_size"]
    traffic = run.lib("traffic")
    pairs = lambda seed: sorted(
        (len(a.prompt), a.max_new_tokens)
        for a in traffic.generate(t, seed, 100352, 32))
    assert pairs(1) == pairs(2**31 + 5) == sorted(traffic.request_set(t))
    check = cell.spec["check"]
    assert set(check["limits"]) == {"served_gap_mean", "served_gap_widest",
                                    "state_gap"}
    assert all(isinstance(v, float) for v in check["limits"].values())
    assert set(check["limits"]) <= set(check["limits_why"])
    assert (check["streams"], check["state_streams"], check["control"],
            check["state_control"]) == (4, 8, "fp8", "bfloat16")
    assert {"ctx_bucket_pages", "fixed_by_ISSUE_49", "who",
            "spread_not_met"} <= set(cell.spec["engine_why"])


def test_configuration_is_the_whole_published_model(cell):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "granite-4.0-h-micro")
    conf = cell.config
    assert conf["source"] == row["source_url"]
    assert conf["reduced"] == [] and conf["reduced_why"] == {}
    assert {k: conf.get(k, "absent") for k in row["config"]} == row["config"]
    assert {"head_dim", "block", "attention", "state_space", "state_dtype",
            "weights"} <= set(conf["assumed"])
    assert conf["deployment"].startswith("ONE chip, ONE replica, the WHOLE")
    mem = conf["memory_analysis"]
    assert mem["copies_of_state_pool_or_embedding"] == 0
    assert max(mem["decode_step_1056_pages_GB"],
               mem["prefill_chunk_1024_widest_context_GB"]) < 14.5


def test_program_config_is_the_preset_uncut(cell):
    import jax.numpy as jnp

    cfg = run.Run(cell, 1, 1.0, False, False, "").program_config()
    types = cell.config["layer_types"]
    assert cfg.mixers == tuple("mha" if t == "attention" else "ssm"
                               for t in types)
    assert (cfg.num_layers, cfg.cache_layers, cfg.vocab_size) == (
        40, (5, 15, 25, 35), 100352)
    assert set(cfg.layers) == {("ssm", "dense"), ("mha", "dense")}
    assert cfg.param_dtype == jnp.bfloat16 and not cfg.use_rope
    assert cfg.tie_embeddings and cfg.moe_layer_indices == ()
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == tuple(
        float(cell.config[k]) for k in (
            "embedding_multiplier", "residual_multiplier",
            "attention_multiplier", "logits_scaling"))
    assert (cfg.hidden_size, cfg.dense_config.intermediate_size,
            cfg.dense_config.gated_ffn) == (2048, 8192, True)
    assert (cfg.num_heads, cfg.resolved_num_kv_heads,
            cfg.resolved_head_dim) == (32, 8, 64)
    assert cfg.slot_state == (("state", (64, 64, 128), jnp.float32),
                              ("conv", (3 * 4352,), jnp.bfloat16))
    assert cfg.state_slot_bytes == 36 * (2_097_152 + 26_112) == 76_437_504
    assert cfg.kv_token_bytes == 8192 and cfg.kv_pool_rows == (2, 4, 128)
    assert (cfg.ssm_groups, cfg.ssm_chunk, cfg.norm_eps) == (1, 256, 1e-5)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_found_by_name_and_listed_for_the_cell_alone(cell, metric):
    reducer, source, layer = READERS[metric]
    name = f"{metric}.ragdocs"
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        reader = json.load(f)
    assert reader["reducer"] == reducer and reader["what"]
    assert callable(getattr(run.lib("reducers"), reducer))
    entry = next(m for m in cell.per_layer() if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["source"] == source
    assert entry["layer"] == layer and entry["moves"] == "serve_tokens_per_s"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for other in ("dsmoe16b.serve.backlog", "nemotron3_nano.serve.manyslot",
                  "sdar_30b_a3b.serve.blockgen", "fmref.train.4k"):
        assert name not in {m["name"]
                            for m in run.load_cell(other).per_layer()}


def test_new_entries_follow_the_old_ones_as_they_were():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    names = [p["name"] for p in m["per_layer"]]
    mine = [f"{k}.ragdocs" for k in READERS]
    first = min(names.index(n) for n in mine)
    assert sorted(names[first:first + 12]) == sorted(mine)
    assert names[first - 1] == "commit_rows_share.blockgen"
    assert len(set(names)) == len(names)
    cells = [w["name"] for w in m["workloads"]]
    assert cells[cells.index(CELL) - 1] == "sdar_30b_a3b.serve.blockgen"
    configs = [c["name"] for c in m["configs"]]
    assert configs[configs.index("granite4_h_micro") - 1] == "sdar_30b_a3b"
    rate = next(e for e in m["end_to_end"]
                if e["name"] == "serve_tokens_per_s")
    assert rate["workloads"][7] == CELL and rate["bound"] == 0.05
    assert m["run_seconds"] == 50
    assert all(w["chips"] == 1 for w in m["workloads"])


MS = 1_000_000


def test_readers_read_records_and_a_trace_by_hand(cell):
    """All twelve through ``read_layer_metrics``; with no records and no
    trace each finds nothing and nothing raises."""
    records = [
        {"kind": "serve_step", "active": 32, "pages_used": 12288},
        {"kind": "serve_decode", "ctx_pages": 528, "ctx_pages_idle": 132.0,
         "state_rows": 32},
        {"kind": "serve_held", "held_slots": 0},
        {"kind": "serve_step", "active": 16, "pages_used": 6144},
        {"kind": "serve_decode", "ctx_pages": 264, "ctx_pages_idle": 66.0,
         "state_rows": 32},
        {"kind": "serve_held", "held_slots": 16},
        {"kind": "serve_prefill", "tokens": 1024, "pad_rows": 0,
         "ctx_pages": 264},
        {"kind": "serve_prefill", "tokens": 512, "pad_rows": 512,
         "ctx_pages": 792},
    ]
    mods = [("jit__paged_decode_step(1)", 0, 15 * MS),
            ("jit__paged_decode_step(1)", 20 * MS, 17 * MS),
            ("jit__prefill_chunk(2)", 40 * MS, 60 * MS),
            ("jit__prefill_chunk(3)", 100 * MS, 80 * MS),
            ("jit__sample_dynamic(4)", 190 * MS, 8 * MS)]
    dev = {"ops": [], "modules": mods, "t0": 0, "t1": 200 * MS}
    ctx = {"trace": {"per_device": {"/device:TPU:0": dev}, "busy_s": 0.18},
           "records": records, "harness": {"engine_step_ms": [20.0, 90.0,
                                                              110.0]},
           "end_to_end": {}, "cell": cell.spec, "config": cell.config,
           "peaks": None, "chips": 1, "lib": run.lib}
    got = {k: v["value"] for k, v in run.read_layer_metrics(cell, ctx).items()}
    assert got == pytest.approx({
        "engine_step_ms.ragdocs": 90.0, "decode_device_ms.ragdocs": 16.0,
        "prefill_device_share.ragdocs": 100.0 * 0.14 / 0.18,
        "batch_occupancy.ragdocs": 75.0,
        "kv_pool_occupancy.ragdocs": 100.0 * 9216 / 24576,
        "state_rows_share.ragdocs": 100.0,
        "host_held_share.ragdocs": 25.0,
        "prefill_tokens_fill.ragdocs": 75.0,
        "prefill_pad_rows.ragdocs": 25.0,
        "prefill_ctx_gathered.ragdocs": 50.0,
        "decode_ctx_gathered.ragdocs": 100.0 * 396 / 1056,
        "decode_ctx_idle.ragdocs": 100.0 * 99 / 1056})
    empty = dict(ctx, trace=None, records=[], harness={})
    assert run.read_layer_metrics(cell, empty) == {}


# ------------------------------------------------ counts, worked by hand

def test_parameter_counts_are_the_issues_table(dims):
    # a state layer: mixer 25.85 M (in 2048 x 8512, conv 4352 x 4 + bias,
    # out 4096 x 2048, A, D, dt bias, norm) + dense 50.33 M + two norms
    ssm = 2048 * 8512 + 5 * 4352 + 3 * 64 + 4096 + 4096 * 2048
    assert counts.ssm_params(dims) == ssm == 25_847_232
    assert counts.dense_params(dims) == 3 * 2048 * 8192 == 50_331_648
    assert counts.layer_params(dims, 0) == ssm + 50_331_648 + 4096 \
        == 76_182_976
    # an attention layer: q 4.19 M, k and v 1.05 M each, o 4.19 M
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert counts.attn_params(dims) == attn == 10_485_760
    assert counts.layer_params(dims, 5) == attn + 50_331_648 + 4096 \
        == 60_821_504
    # embedding = head (tied), counted once, + the final norm
    assert counts.model_params(dims) == (
        36 * 76_182_976 + 4 * 60_821_504 + 100352 * 2048 + 2048) \
        == 3_191_396_096                                 # 6.38 GB in bf16
    assert 6.38e9 < 2 * counts.model_params(dims) < 6.39e9
    assert counts.kv_token_bytes(dims) == 4 * 2 * 8 * 64 * 2 == 8192
    assert counts.state_bytes(dims) == 64 * 64 * 128 * 4 == 2_097_152
    # a slot: 75.50 MB of state + 0.94 MB of convolution inputs
    assert counts.state_slot_bytes(dims) == 36 * (2_097_152 + 3 * 4352 * 2) \
        == 76_437_504
    assert 36 * 2_097_152 == 75_497_472 and 36 * 26_112 == 940_032
    # 32 slots and 24576 pages of 16 tokens beside the weights: 12.05 GB,
    # 75 % of the chip's 16
    held = (2 * counts.model_params(dims)
            + 32 * counts.state_slot_bytes(dims)
            + 24576 * 16 * counts.kv_token_bytes(dims))
    assert held == 12_050_017_792 and 0.75 < held / 16e9 < 0.76
    # the state of a slot is what 9.2 k tokens cost the pool
    assert 9200 < counts.state_slot_bytes(dims) / 8192 < 9400


def test_decode_and_chunk_counts_by_hand(dims):
    # 32 slots at the mean context of 5.0 k: 6.38 GB of weights, 4.89 GB of
    # state and inputs read and written, 1.3 GB of K/V: 15.3 ms at 819 GB/s
    ctx = 32 * 4975
    want = 2 * 3_191_396_096 + 8192 * ctx + 2 * 32 * 76_437_504
    assert counts.decode_step_bytes(dims, ctx, 32) == pytest.approx(want)
    assert 12.5e9 < want < 12.7e9 and 15.2 < want / 819e9 * 1e3 < 15.5
    assert counts.ssm_step_bytes(dims, 32) == 2 * 32 * 2_097_152
    assert counts.paged_decode_bytes(dims, ctx) == 2048 * ctx
    # at 43 slots and up the float32 state moved passes the weights
    assert 2 * 42 * 75_497_472 < 2 * 3_191_396_096 < 2 * 43 * 75_497_472
    parts = counts.chunk_flops_by_part(dims, 1024, 8192)
    products = (40 * 2 * 1024 * 50_331_648
                + 36 * (2 * 1024 * 2048 * 8512 + 2 * 1024 * 4096 * 2048)
                + 4 * 2 * 1024 * 10_485_760)
    assert parts["products"] == pytest.approx(products)
    assert 6.0e12 < products < 6.2e12        # ISSUE 49: 6.1 TFLOP, 31 ms
    form = 36 * (2 * 1024 * 256 * 128 + 2 * 1024 * 256 * 4096
                 + 4 * 1024 * 4096 * 128)
    assert parts["chunked_form"] == pytest.approx(form)
    flash = 4 * 4 * 1024 * (8192 - 512) * 32 * 64
    assert parts["flash_span"] == pytest.approx(flash)
    assert counts.flash_span_flops(dims, 1024, 8192) == pytest.approx(
        flash / 4)
    assert counts.chunk_flops(dims, 1024, 8192) == pytest.approx(
        products + form + flash)
    assert counts.chunk_bytes(dims, 1024, 8192) == pytest.approx(
        2 * (3_191_396_096 + 1024 * 2048) + 2 * 76_437_504
        + 8192 * (8192 + 1024))


# ------------------------------------------- one whole run at a toy size

TINY = {
    "hidden_size": 128, "num_hidden_layers": 4,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "shared_intermediate_size": 256, "vocab_size": 512,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_n_groups": 1,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.03125,
    "logits_scaling": 8, "rms_norm_eps": 1e-05, "tie_word_embeddings": True,
    "position_embedding_type": "nope", "num_local_experts": 0,
}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tiny_tree.write_tree(str(tmp_path_factory.mktemp("tree49")))

    def put(rel, obj):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)

    put("configs/tinygranite.json", dict(
        TINY, name="tinygranite", source="toy sizes for CPU rehearsal",
        reduced=[], served={"param_dtype": "bfloat16"},
        program={"preset": "granite-4.0-h-micro", "overrides": {
            "num_layers": 4, "layer_mixers": ["ssm", "mha", "ssm", "ssm"],
            "hidden_size": 128, "intermediate_size": 256, "num_heads": 4,
            "num_kv_heads": 2, "head_dim": 32,
            "attention_multiplier": 0.03125, "ssm_heads": 4,
            "ssm_head_dim": 16, "ssm_state": 16, "ssm_chunk": 8,
            "vocab_size": 512, "param_dtype": "bfloat16"}}))
    put("workloads/tinygranite.serve.json", {
        "name": "tinygranite.serve", "config": "tinygranite",
        "driver": "serve_ssm", "reference": "reference_granite4",
        "chips": 1,
        "engine": {"max_batch": 4, "page_size": 8, "num_pages": 64,
                   "max_pages_per_slot": 12, "ctx_bucket_pages": 4,
                   "prompt_bucket": 16, "prefill_chunk": 16,
                   "max_steps": 100000000},
        "check": {"streams": 6, "state_streams": 4, "control": "fp8",
                  "state_control": "bfloat16",
                  "limits": {"served_gap_widest": 0.5,
                             "served_gap_mean": 0.003,
                             "state_gap": 0.012}},
        "traffic": {"prompt_len": {"dist": "lognormal", "median": 24,
                                   "sigma": 0.6, "min": 8, "max": 64},
                    "output_len": {"dist": "lognormal", "median": 8,
                                   "sigma": 0.5, "min": 2, "max": 16},
                    "block": 16, "arrivals": {"kind": "backlog"},
                    "queue_floor": 8, "ramp_steps": 3,
                    "ramp_population": 4}})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tinygranite", "source": "toy", "reduced": [], "why": "toy",
        "file": "benchmark/configs/tinygranite.json"})
    manifest["workloads"].append({
        "name": "tinygranite.serve", "config": "tinygranite",
        "traffic": "serve", "chips": 1, "why": "toy"})
    for m in manifest["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("tinygranite.serve")
    for m in manifest["per_layer"]:
        if m["name"].endswith(".ragdocs"):
            m["workloads"].append("tinygranite.serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _drive(tree, trace=False, control=False):
    return run.run_cell("tinygranite.serve", 2**31 + 49, 1.5, trace,
                        control=control, require_tpu=False, root=tree)


def test_toy_cell_is_correct_and_its_control_is_not(tree, capsys):
    res = _drive(tree, control=True)
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    mean = next(s for s in said if s.get("compared") == "served_gap_mean")
    state = next(s for s in said if s.get("compared") == "state_gap")
    check = next(s for s in said if "check" in s)["check"]
    # the precision below lies past the limit the sound run is under
    assert 0 <= mean["value"] <= mean["limit"] \
        < check["control"]["served_gap_mean"]
    assert 0 < state["value"] <= state["limit"]
    # every state layer of the first slot: three of the toy's four layers
    assert len(check["state"]["layers_of_first"]) == 3
    notes = next(s for s in said if "notes" in s)["notes"]
    assert notes["evictions"] == 0 and "slowest_step" in notes


def test_toy_traced_run_reports_the_program_counter_readers(tree):
    res = _drive(tree, trace=True)
    assert {f"{k}.ragdocs" for k, (_, source, _) in READERS.items()
            if source != "device_trace"} <= set(res["metrics"])
    assert res["metrics"]["state_rows_share.ragdocs"]["value"] == 100.0
    # every toy prompt longer than 16 tokens is chunked, and a chunk
    # gathers at least its first bucket of 4 of 12 pages
    assert 0 < res["metrics"]["prefill_ctx_gathered.ragdocs"]["value"] <= 100
    assert "serve_tokens_per_s" not in res["metrics"]
