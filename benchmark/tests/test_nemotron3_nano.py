"""Rehearsal of what PR 39 added to the benchmark (CPU):
``python -m pytest benchmark/tests/test_nemotron3_nano.py -q``.

The cell ``nemotron3_nano.serve.manyslot``, its configuration, its driver
(``drivers/serve_ssm.py``), the plain reference
(``lib/reference_nemotron3.py``) and the thirteen reader files are found by
name through ``run.load_cell`` and ``run.read_layer_metrics``; the counts of
``lib/counts_nemotron3.py`` against numbers worked by hand from the
published sizes; and one whole run of a toy cell of the same architecture,
which is ``correct`` and whose float8 control is not (its bfloat16-state
control is read; at a toy's two dozen tokens it cannot be told yet).
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


run = _load(os.path.join(BENCH, "run.py"), "benchrun_pr39")
counts = run.lib("counts_nemotron3")
ref = run.lib("reference_nemotron3")

CELL = "nemotron3_nano.serve.manyslot"
READERS = {
    "engine_step_ms": ("harness_median", "host_clock", "server"),
    "batch_occupancy": ("record_mean_share", "program_counter", "server"),
    "kv_pool_occupancy": ("record_mean_share", "program_counter", "server"),
    "decode_device_ms": ("module_ms_per_call", "device_trace", "model step"),
    "prefill_device_share": ("module_share_of_busy", "device_trace",
                             "model step"),
    "decode_ctx_gathered": ("record_mean_share", "program_counter",
                            "server"),
    "decode_ctx_idle": ("record_mean_share", "program_counter", "server"),
    "host_held_share": ("record_mean_share", "program_counter", "server"),
    "prefill_tokens_fill": ("record_mean_share", "program_counter",
                            "server"),
    "prefill_pad_rows": ("record_mean_share", "program_counter", "server"),
    "experts_touched_share": ("record_mean_share", "program_counter",
                              "model step"),
    "held_rows_share": ("record_mean_share", "program_counter",
                        "model step"),
    "state_rows_share": ("record_mean_share", "program_counter",
                         "model step"),
}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
PATTERN = "MEMEM*EMEMEM*"


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


@pytest.fixture(scope="module")
def dims(cell):
    return ref.model_dims(cell.config)


# ------------------------------------------------- found by name, as data

def test_cell_config_and_driver_are_found_by_name(cell):
    assert cell.chips == 1 and cell.entry["config"] == "nemotron3_nano"
    assert cell.entry["traffic"] == "manyslot"
    assert cell.spec["driver"] == "serve_ssm"
    assert cell.spec["reference"] == "reference_nemotron3"
    assert [m["name"] for m in cell.end_to_end()] == \
        ["serve_tokens_per_s", "setup_s"]
    for rel in ("lib/reference_nemotron3.py", "lib/counts_nemotron3.py",
                "drivers/serve_ssm.py"):
        assert os.path.exists(os.path.join(BENCH, rel))
    assert len(cell.entry["why"]) <= 200


def test_traffic_and_engine_are_the_issues(cell):
    t, e = cell.spec["traffic"], cell.spec["engine"]
    assert t["arrivals"] == {"kind": "backlog"} and t["queue_floor"] == 512
    assert t["prompt_len"] == {"dist": "lognormal", "median": 384,
                               "sigma": 1.0, "min": 48, "max": 3072}
    assert t["output_len"] == {"dist": "lognormal", "median": 384,
                               "sigma": 0.7, "min": 48, "max": 1536}
    assert (t["block"], t["ramp_population"]) == (256, 256)
    assert (e["max_batch"], e["page_size"], e["max_pages_per_slot"],
            e["num_pages"], e["prefill_chunk"], e["prompt_bucket"]) == (
        256, 16, 288, 32768, 1024, 256)
    means = run.lib("traffic").mix_means(t)
    assert means["prompt_max"] + means["output_max"] \
        == e["max_pages_per_slot"] * e["page_size"]
    assert 550 < means["prompt_mean"] < 650
    assert 430 < means["output_mean"] < 520
    # the slots' mean context fills less than half of the pool: eviction
    # stays bypassed
    live = e["max_batch"] * (means["prompt_mean"] + means["output_mean"] / 2)
    assert live < 0.45 * e["num_pages"] * e["page_size"]
    # every seed the same multiset of (prompt, answer) pairs
    traffic = run.lib("traffic")
    pairs = lambda seed: sorted(
        (len(a.prompt), a.max_new_tokens)
        for a in traffic.generate(t, seed, 65536, 256))
    assert pairs(1) == pairs(2**31 + 5) == sorted(traffic.request_set(t))
    check = cell.spec["check"]
    assert set(check["limits"]) == {"served_gap_mean", "served_gap_widest",
                                    "state_gap"}
    assert set(check["limits"]) <= set(check["limits_why"])
    assert (check["streams"], check["state_streams"], check["control"],
            check["state_control"]) == (4, 8, "fp8", "bfloat16")
    assert {"ctx_bucket_pages", "fixed_by_ISSUE_39"} <= set(
        cell.spec["engine_why"])


def test_configuration_keeps_every_published_number(cell):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    conf = cell.config
    assert conf["source"] == row["source_url"]
    assert conf["reduced"] == REDUCED
    changed = {k for k, v in row["config"].items()
               if conf.get(k, "absent") != v}
    # the pattern is the depth's cut, written out
    assert changed == set(REDUCED) | {"hybrid_override_pattern"}
    assert {k: conf[k] for k in REDUCED} == {
        "num_hidden_layers": 13, "n_routed_experts": 64,
        "vocab_size": 65536}
    assert conf["hybrid_override_pattern"] == PATTERN \
        == row["config"]["hybrid_override_pattern"][:13]
    assert conf["published"] == {
        k: row["config"][k] for k in REDUCED + ["hybrid_override_pattern"]}
    assert conf["held"] == {"expert_first": 0, "experts": 64,
                            "vocab_first": 0, "vocab_rows": 65536}
    assert {"d_inner", "no_rope", "no_dt_clamp", "state_dtype", "gated_norm",
            "expert_storage", "weights", "expert_bias"} <= set(
                conf["assumed"])
    assert set(conf["reduced_why"]) == set(REDUCED)
    assert conf["deployment"].startswith("TWO chips share every layer")
    assert conf["memory_analysis"]["copies_of_state_or_pool"] == 0


def test_program_config_is_the_cut_preset(cell):
    import jax.numpy as jnp

    cfg = run.Run(cell, 1, 1.0, False, False, "").program_config()
    kinds = {"M": ("ssm", None), "E": (None, "moe"), "*": ("mha", None)}
    assert cfg.layers == tuple(kinds[c] for c in PATTERN)
    assert (cfg.num_layers, cfg.moe_layer_indices, cfg.cache_layers) == (
        13, (1, 3, 6, 8, 10), (5, 12))
    assert cfg.state_layers == (0, 2, 4, 7, 9, 11)
    assert cfg.param_dtype == jnp.bfloat16 and not cfg.use_rope
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_first,
            cfg.expert_top_k, cfg.router_score, cfg.router_bias,
            cfg.routed_scaling_factor, cfg.gated_ffn, cfg.hidden_act) == (
        128, 64, 0, 6, "sigmoid", True, 2.5, False, "relu2")
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.num_shared_experts) == (2688, 1856, 2)    # one of 3712
    assert cfg.intermediate_size + cfg.intermediate_pad \
        == cell.config["served"]["expert_width_stored"] == 1920
    assert (cfg.num_heads, cfg.resolved_num_kv_heads,
            cfg.resolved_head_dim) == (32, 2, 128)
    assert cfg.slot_state == (("state", (64, 64, 128), jnp.float32),
                              ("conv", (3 * 6144,), jnp.bfloat16))
    assert cfg.state_slot_bytes == 6 * (2_097_152 + 36_864) == 12_804_096
    assert cfg.kv_token_bytes == 2048 and cfg.vocab_size == 65536
    assert cfg.kv_pool_rows == (2, 2, 128) and cfg.norm_eps == 1e-5


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_found_by_name_and_listed_for_the_cell_alone(cell, metric):
    reducer, source, layer = READERS[metric]
    name = f"{metric}.manyslot"
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        reader = json.load(f)
    assert reader["reducer"] == reducer and reader["what"]
    assert callable(getattr(run.lib("reducers"), reducer))
    entry = next(m for m in cell.per_layer() if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["source"] == source
    assert entry["layer"] == layer and entry["moves"] == "serve_tokens_per_s"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for other in ("dsmoe16b.serve.backlog", "joyai_flash.serve.longctx",
                  "ling3_flash.serve.longgen", "lfm2_24b.serve.shortchat",
                  "fmref.train.4k"):
        assert name not in {m["name"]
                            for m in run.load_cell(other).per_layer()}


def test_new_entries_are_appended_and_the_old_ones_as_they_were():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    names = [p["name"] for p in m["per_layer"]]
    assert sorted(names[-13:]) == sorted(f"{k}.manyslot" for k in READERS)
    assert names[-1] == "state_rows_share.manyslot"
    assert len(set(names)) == len(names)
    assert m["workloads"][-1]["name"] == CELL
    assert m["configs"][-1]["name"] == "nemotron3_nano"
    assert m["configs"][-1]["reduced"] == REDUCED
    rate = next(e for e in m["end_to_end"]
                if e["name"] == "serve_tokens_per_s")
    assert rate["workloads"][-1] == CELL and rate["bound"] == 0.05
    assert m["run_seconds"] == 50
    assert all(w["chips"] == 1 for w in m["workloads"])


MS = 1_000_000


def test_readers_read_records_and_a_trace_by_hand(cell):
    """All thirteen through ``read_layer_metrics``; with no records and no
    trace each finds nothing and nothing raises; records of a program
    that lacks ``state_rows`` (none runs this cell) leave that one out."""
    records = [
        {"kind": "serve_step", "active": 256, "pages_used": 16384},
        {"kind": "serve_decode", "experts_touched": 128.0, "ctx_pages": 72,
         "ctx_pages_idle": 12.0, "held_rows": 800.0, "state_rows": 256},
        {"kind": "serve_held", "held_slots": 0},
        {"kind": "serve_step", "active": 128, "pages_used": 8192},
        {"kind": "serve_decode", "experts_touched": 120.0, "ctx_pages": 36,
         "ctx_pages_idle": 6.0, "held_rows": 736.0, "state_rows": 256},
        {"kind": "serve_held", "held_slots": 128},
        {"kind": "serve_prefill", "tokens": 512, "pad_rows": 0},
        {"kind": "serve_prefill", "tokens": 256, "pad_rows": 256},
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
        "engine_step_ms.manyslot": 70.0, "decode_device_ms.manyslot": 40.0,
        "prefill_device_share.manyslot": 20.0,
        "batch_occupancy.manyslot": 75.0,
        "kv_pool_occupancy.manyslot": 100.0 * 12288 / 32768,
        # 124 of 128 experts over 256 slots: 50 % reads "every expert"
        "experts_touched_share.manyslot": 100.0 * 124 / 256,
        "held_rows_share.manyslot": 300.0,
        "state_rows_share.manyslot": 100.0,
        "host_held_share.manyslot": 25.0,
        "prefill_tokens_fill.manyslot": 37.5,
        "prefill_pad_rows.manyslot": 12.5,
        "decode_ctx_gathered.manyslot": 100.0 * 54 / 288,
        "decode_ctx_idle.manyslot": 100.0 * 9 / 288})
    empty = dict(ctx, trace=None, records=[], harness={})
    assert run.read_layer_metrics(cell, empty) == {}
    older = [dict(r) for r in records]
    for r in older:
        r.pop("state_rows", None)
    with pytest.raises(KeyError):
        # record_mean_share indexes the field (PERF.md section 7 (7)): no
        # program that can build this configuration lacks it
        run.read_layer_metrics(cell, dict(ctx, records=older))


# ------------------------------------------------ counts, worked by hand

def test_parameter_counts_by_hand(dims):
    ssm = (2688 * 10304 + 5 * 6144 + 3 * 64 + 4096 + 4096 * 2688)
    assert counts.ssm_params(dims) == ssm == 38_742_208
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256
    assert counts.attn_params(dims) == attn == 23_396_352
    assert counts.expert_params(dims) == 2 * 2688 * 1856 == 9_977_856
    # as STORED: 64 zero columns beside the 1856 (15 whole lanes), 3.4 %
    assert counts.expert_stored(dims) == 2 * 2688 * 1920 == 10_321_920
    mixture = 2688 * 128 + 128 + 64 * 10_321_920 + 2 * 2688 * 3712
    assert counts.mixture_params(dims) == mixture == 680_902_784
    assert counts.layer_params(dims, 0) == ssm + 2688
    assert counts.layer_params(dims, 1) == mixture + 2688
    assert counts.layer_params(dims, 5) == attn + 2688
    assert counts.model_params(dims) == (
        6 * ssm + 5 * mixture + 2 * attn + 13 * 2688
        + 2 * 65536 * 2688 + 2688) == 4_036_119_040       # 8.07 GB bf16
    assert counts.kv_token_bytes(dims) == 2 * 2 * 2 * 128 * 2 == 2048
    assert counts.state_bytes(dims) == 64 * 64 * 128 * 4 == 2_097_152
    assert counts.state_slot_bytes(dims) == 6 * (2_097_152 + 3 * 6144 * 2) \
        == 12_804_096
    assert 256 * counts.state_slot_bytes(dims) == 3_277_848_576  # 3.28 GB
    # the state a slot a layer is 1024 times what a token costs the pool
    # a layer
    assert counts.state_bytes(dims) // 1024 == 2048


def test_decode_and_chunk_counts_by_hand(dims):
    # 768 rows over the 64 held experts: every one touched
    touched = 64 * (1 - (63 / 64) ** 768)
    assert counts.held_experts_touched(dims, 768) == pytest.approx(touched)
    assert 63.99 < touched < 64
    ctx = 256 * 840
    weights = 4_036_119_040 - 65536 * 2688 + 256 * 2688
    want = 2 * weights + 2048 * ctx + 2 * 256 * 12_804_096
    assert counts.decode_step_bytes(dims, ctx, 256) == pytest.approx(want)
    assert 14.6e9 < want < 14.8e9           # about 18.0 ms at 819 GB/s
    # the state is 45 % of the step's bytes, the K/V rows 3 %
    assert 0.44 < 2 * 256 * 12_804_096 / want < 0.46
    assert 0.02 < 2048 * ctx / want < 0.04
    # a step that measured its touched experts passes them: 48 of 64
    less = counts.decode_step_bytes(dims, ctx, 256, experts_touched=48.0)
    assert want - less == pytest.approx(2 * 5 * 16 * 10_321_920)
    assert counts.state_step_bytes(dims, 256) == 2 * 256 * 2_097_152
    assert counts.ffn_stream_bytes(dims, 64) == 64 * 10_321_920 * 2
    # a 1024-token chunk alone: 1.12 TFLOP (5.7 ms at the chip's 197) and
    # 7.8 GB (9.5 ms): the weights' stream binds it
    flops = counts.chunk_flops(dims, 1024)
    ssm = (2 * 1024 * 2688 * 10304 + 2 * 1024 * 4096 * 2688
           + 2 * 1024 * 128 * 8 * 128 + 2 * 1024 * 128 * 4096
           + 4 * 1024 * 4096 * 128)
    attn = (2 * 1024 * 2688 * (64 + 4) * 128 + 4 * 1024 * 512 * 32 * 128)
    mix = (2 * 1024 * 2688 * 128 + 1024 * 6 * 0.5 * 2 * 9_977_856
           + 4 * 1024 * 2688 * 3712)
    assert flops == pytest.approx(6 * ssm + 2 * attn + 5 * mix)
    assert 1.1e12 < flops < 1.15e12
    assert counts.chunk_bytes(dims, 1024) == pytest.approx(
        2 * (4_036_119_040 - 65536 * 2688 + 1024 * 2688)
        + 2 * 12_804_096 + 2048 * 2048)


# ------------------------------------------- one whole run at a toy size

TINY = {
    "hidden_size": 128, "num_hidden_layers": 5,
    "hybrid_override_pattern": "MEM*E", "num_attention_heads": 16,
    "num_key_value_heads": 1, "head_dim": 8, "mamba_num_heads": 4,
    "mamba_head_dim": 16, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "use_conv_bias": True, "vocab_size": 512,
    "n_routed_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 192, "moe_shared_expert_intermediate_size": 384,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "n_group": 1, "layer_norm_epsilon": 1e-05,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "mlp_hidden_act": "relu2",
}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tiny_tree.write_tree(str(tmp_path_factory.mktemp("tree39")))

    def put(rel, obj):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)

    put("configs/tinyssm.json", dict(
        TINY, name="tinyssm", source="toy sizes for CPU rehearsal",
        reduced=[], published={"n_routed_experts": 8},
        held={"expert_first": 0, "experts": 4},
        served={"param_dtype": "bfloat16", "expert_width_stored": 256},
        program={"preset": "nemotron-3-nano-30b-a3b", "overrides": {
            "pattern": "MEM*E", "hidden_size": 128,
            "intermediate_size": 192, "num_experts": 8, "experts_held": 4,
            "expert_top_k": 2, "num_heads": 16, "num_kv_heads": 1,
            "head_dim": 8, "ssm_heads": 4, "ssm_head_dim": 16,
            "ssm_groups": 2, "ssm_state": 16, "ssm_chunk": 8,
            "vocab_size": 512, "param_dtype": "bfloat16"}}))
    put("workloads/tinyssm.serve.json", {
        "name": "tinyssm.serve", "config": "tinyssm",
        "driver": "serve_ssm", "reference": "reference_nemotron3",
        "chips": 1,
        "engine": {"max_batch": 4, "page_size": 8, "num_pages": 64,
                   "max_pages_per_slot": 12, "ctx_bucket_pages": 4,
                   "prompt_bucket": 16, "prefill_chunk": 16,
                   "max_steps": 100000000},
        "check": {"streams": 6, "state_streams": 4, "control": "fp8",
                  "state_control": "bfloat16",
                  "limits": {"served_gap_widest": 0.5,
                             "served_gap_mean": 0.008,
                             "state_gap": 0.012}},
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
        "name": "tinyssm", "source": "toy", "reduced": [], "why": "toy",
        "file": "benchmark/configs/tinyssm.json"})
    manifest["workloads"].append({
        "name": "tinyssm.serve", "config": "tinyssm", "traffic": "serve",
        "chips": 1, "why": "toy"})
    for m in manifest["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("tinyssm.serve")
    for m in manifest["per_layer"]:
        if m["name"].endswith(".manyslot"):
            m["workloads"].append("tinyssm.serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _drive(tree, trace=False, control=False):
    return run.run_cell("tinyssm.serve", 2**31 + 39, 1.5, trace,
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
    # the toy's slots have consumed 5-40 tokens: a state rounded to
    # bfloat16 after every token has not yet drifted past the projection's
    # own bfloat16 (0.003-0.007 either way); the cell's slots have consumed
    # 600-4600 and its limit lies between the two (PERF.md section 4)
    assert 0 < state["value"] <= state["limit"]
    assert 0 < check["control"]["state_gap"] < 0.02
    # every state layer of the first slot: two M layers in MEM*E
    assert len(check["state"]["layers_of_first"]) == 2
    notes = next(s for s in said if "notes" in s)["notes"]
    assert notes["evictions"] == 0 and "slowest_step" in notes


def test_toy_traced_run_reports_the_program_counter_readers(tree):
    res = _drive(tree, trace=True)
    assert {f"{k}.manyslot" for k, (_, source, _) in READERS.items()
            if source != "device_trace"} <= set(res["metrics"])
    assert res["metrics"]["state_rows_share.manyslot"]["value"] == 100.0
    # top-2 of 8 with 4 held: about one routed row a slot falls here
    assert 25 <= res["metrics"]["held_rows_share.manyslot"]["value"] <= 200
    assert "serve_tokens_per_s" not in res["metrics"]
