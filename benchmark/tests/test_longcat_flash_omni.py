"""Rehearsal of what PR 42 added to the benchmark (CPU):
``python -m pytest benchmark/tests/test_longcat_flash_omni.py -q``.

The cell ``longcat_flash_omni.serve.avturns``, its configuration, its
driver (``drivers/serve_controls.py``), the plain reference
(``lib/reference_longcat.py``) and the thirteen reader files are found by
name through ``run.load_cell`` and ``run.read_layer_metrics``; the counts of
``lib/counts_longcat.py`` against numbers worked by hand from the published
sizes; and one whole run of a toy cell of the same architecture, which is
``correct`` and whose two controls (the float8 one and the reference with
the identity experts' term left out) are not.
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


run = _load(os.path.join(BENCH, "run.py"), "benchrun_pr42")
counts = run.lib("counts_longcat")
ref = run.lib("reference_longcat")

CELL = "longcat_flash_omni.serve.avturns"
OLDER = ("dsmoe16b.serve.backlog", "joyai_flash.serve.longctx",
         "ling3_flash.serve.longgen", "lfm2_24b.serve.shortchat",
         "nemotron3_nano.serve.manyslot", "fmref.train.4k")
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
    "zero_rows_share": ("record_mean_share", "program_counter",
                        "model step"),
}
REDUCED = ["num_layers", "n_routed_experts", "vocab_size"]


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


@pytest.fixture(scope="module")
def dims(cell):
    return ref.model_dims(cell.config)


# ------------------------------------------------- found by name, as data

def test_cell_config_and_driver_are_found_by_name(cell):
    assert cell.chips == 1 and cell.entry["config"] == "longcat_flash_omni"
    assert cell.entry["traffic"] == "avturns"
    assert cell.spec["driver"] == "serve_controls"
    assert cell.spec["reference"] == "reference_longcat"
    assert [m["name"] for m in cell.end_to_end()] == \
        ["serve_tokens_per_s", "setup_s"]
    for rel in ("lib/reference_longcat.py", "lib/counts_longcat.py",
                "drivers/serve_controls.py"):
        assert os.path.exists(os.path.join(BENCH, rel))
    assert len(cell.entry["why"]) <= 200


def test_traffic_and_engine_are_the_issues(cell):
    t, e = cell.spec["traffic"], cell.spec["engine"]
    assert t["arrivals"] == {"kind": "backlog"} and t["queue_floor"] == 128
    assert t["prompt_len"] == {"dist": "lognormal", "median": 1024,
                               "sigma": 0.9, "min": 256, "max": 6144}
    assert t["output_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.7, "min": 32, "max": 1024}
    assert (t["block"], t["ramp_population"]) == (64, 64)
    assert (e["max_batch"], e["page_size"], e["max_pages_per_slot"],
            e["num_pages"], e["prefill_chunk"], e["prompt_bucket"]) == (
        64, 16, 448, 12288, 1024, 256)
    means = run.lib("traffic").mix_means(t)
    assert means["prompt_max"] + means["output_max"] \
        == e["max_pages_per_slot"] * e["page_size"]
    assert 1400 < means["prompt_mean"] < 1600
    assert 290 < means["output_mean"] < 350
    # the 64 pairs at FULL length fit the pool: no eviction
    pairs = run.lib("traffic").request_set(t)
    assert len(pairs) == 64
    assert sum(-(-(p + o) // 16) for p, o in pairs) < e["num_pages"] - 1
    assert 110_000 < sum(p + o for p, o in pairs) < 125_000
    # every seed the same multiset of (prompt, answer) pairs
    traffic = run.lib("traffic")
    drawn = lambda seed: sorted(
        (len(a.prompt), a.max_new_tokens)
        for a in traffic.generate(t, seed, 16384, 64))
    assert drawn(1) == drawn(2**31 + 5) == sorted(pairs)
    check = cell.spec["check"]
    assert set(check["limits"]) == {"served_gap_mean", "served_gap_widest"}
    assert set(check["limits"]) <= set(check["limits_why"])
    assert (check["streams"], check["control"], check["controls"]) == (
        4, "fp8", ["no_zero"])
    assert {"ctx_bucket_pages", "fixed_by_ISSUE_42"} <= set(
        cell.spec["engine_why"])


def test_configuration_keeps_every_published_number(cell):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "LongCat-Flash-Omni")
    conf = cell.config
    assert conf["source"] == row["source_url"]
    assert conf["reduced"] == REDUCED
    changed = {k for k, v in row["config"].items()
               if conf.get(k, "absent") != v}
    assert changed == set(REDUCED)
    assert {k: conf[k] for k in REDUCED} == {
        "num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
    assert conf["published"] == {k: row["config"][k] for k in REDUCED}
    # every width as published
    assert (conf["hidden_size"], conf["num_attention_heads"],
            conf["q_lora_rank"], conf["kv_lora_rank"],
            conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
            conf["v_head_dim"], conf["ffn_hidden_size"],
            conf["expert_ffn_hidden_size"], conf["zero_expert_num"],
            conf["moe_topk"], conf["routed_scaling_factor"]) == (
        6144, 64, 1536, 512, 128, 64, 128, 12288, 2048, 256, 12, 6)
    assert conf["held"]["expert_first"] == 0 and conf["held"]["experts"] == 16
    assert {"order_inside_a_layer", "norm_topk_prob", "router", "mla_scales",
            "expert_bias", "weights"} <= set(conf["assumed"])
    assert {"audio_encoder", "vision_encoder", "audio_codec_decoder",
            "chunked_av_interleaving", "multi_token_prediction"} <= set(
                conf["not_run"])
    assert set(conf["reduced_why"]) == set(REDUCED)
    assert conf["deployment"].startswith("32 chips share each layer")
    assert conf["memory_analysis"]["copies_of_the_pool"] == 0


def test_program_config_is_the_cut_preset(cell):
    import jax.numpy as jnp

    cfg = run.Run(cell, 1, 1.0, False, False, "").program_config()
    assert cfg.layers == (("mla", "dense+moe"), ("mla", "dense+join")) * 4
    assert (cfg.num_layers, cfg.moe_layer_indices, cfg.cache_layers) == (
        8, (0, 2, 4, 6), tuple(range(8)))
    assert cfg.param_dtype == jnp.bfloat16 and cfg.mla_rank_scale
    assert (cfg.num_experts, cfg.zero_experts, cfg.router_width,
            cfg.experts_held, cfg.expert_first, cfg.expert_top_k,
            cfg.router_score, cfg.router_bias, cfg.norm_topk_prob,
            cfg.routed_scaling_factor, cfg.gated_ffn, cfg.hidden_act) == (
        512, 256, 768, 16, 0, 12, "softmax", True, False, 6.0, True, "silu")
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.dense_intermediate_size, cfg.num_shared_experts) == (
        6144, 2048, 12288, 0)
    assert (cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        64, 1536, 512, 128, 64, 128)
    # eight sublayers' pool: 10 240 B a token as stored, 9 216 of rows
    assert cfg.kv_pool_rows == (1, 1, 640)
    assert cfg.kv_pool_token_bytes == 10240 and cfg.kv_token_bytes == 9216
    assert cfg.vocab_size == 16384 and cfg.norm_eps == 1e-5
    assert not cfg.state_layers and not cfg.drop_tokens


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_found_by_name_and_listed_for_the_cell_alone(cell, metric):
    reducer, source, layer = READERS[metric]
    name = f"{metric}.avturns"
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        reader = json.load(f)
    assert reader["reducer"] == reducer and reader["what"]
    assert callable(getattr(run.lib("reducers"), reducer))
    entry = next(m for m in cell.per_layer() if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["source"] == source
    assert entry["layer"] == layer and entry["moves"] == "serve_tokens_per_s"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for other in OLDER:
        assert name not in {m["name"]
                            for m in run.load_cell(other).per_layer()}


def test_new_entries_are_appended_and_the_old_ones_as_they_were():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    names = [p["name"] for p in m["per_layer"]]
    mine = [n for n in names if n.endswith(".avturns")]
    assert sorted(mine) == sorted(f"{k}.avturns" for k in READERS)
    at = names.index(mine[0])
    assert names[at:at + 13] == mine and "zero_rows_share.avturns" in mine
    assert not any(n.endswith(".avturns") for n in names[:at])
    assert len(set(names)) == len(names)
    cells = [w["name"] for w in m["workloads"]]
    assert cells == [OLDER[0], "fmref.train.4k", *OLDER[1:5], CELL]
    assert [c["name"] for c in m["configs"]][6] == "longcat_flash_omni"
    assert m["configs"][6]["reduced"] == REDUCED
    rate = next(e for e in m["end_to_end"]
                if e["name"] == "serve_tokens_per_s")
    assert CELL in rate["workloads"] and rate["bound"] == 0.05
    assert m["run_seconds"] == 50
    assert all(w["chips"] == 1 for w in m["workloads"])


MS = 1_000_000


def test_readers_read_records_and_a_trace_by_hand(cell):
    """All thirteen through ``read_layer_metrics``; with no records and no
    trace each finds nothing and nothing raises; records of a program
    that lacks ``zero_rows`` (none can build this configuration) raise in
    the reducer that indexes it."""
    records = [
        {"kind": "serve_step", "active": 64, "pages_used": 6144},
        {"kind": "serve_decode", "experts_touched": 480.0, "ctx_pages": 128,
         "ctx_pages_idle": 16.0, "held_rows": 16.0, "zero_rows": 256.0},
        {"kind": "serve_held", "held_slots": 0},
        {"kind": "serve_step", "active": 32, "pages_used": 3072},
        {"kind": "serve_decode", "experts_touched": 288.0, "ctx_pages": 96,
         "ctx_pages_idle": 12.0, "held_rows": 8.0, "zero_rows": 128.0},
        {"kind": "serve_held", "held_slots": 32},
        {"kind": "serve_prefill", "tokens": 1024, "pad_rows": 0},
        {"kind": "serve_prefill", "tokens": 512, "pad_rows": 256},
    ]
    mods = [("jit__paged_decode_step(1)", 0, 14 * MS),
            ("jit__paged_decode_step(1)", 20 * MS, 18 * MS),
            ("jit__prefill_chunk(2)", 100 * MS, 30 * MS),
            ("jit__prefill_padded(3)", 140 * MS, 10 * MS),
            ("jit__sample_dynamic(4)", 160 * MS, 8 * MS)]
    dev = {"ops": [], "modules": mods, "t0": 0, "t1": 200 * MS}
    ctx = {"trace": {"per_device": {"/device:TPU:0": dev}, "busy_s": 0.08},
           "records": records, "harness": {"engine_step_ms": [15.0, 17.0,
                                                              40.0]},
           "end_to_end": {}, "cell": cell.spec, "config": cell.config,
           "peaks": None, "chips": 1, "lib": run.lib}
    got = {k: v["value"] for k, v in run.read_layer_metrics(cell, ctx).items()}
    assert got == pytest.approx({
        "engine_step_ms.avturns": 17.0, "decode_device_ms.avturns": 16.0,
        "prefill_device_share.avturns": 50.0,
        "batch_occupancy.avturns": 75.0,
        "kv_pool_occupancy.avturns": 100.0 * 4608 / 12288,
        # 384 of the 768 outputs over 64 slots
        "experts_touched_share.avturns": 600.0,
        # 25 % is one row an expert held at a full batch
        "held_rows_share.avturns": 100.0 * 12 / 64,
        # 400 % is a third of top-12 on every slot
        "zero_rows_share.avturns": 300.0,
        "host_held_share.avturns": 25.0,
        "prefill_tokens_fill.avturns": 75.0,
        "prefill_pad_rows.avturns": 12.5,
        "decode_ctx_gathered.avturns": 100.0 * 112 / 448,
        "decode_ctx_idle.avturns": 100.0 * 14 / 448})
    empty = dict(ctx, trace=None, records=[], harness={})
    assert run.read_layer_metrics(cell, empty) == {}
    older = [dict(r) for r in records]
    for r in older:
        r.pop("zero_rows", None)
    with pytest.raises(KeyError):
        # record_mean_share indexes the field (PERF.md section 7 (7)): no
        # program that can build this configuration lacks it
        run.read_layer_metrics(cell, dict(ctx, records=older))


# ------------------------------------------------ counts, worked by hand

def test_parameter_counts_by_hand(dims):
    mla = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
           + 64 * 128 * 6144)
    assert counts.mla_params(dims) == mla == 90_570_752
    assert counts.dense_ffn_params(dims) == 3 * 6144 * 12288 == 226_492_416
    assert counts.expert_params(dims) == 3 * 6144 * 2048 == 37_748_736
    assert counts.router_width(dims) == 768
    dense = 2 * mla + 2 * 226_492_416 + 6144 * 768
    assert counts.layer_dense_params(dims) == dense == 638_844_928
    # a layer's 512 experts: 19.33 G parameters, 38.7 GB: no chip holds one
    assert 512 * 37_748_736 == 19_327_352_832
    model = 4 * (dense + 16 * 37_748_736) + 2 * 16384 * 6144
    assert counts.model_params(dims) == model == 5_172_625_408   # 10.35 GB
    # 9 216 B of rows a token over the EIGHT sublayers (10 240 as stored)
    assert counts.latent_token_bytes(dims) == 8 * 576 * 2 == 9216
    assert 196_608 * 10_240 == 2_013_265_920                      # the pool


def test_decode_and_chunk_counts_by_hand(dims):
    # 64 rows x top-12 over 768 outputs: 10.2 of the 16 held touched
    touch = 1 - (1 - 12 / 768) ** 64
    assert counts.expected_expert_touch(dims, 64) == pytest.approx(touch)
    assert 10.1 < 16 * touch < 10.2
    ctx = 64 * 1800
    dense = 4 * 638_844_928 + 16384 * 6144
    want = 2 * (dense + 4 * 16 * touch * 37_748_736) + 9216 * ctx
    assert counts.decode_step_bytes(dims, 64, ctx) == pytest.approx(want)
    assert 9.3e9 < want < 9.6e9              # about 11.5 ms at 819 GB/s
    # dense weights 5.1 GB, touched experts 3.1 GB, head 0.2, rows 1.1
    assert 5.1e9 < 2 * 4 * 638_844_928 < 5.12e9
    assert 3.0e9 < 2 * 4 * 16 * touch * 37_748_736 < 3.1e9
    assert 1.0e9 < 9216 * ctx < 1.1e9
    counted = counts.decode_step_bytes(dims, 64, ctx, experts_touched=12.0)
    assert counted - want == pytest.approx(
        2 * 4 * (12 - 16 * touch) * 37_748_736)
    assert counts.ffn_stream_bytes(dims, 10) == 10 * 37_748_736 * 2
    # one fm_latent_decode call: 1152 B and 139 kFLOP a live row, 121
    # FLOP a byte against the chip's ridge of 240
    assert counts.latent_decode_bytes(dims, ctx) == 1152 * ctx
    assert counts.latent_decode_flops(dims, ctx) == 2 * 64 * 1088 * ctx
    assert 120 < 2 * 64 * 1088 / 1152 < 122
    # a 1024-token chunk whose queries see 1500 rows in the mean
    flops = counts.prefill_chunk_flops(dims, 1024, 1500)
    layer = (2 * 1024 * 638_844_928 + 2 * 1024 * 12 * 16 / 768
             * 37_748_736)
    attn = 2 * (2 * 1500 * 512 * 64 * 256 + 2 * 1024 * 1500 * 64 * 320)
    assert flops == pytest.approx(4 * (layer + attn) + 2 * 6144 * 16384)
    assert 5.9e12 < flops < 6.1e12           # 30 ms at the chip's 197
    # the experts are a hundredth of it here (a third in the deployment)
    assert 4 * 2 * 256 * 37_748_736 / flops < 0.015
    assert counts.prefill_chunk_bytes(dims, 1500) == pytest.approx(
        2 * (4 * (638_844_928 + 16 * 37_748_736) + 16384 * 6144)
        + 9216 * 1500)


# ------------------------------------------- one whole run at a toy size

TINY = {
    "hidden_size": 128, "num_layers": 2, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 64, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "vocab_size": 512, "n_routed_experts": 2,
    "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 5,
    "expert_ffn_hidden_size": 128, "ffn_hidden_size": 256,
    "routed_scaling_factor": 6, "rope_theta": 10000000,
    "rms_norm_eps": 1e-05, "attention_method": "MLA",
}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tiny_tree.write_tree(str(tmp_path_factory.mktemp("tree42")))

    def put(rel, obj):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)

    put("configs/tinycat.json", dict(
        TINY, name="tinycat", source="toy sizes for CPU rehearsal",
        reduced=[], published={"n_routed_experts": 8},
        held={"expert_first": 2, "experts": 2},
        served={"param_dtype": "bfloat16"},
        program={"preset": "longcat-flash", "overrides": {
            "num_layers": 4, "hidden_size": 128, "intermediate_size": 128,
            "dense_intermediate_size": 256, "num_experts": 8,
            "zero_experts": 4, "expert_top_k": 5, "expert_first": 2,
            "experts_held": 2, "num_heads": 4, "q_lora_rank": 32,
            "kv_lora_rank": 64, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 512,
            "param_dtype": "bfloat16"}}))
    put("workloads/tinycat.serve.json", {
        "name": "tinycat.serve", "config": "tinycat",
        "driver": "serve_controls", "reference": "reference_longcat",
        "chips": 1,
        "engine": {"max_batch": 4, "page_size": 8, "num_pages": 64,
                   "max_pages_per_slot": 12, "ctx_bucket_pages": 4,
                   "prompt_bucket": 16, "prefill_chunk": 16,
                   "max_steps": 100000000},
        "check": {"streams": 6, "control": "fp8", "controls": ["no_zero"],
                  "limits": {"served_gap_widest": 0.5,
                             "served_gap_mean": 0.003}},
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
        "name": "tinycat", "source": "toy", "reduced": [], "why": "toy",
        "file": "benchmark/configs/tinycat.json"})
    manifest["workloads"].append({
        "name": "tinycat.serve", "config": "tinycat", "traffic": "serve",
        "chips": 1, "why": "toy"})
    for m in manifest["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("tinycat.serve")
    for m in manifest["per_layer"]:
        if m["name"].endswith(".avturns"):
            m["workloads"].append("tinycat.serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _drive(tree, trace=False, control=False):
    return run.run_cell("tinycat.serve", 2**31 + 42, 1.5, trace,
                        control=control, require_tpu=False, root=tree)


def test_toy_cell_is_correct_and_its_controls_are_not(tree, capsys):
    res = _drive(tree, control=True)
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    mean = next(s for s in said if s.get("compared") == "served_gap_mean")
    check = next(s for s in said if "check" in s)["check"]
    # the precision below lies past the limit the sound run is under
    assert 0 <= mean["value"] <= mean["limit"] \
        < check["control"]["served_gap_mean"]
    # and so does a program without the identity experts' term
    told = check["controls"]["no_zero"]
    assert not told["passes_the_limits"]
    assert told["served_gap_mean"] > mean["limit"]
    notes = next(s for s in said if "notes" in s)["notes"]
    assert notes["evictions"] == 0


def test_toy_traced_run_reports_the_program_counter_readers(tree):
    res = _drive(tree, trace=True)
    assert {f"{k}.avturns" for k, (_, source, _) in READERS.items()
            if source != "device_trace"} <= set(res["metrics"])
    # top-5 of 12 outputs, 4 of them identity, 2 of 8 FFN experts held
    zero = res["metrics"]["zero_rows_share.avturns"]["value"]
    held = res["metrics"]["held_rows_share.avturns"]["value"]
    assert 40 <= zero <= 300 and 10 <= held <= 200
    assert "serve_tokens_per_s" not in res["metrics"]


def test_the_fitted_bias_leaves_a_third_of_the_choices_to_identity(dims):
    """At toy sizes: the balanced router's share of identity choices is
    the outputs' share (4 of 12)."""
    toy = ref.model_dims(dict(TINY, published={"n_routed_experts": 8},
                              held={"expert_first": 2},
                              served={"param_dtype": "float32"}))
    params = ref.make_params(2**31 + 7, toy)
    shares = ref.zero_choice_share(params, toy, 2**31 + 7, tokens=512)
    assert len(shares) == 2 and all(0.28 < s < 0.39 for s in shares)
    assert dims["zero"] / (dims["router_experts"] + dims["zero"]) \
        == pytest.approx(1 / 3)
