"""Rehearsal of what PR 46 added to the benchmark (CPU):
``python -m pytest benchmark/tests/test_sdar_30b_a3b.py -q``.

The cell ``sdar_30b_a3b.serve.blockgen``, its configuration, its driver
(``drivers/serve_diffusion.py``), the plain reference
(``lib/reference_sdar.py``) and the thirteen reader files are found by name
through ``run.load_cell`` and ``run.read_layer_metrics``; the counts of
``lib/counts_sdar.py`` against the arithmetic of the configuration's
``reduced_why``; and one whole run of a toy cell of the same architecture,
which is ``correct`` and whose three controls (float8, plain causal
attention inside a block, no commit) are not.
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


run = _load(os.path.join(BENCH, "run.py"), "benchrun_pr46")
counts = run.lib("counts_sdar")
ref = run.lib("reference_sdar")

CELL = "sdar_30b_a3b.serve.blockgen"
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
    "revealed_rows_share": ("record_mean_share", "program_counter",
                            "model step"),
    "commit_rows_share": ("record_mean_share", "program_counter",
                          "model step"),
}
#: the catalog row's ``config``, whole (model-configs guide)
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


@pytest.fixture(scope="module")
def dims(cell):
    return ref.model_dims(cell.config)


# ------------------------------------------------- found by name, as data

def test_cell_config_and_driver_are_found_by_name(cell):
    assert cell.chips == 1 and cell.entry["config"] == "sdar_30b_a3b"
    assert cell.entry["traffic"] == "blockgen"
    assert cell.spec["driver"] == "serve_diffusion"
    assert cell.spec["reference"] == "reference_sdar"
    assert [m["name"] for m in cell.end_to_end()] == \
        ["serve_tokens_per_s", "setup_s"]
    for rel in ("lib/reference_sdar.py", "lib/counts_sdar.py",
                "drivers/serve_diffusion.py"):
        assert os.path.exists(os.path.join(BENCH, rel))
    assert len(cell.entry["why"]) <= 200
    # appended: the manifest's last cell, last configuration, and the
    # last thirteen per-layer metrics, each with its own list
    m = cell.manifest
    assert m["workloads"][-1]["name"] == CELL
    assert m["configs"][-1]["name"] == "sdar_30b_a3b"
    assert m["configs"][-1]["reduced"] == ["num_hidden_layers"]
    assert [p["name"] for p in m["per_layer"][-13:]] == \
        [f"{k}.blockgen" for k in (
            "engine_step_ms", "batch_occupancy", "kv_pool_occupancy",
            "decode_device_ms", "prefill_device_share",
            "decode_ctx_gathered", "decode_ctx_idle", "host_held_share",
            "prefill_tokens_fill", "prefill_pad_rows",
            "experts_touched_share", "revealed_rows_share",
            "commit_rows_share")]
    assert all(p["workloads"] == [CELL] for p in m["per_layer"][-13:])


def test_traffic_and_engine_are_the_issues(cell):
    t, e = cell.spec["traffic"], cell.spec["engine"]
    assert t["arrivals"] == {"kind": "backlog"} and t["queue_floor"] == 128
    assert t["prompt_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.9, "min": 64, "max": 2048}
    assert t["output_len"] == {"dist": "fixed", "value": 512}
    assert (t["block"], t["strata"], t["ramp_population"],
            t["ramp_steps"]) == (64, 8, 64, 4)
    assert (e["max_batch"], e["page_size"], e["num_pages"],
            e["max_pages_per_slot"], e["ctx_bucket_pages"],
            e["prompt_bucket"], e["prefill_chunk"]) == (
        64, 16, 8192, 160, 32, 256, 1024)
    assert (e["denoise_steps"], e["reveal_rule"]) == (
        2, "low_confidence_static")
    means = run.lib("traffic").mix_means(t)
    assert means["prompt_max"] + means["output_max"] \
        == e["max_pages_per_slot"] * e["page_size"]
    assert 650 < means["prompt_mean"] < 750 and means["output_mean"] == 512
    # the block length divides everything a span is cut by
    bl = cell.config["generation"]["block_length"]
    assert not any(e[k] % bl for k in ("page_size", "prompt_bucket",
                                       "prefill_chunk"))
    assert cell.spec["check"]["controls"] == ["fp8", "causal_block",
                                              "no_commit"]


def test_configuration_is_the_catalog_rows_with_the_depth_cut(cell, dims):
    cfg = cell.config
    differ = [k for k, v in CATALOG.items() if cfg.get(k, "absent") != v]
    assert differ == ["num_hidden_layers"] == cfg["reduced"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["num_hidden_layers"] in (6, 7)
    assert cfg["generation"] == {
        "block_length": 4, "mask_token_id": 151669, "denoise_steps": 2,
        "reveal_rule": "low_confidence_static"}
    assert {"block_length", "mask_token_id", "logit_shift", "reveal_rule",
            "commit"} <= set(cfg["assumed"])
    assert "first of seven one-chip pipeline stages" in cfg["deployment"]
    assert dims == {
        "hidden": 2048, "layers": 7, "heads": 32, "kv_heads": 4,
        "head_dim": 128, "vocab": 151936, "experts": 128, "top_k": 8,
        "inter": 768, "norm_topk": True, "rope_theta": 1e6, "eps": 1e-6,
        "block": 4, "mask_id": 151669, "param_dtype": "bfloat16"}


def test_counts_are_the_arithmetic_of_reduced_why(dims):
    assert counts.attn_params(dims) == pytest.approx(18.87e6, rel=1e-3)
    assert counts.expert_params(dims) == 3 * 2048 * 768
    assert counts.layer_params(dims) == pytest.approx(623.1e6, rel=1e-3)
    assert counts.model_params(dims) * 2 == pytest.approx(9.97e9, rel=2e-3)
    assert counts.kv_token_bytes(dims) == 14336
    assert 8192 * 16 * counts.kv_token_bytes(dims) \
        == pytest.approx(1.879e9, rel=1e-3)
    # 256 rows x 8 choices over 128 experts: all of them, near enough
    assert counts.expected_experts_touched(dims, 256) > 127.9
    # a launch at 64 slots of 1k tokens: the issue's 10.3 GB
    moved = counts.denoise_step_bytes(dims, 64 * 1024, 64)
    assert moved == pytest.approx(10.3e9, rel=0.03)
    assert counts.paged_decode_bytes(dims, 64 * 1024, 64) \
        == pytest.approx(64 * 1024 * 2048 + 64 * 2 * 16 * 2048
                         + 2 * 256 * 4096 * 2)


def test_the_programs_config_is_the_files(cell):
    cfg = run.Run(cell, 0, 1.0, False, False, "").program_config()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
            cfg.resolved_num_kv_heads, cfg.resolved_head_dim) == (
        7, 2048, 32, 4, 128)
    assert (cfg.num_experts, cfg.expert_top_k, cfg.intermediate_size,
            cfg.vocab_size, cfg.qk_norm, cfg.rope_theta, cfg.norm_eps) == (
        128, 8, 768, 151936, True, 1e6, 1e-6)
    assert (cfg.block_length, cfg.mask_token_id, cfg.attn_block) == (
        4, 151669, 4)
    assert cfg.kv_pool_token_bytes == 14336


@pytest.mark.parametrize("name", sorted(READERS))
def test_every_reader_names_a_reducer_and_a_field_the_engine_writes(
        cell, name):
    reducers = run.lib("reducers")
    reducer, source, layer = READERS[name]
    path = os.path.join(BENCH, "layer_metrics", f"{name}.blockgen.json")
    with open(path) as f:
        reader = json.load(f)
    assert reader["reducer"] == reducer and hasattr(reducers, reducer)
    entry = next(m for m in cell.manifest["per_layer"]
                 if m["name"] == f"{name}.blockgen")
    assert (entry["source"], entry["layer"], entry["moves"]) == (
        source, layer, "serve_tokens_per_s")
    if reducer == "record_mean_share":
        # (what the layers count comes through ``generate.span_forward``)
        engine = ""
        for rel in ("serving/engine.py", "models/generate.py"):
            with open(os.path.join(ROOT, "flashmoe_tpu", rel)) as f:
                engine += f.read()
        field = reader["args"]["field"]
        assert f'kind="{reader["args"]["kind"]}"' in engine \
            or f'"kind": "{reader["args"]["kind"]}"' in engine
        assert f"{field}=" in engine or f'"{field}"' in engine
        assert reader["args"]["of_engine"] in cell.spec["engine"]
    if name == "decode_device_ms":
        assert reader["args"]["pattern"] == "^jit__paged_denoise_step"


# ------------------------------------------------------- a toy cell, whole

TINY = dict(CATALOG, hidden_size=64, head_dim=16, num_attention_heads=4,
            num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=64, num_hidden_layers=2, vocab_size=512)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tiny_tree.write_tree(str(tmp_path_factory.mktemp("tree46")))

    def put(rel, obj):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)

    put("configs/tinysdar.json", dict(
        TINY, name="tinysdar", source="toy sizes for CPU rehearsal",
        reduced=[], served={"param_dtype": "float32"},
        generation={"block_length": 4, "mask_token_id": 511},
        program={"preset": "sdar-30b-a3b-chat", "overrides": {
            "num_layers": 2, "hidden_size": 64, "intermediate_size": 64,
            "num_experts": 8, "expert_top_k": 2, "num_heads": 4,
            "num_kv_heads": 2, "head_dim": 16, "vocab_size": 512,
            "mask_token_id": 511, "dtype": "float32",
            "param_dtype": "float32"}}))
    put("workloads/tinysdar.serve.json", {
        "name": "tinysdar.serve", "config": "tinysdar",
        "driver": "serve_diffusion", "reference": "reference_sdar",
        "chips": 1,
        "engine": {"max_batch": 4, "page_size": 8, "num_pages": 64,
                   "max_pages_per_slot": 12, "ctx_bucket_pages": 4,
                   "prompt_bucket": 16, "prefill_chunk": 16,
                   "max_steps": 100000000, "denoise_steps": 2,
                   "reveal_rule": "low_confidence_static"},
        "check": {"streams": 6, "control": "fp8",
                  "controls": ["fp8", "causal_block", "no_commit"],
                  "limits": {"served_gap_widest": 0.5,
                             "served_gap_mean": 0.004,
                             "reveal_gap_widest": 0.5,
                             "reveal_gap_mean": 0.004}},
        "traffic": {"prompt_len": {"dist": "lognormal", "median": 16,
                                   "sigma": 0.8, "min": 4, "max": 64},
                    "output_len": {"dist": "fixed", "value": 16},
                    "block": 16, "arrivals": {"kind": "backlog"},
                    "queue_floor": 8, "ramp_steps": 3,
                    "ramp_population": 4}})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tinysdar", "source": "toy", "reduced": [], "why": "toy",
        "file": "benchmark/configs/tinysdar.json"})
    manifest["workloads"].append({
        "name": "tinysdar.serve", "config": "tinysdar", "traffic": "serve",
        "chips": 1, "why": "toy"})
    for m in manifest["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("tinysdar.serve")
    for m in manifest["per_layer"]:
        if m["name"].endswith(".blockgen"):
            m["workloads"].append("tinysdar.serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _drive(tree, trace=False, control=False):
    return run.run_cell("tinysdar.serve", 2**31 + 46, 1.5, trace,
                        control=control, require_tpu=False, root=tree)


def test_toy_cell_is_correct_and_its_controls_are_not(tree, capsys):
    res = _drive(tree, control=True)
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    compared = {s["compared"]: s for s in said if "compared" in s}
    assert set(compared) == {"served_gap_widest", "served_gap_mean",
                             "reveal_gap_widest", "reveal_gap_mean"}
    assert all(0 <= c["value"] <= c["limit"] for c in compared.values())
    check = next(s for s in said if "check" in s)["check"]
    assert check["tokens"] > 0 and check["steps"] > 0
    # every one of the three mistakes lies past a limit the sound run is
    # under
    for name in ("fp8", "causal_block", "no_commit"):
        told = check["controls"][name]
        assert not told["passes_the_limits"], name
    notes = next(s for s in said if "notes" in s)["notes"]
    assert notes["evictions"] == 0


def test_toy_traced_run_reports_the_program_counter_readers(tree):
    res = _drive(tree, trace=True)
    assert {f"{k}.blockgen" for k, (_, source, _) in READERS.items()
            if source != "device_trace"} <= set(res["metrics"])
    # 4 tokens in 3 forwards on every slot, a commit in 3
    revealed = res["metrics"]["revealed_rows_share.blockgen"]["value"]
    commits = res["metrics"]["commit_rows_share.blockgen"]["value"]
    occupancy = res["metrics"]["batch_occupancy.blockgen"]["value"]
    assert 0.9 * occupancy < revealed / (4 / 3) < 1.1 * occupancy + 5
    assert 0.6 * occupancy < commits * 3 < 1.1 * occupancy + 5
    assert "serve_tokens_per_s" not in res["metrics"]
