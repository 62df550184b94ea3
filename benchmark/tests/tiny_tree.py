"""A scratch benchmark tree at toy sizes, written as NEW files only.

The real ``BENCHMARK.json`` and data files are copied, and a toy
configuration, toy cells and a toy per-layer metric are ADDED as files and
manifest entries: the runner finds them by name with no edit to a file
that is there.  CPU rehearsals and the tests below drive it with
``run_cell(..., require_tpu=False, root=<tree>)``.
"""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TINY_MODEL = {
    "hidden_act": "silu", "hidden_size": 64, "moe_intermediate_size": 64,
    "moe_layer_freq": 1, "n_routed_experts": 8, "n_shared_experts": 2,
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "vocab_size": 512, "torch_dtype": "bfloat16",
    "rope_theta": 10000,
}


def write_tree(root: str) -> str:
    """Copy the manifest and data files to ``root`` and add the toy ones."""
    os.makedirs(root, exist_ok=True)
    for sub in ("configs", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(root, "benchmark", sub),
                        dirs_exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)

    def put(rel, obj):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)

    put("configs/tiny.json", {
        "name": "tiny", "source": "toy sizes for CPU rehearsal",
        "model": TINY_MODEL, "reduced": [],
        "served": {"param_dtype": "bfloat16"},
        "program": {"preset": "deepseek-moe-16b", "overrides": {
            "num_layers": 2, "hidden_size": 64, "intermediate_size": 64,
            "num_experts": 8, "expert_top_k": 2, "num_heads": 4,
            "vocab_size": 512, "param_dtype": "bfloat16"}}})
    engine = {"max_batch": 4, "page_size": 8, "num_pages": 64,
              "max_pages_per_slot": 12, "ctx_bucket_pages": 4,
              "prompt_bucket": 16, "max_steps": 100000000}
    lens = {"prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.8,
                           "min": 4, "max": 64},
            "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                           "min": 2, "max": 16},
            "block": 16}
    check = {"streams": 6, "control": "fp8",
             "limits": {"served_gap_widest": 0.5, "served_gap_mean": 0.006}}
    put("workloads/tiny.serve.backlog.json", {
        "name": "tiny.serve.backlog", "config": "tiny", "driver": "serve",
        "chips": 1, "engine": engine, "check": check,
        "traffic": dict(lens, arrivals={"kind": "backlog"}, queue_floor=8,
                        ramp_steps=3, ramp_population=4)})
    put("workloads/tiny.serve.chat.json", {
        "name": "tiny.serve.chat", "config": "tiny", "driver": "serve",
        "chips": 1, "engine": engine, "check": check,
        "traffic": dict(lens, arrivals={"kind": "poisson", "rate_per_s": 20.0},
                        ramp_s=0.5, ramp_population=2, drain_s=20.0)})
    put("configs/tinyref.json", {
        "name": "tinyref", "source": "toy sizes for CPU rehearsal",
        "model": {"capacity_factor": 1.0, "drop_tokens": True,
                  "expert_top_k": 2, "hidden_act": "gelu", "hidden_size": 64,
                  "intermediate_size": 64, "moe_frequency": 2,
                  "num_experts": 8, "num_layers": 2, "vocab_size": 512,
                  "num_heads": 4, "gated_ffn": False,
                  "torch_dtype": "bfloat16"},
        "reduced": [], "served": {"param_dtype": "float32"},
        "program": {"preset": "flashmoe-reference", "overrides": {
            "hidden_size": 64, "intermediate_size": 64, "num_experts": 8,
            "vocab_size": 512, "num_heads": 4}}})
    put("workloads/tinyref.train.json", {
        "name": "tinyref.train", "config": "tinyref", "driver": "train",
        "chips": 1, "traffic": {"batch": 2, "sequence_len": 128},
        "program_overrides": {"sequence_len": 128},
        "optimizer": {"lr": 0.0003, "warmup_steps": 100,
                      "total_steps": 10000, "b1": 0.9, "b2": 0.95,
                      "eps": 1e-08, "weight_decay": 0.1, "clip": 1.0},
        "check": {"steps": 2, "control": "fp8",
                  "limits": {"loss_gap": 0.01, "first_grad_gap": 0.2,
                             "delta_gap": 0.1}}})
    put("workloads/tinyref.layer.ep4.json", {
        "name": "tinyref.layer.ep4", "config": "tinyref", "driver": "layer",
        "chips": 4, "traffic": {"tokens_per_chip": 64,
                                "param_dtype": "bfloat16"},
        "check": {"control": "fp8", "limits": {"worst_row_error": 0.03,
                                               "ambiguous_share": 0.25}}})
    put("layer_metrics/engine_step_ms.tiny.json", {
        "reducer": "harness_median", "args": {"series": "engine_step_ms"}})

    manifest["configs"].append({
        "name": "tiny", "source": "toy sizes for CPU rehearsal",
        "file": "benchmark/configs/tiny.json", "reduced": [], "why": "toy"})
    manifest["configs"].append({
        "name": "tinyref", "source": "toy sizes for CPU rehearsal",
        "file": "benchmark/configs/tinyref.json", "reduced": [],
        "why": "toy"})
    manifest["workloads"] += [
        {"name": "tinyref.train", "config": "tinyref", "traffic": "train",
         "chips": 1, "why": "toy"},
        {"name": "tinyref.layer.ep4", "config": "tinyref",
         "traffic": "layer.ep4", "chips": 4, "why": "toy"}]
    manifest["workloads"] += [
        {"name": "tiny.serve.backlog", "config": "tiny", "traffic": "backlog",
         "chips": 1, "why": "toy"},
        {"name": "tiny.serve.chat", "config": "tiny", "traffic": "chat",
         "chips": 1, "why": "toy"}]
    for m in manifest["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("tiny.serve.backlog")
        if m["name"] in ("ttft_p95_ms", "tpot_p95_ms"):
            m["workloads"].append("tiny.serve.chat")
        if m["name"] == "step_tokens_per_s":
            m["workloads"] += ["tinyref.train", "tinyref.layer.ep4"]
    names = {m["name"] for m in manifest["end_to_end"]}
    for name, unit, cell in (("ttft_p95_ms", "ms", "tiny.serve.chat"),
                             ("tpot_p95_ms", "ms", "tiny.serve.chat"),
                             ("step_tokens_per_s", "tokens/s",
                              "tinyref.train")):
        if name not in names:
            manifest["end_to_end"].append({
                "name": name, "unit": unit,
                "better": "lower" if unit == "ms" else "higher",
                "bound": 0.1, "source": "host_clock", "workloads": [cell]})
    manifest["per_layer"].append({
        "name": "engine_step_ms.tiny", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "server",
        "moves": "serve_tokens_per_s", "workloads": ["tiny.serve.backlog"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
