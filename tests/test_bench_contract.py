"""The driver-facing bench.py JSON contract (one line, machine-readable
partial semantics — advisor round-3 #4)."""

import json
import subprocess
import sys

import jax.numpy as jnp
import pytest


def test_emit_partial_vs_full(capsys):
    import bench
    from flashmoe_tpu.config import BENCH_CONFIGS

    cfg = BENCH_CONFIGS["reference"]
    bench._PARTIAL.update(cfg=cfg, name="reference")
    bench._emit(cfg, "reference", 2.5e-3, 2.6e-3)
    full = json.loads(capsys.readouterr().out.strip())
    assert full["vs_baseline"] == round(2.6 / 2.5, 3)
    assert "partial" not in full
    assert full["unit"] == "ms" and full["value"] == 2.5

    bench._PARTIAL.update(cfg=cfg, name="reference")
    bench._emit(cfg, "reference", 2.5e-3, None, note="deadline hit")
    part = json.loads(capsys.readouterr().out.strip())
    # a partial can never masquerade as a measured no-speedup result
    assert part["vs_baseline"] is None
    assert part["partial"] == "deadline hit"
    assert part["xla_path_ms"] is None


def test_mxu_util_label(monkeypatch):
    import bench
    from flashmoe_tpu.config import BENCH_CONFIGS
    from flashmoe_tpu.parallel import topology

    monkeypatch.setattr(topology, "tpu_generation", lambda d: "v5e")
    cfg = BENCH_CONFIGS["reference"]
    # reference config at the round-2 measured latency: utilization must
    # land in a sane (0, 1) band so the driver can gate on it
    u = bench._mxu_util(cfg, 2.749e-3)
    assert 0.1 < u < 1.0


def test_wire_fields_in_records():
    """Records carry the wire identity (selection keys) and the modeled
    comm bytes the wire saves at the config's nominal ep width."""
    import bench
    from flashmoe_tpu.config import BENCH_CONFIGS

    cfg = BENCH_CONFIGS["reference"]
    off = bench._wire_fields(cfg)
    assert off == {"wire_dtype": "off", "wire_dtype_combine": "off"}
    on = bench._wire_fields(cfg.replace(ep=8, wire_dtype="e4m3"))
    assert on["wire_dtype"] == "e4m3"
    assert on["wire_modeled_comm_saved_mb"] > 0
    assert on["wire_modeled_comm_mb"] > 0
    # single chip: no exchange to save on, but the identity still rides
    one = bench._wire_fields(cfg.replace(ep=1, wire_dtype="e4m3"))
    assert one["wire_modeled_comm_saved_mb"] == 0.0


def test_cli_profile_plumbs_ledger_matrix(monkeypatch, capsys, tmp_path):
    """`bench.py --profile` is the CLI face of
    profiler.ledger.run_ledger_matrix (which test_profiler gates end to
    end): the arg plumbing must hand it the obs dir / quick / steps
    flags, print each returned record as a JSON line, and mirror it
    into the --obs-dir artifacts."""
    import sys as _sys

    import __graft_entry__
    import bench
    from flashmoe_tpu.profiler import ledger

    seen = {}

    def fake_matrix(obs_dir, *, quick=False, steps=1, devices=None,
                    **kw):
        seen.update(obs_dir=obs_dir, quick=quick, steps=steps,
                    n_devices=len(devices or []))
        return [{"metric": "phase_ledger[flat,chunks=1,wire=off]",
                 "value": 1.25, "unit": "ms", "path": "flat"}]

    monkeypatch.setattr(ledger, "run_ledger_matrix", fake_matrix)
    monkeypatch.setattr(__graft_entry__, "_force_cpu_devices",
                        lambda n: None)
    obs = tmp_path / "obs"
    monkeypatch.setattr(_sys, "argv",
                        ["bench.py", "--profile-quick", "--profile-steps",
                         "3", "--obs-dir", str(obs), "--deadline", "0"])
    bench.main()
    assert seen["obs_dir"] == str(obs)
    assert seen["quick"] is True and seen["steps"] == 3
    assert seen["n_devices"] >= 1
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metric"].startswith("phase_ledger[")
    mirrored = [json.loads(line) for line in
                (obs / "bench_records.jsonl").read_text().splitlines()]
    assert mirrored == [rec]


def test_cli_serve_plumbs_load_sweep(monkeypatch, capsys, tmp_path):
    """`bench.py --serve` is the CLI face of
    serving.loadgen.serve_load_sweep (gated end-to-end by
    tests/test_serving.py): the arg plumbing must parse the load list,
    hand through requests/batch, print each record as a JSON line with
    the TTFT/TPOT fields, and mirror into --obs-dir."""
    import sys as _sys

    import bench
    from flashmoe_tpu.serving import loadgen

    seen = {}

    def fake_sweep(loads, *, n_requests=8, max_batch=4, **kw):
        seen.update(loads=list(loads), n=n_requests, b=max_batch)
        return [{"metric": "serve_load[every=2,B=2,req=3]",
                 "value": 120.0, "unit": "tokens_per_sec",
                 "vs_baseline": 1.0, "ttft_ms_p50": 5.0,
                 "tpot_ms_p50": 1.0, "completed": 3}]

    monkeypatch.setattr(loadgen, "serve_load_sweep", fake_sweep)
    obs = tmp_path / "obs"
    monkeypatch.setattr(_sys, "argv",
                        ["bench.py", "--serve", "--serve-loads", "4,2",
                         "--serve-requests", "3", "--serve-batch", "2",
                         "--obs-dir", str(obs), "--deadline", "0"])
    bench.main()
    assert seen == {"loads": [4, 2], "n": 3, "b": 2}
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metric"].startswith("serve_load[")
    assert "ttft_ms_p50" in rec and "tpot_ms_p50" in rec
    mirrored = [json.loads(line) for line in
                (obs / "bench_records.jsonl").read_text().splitlines()]
    assert mirrored == [rec]


def test_cli_serve_flag_exclusivity(monkeypatch, capsys):
    """--serve fail-fasts on modes/knobs it would silently ignore
    (the --profile/--ckpt contract), and its own flags are rejected
    without --serve."""
    import sys as _sys

    import bench

    cases = [
        ["bench.py", "--serve", "--ckpt"],
        ["bench.py", "--serve", "--overlap", "4"],
        ["bench.py", "--serve", "--sweep", "ep"],
        ["bench.py", "--serve", "--wire-dtype", "e4m3"],
        ["bench.py", "--serve", "--a2a-chunks", "2"],
        ["bench.py", "--serve", "--serve-loads", "2,zero"],
        ["bench.py", "--serve", "--serve-loads", "0"],
        ["bench.py", "--serve-requests", "4"],      # needs --serve
        ["bench.py", "--profile-quick", "--serve"],
    ]
    for argv in cases:
        monkeypatch.setattr(_sys, "argv", argv)
        with pytest.raises(SystemExit) as e:
            bench.main()
        assert e.value.code == 2, argv
        capsys.readouterr()


def test_cli_speculate_plumbs_and_guards(monkeypatch, capsys):
    """--speculate K threads into serve_load_sweep(speculate=K)
    (gated end-to-end by tests/test_serving.py), and fail-fasts where
    it would be silently dropped: without --serve, under --fabric
    (whose dispatch returns before the serve lane), and at K < 1."""
    import sys as _sys

    import bench
    from flashmoe_tpu.serving import loadgen

    seen = {}

    def fake_sweep(loads, *, speculate=None, **kw):
        seen["speculate"] = speculate
        return [{"metric": "serve_load[every=4,B=2,req=3,spec=k3]",
                 "value": 120.0, "unit": "tokens_per_sec",
                 "vs_baseline": 1.0, "ttft_ms_p50": 5.0,
                 "tpot_ms_p50": 1.0, "completed": 3}]

    monkeypatch.setattr(loadgen, "serve_load_sweep", fake_sweep)
    monkeypatch.setattr(_sys, "argv",
                        ["bench.py", "--serve", "--speculate", "3",
                         "--serve-loads", "4", "--serve-requests", "3",
                         "--serve-batch", "2", "--deadline", "0"])
    bench.main()
    assert seen == {"speculate": 3}
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert ",spec=k3]" in rec["metric"]

    for argv in [
        ["bench.py", "--speculate", "3"],           # needs --serve
        ["bench.py", "--fabric", "--speculate", "3"],
        ["bench.py", "--serve", "--speculate", "0"],
    ]:
        monkeypatch.setattr(_sys, "argv", argv)
        with pytest.raises(SystemExit) as e:
            bench.main()
        assert e.value.code == 2, argv
        capsys.readouterr()


def test_cli_tiles_flag_exclusivity(monkeypatch, capsys):
    """--tiles fail-fasts on knobs/modes the rowwin tile sweep would
    silently ignore (the --profile/--ckpt/--serve contract)."""
    import sys as _sys

    import bench

    cases = [
        ["bench.py", "--tiles", "--wire-dtype", "e4m3"],
        ["bench.py", "--tiles", "--a2a-chunks", "2"],
        ["bench.py", "--tiles", "--sweep", "ep"],
        ["bench.py", "--tiles", "--overlap", "4"],
        ["bench.py", "--tiles", "--ckpt"],
        ["bench.py", "--tiles", "--serve"],
        ["bench.py", "--tiles", "--profile"],
    ]
    for argv in cases:
        monkeypatch.setattr(_sys, "argv", argv)
        with pytest.raises(SystemExit) as e:
            bench.main()
        assert e.value.code == 2, argv
        capsys.readouterr()


def test_cli_quant_flag_exclusivity(monkeypatch, capsys):
    """--quant fail-fasts on knobs/modes the store sweep would silently
    ignore (ISSUE 15 satellite: refused with --ckpt/--overlap like the
    other shape-changing flags)."""
    import sys as _sys

    import bench

    cases = [
        ["bench.py", "--quant", "--ckpt"],
        ["bench.py", "--quant", "--overlap", "4"],
        ["bench.py", "--quant", "--wire-dtype", "e4m3"],
        ["bench.py", "--quant", "--a2a-chunks", "2"],
        ["bench.py", "--quant", "--sweep", "ep"],
        ["bench.py", "--quant", "--serve"],
        ["bench.py", "--quant", "--profile"],
        ["bench.py", "--quant", "--tiles"],
        ["bench.py", "--quant", "--scaling"],
        ["bench.py", "--quant", "--regression"],
    ]
    for argv in cases:
        monkeypatch.setattr(_sys, "argv", argv)
        with pytest.raises(SystemExit) as e:
            bench.main()
        assert e.value.code == 2, argv
        capsys.readouterr()


def test_quant_fields_in_records():
    """Every emitted record carries the quantized-store identity (the
    wire-knob convention), and the modeled weight-bytes-saved fields
    appear when the store is on."""
    import bench
    from flashmoe_tpu.config import BENCH_CONFIGS

    off = bench._quant_fields(BENCH_CONFIGS["mixtral"])
    assert off == {"expert_quant": "off"}
    on = bench._quant_fields(
        BENCH_CONFIGS["mixtral"].replace(expert_quant="int8"))
    assert on["expert_quant"] == "int8"
    assert on["quant_modeled_weight_saved_mb"] > 0
    assert (on["quant_modeled_weight_mb"]
            < on["quant_modeled_weight_saved_mb"] * 1.05)  # ~half


def _load_tune_sweep():
    import importlib.util as ilu
    import os

    spec = ilu.spec_from_file_location(
        "tune_sweep", os.path.join(os.path.dirname(__file__), "..",
                                   "scripts", "tune_sweep.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tune_sweep_tiles_candidates_are_feasible():
    """The tiles stages measure THE kernel's own candidate grid
    (fused.rowwin_sweep_candidates — code-review finding: the sweeps
    once hand-copied a narrower cm list that silently diverged from
    the chooser): every measured pair divides the shapes, fits the
    VMEM window budget, covers every feasible K-window at its widest
    feasible row tile — including the pair the analytic chooser picks
    — and the wide (mixtral-FFN) shape offers at least two candidates,
    so the sweep cannot be vacuous at the shape the schedule exists
    for."""
    import jax.numpy as jnp

    from flashmoe_tpu.config import MoEConfig
    from flashmoe_tpu.parallel.fused import (
        _rowwin_budget_ok, _rowwin_tiles, rowwin_sweep_candidates,
        rowwin_tile_candidates,
    )

    h, i, e = 4096, 14336, 8
    cfg = MoEConfig(num_experts=e, expert_top_k=2, hidden_size=h,
                    intermediate_size=i, sequence_len=2048,
                    capacity_factor=1.0, drop_tokens=True, ep=1,
                    dtype=jnp.bfloat16)
    cap_pad = -(-cfg.capacity_for(cfg.tokens) // 32) * 32
    full = rowwin_tile_candidates(cap_pad, h, i, 2, False, False, 2)
    cands = rowwin_sweep_candidates(cap_pad, h, i, 2, False, False, 2)
    assert len(cands) >= 2
    assert set(cands) <= set(full)
    assert {kw for _, kw in cands} == {kw for _, kw in full}
    for cm, kw in cands:
        assert cap_pad % cm == 0 and i % kw == 0
        assert _rowwin_budget_ok(cap_pad, h, i, 2, False, cm, kw,
                                 False, 2)
        # widest feasible row tile for this kw
        assert cm == max(c for c, k2 in full if k2 == kw)
    # the analytic chooser's pick is itself a measured candidate
    assert _rowwin_tiles(cap_pad, h, i, 2, None, False, False,
                         2) in cands


def test_cli_scaling_plumbs_sweep_and_knobs(monkeypatch):
    """`bench.py --scaling` hands the weak-scaling sweep its trials and
    wire/chunk knobs (wire-dcn included — the knob the sweep exists to
    measure)."""
    import sys as _sys

    import bench

    seen = {}

    def fake_scaling(trials, *, wire_dtype=None, wire_combine=None,
                     wire_dcn=None, a2a_chunks=None):
        seen.update(trials=trials, wire_dtype=wire_dtype,
                    wire_dcn=wire_dcn, a2a_chunks=a2a_chunks)

    monkeypatch.setattr(bench, "_bench_scaling", fake_scaling)
    monkeypatch.setattr(_sys, "argv",
                        ["bench.py", "--scaling", "--trials", "3",
                         "--wire-dcn", "e4m3", "--a2a-chunks", "2",
                         "--deadline", "0"])
    bench.main()
    assert seen == {"trials": 3, "wire_dtype": None,
                    "wire_dcn": "e4m3", "a2a_chunks": 2}


def test_cli_scaling_flag_exclusivity(monkeypatch, capsys):
    """--scaling fail-fasts on modes it would silently ignore, and
    --wire-dcn is rejected outside --scaling (no other mode runs a
    cross-slice hop)."""
    import sys as _sys

    import bench

    cases = [
        ["bench.py", "--scaling", "--overlap", "4"],
        ["bench.py", "--scaling", "--ckpt"],
        ["bench.py", "--scaling", "--tiles"],
        ["bench.py", "--scaling", "--serve"],
        ["bench.py", "--wire-dcn", "e4m3"],
        ["bench.py", "--wire-dcn", "e4m3", "--overlap", "4"],
    ]
    for argv in cases:
        monkeypatch.setattr(_sys, "argv", argv)
        with pytest.raises(SystemExit) as e:
            bench.main()
        assert e.value.code == 2, argv
        capsys.readouterr()


def test_cli_serve_telemetry_port_plumbed(monkeypatch, capsys):
    """`bench.py --serve --telemetry-port N` hands the port to the
    load sweep (which self-scrapes /metrics mid-sweep)."""
    import sys as _sys

    import bench
    from flashmoe_tpu.serving import loadgen

    seen = {}

    def fake_sweep(loads, *, n_requests=8, max_batch=4,
                   telemetry_port=None, **kw):
        seen.update(port=telemetry_port)
        return [{"metric": "serve_load[every=4,B=4,req=8]",
                 "value": 10.0, "unit": "tokens_per_sec",
                 "telemetry_scrape": {"ok": True}}]

    monkeypatch.setattr(loadgen, "serve_load_sweep", fake_sweep)
    monkeypatch.setattr(_sys, "argv",
                        ["bench.py", "--serve", "--telemetry-port",
                         "0", "--deadline", "0"])
    bench.main()
    assert seen == {"port": 0}
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["telemetry_scrape"]["ok"] is True


def test_cli_live_plane_flag_exclusivity(monkeypatch, capsys):
    """The fail-fast contract on the new flags: --telemetry-port
    without --serve and --regression with modes it cannot record are
    rejected rc 2."""
    import sys as _sys

    import bench

    cases = [
        ["bench.py", "--telemetry-port", "9100"],
        ["bench.py", "--telemetry-port", "9100", "--ckpt"],
        ["bench.py", "--telemetry-port", "9100", "--profile-quick"],
        ["bench.py", "--regression", "--ckpt"],
        ["bench.py", "--regression", "--overlap", "4"],
        ["bench.py", "--regression", "--sweep", "ep"],
        ["bench.py", "--regression", "--tiles"],
    ]
    for argv in cases:
        monkeypatch.setattr(_sys, "argv", argv)
        with pytest.raises(SystemExit) as e:
            bench.main()
        assert e.value.code == 2, argv
        capsys.readouterr()


def test_cli_regression_appends_history(monkeypatch, capsys, tmp_path):
    """`bench.py --serve --regression` appends ONE run entry keyed by
    the records' measurement-identity strings to obs/history.jsonl
    under --obs-dir."""
    import sys as _sys

    import bench
    from flashmoe_tpu.serving import loadgen

    monkeypatch.setattr(
        loadgen, "serve_load_sweep",
        lambda loads, **kw: [
            {"metric": "serve_load[every=4,B=4,req=8]", "value": 50.0,
             "unit": "tokens_per_sec", "ttft_ms_p50": 4.0},
            {"metric": "serve_load[every=1,B=4,req=8]", "value": None,
             "unit": "tokens_per_sec", "skipped": True},
        ])
    obs = tmp_path / "obs"
    monkeypatch.setattr(_sys, "argv",
                        ["bench.py", "--serve", "--regression",
                         "--obs-dir", str(obs), "--deadline", "0"])
    bench.main()
    capsys.readouterr()
    runs = [json.loads(l) for l in
            (obs / "history.jsonl").read_text().splitlines()]
    assert len(runs) == 1
    keys = set(runs[0]["metrics"])
    assert "serve_load[every=4,B=4,req=8]" in keys
    assert "serve_load[every=4,B=4,req=8].ttft_ms_p50" in keys
    # the skipped point never entered the baseline
    assert not any(k.startswith("serve_load[every=1") for k in keys)


def test_cli_fabric_plumbs_load_sweep(monkeypatch):
    """`bench.py --fabric` hands the fabric sweep its offered loads,
    request/batch sizes, the optional live-scrape port, and the
    virtual-clock arming."""
    import sys as _sys

    import bench

    seen = {}

    def fake_fabric(loads, *, requests, max_batch, telemetry_port=None,
                    vclock=False, wire="inproc"):
        seen.update(loads=loads, requests=requests,
                    max_batch=max_batch, telemetry_port=telemetry_port,
                    vclock=vclock, wire=wire)

    monkeypatch.setattr(bench, "_bench_fabric", fake_fabric)
    monkeypatch.setattr(_sys, "argv",
                        ["bench.py", "--fabric", "--telemetry-port",
                         "0", "--deadline", "0"])
    bench.main()
    assert seen == {"loads": [4, 2, 1], "requests": 8, "max_batch": 4,
                    "telemetry_port": 0, "vclock": False,
                    "wire": "inproc"}
    monkeypatch.setattr(_sys, "argv",
                        ["bench.py", "--fabric", "--vclock",
                         "--deadline", "0"])
    bench.main()
    assert seen["vclock"] is True and seen["telemetry_port"] is None
    # --wire tcp plumbs through to the sweep's socket-wire arm
    monkeypatch.setattr(_sys, "argv",
                        ["bench.py", "--fabric", "--wire", "tcp",
                         "--deadline", "0"])
    bench.main()
    assert seen["wire"] == "tcp"


def test_cli_fabric_flag_exclusivity(monkeypatch, capsys):
    """--fabric fail-fasts on modes/knobs it would silently ignore
    (its drill model pins its own config), and --telemetry-port is
    rejected outside --serve/--fabric."""
    import sys as _sys

    import bench

    cases = [
        ["bench.py", "--fabric", "--ckpt"],
        ["bench.py", "--fabric", "--quant"],
        ["bench.py", "--fabric", "--serve"],
        ["bench.py", "--fabric", "--scaling"],
        ["bench.py", "--fabric", "--profile"],
        ["bench.py", "--fabric", "--wire-dtype", "e4m3"],
        ["bench.py", "--fabric", "--a2a-chunks", "2"],
        ["bench.py", "--telemetry-port", "0"],
        ["bench.py", "--vclock"],
        ["bench.py", "--serve", "--vclock"],
        # the socket wire carries fabric KV handoffs only, and the
        # fault sweep picks each drill's wire itself
        ["bench.py", "--wire", "tcp"],
        ["bench.py", "--serve", "--wire", "tcp"],
        ["bench.py", "--fabric", "--faults", "--wire", "tcp"],
    ]
    for argv in cases:
        monkeypatch.setattr(_sys, "argv", argv)
        with pytest.raises(SystemExit) as e:
            bench.main()
        assert e.value.code == 2, argv
        capsys.readouterr()


def test_cli_fabric_faults_plumbs_fault_sweep(monkeypatch):
    """`bench.py --fabric --faults` dispatches the fault sweep (not
    the load sweep) — the recovery-ladder records ride the same
    emit/observability path as every other mode."""
    import sys as _sys

    import bench

    seen = {"faults": 0}
    monkeypatch.setattr(bench, "_bench_fabric_faults",
                        lambda: seen.update(faults=seen["faults"] + 1))
    monkeypatch.setattr(
        bench, "_bench_fabric",
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("--faults must not run the load sweep")))
    monkeypatch.setattr(_sys, "argv",
                        ["bench.py", "--fabric", "--faults",
                         "--deadline", "0"])
    bench.main()
    assert seen["faults"] == 1


def test_cli_faults_flag_exclusivity(monkeypatch, capsys):
    """--faults fail-fasts outside --fabric and refuses knobs the
    fault sweep would silently ignore (--vclock is implied — every
    drill already steps on the virtual clock; there is no live scrape
    window for --telemetry-port)."""
    import sys as _sys

    import bench

    cases = [
        ["bench.py", "--faults"],
        ["bench.py", "--serve", "--faults"],
        ["bench.py", "--fabric", "--faults", "--vclock"],
        ["bench.py", "--fabric", "--faults", "--telemetry-port", "0"],
    ]
    for argv in cases:
        monkeypatch.setattr(_sys, "argv", argv)
        with pytest.raises(SystemExit) as e:
            bench.main()
        assert e.value.code == 2, argv
        capsys.readouterr()


