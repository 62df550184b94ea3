"""End-to-end training CLI as a subprocess."""

import json
import os
import subprocess
import sys

import pytest


def _run(args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    return subprocess.run(
        [sys.executable, "-m", "flashmoe_tpu.runtime.train_cli", *args],
        capture_output=True, text=True, env=env, timeout=timeout,
        cwd=__import__("pathlib").Path(__file__).parent.parent,
    )


SMALL = ["--steps", "2", "--batch", "2",
         "--set", "sequence_len=32", "--set", "hidden_size=64",
         "--set", "intermediate_size=128", "--set", "vocab_size=256",
         "--set", "num_heads=2", "--set", "num_layers=1",
         "--set", "moe_frequency=1", "--set", "num_experts=4",
         "--set", "dtype=float32", "--set", "param_dtype=float32"]


def test_synthetic_training(devices):
    out = _run(SMALL + ["--synthetic"])
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["steps"] == 2
    assert rec["final_loss"] is not None


@pytest.mark.slow
def test_with_data_and_checkpointing(devices, tmp_path):
    import numpy as np
    from flashmoe_tpu.runtime.data import write_token_file
    data = tmp_path / "toks.bin"
    write_token_file(str(data), np.arange(33 * 8, dtype=np.int32) % 256)
    ck = tmp_path / "ck"
    out = _run(SMALL + ["--data", str(data), "--checkpoint-dir", str(ck),
                        "--checkpoint-every", "1"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert (ck / "2").exists()  # checkpoint at final step


@pytest.mark.slow
def test_sigterm_drains_and_resumes(devices, tmp_path):
    """The real preemption path: SIGTERM to the CLI drains a final
    checkpoint + loader cursor inside the grace window, exits 0, and a
    re-run resumes from the drained step (docs/RESILIENCE.md)."""
    import signal
    import time

    import numpy as np
    from flashmoe_tpu.runtime.data import write_token_file

    data = tmp_path / "toks.bin"
    write_token_file(str(data), np.arange(33 * 8, dtype=np.int32) % 256)
    ck = tmp_path / "ck"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    base = SMALL[2:]  # SMALL minus its ["--steps", "2"] prefix
    proc = subprocess.Popen(
        [sys.executable, "-m", "flashmoe_tpu.runtime.train_cli",
         "--steps", "500", *base, "--data", str(data),
         "--checkpoint-dir", str(ck),
         "--checkpoint-every", "3", "--async-save"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env,
        cwd=__import__("pathlib").Path(__file__).parent.parent)
    try:
        # wait for the first periodic checkpoint, then preempt
        deadline = time.time() + 300
        while time.time() < deadline:
            if (ck / "3").exists():
                break
            time.sleep(0.5)
        else:
            proc.kill()
            pytest.fail("no checkpoint appeared before the deadline")
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()
        raise
    assert proc.returncode == 0, err[-2000:]
    assert "preempted: drained at step" in err

    from flashmoe_tpu.runtime import checkpoint as ckpt

    drained = ckpt.latest_step(str(ck))
    assert drained is not None and drained >= 3
    assert ckpt.verify(str(ck), drained)
    ls = ckpt.load_loader_state(str(ck), drained)
    assert ls is not None and ls["epoch"] * 8 + ls["cursor"] == 2 * drained

    # the re-run resumes from the drained step (few steps left)
    out2 = _run(["--steps", str(drained + 2), *base,
                 "--data", str(data), "--checkpoint-dir", str(ck),
                 "--checkpoint-every", "3"], timeout=420)
    assert out2.returncode == 0, out2.stderr[-2000:]
    rec = json.loads(out2.stdout.strip().splitlines()[-1])
    assert rec.get("resumes") == 1.0
