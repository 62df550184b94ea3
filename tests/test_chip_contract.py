"""What PR 22 put in the place of the probe contract: nothing hides the
device.  No TPU where one is needed is a non-zero exit and never a
``skipped`` record; an unknown ``device_kind`` is an error that names it;
the compile cache is placed from outside; one process owns a host's chips;
``chip_smoke.py`` cannot pass on the CPU.  Plus the CPU-side checks of what
the chip's compiler forced into the kernels (VMEM-fitted chunks, the flash
attention VJP, the interpreter cure)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- the device is read, never assumed --------------------------------

class _Dev:
    def __init__(self, kind, platform="tpu"):
        self.device_kind, self.platform = kind, platform


def test_unknown_device_kind_is_an_error_that_names_it():
    from flashmoe_tpu.parallel.topology import tpu_generation

    assert tpu_generation(_Dev("TPU v5 lite")) == "v5e"
    assert tpu_generation(_Dev("TPU v5")) == "v5p"
    assert tpu_generation(_Dev("cpu", "cpu")) == "cpu"
    with pytest.raises(ValueError, match="TPU v9 mega"):
        tpu_generation(_Dev("TPU v9 mega"))


def test_generation_override_of_the_gone_backend_is_gone(monkeypatch):
    """Only the device's own kind decides; no variable overrides it."""
    from flashmoe_tpu.parallel.topology import tpu_generation

    monkeypatch.setenv("FLASHMOE_TPU_GEN", "v5e")
    with pytest.raises(ValueError, match="mystery chip"):
        tpu_generation(_Dev("mystery chip"))


@pytest.mark.parametrize("fn", ["ici_spec", "chip_spec"])
def test_unknown_generation_is_never_priced_as_another(fn):
    from flashmoe_tpu.parallel import topology

    assert getattr(topology, fn)("v5e")
    for gen in ("default", "v9"):
        with pytest.raises(ValueError, match=gen):
            getattr(topology, fn)(gen)


def test_tuning_generation_reads_the_device(monkeypatch):
    from flashmoe_tpu import tuning

    monkeypatch.delenv("FLASHMOE_TPU_GEN", raising=False)
    assert tuning.generation() == tuning.PLANNING_TARGET  # CPU: a target
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v6 lite")])
    assert tuning.generation() == "v6e"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v9 mega")])
    with pytest.raises(ValueError, match="TPU v9 mega"):
        tuning.generation()
    monkeypatch.setenv("FLASHMOE_TPU_GEN", "v4")
    assert tuning.generation() == "v4"


# ---- compile cache placed from outside --------------------------------

def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    from flashmoe_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_default_is_one_fixed_path(monkeypatch):
    from flashmoe_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.enable_compile_cache() == got  # no pid, no time
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_conftest_leaves_the_compile_cache_off():
    with open(os.path.join(ROOT, "tests", "conftest.py")) as f:
        src = f.read()
    assert "compile_cache" not in src and "compilation_cache" not in src


@pytest.mark.parametrize("path", [
    "chip_smoke.py", "benchmark/run.py",
    "flashmoe_tpu/runtime/train_cli.py",
    "flashmoe_tpu/serving/__main__.py", "flashmoe_tpu/runtime/worker.py"])
def test_programs_turn_the_cache_on(path):
    with open(os.path.join(ROOT, path)) as f:
        assert "enable_compile_cache()" in f.read()


# ---- one process for each host's chips --------------------------------

def test_launcher_refuses_many_workers_on_a_tpu_host(monkeypatch):
    from flashmoe_tpu.runtime import launcher

    started = []
    monkeypatch.setattr(launcher.subprocess, "Popen",
                        lambda *a, **k: started.append(a))
    monkeypatch.setattr(launcher.glob, "glob",
                        lambda pat: ["/dev/vfio/0"] if "vfio" in pat else [])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="one process owns"):
        launcher.run_workers(8)
    assert not started  # refused before anything was started
    # held to the CPU, the multi-process simulation is what it always was
    assert not launcher._opens_tpu({"JAX_PLATFORMS": "cpu"})
    assert launcher._opens_tpu({"JAX_PLATFORMS": "tpu,cpu"})


def test_launcher_parent_touches_no_backend():
    code = ("import flashmoe_tpu as fm, flashmoe_tpu.runtime.launcher;"
            "from jax._src import xla_bridge as xb;"
            "assert not xb._backends, xb._backends; print('clean')")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd=ROOT)
    assert r.returncode == 0 and "clean" in r.stdout, r.stderr[-500:]


# ---- chip_smoke.py cannot pass without the chip -----------------------

def test_chip_smoke_on_the_cpu_exits_nonzero_and_prints_no_result():
    r = subprocess.run([sys.executable, "chip_smoke.py"],
                       capture_output=True, text=True, timeout=120, cwd=ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    with open(os.path.join(ROOT, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"],
                       capture_output=True, text=True, timeout=120,
                       cwd=tmp_path, env=env)
    assert r.returncode != 0 and r.stdout.strip() == ""


@pytest.mark.parametrize("fails", [False, True], ids=["passes", "fails"])
def test_chip_smoke_last_line_and_exit_code(fails, monkeypatch, capsys):
    """With the phases stubbed and a TPU faked: a passing run ends on
    exactly the device line and exits 0; a phase that raises is printed,
    the rest still run, there is no device line and the exit is 1."""
    import chip_smoke

    class Tpu:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {}

    def serve(seed):
        if fails:
            raise RuntimeError("boom")
        return True

    ran = []
    monkeypatch.setattr(jax, "devices", lambda *a: [Tpu()])
    monkeypatch.setattr(chip_smoke, "enable_compile_cache", lambda: "x",
                        raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "x")
    monkeypatch.setattr(chip_smoke, "ONE_CHIP", {
        "layer": lambda seed: True, "serve": serve,
        "train": lambda seed: ran.append("train") or True})
    rc = chip_smoke.main([])
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    assert ran == ["train"]
    if fails:
        assert rc == 1 and lines[-1]["failed"] == ["serve"]
        assert not any("device" in x for x in lines)
    else:
        assert rc == 0 and lines[-1] == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


@pytest.mark.parametrize("drop", [False, True], ids=["dropless", "drops"])
def test_chip_smoke_oracle_is_the_dense_oracle(drop):
    """Without drops ``chip_smoke.oracle_layer`` IS ``reference_moe``;
    with them it adds exactly the layer's capacity rule (XLA path)."""
    import chip_smoke
    from flashmoe_tpu.config import MoEConfig
    from flashmoe_tpu.models.reference import init_moe_params, reference_moe
    from flashmoe_tpu.ops.moe import moe_layer

    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=drop, capacity_factor=1.0, gated_ffn=True,
                    hidden_act="silu", num_shared_experts=1,
                    dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (cfg.tokens, cfg.hidden_size), jnp.float32)
    got, ambiguous = chip_smoke.oracle_layer(params, x, cfg)
    want = (moe_layer(params, x, cfg, use_pallas=False).out if drop
            else reference_moe(params, x, cfg)[0])
    clear = ~ambiguous
    assert clear.sum() > cfg.tokens * 0.9
    np.testing.assert_allclose(np.asarray(got)[clear],
                               np.asarray(want)[clear], rtol=2e-4, atol=2e-4)
    if drop:  # and the rule bites: the plain oracle disagrees somewhere
        plain = reference_moe(params, x, cfg)[0]
        assert float(jnp.max(jnp.abs(plain - want))) > 1e-2


# ---- what the chip's compiler forced into the kernels -----------------

def test_grouped_matmul_chunks_n_when_vmem_forces_it(monkeypatch):
    """At Mixtral's I=14336 the [block_m, N] tile outgrows VMEM and N is
    chunked; forced here at a small size by lowering the ceiling."""
    from flashmoe_tpu.ops import expert as ex

    e, t, k, n, bm = 2, 32, 64, 256, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (t, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (e, n, k), jnp.float32)
    gid = jnp.asarray([0, 1], jnp.int32)
    want = jnp.concatenate([x[:bm] @ w[0].T, x[bm:] @ w[1].T])
    monkeypatch.setattr(ex, "_VMEM_CEILING", 40_000)
    need = lambda b: 2 * bm * 64 * 4 + 2 * 64 * b * 4 + bm * b * 12
    assert ex._fit_chunk(n, n, need) < n  # the chunked path really runs
    got = ex.grouped_matmul.__wrapped__(
        x, gid, w, transpose_w=True, block_m=bm, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    got = ex.grouped_matmul.__wrapped__(
        x, gid, jnp.swapaxes(w, 1, 2), block_m=bm, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_grouped_ffn_vmem_request_matches_the_chips_count():
    """``_ffn_vmem`` is what Mosaic counted when it refused token_scaling
    (block_m 128, H 4096, chunk 512, bf16): 'Scoped allocation with size
    22.00M and limit 16.00M'."""
    from flashmoe_tpu.ops import expert as ex

    assert ex._ffn_vmem(128, 4096, 512, False, 2, 2) == 22 << 20
    params = ex._vmem_params(22 << 20)
    assert (22 << 20) < params.vmem_limit_bytes <= ex._VMEM_CEILING
    assert ex._vmem_params(1 << 20).vmem_limit_bytes == ex._VMEM_DEFAULT
    # a gated launch streams the gate chunk as an operand of its own:
    # the chunk and the VMEM request come back, no array of the weights
    x16 = jnp.zeros((16, 128), jnp.bfloat16)
    w16 = jnp.zeros((2, 128, 256), jnp.bfloat16)
    bi, params = ex._ffn_chunks(x16, w16, w16, 16, 128, True)
    assert bi == 128 and params.vmem_limit_bytes == ex._VMEM_DEFAULT
    # a chunk the whole intermediate axis fits under is that axis
    assert ex._ffn_chunks(x16, w16, w16, 16, 256, True)[0] == 256
    w768 = jnp.zeros((2, 128, 768), jnp.bfloat16)
    assert ex._ffn_chunks(x16, w768, w768, 16, 768, True)[0] == 768
    assert ex._ffn_chunks(x16, w768, w768, 16, 512, True)[0] == 384
    with pytest.raises(ValueError, match="requires w_gate"):
        ex._ffn_chunks(x16, w16, None, 16, 128, True)


def test_flash_attention_grad_matches_xla():
    from flashmoe_tpu.ops.attention import attention_xla, flash_attention

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, 128, 64),
                                 jnp.float32) for i in range(3))
    loss = lambda f: lambda q, k, v: (f(q, k, v) ** 2).sum()
    got = jax.jit(jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, block_q=64, block_k=64, interpret=True)),
        argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(loss(attention_xla), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3)


def _flash_grads(fn, q, k, v):
    loss = lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


@pytest.mark.parametrize(
    "t,blocks,causal,dtype,tol",
    [(512, (128, 128), True, jnp.float32, 2e-3),    # both kernels walk 4 tiles
     (512, (128, 128), False, jnp.float32, 2e-3),
     (512, (128, 256), True, jnp.float32, 2e-3),    # the clamp, unequal sides
     (512, (256, 128), True, jnp.float32, 2e-3),
     (2048, None, True, jnp.float32, 2e-3),         # the rule's 1024 x 1024
     (384, None, True, jnp.bfloat16, 3e-2),         # one block a side
     (512, (128, 128), True, jnp.bfloat16, 3e-2),
     (512, (128, 128), False, jnp.bfloat16, 3e-2)],
    ids=["causal", "full", "q_short", "k_short", "rule", "bf16_one_block",
         "bf16_causal", "bf16_full"])
def test_flash_attention_backward_kernels_match_xla(t, blocks, causal, dtype,
                                                    tol):
    """dq, dk, dv of ``fm_flash_bwd_dq`` / ``fm_flash_bwd_dkv`` against
    ``jax.grad`` of the oracle on the same inputs.  bfloat16: both sides
    round the probabilities and their cotangent to 8 bits, in another
    order, so 3 % of a gradient's largest element is the tolerance."""
    from flashmoe_tpu.ops.attention import attention_xla, flash_attention

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, t, 64),
                                 jnp.float32).astype(dtype) for i in range(3))
    bq, bk = blocks or (None, None)
    got = _flash_grads(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True),
        q, k, v)
    want = _flash_grads(lambda q, k, v: attention_xla(
        q, k, v, causal=causal), q, k, v)
    for g, w in zip(got, want):
        assert g.dtype == dtype
        g, w = (np.asarray(a, np.float32) for a in (g, w))
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * np.abs(w).max())


def test_flash_attention_grad_under_the_trainers_checkpoint():
    """``transformer.forward`` wraps a block in
    ``jax.checkpoint(nothing_saveable)``: the forward kernel runs again in
    the backward pass and hands the same residuals to the same kernels."""
    from flashmoe_tpu.ops.attention import flash_attention

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, 256, 64),
                                 jnp.float32) for i in range(3))
    fa = lambda q, k, v: flash_attention(q, k, v, block_q=128, block_k=128,
                                         interpret=True)
    plain = _flash_grads(fa, q, k, v)
    remat = _flash_grads(jax.checkpoint(
        fa, policy=jax.checkpoint_policies.nothing_saveable), q, k, v)
    for g, w in zip(remat, plain):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_under_abstract_trace_on_this_jax():
    from flashmoe_tpu.utils.compat import concrete_leaf, under_abstract_trace

    seen = {}

    @jax.jit
    def f(x):
        seen["jit"] = (under_abstract_trace(), concrete_leaf(x))
        return x + 1

    f(jnp.ones(4))
    assert seen["jit"] == (True, None)
    assert under_abstract_trace() is False
    assert concrete_leaf(jnp.ones(2)) is not None


def test_interpreter_cure_is_idempotent():
    from jax._src.pallas.mosaic.interpret import shared_memory

    from flashmoe_tpu.utils.compat import cure_interpret_device_barrier

    cure_interpret_device_barrier()
    once = shared_memory.SharedMemory.update_clocks_for_device_barrier
    cure_interpret_device_barrier()
    assert shared_memory.SharedMemory.update_clocks_for_device_barrier is once
