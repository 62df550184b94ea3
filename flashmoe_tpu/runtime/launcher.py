"""Multi-process launcher.

The reference shells out to ``nvshmrun -n N -ppn P python worker.py cfg``
(``flashmoe/launcher.py:38-56``).  On TPU, multi-host jobs are normally
started by the cluster scheduler (GKE/“one process per host”), so the
launcher's job is (a) single-host multi-process simulation for development
and (b) generating/executing the per-host command with the coordinator
environment that :mod:`flashmoe_tpu.runtime.bootstrap` consumes.
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys


def _opens_tpu(env: dict) -> bool:
    """Whether a worker started with ``env`` would open this host's TPU:
    the host has chips (their device nodes — looked up without touching a
    JAX backend, which would take the chips for this parent) and ``env``
    does not hold JAX to another platform."""
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def run_workers(n_processes: int = 1, *, config_path: str | None = None,
                bench: bool = False, coordinator: str = "127.0.0.1:8476",
                extra_env: dict | None = None,
                per_rank_env: dict | None = None,
                worker_module: str = "flashmoe_tpu.runtime.worker") -> int:
    """Launch N local worker processes (CPU backend: each gets the virtual
    device set; TPU: ONE process owns all the local chips — libtpu gives
    them to the first worker and every other one would wait on its lock,
    so N > 1 on a TPU host is refused here, before anything starts).
    This parent never touches a JAX backend itself.

    Returns the worst exit code.  Mirrors ``nvshmrun_launcher``'s contract:
    build the command, run it, surface stdout/stderr.  ``per_rank_env``
    maps rank -> env overrides for that rank only (heterogeneity/fault
    injection in tests).
    """
    envs = []
    for rank in range(n_processes):
        env = dict(os.environ)
        env.update(extra_env or {})
        env.update((per_rank_env or {}).get(rank, {}))
        envs.append(env)
    if n_processes > 1 and any(_opens_tpu(env) for env in envs):
        raise RuntimeError(
            f"run_workers(n_processes={n_processes}) on a TPU host: one "
            f"process owns all the local chips, so a second worker would "
            f"hang on libtpu's lock.  Use n_processes=1 (one process "
            f"drives every chip of the host through the mesh), or set "
            f"JAX_PLATFORMS=cpu for the multi-process simulation.")
    procs = []
    for rank, env in enumerate(envs):
        if n_processes > 1:
            env.update({
                "FLASHMOE_COORDINATOR": coordinator,
                "FLASHMOE_NPROCS": str(n_processes),
                "FLASHMOE_RANK": str(rank),
            })
        cmd = [sys.executable, "-m", worker_module]
        if config_path:
            cmd.append(config_path)
        if bench:
            cmd.append("--bench")
        procs.append(subprocess.Popen(cmd, env=env))
    rc = 0
    for p in procs:
        p.wait()
        rc = max(rc, p.returncode)
    return rc


def slurm_command(n_nodes: int, config_path: str) -> str:
    """The srun command line for a multi-host job (reference README's SLURM
    path, ``README.md:118-126``)."""
    return (
        f"srun -N {n_nodes} --ntasks-per-node=1 "
        f"python -m flashmoe_tpu.runtime.worker {config_path} --bench"
    )
