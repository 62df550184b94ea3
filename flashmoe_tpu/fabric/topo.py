"""Mocked fabric topologies: ``FLASHMOE_MOCK_FABRIC`` world blocking.

The serving twin of the PR 12 ``FLASHMOE_MOCK_SLICES`` mock
(:func:`flashmoe_tpu.parallel.topology._mock_slices`): partition the
device world into ``k`` equal contiguous replica blocks so multi-replica
fabric drills and the router tests run
on the virtual CPU mesh without real multi-host serving.

The parse is hardened the same way: a malformed mock (non-integer,
non-positive, or a count that does not divide a multi-device world) is
a configuration error the drill must see at fabric construction — a
``ValueError`` naming the world size and the accepted format — never a
silent fall-back to a single replica.  The one relaxation vs the slice
mock: on a SINGLE-device world any replica count co-locates on that
device (replicas are full engines sharing the module-level jits, not
device partitions), so the 1/2/4-replica CI sweep runs on a bare CPU
host without forcing a virtual mesh.
"""

from __future__ import annotations

import os

#: the env var: a single positive replica count dividing the world size.
ENV_MOCK_FABRIC = "FLASHMOE_MOCK_FABRIC"


def _mock_fabric(n: int) -> int | None:
    """Parse ``FLASHMOE_MOCK_FABRIC`` against a world of ``n`` devices.

    Returns the replica count, or ``None`` when the mock is unset (or
    asks for a single replica — no blocking).  Mirrors
    :func:`flashmoe_tpu.parallel.topology._mock_slices`: malformed
    values raise a ``ValueError`` naming the world size and the
    accepted format."""
    raw = os.environ.get(ENV_MOCK_FABRIC)
    if raw is None or raw.strip() == "":
        return None
    try:
        replicas = int(raw)
    except ValueError:
        raise ValueError(
            f"{ENV_MOCK_FABRIC}={raw!r} is not an integer; the mock "
            f"format is a single positive replica count dividing the "
            f"world size ({n} devices), e.g. {ENV_MOCK_FABRIC}=2")
    if replicas < 1:
        raise ValueError(
            f"{ENV_MOCK_FABRIC}={replicas} must be >= 1 (a positive "
            f"replica count dividing the world size, {n} devices)")
    if replicas > 1 and n > 1 and n % replicas:
        raise ValueError(
            f"{ENV_MOCK_FABRIC}={replicas} does not divide the world "
            f"size ({n} devices); pick a divisor of {n} so every mocked "
            f"replica holds the same contiguous device block")
    return replicas if replicas > 1 else None


def fabric_world(n_devices: int | None = None) -> tuple[int, int]:
    """(replicas, devices_per_replica) for the current (or given)
    world: the ``FLASHMOE_MOCK_FABRIC`` blocking when set, else one
    replica owning every device.  The one resolution
    :class:`~flashmoe_tpu.fabric.engine.ServingFabric` uses."""
    if n_devices is None:
        import jax

        n_devices = len(jax.devices())
    n = int(n_devices)
    if n < 1:
        raise ValueError(f"fabric world needs >= 1 device, got {n}")
    replicas = _mock_fabric(n) or 1
    return replicas, max(1, n // replicas)
