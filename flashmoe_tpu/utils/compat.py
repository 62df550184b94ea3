"""What this code needs from inside jax 0.9.0 (``jax._src``).

``jax.shard_map`` and ``jax.lax.axis_size`` are public and the call sites
use them directly.  The three helpers here have no public spelling; each
was checked against the installed jax 0.9.0 (``pyproject.toml`` pins it).
"""

from __future__ import annotations

#: trace classes that build a jaxpr instead of executing — values under
#: them are abstract, so host-clock instants taken there are TRACE time
_ABSTRACT_TRACE_NAMES = frozenset({"DynamicJaxprTrace", "JaxprTrace"})


def under_abstract_trace() -> bool:
    """True when an abstract (jaxpr-building) trace is active on this
    thread — i.e. the code is being TRACED by ``jit``/``make_jaxpr``,
    not executed.  An *eager* ``shard_map`` body also runs under a trace
    (ShardMapTrace), but its values are concrete per-device arrays and
    its wall clock is real execution time, so the ``parent_trace`` chain
    is walked for a jaxpr-building trace instead."""
    from jax._src.core import trace_ctx

    trace = trace_ctx.trace
    hops = 0
    while trace is not None and hops < 16:
        if type(trace).__name__ in _ABSTRACT_TRACE_NAMES:
            return True
        trace = getattr(trace, "parent_trace", None)
        hops += 1
    return False


def concrete_leaf(leaf):
    """The concrete array under ``leaf``, or ``None`` if it is abstract.

    Eager shard_map values arrive as ``ShardMapTracer(ArrayImpl)`` whose
    ``.val`` chain bottoms out at a blockable concrete array; under an
    abstract trace the chain ends at a valueless tracer instead."""
    v = leaf
    hops = 0
    while v is not None and hops < 16:
        try:
            if hasattr(v, "block_until_ready"):
                return v
            v = getattr(v, "val", None)
        except Exception:  # noqa: BLE001 — tracer attr access can raise
            return None
        hops += 1
    return None


def cure_interpret_device_barrier() -> None:
    """Make the TPU interpreter's per-device barrier take a host int.

    jax 0.9.0 hands ``SharedMemory.update_clocks_for_device_barrier`` the
    ``device_id`` as a ``jax.Array`` and multiplies it there, which
    dispatches a JAX computation from inside an ``io_callback`` thread.
    With three or more ranks doing that at once every thread waits on the
    others for ever (the 4-rank fused kernel; near-zero CPU, all threads
    at ``shared_memory.py:589``).  Every other callback of the interpreter
    casts with ``int(device_id)`` first; this does the same.  Idempotent;
    interpret mode only — nothing here is on a compiled path."""
    from jax._src.pallas.mosaic.interpret import shared_memory

    cls = shared_memory.SharedMemory
    orig = cls.update_clocks_for_device_barrier
    if getattr(orig, "_flashmoe_cured", False):
        return

    def update_clocks_for_device_barrier(self, device_id):
        return orig(self, int(device_id))

    update_clocks_for_device_barrier._flashmoe_cured = True
    cls.update_clocks_for_device_barrier = update_clocks_for_device_barrier
