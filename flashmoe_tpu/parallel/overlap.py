"""Overlap-efficiency measurement.

The reference's headline metric (``/root/reference/README.md:33-35``,
``plots/overlap_efficiency_8.png``) quantifies how much of the dispatch/
combine communication the fused kernel hides behind expert compute.  Here
the metric is defined operationally, on any ``ep`` mesh:

    overlap_efficiency = (t_compute_only + t_comm_only) / t_overlapped

  * ``t_overlapped``   — the full MoE layer on the measured path (fused
    Pallas RDMA kernel or the XLA-collective layer);
  * ``t_compute_only`` — the same layer with both all-to-alls elided
    (identical gate/dispatch/FFN/combine stages and shapes);
  * ``t_comm_only``    — the two all-to-alls alone on identically shaped
    slabs, with no FFN between them.

A value of 1.0 means fully serialized (no overlap); the upper bound
``(a+b)/max(a,b)`` (= 2.0 when legs are balanced) means one leg fully
hidden behind the other.  The same procedure runs on a real v5e-8 and on
the virtual 8-device CPU mesh (where it validates the harness, not the
hardware — XLA's CPU collectives are memcpys).

Timing uses chained in-jit iterations (two chain lengths, differenced),
which takes dispatch and readback out of the per-iteration time.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.reference import init_moe_params
from flashmoe_tpu.parallel.ep import ep_moe_layer, local_capacity
from flashmoe_tpu.parallel.fused import fused_ep_moe_layer


def _comm_only(x, cfg: MoEConfig, mesh: Mesh, *, path: str = "collective"):
    """Both all-to-alls on path-shaped slabs, no compute between —
    capacity slabs for the collective/fused paths, routed-row slabs for
    the ragged path.  With ``cfg.a2a_chunks = n`` each leg runs as n
    smaller exchanges (the pipeline's wire schedule, per-message alpha
    included), so the comm leg measures what the chunked schedule
    actually pays."""
    n = cfg.a2a_chunks or 1

    def body(x):
        d = jax.lax.axis_size("ep")
        s_loc, h = x.shape
        if path == "ragged":
            # uniform-routing expectation: s_loc * k routed rows split
            # evenly over the d peers
            r = max(s_loc * cfg.expert_top_k // d, 1)
        else:
            r = (cfg.num_experts // d) * local_capacity(cfg, s_loc)
        rp = -(-r // n) * n  # rows per dest, padded to the chunk count
        src = (jnp.arange(d * rp, dtype=jnp.int32) % s_loc)
        slab = x[src].reshape(d, rp, h)
        outs = []
        for k in range(n):
            c = slab[:, k * (rp // n):(k + 1) * (rp // n)]
            c = jax.lax.all_to_all(
                c, "ep", split_axis=0, concat_axis=0, tiled=False
            )
            c = jax.lax.all_to_all(
                c, "ep", split_axis=0, concat_axis=0, tiled=False
            )
            outs.append(c)
        back = outs[0] if n == 1 else jnp.concatenate(outs, axis=1)
        # feed the payload back as the next chain input (data dependency —
        # nothing for XLA to dead-code-eliminate)
        return back.reshape(d * rp, h)[:s_loc]

    return jax.shard_map(
        body, mesh=mesh, in_specs=P("ep", None), out_specs=P("ep", None),
        check_vma=False,
    )(x)


def _time_chained(fn, x, *, trials: int, chain: int):
    """Median seconds per application via two-chain-length differencing."""

    def chained(n):
        def run(x0):
            def step(c, _):
                return fn(c).astype(x0.dtype), None
            c, _ = jax.lax.scan(step, x0, None, length=n)
            return c.astype(jnp.float32).sum()
        return jax.jit(run)

    def median_time(f):
        float(f(x))  # compile + warm
        ts = []
        for _ in range(trials):
            t0 = time.perf_counter()
            float(f(x))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    t1 = median_time(chained(1))
    tn = median_time(chained(chain))
    return max(tn - t1, 1e-9) / (chain - 1)


def measure_overlap(cfg: MoEConfig, mesh: Mesh, *, path: str = "fused",
                    trials: int = 5, chain: int = 8,
                    interpret: bool = False, seed: int = 0,
                    a2a_chunks: int | None = None) -> dict:
    """Measure the three legs and the efficiency ratio on ``mesh``.

    ``path``: 'fused' (Pallas RDMA kernel), 'collective' (XLA layer) or
    'ragged' (dropless row exchanges).  ``a2a_chunks`` overrides
    ``cfg.a2a_chunks`` for the XLA transports — the chunked pipeline's
    measured efficiency is then directly comparable against
    :func:`chunked_overlap_bound`'s analytic one; the fused kernel
    ignores the knob (in-kernel per-slab overlap), so passing it with
    ``path='fused'`` is an error.
    Returns {t_overlapped_ms, t_compute_ms, t_comm_ms, overlap_efficiency}.
    """
    ep = mesh.shape["ep"]
    if cfg.num_experts % ep:
        raise ValueError(f"E={cfg.num_experts} not divisible by ep={ep}")
    if a2a_chunks is not None:
        if path == "fused":
            raise ValueError(
                "a2a_chunks applies to the XLA transports; the fused "
                "kernel overlaps in-kernel and ignores the knob")
        cfg = cfg.replace(a2a_chunks=None if a2a_chunks <= 1
                          else a2a_chunks)
    pk, xk = jax.random.split(jax.random.PRNGKey(seed))
    params = init_moe_params(pk, cfg)
    params = jax.tree_util.tree_map(lambda p: p.astype(cfg.dtype), params)
    x = jax.random.normal(xk, (cfg.tokens, cfg.hidden_size), cfg.dtype)

    if path not in ("fused", "collective", "ragged"):
        raise ValueError(f"unknown path {path!r}")
    if path == "ragged":
        from flashmoe_tpu.parallel.ragged_ep import ragged_ep_moe_layer

        layer = ragged_ep_moe_layer
    else:
        layer = ep_moe_layer

    def xla_layer(c, skip=False):
        return layer(params, c, cfg, mesh, use_pallas=interpret,
                     interpret=interpret, skip_exchange=skip).out

    if path == "fused":
        overlapped = lambda c: fused_ep_moe_layer(
            params, c, cfg, mesh, interpret=interpret).out
    else:
        overlapped = xla_layer
    compute_only = lambda c: xla_layer(c, skip=True)
    comm_path = "ragged" if path == "ragged" else "collective"
    comm_only = lambda c: _comm_only(c, cfg, mesh, path=comm_path)

    t_over = _time_chained(overlapped, x, trials=trials, chain=chain)
    t_comp = _time_chained(compute_only, x, trials=trials, chain=chain)
    t_comm = _time_chained(comm_only, x, trials=trials, chain=chain)
    return {
        "t_overlapped_ms": t_over * 1e3,
        "t_compute_ms": t_comp * 1e3,
        "t_comm_ms": t_comm * 1e3,
        "overlap_efficiency": (t_comp + t_comm) / t_over,
        "path": path,
        "ep": ep,
        "a2a_chunks": cfg.a2a_chunks or 1,
    }


def overlap_bound(cfg: MoEConfig, d: int, gen: str = "v5e", *,
                  links: int = 4, mxu_fraction: float = 1.0,
                  schedule: str | None = None,
                  fuse_combine: bool = False) -> dict:
    """Analytical expected overlap efficiency of the fused kernel's
    phase-1-all-sends schedule — the number a future hardware
    ``--overlap`` measurement is judged against instead of being read
    off in isolation (VERDICT r4 next #8; the reference's measured
    analogue is ``plots/overlap_efficiency_8.png``).

    Model (per rank, homogeneous ring of ``d`` ranks, uniform routing):

      C      FFN compute on the ``s_loc * k`` received rows at
             ``mxu_fraction`` of the generation's peak bf16 throughput
             (1.0 = roofline bound; pass the measured ``mxu_util`` for a
             calibrated expectation).
      t_x    egress serialization of phase 1: all (d-1)/d of the slab
             bytes leave at once over ``links`` ICI links
             (``topology._ICI_SPECS`` per-link GB/s).
      T      makespan, per FFN schedule (``_fused_schedule``):
             per_source — step 0 computes the own slab while remote
               slabs fly, step s>=1 waits slab s:
               T = max(C, t_x + C/d) + tail;
             batched / rowwin — the own slab (C/d) is the only compute
               that can hide arrivals; the remaining (d-1)/d of C runs
               after the last arrival (expert-major with VMEM-resident
               hidden for batched, K-windowed with the HBM accumulator
               for rowwin):
               T = max(C/d, t_x) + (d-1)/d * C + tail.
      tail   the last returns can only start after their compute
             finishes: per_source — the LAST SLAB's rows, t_x/(d-1);
             batched — the LAST EXPERT's rows (returns issue per expert
             after its pass 2), t_x/nlx, which is the coarser wait
             whenever nlx < d-1; rowwin — the last WINDOW finishes each
             row tile and returns it immediately, so only the final
             row tile's rows trail: t_x/(nlx * n_row_tiles), the
             finest return granularity of the batched-pass schedules
             (geometry from ``fused.schedule_table``).
      OE     (C + 2*t_x) / T  — the operational metric's numerator is
             the serialized sum of the compute-only leg and BOTH
             all-to-alls (x out, y back).

    ``schedule=None`` resolves the kernel's actual default for this
    (cfg, d) — pass ``fuse_combine`` matching the run (the combine's
    VMEM claim can flip the schedule gate) so the reported bound
    describes the code path that will run.  Latency (alpha) terms are
    dropped: at slab sizes of MBs they are <1% of the beta terms.
    Returns every intermediate so tests can assert the pieces, not just
    the ratio.
    """
    from flashmoe_tpu.parallel.topology import chip_spec, ici_spec

    if schedule is None:
        from flashmoe_tpu.analysis import _geom

        schedule = _geom(cfg, d, fuse_combine=fuse_combine)["schedule"]
    # ValueError naming the supported generations for anything outside
    # {v4, v5e, v5p, v6e} — the planner calls this with arbitrary gen
    # strings, so it must fail cleanly
    peak_tflops, _ = chip_spec(gen)
    bw_link = ici_spec(gen)[1] * 1e9             # B/s one way per link
    dt = jnp.dtype(cfg.dtype).itemsize
    s_loc = cfg.tokens // d
    rows = s_loc * cfg.expert_top_k
    gemms = 3 if cfg.gated_ffn else 2
    flops = gemms * 2.0 * rows * cfg.hidden_size * cfg.intermediate_size
    c_s = flops / (peak_tflops * 1e12 * mxu_fraction)
    b_dir = (d - 1) / d * rows * cfg.hidden_size * dt
    t_x = b_dir / (links * bw_link)
    nlx = max(cfg.num_experts // d, 1)
    if schedule == "batched":
        tail = t_x / nlx
        t_over = max(c_s / d, t_x) + (d - 1) / d * c_s + tail
        compute_bound = c_s / d >= t_x
    elif schedule == "rowwin":
        # batched-pass makespan with per-row-tile return granularity:
        # the last K-window finishes (and returns) one row tile at a
        # time, so only the final tile's rows trail the compute
        from flashmoe_tpu.parallel.fused import schedule_table

        n_row_tiles = schedule_table(cfg, d, fuse_combine=fuse_combine,
                                     schedule="rowwin")["n_row_tiles"]
        tail = t_x / max(nlx * n_row_tiles, 1)
        t_over = max(c_s / d, t_x) + (d - 1) / d * c_s + tail
        compute_bound = c_s / d >= t_x
    else:
        tail = t_x / max(d - 1, 1)
        t_over = max(c_s, t_x + c_s / d) + tail
        compute_bound = c_s >= t_x + c_s / d
    oe = (c_s + 2 * t_x) / t_over
    return {
        "schedule": schedule,
        "compute_ms": c_s * 1e3,
        "t_x_ms": t_x * 1e3,
        "tail_ms": tail * 1e3,
        "t_overlapped_ms": t_over * 1e3,
        "overlap_efficiency_bound": oe,
        "compute_bound": compute_bound,
    }


def chunked_overlap_bound(cfg: MoEConfig, d: int, gen: str = "v5e",
                          chunks: int = 1, *, links: int = 4,
                          mxu_fraction: float = 1.0,
                          path: str = "collective") -> dict:
    """Analytical expected overlap efficiency of the chunked
    double-buffered XLA-transport pipeline (``MoEConfig.a2a_chunks``) —
    the number a :func:`measure_overlap` reading of the chunked
    schedule is judged against, the way :func:`overlap_bound` anchors
    the fused kernel's measurement.

    Model (per rank, uniform routing): FFN compute ``C`` on the
    ``s_loc * k`` routed rows at ``mxu_fraction`` of peak; per-leg wire
    serialization at the leg's wire row size with ``chunks`` messages
    per peer (alpha x chunks — ``analysis.a2a_transport_cost``'s
    chunking rule); makespan ``T`` from
    ``analysis.chunked_pipeline_ms``.  The efficiency mirrors the
    operational metric exactly:

        OE = (C + E(n)) / T(n)     (serial + both chunked legs over
                                    the pipelined makespan)

    so ``chunks=1`` gives exactly 1.0 (fully serialized) and the upper
    bound is ``measure_overlap``'s ``(a+b)/max(a,b)`` shape.  ``path``
    prices capacity slabs ('collective') or routed rows ('ragged').
    Returns every intermediate so tests can assert the pieces."""
    from flashmoe_tpu.analysis import chunked_pipeline_ms, wire_row_bytes
    from flashmoe_tpu.parallel.topology import chip_spec, ici_spec

    if chunks < 1:
        raise ValueError(f"chunks={chunks} must be >= 1")
    if path not in ("collective", "ragged"):
        raise ValueError(
            f"unknown chunked path {path!r}; the fused kernel has its "
            f"own bound (overlap_bound)")
    peak_tflops, _ = chip_spec(gen)   # ValueError on unknown gen
    a_us, gbps = ici_spec(gen)
    a_ms = a_us / 1e3
    bw_ms = gbps * 1e6 * max(links, 1)            # B/ms, striped
    mxu_fraction = max(min(mxu_fraction, 1.0), 1e-6)
    s_loc = cfg.tokens // d
    rows = s_loc * cfg.expert_top_k
    gemms = 3 if cfg.gated_ffn else 2
    flops = gemms * 2.0 * rows * cfg.hidden_size * cfg.intermediate_size
    c_ms = flops / (peak_tflops * 1e9 * mxu_fraction)  # TFLOP/s -> /ms
    if path == "ragged":
        slab_rows = rows / d
    else:
        slab_rows = (cfg.num_experts // d) * local_capacity(cfg, s_loc)
    leg = lambda which: (d - 1) * (
        chunks * a_ms + slab_rows * wire_row_bytes(cfg, which) / bw_ms)
    e_d, e_c = leg("dispatch"), leg("combine")
    t = chunked_pipeline_ms(c_ms, e_d, e_c, chunks)
    serial = c_ms + e_d + e_c
    return {
        "chunks": chunks,
        "path": path,
        "compute_ms": c_ms,
        "leg_dispatch_ms": e_d,
        "leg_combine_ms": e_c,
        "serial_ms": serial,
        "t_overlapped_ms": t,
        "overlap_efficiency_bound": serial / t,
    }
