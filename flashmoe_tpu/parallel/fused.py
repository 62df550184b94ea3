"""Fused expert-parallel MoE: device-initiated all-to-all inside the kernel,
overlapped with the expert FFN — the FlashDMoE headline capability on TPU.

The reference fuses dispatch -> expert GEMMs -> combine-return into one
persistent CUDA kernel in which NVSHMEM puts carry expert payloads between
GPUs while tile processors compute (``csrc/include/flashmoe/moe/moe.cuh:
71-144``; transport in ``os/packet.cuh:207-259`` and
``os/processor/processor.cuh:711-751``; the in-kernel actor scheduler in
``os/scheduler.cuh``/``subscriber.cuh`` exists to keep SMs busy while
payloads are in flight).

On TPU the same capability is a single Pallas kernel per rank, shard_mapped
over the ``ep`` mesh axis:

  * phase 0 — a cross-device barrier (each rank signals every peer), the
    analogue of the symmetric-heap readiness the reference gets from
    collective allocation (``bootstrap.cuh:347-362``);
  * phase 1 — every rank starts ALL its outbound slab RDMAs at once
    (``make_async_remote_copy``, non-blocking — the analogue of
    ``nvshmem_putmem_signal_nbi``), staggered by rank so the ICI links are
    used all-to-all rather than all-to-one;
  * phase 2 — one grid step per source rank, in ring arrival order: wait
    that source's recv semaphore (the data-carrying signal of the
    reference's ``SignalPayload``), run the local experts' up/act/down
    GEMM chain on arrived rows, and RDMA the results back to the source.
    Compute overlaps the in-flight transfers of later slabs —
    payload-granularity overlap, which is the paper's core claim.  FOUR
    FFN schedules (:func:`_fused_schedule`): per-source streaming,
    per-source weights-resident, the arrival-batched default at
    ep >= 3 — own slab computed at step 0 while remote slabs fly, all
    remote slabs computed expert-major at the final step so each weight
    byte streams twice total instead of once per source (the round-5
    cost model showed the per-source schedules' d x weight re-streaming
    dominates every other byte at multi-chip scale) —
    and the row-windowed ``rowwin`` schedule for experts too wide for
    any weights-once residency (mixtral's i=14336): weights stream in
    VMEM-sized K-windows, window-major / row-minor, partial sums parked
    in an HBM f32 accumulator, bounding weight traffic at ~2 streams
    total at the cost of per-window activation re-streaming (ISSUE 12 /
    ROADMAP item 4; tiles picked by the IO-aware chooser
    :func:`_rowwin_tiles`, overridable by measured ``fused_tiles``
    tuning entries);
  * phase 2.5 — in-kernel combine: result rows return via RDMA directly
    into a TOKEN-SORTED buffer (each occupied slab slot is pre-assigned
    the row ``token*k + j`` XLA-side, :func:`flashmoe_tpu.ops.dispatch.
    sorted_return_maps`), so after the drain the combine is one fully
    vectorized pass of ``k``-row segment-sums — no per-row scatter (the
    round-4 implementation accumulated S*K rows one dynamic-slice add at
    a time, estimated as expensive as the whole layer; VERDICT r4 #3).
    The cost moved from the VPU to the DMA engine: per-ROW return copies
    instead of per-tile, ~cap row-DMA issues per (source, expert) that
    overlap the next slab's GEMMs.  This is the reference's combine
    stage (``os/processor/processor.cuh:27-205``) with the atomicAdd
    replaced by disjoint pre-assigned rows + deterministic segment-sum.
    Opt-in via ``FLASHMOE_FUSED_COMBINE=1`` until hardware-benchmarked
    (the open question is per-row RDMA issue/landing efficiency on real
    ICI), requires ep > 1 (at world 1 there is no communication to
    overlap and the per-row copies are pure overhead), and falls back to
    the XLA combine when the maps/tiles would not fit VMEM/SMEM
    (:func:`_fuse_combine_enabled`).
  * phase 3 — drain: wait all remaining send semaphores (row-granular on
    the return path when the combine is fused), then run the combine
    segment-sum if fused.

Gate/plan/dispatch-layout stay in XLA (bandwidth-trivial next to the FFN);
the kernel owns the communication-heavy middle plus the combine.
Capacity-format slabs keep every shape static.

Design decision — why the send slabs are built XLA-side rather than
gathered in-kernel (the reference gathers from ``tokenIds`` inside the
kernel, ``packet.cuh:99-206``): the reference hides per-row staging
latency behind hundreds of concurrently-resident SM blocks; a TPU kernel
is one sequential instruction stream, and this kernel's phase 1 issues
every outbound RDMA up front so remote compute can start.  An in-kernel
row gather there would pay per-row DMA-issue latency serially before any
send departs (~50-100 ns x S*K rows, with no compute to hide behind),
whereas the XLA dispatch builds the same slabs at full VPU/HBM bandwidth
and the RDMAs then stream straight from HBM with no VMEM bounce.  The
single-device path, whose gather IS overlappable with the grid's own
GEMMs, does fuse it (``ops/expert.py:grouped_ffn_tokens``).

Layouts (D = ep world, nLx = local experts, C = per-(rank, expert) capacity):
  x_send  [D, nLx, C, H]  on each source rank: slab d holds tokens routed
                          to rank d's local experts (dest-major).
  x_recv  [D, nLx, C, H]  on each dest rank: slab s is written remotely by
                          source rank s (source-major).
  y_recv  [D, nLx, C, H]  back on the source rank: slab d holds results
                          from owner rank d — exactly the [E, C, H] combine
                          layout after reshape.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.reference import activation_fn, shared_expert_ffn
from flashmoe_tpu.ops import dispatch as dsp
from flashmoe_tpu.ops import stats as st
from flashmoe_tpu.ops.gate import router
from flashmoe_tpu.ops.moe import MoEOutput
from flashmoe_tpu.parallel.ep import local_capacity
from flashmoe_tpu.profiler import spans as prof
from flashmoe_tpu.utils.telemetry import trace_span


def _fused_kernel(
    send_cnt, recv_cnt,                   # SMEM int32 [D, nLx] tile counts
    src_order,                            # SMEM int32 [D, D] processing order
    recv_pos,                             # SMEM int32 [D, nLx, cap] sorted
                                          #   return rows (None = XLA combine)
    w_sorted,                             # ANY [rows_pad, 1] f32 weights
    x_send, w_up, b_up, w_down, b_down,   # inputs (ANY/VMEM)
    wup_sc, wdn_sc,                       # VMEM f32 per-output-channel
                                          #   scales of a quantized
                                          #   weight store ([nLx, I or
                                          #   2I] / [nLx, H]; None at
                                          #   full precision)
    x_recv, y_back, y_stage, out,         # outputs (y_back: the [D,nLx,C,H]
                                          #   slab y_recv, or the token-sorted
                                          #   [rows_pad, H] return buffer when
                                          #   fusing; out: [s_out_pad, H] f32,
                                          #   None when combine stays in XLA)
    acc_hbm,                              # [D, nLx, C, H] f32 HBM partial
                                          #   sums of the rowwin window
                                          #   loop (None otherwise)
    xs_vmem, wup_vmem, wdn_vmem, acc, yv, # VMEM scratch (wdn/acc/yv are
                                          #   [2,bi,h]/[cm,h]/[cm,h] when
                                          #   streaming, [2,i,bh]/[cm,bh]/
                                          #   [cm,bh] on the resident/
                                          #   batched schedules)
    bup_vmem, bdn_vmem,                   # bias tiles
    ys_vmem, ws_vmem, ov_vmem,            # combine chunk tiles (None w/o
                                          #   fusion): y rows, weight col,
                                          #   out rows
    hid_vmem,                             # [n_i_chunks, n_srcs*cap, bi]
                                          #   resident hidden (None when
                                          #   streaming)
    copy_sems, send_x_sems, recv_x_sems, send_y_sems, recv_y_sems,
    *, axis, act_name, cm, bi, gated, fuse_combine, k, cu,
    schedule, bh, quant=False,
):
    """One grid step = one source slab (ring order).

    Transfers are tile-granular and count-aware: both sides share the
    routed-count matrices (exchanged XLA-side), so only row tiles that
    actually hold tokens are sent, waited on, computed, and returned —
    the TPU form of the reference's ``routedTokens``-sized packets and
    zero-token noop signals (``packet.cuh:99-259``), with the noop made
    unnecessary because counts are pre-shared.

    With ``fuse_combine`` the weighted un-permute also runs in-kernel
    (the reference's combine stage, ``processor.cuh:27-205``): result
    rows are returned by per-ROW RDMA into the destination rank's
    token-sorted buffer ``y_back`` at the pre-assigned row
    ``recv_pos[src, e, slot]`` (= token*k + j on the source), so the
    final combine is ``n_chunks`` vectorized ``k``-row segment-sums with
    zero per-row VPU work.  ``k`` is the top-k width, ``cu`` the number
    of output rows per combine chunk (both static).
    """
    s = pl.program_id(0)
    d_world = pl.num_programs(0)
    my = jax.lax.axis_index(axis)
    nlx, cap, h = x_send.shape[1], x_send.shape[2], x_send.shape[3]
    act = activation_fn(act_name)
    n_row_tiles = cap // cm
    n_i_chunks = w_down.shape[1] // bi

    def tiles_of(cnt):
        """Present row tiles for a (rank, expert) count."""
        return jax.lax.div(cnt + (cm - 1), cm)

    # ---- phase 0/1 (first step only): barrier, then start every send ----
    @pl.when(s == 0)
    def _():
        def signal_peer(d, c):
            @pl.when(d != my)
            def _():
                pltpu.semaphore_signal(
                    barrier, inc=1, device_id=d,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )
            return c

        # a world of one has no peer to meet: waiting for zero signals
        # on a semaphore nobody signalled is refused by the interpreter
        # of jax 0.9.0 (an assert in shared_memory.wait)
        if x_send.shape[0] > 1:
            barrier = pltpu.get_barrier_semaphore()
            jax.lax.fori_loop(0, d_world, signal_peer, 0)
            pltpu.semaphore_wait(barrier, d_world - 1)

        def send(step, c):
            dst = jax.lax.rem(my + step + 1, d_world)

            def per_expert(e, c2):
                nt = tiles_of(send_cnt[dst, e])

                # fast path: full expert block in one DMA descriptor when
                # every tile is present (semaphore waits count bytes, so
                # the decomposition on the wait side need not match)
                @pl.when(nt == n_row_tiles)
                def _():
                    pltpu.make_async_remote_copy(
                        src_ref=x_send.at[dst, e],
                        dst_ref=x_recv.at[my, e],
                        send_sem=send_x_sems.at[dst],
                        recv_sem=recv_x_sems.at[my],
                        device_id=dst,
                        device_id_type=pltpu.DeviceIdType.LOGICAL,
                    ).start()

                @pl.when(nt < n_row_tiles)
                def _():
                    def per_tile(t, c3):
                        @pl.when(t < nt)
                        def _():
                            pltpu.make_async_remote_copy(
                                src_ref=x_send.at[dst, e,
                                                  pl.ds(t * cm, cm), :],
                                dst_ref=x_recv.at[my, e,
                                                  pl.ds(t * cm, cm), :],
                                send_sem=send_x_sems.at[dst],
                                recv_sem=recv_x_sems.at[my],
                                device_id=dst,
                                device_id_type=pltpu.DeviceIdType.LOGICAL,
                            ).start()
                        return c3

                    jax.lax.fori_loop(0, n_row_tiles, per_tile, 0)
                return c2

            jax.lax.fori_loop(0, nlx, per_expert, 0)
            return c

        jax.lax.fori_loop(0, d_world - 1, send, 0)
        # own slab: plain local copy (full; local bandwidth is cheap)
        own = pltpu.make_async_copy(
            x_send.at[my], x_recv.at[my], copy_sems.at[0]
        )
        own.start()
        own.wait()

    # ---- phase 2: process source slabs in expected-arrival order ----
    # ``src_order[my]`` is a permutation of sources starting with ``my``
    # (the own slab is local and ready immediately).  The default is ring
    # order (src_order[r, s] = (r+s) mod D), which IS arrival order on a
    # homogeneous ICI torus because phase 1 staggers sends by ring
    # distance.  On heterogeneous fabrics (multi-slice: some sources
    # behind a DCN hop) the caller passes
    # :func:`flashmoe_tpu.parallel.topology.arrival_order`, which sorts
    # sources by predicted alpha-beta arrival time — the static
    # equivalent of the reference subscriber consuming packets in
    # whatever order they land (``os/subscriber.cuh:333-451``); Mosaic
    # semaphores have no try-wait, so the order is bound at trace time
    # from the measured topology instead of polled at run time.
    # Correctness never depends on the order: every slab's recv
    # semaphore is awaited before use (see scripts/skew_sim.py for the
    # quantified cost of a mispredicted order).
    src = src_order[my, s]

    @pl.when(s != 0)
    def _():
        # wait for exactly the tiles this source sent (tile-sized waits
        # against the data-carrying recv semaphore)
        def per_expert(e, c):
            def per_tile(t, c2):
                @pl.when(t < tiles_of(recv_cnt[src, e]))
                def _():
                    pltpu.make_async_copy(
                        x_recv.at[src, e, pl.ds(t * cm, cm), :],
                        x_recv.at[src, e, pl.ds(t * cm, cm), :],
                        recv_x_sems.at[src],
                    ).wait()
                return c2

            return jax.lax.fori_loop(0, n_row_tiles, per_tile, c)

        jax.lax.fori_loop(0, nlx, per_expert, 0)

    def expert_body(e, _):
        # stream this expert's biases once
        # biases arrive lifted to [nLx, 1, dim]: ``.at[e]`` is then a
        # whole (1, dim) slab — a one-row ``pl.ds(e, 1)`` slice of a
        # [nLx, dim] ref is off the (8, 128) tiling and Mosaic refuses it
        bup_dma = pltpu.make_async_copy(
            b_up.at[e], bup_vmem, copy_sems.at[0]
        )
        bdn_dma = pltpu.make_async_copy(
            b_down.at[e], bdn_vmem, copy_sems.at[1]
        )
        bup_dma.start(); bdn_dma.start()
        bup_dma.wait(); bdn_dma.wait()

        # gated mode: w_up holds [gate_chunk | up_chunk] interleaved on a
        # doubled chunk axis (see fused_ep_moe_layer), so one DMA streams
        # both halves of the SwiGLU
        up_chunk = 2 * bi if gated else bi

        # weight-chunk DMA descriptors, double-buffered over two VMEM slots
        # (sems 2+slot / 4+slot): chunk j+1 streams HBM->VMEM while chunk j
        # runs on the MXU — the reference's multistage cp.async operand
        # pipeline (``mmaConfig.cuh:19-171``) expressed as slot-alternating
        # async copies.
        def wu_dma(j, slot):
            return pltpu.make_async_copy(
                w_up.at[e, :, pl.ds(j * up_chunk, up_chunk)],
                wup_vmem.at[slot], copy_sems.at[2 + slot],
            )

        def wd_dma(j, slot):
            return pltpu.make_async_copy(
                w_down.at[e, pl.ds(j * bi, bi), :],
                wdn_vmem.at[slot], copy_sems.at[4 + slot],
            )

        def send_back(sq, t):
            """Return tile t of source ``sq``'s finished rows —
            tile-granular into the slab buffer, or per-ROW into the
            token-sorted buffer when the combine is fused (rows of one
            token land disjointly: pos = token*k + j is unique per slot,
            so there are no write conflicts to order).  Issued
            immediately after the rows exist; y_stage is indexed by the
            source, so later steps never overwrite a slab whose
            asynchronous return is still in flight."""
            if not fuse_combine:
                @pl.when(sq != my)
                def _():
                    pltpu.make_async_remote_copy(
                        src_ref=y_stage.at[sq, e, pl.ds(t * cm, cm), :],
                        dst_ref=y_back.at[my, e, pl.ds(t * cm, cm), :],
                        send_sem=send_y_sems.at[sq],
                        recv_sem=recv_y_sems.at[my],
                        device_id=sq,
                        device_id_type=pltpu.DeviceIdType.LOGICAL,
                    ).start()
            else:
                rows_here = jnp.minimum(cm, recv_cnt[sq, e] - t * cm)

                @pl.when(sq != my)
                def _():
                    def ret_row(r, c3):
                        @pl.when(r < rows_here)
                        def _():
                            pos = recv_pos[sq, e, t * cm + r]
                            pltpu.make_async_remote_copy(
                                src_ref=y_stage.at[sq, e,
                                                   pl.ds(t * cm + r, 1), :],
                                dst_ref=y_back.at[pl.ds(pos, 1), :],
                                send_sem=send_y_sems.at[sq],
                                recv_sem=recv_y_sems.at[my],
                                device_id=sq,
                                device_id_type=pltpu.DeviceIdType.LOGICAL,
                            ).start()
                        return c3

                    jax.lax.fori_loop(0, cm, ret_row, 0)

                @pl.when(sq == my)
                def _():
                    def ret_row_local(r, c3):
                        @pl.when(r < rows_here)
                        def _():
                            pos = recv_pos[sq, e, t * cm + r]
                            pltpu.make_async_copy(
                                y_stage.at[sq, e, pl.ds(t * cm + r, 1), :],
                                y_back.at[pl.ds(pos, 1), :],
                                recv_y_sems.at[my],
                            ).start()
                        return c3

                    jax.lax.fori_loop(0, cm, ret_row_local, 0)

        def row_tile_body(t, carry):
            xd = pltpu.make_async_copy(
                x_recv.at[src, e, pl.ds(t * cm, cm), :],
                xs_vmem, copy_sems.at[0],
            )
            xd.start()
            wu_dma(0, 0).start()
            wd_dma(0, 0).start()
            xd.wait()
            acc[:] = jnp.zeros_like(acc)

            def chunk_body(j, carry_c):
                slot = jax.lax.rem(j, 2)

                @pl.when(j + 1 < n_i_chunks)
                def _prefetch():
                    wu_dma(j + 1, 1 - slot).start()
                    wd_dma(j + 1, 1 - slot).start()

                wu_dma(j, slot).wait()
                if gated:
                    g = jnp.dot(
                        xs_vmem[:], wup_vmem[slot, :, :bi],
                        preferred_element_type=jnp.float32,
                    )
                    up = jnp.dot(
                        xs_vmem[:], wup_vmem[slot, :, bi:],
                        preferred_element_type=jnp.float32,
                    ) + bup_vmem[0, pl.ds(j * bi, bi)].astype(jnp.float32)
                    hidden = (act(g) * up).astype(xs_vmem.dtype)
                else:
                    up = jnp.dot(
                        xs_vmem[:], wup_vmem[slot],
                        preferred_element_type=jnp.float32,
                    ) + bup_vmem[0, pl.ds(j * bi, bi)].astype(jnp.float32)
                    hidden = act(up).astype(xs_vmem.dtype)
                wd_dma(j, slot).wait()
                acc[:] += jnp.dot(
                    hidden, wdn_vmem[slot],
                    preferred_element_type=jnp.float32,
                )
                return carry_c

            jax.lax.fori_loop(0, n_i_chunks, chunk_body, 0)
            yv[:] = (
                acc[:] + bdn_vmem[0].astype(jnp.float32)
            ).astype(yv.dtype)
            st = pltpu.make_async_copy(
                yv, y_stage.at[src, e, pl.ds(t * cm, cm), :], copy_sems.at[0]
            )
            st.start()
            st.wait()
            send_back(src, t)
            return carry

        def resident_expert(first_q, n_srcs):
            """Weights-once two-pass schedule over the sources
            ``src_order[my, first_q : first_q + n_srcs]`` — each weight
            byte streams exactly once for ALL their rows (the reference's
            operand-pipeline reuse, ``mmaConfig.cuh:19-171``, applied
            across row tiles AND sources):

              pass 1  w_up chunk j resident (double-buffered) -> every
                      present row tile of every source streams through
                      it; activated hidden chunks land in the chunk-major
                      VMEM slab ``hid_vmem [n_i_chunks, n_srcs*cap, bi]``
                      (chunk-major so writes index a leading dim — Mosaic
                      restricts dynamic LANE offsets, not major-dim ones).
              pass 2  w_down COLUMN chunk c ([i, bh]) resident -> each
                      row tile contracts its resident hidden against it
                      chunk-by-chunk; output block written once, no
                      cross-chunk accumulator in HBM.

            Used two ways: per-source (``n_srcs=1``; kills the
            n_row_tiles x weight factor, VERDICT r4 weak #4) and
            arrival-batched over all remote sources at the final grid
            step (``n_srcs=d-1``; kills the per-source d x weight factor
            the round-5 cost model exposed — the schedule that makes the
            fused path competitive at multi-chip scale).  The trade: x
            re-streams once per i-chunk, and returns are issued per tile
            only after pass 2 (a tile's rows complete once every column
            chunk lands), so return overlap degrades to per-expert
            granularity — both priced in flashmoe_tpu/analysis.py."""
            n_h_chunks = h // bh

            def src_of(q):
                return src_order[my, first_q + q]

            def wdc_dma(c, slot):
                return pltpu.make_async_copy(
                    w_down.at[e, :, pl.ds(c * bh, bh)],
                    wdn_vmem.at[slot], copy_sems.at[4 + slot],
                )

            # ---- pass 1: up/act, weight-chunk outer, hidden resident ----
            wu_dma(0, 0).start()

            def up_chunk_body(j, carry_c):
                slot = jax.lax.rem(j, 2)

                @pl.when(j + 1 < n_i_chunks)
                def _prefetch():
                    wu_dma(j + 1, 1 - slot).start()

                wu_dma(j, slot).wait()

                def src_body(q, c1):
                    sq = src_of(q)
                    ntq = tiles_of(recv_cnt[sq, e])

                    def tile_body(t, c2):
                        @pl.when(t < ntq)
                        def _():
                            xd = pltpu.make_async_copy(
                                x_recv.at[sq, e, pl.ds(t * cm, cm), :],
                                xs_vmem, copy_sems.at[0],
                            )
                            xd.start()
                            xd.wait()
                            if gated:
                                g = jnp.dot(
                                    xs_vmem[:], wup_vmem[slot, :, :bi],
                                    preferred_element_type=jnp.float32,
                                )
                                up = jnp.dot(
                                    xs_vmem[:], wup_vmem[slot, :, bi:],
                                    preferred_element_type=jnp.float32,
                                ) + bup_vmem[0, pl.ds(j * bi, bi)].astype(
                                    jnp.float32)
                                hidden = (act(g) * up).astype(
                                    xs_vmem.dtype)
                            else:
                                up = jnp.dot(
                                    xs_vmem[:], wup_vmem[slot],
                                    preferred_element_type=jnp.float32,
                                ) + bup_vmem[0, pl.ds(j * bi, bi)].astype(
                                    jnp.float32)
                                hidden = act(up).astype(xs_vmem.dtype)
                            hid_vmem[j, pl.ds(q * cap + t * cm, cm), :] = \
                                hidden
                        return c2

                    return jax.lax.fori_loop(0, n_row_tiles, tile_body, c1)

                jax.lax.fori_loop(0, n_srcs, src_body, 0)
                return carry_c

            jax.lax.fori_loop(0, n_i_chunks, up_chunk_body, 0)

            # ---- pass 2: down proj, output-column chunks, wd once ----
            wdc_dma(0, 0).start()

            def col_body(c, carry_c):
                slot = jax.lax.rem(c, 2)

                @pl.when(c + 1 < n_h_chunks)
                def _prefetch():
                    wdc_dma(c + 1, 1 - slot).start()

                wdc_dma(c, slot).wait()

                def src_body(q, c1):
                    sq = src_of(q)
                    ntq = tiles_of(recv_cnt[sq, e])

                    def tile_body(t, c2):
                        @pl.when(t < ntq)
                        def _():
                            acc[:] = jnp.zeros_like(acc)

                            def contract(j, c3):
                                acc[:] += jnp.dot(
                                    hid_vmem[j,
                                             pl.ds(q * cap + t * cm, cm),
                                             :],
                                    wdn_vmem[slot, pl.ds(j * bi, bi), :],
                                    preferred_element_type=jnp.float32,
                                )
                                return c3

                            jax.lax.fori_loop(0, n_i_chunks, contract, 0)
                            yv[:] = (
                                acc[:]
                                + bdn_vmem[0, pl.ds(c * bh, bh)].astype(
                                    jnp.float32)
                            ).astype(yv.dtype)
                            st = pltpu.make_async_copy(
                                yv,
                                y_stage.at[sq, e, pl.ds(t * cm, cm),
                                           pl.ds(c * bh, bh)],
                                copy_sems.at[0],
                            )
                            st.start()
                            st.wait()
                        return c2

                    return jax.lax.fori_loop(0, n_row_tiles, tile_body, c1)

                jax.lax.fori_loop(0, n_srcs, src_body, 0)
                return carry_c

            jax.lax.fori_loop(0, n_h_chunks, col_body, 0)

            # ---- returns: every column chunk of a tile has landed ----
            def src_ret(q, c1):
                sq = src_of(q)
                ntq = tiles_of(recv_cnt[sq, e])

                def ret_tile(t, c2):
                    @pl.when(t < ntq)
                    def _():
                        send_back(sq, t)
                    return c2

                return jax.lax.fori_loop(0, n_row_tiles, ret_tile, c1)

            jax.lax.fori_loop(0, n_srcs, src_ret, 0)

        def rowwin_expert(first_q, n_srcs):
            """Row-windowed K-streamed schedule, WINDOW-major / row-minor
            (ISSUE 12 / ROADMAP item 4; SonicMoE's IO-aware stance,
            arXiv 2512.14080): the expert's weights stream along the
            intermediate dimension in ``bi``-wide VMEM windows (w_up
            columns + the matching w_down rows, double-buffered), and
            every present row tile of EVERY source in the pass flows
            through the resident window before the next is fetched —
            so each weight element streams once per pass, bounding
            weight traffic at ~2 streams total (own-slab pass at step 0
            + the arrival-batched remote pass at the final step)
            regardless of d or the row-tile count.  This is exactly the
            loop order naive row-windowing misses: a ROW-major window
            loop re-streams every window per row tile and degenerates
            to the stream schedule's bytes.

            The price is per-window activation re-streaming: each row
            tile re-reads its x tile per window and round-trips its f32
            partial sum through the HBM accumulator ``acc_hbm`` at every
            interior window boundary (the [cm, h] f32 state of ALL
            resident rows can never be VMEM-resident at the shapes this
            schedule exists for) — both priced in
            flashmoe_tpu/analysis.py.  The final window folds in the
            down bias, stages the finished tile, and issues its return
            immediately (per-TILE return granularity — finer than the
            batched schedule's per-expert returns)."""
            def src_of(q):
                return src_order[my, first_q + q]

            wu_dma(0, 0).start()
            wd_dma(0, 0).start()

            def win_body(j, carry_c):
                slot = jax.lax.rem(j, 2)

                @pl.when(j + 1 < n_i_chunks)
                def _prefetch():
                    wu_dma(j + 1, 1 - slot).start()
                    wd_dma(j + 1, 1 - slot).start()

                wu_dma(j, slot).wait()
                wd_dma(j, slot).wait()

                # quantized store (MoEConfig.expert_quant): the window
                # buffers hold int8/e4m3 payloads straight off HBM —
                # dequantize IN VMEM against the resident per-output-
                # channel f32 scales (w_up's channels are this window's
                # K columns; w_down's are the full H row), then compute
                # at the activation dtype exactly like the raw path.
                if quant:
                    up_cols = 2 * bi if gated else bi
                    wu_win = (
                        wup_vmem[slot].astype(jnp.float32)
                        * wup_sc[e, pl.ds(j * up_cols, up_cols)][None, :]
                    ).astype(xs_vmem.dtype)
                    wd_win = (
                        wdn_vmem[slot].astype(jnp.float32)
                        * wdn_sc[e, :][None, :]
                    ).astype(xs_vmem.dtype)
                else:
                    wu_win = wup_vmem[slot]
                    wd_win = wdn_vmem[slot]

                def src_body(q, c1):
                    sq = src_of(q)
                    ntq = tiles_of(recv_cnt[sq, e])

                    def tile_body(t, c2):
                        @pl.when(t < ntq)
                        def _():
                            xd = pltpu.make_async_copy(
                                x_recv.at[sq, e, pl.ds(t * cm, cm), :],
                                xs_vmem, copy_sems.at[0],
                            )
                            xd.start()

                            # resume this tile's partial sum (interior
                            # windows; window 0 starts from zero)
                            @pl.when(j > 0)
                            def _resume():
                                ad = pltpu.make_async_copy(
                                    acc_hbm.at[sq, e,
                                               pl.ds(t * cm, cm), :],
                                    acc, copy_sems.at[1],
                                )
                                ad.start()
                                ad.wait()

                            @pl.when(j == 0)
                            def _zero():
                                acc[:] = jnp.zeros_like(acc)

                            xd.wait()
                            if gated:
                                g = jnp.dot(
                                    xs_vmem[:], wu_win[:, :bi],
                                    preferred_element_type=jnp.float32,
                                )
                                up = jnp.dot(
                                    xs_vmem[:], wu_win[:, bi:],
                                    preferred_element_type=jnp.float32,
                                ) + bup_vmem[0, pl.ds(j * bi, bi)].astype(
                                    jnp.float32)
                                hidden = (act(g) * up).astype(
                                    xs_vmem.dtype)
                            else:
                                up = jnp.dot(
                                    xs_vmem[:], wu_win,
                                    preferred_element_type=jnp.float32,
                                ) + bup_vmem[0, pl.ds(j * bi, bi)].astype(
                                    jnp.float32)
                                hidden = act(up).astype(xs_vmem.dtype)
                            acc[:] += jnp.dot(
                                hidden, wd_win,
                                preferred_element_type=jnp.float32,
                            )

                            # interior windows park the partial sum in
                            # HBM; the last window finishes the tile and
                            # returns it immediately
                            @pl.when(j + 1 < n_i_chunks)
                            def _spill():
                                sd = pltpu.make_async_copy(
                                    acc,
                                    acc_hbm.at[sq, e,
                                               pl.ds(t * cm, cm), :],
                                    copy_sems.at[1],
                                )
                                sd.start()
                                sd.wait()

                            @pl.when(j + 1 == n_i_chunks)
                            def _finish():
                                yv[:] = (
                                    acc[:] + bdn_vmem[0].astype(
                                        jnp.float32)
                                ).astype(yv.dtype)
                                st2 = pltpu.make_async_copy(
                                    yv,
                                    y_stage.at[sq, e,
                                               pl.ds(t * cm, cm), :],
                                    copy_sems.at[0],
                                )
                                st2.start()
                                st2.wait()
                                send_back(sq, t)
                        return c2

                    return jax.lax.fori_loop(0, n_row_tiles, tile_body,
                                             c1)

                jax.lax.fori_loop(0, n_srcs, src_body, 0)
                return carry_c

            jax.lax.fori_loop(0, n_i_chunks, win_body, 0)

        def rows_present(first_q, n_srcs):
            """Total routed rows this expert holds across the sources —
            gates the weight streams so empty (source-set, expert) pairs
            never pay them (skewed-routing holes)."""
            def add(q, acc2):
                return acc2 + recv_cnt[src_order[my, first_q + q], e]

            return jax.lax.fori_loop(0, n_srcs, add, 0)

        # only the row tiles the step's source(s) actually routed here
        # (tiles_of(cnt) <= n_row_tiles by construction: counts are clamped
        # to cap and cap % cm == 0)
        if schedule in ("batched", "rowwin"):
            # own slab at step 0 (overlapping remote arrivals), every
            # remote source batched at the final step with weights
            # streamed once per pass (VMEM-resident hidden for batched,
            # K-windowed with the HBM accumulator for rowwin)
            pass_fn = (resident_expert if schedule == "batched"
                       else rowwin_expert)

            @pl.when((s == 0) & (rows_present(0, 1) > 0))
            def _own():
                pass_fn(0, 1)

            @pl.when((s == d_world - 1)
                     & (rows_present(1, d_world - 1) > 0))
            def _remote():
                pass_fn(1, d_world - 1)
        elif schedule == "resident":
            @pl.when(rows_present(s, 1) > 0)
            def _nonempty():
                resident_expert(s, 1)
        else:
            jax.lax.fori_loop(0, tiles_of(recv_cnt[src, e]), row_tile_body,
                              0)
        return _

    if schedule in ("batched", "rowwin"):
        # intermediate steps only consume arrivals (phase-2 waits above);
        # the expert loop runs at the endpoints
        @pl.when((s == 0) | (s == d_world - 1))
        def _():
            jax.lax.fori_loop(0, nlx, expert_body, 0)
    else:
        jax.lax.fori_loop(0, nlx, expert_body, 0)

    if not fuse_combine:
        @pl.when(src == my)
        def _():
            own = pltpu.make_async_copy(
                y_stage.at[src], y_back.at[my], copy_sems.at[0]
            )
            own.start()
            own.wait()

    # ---- phase 3 (last step): drain all semaphores, then (if fused)
    # ---- combine the fully-landed token-sorted returns
    @pl.when(s == d_world - 1)
    def _():
        if not fuse_combine:
            def drain(d, c):
                @pl.when(d != my)
                def _():
                    def per_expert(e, c2):
                        def per_tile(t, c3):
                            # x sends I started toward d
                            @pl.when(t < tiles_of(send_cnt[d, e]))
                            def _():
                                pltpu.make_async_copy(
                                    x_send.at[d, e, pl.ds(t * cm, cm), :],
                                    x_send.at[d, e, pl.ds(t * cm, cm), :],
                                    send_x_sems.at[d],
                                ).wait()
                                # y tiles coming back from owner d (same
                                # predicate: they are the tiles I sent)
                                pltpu.make_async_copy(
                                    y_back.at[d, e, pl.ds(t * cm, cm), :],
                                    y_back.at[d, e, pl.ds(t * cm, cm), :],
                                    recv_y_sems.at[d],
                                ).wait()
                            # y sends I started toward source d
                            @pl.when(t < tiles_of(recv_cnt[d, e]))
                            def _():
                                pltpu.make_async_copy(
                                    y_stage.at[d, e, pl.ds(t * cm, cm), :],
                                    y_stage.at[d, e, pl.ds(t * cm, cm), :],
                                    send_y_sems.at[d],
                                ).wait()
                            return c3

                        return jax.lax.fori_loop(0, n_row_tiles, per_tile,
                                                 c2)

                    jax.lax.fori_loop(0, nlx, per_expert, 0)
                return c

            jax.lax.fori_loop(0, d_world, drain, 0)
        else:
            # Row-granular accounting mirrors the row-granular sends: the
            # wait refs only meter bytes, so a [1, H] wait per present row
            # consumes exactly one returned row's worth.
            row_wait = y_stage.at[0, 0, pl.ds(0, 1), :]

            def drain(d, c):
                def per_expert(e, c2):
                    @pl.when(d != my)
                    def _():
                        def per_tile(t, c3):
                            # x sends I started toward d
                            @pl.when(t < tiles_of(send_cnt[d, e]))
                            def _():
                                pltpu.make_async_copy(
                                    x_send.at[d, e, pl.ds(t * cm, cm), :],
                                    x_send.at[d, e, pl.ds(t * cm, cm), :],
                                    send_x_sems.at[d],
                                ).wait()
                            return c3

                        jax.lax.fori_loop(0, n_row_tiles, per_tile, 0)

                        # y rows I sent toward source d
                        def per_row_sy(r, c3):
                            @pl.when(r < recv_cnt[d, e])
                            def _():
                                pltpu.make_async_copy(
                                    row_wait, row_wait, send_y_sems.at[d],
                                ).wait()
                            return c3

                        jax.lax.fori_loop(0, cap, per_row_sy, 0)

                    # y rows owner d returned into my sorted buffer (for
                    # d == my these were local copies on the same sem)
                    def per_row_ry(r, c3):
                        @pl.when(r < send_cnt[d, e])
                        def _():
                            pltpu.make_async_copy(
                                row_wait, row_wait, recv_y_sems.at[d],
                            ).wait()
                        return c3

                    jax.lax.fori_loop(0, cap, per_row_ry, 0)
                    return c2

                jax.lax.fori_loop(0, nlx, per_expert, 0)
                return c

            jax.lax.fori_loop(0, d_world, drain, 0)

            # every contribution has landed: one vectorized pass of
            # k-row segment-sums over the token-sorted buffer.  Rows
            # whose weight is 0 (dropped assignments, padding) may hold
            # unwritten garbage — `where` SELECTS before multiplying so
            # NaN/inf garbage cannot leak through 0 * NaN.
            cr = cu * k
            n_chunks = out.shape[0] // cu

            def combine_chunk(c, carry):
                yd = pltpu.make_async_copy(
                    y_back.at[pl.ds(c * cr, cr), :], ys_vmem,
                    copy_sems.at[0],
                )
                wd = pltpu.make_async_copy(
                    w_sorted.at[pl.ds(c * cr, cr), :], ws_vmem,
                    copy_sems.at[1],
                )
                yd.start(); wd.start()
                yd.wait(); wd.wait()
                yw = jnp.where(
                    ws_vmem[:] != 0.0, ys_vmem[:].astype(jnp.float32), 0.0
                ) * ws_vmem[:]
                ov_vmem[:] = yw.reshape(cu, k, h).sum(axis=1)
                st = pltpu.make_async_copy(
                    ov_vmem, out.at[pl.ds(c * cu, cu), :], copy_sems.at[0]
                )
                st.start()
                st.wait()
                return carry

            jax.lax.fori_loop(0, n_chunks, combine_chunk, 0)


def _resolve_tiles(cap: int, h: int, i_dim: int, dtype_name: str,
                   fuse_combine: bool) -> tuple[int, int]:
    """Resolve the kernel's (cm row tile, bi weight chunk), measured
    overrides included.  Both the VMEM budget gate and the launch call
    this, so a tuning entry can never re-size the kernel past the budget
    that approved it (advisor r4 #1)."""
    # largest row tile that divides the capacity (callers pad cap to a
    # 32-multiple, so an awkward capacity degrades the tile size instead
    # of being rejected)
    cm = next((t for t in (256, 128, 64, 32, 16, 8) if cap % t == 0), None)
    if cm is None:
        raise ValueError(f"capacity {cap} not a multiple of 8 rows")
    # the combine accumulator claims VMEM, so cap the streamed weight
    # chunk lower when it is resident (see _fuse_combine_enabled)
    bi_cap = 256 if fuse_combine else (512 if cm <= 128 else 256)
    # measured per-generation overrides (flashmoe_tpu.tuning; the
    # reference's arch trait table, arch.cuh:95-222) — applied only when
    # they still divide the shapes they claim to match
    from flashmoe_tpu import tuning

    tuned = tuning.lookup("fused_ep", h=h, i=i_dim, dtype=dtype_name)
    if tuned.get("cm") and cap % tuned["cm"] == 0:
        cm = tuned["cm"]
    if tuned.get("bi_cap") and not fuse_combine:
        bi_cap = tuned["bi_cap"]
    return cm, min(bi_cap, i_dim)


def _weights_resident_choice(cap: int, h: int, i_dim: int, dt_size: int,
                             gated: bool, cm: int, bi: int,
                             fuse_combine: bool, k: int,
                             tuned: dict) -> tuple[bool, int | None]:
    """Static decision: hold every weight byte in VMEM exactly once across
    row tiles (the resident two-pass schedule in the kernel) vs re-stream
    weights per row tile.  Returns ``(enabled, bh)`` with ``bh`` the
    output-column chunk width.

    Heuristic crossover: weight bytes saved, ``(n_row_tiles-1) * wu_mult
    * h * i`` (wu_mult = 3 for gated: gate+up+down matrices), must exceed
    the x bytes added by pass 1's per-chunk re-reads,
    ``(n_i_chunks-1) * cap * h`` — and the hidden slab ``cap * i`` plus
    both weight chunk pairs must fit the VMEM budget.  A measured
    ``weights_resident`` entry in the tuning table (the reference's arch
    trait table mechanism, ``arch.cuh:95-222``) overrides the heuristic;
    the VMEM feasibility check is never overridable."""
    n_row_tiles = cap // cm
    if n_row_tiles <= 1:
        return False, None
    n_i_chunks = i_dim // bi
    if "weights_resident" in tuned:
        if not tuned["weights_resident"]:
            return False, None
    else:
        wu_mult = 3 if gated else 2
        saved = (n_row_tiles - 1) * wu_mult * h * i_dim
        extra = (n_i_chunks - 1) * cap * h
        if saved <= extra:
            return False, None
    ok, bh = _resident_budget_ok(cap, h, i_dim, dt_size, gated, cm, bi,
                                 fuse_combine, k, hid_rows=cap)
    return (ok, bh) if ok else (False, None)


def _resident_budget_ok(cap, h, i_dim, dt_size, gated, cm, bi,
                        fuse_combine, k, *, hid_rows):
    """VMEM feasibility of a resident-style two-pass with ``hid_rows``
    rows of hidden resident.  Returns (ok, bh)."""
    n_i_chunks = i_dim // bi
    bh = next((b for b in (256, 128, 64, 32, 16, 8) if h % b == 0), None)
    if bh is None:
        return False, None
    hid = n_i_chunks * hid_rows * bi * dt_size
    wu2 = 2 * h * (2 * bi if gated else bi) * dt_size
    wdc2 = 2 * i_dim * bh * dt_size
    tiles = cm * h * dt_size + cm * bh * (4 + dt_size)  # xs + acc + yv
    chunk = (_combine_chunk_rows(k) * k * (h * dt_size + 4)
             + _combine_chunk_rows(k) * h * 4) if fuse_combine else 0
    if hid + wu2 + wdc2 + tiles + chunk > 15 * 2**20:
        return False, None
    return True, bh


#: K-window width candidates of the row-windowed schedule, widest first
#: (wider window = fewer activation re-streams; the IO-aware chooser
#: maximizes it under the VMEM budget)
_KW_CANDIDATES = (2048, 1024, 512, 256, 128, 64, 32, 16, 8)


def _rowwin_budget_ok(cap: int, h: int, i_dim: int, dt_size: int,
                      gated: bool, cm: int, kw: int, fuse_combine: bool,
                      k: int, *, w_dt: int | None = None,
                      sc_bytes: float = 0.0) -> bool:
    """VMEM feasibility of the row-windowed schedule at (cm row tile,
    kw K-window): the double-buffered window pair (w_up [h, kw] — or
    [h, 2*kw] gated — plus w_down [kw, h]) + one x row tile + the f32
    partial-sum accumulator tile + the full-width output tile.  The
    cross-window state lives in HBM (``acc_hbm``), so — unlike the
    weights-once schedules — NOTHING here scales with the capacity or
    the source count: this is the schedule that stays feasible when the
    expert is simply bigger than VMEM (mixtral's i=14336).

    ``w_dt``: bytes per WEIGHT element in the window buffers (default =
    ``dt_size``).  Quantized expert storage (``MoEConfig.expert_quant``,
    flashmoe_tpu/quant/) streams int8/e4m3 slabs and dequantizes in
    VMEM, so its windows budget at 1 B/elem — which is exactly why the
    chooser re-solves to wider K-windows under quant; ``sc_bytes``
    charges the resident f32 scale arrays that ride along."""
    wdt = dt_size if w_dt is None else w_dt
    wu2 = 2 * h * (2 * kw if gated else kw) * wdt
    wd2 = 2 * kw * h * wdt
    tiles = cm * h * dt_size + cm * h * 4 + cm * h * dt_size  # xs+acc+yv
    bias = i_dim * 4 + h * 4
    chunk = (_combine_chunk_rows(k) * k * (h * dt_size + 4)
             + _combine_chunk_rows(k) * h * 4) if fuse_combine else 0
    return wu2 + wd2 + tiles + bias + chunk + sc_bytes <= 15 * 2**20


def rowwin_tile_candidates(cap: int, h: int, i_dim: int, dt_size: int,
                           gated: bool, fuse_combine: bool,
                           k: int, *,
                           w_dt: int | None = None,
                           sc_bytes: float = 0.0
                           ) -> list[tuple[int, int]]:
    """Every VMEM-feasible (cm row tile, kw K-window) pair of the
    rowwin schedule at this shape — the candidate grid of the
    IO-aware chooser (:func:`_rowwin_tiles`).  ``w_dt``/``sc_bytes``:
    quantized-store weight width + scale residency
    (:func:`_rowwin_budget_ok`)."""
    return [
        (cm, kw)
        for cm in (256, 128, 64, 32, 16, 8) if cap % cm == 0
        for kw in _KW_CANDIDATES if i_dim % kw == 0
        and _rowwin_budget_ok(cap, h, i_dim, dt_size, gated, cm, kw,
                              fuse_combine, k, w_dt=w_dt,
                              sc_bytes=sc_bytes)
    ]


def _rowwin_tiles(cap: int, h: int, i_dim: int, dt_size: int,
                  dtype_name: str | None, gated: bool,
                  fuse_combine: bool, k: int, *,
                  w_dt: int | None = None,
                  sc_bytes: float = 0.0) -> tuple[int | None,
                                                  int | None]:
    """IO-aware (row tile, K-window) chooser for the rowwin schedule:
    among VMEM-feasible (cm, kw) pairs, minimize the schedule's modeled
    HBM traffic (the SonicMoE stance, arXiv 2512.14080: optimize bytes,
    not FLOPs).  Weight bytes are tile-independent — window-major order
    streams each window exactly once per pass — so the objective is the
    activation term the window loop re-streams: per K-window every
    resident row re-reads its x tile (``n_win * h * dt``) and
    round-trips its f32 partial sum at every interior window boundary
    (``(n_win - 1) * h * 8``).  Traffic falls monotonically with kw, so
    the chooser takes the widest feasible window and spends the VMEM
    that remains on the largest row tile (cm moves no HBM bytes; bigger
    tiles mean fewer DMA issues and better MXU occupancy).

    A measured ``fused_tiles`` tuning entry
    (:mod:`flashmoe_tpu.tuning`) overrides the analytic pick
    when it still divides the shapes — the VMEM gate is never
    overridable.  Returns ``(cm, kw)``, or ``(None, None)`` when no
    pair fits the budget."""
    best = None  # (modeled activation bytes/row, -cm, cm, kw)
    for cm, kw in rowwin_tile_candidates(cap, h, i_dim, dt_size, gated,
                                         fuse_combine, k, w_dt=w_dt,
                                         sc_bytes=sc_bytes):
        n_win = i_dim // kw
        bytes_per_row = n_win * h * dt_size + (n_win - 1) * h * 8
        cand = (bytes_per_row, -cm, cm, kw)
        if best is None or cand < best:
            best = cand
    if best is None:
        return None, None
    cm, kw = best[2], best[3]
    if dtype_name is not None:
        from flashmoe_tpu import tuning

        tuned = tuning.lookup("fused_tiles", h=h, i=i_dim,
                              dtype=dtype_name)
        tcm, tkw = tuned.get("cm"), tuned.get("kw")
        if (tcm and tkw and cap % tcm == 0 and i_dim % tkw == 0
                and _rowwin_budget_ok(cap, h, i_dim, dt_size, gated,
                                      tcm, tkw, fuse_combine, k,
                                      w_dt=w_dt, sc_bytes=sc_bytes)):
            cm, kw = tcm, tkw
    return cm, kw


def _rowwin_choice(cap: int, h: int, i_dim: int, dt_size: int,
                   dtype_name: str | None, gated: bool, cm_stream: int,
                   fuse_combine: bool, k: int, d_world: int,
                   tuned: dict, *,
                   w_dt: int | None = None,
                   sc_bytes: float = 0.0) -> tuple[bool, int | None]:
    """Static stream-vs-rowwin decision (both are the fallbacks when no
    weights-once schedule fits VMEM).  Byte crossover, per local
    expert: weight streams saved by row-windowing — stream pays
    ``d_world * n_row_tiles`` streams, rowwin pays one pass for the own
    slab plus one for the batched remotes — must exceed the activation
    re-streaming the window loop adds (x re-reads + f32 partial-sum
    round-trips over the ~``d_world * cap`` resident rows).  A measured
    ``rowwin`` bit in the ``fused_ep`` tuning entry overrides the
    heuristic; ``FLASHMOE_FUSED_ROWWIN=0`` disables outright; the VMEM
    gate (the chooser finding any feasible pair) is never overridable.
    Rowwin IS a batched-pass schedule (own slab at step 0, all remotes
    in one pass at the final grid step), so the batched kill-switches —
    ``FLASHMOE_FUSED_BATCHED=0`` and a measured ``batched: false``
    entry — disable the auto choice too: a caller who asked for
    per-source arrival processing must get it (a ``rowwin: true`` entry
    or ``MoEConfig.fused_schedule='rowwin'`` still forces past them).
    Returns ``(enabled, kw)``."""
    cm, kw = _rowwin_tiles(cap, h, i_dim, dt_size, dtype_name, gated,
                           fuse_combine, k, w_dt=w_dt,
                           sc_bytes=sc_bytes)
    if cm is None:
        return False, None
    if os.environ.get("FLASHMOE_FUSED_ROWWIN") == "0":
        return False, None
    knob = tuned.get("rowwin")
    if knob is False:
        return False, None
    if knob is not True and (
            os.environ.get("FLASHMOE_FUSED_BATCHED") == "0"
            or tuned.get("batched") is False):
        return False, None
    if knob is not True:
        n_row_tiles = cap // cm_stream
        passes = 2 if d_world > 1 else 1
        streams_saved = d_world * n_row_tiles - passes
        wu_mult = 3 if gated else 2
        # weight streams saved are priced at the STORED width: under a
        # quantized store (w_dt=1) the byte trade rowwin wins shrinks,
        # while the activation re-streaming it pays does not
        saved = (streams_saved * wu_mult * h * i_dim
                 * (dt_size if w_dt is None else w_dt))
        n_win = i_dim // kw
        rows = d_world * cap
        extra = rows * h * ((n_win - 1) * dt_size + (n_win - 1) * 8)
        if saved <= extra:
            return False, None
    return True, kw


def _fused_schedule(cap: int, h: int, i_dim: int, dt_size: int,
                    gated: bool, cm: int, bi: int, fuse_combine: bool,
                    k: int, d_world: int,
                    tuned: dict, *, dtype_name: str | None = None,
                    forced: str | None = None,
                    w_dt: int | None = None,
                    sc_bytes: float = 0.0) -> tuple[str, int | None]:
    """Static FFN-schedule choice for the fused kernel:

      batched    own slab at step 0, ALL remote slabs expert-major at the
                 final step with weights streamed once -> 2x weight HBM
                 traffic instead of the per-source d x (the round-5 cost
                 model's headline finding).  Default at
                 d >= 3 when the (d-1)*cap-row hidden slab fits VMEM —
                 at d=2 the two schedules move identical weight bytes
                 and per-source keeps finer overlap.
      resident   per-source two-pass (kills the n_row_tiles x factor,
                 VERDICT r4 weak #4) when its byte trade wins.
      rowwin     row-windowed K-dim streaming, window-major / row-minor
                 (ISSUE 12 / ROADMAP item 4): expert weights stream in
                 VMEM-sized K-windows and every resident row tile —
                 batched across ALL the pass's source slabs, like the
                 arrival-batched schedule — passes through a window
                 before the next is fetched, partial sums parked in an
                 HBM f32 accumulator.  ~2 weight streams total
                 regardless of d, at the cost of per-window activation
                 re-streaming — the schedule that serves wide experts
                 (mixtral i=14336) whose hidden slab can never be VMEM
                 resident.  Chosen over stream when its byte trade wins
                 (:func:`_rowwin_choice`).
      stream     per-row-tile weight streaming (the round-<=4 schedule).

    ``FLASHMOE_FUSED_BATCHED=0`` or a ``batched: false`` tuning entry
    disables the batched schedule; a ``batched: true`` entry forces it
    past the d>=3 heuristic (never past the VMEM gate).  ``rowwin``
    tuning bits / ``FLASHMOE_FUSED_ROWWIN=0`` gate rowwin the same way.

    ``forced`` (``MoEConfig.fused_schedule``) pins the schedule; a
    forced schedule still faces the hard VMEM gate — ValueError with
    the reason rather than an infeasible launch.  The second return
    value is the output-column chunk ``bh`` for batched/resident, the
    K-window ``kw`` for rowwin, None for stream."""
    if forced is not None:
        if forced == "stream":
            return "stream", None
        if forced in ("batched", "resident"):
            if forced == "batched" and d_world < 2:
                raise ValueError(
                    "fused_schedule='batched' needs an ep world of >= 2 "
                    "ranks (there is no remote batch at d_world=1)")
            hid_rows = ((d_world - 1) * cap if forced == "batched"
                        else cap)
            ok, bh = _resident_budget_ok(
                cap, h, i_dim, dt_size, gated, cm, bi, fuse_combine, k,
                hid_rows=hid_rows)
            if not ok:
                raise ValueError(
                    f"fused_schedule={forced!r} is VMEM-infeasible at "
                    f"this shape: the {hid_rows}-row hidden slab plus "
                    f"the double-buffered weight chunks exceed the "
                    f"budget ('rowwin' or 'stream' "
                    f"stay feasible)")
            return forced, bh
        if forced == "rowwin":
            cmr, kwr = _rowwin_tiles(cap, h, i_dim, dt_size, dtype_name,
                                     gated, fuse_combine, k, w_dt=w_dt,
                                     sc_bytes=sc_bytes)
            if cmr is None:
                raise ValueError(
                    "fused_schedule='rowwin' is VMEM-infeasible at this "
                    "shape: no (row tile, K-window) pair fits the "
                    "window double-buffer + accumulator budget")
            return "rowwin", kwr
        raise ValueError(f"unknown fused schedule {forced!r}")
    knob = tuned.get("batched")
    env_off = os.environ.get("FLASHMOE_FUSED_BATCHED") == "0"
    want_batched = (knob if knob is not None
                    else (d_world >= 3 and not env_off))
    if want_batched and d_world >= 2 and not env_off:
        ok, bh = _resident_budget_ok(
            cap, h, i_dim, dt_size, gated, cm, bi, fuse_combine, k,
            hid_rows=(d_world - 1) * cap)
        if ok:
            return "batched", bh
    resident, bh = _weights_resident_choice(
        cap, h, i_dim, dt_size, gated, cm, bi, fuse_combine, k, tuned)
    if resident:
        return "resident", bh
    rowwin, kw = _rowwin_choice(cap, h, i_dim, dt_size, dtype_name,
                                gated, cm, fuse_combine, k, d_world,
                                tuned, w_dt=w_dt, sc_bytes=sc_bytes)
    if rowwin:
        return "rowwin", kw
    return "stream", None


def schedule_table(cfg: MoEConfig, d_world: int, *,
                   fuse_combine: bool = False,
                   schedule: str | None = None) -> dict:
    """Public resolution of the fused kernel's execution geometry at
    ``(cfg, d_world)`` — THE single function behind the kernel launch,
    the byte model (``analysis._geom``), the planner's per-schedule
    feasibility rows, and the collective census, so no consumer can
    resolve a different geometry than the kernel actually runs (ISSUE
    12 satellite: the planner once imported the private helpers
    directly and could drift).

    ``schedule`` forces which schedule's geometry is REPORTED (the
    planner prices every schedule, not just the resolved one) without
    touching the resolution; None reports the resolved schedule's.
    ``cfg.fused_schedule`` is honored by the resolution; when the
    forced schedule is VMEM-infeasible the table falls back to the auto
    choice and records the reason under ``forced_infeasible`` (the
    LAUNCH path raises instead — see :func:`_fused_schedule`).

    Returns::

        schedule       the schedule the kernel would run
        priced         the schedule this table's geometry describes
                       (= ``schedule`` arg or the resolved one)
        feasible       {batched, resident, stream, rowwin}: hard VMEM
                       gates only (a schedule can be feasible yet not
                       chosen)
        cap, cap_raw   32-padded / raw per-(rank, expert) capacity
        cm, bi         row tile and weight-chunk width at ``priced``
                       (for rowwin, ``bi`` IS the K-window ``kw`` — the
                       IO-aware chooser's pick)
        kw             the K-window when ``priced == 'rowwin'``, None
                       otherwise
        n_row_tiles, n_i_chunks   derived loop extents (for rowwin,
                       ``n_i_chunks`` is the window count)
        s_loc, h, i, dt, gated    shared shape facts
        forced_infeasible         reason string, or None
    """
    from flashmoe_tpu import tuning

    s_loc = cfg.tokens // d_world
    h, i_dim = cfg.hidden_size, cfg.intermediate_size
    dt = jnp.dtype(cfg.dtype).itemsize
    name = jnp.dtype(cfg.dtype).name
    cap_raw = local_capacity(cfg, s_loc)
    cap = -(-cap_raw // 32) * 32
    cm, bi = _resolve_tiles(cap, h, i_dim, name, fuse_combine)
    gated = cfg.gated_ffn
    k = cfg.expert_top_k
    # quantized expert storage (MoEConfig.expert_quant): the rowwin
    # K-window streamer fetches int8/e4m3 slabs and dequantizes in
    # VMEM, so its window geometry re-solves at the QUANTIZED bytes
    # per element (wider feasible windows -> fewer HBM accumulator
    # round-trips), with the resident f32 scale arrays charged against
    # the budget.  The weights-once schedules boundary-dequantize
    # layer-side and keep pricing at the compute width.
    wdt, sc_bytes = _quant_geometry(cfg, d_world)
    tuned = tuning.lookup("fused_ep", h=h, i=i_dim, dtype=name)
    batched_ok = d_world >= 2 and _resident_budget_ok(
        cap, h, i_dim, dt, gated, cm, bi, fuse_combine, k,
        hid_rows=(d_world - 1) * cap)[0]
    resident_ok = cap // cm > 1 and _resident_budget_ok(
        cap, h, i_dim, dt, gated, cm, bi, fuse_combine, k,
        hid_rows=cap)[0]
    rw_cm, rw_kw = _rowwin_tiles(cap, h, i_dim, dt, name, gated,
                                 fuse_combine, k, w_dt=wdt,
                                 sc_bytes=sc_bytes)
    feasible = {"batched": batched_ok, "resident": resident_ok,
                "stream": True, "rowwin": rw_cm is not None}
    forced_infeasible = None
    try:
        resolved, _aux = _fused_schedule(
            cap, h, i_dim, dt, gated, cm, bi, fuse_combine, k, d_world,
            tuned, dtype_name=name, forced=cfg.fused_schedule,
            w_dt=wdt, sc_bytes=sc_bytes)
    except ValueError as e:
        forced_infeasible = str(e)
        resolved, _aux = _fused_schedule(
            cap, h, i_dim, dt, gated, cm, bi, fuse_combine, k, d_world,
            tuned, dtype_name=name, w_dt=wdt, sc_bytes=sc_bytes)
    priced = schedule if schedule is not None else resolved
    if priced not in feasible:
        raise ValueError(
            f"unknown fused schedule {priced!r}; choose from "
            f"{tuple(sorted(feasible))}")
    if priced == "rowwin" and rw_cm is not None:
        cm, bi = rw_cm, rw_kw
    return {
        "schedule": resolved, "priced": priced, "feasible": feasible,
        "cap": cap, "cap_raw": cap_raw, "cm": cm, "bi": bi,
        "kw": rw_kw if priced == "rowwin" else None,
        "n_row_tiles": cap // cm, "n_i_chunks": i_dim // bi,
        "s_loc": s_loc, "h": h, "i": i_dim, "dt": dt, "gated": gated,
        # bytes per weight element the ROWWIN streamer fetches (1 under
        # a quantized store, = dt otherwise); the weights-once
        # schedules stream boundary-dequantized compute-width weights
        "wdt": wdt if wdt is not None else dt,
        "forced_infeasible": forced_infeasible,
    }


def _quant_geometry(cfg: MoEConfig, d_world: int
                    ) -> tuple[int | None, float]:
    """(weight bytes/elem for the rowwin window buffers, resident
    scale-array VMEM bytes) under ``cfg.expert_quant`` — (None, 0.0)
    when quant is off, so every geometry resolution stays byte-
    identical to a pre-quant build."""
    if cfg.expert_quant is None:
        return None, 0.0
    from flashmoe_tpu.quant import core as qcore

    wdt = int(qcore.weight_itemsize(cfg.expert_quant, cfg.dtype))
    nlx = max(cfg.num_experts // max(d_world, 1), 1)
    chans = (2 if cfg.gated_ffn else 1) * cfg.intermediate_size \
        + cfg.hidden_size
    return wdt, float(nlx * chans * 4)


def schedule_metadata(cfg: MoEConfig, d_world: int, *,
                      fuse_combine: bool = False) -> dict:
    """Back-compat view of :func:`schedule_table`: ``{schedule,
    feasible, cap, cm, bi, n_row_tiles, n_i_chunks}`` — the keys PR-1
    consumers read.  New code should call :func:`schedule_table`, which
    adds the rowwin geometry and the forced-schedule surface."""
    t = schedule_table(cfg, d_world, fuse_combine=fuse_combine)
    return {k: t[k] for k in ("schedule", "feasible", "cap", "cm", "bi",
                              "n_row_tiles", "n_i_chunks")}


def _fused_shard(send_cnt, recv_cnt, src_order, x_send, w_up, b_up, w_down,
                 b_down, *,
                 cfg: MoEConfig, axis: str, interpret, collective_id: int,
                 detect_races: bool = False, w_gate=None,
                 recv_pos=None, w_sorted=None, cu: int | None = None,
                 wup_sc=None, wdn_sc=None, wg_sc=None):
    """Launch the fused kernel.  With ``recv_pos``/``w_sorted``/``cu`` the
    combine runs in-kernel and the call returns ``(out [s_out_pad, h] f32,
    y_sorted [rows_pad, h])``; otherwise it returns the slab ``y_recv``
    for the XLA combine.

    ``wup_sc``/``wdn_sc``/``wg_sc`` (``MoEConfig.expert_quant``): f32
    per-output-channel scales of a QUANTIZED weight store — ``w_up`` /
    ``w_down`` / ``w_gate`` then carry int8/e4m3 payloads.  When the
    resolved schedule is ``rowwin``, the K-window streamer fetches the
    quantized slabs and dequantizes in VMEM (geometry re-solved at 1
    B/elem); the weights-once schedules dequantize at this boundary
    instead (XLA-side — their VMEM residency is capacity-bound, not
    weight-width-bound) and launch exactly as at full precision."""
    d_world, nlx, cap, h = x_send.shape
    i_dim = w_down.shape[1]
    gated = w_gate is not None
    fuse_combine = recv_pos is not None
    k = cfg.expert_top_k
    quant = wup_sc is not None
    # one resolution of (cm, bi) shared with the combine budget gate, so
    # the VMEM estimate that approved the opt-in describes the kernel that
    # actually launches (advisor r4 #1)
    dt_name = jnp.dtype(x_send.dtype).name
    dt_size = jnp.dtype(x_send.dtype).itemsize
    cm, bi = _resolve_tiles(cap, h, i_dim, dt_name, fuse_combine)
    from flashmoe_tpu import tuning

    # per-K-GROUP scales always take the boundary-dequant path (the
    # in-kernel dequant is per-output-channel only), so their geometry
    # must budget at the COMPUTE width the kernel will actually stream
    grouped = quant and any(
        s is not None and s.shape[-2] != 1
        for s in (wup_sc, wdn_sc, wg_sc))
    w_dt, sc_bytes = (_quant_geometry(cfg, d_world)
                      if quant and not grouped else (None, 0.0))
    schedule, aux = _fused_schedule(
        cap, h, i_dim, dt_size, gated, cm, bi,
        fuse_combine, k, d_world,
        tuning.lookup("fused_ep", h=h, i=i_dim, dtype=dt_name),
        dtype_name=dt_name, forced=cfg.fused_schedule,
        w_dt=w_dt, sc_bytes=sc_bytes,
    )
    if quant and (schedule != "rowwin" or grouped):
        # weights-once schedules hold capacity-scaled hidden slabs, not
        # weight windows — dequantize at the boundary and launch the
        # unchanged full-precision kernel (the planner prices their
        # weight streams at the compute width for the same reason).
        # Per-K-GROUP scales take the same boundary path on rowwin too:
        # the in-kernel dequant is per-output-channel only.
        from flashmoe_tpu.quant import core as qcore

        w_up = qcore.dequantize_channelwise(w_up, wup_sc, cfg.dtype)
        w_down = qcore.dequantize_channelwise(w_down, wdn_sc, cfg.dtype)
        if gated:
            w_gate = qcore.dequantize_channelwise(w_gate, wg_sc,
                                                  cfg.dtype)
        quant = False
    bh = None
    if schedule == "rowwin":
        # the IO-aware chooser owns BOTH tiles on the rowwin schedule:
        # bi becomes the K-window width (aux == kw by construction), so
        # every bi-keyed mechanism below — the gated gate|up interleave,
        # the wu/wd window DMAs, the [2, bi, h] w_down slots — windows
        # the K dimension without a second code path
        cm, bi = _rowwin_tiles(cap, h, i_dim, dt_size, dt_name, gated,
                               fuse_combine, k, w_dt=w_dt,
                               sc_bytes=sc_bytes)
    else:
        bh = aux
    if i_dim % bi:
        raise ValueError(f"intermediate {i_dim} not divisible by {bi}")
    sc_args = None
    if gated:
        # interleave per-chunk: [nlx, H, nj*2*bi] as [gate_chunk | up_chunk]
        nj = i_dim // bi
        wg = w_gate.reshape(nlx, h, nj, bi)
        wu = w_up.reshape(nlx, h, nj, bi)
        w_up = jnp.concatenate([wg, wu], axis=-1).reshape(
            nlx, h, nj * 2 * bi
        )
        if quant:
            # scales interleave exactly like their payload columns
            sgp = wg_sc.reshape(nlx, nj, bi)
            sup = wup_sc.reshape(nlx, nj, bi)
            sc_args = (jnp.concatenate([sgp, sup], axis=-1).reshape(
                nlx, nj * 2 * bi).astype(jnp.float32),
                wdn_sc.reshape(nlx, h).astype(jnp.float32))
    elif quant:
        sc_args = (wup_sc.reshape(nlx, i_dim).astype(jnp.float32),
                   wdn_sc.reshape(nlx, h).astype(jnp.float32))

    unified = functools.partial(
        _fused_kernel, axis=axis, act_name=cfg.hidden_act, cm=cm, bi=bi,
        gated=gated, fuse_combine=fuse_combine, k=k, cu=cu,
        schedule=schedule, bh=bh, quant=quant,
    )
    out_shapes = [
        jax.ShapeDtypeStruct((d_world, nlx, cap, h), x_send.dtype),  # x_recv
    ]
    if fuse_combine:
        rows_pad = w_sorted.shape[0]
        if rows_pad % (cu * k):
            raise ValueError(
                f"sorted return rows {rows_pad} not a multiple of the "
                f"combine chunk {cu * k}")
        # token-sorted return buffer replaces the slab y_recv
        out_shapes.append(
            jax.ShapeDtypeStruct((rows_pad, h), x_send.dtype))
    else:
        out_shapes.append(
            jax.ShapeDtypeStruct((d_world, nlx, cap, h), x_send.dtype))
    out_shapes.append(
        jax.ShapeDtypeStruct((d_world, nlx, cap, h), x_send.dtype))  # y_stage
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [smem_spec, smem_spec, smem_spec]
    inputs = [send_cnt, recv_cnt, src_order]
    out_specs = [any_spec, any_spec, any_spec]
    if fuse_combine:
        # recv_pos feeds scalar DMA addressing (SMEM); w_sorted streams
        # through a [cu*k, 1] scratch during the drain combine
        in_specs += [smem_spec, any_spec]
        inputs += [recv_pos, w_sorted.astype(jnp.float32)]
        out_shapes.append(
            jax.ShapeDtypeStruct((rows_pad // k, h), jnp.float32))  # out
        out_specs.append(any_spec)
    if schedule == "rowwin":
        # HBM f32 partial-sum accumulator of the window loop: scratch
        # that must persist across K-windows for EVERY resident row, so
        # it cannot live in VMEM (that infeasibility is the whole
        # reason this schedule exists) and Pallas scratch shapes are
        # VMEM/SMEM-only — it rides as an extra ANY-space output the
        # caller discards
        out_shapes.append(jax.ShapeDtypeStruct(
            (d_world, nlx, cap, h), jnp.float32))
        out_specs.append(any_spec)
    in_specs += [any_spec] * 5
    inputs += [x_send, w_up, b_up[:, None, :], w_down, b_down[:, None, :]]
    if quant:
        # per-output-channel f32 scales: tiny ([nLx, I(+I)] + [nLx, H])
        # and read every window, so they live whole in VMEM
        in_specs += [pl.BlockSpec(memory_space=pltpu.VMEM)] * 2
        inputs += list(sc_args)

    # one generic wrapper splits the positional refs by the static layout
    # (inputs / outputs / scratch counts vary with fuse_combine and
    # weights_resident)
    def kernel(*refs):
        i0 = 0
        send_cnt_, recv_cnt_, src_order_ = refs[0:3]
        i0 = 3
        recv_pos_ = w_sorted_ = None
        if fuse_combine:
            recv_pos_, w_sorted_ = refs[3:5]
            i0 = 5
        xw = refs[i0:i0 + 5]
        i0 += 5
        wup_sc_ = wdn_sc_ = None
        if quant:
            wup_sc_, wdn_sc_ = refs[i0:i0 + 2]
            i0 += 2
        x_recv_, y_back_, y_stage_ = refs[i0:i0 + 3]
        i0 += 3
        out_ = None
        if fuse_combine:
            out_ = refs[i0]
            i0 += 1
        acc_hbm_ = None
        if schedule == "rowwin":
            acc_hbm_ = refs[i0]
            i0 += 1
        xs, wup, wdn, acc_, yv_, bup, bdn = refs[i0:i0 + 7]
        i0 += 7
        ys = ws = ov = hid = None
        if fuse_combine:
            ys, ws, ov = refs[i0:i0 + 3]
            i0 += 3
        if schedule in ("resident", "batched"):
            hid = refs[i0]
            i0 += 1
        unified(send_cnt_, recv_cnt_, src_order_, recv_pos_, w_sorted_,
                *xw, wup_sc_, wdn_sc_,
                x_recv_, y_back_, y_stage_, out_, acc_hbm_,
                xs, wup, wdn, acc_, yv_, bup, bdn, ys, ws, ov, hid,
                *refs[i0:])

    # streaming/rowwin schedules: wdn holds [bi, h] row chunks, acc/yv
    # full-width row tiles (for rowwin bi IS the K-window and the
    # cross-window acc state spills to the HBM accumulator above).
    # resident/batched schedules: wdn holds [i, bh] COLUMN chunks,
    # acc/yv are [cm, bh] output blocks, and the activated hidden
    # lives in the chunk-major hid slab (sized for one source per-source,
    # for all remote sources when batched).
    n_i_chunks = i_dim // bi
    two_pass = schedule in ("resident", "batched")
    scratch = [
        pltpu.VMEM((cm, h), x_send.dtype),        # xs
        # weight slots hold whatever streams from HBM: the compute
        # dtype at full precision, the int8/e4m3 payload under a
        # quantized store (w_up.dtype == x_send.dtype when quant off,
        # so the allocation is byte-identical to the pre-quant build)
        pltpu.VMEM((2, h, 2 * bi if gated else bi),
                   w_up.dtype),                   # w_up (+gate) 2 slots
        (pltpu.VMEM((2, i_dim, bh), w_down.dtype) if two_pass
         else pltpu.VMEM((2, bi, h), w_down.dtype)),  # w_down 2 slots
        pltpu.VMEM((cm, bh if two_pass else h),
                   jnp.float32),                  # acc
        pltpu.VMEM((cm, bh if two_pass else h),
                   x_send.dtype),                 # y tile / block
        pltpu.VMEM((1, i_dim), b_up.dtype),       # bias up
        pltpu.VMEM((1, h), b_down.dtype),         # bias down
    ]
    if fuse_combine:
        scratch.append(pltpu.VMEM((cu * k, h), x_send.dtype))  # y rows
        scratch.append(pltpu.VMEM((cu * k, 1), jnp.float32))   # weight col
        scratch.append(pltpu.VMEM((cu, h), jnp.float32))       # out rows
    if two_pass:
        hid_rows = (d_world - 1) * cap if schedule == "batched" else cap
        scratch.append(
            pltpu.VMEM((n_i_chunks, hid_rows, bi), x_send.dtype))  # hidden
    scratch += [
        pltpu.SemaphoreType.DMA((6,)),            # local copy + wt sems
        pltpu.SemaphoreType.DMA((d_world,)),      # send x
        pltpu.SemaphoreType.DMA((d_world,)),      # recv x
        pltpu.SemaphoreType.DMA((d_world,)),      # send y
        pltpu.SemaphoreType.DMA((d_world,)),      # recv y
    ]
    interp = False
    if interpret:
        # the interpreter's vector-clock race detector is the framework's
        # lock-free-protocol sanitizer (the reference relies on manual
        # fence discipline with no tooling — SURVEY §5).
        # FLASHMOE_INTERPRET_DMA=on_wait executes DMAs lazily at their
        # wait instead of on io_callback threads — slower-arrival
        # semantics, but immune to the interpreter's eager-thread
        # deadlocks (see fused_ep_moe_layer's interpret note).
        from flashmoe_tpu.utils.compat import cure_interpret_device_barrier

        cure_interpret_device_barrier()
        interp = pltpu.InterpretParams(
            dma_execution_mode=os.environ.get("FLASHMOE_INTERPRET_DMA",
                                              "eager"),
            detect_races=detect_races,
        )
    results = pl.pallas_call(
        kernel,
        name="fm_fused_ep",
        grid=(d_world,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id,
        ),
        interpret=interp,
    )(*inputs)
    if schedule == "rowwin":
        results = results[:-1]  # drop the HBM accumulator scratch
    if fuse_combine:
        _, y_sorted, _, out = results
        return out, y_sorted
    _, y_recv, _ = results
    return y_recv


# ----------------------------------------------------------------------
# Differentiable core: Pallas forward, Pallas-GEMM backward
# ----------------------------------------------------------------------
#
# The kernel's dataflow is  x_send --a2a--> x_recv --FFN--> y_stage
# --a2a--> y_recv.  ``all_to_all(split=concat=0)`` is its own transpose,
# so the VJP re-exchanges the cotangents/primals with XLA collectives
# (cheap next to the FFN FLOPs) and runs every large GEMM — the
# pre-activation recompute, dHidden/dX, and both dW — through the Pallas
# grouped kernels (:func:`flashmoe_tpu.ops.expert.ffn_backward_core`).
# Expert shards are disjoint across ep ranks, so dW needs no psum.

@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13))
def _fused_core(send_cnt, recv_cnt, src_order, x_send, w_up, b_up, w_down,
                b_down, w_gate, cfg, axis, interpret, collective_id,
                detect_races):
    return _fused_shard(
        send_cnt, recv_cnt, src_order, x_send, w_up, b_up, w_down, b_down,
        cfg=cfg, axis=axis, interpret=interpret,
        collective_id=collective_id, detect_races=detect_races,
        w_gate=w_gate,
    )


def _fused_core_fwd(send_cnt, recv_cnt, src_order, x_send, w_up, b_up,
                    w_down, b_down, w_gate, cfg, axis, interpret,
                    collective_id, detect_races):
    y = _fused_core(send_cnt, recv_cnt, src_order, x_send, w_up, b_up,
                    w_down, b_down, w_gate, cfg, axis, interpret,
                    collective_id, detect_races)
    return y, (send_cnt, recv_cnt, src_order, x_send, w_up, b_up, w_down,
               b_down, w_gate)


def _ffn_bwd_from_dy(cfg, axis, interpret, res, dy):
    """Shared backward tail: slab cotangent ``dy`` (of y_recv) -> gradients
    of (x_send, w_up, b_up, w_down, b_down, w_gate) via XLA re-exchange +
    Pallas grouped-GEMM backward kernels."""
    from flashmoe_tpu.ops.expert import (
        _auto_block, ffn_backward_core, grouped_matmul,
    )

    x_send, w_up, b_up, w_down, b_down, w_gate = res
    d, nlx, cap, h = x_send.shape
    gated = w_gate is not None

    a2a = functools.partial(
        jax.lax.all_to_all, axis_name=axis, split_axis=0, concat_axis=0,
        tiled=False,
    )
    x_recv = a2a(x_send)       # recompute received slabs (fwd exchange)
    dy_stage = a2a(dy)         # transpose of the return exchange

    def to_rows(t):            # [D, nlx, cap, h] -> [nlx*D*cap, h]
        return t.transpose(1, 0, 2, 3).reshape(nlx * d * cap, h)

    def from_rows(r):
        return r.reshape(nlx, d, cap, h).transpose(1, 0, 2, 3)

    xr = to_rows(x_recv)
    dyr = to_rows(dy_stage)
    bm = _auto_block(cap, 256)
    tiles_per_e = (d * cap) // bm
    gid = jnp.arange(nlx * tiles_per_e, dtype=jnp.int32) // tiles_per_e

    # recompute pre-activations through the Pallas grouped matmul
    i_dim = w_up.shape[2]
    u = grouped_matmul(xr, gid, w_up, block_m=bm, out_dtype=jnp.float32,
                       interpret=interpret)
    u = (u.reshape(nlx, d * cap, i_dim)
         + b_up[:, None, :].astype(jnp.float32)).reshape(-1, i_dim)
    g = None
    if gated:
        g = grouped_matmul(xr, gid, w_gate, block_m=bm,
                           out_dtype=jnp.float32, interpret=interpret)

    dxr, d_wu, d_bu, d_wd, d_bd, d_wg = ffn_backward_core(
        xr, gid, w_up, w_down, w_gate, u, g, dyr,
        act_name=cfg.hidden_act, gated=gated, block_m=bm,
        interpret=interpret,
    )
    d_x_send = a2a(from_rows(dxr.astype(x_send.dtype)))
    return (d_x_send,
            d_wu.astype(w_up.dtype), d_bu.astype(b_up.dtype),
            d_wd.astype(w_down.dtype), d_bd.astype(b_down.dtype),
            d_wg.astype(w_gate.dtype) if gated else None)


def _fused_core_bwd(cfg, axis, interpret, collective_id, detect_races,
                    res, dy):
    import numpy as np

    (send_cnt, recv_cnt, src_order, x_send, w_up, b_up, w_down, b_down,
     w_gate) = res
    grads = _ffn_bwd_from_dy(
        cfg, axis, interpret,
        (x_send, w_up, b_up, w_down, b_down, w_gate), dy,
    )
    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (f0(send_cnt), f0(recv_cnt), f0(src_order)) + grads


_fused_core.defvjp(_fused_core_fwd, _fused_core_bwd)


# ----------------------------------------------------------------------
# Combine-fused core: the kernel also owns the weighted un-permute
# ----------------------------------------------------------------------
#
# Dataflow:  x_send --a2a--> x_recv --FFN--> y_stage --row RDMA to the
#            pre-assigned sorted rows--> y_sorted --k-row segment-sum-->
#            out[t] = sum_j w_sorted[t*k+j] * y_sorted[t*k+j].
# The VJP peels the combine analytically (each occupied slab slot's
# cotangent is dy[slot] = w_sorted[ret_pos[slot]] * dout[ret_pos[slot]
# // k]) and reuses the shared FFN backward.  w_sorted stays a
# differentiable input so router gradients flow through
# dsp.sorted_return_maps' scatter transpose; ret_pos (the source-side
# slot -> sorted-row map) rides along only for the backward.

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(12, 13, 14, 15, 16, 17))
def _fused_combine_core(send_cnt, recv_cnt, src_order, ret_pos, recv_pos,
                        w_sorted, x_send, w_up, b_up, w_down, b_down,
                        w_gate, cfg, axis, interpret, collective_id,
                        detect_races, cu):
    out, _ = _fused_shard(
        send_cnt, recv_cnt, src_order, x_send, w_up, b_up, w_down, b_down,
        cfg=cfg, axis=axis, interpret=interpret,
        collective_id=collective_id, detect_races=detect_races,
        w_gate=w_gate, recv_pos=recv_pos, w_sorted=w_sorted, cu=cu,
    )
    return out


def _fused_combine_core_fwd(send_cnt, recv_cnt, src_order, ret_pos,
                            recv_pos, w_sorted, x_send, w_up, b_up,
                            w_down, b_down, w_gate, cfg, axis, interpret,
                            collective_id, detect_races, cu):
    out, y_sorted = _fused_shard(
        send_cnt, recv_cnt, src_order, x_send, w_up, b_up, w_down, b_down,
        cfg=cfg, axis=axis, interpret=interpret,
        collective_id=collective_id, detect_races=detect_races,
        w_gate=w_gate, recv_pos=recv_pos, w_sorted=w_sorted, cu=cu,
    )
    return out, (send_cnt, recv_cnt, src_order, ret_pos, recv_pos,
                 w_sorted, x_send, w_up, b_up, w_down, b_down, w_gate,
                 y_sorted)


def _fused_combine_core_bwd(cfg, axis, interpret, collective_id,
                            detect_races, cu, res, dout):
    import numpy as np

    (send_cnt, recv_cnt, src_order, ret_pos, recv_pos, w_sorted, x_send,
     w_up, b_up, w_down, b_down, w_gate, y_sorted) = res
    d, nlx, cap, h = x_send.shape
    k = cfg.expert_top_k
    rows_pad = w_sorted.shape[0]

    dout = dout.astype(jnp.float32)            # [rows_pad // k, h]
    # combine transpose per slab slot: dy[slot] = w * dout[token], both
    # read through the slot's sorted row.  Unoccupied slots must be hard
    # zero (their y was never computed; their ret_pos is a placeholder).
    cnt = jnp.minimum(send_cnt, cap).astype(jnp.int32)  # [d, nlx]
    occupied = (
        jnp.arange(cap, dtype=jnp.int32)[None, None, :] < cnt[..., None]
    )
    w_slab = w_sorted[:, 0][ret_pos]           # [d, nlx, cap]
    dy = jnp.where(
        occupied[..., None],
        w_slab[..., None] * dout[ret_pos // k],
        0.0,
    ).astype(x_send.dtype)
    grads = _ffn_bwd_from_dy(
        cfg, axis, interpret,
        (x_send, w_up, b_up, w_down, b_down, w_gate), dy,
    )
    # d_w_sorted[r] = <dout[r // k], y_sorted[r]> on rows some occupied
    # slot returned into; other rows hold unwritten garbage whose
    # cotangent the sorted_return_maps scatter-transpose would drop, but
    # NaN garbage must not leak through intermediate arithmetic.
    occ_rows = (
        jnp.zeros(rows_pad + 1, jnp.bool_)
        .at[jnp.where(occupied, ret_pos, rows_pad).reshape(-1)].set(True)
    )[:rows_pad]
    tok_of_row = (
        jnp.arange(rows_pad, dtype=jnp.int32) // k
    )
    d_ws = jnp.where(
        occ_rows,
        jnp.einsum("rh,rh->r", dout[tok_of_row],
                   jnp.where(occ_rows[:, None],
                             y_sorted.astype(jnp.float32), 0.0)),
        0.0,
    )

    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (f0(send_cnt), f0(recv_cnt), f0(src_order), f0(ret_pos),
            f0(recv_pos), d_ws[:, None]) + grads


_fused_combine_core.defvjp(_fused_combine_core_fwd, _fused_combine_core_bwd)


def _combine_chunk_rows(k: int) -> int:
    """Output rows per drain-combine chunk (static).  The chunk reads
    ``cu * k`` sorted y rows + writes ``cu`` output rows; shrink for wide
    top-k so the [cu*k, h] tile stays a modest VMEM slice."""
    return 128 if k <= 3 else 64


def _fuse_combine_budget_ok(cfg: MoEConfig, s_loc: int, h: int, i_dim: int,
                            cap: int) -> bool:
    """Memory feasibility of the in-kernel combine: the FFN streaming
    tiles + the drain combine chunks ([cu*k, h] y rows, [cu, h] f32 out
    rows) must fit VMEM, and the sorted-row map ``recv_pos`` ([E, cap]
    i32) must fit SMEM — it is a whole-array scalar-memory input, and a
    VMEM-only estimate let large E x capacity configs sail into Mosaic
    compile failures instead of the XLA-combine fallback (advisor
    round-3 #1).  The round-4 [s_pad, h] f32 VMEM accumulator is gone
    (the sorted-return restructure writes output chunks once), so the
    budget no longer scales with the local token count."""
    dt = jnp.dtype(cfg.dtype).itemsize
    # the same (cm, bi) resolution — tuning overrides included — that
    # _fused_shard will use for the launch (advisor r4 #1)
    cm, bi = _resolve_tiles(cap, h, i_dim, jnp.dtype(cfg.dtype).name, True)
    k = cfg.expert_top_k
    cu = _combine_chunk_rows(k)
    n_experts = cfg.num_experts
    weights = 2 * h * (2 * bi if cfg.gated_ffn else bi) * dt + 2 * bi * h * dt
    # xs, yv tiles (model dtype) + acc (f32)
    tiles = cm * h * (2 * dt + 4)
    # drain combine: y rows (dtype) + weight col + out rows (f32)
    chunk = cu * k * h * dt + cu * k * 4 + cu * h * 4
    # conservative SMEM budget: the sorted-row map plus the count matrices
    # must stay well under the ~1 MiB scalar memory of current TPU cores
    smem_bytes = n_experts * cap * 4 + 2 * n_experts * 4
    return (weights + tiles + chunk <= 15 * 2**20
            and smem_bytes <= 256 * 2**10)


def _fuse_combine_enabled(cfg: MoEConfig, s_loc: int, h: int, i_dim: int,
                          cap: int, d_world: int | None = None) -> bool:
    """Whether the weighted un-permute runs inside the RDMA kernel.

    OPT-IN (``FLASHMOE_FUSED_COMBINE=1``) until a measurement on the
    chip shows it beating the XLA combine: the sorted-return restructure
    (round 5) moved the cost from S*K sequential VPU row-adds to per-row
    return DMAs whose issue cost overlaps the FFN, but the DMA-engine
    behavior of thousands of [1, h] remote copies on real ICI is exactly
    the kind of question only a measurement answers — the same
    measured-before-default policy applied to the gather-fused kernel in
    round 3.  Requires a multi-rank ep world: at d_world == 1 there is no
    communication to overlap and the per-row copies are pure overhead
    over the XLA combine.  Even when requested, memory-infeasible configs
    fall back to the XLA combine (same math, no return-path overlap)
    rather than failing Mosaic compilation.
    """
    if os.environ.get("FLASHMOE_FUSED_COMBINE") != "1":
        return False
    if (d_world if d_world is not None else cfg.ep) <= 1:
        return False
    ok = _fuse_combine_budget_ok(cfg, s_loc, h, i_dim, cap)
    if not ok:
        import warnings
        warnings.warn(
            "FLASHMOE_FUSED_COMBINE=1 requested but the combine maps/"
            "chunks exceed the SMEM/VMEM budget; using the XLA "
            "combine instead", stacklevel=2)
    return ok


def fused_ep_moe_layer(params, x, cfg: MoEConfig, mesh: Mesh, *,
                       interpret: bool = False,
                       use_pallas_gate: bool | None = None,
                       token_axes: tuple[str, ...] = ("ep",),
                       collective_id: int = 7,
                       detect_races: bool = False,
                       src_order=None) -> MoEOutput:
    """Expert-parallel MoE with the fused in-kernel all-to-all.

    Same contract as :func:`flashmoe_tpu.parallel.ep.ep_moe_layer`.  Gated
    (SwiGLU) experts stream through the kernel with chunk-interleaved
    gate|up weights; shared experts run XLA-side on the local token shard
    (they are replicated dense compute, not communication).

    ``src_order`` ([D, D] int32; row r = the order in which rank r
    processes source slabs, starting with r itself) overrides the default
    ring schedule — pass :func:`flashmoe_tpu.parallel.topology.
    arrival_order` on heterogeneous fabrics so slow-linked sources are
    processed last instead of stalling earlier slabs (the reference's
    arrival-order subscriber, ``os/subscriber.cuh:333-451``, bound
    statically from the measured topology).
    """

    if cfg.wire_dtype or cfg.wire_dtype_combine:
        # config.py already rejects moe_backend='fused' + wire; this
        # guards DIRECT layer calls so a wire knob is never silently
        # ignored by the raw-slab RDMA transport
        raise ValueError(
            "fused_ep_moe_layer moves raw slabs in-kernel and cannot "
            "honor wire_dtype compression; use ep_moe_layer or "
            "ragged_ep_moe_layer")
    d_world = mesh.shape["ep"]
    if src_order is None:
        # a bootstrapped runtime on a heterogeneous fabric publishes its
        # arrival-order schedule (gated on this mesh's device ordering
        # actually matching the table's rank indexing); everywhere else
        # the ring default stands
        from flashmoe_tpu.runtime.bootstrap import current_src_order

        src_order = current_src_order(mesh, d_world)
    if src_order is None:
        from flashmoe_tpu.parallel.topology import default_ring

        src_order = jnp.asarray(default_ring(d_world))
    else:
        if src_order.shape != (d_world, d_world):
            raise ValueError(
                f"src_order must be [{d_world}, {d_world}] (one "
                f"processing order per ep rank), got {src_order.shape}")
        # a row that is not an own-first permutation would make the kernel
        # process a slab whose recv semaphore was never awaited (step 0)
        # or wait on the never-signaled own slab — a silent race or a
        # hang; src_order normally comes concrete from arrival_order, so
        # check it at trace time when possible
        try:
            so = __import__("numpy").asarray(src_order)
        except Exception:  # traced value: caller owns the invariant
            so = None
        if so is not None:
            for r in range(d_world):
                if so[r, 0] != r or sorted(so[r]) != list(range(d_world)):
                    raise ValueError(
                        f"src_order row {r} must be a permutation of "
                        f"0..{d_world - 1} starting with {r}, got "
                        f"{so[r].tolist()}")
        src_order = jnp.asarray(src_order, jnp.int32)

    def body(params, x, src_order):
        d = jax.lax.axis_size("ep")
        s_loc, h = x.shape
        nlx = cfg.num_experts // d
        cap = local_capacity(cfg, s_loc)
        # pad the capacity buffer to a row-tile multiple (e.g. CF=1.25 can
        # give cap=320 -> padded 320, cap=40 -> 64); counts stay clamped to
        # the real cap, so padded rows are never transferred or computed
        cap_pad = -(-cap // 32) * 32

        use_gate_pallas = (
            use_pallas_gate
            if use_pallas_gate is not None
            else (interpret or jax.default_backend() == "tpu")
        )
        # phase spans (telemetry.trace_span): the xprof counterpart of the
        # reference's NVTX "Flashmoe" domain — metadata only, no ops.
        # With cfg.profile_phases the spans also fence (prof.fence no-ops
        # on tracers) so the host phase timeline sees real durations.
        with trace_span("moe.gate"):
            r = router(x, params["gate_w"], cfg, use_pallas=use_gate_pallas,
                       interpret=interpret)
            if cfg.profile_phases:
                prof.fence(r)
        with trace_span("moe.dispatch"):
            plan = dsp.make_plan(r.expert_idx, cfg, cap)
            xbuf = dsp.dispatch(x.astype(cfg.dtype), plan, cfg, cap)
            if cap_pad != cap:
                xbuf = jnp.pad(xbuf, ((0, 0), (0, cap_pad - cap), (0, 0)))
            x_send = xbuf.reshape(d, nlx, cap_pad, h)
            if cfg.profile_phases:
                prof.fence(x_send)

        # routed-count matrices: what I send each (dest, expert) and what
        # each source sends my experts — shared knowledge on both ends, so
        # the kernel can skip absent tiles without noop signals
        send_cnt = jnp.minimum(plan.counts, cap).astype(jnp.int32).reshape(
            d, nlx
        )
        recv_cnt = jax.lax.all_to_all(
            send_cnt.reshape(d, 1, nlx), "ep", split_axis=0, concat_axis=0,
            tiled=False,
        ).reshape(d, nlx)

        quant_on = cfg.expert_quant is not None
        quant_err = None
        sc_kw = {}
        if quant_on:
            # quantized expert storage (flashmoe_tpu/quant/): the
            # kernel streams int8/e4m3 payloads (rowwin dequantizes in
            # VMEM; weights-once schedules dequantize at the
            # _fused_shard boundary).  Full-precision params quantize
            # in-graph first so the knob behaves identically whether
            # the state was stored quantized or not.  Inference-only
            # (config.py rejects is_training), so the custom-VJP
            # wrapper is bypassed below.
            from flashmoe_tpu import quant as qt

            if cfg.collect_stats:
                quant_err = qt.weight_quant_error(params, cfg)
            if not any(kk + qt.SCALE_SUFFIX in params
                       for kk in qt.QUANT_WEIGHT_KEYS):
                params = qt.quantize_ffn_params(params, cfg.expert_quant)
            w_args = (
                params["w_up"], params["b_up"],
                params["w_down"], params["b_down"],
                params.get("w_gate") if cfg.gated_ffn else None,
            )
            sc_kw = dict(
                wup_sc=params["w_up" + qt.SCALE_SUFFIX],
                wdn_sc=params["w_down" + qt.SCALE_SUFFIX],
                wg_sc=(params.get("w_gate" + qt.SCALE_SUFFIX)
                       if cfg.gated_ffn else None))
            if any(s is not None and s.shape[-2] != 1
                   for s in sc_kw.values()):
                # per-K-GROUP scales would boundary-dequantize here
                # while the planner prices the per-channel int8
                # streamer — a schedule/geometry the kernel never runs
                # (code-review finding).  Refuse instead of diverging.
                raise ValueError(
                    "the fused path supports per-OUTPUT-CHANNEL quant "
                    "scales only (quantize_state without group_size); "
                    "per-K-group states run on the collective/ragged "
                    "paths, or dequantize_state() + requantize "
                    "per-channel")
        else:
            # the same quant-off guard every layer path applies: a
            # quantized state must never astype raw payloads below
            from flashmoe_tpu.quant import ensure_unquantized

            ensure_unquantized(params)
            w_args = (
                params["w_up"].astype(cfg.dtype), params["b_up"],
                params["w_down"].astype(cfg.dtype), params["b_down"],
                (params["w_gate"].astype(cfg.dtype)
                 if cfg.gated_ffn else None),
            )
        i_dim = params["w_down"].shape[1]
        # tier-0 degradation needs the per-expert outputs BEFORE the
        # weighted combine, so the in-kernel (fused) combine is
        # incompatible with it — degrade forces the XLA combine branch
        # (same math, explicit ybuf).  A quantized store also keeps the
        # XLA combine: the sorted-return path has no quant arm.
        if (_fuse_combine_enabled(cfg, s_loc, h, i_dim, cap_pad, d)
                and not cfg.degrade_unhealthy_experts
                and not quant_on):
            kk = cfg.expert_top_k
            cu = _combine_chunk_rows(kk)
            rows_pad = -(-(s_loc * kk) // (cu * kk)) * (cu * kk)
            ret_pos, w_sorted = dsp.sorted_return_maps(
                plan, r.combine_weights, cfg, cap, rows_pad
            )
            if cap_pad != cap:
                ret_pos = jnp.pad(ret_pos, ((0, 0), (0, cap_pad - cap)))
            ret_pos = ret_pos.reshape(d, nlx, cap_pad)
            # each owner needs to know where its computed rows land in
            # every source's sorted buffer — the same exchange shape as
            # the count matrices
            recv_pos = jax.lax.all_to_all(
                ret_pos, "ep", split_axis=0, concat_axis=0, tiled=False,
            )
            with trace_span("moe.fused_kernel"):
                out = _fused_combine_core(
                    send_cnt, recv_cnt, src_order, ret_pos, recv_pos,
                    w_sorted[:, None], x_send, *w_args,
                    cfg, "ep", interpret, collective_id, detect_races, cu,
                )[:s_loc]
                if cfg.profile_phases:
                    prof.fence(out)
        else:
            with trace_span("moe.fused_kernel"):
                if quant_on:
                    # direct launch: the custom-VJP wrapper only exists
                    # for training, which config.py rejects under quant
                    y_recv = _fused_shard(
                        send_cnt, recv_cnt, src_order, x_send,
                        w_args[0], w_args[1], w_args[2], w_args[3],
                        cfg=cfg, axis="ep", interpret=interpret,
                        collective_id=collective_id,
                        detect_races=detect_races, w_gate=w_args[4],
                        **sc_kw)
                else:
                    y_recv = _fused_core(
                        send_cnt, recv_cnt, src_order, x_send, *w_args,
                        cfg, "ep", interpret, collective_id,
                        detect_races,
                    )
                if cfg.profile_phases:
                    prof.fence(y_recv)
            with trace_span("moe.combine"):
                ybuf = y_recv.reshape(cfg.num_experts, cap_pad, h)
                combine_w = r.combine_weights
                if cfg.degrade_unhealthy_experts:
                    # tier-0 (ops/health.py): same per-rank masking as the
                    # collective layer — ybuf rows are this rank's tokens'
                    # results per global expert
                    from flashmoe_tpu.ops import health as hlt

                    healthy = hlt.expert_health_capacity(ybuf)
                    ybuf, combine_w = hlt.degrade_outputs(
                        ybuf, combine_w, r.expert_idx, healthy)
                out = dsp.combine(ybuf, plan, combine_w, cfg, cap_pad)
                if cfg.profile_phases:
                    prof.fence(out)
        if cfg.num_shared_experts:
            out = out + shared_expert_ffn(
                x.astype(cfg.dtype), params, cfg
            ).astype(out.dtype)

        aux = jax.lax.pmean(r.aux_loss, token_axes) * cfg.aux_loss_coef
        z = jax.lax.pmean(r.z_loss, token_axes)
        counts = jax.lax.psum(r.expert_counts, token_axes)
        stats = None
        if cfg.collect_stats:
            # the fused kernel drops at the same capacity clamp (send_cnt
            # = min(counts, cap)), so the collective layer's stats math
            # applies verbatim
            local = st.moe_stats(r, cfg, cap)
            stats = st.reduce_stats(local, r.probs_mean, token_axes)
            if cfg.degrade_unhealthy_experts:
                from flashmoe_tpu.ops import health as hlt

                stats = hlt.attach_degradation(stats, healthy,
                                               r.expert_idx, token_axes)
            if quant_err is not None:
                stats = st.with_quant_error(stats, quant_err,
                                            token_axes)
        return MoEOutput(out.astype(cfg.dtype), aux, z, counts, stats)

    pspecs = {k: P("ep") if k != "gate_w" and not k.startswith("shared")
              else P() for k in params}
    stats_specs = (st.MoEStats(*([P()] * len(st.MoEStats._fields)))
                   if cfg.collect_stats else None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, P(token_axes, None), P()),
        out_specs=MoEOutput(P(token_axes, None), P(), P(), P(),
                            stats_specs),
        check_vma=False,
    )
    out = fn(params, x, src_order)
    if interpret and not isinstance(out.out, jax.core.Tracer):
        # Eager interpret mode runs the kernel's DMAs on io_callback
        # threads that can still be draining when the caller dispatches
        # the next computation; JAX's interpreter can deadlock against
        # them (observed: combine-test thread stuck in
        # interpret_pallas_call store while the next trace blocks).
        # Synchronize before handing results back — debug mode only, and
        # a no-op under jit where out is a Tracer.
        jax.block_until_ready(out.out)
    return out
