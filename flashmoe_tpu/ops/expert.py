"""Grouped expert FFN: up-GEMM -> (+bias) -> activation -> down-GEMM -> (+bias).

TPU-native re-design of the reference's expert pipeline: there, the fused
kernel's processors run tile-level ``preGEMM``/``postGEMM`` Tasks through the
``fGET`` fused GEMM+bias+activation (``csrc/include/flashmoe/os/processor/
processor.cuh:339-468``), with an in-kernel scheduler feeding tiles as packets
arrive, and a standalone two-GEMM ``expert`` kernel used for throughput probes
(``csrc/include/flashmoe/moe/expert.cuh:194-372``).

On TPU the scheduler's job — keeping the matrix units fed while tiles stream
— is done by the Pallas grid pipeline: the grid is (row-tile, intermediate-
chunk); weights for each chunk are DMA'd HBM->VMEM by the pipeline while the
previous chunk computes on the MXU, and a float32 VMEM accumulator carries
the down-projection partial sums across chunks.  Group (=expert) selection is
data-dependent, handled megablox-style with a scalar-prefetched per-row-tile
group id that the BlockSpec index maps consume — so each row tile streams
exactly its own expert's weights, and skewed expert loads never waste MXU
steps on padding rows of other experts.

Two implementations with identical semantics:
  * :func:`expert_ffn_dense` — batched einsum over [E, C, H] (XLA path).
  * :func:`grouped_ffn`      — the Pallas kernel over row-sorted tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashmoe_tpu.config import BLOCK_M, MoEConfig
from flashmoe_tpu.models.reference import activation_fn

# default intermediate-dimension chunk for the grouped kernels (VMEM
# working-set sizing); call sites share this instead of bare literals
DEFAULT_BLOCK_I = 512

# Mosaic scopes a kernel to 16 MiB of VMEM unless told otherwise; a v5e
# core has 128 MiB.  Each kernel here counts what its double-buffered
# blocks and scratch take (``need``), asks for that much, and shrinks its
# chunk only when even the ceiling would not hold it.  block_m is the
# caller's (``tile_gid`` is laid out by it), so only chunks shrink.
_VMEM_DEFAULT = 16 << 20
_VMEM_CEILING = 64 << 20


def _fit_chunk(dim: int, block: int, need) -> int:
    """Largest chunk of ``dim`` no larger than ``block`` whose working set
    ``need(chunk)`` (bytes) fits :data:`_VMEM_CEILING`."""
    while need(block) > _VMEM_CEILING and block > 8:
        block = _auto_block(dim, block - 1)
    return block


def _vmem_params(need: int) -> pltpu.CompilerParams:
    """Scoped-VMEM request for a working set of ``need`` bytes, with a
    quarter on top for Mosaic's own temporaries."""
    limit = need + need // 4 + (2 << 20)
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(max(limit, _VMEM_DEFAULT), _VMEM_CEILING))


def _ffn_vmem(block_m: int, h: int, bi: int, gated: bool, x_isz: int,
              w_isz: int, res_outs: int = 0) -> int:
    """Bytes one grouped-FFN grid step keeps in VMEM: two input and two
    output row tiles, the f32 accumulator, two buffers each of the up
    (and gate) and down weight chunks, and ``res_outs`` double-buffered
    [block_m, bi] residual tiles."""
    rows = 4 * block_m * h * x_isz + block_m * h * 4
    weights = 2 * h * bi * ((2 if gated else 1) + 1) * w_isz
    return rows + weights + res_outs * 2 * block_m * bi * x_isz


def _ffn_chunks(x, w_up, w_gate, block_m: int, block_i: int, gated: bool,
                res_outs: int = 0):
    """How one grouped-FFN launch walks the intermediate axis: the chunk
    ``bi`` (all of I when ``block_i`` allows it, else the largest divisor
    of I under ``block_i``, shrunk until the working set fits VMEM) and
    the compiler params that ask for the VMEM the launch needs.  A gated
    FFN's gate weights are an operand of their own beside ``w_up``, under
    the same index map: no launch builds an array of the weights' size."""
    if gated and w_gate is None:
        raise ValueError("gated_ffn requires w_gate")
    _, h, i = w_up.shape
    need = lambda b: _ffn_vmem(block_m, h, b, gated, x.dtype.itemsize,
                               w_up.dtype.itemsize, res_outs)
    bi = _fit_chunk(i, i if block_i >= i else _auto_block(i, block_i), need)
    return bi, _vmem_params(need(bi))


def _chunk_of_step(ti, j, *scalars):
    """The intermediate chunk whose weight blocks grid step ``(ti, j)``
    holds: ``j``, every kernel's walk."""
    return j


def _chunk_under_live(nj: int):
    """:func:`_chunk_of_step` for a launch with ``live_tiles`` that walks
    ``nj`` > 1 chunks, where the pipeline's rule (a block is fetched when
    its index differs from the step before) would cost bytes no row needs.

    A live tile walks the chunks forward when its number is even and
    backward when it is odd, so two consecutive tiles meet at ONE chunk:
    where they are one expert's, that chunk's blocks are fetched once for
    the pair (with ``j`` under both, the index wraps and a group of t
    tiles streams ``t x nj`` blocks; so it streams ``t x (nj - 1) + 1``).
    The float32 sum over the chunks of an odd tile runs in the other
    order.

    A tile past the last live one holds the chunk the last live tile
    ended at, at every ``j`` (its group id repeats that tile's too): from
    the last live step to the end of the grid no weight block's index
    changes and nothing is fetched.  With ``j`` there the dead tiles
    streamed the last live expert's matrices once more EACH."""
    def walk(ti, j):
        return jnp.where(ti % 2 == 0, j, nj - 1 - j)

    def chunk(ti, j, gid, live_ref):
        last = live_ref[0] - 1
        return jnp.where(ti <= last, walk(ti, j), walk(last, nj - 1))

    return chunk


def _up_specs(h: int, bi: int, gated: bool, chunk=_chunk_of_step):
    """BlockSpecs of the up (and gate) weight chunk of a row tile's
    expert: ``[E, H, I]`` blocks ``(1, H, bi)`` chosen by the
    scalar-prefetched group id, the chunk by ``chunk``."""
    spec = pl.BlockSpec(
        (1, h, bi), lambda ti, j, gid, *s: (gid[ti], 0, chunk(ti, j, gid, *s)),
        memory_space=pltpu.VMEM)
    return [spec, spec] if gated else [spec]


def _up_products(x, wup_ref, wg_ref, bup_ref):
    """One I-chunk's float32 up product with its bias, and the gate
    product (None when the FFN is not gated)."""
    up = jnp.dot(x, wup_ref[0], preferred_element_type=jnp.float32)
    up = up + bup_ref[0, 0, :].astype(jnp.float32)
    if wg_ref is None:
        return up, None
    return up, jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)


# ----------------------------------------------------------------------
# XLA path: batched over the capacity buffer
# ----------------------------------------------------------------------

def expert_ffn_dense(xs, params, cfg: MoEConfig):
    """Batched per-expert FFN on the capacity buffer.

    xs: [E, C, H] -> [E, C, H].  XLA maps the batched matmuls straight onto
    the MXU; activation/bias fuse into the GEMM epilogues automatically.
    """
    act = activation_fn(cfg.hidden_act)
    up = jnp.einsum(
        "ech,ehi->eci", xs, params["w_up"].astype(xs.dtype),
        preferred_element_type=cfg.accum_dtype,
    ) + params["b_up"][:, None, :].astype(cfg.accum_dtype)
    if cfg.gated_ffn:
        g = jnp.einsum(
            "ech,ehi->eci", xs, params["w_gate"].astype(xs.dtype),
            preferred_element_type=cfg.accum_dtype,
        )
        hidden = act(g) * up
    else:
        hidden = act(up)
    down = jnp.einsum(
        "eci,eih->ech", hidden.astype(xs.dtype),
        params["w_down"].astype(xs.dtype),
        preferred_element_type=cfg.accum_dtype,
    ) + params["b_down"][:, None, :].astype(cfg.accum_dtype)
    return down.astype(xs.dtype)


# ----------------------------------------------------------------------
# Pallas grouped kernel
# ----------------------------------------------------------------------

def _ffn_kernel(gid_ref, *refs, act_name, gated, live):
    """One (row-tile, I-chunk) grid step.  ``refs``: the count of live
    tiles (when ``live``: a second scalar), the row tile, the up weights,
    the gate weights (when ``gated``), the up bias, the down weights and
    bias, the output tile and the float32 accumulator."""
    live_ref, refs = (refs[0], refs[1:]) if live else (None, refs)
    x_ref, wup_ref = refs[:2]
    wg_ref = refs[2] if gated else None
    bup_ref, wdn_ref, bdn_ref, out_ref, acc_ref = refs[-5:]
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    act = activation_fn(act_name)

    def step():
        @pl.when(j == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        x = x_ref[:]
        up, g = _up_products(x, wup_ref, wg_ref, bup_ref)
        hidden = act(g) * up if gated else act(up)
        acc_ref[:] += jnp.dot(
            hidden.astype(x.dtype), wdn_ref[0],
            preferred_element_type=jnp.float32
        )

        @pl.when(j == nj - 1)
        def _():
            out_ref[:] = (
                acc_ref[:] + bdn_ref[0, 0, :].astype(jnp.float32)
            ).astype(out_ref.dtype)

    if live:
        # a tile past the last populated one: nothing to compute, and
        # nothing to fetch, because no weight block's index moves there:
        # its group id repeats the last live tile's and its chunk stays
        # where that tile ended (``_chunk_under_live``; with one chunk
        # there is one index).  Which chunk a live step holds is the
        # index maps' business: the sum below takes them as they come
        pl.when(pl.program_id(0) < live_ref[0])(step)
    else:
        step()


@functools.partial(
    jax.jit, static_argnames=("act_name", "gated", "block_m", "block_i",
                              "interpret"),
)
def grouped_ffn(x, tile_gid, w_up, b_up, w_down, b_down, w_gate=None,
                live_tiles=None, *,
                act_name: str, gated: bool = False, block_m: int = BLOCK_M,
                block_i: int = DEFAULT_BLOCK_I, interpret: bool = False):
    """Grouped FFN over row-sorted tokens.

    x:        [T, H] tokens, grouped so rows of one row-tile share an expert.
    tile_gid: [T // block_m] int32 expert id owning each row tile.
    w_up:     [E, H, I]; b_up: [E, I]; w_down: [E, I, H]; b_down: [E, H];
    w_gate:   [E, H, I] for SwiGLU-style experts.
    live_tiles: [1] int32, the populated row tiles (a ragged plan's
              ``num_rows // block_m``); the tiles from there on are not
              computed, their rows of the output hold nothing, and they
              fetch no weights however many chunks the intermediate axis
              is walked in, PROVIDED their ``tile_gid`` repeats the last
              live tile's (``ops/ragged.sorted_rows_plan`` lays it out
              so): their row tile and output tile still move.  None:
              every tile is computed.

    Returns [T, H].  The scalar-prefetched ``tile_gid`` drives the weight
    BlockSpec index maps, so each row tile DMAs only its own expert's weight
    chunks (megablox-style block-sparse grouped GEMM).  Where the
    intermediate axis is ONE chunk (an expert's matrices, double buffered,
    within ``_VMEM_CEILING``), consecutive tiles of one expert fetch them
    once; walked in ``nj`` > 1, every tile streams its expert's chunks
    again, so an expert streams once a TILE (less one chunk of ``nj`` a
    tile after its first where ``live_tiles`` is given:
    :func:`_chunk_under_live`).
    """
    t, h = x.shape
    e, _, i = w_up.shape
    if t % block_m:
        raise ValueError(f"rows {t} must be a multiple of block_m={block_m}")
    bi, vmem = _ffn_chunks(x, w_up, w_gate, block_m, block_i, gated)
    nt, nj = t // block_m, i // bi
    live = live_tiles is not None
    scalars = (tile_gid, live_tiles) if live else (tile_gid,)
    chunk = _chunk_under_live(nj) if live and nj > 1 else _chunk_of_step
    up_weights = (w_up, w_gate) if gated else (w_up,)

    # biases are lifted to [E, 1, dim] so their (1, dim) trailing block shape
    # satisfies the TPU (8, 128) tiling rule via the equal-dimension escape
    b_up3 = b_up.reshape(e, 1, i)
    b_down3 = b_down.reshape(e, 1, h)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(nt, nj),
        in_specs=[
            pl.BlockSpec((block_m, h), lambda ti, j, *_: (ti, 0),
                         memory_space=pltpu.VMEM),
            *_up_specs(h, bi, gated, chunk),
            pl.BlockSpec(
                (1, 1, bi),
                lambda ti, j, gid, *s: (gid[ti], 0, chunk(ti, j, gid, *s)),
                memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (1, bi, h),
                lambda ti, j, gid, *s: (gid[ti], chunk(ti, j, gid, *s), 0),
                memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, h), lambda ti, j, gid, *_: (gid[ti], 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_m, h), lambda ti, j, *_: (ti, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((block_m, h), jnp.float32)],
    )
    flops = 2 * t * h * i * (3 if gated else 2)
    return pl.pallas_call(
        functools.partial(_ffn_kernel, act_name=act_name, gated=gated,
                          live=live),
        name="fm_ffn_fwd",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=x.size * x.dtype.itemsize
            + sum(w.size * w.dtype.itemsize for w in up_weights)
            + w_down.size * w_down.dtype.itemsize,
            transcendentals=t * i,
        ),
        compiler_params=vmem,
        interpret=interpret,
    )(*scalars, x, *up_weights, b_up3, w_down, b_down3)


# ----------------------------------------------------------------------
# Gather-fused grouped kernel: expert slabs built from token rows on the
# fly, never materializing the [E, C, H] dispatch buffer in HBM
# ----------------------------------------------------------------------

def _ffn_gather_kernel(gid_ref, tok_ref, x_ref, wup_ref, *refs,
                       act_name, gated, block_m):
    """One (row-tile, I-chunk) grid step with in-kernel token gather
    (``refs`` as :func:`_ffn_kernel`'s from the gate weights on, then the
    gathered row tiles and the DMA semaphores).

    At each tile's first I-chunk the kernel issues per-row DMAs that pull
    the NEXT tile's token rows from ``x`` (HBM) into the alternate VMEM
    slab, then waits for the current tile's rows — the gather streams
    behind the previous tile's GEMMs exactly like the reference's packet
    stage building heap cells from ``tokenIds`` while processors compute
    (``packet.cuh:99-206``).
    """
    wg_ref = refs[0] if gated else None
    bup_ref, wdn_ref, bdn_ref, out_ref, xtile, acc_ref, sems = refs[-7:]
    ti = pl.program_id(0)
    j = pl.program_id(1)
    nt = pl.num_programs(0)
    nj = pl.num_programs(1)
    act = activation_fn(act_name)

    def start_gather(tile, slot):
        def body(i, _):
            tok = tok_ref[tile * block_m + i]
            pltpu.make_async_copy(
                x_ref.at[pl.ds(tok, 1), :],
                xtile.at[slot, pl.ds(i, 1), :],
                sems.at[slot],
            ).start()
            return 0
        jax.lax.fori_loop(0, block_m, body, 0)

    def wait_gather(slot):
        # per-row waits mirror the per-row starts one-for-one, so the
        # semaphore balance is exact under either byte- or completion-
        # counting DMA semantics
        def body(i, _):
            pltpu.make_async_copy(
                x_ref.at[pl.ds(0, 1), :],
                xtile.at[slot, pl.ds(i, 1), :],
                sems.at[slot],
            ).wait()
            return 0
        jax.lax.fori_loop(0, block_m, body, 0)

    slot = jax.lax.rem(ti, 2)

    @pl.when(j == 0)
    def _():
        @pl.when(ti == 0)
        def _():
            start_gather(0, 0)

        @pl.when(ti + 1 < nt)
        def _():
            start_gather(ti + 1, jax.lax.rem(ti + 1, 2))

        wait_gather(slot)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = xtile[slot]
    up, g = _up_products(x, wup_ref, wg_ref, bup_ref)
    hidden = act(g) * up if gated else act(up)
    acc_ref[:] += jnp.dot(
        hidden.astype(x.dtype), wdn_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(j == nj - 1)
    def _():
        out_ref[:] = (
            acc_ref[:] + bdn_ref[0, 0, :].astype(jnp.float32)
        ).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("act_name", "gated", "block_m", "block_i",
                              "interpret"),
)
def grouped_ffn_tokens(x, src_tok, tile_gid, w_up, b_up, w_down, b_down,
                       w_gate=None, *, act_name: str, gated: bool = False,
                       block_m: int = BLOCK_M,
                       block_i: int = DEFAULT_BLOCK_I,
                       interpret: bool = False):
    """Grouped FFN reading token rows directly: the dispatch gather fused
    into the kernel (no [T, H] grouped buffer ever hits HBM).

    x:        [S, H] tokens in natural order (stays in HBM).
    src_tok:  [T] int32 source token per slab row (expert-grouped order).
    tile_gid: [T // block_m] int32 expert id owning each row tile.

    Returns [T, H] in slab order.  Rows whose slot is unpopulated compute
    on token 0's data; combine never reads them.  Forward-only: the
    training path keeps the explicit dispatch so the backward has its
    residuals (see :func:`grouped_ffn_ad`).
    """
    s, h = x.shape
    (t,) = src_tok.shape
    e, _, i = w_up.shape
    if t % block_m:
        raise ValueError(f"slab rows {t} must be a multiple of {block_m}")
    bi, vmem = _ffn_chunks(x, w_up, w_gate, block_m, block_i, gated)
    nt, nj = t // block_m, i // bi
    up_weights = (w_up, w_gate) if gated else (w_up,)
    b_up3 = b_up.reshape(e, 1, i)
    b_down3 = b_down.reshape(e, 1, h)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nt, nj),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # x: full [S, H] in HBM
            *_up_specs(h, bi, gated),
            pl.BlockSpec((1, 1, bi), lambda ti, j, gid, tok: (gid[ti], 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bi, h), lambda ti, j, gid, tok: (gid[ti], j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, h), lambda ti, j, gid, tok: (gid[ti], 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_m, h), lambda ti, j, gid, tok: (ti, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, block_m, h), x.dtype),
            pltpu.VMEM((block_m, h), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    flops = 2 * t * h * i * (3 if gated else 2)
    return pl.pallas_call(
        functools.partial(_ffn_gather_kernel, act_name=act_name, gated=gated,
                          block_m=block_m),
        name="fm_ffn_fwd_gather",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=t * h * x.dtype.itemsize * 2
            + sum(w.size * w.dtype.itemsize for w in up_weights)
            + w_down.size * w_down.dtype.itemsize,
            transcendentals=t * i,
        ),
        compiler_params=vmem,
        interpret=interpret,
    )(tile_gid, src_tok, x, *up_weights, b_up3, w_down, b_down3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12))
def grouped_ffn_tokens_ad(x, src_tok, tile_gid, w_up, b_up, w_down, b_down,
                           w_gate, act_name, gated, block_m, block_i,
                           interpret):
    """Differentiable wrapper over :func:`grouped_ffn_tokens`.

    The forward is the cheap gather-fused kernel (no residuals written);
    under differentiation the backward re-gathers the slab rows and
    reuses the residual-saving grouped-FFN VJP, scattering dX back to
    token order.  Costs one extra forward recompute — only paid when
    someone actually differentiates through the inference path."""
    return grouped_ffn_tokens(
        x, src_tok, tile_gid, w_up, b_up, w_down, b_down, w_gate,
        act_name=act_name, gated=gated, block_m=block_m, block_i=block_i,
        interpret=interpret,
    )


def _gft_fwd(x, src_tok, tile_gid, w_up, b_up, w_down, b_down, w_gate,
             act_name, gated, block_m, block_i, interpret):
    y = grouped_ffn_tokens(
        x, src_tok, tile_gid, w_up, b_up, w_down, b_down, w_gate,
        act_name=act_name, gated=gated, block_m=block_m, block_i=block_i,
        interpret=interpret,
    )
    return y, (x, src_tok, tile_gid, w_up, b_up, w_down, b_down, w_gate)


def _gft_bwd(act_name, gated, block_m, block_i, interpret, res, dy):
    import numpy as np

    x, src_tok, tile_gid, w_up, b_up, w_down, b_down, w_gate = res
    xb = x[src_tok]
    if gated:
        def f(xb_, wu, bu, wd, bd, wg):
            return grouped_ffn_ad(xb_, tile_gid, wu, bu, wd, bd, wg,
                                  act_name, gated, block_m, block_i,
                                  interpret)
        _, vjp = jax.vjp(f, xb, w_up, b_up, w_down, b_down, w_gate)
        dxb, dwu, dbu, dwd, dbd, dwg = vjp(dy)
    else:
        def f(xb_, wu, bu, wd, bd):
            return grouped_ffn_ad(xb_, tile_gid, wu, bu, wd, bd, None,
                                  act_name, gated, block_m, block_i,
                                  interpret)
        _, vjp = jax.vjp(f, xb, w_up, b_up, w_down, b_down)
        dxb, dwu, dbu, dwd, dbd = vjp(dy)
        dwg = None
    dx = jnp.zeros(x.shape, jnp.float32).at[
        src_tok].add(dxb.astype(jnp.float32)).astype(x.dtype)
    ct_int = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dx, ct_int(src_tok), ct_int(tile_gid), dwu, dbu, dwd, dbd, dwg)


grouped_ffn_tokens_ad.defvjp(_gft_fwd, _gft_bwd)


def _capacity_tiling(c: int, cfg: MoEConfig | None = None
                     ) -> tuple[int, int, int]:
    """Shared row-tile selection for the capacity-buffer kernels: returns
    ``(block_m, padded_capacity, block_i)``.  Capacities <= 512 round up
    to the sublane multiple (each expert's weights stream through VMEM
    exactly once); larger ones tile at the largest dividing block.

    When a measured tuning entry matches (``flashmoe_tpu.tuning`` — the
    TPU analogue of the reference's per-arch trait table,
    ``arch.cuh:95-222``), its block sizes override the heuristic."""
    if c <= 512:
        bm = ((c + 7) // 8) * 8
    else:
        bm = next(b for b in (512, 256, 128) if c % b == 0) if any(
            c % b == 0 for b in (512, 256, 128)
        ) else 512
    block_i = 512 if bm <= 256 else 256
    if cfg is not None:
        from flashmoe_tpu import tuning

        t = tuning.lookup(
            "capacity_ffn", h=cfg.hidden_size, i=cfg.intermediate_size,
            dtype=jnp.dtype(cfg.dtype).name,
        )
        bm_t = t.get("block_m")
        # same ignore-if-not-dividing contract as the fused kernel's cm
        # override: a block measured at a large capacity must not inflate
        # a small runtime capacity's padding (tuning entries match on
        # (h, i, dtype) only)
        if bm_t and bm_t % 8 == 0 and c % bm_t == 0:
            bm = bm_t
        if t.get("block_i"):
            block_i = t["block_i"]  # _auto_block re-fits it to I below
    cp = ((c + bm - 1) // bm) * bm
    return bm, cp, block_i


def capacity_ffn_gather(x, plan, cfg: MoEConfig, capacity: int, params, *,
                        interpret: bool = False):
    """Capacity-path FFN with the dispatch gather fused into the kernel.

    Pads capacity to the row-tile size, derives per-slot source tokens
    from the plan, and runs the gather-fused kernel (differentiable via
    re-gather, :func:`grouped_ffn_tokens_ad`).  Returns ``([E, Cp, H],
    Cp)`` — combine must use the padded capacity so flat slot indices
    line up.
    """
    from flashmoe_tpu.ops import dispatch as dsp

    _, h = x.shape
    e = cfg.num_experts
    bm, cp, block_i = _capacity_tiling(capacity, cfg)
    src_tok, _ = dsp.dispatch_indices(plan, cfg, cp)
    tiles_per_e = cp // bm
    tile_gid = jnp.arange(e * tiles_per_e, dtype=jnp.int32) // tiles_per_e
    y = grouped_ffn_tokens_ad(
        x, src_tok.reshape(-1), tile_gid,
        params["w_up"].astype(x.dtype), params["b_up"],
        params["w_down"].astype(x.dtype), params["b_down"],
        params["w_gate"].astype(x.dtype) if cfg.gated_ffn else None,
        cfg.hidden_act, cfg.gated_ffn, bm, block_i, interpret,
    )
    return y.reshape(e, cp, h), cp


# ----------------------------------------------------------------------
# Grouped matmul / transposed grouped matmul — the backward kernels
# ----------------------------------------------------------------------

def _auto_block(dim: int, cap: int) -> int:
    """Largest chunk <= cap that divides dim (config validation keeps dims
    64-multiples, so this lands on an MXU-friendly size instead of
    rejecting e.g. H=768)."""
    for b in (512, 448, 384, 320, 256, 192, 128, 64, 32, 16, 8):
        if b <= cap and dim % b == 0:
            return b
    raise ValueError(f"dimension {dim} not a multiple of 8")

def _gmm_kernel(gid_ref, x_ref, w_ref, out_ref, acc_ref, *, transpose_w):
    """One (row-tile, N-chunk, K-chunk) grid step of out = x @ w[gid] (or
    @ w[gid]^T when ``transpose_w`` — the weight block is then [bn, bk] and
    the contraction runs over its last dim, so no transposed weight copy is
    ever materialized in HBM)."""
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if transpose_w:
        acc_ref[:] += jax.lax.dot_general(
            x_ref[:], w_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    else:
        acc_ref[:] += jnp.dot(
            x_ref[:], w_ref[0], preferred_element_type=jnp.float32
        )

    @pl.when(j == nj - 1)
    def _():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("transpose_w", "block_m", "block_k",
                              "out_dtype", "interpret"),
)
def grouped_matmul(x, tile_gid, w, *, transpose_w: bool = False,
                   block_m: int = BLOCK_M, block_k: int = 512,
                   out_dtype=None, interpret: bool = False):
    """out[T, N] = x[T, K] @ w[gid(tile), K, N]   (transpose_w: w is
    [E, N, K] and contracts on its last dim).

    The grouped-GEMM primitive of the backward pass: dA and dX are grouped
    matmuls against the *forward* weight layouts with ``transpose_w=True``.
    N stays whole unless the [block_m, N] output tile and the [N, bk]
    weight chunk would outgrow VMEM (Mixtral's I=14336), then it is
    chunked too.
    """
    t, k = x.shape
    if transpose_w:
        e, n, kw = w.shape
    else:
        e, kw, n = w.shape
    if kw != k:
        raise ValueError(f"contraction mismatch: x K={k}, w K={kw}")
    if t % block_m:
        raise ValueError(f"rows {t} must be a multiple of {block_m}")
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    bk = _auto_block(k, block_k)
    need = lambda b: (2 * block_m * bk * x.dtype.itemsize
                      + 2 * bk * b * w.dtype.itemsize
                      + block_m * b * (2 * out_dtype.itemsize + 4))
    bn = _fit_chunk(n, n, need)
    nt, nn, nk = t // block_m, n // bn, k // bk

    if transpose_w:
        w_spec = pl.BlockSpec((1, bn, bk),
                              lambda ti, jn, j, gid: (gid[ti], jn, j),
                              memory_space=pltpu.VMEM)
    else:
        w_spec = pl.BlockSpec((1, bk, bn),
                              lambda ti, jn, j, gid: (gid[ti], j, jn),
                              memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, nn, nk),
        in_specs=[
            pl.BlockSpec((block_m, bk), lambda ti, jn, j, gid: (ti, j),
                         memory_space=pltpu.VMEM),
            w_spec,
        ],
        out_specs=pl.BlockSpec((block_m, bn),
                               lambda ti, jn, j, gid: (ti, jn),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((block_m, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w),
        name="fm_gmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, n), out_dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * t * k * n,
            bytes_accessed=x.size * x.dtype.itemsize
            + w.size * w.dtype.itemsize,
            transcendentals=0,
        ),
        compiler_params=_vmem_params(need(bn)),
        interpret=interpret,
    )(tile_gid, x, w)


def _tgmm_kernel(gid_ref, x_ref, dy_ref, out_ref):
    """One (K-chunk, N-chunk, row-tile) step of dW[e] += x_tile^T @ dy_tile.

    Row tiles sweep fastest and ``tile_gid`` is nondecreasing (both the
    capacity and the ragged layouts are expert-major), so all tiles of one
    expert revisit the same output block consecutively — the accumulation
    lives in the block's VMEM copy and flushes once per expert."""
    t = pl.program_id(2)
    contrib = jax.lax.dot_general(
        x_ref[:], dy_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    first = jnp.logical_or(
        t == 0, gid_ref[jnp.maximum(t - 1, 0)] != gid_ref[t]
    )

    @pl.when(first)
    def _():
        out_ref[0] = contrib.astype(out_ref.dtype)

    @pl.when(jnp.logical_not(first))
    def _():
        out_ref[0] += contrib.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("num_experts", "block_m", "block_k",
                              "block_n", "interpret"),
)
def tgmm(x, dy, tile_gid, num_experts: int, *, block_m: int = BLOCK_M,
         block_k: int = 512, block_n: int = 512,
         interpret: bool = False):
    """dW[E, K, N] = segment-sum over row tiles of x[T, K]^T @ dy[T, N].

    The weight-gradient kernel (megablox's transposed grouped GEMM):
    ``tile_gid`` MUST be nondecreasing.  Returns float32.
    """
    t, k = x.shape
    t2, n = dy.shape
    if t != t2:
        raise ValueError(f"row mismatch {t} vs {t2}")
    if t % block_m:
        raise ValueError(f"rows {t} must be a multiple of {block_m}")
    bk, bn = _auto_block(k, block_k), _auto_block(n, block_n)
    nt, nk, nn = t // block_m, k // bk, n // bn

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nk, nn, nt),
        in_specs=[
            pl.BlockSpec((block_m, bk), lambda jk, jn, ti, gid: (ti, jk),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_m, bn), lambda jk, jn, ti, gid: (ti, jn),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, bk, bn), lambda jk, jn, ti, gid: (gid[ti], jk, jn),
            memory_space=pltpu.VMEM,
        ),
    )
    out = pl.pallas_call(
        _tgmm_kernel,
        name="fm_tgmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_experts, k, n), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * t * k * n,
            bytes_accessed=(x.size + dy.size) * x.dtype.itemsize
            + num_experts * k * n * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(tile_gid, x, dy)
    # experts absent from tile_gid (zero routed tokens on the ragged path)
    # have blocks the kernel never visited — UNINITIALIZED memory, not
    # zeros.  Select, don't multiply: NaN garbage * 0 would stay NaN.
    present = jnp.zeros((num_experts,), jnp.bool_).at[tile_gid].set(True)
    return jnp.where(present[:, None, None], out, 0.0)


def _segment_bias_grad(d, tile_gid, num_experts: int, block_m: int):
    """db[E, N] = per-expert row sum of d[T, N] (tiny; XLA einsum)."""
    nt = d.shape[0] // block_m
    per_tile = d.reshape(nt, block_m, -1).sum(axis=1)
    oh = jax.nn.one_hot(tile_gid, num_experts, dtype=per_tile.dtype)
    return jnp.einsum("tn,te->en", per_tile, oh)


# ----------------------------------------------------------------------
# Residual-saving forward + custom VJP: the fused backward path
# ----------------------------------------------------------------------

def _ffn_res_kernel(gid_ref, x_ref, wup_ref, *refs, act_name, gated):
    """Same as :func:`_ffn_kernel` but additionally writes the
    pre-activation up (and gate) chunks — the residuals the backward needs,
    saved on the way through instead of recomputed."""
    wg_ref = refs[0] if gated else None
    (bup_ref, wdn_ref, bdn_ref, out_ref, u_out_ref, g_out_ref,
     acc_ref) = refs[-7:]
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    act = activation_fn(act_name)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:]
    up, g = _up_products(x, wup_ref, wg_ref, bup_ref)
    u_out_ref[:] = up.astype(u_out_ref.dtype)
    if gated:
        g_out_ref[:] = g.astype(g_out_ref.dtype)
        hidden = act(g) * up
    else:
        hidden = act(up)
    acc_ref[:] += jnp.dot(
        hidden.astype(x.dtype), wdn_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(j == nj - 1)
    def _():
        out_ref[:] = (
            acc_ref[:] + bdn_ref[0, 0, :].astype(jnp.float32)
        ).astype(out_ref.dtype)


def _grouped_ffn_res(x, tile_gid, w_up, b_up, w_down, b_down, w_gate, *,
                     act_name, gated, block_m, block_i, interpret):
    """Forward returning (y, u, g): u/g are the [T, I] pre-activation
    buffers (g is a zero-row placeholder when not gated)."""
    t, h = x.shape
    e, _, i = w_up.shape
    if t % block_m:
        raise ValueError(f"rows {t} must be a multiple of block_m={block_m}")
    bi, vmem = _ffn_chunks(x, w_up, w_gate, block_m, block_i, gated,
                           res_outs=2)
    nt, nj = t // block_m, i // bi
    up_weights = (w_up, w_gate) if gated else (w_up,)
    b_up3 = b_up.reshape(e, 1, i)
    b_down3 = b_down.reshape(e, 1, h)

    g_spec = (
        pl.BlockSpec((block_m, bi), lambda ti, j, gid: (ti, j),
                     memory_space=pltpu.VMEM)
        if gated else
        # not gated: the kernel never writes the gate residual — collapse
        # it to one block so no [T, I] buffer is allocated for garbage
        pl.BlockSpec((block_m, bi), lambda ti, j, gid: (0, 0),
                     memory_space=pltpu.VMEM)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, nj),
        in_specs=[
            pl.BlockSpec((block_m, h), lambda ti, j, gid: (ti, 0),
                         memory_space=pltpu.VMEM),
            *_up_specs(h, bi, gated),
            pl.BlockSpec((1, 1, bi), lambda ti, j, gid: (gid[ti], 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bi, h), lambda ti, j, gid: (gid[ti], j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, h), lambda ti, j, gid: (gid[ti], 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_m, h), lambda ti, j, gid: (ti, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_m, bi), lambda ti, j, gid: (ti, j),
                         memory_space=pltpu.VMEM),
            g_spec,
        ],
        scratch_shapes=[pltpu.VMEM((block_m, h), jnp.float32)],
    )
    y, u, g = pl.pallas_call(
        functools.partial(_ffn_res_kernel, act_name=act_name, gated=gated),
        name="fm_ffn_fwd_res",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((t, h), x.dtype),
            jax.ShapeDtypeStruct((t, i), x.dtype),
            jax.ShapeDtypeStruct((t, i) if gated else (block_m, bi),
                                 x.dtype),
        ],
        compiler_params=vmem,
        interpret=interpret,
    )(tile_gid, x, *up_weights, b_up3, w_down, b_down3)
    return y, u, (g if gated else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def grouped_ffn_ad(x, tile_gid, w_up, b_up, w_down, b_down, w_gate,
                   act_name, gated, block_m, block_i, interpret):
    """Differentiable grouped FFN: Pallas forward AND Pallas backward.

    The backward's four large GEMMs run on kernels (dA and dX via
    :func:`grouped_matmul` ``transpose_w=True`` against the forward weight
    layouts; dW_up/dW_down via :func:`tgmm`), with pre-activations saved
    from the forward instead of recomputed — unlike the reference, which
    has no backward at all (SURVEY §2.6), and unlike round 1, which
    recomputed the whole forward through XLA."""
    return grouped_ffn(
        x, tile_gid, w_up, b_up, w_down, b_down, w_gate,
        act_name=act_name, gated=gated, block_m=block_m, block_i=block_i,
        interpret=interpret,
    )


def _gffn_fwd(x, tile_gid, w_up, b_up, w_down, b_down, w_gate,
              act_name, gated, block_m, block_i, interpret):
    y, u, g = _grouped_ffn_res(
        x, tile_gid, w_up, b_up, w_down, b_down, w_gate,
        act_name=act_name, gated=gated, block_m=block_m, block_i=block_i,
        interpret=interpret,
    )
    return y, (x, tile_gid, w_up, b_up, w_down, b_down, w_gate, u, g)


def ffn_backward_core(x, tile_gid, w_up, w_down, w_gate, u, g, dy, *,
                      act_name, gated, block_m, interpret):
    """Shared backward math over pre-activation residuals (u, g).

    All four large GEMMs run on the Pallas kernels: dHidden and dX via
    :func:`grouped_matmul` (transposed-weight contraction), dW via
    :func:`tgmm`.  Returns float32 (dx, d_wu, d_bu, d_wd, d_bd, d_wg) —
    d_wg is None when not gated.  Used by both the single-device grouped
    FFN VJP and the fused EP layer's VJP."""
    act = activation_fn(act_name)
    e = w_up.shape[0]
    dyc = dy.astype(x.dtype)

    # dHidden = dY @ w_down^T   [T, I]
    d_hidden = grouped_matmul(
        dyc, tile_gid, w_down, transpose_w=True, block_m=block_m,
        out_dtype=jnp.float32, interpret=interpret,
    )
    uf = u.astype(jnp.float32)
    if gated:
        gf = g.astype(jnp.float32)
        act_g, act_vjp = jax.vjp(act, gf)
        d_gate = act_vjp(d_hidden * uf)[0]
        d_up = d_hidden * act_g
        hidden = (act_g * uf).astype(x.dtype)
        dx = grouped_matmul(
            d_gate.astype(x.dtype), tile_gid, w_gate, transpose_w=True,
            block_m=block_m, out_dtype=jnp.float32, interpret=interpret,
        ) + grouped_matmul(
            d_up.astype(x.dtype), tile_gid, w_up, transpose_w=True,
            block_m=block_m, out_dtype=jnp.float32, interpret=interpret,
        )
        d_wg = tgmm(x, d_gate.astype(x.dtype), tile_gid, e,
                    block_m=block_m, interpret=interpret)
    else:
        act_u, act_vjp = jax.vjp(act, uf)
        d_up = act_vjp(d_hidden)[0]
        hidden = act_u.astype(x.dtype)
        dx = grouped_matmul(
            d_up.astype(x.dtype), tile_gid, w_up, transpose_w=True,
            block_m=block_m, out_dtype=jnp.float32, interpret=interpret,
        )
        d_wg = None
    d_wu = tgmm(x, d_up.astype(x.dtype), tile_gid, e,
                block_m=block_m, interpret=interpret)
    d_wd = tgmm(hidden, dyc, tile_gid, e,
                block_m=block_m, interpret=interpret)
    d_bu = _segment_bias_grad(d_up, tile_gid, e, block_m)
    d_bd = _segment_bias_grad(dy.astype(jnp.float32), tile_gid, e, block_m)
    return dx, d_wu, d_bu, d_wd, d_bd, d_wg


def _gffn_bwd(act_name, gated, block_m, block_i, interpret, res, dy):
    import numpy as np

    x, tile_gid, w_up, b_up, w_down, b_down, w_gate, u, g = res
    dx, d_wu, d_bu, d_wd, d_bd, d_wg = ffn_backward_core(
        x, tile_gid, w_up, w_down, w_gate, u, g, dy,
        act_name=act_name, gated=gated, block_m=block_m,
        interpret=interpret,
    )
    ct_wg = d_wg.astype(w_gate.dtype) if gated else None
    ct_gid = np.zeros(tile_gid.shape, jax.dtypes.float0)
    return (dx.astype(x.dtype), ct_gid, d_wu.astype(w_up.dtype),
            d_bu.astype(b_up.dtype), d_wd.astype(w_down.dtype),
            d_bd.astype(b_down.dtype), ct_wg)


grouped_ffn_ad.defvjp(_gffn_fwd, _gffn_bwd)


def capacity_buffer_ffn_ad(xs, params, cfg: MoEConfig,
                           interpret: bool = False):
    """Differentiable capacity-buffer FFN: the grouped Pallas kernel with
    its fused Pallas backward (:func:`grouped_ffn_ad`) under the same
    reshaping as :func:`capacity_buffer_ffn_pallas` — autodiff flows
    through the reshapes natively."""
    e, c, h = xs.shape
    bm, cp, block_i = _capacity_tiling(c, cfg)
    if cp != c:
        xs = jnp.pad(xs, ((0, 0), (0, cp - c), (0, 0)))
    x = xs.reshape(e * cp, h)
    tiles_per_e = cp // bm
    tile_gid = jnp.arange(e * tiles_per_e, dtype=jnp.int32) // tiles_per_e
    out = grouped_ffn_ad(
        x, tile_gid, params["w_up"].astype(x.dtype), params["b_up"],
        params["w_down"].astype(x.dtype), params["b_down"],
        params["w_gate"].astype(x.dtype) if cfg.gated_ffn else None,
        cfg.hidden_act, cfg.gated_ffn, bm, block_i, interpret,
    )
    return out.reshape(e, cp, h)[:, :c, :]


def capacity_buffer_ffn_pallas(xs, params, cfg: MoEConfig, *,
                               interpret: bool = False):
    """Run the grouped kernel on an [E, C, H] capacity buffer.

    The capacity buffer is already expert-major, so tile group ids are just
    ``expert_of_tile = tile_index // (C / block_m)`` — no sort needed.  C is
    padded up to a block multiple; pad rows compute garbage that combine
    never reads.
    """
    e, c, h = xs.shape
    bm, cp, block_i = _capacity_tiling(c, cfg)
    if cp != c:
        xs = jnp.pad(xs, ((0, 0), (0, cp - c), (0, 0)))
    x = xs.reshape(e * cp, h)
    tiles_per_e = cp // bm
    tile_gid = (
        jnp.arange(e * tiles_per_e, dtype=jnp.int32) // tiles_per_e
    )
    out = grouped_ffn(
        x, tile_gid, params["w_up"].astype(x.dtype),
        params["b_up"], params["w_down"].astype(x.dtype), params["b_down"],
        params["w_gate"].astype(x.dtype) if cfg.gated_ffn else None,
        act_name=cfg.hidden_act, gated=cfg.gated_ffn, block_m=bm,
        block_i=block_i, interpret=interpret,
    )
    return out.reshape(e, cp, h)[:, :c, :]
