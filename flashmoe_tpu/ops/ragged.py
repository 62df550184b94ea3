"""Dropless MoE: ragged token grouping + block-sparse grouped FFN.

The reference's dropless mode sets expert capacity to the full token count
(``EC = S`` when ``drop_tokens=0``, ``types.cuh:497-499``) and lets its
dynamic tile scheduler process only the ``routedTokens`` actually present
(``SignalPayload.routedTokens``, dispatch clamp at ``packet.cuh:99-206``) —
dense capacity buffers would waste memory and FLOPs, so tile-level dynamism
is the whole point of its in-kernel OS.

The TPU equivalent of that dynamism is *ragged grouping under static
shapes*: sort the (token, k) assignments by expert, pad each expert's
segment up to the row-tile size, and hand the result to the grouped Pallas
FFN kernel whose scalar-prefetched ``tile_gid`` already supports
data-dependent group ids (:func:`flashmoe_tpu.ops.expert.grouped_ffn`).
Pad rows cost at most ``E * (block_m - 1)`` extra rows — tile-level waste,
exactly like the reference's partially-filled final tile per expert — and
no token is ever dropped.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from flashmoe_tpu.config import MoEConfig


class RaggedPlan(NamedTuple):
    """Ragged grouping of (token, k) assignments by expert.

    position:    [S, K] destination row of each assignment in the sorted,
                 segment-padded buffer.
    tile_gid:    [T_pad // block_m] expert id per row tile (dynamic values,
                 static shape).
    counts:      [E] assignments per expert.
    num_rows:    [] total populated+padded rows (<= T_pad, dynamic).
    src_tok:     [T_pad] source token id per buffer row (pad rows point at
                 token 0; they are never read back by combine).
    present:     [T_pad] bool, True for populated rows.
    """

    position: jax.Array
    tile_gid: jax.Array
    counts: jax.Array
    num_rows: jax.Array
    src_tok: jax.Array
    present: jax.Array


def padded_total_rows(cfg: MoEConfig, s: int, block_m: int) -> int:
    """Static upper bound on the grouped buffer: every assignment plus up
    to block_m-1 pad rows per expert."""
    total = s * cfg.expert_top_k + cfg.num_experts * block_m
    return ((total + block_m - 1) // block_m) * block_m


def make_ragged_plan(expert_idx, cfg: MoEConfig, block_m: int) -> RaggedPlan:
    """Compute the expert-sorted, tile-padded layout. Pure integer work.

    One stable argsort powers everything: assignment positions (inverse
    permutation minus segment starts), the per-row source-token index
    plane (the inverse map, derived by locating each buffer row in its
    expert's padded segment — all gathers, no H-wide scatter), and the
    per-tile group ids."""
    s, k = expert_idx.shape
    e = cfg.num_experts
    flat_e = expert_idx.T.reshape(-1)  # k-major (matches capacity priority)
    n = flat_e.shape[0]

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    unpadded_starts = jnp.searchsorted(
        sorted_e, jnp.arange(e, dtype=flat_e.dtype), side="left"
    ).astype(jnp.int32)
    counts = jnp.concatenate(
        [unpadded_starts[1:], jnp.full((1,), n, jnp.int32)]
    ) - unpadded_starts
    padded = ((counts + block_m - 1) // block_m) * block_m
    seg_starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)[:-1]]
    )  # [E] padded segment starts

    sorted_pos = jnp.argsort(order).astype(jnp.int32)  # inverse permutation
    rank = sorted_pos - unpadded_starts[flat_e]
    position = (seg_starts[flat_e] + rank).reshape(k, s).T  # [S, K]

    t_pad = padded_total_rows(cfg, s, block_m)
    n_tiles = t_pad // block_m
    tile_starts = jnp.arange(n_tiles, dtype=jnp.int32) * block_m
    seg_ends = seg_starts + padded
    # tile t belongs to expert e iff seg_starts[e] <= t*block_m < seg_ends[e];
    # tail tiles past all segments clamp to the last expert (computed, unread)
    tile_gid = jnp.clip(
        jnp.searchsorted(seg_ends, tile_starts, side="right"), 0, e - 1
    ).astype(jnp.int32)

    # inverse map: which (token, k) assignment feeds each buffer row
    rows = jnp.arange(t_pad, dtype=jnp.int32)
    e_row = jnp.clip(
        jnp.searchsorted(seg_ends, rows, side="right"), 0, e - 1
    ).astype(jnp.int32)
    row_rank = rows - seg_starts[e_row]
    present = row_rank < counts[e_row]
    sorted_idx = unpadded_starts[e_row] + jnp.minimum(
        row_rank, jnp.maximum(counts[e_row] - 1, 0)
    )
    src_tok = jnp.where(
        present, (order[jnp.clip(sorted_idx, 0, n - 1)] % s).astype(
            jnp.int32), 0
    )
    return RaggedPlan(position, tile_gid, counts, seg_ends[-1], src_tok,
                      present)


def sorted_rows_tiles(n_rows: int, groups: int, block_m: int) -> int:
    """Row tiles that hold ``n_rows`` sorted rows of at most ``groups``
    groups, each group padded to whole tiles, whatever the rows' split:
    every row and up to ``block_m - 1`` pad rows a group that has rows."""
    return max(1, (n_rows + min(groups, n_rows) * (block_m - 1)) // block_m)


def sorted_rows_plan(order, sizes, block_m: int, n_tiles: int,
                     first_tile=None):
    """The tile-padded layout of rows ALREADY sorted by group, from that
    sort and the groups' sizes alone: no second sort, and the only
    searches are ``n_tiles`` tile starts against ``len(sizes)`` group ends.
    With ``first_tile`` (a scalar) the plan is a WINDOW of the layout:
    tiles ``first_tile`` .. + ``n_tiles`` - 1 of it, ``live`` the
    populated tiles among them.

    order: [N] the stable argsort of the rows' group ids (rows of no group
    sort last: ``sum(sizes)`` may be under N); sizes: [G] rows a group.
    Returns (``src`` [n_tiles * block_m] the row of ``order``'s domain
    that feeds each padded row, 0 for a pad row; ``tile_gid`` [n_tiles],
    the tiles past the last live one repeating its group; ``live`` [1]
    the populated tiles; ``starts`` / ``pad_starts`` [G] where a group
    begins among the sorted and among the padded rows: sorted row i of
    group g lies at ``pad_starts[g] + i - starts[g]``)."""
    sizes = sizes.astype(jnp.int32)
    tiles = (sizes + block_m - 1) // block_m
    tile_ends = jnp.cumsum(tiles)
    starts = jnp.cumsum(sizes) - sizes
    pad_starts = (tile_ends - tiles) * block_m
    live = tile_ends[-1:]
    def tile():
        at = jnp.arange(n_tiles, dtype=jnp.int32)
        return at if first_tile is None else at + first_tile

    t = jnp.minimum(tile(), jnp.maximum(live - 1, 0))
    tile_gid = jnp.minimum(
        jnp.sum(tile_ends[None, :] <= t[:, None], axis=1, dtype=jnp.int32),
        sizes.shape[0] - 1)
    rank = (tile() * block_m
            - pad_starts[tile_gid])[:, None] + jnp.arange(
                block_m, dtype=jnp.int32)[None, :]
    if first_tile is not None:
        live = jnp.clip(live - first_tile, 0, n_tiles)
    populated = rank < sizes[tile_gid][:, None]
    at = jnp.clip(starts[tile_gid][:, None] + rank, 0, order.shape[0] - 1)
    src = jnp.where(populated, order[at].astype(jnp.int32), 0)
    return src.reshape(-1), tile_gid, live, starts, pad_starts


def ragged_dispatch(x, plan: RaggedPlan, cfg: MoEConfig, block_m: int):
    """Gather tokens into the expert-sorted padded buffer: [T_pad, H].

    Row-gather via the plan's inverse map (``src_tok``).  Note: under
    differentiation the gather's VJP is an H-wide scatter-add back to
    token order, so the dropless TRAINING step still pays one scatter in
    the backward (a wash vs the old scatter-forward formulation); the
    real win is inference, which skips this buffer entirely via the
    gather-fused kernel."""
    buf = jnp.where(plan.present[:, None], x[plan.src_tok], 0)
    return buf.astype(x.dtype)


def ragged_combine(y, plan: RaggedPlan, combine_weights, cfg: MoEConfig):
    """Gather each token's K expert outputs and take the weighted sum."""
    s, k = plan.position.shape
    gathered = y[plan.position.reshape(-1)].reshape(s, k, -1)
    w = combine_weights.astype(jnp.float32)
    return jnp.einsum(
        "skh,sk->sh", gathered.astype(jnp.float32), w,
        preferred_element_type=jnp.float32,
    )
