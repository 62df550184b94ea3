"""Device-mesh construction and sharding helpers.

The reference bootstraps its "mesh" dynamically: NVSHMEM init, pairwise
alpha-beta topology probing (``csrc/include/flashmoe/topo.cuh``), and the
Decider's DP x EP group formation (``os/decider/decider.cuh``).  On TPU the
interconnect geometry is a known torus exposed through
``jax.sharding.Mesh``; this module builds the standard
(dp, pp, ep, tp, sp) meshes and the canonical PartitionSpecs for MoE
parameters and activations.  Topology-aware *placement* (which expert on
which chip) remains a real decision for heterogeneous/multi-slice jobs and
lives in :mod:`flashmoe_tpu.parallel.decider`.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flashmoe_tpu.config import FFN_PARTS, MoEConfig

# Canonical mesh axis order: slowest-varying (DCN-adjacent) first.  dp and pp
# tolerate slow links; ep's all-to-all and tp's collectives want ICI
# neighbours, so they take the fastest-varying (innermost torus) axes.
AXES = ("dp", "pp", "ep", "tp", "sp")


def make_mesh(cfg: MoEConfig | None = None, *, dp=None, pp=None, ep=None,
              tp=None, sp=None, devices: Sequence | None = None) -> Mesh:
    """Build a Mesh over the available devices.

    Sizes default to the config's parallelism fields; any remaining factor
    of the device count folds into dp.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    sizes = {
        "dp": dp if dp is not None else (cfg.dp if cfg else 1),
        "pp": pp if pp is not None else (cfg.pp if cfg else 1),
        "ep": ep if ep is not None else (cfg.ep if cfg else 1),
        "tp": tp if tp is not None else (cfg.tp if cfg else 1),
        "sp": sp if sp is not None else (cfg.sp if cfg else 1),
    }
    used = math.prod(sizes.values())
    if dp is None and n % used == 0:
        # dp not pinned by the caller: fold the leftover device factor in
        sizes["dp"] *= n // used
    elif n != used:
        raise ValueError(
            f"{n} devices don't match mesh {sizes}; pass devices= to "
            f"restrict, or leave dp unset to absorb the remainder"
        )
    shape = tuple(sizes[a] for a in AXES)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, AXES)


def moe_param_specs(cfg: MoEConfig) -> dict:
    """PartitionSpecs for MoE-layer parameters.

    Experts shard over ep; each expert's weight matrices shard over tp on
    the intermediate dimension (column-parallel up, row-parallel down —
    Megatron-style, so only one psum per FFN).
    """
    ep_ax = "ep" if cfg.ep > 1 else None
    tp_ax = "tp" if cfg.tp > 1 else None
    specs = {
        "gate_w": P(None, None),
        "w_up": P(ep_ax, None, tp_ax),
        "b_up": P(ep_ax, tp_ax),
        "w_down": P(ep_ax, tp_ax, None),
        "b_down": P(ep_ax, None),
    }
    if cfg.gated_ffn:
        specs["w_gate"] = P(ep_ax, None, tp_ax)
    if cfg.num_shared_experts:
        specs["shared_w_up"] = P(None, tp_ax)
        specs["shared_w_down"] = P(tp_ax, None)
        if cfg.gated_ffn:
            specs["shared_w_gate"] = P(None, tp_ax)
    return specs


def token_spec() -> P:
    """Activations: tokens shard over (dp, ep, sp) jointly, hidden replicated.

    Folding ep into the token axis is the GShard layout: each EP rank owns a
    distinct token shard, and the MoE all-to-all exchanges tokens *within*
    the ep axis.
    """
    return P(("dp", "ep", "sp"), None)


def shard_params(params, cfg: MoEConfig, mesh: Mesh):
    specs = moe_param_specs(cfg)
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }


def transformer_param_specs(cfg: MoEConfig) -> dict:
    """PartitionSpecs for the full transformer parameter tree
    (:func:`flashmoe_tpu.models.transformer.init_params` layout).

    Attention projections are Megatron-style tp-split (columns for qkv,
    rows for the output projection); the LM head is column-parallel over
    the vocab; MoE experts shard over ep.
    """
    tp_ax = "tp" if cfg.tp > 1 else None
    mixer_specs = {
        "attn_norm": P(None),
        "wq": P(None, tp_ax),
        "wk": P(None, tp_ax),
        "wv": P(None, tp_ax),
        "wo": P(tp_ax, None),
    }
    ffn_specs = {
        "moe": moe_param_specs(cfg),
        "dense": moe_param_specs(cfg.dense_config.replace(ep=1)),
    }
    # a layer holds the parts ``cfg.layers`` names (a mixture branch
    # read beside a dense part under ``branch``)
    layers = [
        {**(mixer_specs if mixer is not None else {}),
         **({"ffn_norm": P(None), "moe": ffn_specs[FFN_PARTS[ffn][0]]}
            if ffn is not None else {}),
         **({"branch": ffn_specs["moe"]}
            if FFN_PARTS[ffn][1] == "moe" else {})}
        for mixer, ffn in cfg.layers
    ]
    return {
        "embed": P(None, None),
        "final_norm": P(None),
        "lm_head": P(None, tp_ax),
        "layers": layers,
    }
