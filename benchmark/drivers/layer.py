"""Driver of the layer cells: one mixture-of-experts layer forward over an
expert-parallel mesh, through the route the model block takes
(``models/transformer._ffn`` with the mesh: the program resolves the
transport and the kernels, the benchmark picks nothing).

Set-up: the layer's weights on the devices from the seed, experts sharded
over ``ep``; the tokens, ``tokens_per_chip`` on each chip; one call to
compile.  The window calls the jitted layer back to back, one call
dispatched ahead of the one waited for.  The check, after the window: the
rows of the window's LAST call against the plain layer, shard by shard
(the capacity rule counts within a rank), ties and capacity-edge rows set
aside and counted.
"""

from __future__ import annotations

import gc

import numpy as np



def build(run):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flashmoe_tpu.models import transformer
    from flashmoe_tpu.parallel.mesh import make_mesh

    ref = run.lib("reference")
    spec, config = run.cell.spec, run.cell.config
    dims = dict(ref.model_dims(config),
                param_dtype=spec["traffic"]["param_dtype"])
    ep = run.cell.chips
    per_chip = int(spec["traffic"]["tokens_per_chip"])
    total = per_chip * ep
    cfg = run.program_config(ep=ep, sequence_len=total)
    li = cfg.moe_layer_indices[-1]
    mesh = make_mesh(cfg, dp=1, devices=jax.devices()[:ep])
    dt = jnp.dtype(dims["param_dtype"])
    shard = lambda name: NamedSharding(
        mesh, P() if name == "gate_w" else P("ep"))
    names = ["gate_w", "w_up", "b_up", "w_down", "b_down"] + (
        ["w_gate"] if dims["gated"] else [])
    make = jax.jit(
        lambda key: ref._ffn_params(key, dims, dims["experts"], 0, dt),
        out_shardings={k: shard(k) for k in names})
    layer = {"moe": make(ref.seed_key(run.seed))}
    x_sh = NamedSharding(mesh, P(None, ("dp", "ep"), None))
    x = jax.jit(lambda key: jax.random.normal(
        key, (1, total, dims["hidden"]), jnp.float32).astype(
        jnp.dtype(dims["dtype"])), out_shardings=x_sh)(
        ref.seed_key(run.seed, stream=2))
    fn = jax.jit(lambda layer, x: transformer._ffn(
        layer, x, cfg, li, mesh, None)[0])
    out = jax.block_until_ready(fn(layer, x))
    run.say(phase="compiled", s=round(run.clock() - run.t_start, 3),
            moe_backend=cfg.moe_backend, out_shape=list(out.shape))
    del out
    return {"fn": fn, "layer": layer, "x": x, "dims": dims, "ep": ep,
            "per_chip": per_chip, "total": total}


def measure(state, run):
    fn, layer, x = state["fn"], state["layer"], state["x"]
    w0, t_done, gaps, out = run.back_to_back(lambda i: fn(layer, x),
                                             "bench.layer_call")
    state["out"] = out
    done = len(gaps)
    return {"end_to_end": {
                "step_tokens_per_s": done * state["total"] / (t_done - w0)},
            "window_start": w0, "attempted": done, "failed": 0,
            "notes": {"calls": done, "window_s": t_done - w0,
                      "call_ms_mean": 1e3 * (t_done - w0) / max(done, 1)},
            "harness": {"tokens_per_call": state["total"]}, "records": []}


def check(state, run):
    import jax

    ref = run.lib("reference")
    spec = run.cell.spec["check"]
    per, ep = state["per_chip"], state["ep"]
    t0 = run.clock()
    dev = jax.devices()[0]
    got = np.asarray(state.pop("out"))[0]
    x = np.asarray(state["x"])[0]
    p = {k: jax.device_put(np.asarray(v), dev)
         for k, v in state["layer"]["moe"].items()}
    state.pop("layer")
    state.pop("fn")
    gc.collect()
    xs = [jax.device_put(x[r * per:(r + 1) * per], dev) for r in range(ep)]
    gots = [got[r * per:(r + 1) * per] for r in range(ep)]
    res = ref.layer_row_errors(p, state["dims"], xs, gots)
    limits = spec["limits"]
    compared = [{"name": k, "value": res[k], "limit": limits.get(k),
                 "ok": limits.get(k) is None or res[k] <= limits[k]}
                for k in ("worst_row_error", "ambiguous_share")]
    notes = {"rows": res["rows"], "mean_row_error": res["mean_row_error"],
             "reference_s": round(run.clock() - t0, 3)}
    if run.control:
        low = ref.layer_row_errors(p, state["dims"], xs, gots,
                                   control=spec["control"])
        notes["control"] = {"precision": spec["control"],
                            "worst_row_error": low["worst_row_error"],
                            "mean_row_error": low["mean_row_error"]}
    return {"correct": all(c["ok"] for c in compared),
            "compared": compared, "notes": notes}


def close(state):
    state.clear()
    gc.collect()
