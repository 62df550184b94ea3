"""Driver of the training cells: ``runtime.trainer.make_train_step`` as
``runtime/train_cli`` builds it, on batches the harness makes from the seed.

Set-up builds ONE object, the jitted step with its state (weights from the
seed by ``lib/reference.make_params``, the program's optimizer), drives it
through its first steps on the window's own call and feed, reads what the
check compares (each step's loss; the first gradient leaf by leaf, worked
out from Adam's first moment after one step and read through a short
sketch that keeps its direction; the norm of each leaf's change after
those steps), and hands the same object to the window.

The window calls the step back to back, each on a new batch, one step
dispatched ahead of the one being waited for; the rate is the tokens of the
steps that completed over the time to the last completion.

The check, after the window and after the program's state is freed: the
plain reference follows the same first steps in float32 from the same
weights and batches.
"""

from __future__ import annotations

import gc
import math

import numpy as np



def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up from 0 to ``lr`` over ``warmup_steps``, then a
    cosine to 0 at ``total_steps`` (what the trainer's schedule gives)."""
    w, total = opt["warmup_steps"], max(opt["total_steps"],
                                        opt["warmup_steps"] + 1)
    if step < w:
        return opt["lr"] * step / w
    frac = min(1.0, (step - w) / (total - w))
    return opt["lr"] * 0.5 * (1.0 + math.cos(math.pi * frac))


def _adam_mu(opt_state):
    """Adam's first moment inside an optax chain's state."""
    found = []

    def walk(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for child in node:
                walk(child)

    walk(opt_state)
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0]


def build(run):
    import jax
    import jax.numpy as jnp

    from flashmoe_tpu.runtime import bootstrap
    from flashmoe_tpu.runtime.trainer import (
        TrainState, make_optimizer, make_train_step, state_shardings,
    )

    ref = run.lib("reference")
    spec, config = run.cell.spec, run.cell.config
    dims = ref.model_dims(config)
    rt = bootstrap.initialize(run.program_config(is_training=True))
    cfg, mesh = rt.cfg, rt.mesh
    batch, seq = spec["traffic"]["batch"], spec["traffic"]["sequence_len"]
    opt = spec["optimizer"]
    optimizer = make_optimizer(cfg, lr=opt["lr"],
                               total_steps=opt["total_steps"])
    params = ref.make_params(run.seed, dims)
    state = TrainState(params, optimizer.init(params),
                       jnp.zeros((), jnp.int32), None)
    state = jax.device_put(state, state_shardings(state, cfg, mesh))
    del params
    step = make_train_step(cfg, mesh, optimizer)
    key = ref.seed_key(run.seed, stream=1)

    @jax.jit
    def feed(i):
        return {"tokens": jax.random.randint(
            jax.random.fold_in(key, i), (batch, seq + 1), 0, dims["vocab"])}

    run.say(phase="state", s=round(run.clock() - run.t_start, 3))
    n_check = int(spec["check"]["steps"])
    losses, first = [], None
    b1 = opt["b1"]
    norms = jax.jit(lambda tree, scale: ref.leaf_norms(tree) * scale)
    for i in range(n_check):
        state, m = step(state, feed(i))
        losses.append(float(m["loss"]))
        if first is None:
            # the first gradient as the optimizer got it: Adam's first
            # moment after one step is (1 - b1) times it
            mu = _adam_mu(state.opt_state)
            first = np.asarray(norms(mu, 1.0 / (1.0 - b1)))
            sketches = ref.tree_sketches(mu, 1.0 / (1.0 - b1))
            del mu
    start = ref.make_params(run.seed, dims)
    delta = np.asarray(jax.jit(lambda a, b: ref.leaf_norms(
        jax.tree_util.tree_map(lambda x, y: x - y, a, b)))(
        state.params, start))
    del start
    run.say(phase="first_steps", s=round(run.clock() - run.t_start, 3),
            losses=losses)
    return {"state": state, "step": step, "feed": feed, "dims": dims,
            "next": n_check, "tokens_per_step": batch * seq,
            "program": {"losses": losses, "first_grad_norms": first,
                        "first_grad_sketches": sketches,
                        "delta_norms": delta}}


def measure(state, run):
    step, feed, first = state["step"], state["feed"], state["next"]
    box = {"state": state["state"]}

    def call(i):
        box["state"], m = step(box["state"], feed(first + i))
        return m["loss"]

    w0, t_done, step_s, loss = run.back_to_back(call, "bench.train_step")
    state["state"] = box["state"]
    done = len(step_s)
    tokens = done * state["tokens_per_step"]
    return {"end_to_end": {"step_tokens_per_s": tokens / (t_done - w0)},
            "window_start": w0, "attempted": done, "failed": 0,
            "notes": {"steps": done, "window_s": t_done - w0,
                      "step_s_median": float(np.median(step_s)),
                      "last_loss": float(loss)},
            "harness": {"step_ms": [1e3 * s for s in step_s],
                        "tokens_per_step": state["tokens_per_step"]},
            "records": []}


def check(state, run):
    ref = run.lib("reference")
    spec = run.cell.spec
    state.pop("state")
    state.pop("step")
    gc.collect()
    t0 = run.clock()
    dims, feed = state["dims"], state["feed"]
    n = int(spec["check"]["steps"])
    opt = dict(spec["optimizer"], lr=lambda i: lr_at(spec["optimizer"], i))
    batches = [feed(i)["tokens"] for i in range(n)]
    prog = state["program"]

    def gaps(prog, want):
        loss_gap = max(abs(a - b) / abs(b)
                       for a, b in zip(prog["losses"], want["losses"]))
        sk = ref.sketch_gaps(prog["first_grad_sketches"],
                             want["first_grad_sketches"])
        return {"loss_gap": loss_gap, "first_grad_gap": sk["worst"],
                "delta_gap": ref.worst_leaf_gap(
                    prog["delta_norms"], want["delta_norms"])}, sk

    start = lambda: ref.make_params(run.seed, dims)
    want = ref.reference_train_steps(start, dims, batches, opt)
    got, sk = gaps(prog, want)
    limits = spec["check"]["limits"]
    compared = [{"name": k, "value": v, "limit": limits.get(k),
                 "ok": limits.get(k) is None or v <= limits[k]}
                for k, v in got.items()]
    notes = {"program_losses": prog["losses"],
             "reference_losses": want["losses"],
             "first_grad_gap_median_leaf": sk["median"],
             "first_grad_gap_worst_leaf_index": sk["worst_index"],
             "first_grad_gap_per_leaf": [round(g, 4) for g in sk["per_leaf"]],
             "first_grad_norm_gap": ref.worst_leaf_gap(
                 prog["first_grad_norms"], want["first_grad_norms"]),
             "reference_s": round(run.clock() - t0, 3)}
    if run.control:
        gc.collect()
        low = ref.reference_train_steps(
            start, dims, batches, opt, quant=spec["check"]["control"])
        # the control stands in the program's place
        low_gaps, low_sk = gaps(low, want)
        notes["control"] = dict(
            low_gaps, precision=spec["check"]["control"],
            first_grad_gap_median_leaf=low_sk["median"],
            first_grad_gap_per_leaf=[round(g, 4)
                                     for g in low_sk["per_leaf"]])
    return {"correct": all(c["ok"] for c in compared),
            "compared": compared, "notes": notes}


def close(state):
    state.clear()
    gc.collect()
