"""Real-hardware validation sweep: drives every Pallas kernel and layer
path on the actual TPU chip and checks against the dense-math oracle.

Run: python scripts/tpu_validate.py        (needs the TPU backend live)

This is the hardware half of the verification story: the CPU interpreter
cannot catch Mosaic layout/lowering errors, so any kernel change must pass
here before it counts (see .claude/skills/verify/SKILL.md).
"""

from __future__ import annotations

import signal
import sys
import time

import jax
import jax.numpy as jnp


def deadline(seconds: int):
    def handler(signum, frame):
        print(f"FAIL: deadline {seconds}s exceeded (backend hung?)",
              flush=True)
        sys.exit(2)
    signal.signal(signal.SIGALRM, handler)
    signal.alarm(seconds)


def main() -> int:
    deadline(840)  # each remote compile is ~20-90s; checks 7/8 added four
    import flashmoe_tpu as fm
    from flashmoe_tpu.models.reference import init_moe_params, reference_moe
    from flashmoe_tpu.ops.attention import attention_xla, flash_attention

    assert jax.default_backend() == "tpu", jax.default_backend()
    failures = []

    def check(name, err, tol):
        ok = err < tol
        print(f"{'ok  ' if ok else 'FAIL'} {name}: err={err:.3e} tol={tol}",
              flush=True)
        if not ok:
            failures.append(name)

    # 1. capacity path, f32 (exact-ish)
    cfg = fm.MoEConfig(num_experts=8, expert_top_k=2, hidden_size=512,
                       intermediate_size=1024, sequence_len=256,
                       capacity_factor=4.0, drop_tokens=True,
                       dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 512), jnp.float32)
    t0 = time.time()
    got = fm.moe_layer(params, x, cfg, use_pallas=True)
    want, _ = reference_moe(params, x, cfg)
    check("capacity_f32", float(jnp.max(jnp.abs(got.out - want))), 1e-4)
    print(f"  (compile+run {time.time()-t0:.1f}s)")

    # 1b. gather-fused capacity path (opt-in kernel: dispatch built inside
    # the kernel via per-row DMA; must pass here before it can be default)
    cfg_g = cfg.replace(gather_fused=True)
    got_g = fm.moe_layer(params, x, cfg_g, use_pallas=True)
    check("capacity_gather_f32", float(jnp.max(jnp.abs(got_g.out - want))),
          1e-4)

    # 2. dropless ragged path
    cfg2 = cfg.replace(drop_tokens=False)
    got2 = fm.moe_layer(params, x, cfg2, use_pallas=True)
    want2, _ = reference_moe(params, x, cfg2)
    check("dropless_ragged_f32", float(jnp.max(jnp.abs(got2.out - want2))),
          1e-4)

    # 2b. dropless gather-fused kernel (grouped_ffn_tokens via the ragged
    # plan's inverse map) — same promotion gate as 1b
    got2g = fm.moe_layer(params, x, cfg2.replace(gather_fused=True),
                         use_pallas=True)
    check("dropless_gather_f32", float(jnp.max(jnp.abs(got2g.out - want2))),
          1e-4)

    # 3. gated bf16 (Mixtral-style)
    cfg3 = fm.MoEConfig(num_experts=8, expert_top_k=2, hidden_size=512,
                        intermediate_size=1024, sequence_len=256,
                        gated_ffn=True, hidden_act="silu",
                        drop_tokens=False)
    p3 = init_moe_params(jax.random.PRNGKey(2), cfg3)
    x3 = jax.random.normal(jax.random.PRNGKey(3), (256, 512), jnp.bfloat16)
    g3 = fm.moe_layer(p3, x3, cfg3, use_pallas=True)
    w3, _ = reference_moe(p3, x3, cfg3)
    rel = float(jnp.max(jnp.abs(g3.out.astype(jnp.float32)
                                - w3.astype(jnp.float32)))
                / jnp.max(jnp.abs(w3.astype(jnp.float32))))
    check("gated_bf16_rel", rel, 0.05)

    # 4. flash attention kernel
    q = jax.random.normal(jax.random.PRNGKey(4), (1, 4, 512, 64),
                          jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(5), (1, 4, 512, 64),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(6), (1, 4, 512, 64),
                          jnp.float32)
    fa = flash_attention(q, k, v, causal=True)
    wa = attention_xla(q, k, v, causal=True)
    check("flash_attention", float(jnp.max(jnp.abs(fa - wa))), 1e-4)

    # 5. TRAINING grad through the fused dropless path — the PALLAS
    # backward (ragged_dispatch buffer -> grouped_ffn_ad with
    # grouped_matmul/tgmm custom VJPs), checked against XLA-path grads.
    # is_training=True keeps the explicit dispatch buffer + residual-saving
    # backward; the (opt-in) gather-fused inference VJP is covered in 5b.
    def loss(p, use_pallas, c):
        o = fm.moe_layer(p, x, c, use_pallas=use_pallas)
        return jnp.sum(o.out.astype(jnp.float32) ** 2) + o.aux_loss

    def relerr(ga, gb):
        return max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
            / max(float(jnp.max(jnp.abs(b.astype(jnp.float32)))), 1e-9)
            for a, b in zip(jax.tree_util.tree_leaves(ga),
                            jax.tree_util.tree_leaves(gb))
        )

    cfg2t = cfg2.replace(is_training=True)
    gp = jax.grad(lambda p: loss(p, True, cfg2t))(params)
    gx = jax.grad(lambda p: loss(p, False, cfg2t))(params)
    finite = all(bool(jnp.isfinite(l).all())
                 for l in jax.tree_util.tree_leaves(gp))
    check("fused_grad_finite", 0.0 if finite else 1.0, 0.5)
    check("pallas_bwd_vs_xla_grads_rel", relerr(gp, gx), 0.02)

    # 5b. grad through the gather-fused inference capacity path (the
    # re-gather VJP) vs the XLA path
    gcap = jax.grad(lambda p: loss(p, True, cfg_g))(params)
    gcapx = jax.grad(lambda p: loss(p, False, cfg_g))(params)
    check("gather_fused_regather_vjp_rel", relerr(gcap, gcapx), 0.02)

    # 6. backward kernels standalone (grouped_matmul / tgmm vs einsum)
    from flashmoe_tpu.ops.expert import grouped_matmul, tgmm
    e, t_rows, kd, nd, bm = 4, 8 * 128, 512, 512, 128
    gid = (jnp.arange(t_rows // bm, dtype=jnp.int32)
           % e).sort()
    row_e = jnp.repeat(gid, bm)
    xg = jax.random.normal(jax.random.PRNGKey(7), (t_rows, kd), jnp.float32)
    wg = jax.random.normal(jax.random.PRNGKey(8), (e, nd, kd), jnp.float32)
    got_t = grouped_matmul(xg, gid, wg, transpose_w=True, block_m=bm)
    want_t = jnp.einsum("tk,tnk->tn", xg, wg[row_e])
    check("grouped_matmul_T", float(jnp.max(jnp.abs(got_t - want_t))), 5e-3)
    dyg = jax.random.normal(jax.random.PRNGKey(9), (t_rows, nd), jnp.float32)
    got_w = tgmm(xg, dyg, gid, e, block_m=bm)
    oh = jax.nn.one_hot(row_e, e, dtype=jnp.float32)
    want_w = jnp.einsum("tk,tn,te->ekn", xg, dyg, oh)
    check("tgmm", float(jnp.max(jnp.abs(got_w - want_w))), 5e-3)

    # 7. the DYNAMIC-size transport: jax.lax.ragged_all_to_all must lower
    # and run on the real chip (the reference ships exactly routedTokens
    # rows per packet, types.cuh:299-334; every CPU test forces the dense
    # arm because the op has no CPU lowering — this is the only place the
    # ragged arm executes for real).  ep=1 mesh: proves compilation +
    # numerics of the full ragged layout path vs the dense arm.

    from flashmoe_tpu.parallel.mesh import make_mesh
    from flashmoe_tpu.parallel.ragged_ep import ragged_ep_moe_layer

    cfg_r = cfg2.replace(ep=1)
    mesh1 = make_mesh(cfg_r, dp=1, devices=jax.devices()[:1])
    t0 = time.time()
    got_r = ragged_ep_moe_layer(params, x, cfg_r, mesh1, exchange="ragged")
    got_d = ragged_ep_moe_layer(params, x, cfg_r, mesh1, exchange="dense")
    check("ragged_all_to_all_vs_dense",
          float(jnp.max(jnp.abs(got_r.out - got_d.out))), 1e-5)
    check("ragged_arm_vs_oracle",
          float(jnp.max(jnp.abs(got_r.out - want2))), 1e-4)
    print(f"  (ragged compile+run {time.time()-t0:.1f}s)")

    # 8. fused RDMA kernel on silicon (ep=1: transfer legs degenerate to
    # local copies but the whole Mosaic kernel — semaphores, DMA chains,
    # streamed weights — must lower), XLA combine then in-kernel combine
    # (the round-3 kernel that had only ever run under the interpreter)
    from flashmoe_tpu.parallel.fused import fused_ep_moe_layer

    got_f = fused_ep_moe_layer(params, x, cfg_r, mesh1)
    check("fused_kernel_xla_combine",
          float(jnp.max(jnp.abs(got_f.out - want2))), 1e-4)
    # the in-kernel sorted-return combine is ep>1-only since round 5
    # (the gate falls back to the XLA combine at one rank), so its
    # Mosaic lowering cannot be validated on one chip — re-running here
    # would just compile the identical kernel twice
    print("  fused_kernel_in_kernel_combine: SKIPPED (ep>1-only; "
          "needs a multi-chip window)", flush=True)

    # 9. two-pass expert-tiled gate (large E): Mosaic-lowering check of
    # the multi-tile online-softmax/top-k kernel vs the XLA router
    from flashmoe_tpu.ops.gate import router_pallas_tiled, router_xla

    cfg_e = fm.MoEConfig(num_experts=1280, expert_top_k=2,
                         hidden_size=512, intermediate_size=1024,
                         dtype=jnp.float32, param_dtype=jnp.float32)
    w_big = jax.random.normal(jax.random.PRNGKey(10), (512, 1280),
                              jnp.float32) * 0.1
    rt = router_pallas_tiled(x, w_big, cfg_e)  # inference: pass 1 only
    rx = router_xla(x, w_big, cfg_e)
    idx_mism = float(jnp.sum(rt.expert_idx != rx.expert_idx))
    check("tiled_gate_idx_mismatch", idx_mism, 0.5)
    check("tiled_gate_weights",
          float(jnp.max(jnp.abs(rt.combine_weights
                                - rx.combine_weights))), 1e-4)
    # training mode lowers the logits spill + stats pass as well
    cfg_et = cfg_e.replace(is_training=True)
    rtt = router_pallas_tiled(x, w_big, cfg_et)
    rxt = router_xla(x, w_big, cfg_et)
    check("tiled_gate_train_aux",
          abs(float(rtt.aux_loss) - float(rxt.aux_loss)), 1e-3)

    print("ALL OK" if not failures else f"FAILURES: {failures}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
