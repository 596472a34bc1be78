"""Experiment: per-client conv formulations for the flagship ResNet round.

The flagship round's dominant cost is the per-client conv fwd+bwd: under
vmap every client carries its own weight set, so XLA lowers each conv to a
grouped conv / small batched GEMM (docs/PERFORMANCE.md "Remaining ceiling
analysis": 45-70 GB/s effective on those shapes). This script measures, per
ResNet-18 stage shape, a single conv layer's fwd+bwd under three
formulations:

  A. vmap(lax.conv_general_dilated) over clients — what flax+vmap produce
     today (the baseline the round program runs).
  B. explicit im2col: conv_general_dilated_patches once per client batch,
     then one batched GEMM ('cmk,cko->cmo') — fwd AND both backward
     contractions become MXU-aligned batched GEMMs.
  C. B, with the patches precomputed OUTSIDE the grad (activation-style
     reuse; bounds what fusing patch extraction would buy).

Timing: chain N dispatches, fetch ONE scalar at the end.

Usage: python scripts/exp_client_conv.py [n_chain] [chunk] [batch]
"""

from __future__ import annotations

import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

STAGES = [
    ("stage1", 32, 64, 64),
    ("stage2", 16, 128, 128),
    ("stage3", 8, 256, 256),
    ("stage4", 4, 512, 512),
]


def timeit(fn, args, n):
    out = fn(*args)
    jax.device_get(out)  # compile + settle
    t0 = time.perf_counter()
    acc = out
    for _ in range(n):
        acc = acc + fn(*args)
    jax.device_get(acc)
    return (time.perf_counter() - t0) / n


def main():
    n_chain = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    chunk = int(sys.argv[2]) if len(sys.argv) > 2 else 40
    batch = int(sys.argv[3]) if len(sys.argv) > 3 else 25

    key = jax.random.key(0)
    for name, hw, cin, cout in STAGES:
        kx, kw, kg = jax.random.split(jax.random.fold_in(key, hw), 3)
        x = jax.random.normal(kx, (chunk, batch, hw, hw, cin), jnp.bfloat16)
        w = jax.random.normal(kw, (chunk, 3, 3, cin, cout), jnp.bfloat16)
        # Fixed cotangent so bwd cost is measured without a real loss.
        g = jax.random.normal(kg, (chunk, batch, hw, hw, cout), jnp.bfloat16)

        # --- A: vmapped conv ------------------------------------------------
        def conv_one(xc, wc):
            return jax.lax.conv_general_dilated(
                xc, wc, window_strides=(1, 1), padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )

        def loss_a(w_, x_):
            y = jax.vmap(conv_one)(x_, w_)
            return jnp.sum((y * g).astype(jnp.float32))

        f_a = jax.jit(jax.grad(loss_a, argnums=(0, 1)))

        def run_a(w_, x_):
            gw, gx = f_a(w_, x_)
            return jnp.sum(gw.astype(jnp.float32)) + jnp.sum(
                gx.astype(jnp.float32)
            )

        t_a = timeit(jax.jit(run_a), (w, x), n_chain)

        # --- B: im2col + batched GEMM --------------------------------------
        def patches_one(xc):
            # [B, H, W, 9*cin] patch tensor for one client's batch.
            return jax.lax.conv_general_dilated_patches(
                xc, (3, 3), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )

        def loss_b(w_, x_):
            p = jax.vmap(patches_one)(x_)  # [C, B, H, W, 9cin]
            p = p.reshape(chunk, batch * hw * hw, 9 * cin)
            wmat = w_.transpose(0, 3, 1, 2, 4).reshape(chunk, 9 * cin, cout)
            y = jnp.einsum(
                "cmk,cko->cmo", p, wmat,
                preferred_element_type=jnp.float32,
            ).astype(jnp.bfloat16)
            gm = g.reshape(chunk, batch * hw * hw, cout)
            return jnp.sum((y * gm).astype(jnp.float32))

        f_b = jax.jit(jax.grad(loss_b, argnums=(0, 1)))

        def run_b(w_, x_):
            gw, gx = f_b(w_, x_)
            return jnp.sum(gw.astype(jnp.float32)) + jnp.sum(
                gx.astype(jnp.float32)
            )

        t_b = timeit(jax.jit(run_b), (w, x), n_chain)

        # --- C: weight-grad GEMM only, patches given ------------------------
        p_pre = jax.jit(
            lambda x_: jax.vmap(patches_one)(x_).reshape(
                chunk, batch * hw * hw, 9 * cin
            )
        )(x)
        gm = g.reshape(chunk, batch * hw * hw, cout)

        def wgrad_only(p_, g_):
            gw = jnp.einsum(
                "cmk,cmo->cko", p_, g_,
                preferred_element_type=jnp.float32,
            )
            return jnp.sum(gw)

        t_c = timeit(jax.jit(wgrad_only), (p_pre, gm), n_chain)

        # Traffic estimate for A's fwd+bwd (bf16): x and g read ~2-3x, w
        # negligible.
        mb = (x.size + g.size) * 2 / 2**20
        print(
            f"{name}: vmap-conv {t_a*1e3:7.2f} ms | im2col-gemm "
            f"{t_b*1e3:7.2f} ms | wgrad-gemm-only {t_c*1e3:7.2f} ms "
            f"| act+cot {mb:.0f} MB"
        )


if __name__ == "__main__":
    main()
