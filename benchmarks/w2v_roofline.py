"""W2V SGNS roofline: where does 5.6M pairs/s sit vs the gather/scatter
op ceiling? (VERDICT r3 weak #3 — apply the ALS roofline methodology to
the Word2Vec step, closing SURVEY.md §2.5's "Pallas negative-sampling
kernel" mandate with either a kernel or a measured refutation.)

Per SGNS step at B pairs, N negatives, K dims the step MUST touch
B·(N+2) embedding rows twice — gather (read) and scatter-add (write);
that row traffic is irreducible for the algorithm (every sampled row's
value feeds the loss; every sampled row receives a gradient). So the
question "can a Pallas kernel beat the XLA step?" reduces to "does the
XLA step already run at the hardware's row-op rate?" — measured here by
timing stripped-down variants of the same scan:

  full        the real step (gathers + math + scatters)
  gather-only same gathers + math, gradients summed instead of scattered
  scatter-only constant rows scattered to the same indices, no gathers
  pure-gather a bare table[idx] sum, the op-rate ceiling probe
  sorted-gather same with per-step sorted indices (ALS measured ~20×
              from monotonic row ids in its fused gather+Gram pipeline
              — does a bare gather see any of that here?)

Run on the TPU: python benchmarks/w2v_roofline.py [--quick]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--vocab", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16_384)
    ap.add_argument("--negatives", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    V, K, B, N = args.vocab, args.dim, args.batch, args.negatives
    steps = 30 if args.quick else 100
    reps = 2 if args.quick else 3
    rows_per_pair = N + 2

    key = jax.random.key(0)
    emb_in = jax.random.normal(key, (V, K), jnp.float32) * 0.01
    emb_out = jax.random.normal(key, (V, K), jnp.float32) * 0.01
    n_pairs = 1_000_000
    pairs = jax.random.randint(key, (n_pairs, 2), 0, V, jnp.int32)

    def sgns_math(c, pos, ngs, inv_b):
        pos_score = jnp.sum(c * pos, axis=-1)
        neg_score = jnp.einsum("bk,bnk->bn", c, ngs)
        g_pos = (jax.nn.sigmoid(pos_score) - 1.0) * inv_b
        g_neg = jax.nn.sigmoid(neg_score) * inv_b
        g_c = g_pos[:, None] * pos + jnp.einsum("bn,bnk->bk", g_neg, ngs)
        g_ctx = g_pos[:, None] * c
        g_ngs = g_neg[..., None] * c[:, None, :]
        return g_c, g_ctx, g_ngs

    def variant_full(carry, key):
        emb_in, emb_out = carry
        k1, k2 = jax.random.split(key)
        idx = jax.random.randint(k1, (B,), 0, n_pairs)
        batch = pairs[idx]
        center, ctx = batch[:, 0], batch[:, 1]
        neg = jax.random.randint(k2, (B, N), 0, V)
        g_c, g_ctx, g_ngs = sgns_math(emb_in[center], emb_out[ctx],
                                      emb_out[neg], 1.0 / B)
        emb_in = emb_in.at[center].add(-0.05 * g_c)
        emb_out = emb_out.at[ctx].add(-0.05 * g_ctx)
        emb_out = emb_out.at[neg.reshape(-1)].add(
            -0.05 * g_ngs.reshape(-1, K))
        return (emb_in, emb_out), 0.0

    def variant_gather_only(carry, key):
        emb_in, emb_out = carry
        k1, k2 = jax.random.split(key)
        idx = jax.random.randint(k1, (B,), 0, n_pairs)
        batch = pairs[idx]
        center, ctx = batch[:, 0], batch[:, 1]
        neg = jax.random.randint(k2, (B, N), 0, V)
        g_c, g_ctx, g_ngs = sgns_math(emb_in[center], emb_out[ctx],
                                      emb_out[neg], 1.0 / B)
        # consume gradients without row writes (keeps the gathers +
        # math live under DCE; one scalar accumulate instead)
        s = g_c.sum() + g_ctx.sum() + g_ngs.sum()
        return (emb_in + s * 0.0, emb_out), 0.0

    def variant_scatter_only(carry, key):
        emb_in, emb_out = carry
        k1, k2 = jax.random.split(key)
        center = jax.random.randint(k1, (B,), 0, V)
        ctx = jax.random.randint(k1, (B,), 0, V)
        neg = jax.random.randint(k2, (B, N), 0, V)
        row = jnp.full((B, K), 1e-6, jnp.float32)
        rows_n = jnp.full((B * N, K), 1e-6, jnp.float32)
        emb_in = emb_in.at[center].add(row)
        emb_out = emb_out.at[ctx].add(row)
        emb_out = emb_out.at[neg.reshape(-1)].add(rows_n)
        return (emb_in, emb_out), 0.0

    def variant_pure_gather(carry, key):
        emb_in, emb_out = carry
        k2 = jax.random.fold_in(key, 1)
        neg = jax.random.randint(k2, (B * rows_per_pair,), 0, V)
        s = emb_out[neg].sum()
        return (emb_in + s * 0.0, emb_out), 0.0

    def variant_sorted_gather(carry, key):
        emb_in, emb_out = carry
        k2 = jax.random.fold_in(key, 1)
        neg = jnp.sort(jax.random.randint(k2, (B * rows_per_pair,), 0, V))
        s = emb_out[neg].sum()
        return (emb_in + s * 0.0, emb_out), 0.0

    def run(variant):
        @jax.jit
        def loop(emb_in, emb_out, key):
            keys = jax.random.split(key, steps)
            (ei, eo), _ = jax.lax.scan(variant, (emb_in, emb_out), keys)
            return ei, eo

        loop(emb_in, emb_out, key)  # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            ei, eo = loop(emb_in, emb_out, key)
            float(ei[0, 0])  # execution fence
            best = min(best, time.perf_counter() - t0)
        return best / steps

    results = {}
    for name, fn in [("full", variant_full),
                     ("gather_only", variant_gather_only),
                     ("scatter_only", variant_scatter_only),
                     ("pure_gather", variant_pure_gather),
                     ("sorted_gather", variant_sorted_gather)]:
        step_s = run(fn)
        results[name] = step_s
        rows = B * rows_per_pair
        print(f"{name:14s} {step_s*1e3:7.3f} ms/step  "
              f"{B/step_s/1e6:6.2f} M pairs/s  "
              f"{rows/step_s/1e6:7.1f} M rows/s", flush=True)

    # ceiling statement: the full step must gather AND scatter
    # rows_per_pair rows per pair; with measured per-row op costs
    # t_g (pure gather) and t_s (scatter-only), the op-bound floor is
    pg = results["pure_gather"] / (B * rows_per_pair)   # s per gathered row
    so = results["scatter_only"] / (B * rows_per_pair)  # s per scattered row
    floor_step = (pg + so) * B * rows_per_pair
    print(f"\nop-bound floor (gather+scatter at measured rates): "
          f"{floor_step*1e3:.3f} ms/step = "
          f"{B/floor_step/1e6:.2f} M pairs/s")
    print(f"full step is {results['full']/floor_step:.2f}x the floor; "
          f"sorted gather is {results['pure_gather']/results['sorted_gather']:.2f}x "
          f"the unsorted gather")


if __name__ == "__main__":
    main()
