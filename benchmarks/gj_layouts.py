"""Same-window A/B of the GJ solver's layouts, DEVICE time via xplane.
Chained solves (b_{i+1} = A^-1 b_i) inside one jit defeat CSE and
amortize dispatch; the time is the whole solve's (the kernel and the XLA
layout copies around it). The first two shapes are the hot buckets of an
ML-20M train at rank 64 (item side, user side); run by no benchmark cell:
`python3 benchmarks/gj_layouts.py` on the chip."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from predictionio_tpu.ops.pallas_solve import gj_solve
from predictionio_tpu.utils.profiling import trace_device_time_s

print("backend:", jax.default_backend())
N = 20

def bench(k, r):
    rng = np.random.default_rng(0)
    y = rng.normal(size=(r, k, k)).astype(np.float32)
    a = y @ y.transpose(0, 2, 1) + 0.5 * k * np.eye(k, dtype=np.float32)
    b = rng.normal(size=(r, k)).astype(np.float32)
    ref = np.linalg.solve(a, b[..., None])[..., 0]
    ad, bd = jnp.asarray(a), jnp.asarray(b)
    out = {}
    for layout in ("lanes", "aug", "packed", "blocked2", "chol"):
        if layout == "chol":
            def solve(a_, b_):
                c = jnp.linalg.cholesky(a_)
                y1 = lax.linalg.triangular_solve(c, b_[..., None],
                                                 left_side=True, lower=True)
                return lax.linalg.triangular_solve(
                    c, y1, left_side=True, lower=True, transpose_a=True)[..., 0]
        else:
            solve = lambda a_, b_, L=layout: gj_solve(a_, b_, layout=L)
        one = jax.jit(solve)
        x = np.asarray(one(ad, bd))
        rel = np.abs(x - ref).max() / np.abs(ref).max()
        assert rel < 1e-4, (layout, k, rel)
        chain = jax.jit(lambda a_, b_: lax.fori_loop(
            0, N, lambda i, bb: solve(a_, bb), b_))
        chain(ad, bd).block_until_ready()  # compile
        best = min(trace_device_time_s(
            lambda: chain(ad, bd).block_until_ready()) for _ in range(3))
        if best <= 0:
            sys.exit("device trace captured nothing (no xplane protos on "
                     "this image, or wrong backend) — A/B needs device time")
        out[layout] = best / N
        print(f"  k={k:3d} r={r} {layout:6s}: {best/N*1e3:7.2f} ms/solve (device)")
    print(f"  k={k:3d} r={r}: lanes vs aug {out['aug']/out['lanes']:.2f}x, "
          f"blocked2 vs aug {out['aug']/out['blocked2']:.2f}x, "
          f"lanes vs chol {out['chol']/out['lanes']:.2f}x")

for k, r in [(64, 31296), (64, 12768), (128, 12664), (32, 12664)]:
    bench(k, r)
