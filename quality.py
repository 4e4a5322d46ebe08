#!/usr/bin/env python
"""Quality-parity CLI: TPU ALS vs MLlib-faithful CPU reference on
identical data (VERDICT r1 #1; north-star's "at matching MAP@10" half).

    python quality.py --mode explicit --scale 2m --rank 64 --iters 10
    python quality.py --mode implicit --scale 2m --rank 64 --alpha 40

Prints one JSON line per run. `--cpu` forces the TPU path onto the CPU
backend (virtual mesh) for hardware-free runs.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--telemetry-gate", action="store_true",
                   help="run the observability CI gate (no jax, no data): "
                        "fails if any in-package HTTP surface bypasses the "
                        "telemetry middleware, if an admitted "
                        "/queries.json or /events.json request produces a "
                        "flight-recorder timeline without its admission "
                        "and dispatch/commit spans, if the alert_* "
                        "families fail to render under a watchdog, or if "
                        "a 4-worker pool drill's supervisor /metrics "
                        "counter totals differ from the sum of the "
                        "per-worker registries (fleet-aggregation drill, "
                        "history sampling held under the 5% overhead "
                        "bar), if the always-on stack sampler is not "
                        "live with /debug/profile.json non-empty under "
                        "load at ≤5% p95 overhead (profiler drill), if "
                        "the jit-cache inventory at /debug/jit.json is "
                        "empty or inconsistent under load, misses the "
                        "retrace blame for a shape outside the warmed "
                        "bucket ladder, drops route attribution, or the "
                        "device clock exceeds the 5% overhead bar "
                        "(device drill), or if the fleet-merged "
                        "flamegraph's sample count / device-microsecond "
                        "total differs from the exact per-worker sum / "
                        "misattributes the seeded burn route")
    p.add_argument("--serving-gate", action="store_true",
                   help="run the serving CI gate (no jax, no data): fails "
                        "if any predict route bypasses admission control / "
                        "the serving plane")
    p.add_argument("--ingest-gate", action="store_true",
                   help="run the ingest CI gate (no jax, no data): fails "
                        "if any event-server write route bypasses the "
                        "group-commit write plane, or if an overloaded "
                        "server answers anything but 200/201/429")
    p.add_argument("--chaos-gate", action="store_true",
                   help="run the supervisor chaos CI gate (no jax, no "
                        "data): boots a supervised stub worker pool and "
                        "drills hard-kill, slow-worker (delay:500) and "
                        "erroring-worker recovery plus crash-loop circuit "
                        "breaking; fails unless capacity self-heals with "
                        "bounded restarts")
    p.add_argument("--hotpath-gate", action="store_true",
                   help="run the HTTP hot-path CI gate (no jax, no data): "
                        "fails if a hot-route handler (or anything it "
                        "calls in-module) uses bare json.dumps/json.loads "
                        "instead of utils.fastjson, or if a committed "
                        "ingest write fails to invalidate the per-user "
                        "serving result cache before the ack "
                        "(read-your-writes drill)")
    p.add_argument("--experiment-gate", action="store_true",
                   help="run the experimentation-plane CI gate (no jax, no "
                        "data): fails unless the sticky user→variant "
                        "mapping is identical across interpreters with "
                        "different PYTHONHASHSEEDs, the result cache "
                        "isolates variants, the Thompson bandit fed "
                        "$reward events through the real ingest funnel "
                        "converges ≥80% of traffic onto the better arm, "
                        "and the experiment_* telemetry renders")
    p.add_argument("--analysis-gate", action="store_true",
                   help="run the concurrency-analysis CI gate, two "
                        "halves: (1) a lock-sanitizer drill — "
                        "cross-plane concurrent workload under "
                        "instrumented locks (PIO_LOCKSAN machinery) "
                        "asserting no dynamic lock-order cycle and that "
                        "every observed edge matches the static lock "
                        "graph or a reviewed conf/lockorder-baseline.json "
                        "entry; (2) the pio-lint engine's full rule set "
                        "(no imports of the scanned code) — "
                        "interprocedural event-loop blocking-call rule, "
                        "whole-program lock-order deadlock detection, "
                        "race detector, jit shape discipline, coverage "
                        "rules, and the migrated serving/ingest/hotpath "
                        "static gates — failing on any finding not "
                        "inline-suppressed or grandfathered in "
                        "conf/analysis-baseline.json, with the "
                        "pio-lint --json artifact written to "
                        "$PIO_LINT_ARTIFACT for CI diffing")
    p.add_argument("--online-gate", action="store_true",
                   help="run the online-learning CI gate (jax on the local "
                        "backend, in-memory data): trains a small engine, "
                        "then drills freshness (burst of rating events for "
                        "existing and never-seen users must reach the "
                        "served model with p95 event→servable ≤ 5 s), "
                        "crash recovery (a fault between fold-in and "
                        "watermark advance must replay to bit-identical "
                        "factors with zero events lost), full-retrain "
                        "parity (folded rows bitwise-match their own "
                        "half-epoch; plane-wide drift bounded), the "
                        "session model family (a sessionrec engine's "
                        "fresh view events servable within the same 5 s "
                        "bar; crash replay rebuilds bit-identical "
                        "session windows/embeddings/scores), and the "
                        "online_* telemetry render")
    p.add_argument("--mode", choices=["explicit", "implicit"],
                   default="explicit")
    p.add_argument("--scale", choices=["100k", "2m", "20m"], default="100k")
    p.add_argument("--rank", type=int, default=10)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--reg", type=float, default=0.1)
    p.add_argument("--alpha", type=float, default=40.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ref-iters", type=int, default=None,
                   help="cap the CPU reference's iterations (it is slow at "
                        "20m scale); metrics stay comparable once converged")
    p.add_argument("--map-max-users", type=int, default=20_000)
    p.add_argument("--cpu", action="store_true",
                   help="run the TPU path on the CPU backend")
    args = p.parse_args()

    if args.telemetry_gate:
        from predictionio_tpu.telemetry.gate import run_gate

        return run_gate()

    if args.serving_gate:
        from predictionio_tpu.serving.gate import run_gate

        return run_gate()

    if args.ingest_gate:
        from predictionio_tpu.ingest.gate import run_gate

        return run_gate()

    if args.chaos_gate:
        from predictionio_tpu.runtime.gate import run_gate

        return run_gate()

    if args.hotpath_gate:
        from predictionio_tpu.utils.hotpath_gate import run_gate

        return run_gate()

    if args.experiment_gate:
        from predictionio_tpu.experiment.gate import run_gate

        return run_gate()

    if args.analysis_gate:
        from predictionio_tpu.analysis.gate import run_gate

        return run_gate()

    if args.online_gate:
        from predictionio_tpu.online.gate import run_gate

        return run_gate()

    from predictionio_tpu.utils import compile_cache

    # both read when jax is first imported, which nothing above has done
    compile_cache.configure()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from predictionio_tpu.quality.parity import run_parity

    out = run_parity(mode=args.mode, scale=args.scale, rank=args.rank,
                     iterations=args.iters, reg=args.reg, alpha=args.alpha,
                     seed=args.seed, ref_iterations=args.ref_iters,
                     map_max_users=args.map_max_users)
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main() or 0)
