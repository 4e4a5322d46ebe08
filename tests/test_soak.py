"""Short-window soak mechanism drill (VERDICT r4 next #6).

The full receipt is `bench.py --soak --duration 600`; the suite runs
the same machinery — concurrent ingest +
serving + background retrain/reload with RSS/fd/thread probes and the
starvation/error gates — over a window short enough for CI. The
flatness assertions themselves execute either way (bench_soak raises on
any error, starvation, RSS growth past bar, or fd leak)."""

import pytest


@pytest.mark.e2e
def test_short_soak_mixed_load():
    import bench

    record = bench.bench_soak(duration_s=25.0, emit=False,
                              retrain_every_s=8.0)
    assert record["errors"] == 0
    assert record["counts"]["serve"] > 0
    assert record["counts"]["ingest"] > 0
    assert record["counts"]["retrain"] >= 1
    assert record["counts"]["reload"] >= 1
    assert record["rss_mb"]["growth_vs_warm"] <= 1.15
    assert record["fds"]["end"] <= record["fds"]["baseline"] + 15
