"""Device-plane observability: jit-cache inventory with retrace blame,
per-dispatch device-time attribution, the capped-label guard on
metered_jit, fleet merge sum-exactness, the `/debug/profile/device.json`
delegation contract, the device-memory alert rule, and the
`coverage-jit-metering` lint rule. The live HTTP + 4-worker fleet drills
run in `quality.py --telemetry-gate`."""

import http.client
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from predictionio_tpu.telemetry import device
from predictionio_tpu.telemetry.device import (
    UNTRACKED_ROUTE,
    diff_signatures,
    merge_device,
    signature_of,
)
from predictionio_tpu.telemetry.registry import (
    LABEL_OVERFLOW,
    capped_label,
    reset_label_caps,
)


@pytest.fixture(autouse=True)
def _clean_device_state():
    device.reset_state()
    yield
    device.reset_state()


# -- abstract signatures and diffing ------------------------------------------

class TestSignatures:
    def test_arrays_become_dtype_bracket_dims(self):
        sig = signature_of((np.zeros((4, 8), np.float32),), None)
        assert sig == ("arg0:float32[4,8]",)

    def test_scalars_and_kwargs_sorted(self):
        sig = signature_of((True, 3, 2.5), {"b": "s", "a": None})
        assert sig == ("arg0:bool(True)", "arg1:int(3)", "arg2:float(2.5)",
                       "a=None", "b=str(s)")

    def test_dimension_level_blame_same_dtype_rank(self):
        old = signature_of((np.zeros((4, 8), np.float32),), None)
        new = signature_of((np.zeros((64, 8), np.float32),), None)
        assert diff_signatures(old, new) == ["arg0 dim0: 4→64"]

    def test_dtype_change_is_spec_level(self):
        old = signature_of((np.zeros((4,), np.float32),), None)
        new = signature_of((np.zeros((4,), np.int32),), None)
        assert diff_signatures(old, new) == ["arg0: float32[4]→int32[4]"]

    def test_added_and_removed_arguments(self):
        assert diff_signatures(("arg0:int(1)",),
                               ("arg0:int(1)", "arg1:int(2)")) == \
            ["arg1:int(2) added"]
        assert diff_signatures(("arg0:int(1)", "arg1:int(2)"),
                               ("arg0:int(1)",)) == ["arg1:int(2) removed"]

    def test_kwarg_value_change(self):
        old = signature_of((), {"k": 10})
        new = signature_of((), {"k": 20})
        assert diff_signatures(old, new) == ["k: int(10)→int(20)"]


# -- retrace blame on the serving bucket ladder (real metered_jit) ------------

class TestRetraceBlameOnBucketLadder:
    def test_third_tier_shape_is_blamed_and_counters_agree(self):
        jax = pytest.importorskip("jax")
        jnp = jax.numpy
        from predictionio_tpu.utils.profiling import JIT_COMPILES, metered_jit

        label = "test_device.ladder_score"
        score = metered_jit(lambda x: jnp.sum(x * 2.0), label=label)
        compiles_before = JIT_COMPILES.labels(fn=label).value

        # warm two bucket tiers, then dispatch a shape outside the ladder
        for rows in (4, 16):
            for _ in range(2):
                score(jnp.zeros((rows, 8), jnp.float32))
        with device.attribution("/queries.json", tier="64"):
            score(jnp.zeros((64, 8), jnp.float32))

        _status, body = device.jit_payload()
        fn = body["fns"][label]
        # the escaped shape must carry dimension-level blame
        blames = fn["retrace_blame"]
        assert blames, "no retrace blame recorded for the escaped shape"
        assert any("dim0" in c and "64" in c
                   for b in blames for c in b["changed"])
        # exact agreement: the /metrics counter and the inventory saw the
        # same compiles (3 tiers traced once each on this fresh label)
        compiled_delta = JIT_COMPILES.labels(fn=label).value \
            - compiles_before
        assert fn["compiles_total"] == compiled_delta == 3
        # two warm tiers then one escape: exactly 2 retraces (tier 2's
        # warm-up compile counts as one by design)
        assert fn["retraces_total"] == 2
        assert len(fn["signatures"]) == 3
        assert sum(s["dispatches"] for s in fn["signatures"]) == \
            fn["dispatches_total"] == 5

    def test_attribution_context_labels_the_route(self):
        t0 = time.perf_counter()
        with device.attribution("/queries.json", tier="16"):
            device.record_dispatch("test_device.attr", (1,), out=None,
                                   t0=t0, t1=t0 + 0.001)
        device.record_dispatch("test_device.attr", (1,), out=None,
                               t0=t0, t1=t0 + 0.001)
        _status, body = device.jit_payload()
        rows = {(r["route"], r["tier"]): r
                for r in body["device_attribution"]
                if r["fn"] == "test_device.attr"}
        assert ("/queries.json", "16") in rows
        assert (UNTRACKED_ROUTE, "") in rows
        assert rows[("/queries.json", "16")]["us"] >= 900


# -- capped labels (the metered_jit label-collision guard) --------------------

class TestCappedLabel:
    def test_overflow_collapses_after_cap(self):
        group = "test_device_cap"
        reset_label_caps(group)
        try:
            admitted = [capped_label(group, f"fn{i}", cap=4)
                        for i in range(6)]
            assert admitted[:4] == ["fn0", "fn1", "fn2", "fn3"]
            assert admitted[4] == admitted[5] == LABEL_OVERFLOW
            # values admitted before the cap keep stable identity forever
            assert capped_label(group, "fn1", cap=4) == "fn1"
        finally:
            reset_label_caps(group)

    def test_metered_jit_labels_ride_the_jit_fn_group(self):
        jax = pytest.importorskip("jax")
        jnp = jax.numpy
        from predictionio_tpu.utils.profiling import metered_jit

        # a runtime-value-minted label (the old score_topk_k{k} bug
        # shape) must resolve through the shared "jit_fn" cap group
        f = metered_jit(lambda x: x + 1, label="test_device.capped")
        f(jnp.zeros((2,), jnp.float32))
        _status, body = device.jit_payload()
        assert "test_device.capped" in body["fns"]
        assert capped_label("jit_fn", "test_device.capped") == \
            "test_device.capped"


# -- /debug/profile/device.json delegation (satellite: moved envelope) --------

class TestDeviceMemoryEndpoint:
    def test_503_envelope_without_jax(self):
        # the contract is per-process; this test process may have jax
        # loaded, so probe a fresh interpreter that never imports it
        code = (
            "import json, sys\n"
            "from predictionio_tpu.telemetry import device\n"
            "assert 'jax' not in sys.modules\n"
            "s, b = device.memory_payload()\n"
            "from predictionio_tpu.telemetry import profiler\n"
            "s2, b2 = profiler.device_payload()\n"
            "assert 'jax' not in sys.modules, 'delegate imported jax'\n"
            "print(json.dumps([s, b, s2, b2]))\n")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        status, body, status2, body2 = json.loads(out.stdout)
        assert status == status2 == 503
        assert body == body2 == {
            "status": 503, "error": "jax not loaded in this process"}

    def test_profiler_delegate_matches_device_impl(self):
        pytest.importorskip("jax")
        from predictionio_tpu.telemetry import profiler

        status, body = profiler.device_payload()
        assert status == 200
        assert "live_buffers" in body and "memory_stats" in body


# -- fleet merge sum-exactness ------------------------------------------------

class TestMergeDevice:
    def _state(self, route, us, n=3, fn="f", retraces=1):
        return {"attribution": [[route, fn, "8", "cpu", us, n]],
                "fns": {fn: {"compiles": 2, "dispatches": n,
                             "retraces": retraces}},
                "total_us": us, "clock_running": True}

    def test_totals_are_sum_exact_inside_one_payload(self):
        merged = merge_device([
            ("w0", self._state("/queries.json", 1500)),
            ("w1", self._state("/queries.json", 2500)),
            ("w2", self._state("/events.json", 7)),
        ])
        assert merged["fleet"] is True
        assert merged["total_us"] == 4007
        # exactness is checkable from the single payload
        assert merged["total_us"] == sum(merged["workers"].values())
        assert merged["workers"] == {"w0": 1500, "w1": 2500, "w2": 7}
        assert merged["routes"] == {"/queries.json": 4000,
                                    "/events.json": 7}
        assert merged["fns"]["f"] == {"compiles": 6, "dispatches": 9,
                                      "retraces": 3}
        assert merged["clocks_running"] == 3

    def test_dead_worker_merges_as_zero_not_crash(self):
        merged = merge_device([("w0", self._state("/q", 10)),
                               ("w1", None)])
        assert merged["workers"] == {"w0": 10, "w1": 0}
        assert merged["total_us"] == 10

    def test_attribution_rows_merge_by_full_key(self):
        a = self._state("/q", 100)
        merged = merge_device([("w0", a), ("w1", a)])
        rows = merged["attribution"]
        assert len(rows) == 1
        assert rows[0]["us"] == 200 and rows[0]["dispatches"] == 6

    def test_export_state_round_trips_through_merge(self):
        t0 = time.perf_counter()
        with device.attribution("/queries.json", tier="4"):
            device.record_dispatch("test_device.rt", (1,), out=None,
                                   t0=t0, t1=t0 + 0.002)
        st = device.export_state()
        merged = merge_device([("w0", st), ("w1", st)])
        assert merged["total_us"] == 2 * st["total_us"] > 0
        assert merged["total_us"] == sum(merged["workers"].values())


# -- the device-memory headroom alert rule ------------------------------------

class TestHeadroomAlertRule:
    def test_min_stat_reduces_to_most_constrained_device(self):
        from predictionio_tpu.telemetry.alerts import AlertRule
        from predictionio_tpu.telemetry.history import MetricsHistory
        from predictionio_tpu.telemetry.registry import MetricsRegistry

        reg = MetricsRegistry()
        g = reg.gauge("device_mem_headroom_ratio", "t",
                      labelnames=("device",))
        hist = MetricsHistory(reg, interval_s=1.0, window_s=600)
        rule = AlertRule(name="device-headroom-5m", kind="threshold",
                         metric="device_mem_headroom_ratio",
                         stat="min", op="<", value=0.10, window_s=300.0)
        # silent while the gauge family has no samples (CPU deployments)
        assert rule.measure(hist) is None
        for t in range(3):
            g.labels(device="tpu:0").set(0.50)
            g.labels(device="tpu:1").set(0.04)   # the constrained one
            hist.sample_now(now=1000.0 + t)
        measured = rule.measure(hist)
        # min-agg picks tpu:1, not the healthy tpu:0
        assert measured == pytest.approx(0.04)
        assert rule.breached(measured)

    def test_default_rules_ship_the_headroom_page(self):
        from predictionio_tpu.telemetry.alerts import default_rules

        rules = {r.name: r for r in default_rules()}
        rule = rules["device-headroom-5m"]
        assert rule.metric == "device_mem_headroom_ratio"
        assert (rule.stat, rule.op) == ("min", "<")
        assert rule.severity == "page"


# -- memory sampler gauges ----------------------------------------------------

class TestMemorySampler:
    def test_sample_folds_live_bytes_and_high_water(self):
        jax = pytest.importorskip("jax")
        jnp = jax.numpy
        keep = jnp.ones((256, 4), jnp.float32)  # pin a live buffer
        sampler = device.MemorySampler(interval_s=60.0)
        live = sampler.sample_now()
        assert live, "no live devices despite a pinned buffer"
        dev, nbytes = next(iter(live.items()))
        assert nbytes > 0
        assert sampler.high_water[dev] >= nbytes
        del keep

    def test_empty_without_jax_loaded(self):
        code = (
            "import sys\n"
            "from predictionio_tpu.telemetry import device\n"
            "s = device.MemorySampler(interval_s=60.0)\n"
            "assert s.sample_now() == {}\n"
            "assert 'jax' not in sys.modules\n")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr


# -- /debug/jit.json over HTTP ------------------------------------------------

class TestJitRoute:
    def test_route_serves_inventory_and_clock_block(self):
        from predictionio_tpu.utils.http import (
            HttpService,
            JsonRequestHandler,
        )

        class _OkHandler(JsonRequestHandler):
            def do_GET(self):
                self.read_body()
                self.send_json(200, {"ok": True})

        t0 = time.perf_counter()
        device.record_dispatch("test_device.http", (1,), out=None,
                               t0=t0, t1=t0 + 0.001)
        svc = HttpService("127.0.0.1", 0, _OkHandler,
                          server_name="devtestsvc")
        svc.start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", svc.port,
                                              timeout=10)
            conn.request("GET", "/debug/jit.json")
            resp = conn.getresponse()
            status, body = resp.status, json.loads(resp.read())
            conn.close()
        finally:
            svc.shutdown()
        assert status == 200
        assert "test_device.http" in body["fns"]
        assert body["totals"]["dispatches"] >= 1
        assert set(body["clock"]) == {"enabled", "running", "queue_depth",
                                      "backend"}


# -- the coverage-jit-metering lint rule --------------------------------------

class TestCoverageJitMeteringRule:
    def _findings(self, tmp_path, source):
        from predictionio_tpu.analysis import engine
        from predictionio_tpu.analysis.engine import Project

        (tmp_path / "mod.py").write_text(source)
        return engine.run_rules(Project(str(tmp_path)),
                                ["coverage-jit-metering"])

    def test_flags_bare_call_decorator_and_partial(self, tmp_path):
        findings = self._findings(tmp_path, (
            "import functools\n"
            "import jax\n"
            "from jax import jit, pjit\n"
            "f = jax.jit(lambda x: x)\n"
            "g = pjit(lambda x: x)\n"
            "@jit\n"
            "def h(x):\n"
            "    return x\n"
            "k = functools.partial(jax.jit, static_argnums=(0,))\n"
            "def ok(x):\n"
            "    return x\n"))
        lines = sorted(f.line for f in findings)
        assert lines == [4, 5, 6, 9]
        assert all(f.rule == "coverage-jit-metering" for f in findings)

    def test_metered_sites_and_suppressions_pass(self, tmp_path):
        findings = self._findings(tmp_path, (
            "import jax\n"
            "from predictionio_tpu.utils.profiling import metered_jit\n"
            "a = metered_jit(lambda x: x, label='m.a')\n"
            "b = jax.jit(lambda x: x)"
            "  # pio-lint: disable=coverage-jit-metering\n"))
        assert findings == []

    def test_repo_is_triaged_to_zero(self):
        from predictionio_tpu.analysis import engine
        from predictionio_tpu.analysis.engine import Project

        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        proj = Project(repo_root, subdirs=("predictionio_tpu",))
        assert engine.run_rules(proj, ["coverage-jit-metering"]) == []


class TestDeviceClockDropsWhatItMeasured:
    def test_the_drain_thread_keeps_no_dispatch_output_alive(self):
        """The output of a train step is the train's whole state on the
        device. The drain thread waits for its next item with the last
        one no longer referenced, or that state would outlive its train
        (it did: 8 GB of an encoder's state held the chip at PR 27)."""
        import gc
        import weakref

        import jax  # noqa: F401  (or the drain thread's first import is timed)

        class Output:
            pass

        clock = device.DeviceClock(maxsize=4)
        try:
            out = Output()
            alive = weakref.ref(out)
            assert clock.submit(out, 0.0, 0.0, "t.drop", "(test)", "", False)
            del out
            assert clock.flush(timeout=5.0)
            deadline = time.monotonic() + 20.0
            while alive() is not None and time.monotonic() < deadline:
                gc.collect()
                time.sleep(0.01)
            assert alive() is None
        finally:
            clock.stop()
