"""Telemetry subsystem conformance (ISSUE 2): registry thread-safety,
histogram math, Prometheus exposition, /metrics on every server, trace-id
propagation through the SDK → event server → storage → prediction server,
and the ≤5% instrumentation-overhead bar on the query hot path."""

import gc
import http.client
import json
import logging
import statistics
import sys
import threading
import time

import pytest

from predictionio_tpu.data.api import EventServer, EventServerConfig, Stats
from predictionio_tpu.sdk import EngineClient, EventClient
from predictionio_tpu.storage.base import AccessKey, App
from predictionio_tpu.telemetry import middleware, tracing
from predictionio_tpu.telemetry.registry import (
    REGISTRY,
    MetricsRegistry,
    parse_prometheus,
)
from predictionio_tpu.utils.http import HttpService, JsonRequestHandler

REQUIRED_FAMILIES = ("http_requests_total", "http_request_duration_seconds",
                     "http_in_flight")


def _get(port, path, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", path, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


# -- registry ---------------------------------------------------------------

class TestRegistry:
    def test_counter_thread_safety(self):
        reg = MetricsRegistry()
        c = reg.counter("race_total", "t", labelnames=("who",))
        n_threads, per_thread = 8, 10_000

        def work(i):
            child = c.labels(who="all")
            for _ in range(per_thread):
                child.inc()

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.labels(who="all").value == n_threads * per_thread

    def test_histogram_thread_safety(self):
        reg = MetricsRegistry()
        h = reg.histogram("race_seconds", "t", buckets=(0.5, 1.0))

        def work():
            for _ in range(5_000):
                h.observe(0.25)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        _, (counts, total, count) = h.collect()[0]
        assert count == 40_000 and counts[0] == 40_000
        assert total == pytest.approx(40_000 * 0.25)

    def test_histogram_bucket_math(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", "t", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.1, 0.5, 1.0, 7.0, 99.0):
            h.observe(v)
        _, (counts, total, count) = h.collect()[0]
        # per-bucket: boundary values land in their own bucket (le = ≤)
        assert counts == [2, 2, 1]  # ≤0.1: {.05,.1}; ≤1: {.5,1}; ≤10: {7}
        assert count == 6           # +Inf picks up 99.0
        assert total == pytest.approx(sum((0.05, 0.1, 0.5, 1.0, 7.0, 99.0)))
        # rendered cumulatively
        text = reg.render()
        assert 'lat_bucket{le="0.1"} 2' in text
        assert 'lat_bucket{le="1"} 4' in text
        assert 'lat_bucket{le="10"} 5' in text
        assert 'lat_bucket{le="+Inf"} 6' in text

    def test_type_conflict_rejected(self):
        reg = MetricsRegistry()
        reg.counter("m", "t")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("m", "t")
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("m", "t", labelnames=("x",))

    def test_exposition_golden(self):
        reg = MetricsRegistry()
        c = reg.counter("events_total", "Events seen",
                        labelnames=("app", "status"))
        c.labels(app="a", status="201").inc()
        c.labels(app="a", status="201").inc()
        c.labels(app="b", status="400").inc(3)
        reg.gauge("in_flight", "Now").set(2)
        h = reg.histogram("latency_seconds", "Latency", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        assert reg.render() == (
            "# HELP events_total Events seen\n"
            "# TYPE events_total counter\n"
            'events_total{app="a",status="201"} 2\n'
            'events_total{app="b",status="400"} 3\n'
            "# HELP in_flight Now\n"
            "# TYPE in_flight gauge\n"
            "in_flight 2\n"
            "# HELP latency_seconds Latency\n"
            "# TYPE latency_seconds histogram\n"
            'latency_seconds_bucket{le="0.1"} 1\n'
            'latency_seconds_bucket{le="1"} 2\n'
            'latency_seconds_bucket{le="+Inf"} 3\n'
            "latency_seconds_sum 5.55\n"
            "latency_seconds_count 3\n"
        )

    def test_parse_prometheus_roundtrip(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total", "x", labelnames=("k",))
        c.labels(k="v").inc(7)
        parsed = parse_prometheus(reg.render())
        assert parsed["x_total"]['{k="v"}'] == 7.0

    def test_label_value_escaping(self):
        reg = MetricsRegistry()
        c = reg.counter("esc_total", "t", labelnames=("p",))
        c.labels(p='a"b\\c\nd').inc()
        assert 'esc_total{p="a\\"b\\\\c\\nd"} 1' in reg.render()


# -- tracing ----------------------------------------------------------------

class TestTracing:
    def test_trace_and_span_nesting(self):
        assert tracing.current_trace_id() is None
        with tracing.trace("abc123") as ctx:
            assert tracing.current_trace_id() == "abc123"
            with tracing.span("inner") as child:
                assert child.trace_id == "abc123"
                assert child.parent_span_id == ctx.span_id
            assert tracing.current() is ctx or \
                tracing.current().trace_id == "abc123"
        assert tracing.current_trace_id() is None

    def test_header_roundtrip(self):
        headers = {}
        with tracing.trace("roundtrip1"):
            tid = tracing.inject_headers(headers)
        assert tid == "roundtrip1"
        ctx, inbound = tracing.context_from_headers(headers)
        assert inbound and ctx.trace_id == "roundtrip1"

    def test_hostile_header_rejected(self):
        ctx, inbound = tracing.context_from_headers(
            {tracing.TRACE_HEADER: 'evil"} bad\nstuff'})
        assert not inbound
        assert ctx.trace_id != 'evil"} bad\nstuff'

    def test_log_record_factory_stamps_trace_id(self, caplog):
        tracing.install_log_record_factory()
        log = logging.getLogger("test.telemetry.factory")
        with caplog.at_level(logging.INFO, logger="test.telemetry.factory"):
            with tracing.trace("logstamp1"):
                log.info("inside")
            log.info("outside")
        inside, outside = caplog.records[-2:]
        assert inside.trace_id == "logstamp1"
        assert outside.trace_id == "-"


# -- /metrics on every server ----------------------------------------------

def _assert_metrics_ok(port):
    # one ordinary request first so http_requests_total has a sample
    _get(port, "/")
    status, headers, body = _get(port, "/metrics")
    assert status == 200
    assert headers.get("Content-Type", "").startswith("text/plain")
    text = body.decode()
    for family in REQUIRED_FAMILIES:
        assert f"# TYPE {family} " in text, f"{family} missing"
    parsed = parse_prometheus(text)
    assert any(v > 0 for v in parsed["http_requests_total"].values())
    return text


@pytest.fixture()
def event_server(memory_storage):
    app_id = memory_storage.meta_apps().insert(App(id=0, name="TApp"))
    key = AccessKey.generate(app_id)
    memory_storage.meta_access_keys().insert(key)
    srv = EventServer(EventServerConfig(ip="127.0.0.1", port=0, stats=True),
                      memory_storage)
    srv.start()
    yield srv, key.key
    srv.shutdown()


class TestMetricsEndpoint:
    def test_event_server(self, event_server):
        srv, _ = event_server
        text = _assert_metrics_ok(srv.port)
        assert 'server="eventserver"' in text

    def test_prediction_server(self, memory_storage):
        from predictionio_tpu.workflow.create_server import (
            PredictionServer, ServerConfig)
        from tests.test_prediction_server import train_once
        from tests.test_recommendation_template import ingest_ratings

        ingest_ratings(memory_storage)
        train_once(memory_storage)
        server = PredictionServer(
            ServerConfig(ip="127.0.0.1", port=0, engine_id="rec-test",
                         engine_variant="rec-test"), memory_storage)
        server.start()
        try:
            text = _assert_metrics_ok(server.port)
            assert 'server="predictionserver"' in text
        finally:
            server.shutdown()

    def test_dashboard(self, memory_storage):
        from predictionio_tpu.tools.dashboard import Dashboard

        dash = Dashboard(ip="127.0.0.1", port=0, storage=memory_storage)
        dash.start()
        try:
            text = _assert_metrics_ok(dash.port)
            assert 'server="dashboard"' in text
            # the summary panel renders on the landing page
            _, _, page = _get(dash.port, "/")
            assert b"<h2>Telemetry</h2>" in page
            assert b"http_requests_total" in page
        finally:
            dash.shutdown()

    def test_admin_server(self, memory_storage):
        from predictionio_tpu.tools.admin import AdminServer

        admin = AdminServer(ip="127.0.0.1", port=0, storage=memory_storage)
        admin.start()
        try:
            text = _assert_metrics_ok(admin.port)
            assert 'server="adminserver"' in text
        finally:
            admin.shutdown()

    def test_route_templates_bound_cardinality(self, event_server):
        srv, key = event_server
        for i in range(5):
            _get(srv.port, f"/events/ev-{i}.json?accessKey={key}")
            _get(srv.port, f"/no/such/route/{i}")
        _, _, body = _get(srv.port, "/metrics")
        text = body.decode()
        assert 'route="/events/<id>.json"' in text
        assert 'route="<other>"' in text
        assert 'route="/events/ev-0.json"' not in text
        assert 'route="/no/such/route/0"' not in text


# -- stats migration --------------------------------------------------------

class TestStatsMigration:
    def test_per_instance_baseline(self):
        s1 = Stats()
        s1.update(1, "rate", 201)
        s1.update(1, "rate", 201)
        s2 = Stats()  # a later server start must not see s1's counts
        s1.update(1, "view", 201)
        assert s1.snapshot(1)["counts"] == [
            {"event": "rate", "status": 201, "count": 2},
            {"event": "view", "status": 201, "count": 1},
        ]
        assert s2.snapshot(1)["counts"] == [
            {"event": "view", "status": 201, "count": 1},
        ]

    def test_registry_view_is_cumulative(self, event_server):
        srv, key = event_server
        ev = {"event": "rate", "entityType": "user", "entityId": "u1",
              "targetEntityType": "item", "targetEntityId": "i1",
              "properties": {"rating": 4.0}}
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        conn.request("POST", f"/events.json?accessKey={key}",
                     json.dumps(ev).encode(),
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 201
        conn.close()
        _, _, body = _get(srv.port, "/metrics")
        parsed = parse_prometheus(body.decode())
        rate = [v for k, v in parsed["eventserver_events_total"].items()
                if 'event="rate"' in k and 'status="201"' in k]
        assert rate and sum(rate) >= 1


# -- trace propagation: sdk → event server → storage → prediction server ----

class TestTracePropagation:
    def test_end_to_end(self, memory_storage, caplog):
        from predictionio_tpu.storage.registry import STORAGE_OP_SECONDS
        from predictionio_tpu.workflow.create_server import (
            PredictionServer, ServerConfig)
        from tests.test_prediction_server import train_once
        from tests.test_recommendation_template import ingest_ratings

        app_id = memory_storage.meta_apps().insert(App(id=0, name="TraceApp"))
        key = AccessKey.generate(app_id)
        memory_storage.meta_access_keys().insert(key)
        ingest_ratings(memory_storage)
        train_once(memory_storage)

        events = EventServer(
            EventServerConfig(ip="127.0.0.1", port=0), memory_storage)
        events.start()
        engine = PredictionServer(
            ServerConfig(ip="127.0.0.1", port=0, engine_id="rec-test",
                         engine_variant="rec-test"), memory_storage)
        engine.start()
        ec = EventClient(access_key=key.key,
                         url=f"http://127.0.0.1:{events.port}")
        qc = EngineClient(url=f"http://127.0.0.1:{engine.port}")
        tid = "e2etrace0001"
        inserts_before = STORAGE_OP_SECONDS.labels(
            repo="l_events", op="insert").count
        try:
            with caplog.at_level(logging.INFO,
                                 logger="predictionio_tpu.http.access"):
                with tracing.trace(tid):
                    ec.create_event(event="rate", entity_type="user",
                                    entity_id="u0",
                                    target_entity_type="item",
                                    target_entity_id="i0",
                                    properties={"rating": 5.0})
                    assert ec.last_trace_id == tid  # response header echo
                    qc.send_query({"user": "u0", "num": 2})
                    assert qc.last_trace_id == tid
                # The access line is emitted by the handler thread *after*
                # the response bytes go out, so the client can get here
                # first — poll briefly instead of racing it.
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    msgs = [r.getMessage() for r in caplog.records]
                    if (any("eventserver" in m and tid in m for m in msgs)
                            and any("predictionserver" in m and tid in m
                                    for m in msgs)):
                        break
                    time.sleep(0.02)
        finally:
            ec.close()
            qc.close()
            events.shutdown()
            engine.shutdown()
        # one trace id, visible in BOTH servers' access logs
        msgs = [r.getMessage() for r in caplog.records]
        assert any("eventserver" in m and tid in m for m in msgs), msgs
        assert any("predictionserver" in m and tid in m for m in msgs), msgs
        # ... and the storage layer under the event server measured the write
        assert STORAGE_OP_SECONDS.labels(
            repo="l_events", op="insert").count > inserts_before


# -- overhead bar -----------------------------------------------------------

class _PingHandler(JsonRequestHandler):
    def do_GET(self):
        self.send_json(200, {"ok": True})


def _request_bookkeeping(headers, jax_loaded):
    """`_run_instrumented`'s bookkeeping for one request, exactly
    (everything but the handler body), the flight-recorder path included:
    timeline begin/finish, a recorded span, RECORDER.offer, and the
    slo.observe fold inside record_request."""
    from predictionio_tpu.telemetry import spans as spans_mod
    from predictionio_tpu.telemetry.recorder import RECORDER

    ctx, inbound = tracing.context_from_headers(headers)
    token = tracing.activate(ctx)
    tl, tl_token = spans_mod.begin("overheadbench", "/", "GET", ctx.trace_id)
    in_flight = middleware._in_flight("overheadbench")
    in_flight.inc()
    if jax_loaded:
        ann = tracing._jax_annotation("overheadbench GET /")
        if ann is not None:
            ann.__enter__()
            ann.__exit__(None, None, None)
    in_flight.dec()
    middleware.record_request("overheadbench", "GET", "/", 200, 0.001)
    spans_mod.finish(tl, tl_token, 200, 0.001)
    RECORDER.offer(tl)
    middleware.access_logger.log(
        logging.INFO if inbound else logging.DEBUG,
        "%s %s %s -> %s %.1fms trace=%s",
        "overheadbench", "GET", "/", 200, 1.0, ctx.trace_id)
    tracing.deactivate(token)


# registry operations the bookkeeping of one request may do: what it does
# today. A new one is a deliberate edit of this line and of the hot path.
REGISTRY_OPS_A_REQUEST = 5


def test_instrumentation_overhead_under_5_percent(monkeypatch):
    """What the ≤5% bar on the query hot path stands for, as a count: the
    per-request telemetry machinery does a fixed number of registry
    operations (a child looked up, a counter or gauge moved, a histogram
    observed), the same for every request however many came before, and no
    more than `REGISTRY_OPS_A_REQUEST`; the request in `sample_rate` whose
    timeline the flight recorder keeps does two more at most. The timed ratio against a loopback
    request's p50 is `test_instrumentation_overhead_ratio_timed` (slow):
    on a CPU shared by the suite's workers it measured the machine
    (ROADMAP.md D10)."""
    from predictionio_tpu.telemetry import registry as registry_mod

    ops = []
    for cls, names in ((registry_mod._Child, ("inc", "set")),
                       (registry_mod._HistogramChild, ("observe",)),
                       (registry_mod.Counter, ("labels",)),
                       (registry_mod.Histogram, ("labels",))):
        for name in names:
            def counted(self, *a, _real=getattr(cls, name),
                        _op=f"{cls.__name__}.{name}", **kw):
                ops.append(_op)
                return _real(self, *a, **kw)

            monkeypatch.setattr(cls, name, counted)
    from predictionio_tpu.telemetry.recorder import RECORDER

    headers = {tracing.TRACE_HEADER: "overheadbench1"}
    jax_loaded = "jax" in sys.modules
    # a healthy request the flight recorder lets go: nearly every one
    monkeypatch.setattr(RECORDER, "_random", lambda: 1.0)
    for _ in range(3):  # first requests make the labelled children
        _request_bookkeeping(headers, jax_loaded)
    per_request = []
    for _ in range(200):
        del ops[:]
        _request_bookkeeping(headers, jax_loaded)
        per_request.append(tuple(ops))
    assert len(set(per_request)) == 1, set(per_request)
    assert 0 < len(per_request[0]) <= REGISTRY_OPS_A_REQUEST, per_request[0]
    # one it samples keeps its timeline: the ring's size, maybe an eviction
    monkeypatch.setattr(RECORDER, "_random", lambda: 0.0)
    del ops[:]
    _request_bookkeeping(headers, jax_loaded)
    assert len(per_request[0]) < len(ops) <= REGISTRY_OPS_A_REQUEST + 2, ops


@pytest.mark.slow
def test_instrumentation_overhead_ratio_timed():
    """The per-request telemetry machinery against a real loopback request
    on the query hot path: ≤5% on a quiet machine. Timed in-process (the
    exact bookkeeping `middleware` runs per request) against the measured
    p50 of a real instrumented HTTP round-trip — an A/B of two live
    servers at this tolerance would be noise-bound. Prints the ratio."""
    svc = HttpService("127.0.0.1", 0, _PingHandler, server_name="overheadsvc")
    svc.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", svc.port, timeout=10)
        samples = []
        for _ in range(50):  # warm-up
            conn.request("GET", "/")
            conn.getresponse().read()
        for _ in range(300):
            t0 = time.perf_counter()
            conn.request("GET", "/")
            conn.getresponse().read()
            samples.append(time.perf_counter() - t0)
        conn.close()
    finally:
        svc.shutdown()
    request_p50 = statistics.median(samples)

    # Microbenchmark hygiene: GC off, min over batches — the machinery's
    # cost is its best repeatable time, not GC jitter.
    headers = {tracing.TRACE_HEADER: "overheadbench1"}
    jax_loaded = "jax" in sys.modules
    n = 1000
    batches = []
    gc.disable()
    try:
        for _ in range(10):
            t0 = time.perf_counter()
            for _ in range(n):
                _request_bookkeeping(headers, jax_loaded)
            batches.append((time.perf_counter() - t0) / n)
    finally:
        gc.enable()
    per_request = min(batches)
    print(f"telemetry adds {per_request * 1e6:.1f}µs/request against a "
          f"{request_p50 * 1e6:.1f}µs p50 ({per_request / request_p50:.1%})")

    assert per_request <= 0.05 * request_p50, (
        f"telemetry adds {per_request * 1e6:.1f}µs/request against a "
        f"{request_p50 * 1e6:.1f}µs p50 "
        f"({per_request / request_p50:.1%} > 5%)")
