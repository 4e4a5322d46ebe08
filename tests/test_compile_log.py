"""The compile log (`telemetry/device.py::COMPILE_LOG`): JAX's compile
events booked to `metered_jit`'s labels as intervals, the three places
that show them (registry, `/debug/jit.json`, the timeline), and the
process's first seconds. Counts and containment only: no test here reads
a clock against a limit."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from predictionio_tpu.telemetry import device, spans
from predictionio_tpu.telemetry.device import (
    COMPILE_LOG,
    CompileLog,
    CompileRecord,
    phase_seconds,
)
from predictionio_tpu.telemetry.registry import REGISTRY
from predictionio_tpu.utils.profiling import JIT_COMPILES, metered_jit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUILD_PHASES = ("trace", "lower", "backend_compile")


@pytest.fixture(autouse=True)
def _clean_state():
    device.reset_state()
    COMPILE_LOG.clear()
    yield
    device.reset_state()
    COMPILE_LOG.clear()


def counter(name: str, **labels) -> float:
    want = "{" + ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    for line in REGISTRY.render().splitlines():
        if line.startswith(name + want + "}"):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def of(label: str, records=None):
    records = COMPILE_LOG.records() if records is None else records
    return [r for r in records if r.fn == label]


# -- the log itself, on hand-made intervals ----------------------------------

class TestTheLog:
    def test_a_record_inside_a_later_one_is_nested_and_counted_once(self):
        log = CompileLog()
        # as JAX sends them: each as it ends, the innermost first
        assert log.add("trace", "f", 2.0, 3.0) == 1.0
        assert log.add("trace", "f", 1.5, 4.0) == 1.5   # 2.5 less the inner
        assert log.add("lower", "f", 4.5, 5.0) == 0.5   # another phase
        assert log.add("trace", "f", 1.0, 6.0) == 2.5   # 5 less the 2.5
        got = log.records()
        assert [(r.phase, r.start, r.depth) for r in got] == [
            ("trace", 2.0, 2), ("trace", 1.5, 1), ("lower", 4.5, 1),
            ("trace", 1.0, 0)]
        assert phase_seconds(got) == {"trace": 5.0, "lower": 0.5}

    def test_what_came_before_is_not_inside(self):
        log = CompileLog()
        log.add("trace", "f", 1.0, 2.0)
        assert log.add("trace", "g", 2.0, 3.0) == 1.0
        assert [r.depth for r in log.records()] == [0, 0]

    def test_another_threads_record_is_not_inside(self):
        log = CompileLog()
        t = threading.Thread(target=log.add, args=("trace", "theirs", 2., 3.))
        t.start()
        t.join()
        assert log.add("trace", "mine", 1.0, 4.0) == 3.0
        assert {r.fn: r.depth for r in log.records()} == {"theirs": 0,
                                                          "mine": 0}
        mine = log.records(thread=threading.get_ident())
        assert [r.fn for r in mine] == ["mine"]

    def test_the_cap_drops_the_oldest_and_counts(self):
        log = CompileLog(cap=3)
        for k in range(5):
            log.add("trace", f"f{k}", float(k), k + 0.5)
        assert [r.fn for r in log.records()] == ["f2", "f3", "f4"]
        assert log.dropped == 2
        log.clear()
        assert log.records() == [] and log.dropped == 0

    def test_a_full_log_still_takes_the_latest_build(self):
        """A long-lived server's late retrace is what an operator opens
        the log for: the oldest records make room for it, whole."""
        log = CompileLog(cap=4)
        for k in range(4):
            log.add("trace", "old", float(k), k + 0.5)
        log.add("trace", "late", 10.5, 11.0)
        log.add("trace", "late", 10.0, 12.0)     # holds the one before
        log.add("lower", "late", 12.0, 13.0)
        assert log.dropped == 3
        late = [r for r in log.records() if r.fn == "late"]
        assert [(r.phase, r.depth) for r in late] == [
            ("trace", 1), ("trace", 0), ("lower", 0)]
        assert phase_seconds(late) == {"trace": 2.0, "lower": 1.0}

    def test_records_of_a_window_lie_wholly_inside_it(self):
        log = CompileLog()
        for k in range(4):
            log.add("trace", f"f{k}", float(k), k + 0.5)
        assert [r.fn for r in log.records(1.0, 2.5)] == ["f1", "f2"]

    @pytest.mark.parametrize("intervals, want", [
        ([(0.0, 1.0), (2.0, 3.0)], 2.0),                 # apart
        ([(0.0, 3.0), (1.0, 2.0)], 3.0),                 # nested
        ([(0.0, 2.0), (1.0, 3.0)], 3.0),                 # overlapping
        ([(1.0, 2.0), (0.0, 3.0), (0.5, 1.5), (4.0, 4.5)], 3.5),
    ])
    def test_a_phases_seconds_are_the_union_of_its_intervals(self, intervals,
                                                             want):
        records = [CompileRecord("trace", "f", lo, hi, 0, None, None)
                   for lo, hi in intervals]
        assert phase_seconds(records) == {"trace": pytest.approx(want)}


# -- JAX's events, booked to the label in flight -----------------------------

def fresh(label: str, body=None):
    import jax.numpy as jnp

    def fn(x):
        return (jnp.sin(x) * 2.0).sum() if body is None else body(x)

    fn.__name__ = label.replace(".", "_")
    return metered_jit(fn, label=label)


class TestBookedToTheLabel:
    def test_a_first_call_leaves_the_three_phases_and_a_second_none(self):
        import jax.numpy as jnp

        f = fresh("clog.first")
        x = jnp.ones((4, 4))
        assert of("clog.first") == []
        f(x)
        first = of("clog.first")
        outer = [r for r in first if r.depth == 0]
        assert sorted(r.phase for r in outer) == sorted(BUILD_PHASES)
        # in the order a program is built, one after the other
        by_phase = {r.phase: r for r in outer}
        assert (by_phase["trace"].end <= by_phase["lower"].start
                <= by_phase["backend_compile"].start)
        f(x)
        assert of("clog.first") == first

    def test_a_jit_traced_inside_another_books_its_seconds_once(self):
        import jax
        import jax.numpy as jnp

        # the kernels' bodies are such jits
        @jax.jit  # pio-lint: disable=coverage-jit-metering
        def clog_inner(x):
            return jnp.tanh(x) @ x

        f = fresh("clog.outer", body=lambda x: clog_inner(x).sum() + 1.0)
        before = counter("jit_phase_seconds_total", fn="clog.outer",
                         phase="trace")
        f(jnp.ones((4, 4)))
        traces = [r for r in of("clog.outer") if r.phase == "trace"]
        outer = [r for r in traces if r.depth == 0]
        inner = [r for r in traces if r.depth > 0]
        assert len(outer) == 1 and inner, traces
        # the inner jit has no label of its own: it is the outer's call,
        # under the name JAX has for it
        assert "clog_inner" in {r.name for r in inner}
        assert outer[0].name == "clog_outer"
        for r in inner:
            assert outer[0].start <= r.start and r.end <= outer[0].end
            assert outer[0].end - outer[0].start >= r.end - r.start
        whole = outer[0].end - outer[0].start
        assert phase_seconds(traces)["trace"] == pytest.approx(whole)
        # the registry's total is the union too, not the records' sum
        booked = counter("jit_phase_seconds_total", fn="clog.outer",
                         phase="trace") - before
        assert booked == pytest.approx(whole, abs=1e-6)
        assert sum(r.end - r.start for r in traces) > whole

    def test_outside_a_metered_call_jax_names_the_function(self):
        import jax
        import jax.numpy as jnp

        device.listen()

        @jax.jit  # pio-lint: disable=coverage-jit-metering
        def clog_bare(x):
            return x * 3.0

        clog_bare(jnp.ones((3,)))
        phases = {r.phase for r in of("clog_bare")}
        assert phases == set(BUILD_PHASES)

    def test_jaxs_names_do_not_use_up_the_labels_room(self):
        """Every eager op has a name of its own; `metered_jit`'s labels
        share a capped group, which they must not fill."""
        from predictionio_tpu.telemetry.registry import (
            DEFAULT_LABEL_CAP, LABEL_OVERFLOW, capped_label,
            reset_label_caps)

        reset_label_caps("jit_fn")
        reset_label_caps("jit_eager_fn")
        try:
            for k in range(DEFAULT_LABEL_CAP + 8):
                device._on_duration(
                    "/jax/core/compile/jaxpr_trace_duration", 0.001,
                    fun_name=f"clog_eager_{k}")
            assert capped_label("jit_fn", "clog.late") == "clog.late"
            assert len(of(f"clog_eager_{DEFAULT_LABEL_CAP + 7}")) == 1
            assert (f'jit_phase_seconds_total{{fn="{LABEL_OVERFLOW}",'
                    f'phase="trace"}}') in REGISTRY.render()
        finally:
            reset_label_caps("jit_fn")
            reset_label_caps("jit_eager_fn")

    def test_the_wrapper_adds_one_store_and_one_restore_a_dispatch(
            self, monkeypatch):
        """The thread-local's cost is held by count, not by a clock."""
        import jax.numpy as jnp

        class Counting:
            stores, loads = [], 0

            def __setattr__(self, name, value):
                Counting.stores.append((name, value))
                object.__setattr__(self, name, value)

            def __getattr__(self, name):   # only when it was never stored
                Counting.loads += 1
                raise AttributeError(name)

        monkeypatch.setattr(device, "IN_FLIGHT", Counting())
        f = fresh("clog.counted")
        x = jnp.ones((2, 2))
        f(x)
        Counting.stores.clear()
        for _ in range(5):
            f(x)
        assert Counting.stores == [("fn", "clog.counted"), ("fn", None)] * 5

    def test_an_enclosing_calls_label_comes_back(self):
        import jax.numpy as jnp

        inner = fresh("clog.nested_inner")
        outer = fresh("clog.nested_outer", body=lambda x: inner(x) + 1.0)
        outer(jnp.ones((2, 2)))
        assert getattr(device.IN_FLIGHT, "fn", None) is None
        # the outer's lowering and compile came after the inner returned
        assert {r.phase for r in of("clog.nested_outer")} >= {
            "lower", "backend_compile"}

    def test_a_call_that_raises_clears_the_label(self):
        def boom(x):
            raise RuntimeError("in the trace")

        f = fresh("clog.raises", body=boom)
        with pytest.raises(RuntimeError, match="in the trace"):
            f(1.0)
        assert getattr(device.IN_FLIGHT, "fn", None) is None


# -- the three places that show it -------------------------------------------

FIELDS = ("trace_seconds", "lower_seconds", "backend_compile_seconds",
          "cache_load_seconds", "cache_hits", "cache_misses")


class TestWhereItShows:
    def test_the_inventory_carries_the_six_fields_and_its_totals_hold(self):
        import jax.numpy as jnp

        f = fresh("clog.inventory")
        base = JIT_COMPILES.labels(fn="clog.inventory").value
        f(jnp.ones((4,)))
        f(jnp.ones((4,)))
        f(jnp.ones((8,)))       # a second signature: a retrace
        _, payload = device.jit_payload()
        entry = payload["fns"]["clog.inventory"]
        assert entry["compiles_total"] == 2
        assert entry["compiles_total"] == (
            JIT_COMPILES.labels(fn="clog.inventory").value - base)
        assert payload["totals"]["compiles"] == 2
        assert entry["retraces_total"] == 1
        for key in FIELDS:
            assert key in entry, key
            assert entry[key] == pytest.approx(
                sum(s[key] for s in entry["signatures"]), abs=1e-5)
        for sig in entry["signatures"]:
            assert sig["trace_seconds"] > 0 and sig["lower_seconds"] > 0
            assert sig["backend_compile_seconds"] > 0
            # the phases are parts of the wall `compile_seconds` stays
            assert (sig["trace_seconds"] + sig["lower_seconds"]
                    + sig["backend_compile_seconds"]
                    <= sig["compile_seconds"] + 1e-5)
            assert sig["cache_load_seconds"] <= sig["backend_compile_seconds"]

    def test_a_timeline_names_the_phases_inside_the_compile_span(self):
        import jax.numpy as jnp

        f = fresh("clog.timeline")
        tl, token = spans.begin("workflow", "train", "RUN", "clog")
        try:
            f(jnp.ones((4,)))
            f(jnp.ones((4,)))
        finally:
            spans.finish(tl, token, status=None, duration_s=0.0)
        by_name = {s[0]: s for s in tl.spans}
        assert set(by_name) == {"jit.compile.clog.timeline",
                                "jit.trace.clog.timeline",
                                "jit.lower.clog.timeline",
                                "jit.backend.clog.timeline"}
        _, c_start, c_seconds, _, c_nested = by_name[
            "jit.compile.clog.timeline"]
        assert not c_nested
        parts = 0.0
        for short in ("trace", "lower", "backend"):
            _, start, seconds, _, nested = by_name[
                f"jit.{short}.clog.timeline"]
            assert nested   # left out of the timeline's stage sum
            assert c_start - 1e-3 <= start
            assert start + seconds <= c_start + c_seconds + 1e-3
            parts += seconds
        assert parts <= c_seconds + 1e-3
        assert tl.span_sum_s() == pytest.approx(c_seconds)

    def test_a_recompile_inside_a_marked_window_is_returned_by_name(self):
        import jax.numpy as jnp

        warm, leaky = fresh("clog.warm"), fresh("clog.leaky")
        # made before the window: an eager `ones` is a program too, and
        # the log would name it
        x4, x5 = jnp.ones((4,)), jnp.ones((5,))
        warm(x4)
        leaky(x4)
        w0 = time.perf_counter()
        warm(x4)
        leaky(x5)   # a shape the warm-up did not hold
        w1 = time.perf_counter()
        built = [r for r in COMPILE_LOG.records(w0, w1) if r.depth == 0]
        assert {(r.fn, r.phase) for r in built} == {
            ("clog.leaky", p) for p in BUILD_PHASES}
        assert counter("jit_retraces_total", fn="clog.leaky") >= 1


# -- the persistent cache, from two processes --------------------------------

CACHE_SCRIPT = """
import json, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
if {kept!r}:   # small programs persist too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from predictionio_tpu.telemetry import device
from predictionio_tpu.telemetry.registry import REGISTRY
from predictionio_tpu.utils.profiling import metered_jit
f = metered_jit(lambda x: (jnp.cos(x) @ x).sum(), label="clog.cached")
f(jnp.ones((8, 8)))
entry = device.jit_payload()[1]["fns"]["clog.cached"]
mine = [r for r in device.COMPILE_LOG.records() if r.fn == "clog.cached"]
requests = {{line.split(" ")[0]: float(line.split(" ")[1])
            for line in REGISTRY.render().splitlines()
            if line.startswith("jit_cache_requests_total{{")}}
print(json.dumps({{
    "hits": entry["cache_hits"], "misses": entry["cache_misses"],
    "load_seconds": entry["cache_load_seconds"],
    "backend_seconds": entry["backend_compile_seconds"],
    "cache_load_records": [[r.start, r.end, r.depth] for r in mine
                           if r.phase == "cache_load"],
    "backend": [[r.start, r.end, r.cache] for r in mine
                if r.phase == "backend_compile"],
    "built": len([r for r in device.COMPILE_LOG.records()
                  if r.phase == "backend_compile"]),
    "requests": requests}}))
"""


def two_processes_on_one_cache(tmp_path, kept: bool):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    runs = []
    for _ in range(2):
        done = subprocess.run(
            [sys.executable, "-c", CACHE_SCRIPT.format(root=ROOT, kept=kept)],
            env=env, capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        runs.append(json.loads(done.stdout.strip().splitlines()[-1]))
    return runs


def test_a_second_process_on_the_same_cache_directory_loads(tmp_path):
    cold, warm = two_processes_on_one_cache(tmp_path, kept=True)
    assert (cold["hits"], cold["misses"]) == (0, 1)
    assert cold["cache_load_records"] == []
    assert [b[2] for b in cold["backend"]] == ["miss"]
    # on an empty cache every program built is a miss
    assert cold["requests"] == {
        'jit_cache_requests_total{result="miss"}': cold["built"]}
    assert (warm["hits"], warm["misses"]) == (1, 0)
    assert [b[2] for b in warm["backend"]] == ["hit"]
    (lo, hi, depth), = warm["cache_load_records"]
    (b_lo, b_hi, _), = warm["backend"]
    # the load lies inside the backend-compile event that holds it
    assert depth == 1 and b_lo <= lo + 1e-4 and hi <= b_hi
    assert 0 < warm["load_seconds"] <= warm["backend_seconds"]
    assert warm["requests"] == {
        'jit_cache_requests_total{result="hit"}': warm["built"]}


def test_a_compile_too_quick_to_be_kept_is_a_miss_all_the_same(tmp_path):
    """JAX's own miss count leaves out a compile it does not go on to
    store (under a second, by default): a cold cache of quick programs
    would read 0. The log's mark is the backend's compile itself."""
    for run in two_processes_on_one_cache(tmp_path, kept=False):
        assert (run["hits"], run["misses"]) == (0, 1)
        assert [b[2] for b in run["backend"]] == ["miss"]
        assert run["requests"] == {
            'jit_cache_requests_total{result="miss"}': run["built"]}


# -- the process's first seconds ---------------------------------------------

class TestFirstSeconds:
    def test_the_packages_import_is_the_logs_first_span(self):
        """Two stamps in the package's `__init__`, read when the device
        plane is first imported: no finder, no loader, and the root
        package still brings in nothing of the device plane itself."""
        script = (
            "import sys, time; sys.path.insert(0, %r)\n"
            "t0 = time.perf_counter()\n"
            "import predictionio_tpu.utils.compile_cache\n"
            "t1 = time.perf_counter()\n"
            "assert 'predictionio_tpu.telemetry.device' not in sys.modules\n"
            "assert 'jax' not in sys.modules\n"
            "assert not [f for f in sys.meta_path if 'predictionio_tpu' in "
            "str(getattr(f, '__module__', ''))]\n"
            "from predictionio_tpu.telemetry.device import COMPILE_LOG\n"
            "import json\n"
            "print(json.dumps([[r.phase, r.fn, r.depth, "
            "t0 <= r.start and r.end <= t1] "
            "for r in COMPILE_LOG.records()]))\n" % ROOT)
        done = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stderr[-2000:]
        records = json.loads(done.stdout.strip().splitlines()[-1])
        assert records == [["process.import", "predictionio_tpu", 0, True]]

    @pytest.mark.parametrize("how, lines, want", [
        # `pio train`'s entry: the import is timed and the log listens
        ("entry_imports_jax",
         "jax = device.import_jax()\n"
         "assert device.import_jax() is jax\n",
         {"jax_records": 1, "listening": True, "heard": True}),
        # `import jax` can wait on another thread's import lock, and a
        # lock of ours held across it is a deadlock importlib cannot see:
        # the lock is taken with jax in hand, for the flag alone
        ("the_lock_is_not_held_over_the_import",
         "class Lock:\n"
         "    def __enter__(self): assert 'jax' in sys.modules\n"
         "    def __exit__(self, *exc): return False\n"
         "device._listen_lock = Lock()\n"
         "device.listen()\n",
         {"jax_records": 0, "listening": True, "heard": True}),
        # the caller's own `import jax` is not the program's to time
        ("caller_imported_jax",
         "import jax\n"
         "device.import_jax()\n",
         {"jax_records": 0, "listening": True, "heard": True}),
        # a library's caller: the first workflow context listens
        ("a_context_where_jax_is_loaded",
         "import jax\n"
         "from predictionio_tpu.controller.context import WorkflowContext\n"
         "assert not device._listening\n"
         "WorkflowContext()\n",
         {"jax_records": 0, "listening": True, "heard": True}),
        # a storage-only process stays jax-free
        ("a_context_without_jax",
         "from predictionio_tpu.controller.context import WorkflowContext\n"
         "WorkflowContext()\n"
         "assert 'jax' not in sys.modules\n",
         {"jax_records": 0, "listening": False, "heard": False}),
    ])
    def test_where_the_log_starts_to_listen(self, how, lines, want):
        """Before any `metered_jit`: an eager op is a program, and a first
        call pays for the ones that come before its own."""
        script = (
            "import sys; sys.path.insert(0, %r)\n"
            "from predictionio_tpu.telemetry import device\n"
            "assert not device._listening\n"
            "%s"
            "if 'jax' in sys.modules:\n"
            "    import jax.numpy as jnp\n"
            "    x = jnp.ones((3,)) * 2\n"
            "import json\n"
            "records = device.COMPILE_LOG.records()\n"
            "print(json.dumps({'jax_records': sum("
            "(r.phase, r.fn) == ('process.import', 'jax') for r in records),"
            " 'listening': device._listening, 'heard': any("
            "r.phase == 'backend_compile' for r in records)}))\n"
            % (ROOT, lines))
        done = subprocess.run([sys.executable, "-c", script],
                              env=dict(os.environ, JAX_PLATFORMS="cpu"),
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stderr[-2000:]
        assert json.loads(done.stdout.strip().splitlines()[-1]) == want

    def test_the_native_librarys_load_is_a_span_once_a_process(
            self, monkeypatch):
        from predictionio_tpu import native

        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_lib_failed", False)
        lib = native.get_lib()
        native.get_lib()
        loads = [r for r in COMPILE_LOG.records() if r.phase == "native.load"]
        assert len(loads) == 1
        assert loads[0].fn == ("unavailable" if lib is None else
                               loads[0].fn)
        assert lib is None or loads[0].fn.startswith("pio_native_")
