"""The chip bring-up contract, as far as a CPU can show it: where the
compile cache goes, `chip_smoke.py`'s phase chain in its small CPU mode,
its refusal to pass where no accelerator is found, and a serving process
that cannot have its device failing its start.

Named to sort last on purpose: the smoke is the longest subprocess chain
in the suite, so a time-limited run loses this file first, not others.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import tempfile

import pytest

from predictionio_tpu.utils import compile_cache

REPO = pathlib.Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


class TestCompileCacheDir:
    def test_env_set_is_used_and_nothing_else_is_set(self):
        env = {"JAX_COMPILATION_CACHE_DIR": "/some/dir", "OTHER": "x"}
        assert compile_cache.configure(env) == "/some/dir"
        assert env == {"JAX_COMPILATION_CACHE_DIR": "/some/dir", "OTHER": "x"}

    def test_unset_goes_under_the_checkout_at_a_fixed_path(self):
        env = {}
        want = str(REPO / ".pio_store" / "jax_cache")
        assert compile_cache.configure(env) == want
        assert env == {"JAX_COMPILATION_CACHE_DIR": want}
        # the path is part of the cache key: same every time, in every
        # process — no tempdir, pid or clock in it
        assert compile_cache.configure({}) == want
        assert not want.startswith(tempfile.gettempdir() + os.sep)
        assert str(os.getpid()) not in want

    def test_console_entry_resolves_it_before_any_verb_runs(self, monkeypatch):
        from predictionio_tpu.tools import console

        # setenv first so monkeypatch restores the variable afterwards
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "x")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert console.main(["version"]) == 0
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == \
            compile_cache.default_dir()


def _run_smoke(*args, cwd=REPO, script=SMOKE):
    # a cache directory of the smoke's own: its retrain phase counts the
    # directory's entries, and the suite's other workers persist theirs
    # into the checkout's while it runs
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(
        REPO / ".pio_store" / "chip_smoke_jax_cache"))
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices: one will do
    return subprocess.run([sys.executable, str(script), *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


class TestChipSmoke:
    def test_small_cpu_mode_runs_the_whole_phase_chain(self):
        proc = _run_smoke("--small")
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
        lines = proc.stdout.strip().splitlines()
        assert json.loads(lines[-1]) == {
            "ok": True,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
        phases = {p["phase"]: p for p in json.loads(lines[-2])["phases"]}
        assert list(phases) == [
            "probe", "data", "app_new", "import", "kernels", "train",
            "retrain", "check_model", "batchpredict", "eventserver",
            "deploy", "queries", "fold"]
        # every phase that would touch the chip said where it ran
        for name in ("kernels", "train", "retrain", "batchpredict", "deploy",
                     "fold"):
            assert phases[name]["device"]["platform"] == "cpu", name
        assert phases["fold"]["foldin_dispatches_on_device"] > 0
        assert phases["batchpredict"]["queries"] > 64  # the device branch

    def test_without_an_accelerator_it_fails_and_prints_no_result(self):
        proc = _run_smoke()
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "needs platform 'tpu'" in proc.stderr

    def test_alone_in_a_directory_it_fails_and_prints_no_result(
            self, tmp_path):
        lone = tmp_path / "chip_smoke.py"
        shutil.copy(SMOKE, lone)
        proc = _run_smoke(cwd=tmp_path, script=lone)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout


def test_a_server_that_cannot_have_its_device_fails_its_start(
        memory_storage, monkeypatch):
    """With the online plane on, the serving process folds on the device.
    Where JAX cannot bring its platform up (the chip belongs to another
    process) the server must not come up — a pool worker then never
    reports ready — instead of dropping the plane and serving on."""
    import jax

    from predictionio_tpu.online import DeviceUnavailable, OnlineConfig
    from predictionio_tpu.workflow.create_server import (
        PredictionServer, ServerConfig,
    )
    from tests.test_experiment import train_variant
    from tests.test_recommendation_template import ingest_ratings

    ingest_ratings(memory_storage)
    train_variant(memory_storage, iters=2)

    def busy(*a, **kw):
        raise RuntimeError("Unable to initialize backend 'tpu': ABORTED")

    monkeypatch.setattr(jax, "devices", busy)
    with pytest.raises(DeviceUnavailable, match="Unable to initialize"):
        PredictionServer(
            ServerConfig(ip="127.0.0.1", port=0, engine_id="rec-test",
                         engine_variant="rec-test"),
            memory_storage, plugins=None, online=OnlineConfig())
