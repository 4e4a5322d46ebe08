"""Failure-path e2e, rank death (moved whole from `test_failure_paths.py`):
a missing rank fails the survivor's bootstrap within the timeout, a rank
dying mid-run fails the survivor's collective; neither hangs."""

import os
import socket
import subprocess
import sys
import time

import pytest

from tests.failure_paths_helpers import (
    MIDRUN_WORKER,
    RANK0_WORKER,
    REPO,
)


@pytest.mark.e2e
class TestRankDeath:
    def test_missing_rank_fails_bootstrap_within_timeout(self, tmp_path):
        """2-process world, rank 1 never shows up: rank 0 must error out
        within PIO_COORDINATOR_TIMEOUT_S, not hang on jax's long default."""
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        worker = tmp_path / "rank0.py"
        worker.write_text(RANK0_WORKER)
        env = dict(os.environ)
        env.pop("PIO_CONF_DIR", None)
        env.update(
            JAX_PLATFORMS="cpu",
            PIO_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            PIO_NUM_PROCESSES="2",
            PIO_PROCESS_ID="0",
            PIO_COORDINATOR_TIMEOUT_S="10",
            PIO_TEST_REPO=str(REPO),
        )
        t0 = time.time()
        proc = subprocess.run([sys.executable, str(worker)], env=env,
                              capture_output=True, text=True, timeout=120)
        elapsed = time.time() - t0
        # the exact exit path varies (the error may also fire from jax's
        # shutdown hook); the contract is: nonzero exit, deadline error
        # surfaced, and bounded time — NOT a hang on jax's long default
        all_out = proc.stdout + proc.stderr
        assert proc.returncode != 0, all_out
        assert ("BOOTSTRAP_FAILED" in proc.stdout
                or "DEADLINE_EXCEEDED" in all_out), all_out
        assert "BOOTSTRAP_OK" not in proc.stdout
        assert elapsed < 60, f"detection took {elapsed:.0f}s"

    def test_rank_death_mid_run_fails_survivor_not_hangs(self, tmp_path):
        """Rank 1 hard-dies after bootstrap; rank 0's next cross-host
        collective must raise (JaxRuntimeError via the gloo transport
        deadline, ~30 s) instead of hanging forever — the failure-
        detection half of the recovery story (re-launch is the operator's
        move, as with a dead Spark executor [U])."""
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        worker = tmp_path / "midrun.py"
        worker.write_text(MIDRUN_WORKER)
        procs = []
        for pid in range(2):
            env = dict(os.environ)
            env.pop("PIO_CONF_DIR", None)
            env.update(
                JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=4",
                PIO_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                PIO_NUM_PROCESSES="2",
                PIO_PROCESS_ID=str(pid),
                PIO_TEST_REPO=str(REPO),
            )
            procs.append(subprocess.Popen(
                [sys.executable, str(worker)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
        try:
            outs = [p.communicate(timeout=180)[0] for p in procs]
        finally:
            # on the hang this test guards against, don't leak live workers
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)
        assert procs[1].returncode == 9  # the injected death
        # detection races between two valid paths: (a) the collective
        # raises JaxRuntimeError (gloo transport deadline) and our handler
        # exits 5, or (b) the coordination-service heartbeat notices the
        # dead peer first and jax's distributed client terminates the
        # survivor itself. Either way: nonzero exit, death named, NO hang.
        assert procs[0].returncode != 0, outs[0]
        assert ("COLLECTIVE_FAILED" in outs[0]
                or "heartbeat timeout" in outs[0]
                or "another task died" in outs[0]), outs[0]
        assert "COLLECTIVE_OK" not in outs[0]
