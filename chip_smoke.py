#!/usr/bin/env python3
"""chip_smoke.py — the `pio` lifecycle, once, on the chip.

The quickest proof that the system still starts on the machine builders
have: the four routes users pay for (`pio train`, `pio batchpredict`,
`/queries.json`, the online fold) through their real entry points, at the
full width of the model the north star is about — Recommendation
template, ALS rank 64, factor tables at the `20m` shape of
`quality/datasets.py` (138 500 users x 27 000 items). Widths are never
cut; the ratings count is the scale (`--ratings`, printed with its cut).

Each phase is its own process, one after the other, because a chip
belongs to one process at a time. This parent never imports jax: it
drives children, reads what they print, and holds their results against
the plain numpy references in the repo (`ops/ranking.py`'s host scorer,
`quality/mllib_als.py`'s normal-equation solve) with a tolerance, never
bitwise.

    probe -> data -> app new -> import -> kernels -> train -> retrain
          -> batchpredict -> eventserver + deploy (PIO_ONLINE=1) -> queries
          -> fold

Every phase that is meant to touch the chip must say so itself
(`platform=... device_kind=... devices=N` in its own output); a phase
that fails, or lands anywhere but on the expected platform, ends the run
with a non-zero exit code and no result line. Where JAX finds no
accelerator the run fails at the probe. `--small` is the explicit CPU
mode (tiny tables, Pallas in interpret mode) that tier-1 uses to prove
the phase chain; its result line names the CPU and is not a chip pass.

The last line of standard output is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
PIO = os.path.join(ROOT, "bin", "pio")
WORK = os.path.join(ROOT, ".pio_store", "chip_smoke")

ENGINE_ID = "smoke"
APP = "SmokeApp"
REG = 0.05  # bench.py's rank-64 lambda
TOP_K = 10
# the whole run must end inside the driver's 1200 s, compilation included
BUDGET_S = 1150.0

# [rows, rank] of the solver batches checked against numpy: the largest
# rank-64 bucket of a 20M train, the quickstart bucket, and the rank-128
# (Schur) batch the four-chip configuration solves
KERNEL_SHAPES = ((12664, 64), (943, 10), (4096, 128))
KERNEL_SHAPES_SMALL = ((40, 8),)
INTERPRET_ROWS = 192  # interpret mode re-solves this many rows of each batch

# Tolerances, fixed beforehand from the arithmetic each result went
# through (relative to the largest entry of the reference row).
# - solver alone: same f32 A, b into the Pallas kernel, XLA's Cholesky and
#   numpy's f64 solve; the systems' condition numbers stay under 1e3, so
#   f32 round-off (2^-24 per operation, amplified by the condition number
#   over a rank-long elimination) stays far below 1e-3.
#   The same bound holds the compiled kernel to its interpreted self.
SOLVER_RTOL = 1e-3
# - rows of a trained or folded model: f32 Gram/RHS sums (HIGHEST matmul
#   precision, `ops/als.normal_eq_einsum`) ahead of that solver, against
#   the f64 reference; the sums add round-off that grows with the row's
#   rating count. Measured on v5e: 1.4e-4 worst over all 27 000 item rows
#   at 2M ratings, 5.2e-5 at 20M (PERF.md, PR 21).
MODEL_ROW_RTOL = 1e-3
# - a device score (`ops/ranking.py`, batch-predict) is a rank-long dot
#   product at the backend's default matmul precision, which on a TPU
#   rounds both f32 operands to bf16 (2^-9 relative each):
#   |error| <= 2^-8 * |u| * |v| (Cauchy-Schwarz over the per-term bound)
SCORE_EPS = 2.0 ** -8

DEVICE_RE = re.compile(r"platform=(\w+) device_kind='([^']*)' devices=(\d+)")


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# -- children that are part of this file ---------------------------------------


def child_probe() -> int:
    import jax

    d = jax.devices()
    print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                      "count": len(d)}))
    return 0


def _systems(rows: int, k: int, seed: int):
    """SPD systems shaped like one ALS bucket: A = sum_c y y^T + reg*n*I
    over n ratings of correlated rank-k factors, b = sum_c r y; the last 8
    rows are all-zero padding systems. f64."""
    import numpy as np

    rng = np.random.default_rng(seed)
    cap = 2 * k
    n = np.exp(rng.uniform(0.0, np.log(cap), rows)).astype(np.int64)
    n[-8:] = 0
    a = np.empty((rows, k, k))
    b = np.empty((rows, k))
    for s in range(0, rows, 2048):
        e = min(rows, s + 2048)
        # a shared direction on top of noise, as trained factors have
        y = (0.25 + rng.standard_normal((e - s, cap, k)) / np.sqrt(k))
        y *= (np.arange(cap)[None, :] < n[s:e, None])[..., None]
        r = rng.integers(1, 11, (e - s, cap)) / 2.0
        a[s:e] = y.transpose(0, 2, 1) @ y
        b[s:e] = (r[:, None, :] @ y)[:, 0]
    a += (REG * n)[:, None, None] * np.eye(k)
    return a, b, n > 0


def child_kernels(small: bool) -> int:
    """Compiled Pallas solve vs interpret mode vs numpy, once, at the real
    bucket shapes. A compiled solve that leaves f32 round-off is a
    failure of the run, not a note."""
    import jax
    import numpy as np

    from predictionio_tpu.ops import pallas_solve
    from predictionio_tpu.parallel.mesh import describe_devices

    print(describe_devices(jax.devices()), flush=True)

    def chol(a, b):
        c = jax.numpy.linalg.cholesky(a)
        y = jax.lax.linalg.triangular_solve(c, b[..., None], left_side=True,
                                            lower=True)
        return jax.lax.linalg.triangular_solve(
            c, y, left_side=True, lower=True, transpose_a=True)[..., 0]

    def rel(x, ref):
        scale = np.maximum(np.abs(ref).max(axis=1), 1e-30)
        return float((np.abs(x - ref).max(axis=1) / scale).max())

    compiled = jax.jit(pallas_solve.gj_solve)
    interpreted = jax.jit(
        functools.partial(pallas_solve.gj_solve, interpret=True))
    ok = True
    out = []
    for rows, k in (KERNEL_SHAPES_SMALL if small else KERNEL_SHAPES):
        a64, b64, live = _systems(rows, k, seed=k)
        ref = np.linalg.solve(a64[live], b64[live][..., None])[..., 0]
        a32, b32 = a64.astype(np.float32), b64.astype(np.float32)
        n_i = min(rows, INTERPRET_ROWS)
        x_i = np.asarray(interpreted(a32[:n_i], b32[:n_i]))
        rec = {"rows": rows, "rank": k,
               "cond_max": float(np.linalg.cond(a64[live]).max()),
               "chol_vs_f64": rel(np.asarray(jax.jit(chol)(a32, b32))[live],
                                  ref),
               "interpret_vs_f64": rel(x_i[live[:n_i]], ref[:live[:n_i].sum()])}
        bad = rec["interpret_vs_f64"] > SOLVER_RTOL
        if not small:
            t0 = time.perf_counter()
            x_c = np.asarray(compiled(a32, b32))
            rec["compiled_first_call_wall_s"] = round(
                time.perf_counter() - t0, 3)
            rec["compiled_vs_f64"] = rel(x_c[live], ref)
            rec["compiled_vs_interpret"] = rel(x_c[:n_i][live[:n_i]],
                                               x_i[live[:n_i]])
            rec["padding_rows_zero"] = bool((x_c[~live] == 0).all())
            bad = (bad or rec["compiled_vs_f64"] > SOLVER_RTOL
                   or rec["compiled_vs_interpret"] > SOLVER_RTOL
                   or not rec["padding_rows_zero"]
                   or not np.isfinite(x_c).all())
        rec["ok"] = not bad
        ok = ok and not bad
        out.append(rec)
    print(json.dumps({"kernels": out}))
    return 0 if ok else 1


# -- the parent ------------------------------------------------------------------


def make_ratings(n_users: int, n_items: int, n_ratings: int, seed: int):
    """(user, item, rating) triplets from the seed, unique pairs, every
    user and every item rated at least once. Returns the three arrays and
    how many ratings the cover added."""
    import numpy as np

    from predictionio_tpu.quality import datasets

    split = datasets.synth_explicit((n_users, n_items, n_ratings), seed=seed,
                                    test_frac=0.0)
    u, i, r = split.train_u, split.train_i, split.train_r
    # a cut count leaves some rows without a rating; one each keeps both
    # table heights exact (no pair can repeat: the row had none)
    rng = np.random.default_rng(seed + 1)
    miss_u = np.setdiff1d(np.arange(n_users, dtype=np.int32), u)
    u = np.concatenate([u, miss_u])
    i = np.concatenate(
        [i, rng.integers(0, n_items, len(miss_u), dtype=np.int32)])
    miss_i = np.setdiff1d(np.arange(n_items, dtype=np.int32), i)
    i = np.concatenate([i, miss_i])
    u = np.concatenate(
        [u, rng.integers(0, n_users, len(miss_i), dtype=np.int32)])
    n_cover = len(miss_u) + len(miss_i)
    r = np.concatenate(
        [r, rng.integers(1, 11, n_cover).astype(np.float32) / 2.0])
    return u, i, r, n_cover


class Smoke:
    def __init__(self, args):
        self.small = args.small
        self.expect = "cpu" if args.small else "tpu"
        self.seed = args.seed
        self.t0 = time.monotonic()
        from predictionio_tpu.quality import datasets

        if args.small:
            self.n_users, self.n_items, self.full_ratings = 160, 64, 1200
            self.rank, self.iterations, self.n_queries = 8, 2, 72
        else:
            self.n_users, self.n_items, self.full_ratings = \
                datasets.SCALES["20m"]
            self.rank, self.iterations, self.n_queries = 64, 3, 256
        self.n_ratings = min(args.ratings or self.full_ratings,
                             self.full_ratings)
        self.logs = os.path.abspath(args.logs or os.path.join(WORK, "logs"))
        self.servers: list = []
        self.phases: list = []
        self.device = None

        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK)
        os.makedirs(self.logs, exist_ok=True)
        from predictionio_tpu.utils import compile_cache

        # All storage, the bucket cache and the native build under WORK:
        # nothing leaks in from ~/.pio_tpu or a local conf/pio-env.sh.
        # This process reads the store too, so it is its own environment
        # that changes; children inherit it.
        env = os.environ
        for key in [k for k in env if k.startswith("PIO_")]:
            del env[key]
        env["PIO_FS_BASEDIR"] = WORK
        env["PIO_CONF_DIR"] = os.path.join(WORK, "conf")
        env["PIO_PYTHON"] = sys.executable
        env["PIO_LOG_LEVEL"] = "INFO"
        # JAX names every program it had to compile and every one too
        # quick to persist: the retrain phase reads these lines
        env["JAX_EXPLAIN_CACHE_MISSES"] = "1"
        if args.small:
            env["JAX_PLATFORMS"] = "cpu"
        self.cache_dir = compile_cache.configure()

    # -- plumbing ----------------------------------------------------------------

    def remaining(self) -> float:
        return BUDGET_S - (time.monotonic() - self.t0)

    def run(self, name: str, argv: list, timeout: float = 600.0,
            env: dict | None = None) -> tuple[str, str]:
        """One child, to the end. Returns (stdout, stderr); raises on a
        non-zero exit or a timeout."""
        out_p = os.path.join(self.logs, name + ".out")
        err_p = os.path.join(self.logs, name + ".err")
        t = time.monotonic()
        with open(out_p, "w") as out, open(err_p, "w") as err:
            try:
                rc = subprocess.run(
                    argv, stdout=out, stderr=err, cwd=WORK,
                    env={**os.environ, **(env or {})},
                    timeout=max(5.0, min(timeout, self.remaining()))
                ).returncode
            except subprocess.TimeoutExpired:
                rc = "timeout"
        with open(out_p) as f:
            out_s = f.read()
        with open(err_p) as f:
            err_s = f.read()
        self.clock(name, t)
        if rc != 0:
            raise SmokeFailure(
                f"{name}: exit {rc}\n--- stdout ---\n{out_s[-2000:]}"
                f"\n--- stderr ---\n{err_s[-4000:]}")
        return out_s, err_s

    def clock(self, phase: str, started: float) -> None:
        self._last = {"phase": phase,
                      "wall_s": round(time.monotonic() - started, 2)}

    def done(self, chip: bool, device=None, **info) -> None:
        """Record the phase `clock` last timed."""
        rec = dict(self._last, chip=chip, **info)
        if device is not None:
            rec["device"] = device
        self.phases.append(rec)
        say(json.dumps(rec))

    def device_of(self, name: str, text: str) -> dict:
        """The device a phase says it ran on; anything but the expected
        platform fails the run."""
        m = DEVICE_RE.search(text)
        if m is None:
            raise SmokeFailure(f"{name}: no device report in its output")
        dev = {"platform": m.group(1), "kind": m.group(2),
               "count": int(m.group(3))}
        if dev["platform"] != self.expect:
            raise SmokeFailure(
                f"{name}: ran on {dev}, expected platform {self.expect!r}")
        return dev

    def start_server(self, name: str, argv: list, pattern: str,
                     env: dict | None = None, timeout: float = 180.0):
        out_p = os.path.join(self.logs, name + ".out")
        err_p = os.path.join(self.logs, name + ".err")
        t = time.monotonic()
        with open(out_p, "w") as out, open(err_p, "w") as err:
            proc = subprocess.Popen(argv, stdout=out, stderr=err, cwd=WORK,
                                    env={**os.environ, **(env or {})},
                                    start_new_session=True)
        self.servers.append((name, proc))
        deadline = time.monotonic() + min(timeout, self.remaining())
        while time.monotonic() < deadline:
            with open(out_p) as f:
                m = re.search(pattern, f.read())
            if m:
                self.clock(name, t)
                return proc, int(m.group(1))
            if proc.poll() is not None:
                break
            time.sleep(0.2)
        with open(err_p) as f:
            raise SmokeFailure(f"{name}: did not come up (exit "
                               f"{proc.poll()})\n{f.read()[-4000:]}")

    def stop_servers(self) -> None:
        for name, proc in self.servers:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for name, proc in self.servers:
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait(timeout=10)
        self.servers.clear()

    def log_text(self, name: str) -> str:
        with open(os.path.join(self.logs, name + ".err")) as f:
            return f.read()

    @staticmethod
    def http(method: str, url: str, body=None, timeout: float = 30.0):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            url, data=data, method=method,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, resp.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    # -- phases ------------------------------------------------------------------

    def probe(self) -> None:
        out, _ = self.run("probe", [sys.executable, __file__, "--child",
                                    "probe"], timeout=120)
        self.device = json.loads(out.strip().splitlines()[-1])
        if self.device["platform"] != self.expect:
            raise SmokeFailure(
                f"JAX found {self.device}; this run needs platform "
                f"{self.expect!r} (the CPU mode is --small)")
        self.done(chip=not self.small, device=self.device)

    def data(self) -> None:
        """Ratings from the seed (`datasets.synth_explicit`), written as
        the JSON-lines file `pio import` reads."""
        t = time.monotonic()
        u, i, r, n_cover = make_ratings(self.n_users, self.n_items,
                                        self.n_ratings, self.seed)
        self.u, self.i, self.r = u, i, r
        self.events_path = os.path.join(WORK, "events.jsonl")
        with open(self.events_path, "w") as f:
            for s in range(0, len(u), 500_000):
                e = s + 500_000
                f.write("".join(
                    '{"event":"rate","entityType":"user","entityId":"u%d",'
                    '"targetEntityType":"item","targetEntityId":"i%d",'
                    '"properties":{"rating":%s}}\n' % row
                    for row in zip(u[s:e].tolist(), i[s:e].tolist(),
                                   r[s:e].tolist())))
        self.engine_json = os.path.join(WORK, "engine.json")
        with open(self.engine_json, "w") as f:
            json.dump({
                "id": ENGINE_ID,
                "engineFactory": "predictionio_tpu.templates.recommendation."
                                 "RecommendationEngine",
                "datasource": {"params": {"appName": APP}},
                "algorithms": [{"name": "als", "params": {
                    "rank": self.rank, "numIterations": self.iterations,
                    "lambda": REG, "seed": self.seed + 1}}],
            }, f)
        self.clock("data", t)
        self.done(chip=False, users=self.n_users, items=self.n_items,
                  ratings=int(len(r)), rank=self.rank,
                  ratings_cut_from=self.full_ratings,
                  cover_ratings_added=n_cover)

    def app_and_import(self) -> None:
        out, _ = self.run("app_new", [PIO, "app", "new", APP], timeout=120)
        self.access_key = re.search(r"Access Key: (\S+)", out).group(1)
        self.done(chip=False)
        out, err = self.run("import", [PIO, "import", "--appname", APP,
                                       "--input", self.events_path],
                            timeout=900)
        n = int(re.search(r"Imported (\d+) events", out).group(1))
        if n != len(self.r) or "skipped" in out:
            raise SmokeFailure(f"import: {out.strip()!r}, wrote {len(self.r)}")
        path = re.search(r"import: served by the (\w+) path", err)
        self.done(chip=False, events=n,
                  import_path=path.group(1) if path else "unknown")

    def kernels(self) -> None:
        out, _ = self.run(
            "kernels", [sys.executable, __file__, "--child", "kernels"]
            + (["--small"] if self.small else []), timeout=600)
        dev = self.device_of("kernels", out)
        self.done(chip=not self.small, device=dev,
                  **json.loads(out.strip().splitlines()[-1]))

    def train(self, name: str = "train", first_err: str | None = None) -> str:
        """`pio train`. With `first_err` (the first train's log) this is the
        retrain: a fresh process against the same cache directory must
        compile none of the programs the first persisted — whatever it
        still compiles is a program JAX calls too quick to persist."""
        entries = len(os.listdir(self.cache_dir))
        _, err = self.run(name, [PIO, "train", "--engine-json",
                                 self.engine_json], timeout=900)
        dev = self.device_of(name, err)
        solver = re.search(r"solver='auto' resolved to '(\w+)'", err)
        solver = solver.group(1) if solver else None
        if not self.small and solver != "gj":
            raise SmokeFailure(f"{name}: solver='auto' resolved to "
                               f"{solver!r}, not the Pallas 'gj'")
        compiled = re.search(
            r"als\.train_steps compiled \(.*?, ([\d.]+)s\)", err)
        miss_re = r"PERSISTENT COMPILATION CACHE MISS"
        quick_re = (r"Not writing persistent cache entry for \S+ because it "
                    r"took <")
        miss = len(re.findall(miss_re, err))
        quick = len(re.findall(quick_re, err))
        grown = len(os.listdir(self.cache_dir)) - entries
        self.done(chip=not self.small, device=dev, solver=solver,
                  train_steps_first_call_wall_s=(
                      float(compiled.group(1)) if compiled else None),
                  programs_compiled=miss, of_them_too_quick_to_persist=quick,
                  cache_entries_added=grown)
        if first_err is not None and (miss != quick or grown):
            raise SmokeFailure(
                f"{name}: recompiled {miss - quick} program(s) the first "
                f"train should have persisted ({grown} new cache entries)")
        return err

    def load_model(self):
        """The trained model as `pio deploy` would load it, in this
        process (numpy only), plus the generator-id -> table-row maps."""
        import numpy as np

        from predictionio_tpu.storage.registry import Storage
        from predictionio_tpu.workflow.create_server import (
            ServerConfig, load_served_state,
        )

        Storage.reset(None)
        state = load_served_state(Storage.get(), ServerConfig(
            engine_id=ENGINE_ID, engine_variant=ENGINE_ID))
        Storage.get().close()
        Storage.reset(None)
        model = state.models[0]
        self.urow = np.asarray([model.user_ids[f"u{g}"]
                                for g in range(self.n_users)], np.int64)
        self.irow = np.asarray([model.item_ids[f"i{g}"]
                                for g in range(self.n_items)], np.int64)
        return model

    def check_model(self) -> None:
        """Shapes, finiteness, and a spread of item rows re-solved with
        the numpy normal-equation reference against the final user
        factors (the last half-epoch solves items from users)."""
        import numpy as np

        from predictionio_tpu.quality import mllib_als

        t = time.monotonic()
        self.model = m = self.load_model()
        want = ((self.n_users, self.rank), (self.n_items, self.rank))
        got = (m.user_factors.shape, m.item_factors.shape)
        if got != want:
            raise SmokeFailure(f"train: factor tables {got}, want {want}")
        if not (np.isfinite(m.user_factors).all()
                and np.isfinite(m.item_factors).all()):
            raise SmokeFailure("train: non-finite factors")
        du, di = self.urow[self.u], self.irow[self.i]
        order = np.argsort(np.bincount(di, minlength=self.n_items))
        picks = order[np.linspace(0, self.n_items - 1, 24).astype(int)]
        worst = 0.0
        for j in picks:
            sel = di == j
            ref = mllib_als.solve_one_row(m.user_factors, du[sel],
                                          self.r[sel], REG)
            worst = max(worst, float(np.abs(m.item_factors[j] - ref).max()
                                     / np.abs(ref).max()))
        if worst > MODEL_ROW_RTOL:
            raise SmokeFailure(f"train: solved item rows differ from the "
                               f"numpy reference by {worst:.3g} (relative), "
                               f"tolerance {MODEL_ROW_RTOL}")
        self.clock("check_model", t)
        self.done(chip=False, rows_checked=len(picks),
                  solved_rows_max_rel_err=worst, tolerance=MODEL_ROW_RTOL)

    def check_topk(self, name: str, model, user_row: int, got: list,
                   tol_scale: float) -> float:
        """One answer against numpy: every returned item unseen, its score
        within tolerance of the numpy score, and no better item left out
        by more than the tolerance. Returns the largest score error."""
        import numpy as np

        scores = model.user_factors[user_row] @ model.item_factors.T
        seen = model.seen.get(int(user_row)) if model.seen else None
        if seen is not None:
            scores[seen] = -np.inf
        k = min(TOP_K, int(np.isfinite(scores).sum()))
        if len(got) != k:
            raise SmokeFailure(f"{name}: {len(got)} items, want {k}")
        kth = np.sort(scores)[-k]
        tol = tol_scale * float(
            np.linalg.norm(model.user_factors[user_row])
            * np.linalg.norm(model.item_factors, axis=1).max()) + 1e-5
        worst = 0.0
        for rec in got:
            true = float(scores[model.item_ids[rec["item"]]])
            err = abs(rec["score"] - true)
            worst = max(worst, err)
            if not np.isfinite(true) or err > tol or true < kth - tol:
                raise SmokeFailure(
                    f"{name}: item {rec['item']} score {rec['score']:.6f}, "
                    f"numpy {true:.6f}, k-th best {kth:.6f}, tol {tol:.3g}")
        served = [rec["score"] for rec in got]
        if served != sorted(served, reverse=True):
            raise SmokeFailure(f"{name}: answer not sorted by score")
        return worst

    def batchpredict(self) -> None:
        import numpy as np

        rng = np.random.default_rng(self.seed + 2)
        self.query_users = rng.choice(self.n_users, self.n_queries,
                                      replace=False)
        q_path = os.path.join(WORK, "queries.jsonl")
        o_path = os.path.join(WORK, "predictions.jsonl")
        with open(q_path, "w") as f:
            for g in self.query_users:
                f.write(json.dumps({"user": f"u{g}", "num": TOP_K}) + "\n")
        _, err = self.run("batchpredict", [
            PIO, "batchpredict", "--input", q_path, "--output", o_path,
            "--engine-id", ENGINE_ID, "--engine-variant", ENGINE_ID],
            timeout=600)
        dev = self.device_of("batchpredict", err)
        with open(o_path) as f:
            rows = [json.loads(line) for line in f]
        if len(rows) != self.n_queries:
            raise SmokeFailure(f"batchpredict: {len(rows)} answers for "
                               f"{self.n_queries} queries")
        # the device scorer multiplies at the backend's default precision
        scale = SCORE_EPS if self.expect == "tpu" else 1e-5
        worst = max(
            self.check_topk("batchpredict", self.model, self.urow[g],
                            row["prediction"]["itemScores"], scale)
            for g, row in zip(self.query_users, rows))
        self.done(chip=not self.small, device=dev, queries=len(rows),
                  score_max_abs_err=worst)

    def serve_and_fold(self) -> None:
        import numpy as np

        from predictionio_tpu.quality import mllib_als

        _, es_port = self.start_server(
            "eventserver", [PIO, "eventserver", "--ip", "127.0.0.1",
                            "--port", "0"], r"listening on [\d.]+:(\d+)")
        self.done(chip=False)
        _, port = self.start_server(
            "deploy", [PIO, "deploy", "--ip", "127.0.0.1", "--port", "0",
                       "--engine-json", self.engine_json],
            r"deployed on [\d.]+:(\d+)", env={"PIO_ONLINE": "1"},
            timeout=300)
        base = f"http://127.0.0.1:{port}"
        status = json.loads(self.http("GET", base + "/")[1])
        if "online" not in status:
            raise SmokeFailure("deploy: the online plane did not start "
                               "(the server dropped it and carried on):\n"
                               + self.log_text("deploy")[-3000:])
        # the server holds the chip from here on: the plane claims its
        # backend at start and says which one it got
        code, body = self.http("GET", base + "/debug/profile/device.json")
        if code != 200:
            raise SmokeFailure(f"deploy: device.json {code}: {body[:300]}")
        payload = json.loads(body)
        dev = {"platform": payload["backend"], "kind": payload["device_kind"],
               "count": len(payload["devices"])}
        if dev["platform"] != self.expect:
            raise SmokeFailure(f"deploy: serving process is on {dev}")
        self.done(chip=not self.small, device=dev)

        # -- /queries.json: host numpy by design (ops/ranking.py), so this
        # phase proves the route, not the chip
        t = time.monotonic()
        m = self.model
        for g in self.query_users[:8]:
            code, body = self.http("POST", base + "/queries.json",
                                   {"user": f"u{g}", "num": TOP_K})
            if code != 200:
                raise SmokeFailure(f"queries: {code} {body[:300]}")
            self.check_topk("queries", m, self.urow[g],
                            json.loads(body)["itemScores"], 1e-6)
        self.clock("queries", t)
        self.done(chip=False, requests=8,
                  note="ALS /queries.json scores on the host by policy")

        # -- the fold: fresh ratings from a known and from a new user
        t = time.monotonic()
        du, di = self.urow[self.u], self.irow[self.i]
        rng = np.random.default_rng(self.seed + 3)
        counts = np.bincount(self.i, minlength=self.n_items)
        mid = np.argsort(counts)[self.n_items // 4: 3 * self.n_items // 4]
        known = int(self.query_users[0])
        fresh_items = rng.choice(
            np.setdiff1d(mid, self.i[self.u == known]), 8, replace=False)
        posts = ([(f"u{known}", int(j), 4.5) for j in fresh_items[:3]]
                 + [("unew", int(j), float(rng.integers(6, 11)) / 2.0)
                    for j in fresh_items])
        # one batch request is one transaction: the tailer sees all of it
        # in one poll, so the fold is one batch and has one right answer
        code, body = self.http(
            "POST", f"http://127.0.0.1:{es_port}/batch/events.json"
            f"?accessKey={self.access_key}",
            [{"event": "rate", "entityType": "user", "entityId": user,
              "targetEntityType": "item", "targetEntityId": f"i{j}",
              "properties": {"rating": rating}} for user, j, rating in posts])
        if code != 200 or any(r.get("status") != 201
                              for r in json.loads(body)):
            raise SmokeFailure(f"fold: batch POST {code} {body[:400]}")
        deadline = time.monotonic() + min(420.0, self.remaining())
        folded = 0
        while time.monotonic() < deadline and folded < len(posts):
            time.sleep(0.5)
            folded = json.loads(self.http("GET", base + "/")[1])[
                "online"]["eventsFolded"]
        if folded < len(posts):
            raise SmokeFailure(
                f"fold: {folded}/{len(posts)} posted events became servable"
                f"\n{self.log_text('deploy')[-4000:]}")
        self.clock("fold", t)  # posted -> servable, first-fold compile included

        # the same fold in numpy (online/foldin.py's order: users against
        # the served item factors, then the dirty items against the
        # updated users)
        uf = np.concatenate([m.user_factors,
                             np.zeros((1, self.rank), np.float32)])
        itf = m.item_factors.copy()
        new_row = self.n_users
        k_row = int(self.urow[known])
        hist = {k_row: (list(di[du == k_row]), list(self.r[du == k_row])),
                new_row: ([], [])}
        for user, j, rating in posts:
            cols, vals = hist[k_row if user != "unew" else new_row]
            cols.append(int(self.irow[j]))
            vals.append(rating)
        for row, (cols, vals) in hist.items():
            uf[row] = mllib_als.solve_one_row(
                itf, np.asarray(cols), np.asarray(vals, np.float32), REG)
        for j in fresh_items:
            jr = int(self.irow[j])
            cols = list(du[di == jr])
            vals = list(self.r[di == jr])
            for user, pj, rating in posts:
                if pj == j:
                    cols.append(k_row if user != "unew" else new_row)
                    vals.append(rating)
            itf[jr] = mllib_als.solve_one_row(
                uf, np.asarray(cols), np.asarray(vals, np.float32), REG)

        class Folded:  # what check_topk reads
            user_factors, item_factors, item_ids = uf, itf, m.item_ids
            seen = {r: np.asarray(c) for r, (c, _) in hist.items()}

        worst = 0.0
        for user, row in ((f"u{known}", k_row), ("unew", new_row)):
            code, body = self.http("POST", base + "/queries.json",
                                   {"user": user, "num": TOP_K})
            got = json.loads(body).get("itemScores") if code == 200 else None
            if not got:
                raise SmokeFailure(f"fold: {user} not servable: {code} "
                                   f"{body[:300]}")
            worst = max(worst, self.check_topk(
                f"fold[{user}]", Folded, row, got, MODEL_ROW_RTOL))

        # DeviceClock books wall time under device="cpu" whenever anything
        # goes wrong; every later per-layer number reads this label
        metrics = self.http("GET", base + "/metrics")[1]
        booked = sum(
            float(line.rsplit(" ", 1)[1]) for line in metrics.splitlines()
            if line.startswith("device_dispatches_total{")
            and 'fn="foldin.solve"' in line
            and f'device="{self.expect}"' in line)
        if booked <= 0:
            raise SmokeFailure(
                'fold: no device_dispatches_total{fn="foldin.solve", '
                f'device="{self.expect}"}} on /metrics:\n' + "\n".join(
                    ln for ln in metrics.splitlines()
                    if ln.startswith("device_dispatches_total")))
        self.done(chip=not self.small, device=dev, events=len(posts),
                  score_max_abs_err=worst, foldin_dispatches_on_device=booked)
        self.stop_servers()

    def main(self) -> dict:
        os.makedirs(self.cache_dir, exist_ok=True)
        say(f"work dir {WORK}; logs {self.logs}; compile cache "
            f"{self.cache_dir} ({len(os.listdir(self.cache_dir))} entries)")
        try:
            self.probe()
            self.data()
            self.app_and_import()
            self.kernels()
            # both trains before anything is posted: a new event changes
            # the bucket shapes, and with them the program
            self.train("retrain", first_err=self.train())
            self.check_model()
            self.batchpredict()
            self.serve_and_fold()
        finally:
            self.stop_servers()
        if "jax" in sys.modules:
            raise SmokeFailure("the smoke's parent process imported jax")
        return {"phases": self.phases,
                "wall_s": round(time.monotonic() - self.t0, 1),
                "ratings": int(len(self.r)),
                "ratings_cut_from": self.full_ratings}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ratings", type=int, default=2_000_000,
                    help="ratings to generate (the scale; 20M is the uncut "
                         "`20m` count, 2M the floor)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small", action="store_true",
                    help="explicit CPU mode: tiny tables, Pallas interpreted")
    ap.add_argument("--logs", default=None,
                    help="directory for the children's output "
                         "(default: inside the work dir)")
    ap.add_argument("--keep", action="store_true",
                    help="leave the work dir in place")
    ap.add_argument("--child", choices=("probe", "kernels"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child == "probe":
        return child_probe()
    if args.child == "kernels":
        return child_kernels(args.small)
    if not os.path.exists(PIO):
        print(f"chip_smoke: {PIO} not found — this script drives the "
              "repository it sits in", file=sys.stderr)
        return 2
    smoke = Smoke(args)
    try:
        summary = smoke.main()
    except SmokeFailure as e:
        print(f"[smoke] FAILED after {time.monotonic() - smoke.t0:.0f}s: {e}",
              file=sys.stderr)
        return 1
    finally:
        if not args.keep:
            shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": smoke.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
