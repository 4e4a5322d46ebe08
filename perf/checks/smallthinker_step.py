"""`correct` for a cell that trains the encoder as a model that routes
before it mixes (SmallThinker's block: a softmax router on the block's
input, grouped-query attention that is windowed and rotated in three
layers of four and full and unrotated in the fourth, ReLU-gated held
experts, no shared expert, no dense layer, an untied head): what the
first optimizer step of the window's last call produced, at the timed
sizes, against the plain reference (`perf/reference/smallthinker.py`,
float32 at the highest matmul precision) at the same weights and on the
same batch.

As `perf/checks/encoder_step.py`, with one loss and four groups of
gradient blocks:

- the loss of the step, relative (`ce_rel_err`), and the same over the
  rows the window cuts keys from alone, the positions at or past
  `sliding_window_size` in their history (`ce_past_window_rel_err`; 289
  of a call's 131 072 tokens under the cell's shape, all in the first
  step, so the whole loss hardly feels them), from the step's own terms
  a row (`nll_rows`);
- `window_off_share`: how much of what taking the window away does to
  those rows' terms is found in the program's. The check computes the
  reference a second time without the window on the sequences that
  hold such rows; w = its terms less the sound reference's on those
  rows, e = the program's less the sound reference's; the number is
  |<e, w>| / <w, w>: 0 with the window in place, 1 without it. Under
  random weights a far row's softmax lies over thousands of keys, the
  keys the window cuts move a row's term by about as much as the
  program's bfloat16 operands do and with either sign, so the mean
  over 288 rows cannot tell the window from rounding; the projection
  on w can (the rounding is not aligned with w);
- every token's picks in every layer: the share of (token, pick) pairs
  whose expert the other side did not pick for that token;
- for each of the configuration's report blocks the gradient the step
  used (Adam's first moment over 1 - b1), entry by entry: ||g - g_ref||
  / ||g_ref||, the largest of each group: attention's projections (a
  leaf under `.gqa.`: `attn_grad_max_rel_err`), the routers (`w_g`:
  `router_grad_max_rel_err`), a held expert's matrices (`experts_`:
  `expert_grad_max_rel_err`; a token that picks another expert moves
  these two by a whole term) and the rest (norms, head columns,
  embedding columns: `grad_max_rel_err`);
- the sign of the blocks' first Adam update against the reference's
  gradient (a state left unchanged reads 1);
- nothing non-finite in the parameters the call returned.

`"control"` in the specification (`perf/tests/control_smallthinker.py`
writes it; one name, or several with commas between) returns the
numbers of a reference that is wrong on purpose against the sound one,
and prints the program's own beside them: `bfloat16_reference` computes
everything in bfloat16; `sigmoid_scores` puts the picked logits through
a sigmoid and normalises them in place of the softmax over the picked;
`silu_gate` gates the experts with SiLU; `router_after_attention` feeds
the router the normed stream the experts read; `no_window_reference`
takes the window off; `rotate_full` rotates the full layer too. Each
has to come out as not correct. Of several, every one's numbers are
printed with its verdict, and the one that came nearest to passing is
returned: the run is `correct` only if some control was.

The reference runs a sequence at a time and a query block at a time
under `jax.checkpoint`: where it keeps its intermediates, not what it
computes.
"""

from __future__ import annotations

import numpy as np

from perf.checks.encoder_step import _index, _moved_share
from perf.harness import say
from perf.reference import smallthinker as reference

CONTROLS = {"bfloat16_reference": {"dtype": "bfloat16"},
            "sigmoid_scores": {"wrong": ("sigmoid_scores",)},
            "silu_gate": {"wrong": ("silu_gate",)},
            "router_after_attention": {"wrong": ("router_after_attention",)},
            "no_window_reference": {"windowed": False},
            "rotate_full": {"wrong": ("rotate_full",)}}
GROUPS = {".gqa.": "attn_grad_max_rel_err", "w_g": "router_grad_max_rel_err",
          "experts_": "expert_grad_max_rel_err"}


def reference_objective(cfg, blocks, q_block: int, n1: int, switches: dict):
    """`f(picked, params, tokens, seg, pos)` for one sequence: its share
    of the step's loss, with every row's term and every token's picks
    beside it. `picked` holds the report blocks, put into `params`
    before the forward pass, so that the gradient is taken of the blocks
    alone. The weights are an argument: closed over, they would be
    constants of the program."""
    import jax
    import jax.numpy as jnp

    def with_blocks(params, picked):
        tree = jax.tree_util.tree_map(lambda a: a, params)
        for name, path, ix in blocks:
            *parents, last = path.split(".")
            node = tree
            for part in parents:
                node = node[int(part)] if part.isdigit() else node[part]
            key = int(last) if last.isdigit() else last
            node[key] = (node[key].at[_index(ix)].set(picked[name])
                         if ix else picked[name])
        return tree

    def objective(picked, params, tokens, seg, pos):
        with jax.default_matmul_precision("highest"):
            nll, _, routed = reference.nll_rows(
                with_blocks(params, picked), cfg, tokens, seg, pos,
                q_block=q_block, wrap=jax.checkpoint, **switches)
        return jnp.sum(nll) / n1, (nll, jnp.stack([p for _, p in routed]))

    return objective


def _rows(batch, window: int):
    """[B, L] masks: the rows of the batch whose next token lies in
    their own history, and those of them at or past the window in it."""
    _, seg, pos = batch
    l = seg.shape[1]
    ok = ((seg != 0) & (np.roll(seg, -1, axis=1) == seg)
          & (np.arange(l) < l - 1)[None, :])
    return ok, ok & (pos >= window)


def _capped_jit(fn, spec):
    """`jax.jit(fn)`, on a TPU compiled under `hbm_cap_mib` of device
    memory."""
    import jax

    cap = spec.get("hbm_cap_mib")
    capped = cap and jax.devices()[0].platform == "tpu"
    return jax.jit(fn, compiler_options=(
        {"xla_tpu_max_hbm_size_mib": int(cap)} if capped else None))


def _reference_step(cfg, params, batch, blocks, spec, dtype=None,
                    **switches):
    """What the reference gives on the batch, in the shape of
    `_program`, a sequence at a time. With `dtype` the weights are cast
    to it first and everything is computed in it. On a TPU the program
    is compiled under `hbm_cap_mib` of device memory."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.encoder import leaf_of

    tokens, seg, pos = (jnp.asarray(a) for a in batch)
    n1 = max(int(_rows(batch, cfg.sliding_window)[0].sum()), 1)
    grad_fn = _capped_jit(jax.value_and_grad(
        reference_objective(cfg, blocks, int(spec["q_block"]), n1, switches),
        has_aux=True), spec)
    if dtype is not None:
        params = jax.jit(lambda p: jax.tree_util.tree_map(
            lambda a: a.astype(dtype), p))(params)
    picked = {name: leaf_of(params, path, ix) for name, path, ix in blocks}
    grads, rows, picks = None, [], []
    for n in range(tokens.shape[0]):
        (_, (nll, p)), g = grad_fn(picked, params, tokens[n], seg[n], pos[n])
        g = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), g)
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
        rows.append(nll.astype(jnp.float32))
        picks.append(p)
    grads, rows, picks = jax.device_get(
        (grads, jnp.stack(rows), jnp.concatenate(picks, axis=1)))
    grads = {name: np.asarray(g, np.float64) for name, g in grads.items()}
    return {"rows": np.asarray(rows, np.float64), "picks": picks,
            "grads": grads,
            # what Adam's first step does with such a gradient
            "update_sign": {name: -np.sign(g) for name, g in grads.items()}}


def _window_off(cfg, params, batch, spec, rows, far):
    """w [B, L]: what taking the window away does to the reference's
    terms `rows` on the rows `far` (zero elsewhere), from a second
    forward pass of the sequences that hold such rows."""
    import jax
    import jax.numpy as jnp

    def rows_of(params, tokens, seg, pos):
        with jax.default_matmul_precision("highest"):
            return reference.nll_rows(
                params, cfg, tokens, seg, pos, q_block=int(spec["q_block"]),
                wrap=jax.checkpoint, windowed=False)[0]

    forward = _capped_jit(rows_of, spec)
    w = np.zeros(far.shape)
    for n in np.flatnonzero(far.any(axis=1)):
        off = forward(params, *(jnp.asarray(a[n]) for a in batch))
        w[n] = np.where(far[n], np.asarray(off, np.float64) - rows[n], 0.0)
    return w


def _program(report: dict, initial: dict) -> dict:
    """What the timed step reported, in the shape of `_reference_step`;
    `initial` holds the report blocks of the parameters it started from."""
    got = report["metrics"]
    return {"rows": np.asarray(got["nll_rows"], np.float64),
            "picks": np.asarray(got["picks"]),
            "grads": {name: np.asarray(g, np.float64)
                      for name, g in report["grads"].items()},
            "update_sign": {name: np.sign(np.asarray(after) - initial[name])
                            for name, after in report["params"].items()}}


def group_of(path: str) -> str:
    return next((group for part, group in GROUPS.items() if part in path),
                "grad_max_rel_err")


def compare(got: dict, want: dict, blocks, who: str) -> dict:
    """The numbers of `got` against the reference `want`, by name. `want`
    carries `_rows`' masks (`ok`, `far`) and `_window_off`'s `w`."""
    ok, far, w = want["ok"], want["far"], want["w"]

    def ce(side, rows):
        return float(side["rows"][rows].sum()) / max(int(rows.sum()), 1)

    e = np.where(far, got["rows"] - want["rows"], 0.0)
    say(f"{who}: the terms of the rows past the window off by "
        f"{np.sqrt((e * e).sum() / max(int(far.sum()), 1)):.4e} (root mean "
        f"square)")
    out = {"ce_rel_err": abs(ce(got, ok) - ce(want, ok)) / ce(want, ok),
           "ce_past_window_rel_err": abs(ce(got, far) - ce(want, far))
           / max(ce(want, far), 1e-30),
           "window_off_share": abs(float((e * w).sum()))
           / max(float((w * w).sum()), 1e-30),
           "expert_picks_moved_share": _moved_share(got["picks"],
                                                    want["picks"]),
           **{group: 0.0 for group in GROUPS.values()},
           "grad_max_rel_err": 0.0, "update_sign_max_wrong_share": 0.0}
    for name, path, _ in blocks:
        g = want["grads"][name]
        norm = float(np.sqrt((g * g).sum()))
        err = float(np.sqrt(((got["grads"][name] - g) ** 2).sum())) / max(
            norm, 1e-30)
        big = np.abs(g) > 0.1 * np.sqrt((g * g).mean())
        sign = np.asarray(got["update_sign"][name])
        wrong = (float((sign[big] != -np.sign(g[big])).mean())
                 if big.any() else 0.0)
        say(f"{who}: block {name}: gradient off by {err:.3e} of the "
            f"reference's norm {norm:.6e}; first update against the "
            f"reference's sign on {int(big.sum())} entries: {wrong:.3e} "
            f"the other way")
        group = group_of(path)
        out[group] = max(out[group], err)
        out["update_sign_max_wrong_share"] = max(
            out["update_sign_max_wrong_share"], wrong)
    return out


def _controls(cfg, args, want, names: list, limits: dict) -> dict:
    """Each control's numbers against the sound reference `want`,
    printed with its verdict; returned: those of the control that came
    nearest to passing (the smallest of its largest value / limit)."""
    import jax.numpy as jnp

    nearest = None
    for name in names:
        wrong = dict(CONTROLS[name])
        if "dtype" in wrong:
            wrong["dtype"] = jnp.dtype(wrong["dtype"])
        numbers = compare(_reference_step(cfg, *args, **wrong), want,
                          cfg.report_blocks, name.replace("_", " "))
        over = {n: v / limits[n] for n, v in numbers.items()
                if limits.get(n)}
        worst = max(over.values(), default=0.0)
        say(f"control {name}: " + ", ".join(
            f"{n} {v:.4e}" + (" OVER" if over.get(n, 0.0) > 1.0 else "")
            for n, v in numbers.items())
            + (": not correct" if worst > 1.0 else ": CORRECT"))
        if nearest is None or worst < nearest[0]:
            nearest = (worst, numbers)
    return nearest[1]


def run(spec: dict, config: dict, model, seed: int) -> list[dict]:
    """The numbers compared, each `{"name", "value", "limit"}`; a limit
    of None marks a number that is printed and not held."""
    import jax

    from predictionio_tpu.models import encoder

    cfg = encoder.EncoderConfig.from_dict(config)
    report = model.train_report
    vocab = int(np.asarray(model.params["emb"]).shape[0])
    # the program's own init, from the key the call used
    state = jax.jit(lambda k: encoder.init_params(cfg, vocab, k))(
        jax.random.key(int(seed)))
    stats = jax.devices()[0].memory_stats() or {}
    say(f"before the reference the device holds {stats.get('bytes_in_use')} "
        f"B of {stats.get('bytes_limit')}")
    ok, far = _rows(report["batch"], cfg.sliding_window)
    n_far = max(int(far.sum()), 1)
    say(f"the step's batch counts {int(ok.sum())} rows, {int(far.sum())} of "
        f"them at or past position {cfg.sliding_window} of their history")
    args = (state, report["batch"], cfg.report_blocks, spec)
    want = _reference_step(cfg, *args)
    w = _window_off(cfg, state, report["batch"], spec, want["rows"], far)
    say(f"without the window those rows' terms move by "
        f"{np.sqrt((w * w).sum() / n_far):.4e} (root mean square), their "
        f"mean by {w.sum() / n_far:.4e}")
    want.update(ok=ok, far=far, w=w)
    initial = jax.device_get(encoder.report_of(cfg, state))
    numbers = compare(_program(report, initial), want, cfg.report_blocks,
                      "program")
    limits = spec["limits"]
    control = spec.get("control")
    if control:
        say("the program's numbers: " + ", ".join(
            f"{n} {v:.4e}" for n, v in numbers.items()))
        numbers = _controls(cfg, args, want, control.split(","), limits)
    del state
    numbers["nonfinite_entries"] = int(sum(
        (~np.isfinite(leaf)).sum()
        for leaf in jax.tree_util.tree_leaves(model.params)))
    return [{"name": n, "value": v, "limit": limits.get(n)}
            for n, v in numbers.items()]
