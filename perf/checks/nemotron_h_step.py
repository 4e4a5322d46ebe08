"""`correct` for a cell that trains the encoder as a model whose layers
are one sublayer each (Nemotron-H's pattern: a Mamba-2 layer with
several B/C groups under a gated norm a group, an expert feed-forward of
ungated squared-ReLU experts beside a wider shared one behind a scaled
sigmoid router with a bias that picks, or attention without positions,
each alone under one norm and one residual; an untied head): what the
first optimizer step of the window's last call produced, at the timed
sizes, against the plain reference (`perf/reference/nemotron_h.py`,
float32 at the highest matmul precision, the recurrence a token at a
time, groups and all) at the same weights and on the same batch.

As `perf/checks/ssd_step.py` and `perf/checks/smallthinker_step.py`:

- the loss of the step, relative;
- every token's picks in every expert layer: the share of (token, pick)
  pairs whose expert the other side did not pick for that token;
- for each of the configuration's report blocks the gradient the step
  used (Adam's first moment over 1 - b1), entry by entry: ||g - g_ref||
  / ||g_ref||, the largest of each group: the Mamba-2 layers' blocks (a
  leaf under `.ssd.`: `ssd_grad_max_rel_err`), the attention layer's
  (`.gqa.`: `attn_grad_max_rel_err`), the routers (`w_g`:
  `router_grad_max_rel_err`), the held experts' matrices (`experts_`:
  `expert_grad_max_rel_err`; a token that picks another expert moves
  these two by a whole term) and the rest (the shared expert's slices,
  norms, head columns, embedding columns: `grad_max_rel_err`);
- the sign of the blocks' first Adam update against the reference's
  gradient (a state left unchanged reads 1);
- nothing non-finite in the parameters the call returned.

`"control"` in the specification (`perf/tests/control_nemotron_h.py`
writes it; one name, or several with commas between) returns the
numbers of a reference that is wrong on purpose against the sound one,
and prints the program's own beside them: `bfloat16_reference` computes
everything in bfloat16, the state and the decays too;
`no_reset_reference` lets the scan's state and the convolution's taps
run on across history boundaries; `one_group` has every head read B and
C of group 0; `norm_all_channels` norms over all channels at once;
`norm_before_gate` norms y and gates after; `relu_not_squared` leaves
the square out; `gated_expert` gates the up projection with its own
SiLU; `scale_1` leaves the routed weights unscaled; `no_shared` leaves
the shared expert out. Each has to come out as not correct. Of several,
every one's numbers are printed with its verdict, and the one that came
nearest to passing is returned: the run is `correct` only if some
control was.

The reference runs a sequence at a time, a query block and a run of
`ssm_block` tokens at a time under `jax.checkpoint`: where it keeps its
intermediates, not what it computes.
"""

from __future__ import annotations

import numpy as np

from perf.checks.encoder_step import _index, _moved_share
from perf.harness import say
from perf.reference import nemotron_h as reference

CONTROLS = {"bfloat16_reference": {"dtype": "bfloat16"},
            "no_reset_reference": {"kda_resets": False},
            **{name: {"wrong": (name,)} for name in (
                "one_group", "norm_all_channels", "norm_before_gate",
                "relu_not_squared", "gated_expert", "scale_1", "no_shared")}}
GROUPS = {".ssd.": "ssd_grad_max_rel_err", ".gqa.": "attn_grad_max_rel_err",
          "w_g": "router_grad_max_rel_err",
          "experts_": "expert_grad_max_rel_err"}


def reference_objective(cfg, blocks, spec: dict, n1: int, switches: dict):
    """`f(picked, params, tokens, seg, pos)` for one sequence: its share
    of the step's loss, with the sum and every token's picks beside it.
    `picked` holds the report blocks, put into `params` before the
    forward pass, so that the gradient is taken of the blocks alone. The
    weights are an argument: closed over, they would be constants of the
    program."""
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
            s1, _, _, _, routed, _ = reference.nll_sums(
                with_blocks(params, picked), cfg, tokens, seg, pos,
                q_block=int(spec["q_block"]), wrap=jax.checkpoint,
                ssm_block=int(spec["ssm_block"]), **switches)
        return s1 / n1, (s1, jnp.stack([p for _, p in routed]))

    return objective


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
    b, l = tokens.shape
    ahead = np.roll(batch[1], -1, axis=1)
    n1 = max(int(((batch[1] != 0) & (ahead == batch[1])
                  & (np.arange(l) < l - 1)[None, :]).sum()), 1)
    cap = spec.get("hbm_cap_mib")
    capped = cap and jax.devices()[0].platform == "tpu"
    grad_fn = jax.jit(
        jax.value_and_grad(
            reference_objective(cfg, blocks, spec, n1, switches),
            has_aux=True),
        compiler_options=({"xla_tpu_max_hbm_size_mib": int(cap)}
                          if capped else None))
    if dtype is not None:
        params = jax.jit(lambda p: jax.tree_util.tree_map(
            lambda a: a.astype(dtype), p))(params)
    picked = {name: leaf_of(params, path, ix) for name, path, ix in blocks}
    sums, picks = None, []
    for n in range(b):
        (_, (s1, p)), g = grad_fn(picked, params, tokens[n], seg[n], pos[n])
        part = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                      (s1, g))
        sums = part if sums is None else jax.tree_util.tree_map(
            jnp.add, sums, part)
        picks.append(p)
    (s1, grads), picks = jax.device_get((sums,
                                         jnp.concatenate(picks, axis=1)))
    grads = {name: np.asarray(g, np.float64) for name, g in grads.items()}
    return {"ce": float(s1) / n1, "picks": picks, "grads": grads,
            # what Adam's first step does with such a gradient
            "update_sign": {name: -np.sign(g) for name, g in grads.items()}}


def _program(report: dict, initial: dict) -> dict:
    """What the timed step reported, in the shape of `_reference_step`;
    `initial` holds the report blocks of the parameters it started from."""
    got = report["metrics"]
    return {"ce": float(got["ce"]), "picks": np.asarray(got["picks"]),
            "grads": {name: np.asarray(g, np.float64)
                      for name, g in report["grads"].items()},
            "update_sign": {name: np.sign(np.asarray(after) - initial[name])
                            for name, after in report["params"].items()}}


def group_of(path: str) -> str:
    return next((group for part, group in GROUPS.items() if part in path),
                "grad_max_rel_err")


def compare(got: dict, want: dict, blocks, who: str) -> dict:
    """The numbers of `got` against the reference `want`, by name."""
    out = {"ce_rel_err": abs(got["ce"] - want["ce"]) / want["ce"],
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
    # the program's own init, from the key the call used; the router's
    # bias buffer starts at zero and the first step picks with that
    state = jax.jit(lambda k: {**encoder.init_params(cfg, vocab, k),
                               **encoder.init_buffers(cfg)})(
        jax.random.key(int(seed)))
    stats = jax.devices()[0].memory_stats() or {}
    say(f"before the reference the device holds {stats.get('bytes_in_use')} "
        f"B of {stats.get('bytes_limit')}")
    args = (state, report["batch"], cfg.report_blocks, spec)
    want = _reference_step(cfg, *args)
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
