"""`correct` for a cell that trains the encoder with two kinds of token
mixer (Kimi Delta Attention beside latent attention, no MTP module):
what the first optimizer step of the window's last call produced, at the
timed sizes, against the plain reference (`perf/reference/kimi_linear.py`,
float32 at the highest matmul precision, the KDA layers a token at a
time) at the same weights and on the same batch.

As `perf/checks/encoder_step.py` (whose two helpers it borrows), with
one loss and the gradient blocks in three groups:

- the loss of the step, relative;
- every token's picks in every expert layer: the share of (token, pick)
  pairs whose expert the other side did not pick for that token;
- for each of the configuration's report blocks the gradient the step
  used (Adam's first moment over 1 - b1), entry by entry: ||g - g_ref||
  / ||g_ref||, the largest of each group: the KDA layers' blocks (a
  leaf under `.kda.`: projections, a convolution's taps, A_log,
  dt_bias, the gates' matrices, the head norm, W_o), the routed blocks
  (a router, a held expert), and the rest (the MLA layer's, the shared
  expert, embedding rows);
- the sign of the blocks' first Adam update against the reference's
  gradient (a state left unchanged reads 1);
- nothing non-finite in the parameters the call returned.

`"control"` in the specification (`perf/tests/control_kimi.py` writes
it) returns the numbers of a reference that is wrong on purpose against
the sound one, and prints the program's own beside them:
`bfloat16_reference` computes everything in bfloat16, the state of the
recurrence too; `no_reset_reference` lets the KDA layers' state and
convolution run on across history boundaries. Each has to come out as
not correct.

The reference runs a sequence at a time, a query block and a run of
`kda_block` tokens at a time under `jax.checkpoint`: where it keeps its
intermediates, not what it computes.
"""

from __future__ import annotations

import numpy as np

from perf.checks.encoder_step import _index, _moved_share
from perf.harness import say
from perf.reference import kimi_linear as reference


def reference_objective(cfg, blocks, spec: dict, n1: int, resets: bool):
    """`f(picked, params, tokens, seg, pos)` for one sequence: its share
    of the step's loss with the sum and every token's picks beside it.
    `picked` holds the report blocks, put into `params` before the
    forward pass, so that the gradient is taken of the blocks alone. The
    weights are an argument: closed over, they would be constants of
    the program."""
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
                kda_block=int(spec["kda_block"]), kda_resets=resets)
        return s1 / n1, (s1, jnp.stack([picks for _, picks in routed]))

    return objective


def _reference_step(cfg, params, batch, blocks, spec, dtype=None,
                    resets=True):
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
        jax.value_and_grad(reference_objective(cfg, blocks, spec, n1, resets),
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
    (s1, grads), picks = jax.device_get(
        (sums, jnp.concatenate(picks, axis=1)))
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
    if ".kda." in path:
        return "kda_grad_max_rel_err"
    if "w_g" in path or "experts_" in path:
        return "routed_grad_max_rel_err"
    return "grad_max_rel_err"


def compare(got: dict, want: dict, blocks, who: str) -> dict:
    """The numbers of `got` against the reference `want`, by name."""
    out = {"ce_rel_err": abs(got["ce"] - want["ce"]) / want["ce"],
           "expert_picks_moved_share": _moved_share(got["picks"],
                                                    want["picks"]),
           "kda_grad_max_rel_err": 0.0, "routed_grad_max_rel_err": 0.0,
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
        out[group_of(path)] = max(out[group_of(path)], err)
        out["update_sign_max_wrong_share"] = max(
            out["update_sign_max_wrong_share"], wrong)
    return out


def run(spec: dict, config: dict, model, seed: int) -> list[dict]:
    """The numbers compared, each `{"name", "value", "limit"}`; a limit
    of None marks a number that is printed and not held."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import encoder

    cfg = encoder.EncoderConfig.from_dict(config)
    report = model.train_report
    vocab = int(np.asarray(model.params["emb"]).shape[0])
    # the program's own init, from the key the call used
    state = jax.jit(lambda k: {
        **encoder.init_params(cfg, vocab, k), **encoder.init_buffers(cfg)})(
        jax.random.key(int(seed)))
    stats = jax.devices()[0].memory_stats() or {}
    say(f"before the reference the device holds {stats.get('bytes_in_use')} "
        f"B of {stats.get('bytes_limit')}")
    args = (cfg, state, report["batch"], cfg.report_blocks, spec)
    want = _reference_step(*args)
    initial = jax.device_get(encoder.report_of(cfg, state))
    numbers = compare(_program(report, initial), want, cfg.report_blocks,
                      "program")
    control = spec.get("control")
    if control:
        say("the program's numbers: " + ", ".join(
            f"{n} {v:.4e}" for n, v in numbers.items()))
        wrong = {"bfloat16_reference": {"dtype": jnp.bfloat16},
                 "no_reset_reference": {"resets": False}}[control]
        numbers = compare(_reference_step(*args, **wrong), want,
                          cfg.report_blocks, control.replace("_", " "))
    del state
    numbers["nonfinite_entries"] = int(sum(
        (~np.isfinite(leaf)).sum()
        for leaf in jax.tree_util.tree_leaves(model.params)))
    limits = spec["limits"]
    return [{"name": n, "value": v, "limit": limits.get(n)}
            for n, v in numbers.items()]
