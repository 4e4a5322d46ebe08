"""`correct` for a cell that trains the encoder: what the first optimizer
step of the window's last call produced, at the timed sizes, against the
plain reference (`perf/reference/encoder.py`, float32 at the highest
matmul precision) at the same weights and on the same batch.

The program makes its weights on the device from the call's seed, so the
check makes them again with the program's `init_state` (weights are
data) and hands the reference the batch the step saw, which the model's
`train_report` carries. Compared:

- both losses of the step (CE and the MTP module's), relative;
- every token's picks in every expert layer and in the MTP module: the
  share of (token, pick) pairs whose expert the other side did not pick
  for that token;
- for each of the configuration's report blocks (one expert's matrices,
  a router, latent projections, the shared expert, W_eh, embedding rows),
  the gradient the step used, entry by entry: ||g - g_ref|| / ||g_ref||.
  The step's gradient is read from the state it left: Adam's first
  moment starts at zero, so after the first step it is (1 - b1) g. The
  routed blocks (a router, a held expert's matrices) are held apart
  from those every token reaches: a token that picks another expert
  moves their gradient by a whole term;
- the sign of the blocks' first Adam update (the parameters the step
  left against the initial ones) against the reference's gradient: the
  share of the entries whose reference gradient is above
  a tenth of the block's root mean square that moved the other way (a
  state left unchanged reads 1);
- nothing non-finite in the parameters the call returned.

With `"control": "bfloat16_reference"` in the specification
(`perf/tests/control_encoder.py` writes it) the numbers returned are not
the program's: they are the reference's own, computed in bfloat16
throughout (weights, activations, router scores, softmax, norms, the
loss; only RoPE's angles stay float32), the nearest precision below the
configuration's, against the float32 reference. That run has to come
out as not correct; the program's numbers are printed beside them.

The reference runs a sequence at a time and a query block at a time
under `jax.checkpoint` (its `wrap`), which changes where it keeps its
intermediates and not what it computes: the published widths do not fit
one chip otherwise.
"""

from __future__ import annotations

import numpy as np

from perf.harness import say
from perf.reference import encoder as reference


def _index(ix):
    return tuple(slice(*i) if isinstance(i, tuple) else i for i in ix)


def reference_objective(cfg, blocks, q_block: int, n1: int, n2: int):
    """`f(picked, params, tokens, seg, pos)` for one sequence: its share
    of the step's loss (sums over the batch's counts n1, n2) with the
    sums and every token's picks beside it. `picked` holds the report
    blocks, put into `params` before the forward pass, so that the
    gradient is taken of the blocks alone (whole leaves of the stacked
    experts would be gigabytes of output). The weights are an argument:
    closed over, they would be constants of the program."""
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
            s1, _, s2, _, routed, r2 = reference.nll_sums(
                with_blocks(params, picked), cfg, tokens, seg, pos,
                q_block=q_block, wrap=jax.checkpoint)
        return (s1 / n1 + cfg.mtp_loss_weight * s2 / n2,
                (s1, s2, jnp.stack([picks for _, picks in routed]), r2[1]))

    return objective


def _reference_step(cfg, params, batch, blocks, q_block, hbm_cap_mib=None,
                    dtype=None):
    """What the reference gives on the batch, in the shape of `_program`:
    its gradients and sums a sequence at a time. With `dtype` the weights
    are cast to it first and everything is computed in it. On a TPU the
    program is compiled under `hbm_cap_mib` of device memory: the
    compiler plans up to what it is told the chip has, and the chip also
    holds the weights and whatever the run left."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.encoder import leaf_of

    tokens, seg, pos = (jnp.asarray(a) for a in batch)
    b, l = tokens.shape

    def valid(k):
        ahead = np.roll(batch[1], -k, axis=1)
        return int(((batch[1] != 0) & (ahead == batch[1])
                    & (np.arange(l) < l - k)[None, :]).sum())

    n1, n2 = max(valid(1), 1), max(valid(2), 1)
    capped = hbm_cap_mib and jax.devices()[0].platform == "tpu"
    grad_fn = jax.jit(
        jax.value_and_grad(reference_objective(cfg, blocks, q_block, n1, n2),
                           has_aux=True),
        compiler_options=({"xla_tpu_max_hbm_size_mib": int(hbm_cap_mib)}
                          if capped else None))
    if dtype is not None:
        params = jax.jit(lambda p: jax.tree_util.tree_map(
            lambda a: a.astype(dtype), p))(params)
    picked = {name: leaf_of(params, path, ix) for name, path, ix in blocks}
    sums, picks, mtp_picks = None, [], []
    for n in range(b):
        (_, (s1, s2, p, p2)), g = grad_fn(picked, params, tokens[n], seg[n],
                                          pos[n])
        part = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                      (s1, s2, g))
        sums = part if sums is None else jax.tree_util.tree_map(
            jnp.add, sums, part)
        picks.append(p)
        mtp_picks.append(p2)
    (s1, s2, grads), picks, mtp_picks = jax.device_get(
        (sums, jnp.concatenate(picks, axis=1), jnp.concatenate(mtp_picks)))
    grads = {name: np.asarray(g, np.float64) for name, g in grads.items()}
    return {"ce": float(s1) / n1, "ce_mtp": float(s2) / n2, "picks": picks,
            "mtp_picks": mtp_picks, "grads": grads,
            # what Adam's first step does with such a gradient
            "update_sign": {name: -np.sign(g) for name, g in grads.items()}}


def _program(report: dict, initial: dict) -> dict:
    """What the timed step reported, in the shape of `_reference_step`;
    `initial` holds the report blocks of the parameters it started from."""
    got = report["metrics"]
    return {"ce": float(got["ce"]), "ce_mtp": float(got["ce_mtp"]),
            "picks": np.asarray(got["picks"]),
            "mtp_picks": np.asarray(got["mtp_picks"]),
            "grads": {name: np.asarray(g, np.float64)
                      for name, g in report["grads"].items()},
            "update_sign": {name: np.sign(np.asarray(after) - initial[name])
                            for name, after in report["params"].items()}}


def _moved_share(a, b) -> float:
    """Share of the (token, pick) pairs of a [..., T, k] whose expert is
    not among the same token's picks in b."""
    a, b = np.asarray(a), np.asarray(b)
    return float(1.0 - (a[..., :, None] == b[..., None, :]).any(-1).mean())


def compare(got: dict, want: dict, blocks, who: str) -> dict:
    """The numbers of `got` against the reference `want`, by name."""
    grad_err, sign_err = {}, {}
    for name, _, _ in blocks:
        g = want["grads"][name]
        norm = float(np.sqrt((g * g).sum()))
        grad_err[name] = float(np.sqrt(
            ((got["grads"][name] - g) ** 2).sum())) / max(norm, 1e-30)
        big = np.abs(g) > 0.1 * np.sqrt((g * g).mean())
        sign = np.asarray(got["update_sign"][name])
        sign_err[name] = (float((sign[big] != -np.sign(g[big])).mean())
                          if big.any() else 0.0)
        say(f"{who}: block {name}: gradient off by {grad_err[name]:.3e} of "
            f"the reference's norm {norm:.6e}; first update against the "
            f"reference's sign on {int(big.sum())} entries: "
            f"{sign_err[name]:.3e} the other way")
    n_picks = got["picks"].size + got["mtp_picks"].size
    moved = (_moved_share(got["picks"], want["picks"]) * got["picks"].size
             + _moved_share(got["mtp_picks"], want["mtp_picks"])
             * got["mtp_picks"].size) / max(n_picks, 1)
    routed = [name for name, path, _ in blocks
              if "w_g" in path or "experts_" in path]
    return {
        "ce_rel_err": abs(got["ce"] - want["ce"]) / want["ce"],
        "ce_mtp_rel_err": (abs(got["ce_mtp"] - want["ce_mtp"])
                           / want["ce_mtp"]),
        "expert_picks_moved_share": moved,
        "grad_max_rel_err": max(v for n, v in grad_err.items()
                                if n not in routed),
        "routed_grad_max_rel_err": max((grad_err[n] for n in routed),
                                       default=0.0),
        "update_sign_max_wrong_share": max(sign_err.values()),
    }


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
    live = jax.live_arrays()
    say(f"before the reference the device holds {stats.get('bytes_in_use')} "
        f"B of {stats.get('bytes_limit')}; {len(live)} live arrays of "
        f"{sum(a.nbytes for a in live)} B")
    args = (cfg, state, report["batch"], cfg.report_blocks,
            int(spec["q_block"]), spec.get("hbm_cap_mib"))
    want = _reference_step(*args)
    initial = jax.device_get(encoder.report_of(cfg, state))
    numbers = compare(_program(report, initial), want, cfg.report_blocks,
                      "program")
    if spec.get("control") == "bfloat16_reference":
        say("the program's numbers: " + ", ".join(
            f"{n} {v:.4e}" for n, v in numbers.items()))
        numbers = compare(_reference_step(*args, dtype=jnp.bfloat16), want,
                          cfg.report_blocks, "bfloat16 reference")
    del state
    numbers["nonfinite_entries"] = int(sum(
        (~np.isfinite(leaf)).sum()
        for leaf in jax.tree_util.tree_leaves(model.params)))
    limits = spec["limits"]
    return [{"name": n, "value": v, "limit": limits.get(n)}
            for n, v in numbers.items()]
