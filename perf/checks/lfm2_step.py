"""`correct` for a cell that trains the encoder as a model of short
convolutions and attention (LFM2's `layer_types`: a doubly gated short
convolution as the mixer three layers in four, rotated grouped-query
attention under a norm a head on q and k in the fourth; a dense SwiGLU
in the leading layer, then sigmoid-routed SiLU-gated held experts
picked by a bias buffer, the weights over the picked scores' sum plus
1e-6, no shared expert; a tied head): what the first optimizer step of
the window's last call produced, at the timed sizes, against the plain
reference (`perf/reference/lfm2_moe.py`, float32 at the highest matmul
precision, the convolution three shifted sums under the histories'
mask, attention a full masked softmax a query block at a time, the
experts a loop over the held) at the same weights and on the same batch.

As `perf/checks/nemotron_h_step.py`:

- the loss of the step, relative;
- every token's picks in every expert layer: the share of (token, pick)
  pairs whose expert the other side did not pick for that token;
- for each of the configuration's report blocks the gradient the step
  used (Adam's first moment over 1 - b1), entry by entry: ||g - g_ref||
  / ||g_ref||, the largest of each group: the convolution mixers' blocks
  (a leaf under `.sconv.`: both gates' and x's columns of W_in, the
  taps, W_out: `sconv_grad_max_rel_err`), attention's projections
  (`.gqa.`: `attn_grad_max_rel_err`) with the two norms' weights apart
  (`.gqa.q_norm`, `.gqa.k_norm`: 64 entries each, whose norm is small:
  `qk_norm_grad_max_rel_err`), the routers (`w_g`:
  `router_grad_max_rel_err`), the held experts' matrices and the norm
  they read (`experts_`, an expert layer's `norm2`:
  `expert_grad_max_rel_err`; a token that picks another expert moves
  these by a whole term) and the rest (the dense feed-forward's slices,
  the other block norms, the embedding's columns, which the tied head
  writes too: `grad_max_rel_err`);
- the sign of the blocks' first Adam update against the reference's
  gradient (a state left unchanged reads 1);
- nothing non-finite in the parameters the call returned.

`"control"` in the specification (`perf/tests/control_lfm2.py` writes
it; one name, or several with commas between) returns the numbers of a
reference that is wrong on purpose against the sound one, and prints
the program's own beside them: `bfloat16_reference` computes everything
in bfloat16; `no_reset_reference` lets the taps read across history
boundaries; `no_in_gate` convolves x and not B * x; `no_out_gate`
leaves C out; `gates_swapped` gates with C before and B after;
`silu_on_taps` puts SiLU on the convolution's sum; `no_qk_norm` rotates
q and k as projected; `norm_after_rotation` norms after RoPE;
`interleaved_pairs` turns (x[2i], x[2i+1]). Each has to come out as not
correct. (The 1e-6 in the weights' denominator is no control here: 1e-2
in its place moves a weight by half a percent, under what the program's
bfloat16 operands do; `tests/test_encoder_lfm2.py` tells it in float32.) Of several, every one's numbers are printed with its
verdict, and the one that came nearest to passing is returned: the run
is `correct` only if some control was.

The reference runs a sequence at a time and a query block at a time
under `jax.checkpoint`: where it keeps its intermediates, not what it
computes.
"""

from __future__ import annotations

import numpy as np

from perf.checks.encoder_step import _index, _moved_share
from perf.checks.nemotron_h_step import _program
from perf.harness import say
from perf.reference import lfm2_moe as reference

CONTROLS = {"bfloat16_reference": {"dtype": "bfloat16"},
            "no_reset_reference": {"kda_resets": False},
            **{name: {"wrong": (name,)} for name in (
                "no_in_gate", "no_out_gate", "gates_swapped", "silu_on_taps",
                "no_qk_norm", "norm_after_rotation",
                "interleaved_pairs")}}
# the first part a leaf's path holds decides its group
GROUPS = {".gqa.q_norm": "qk_norm_grad_max_rel_err",
          ".gqa.k_norm": "qk_norm_grad_max_rel_err",
          ".sconv.": "sconv_grad_max_rel_err",
          ".gqa.": "attn_grad_max_rel_err",
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
                q_block=int(spec["q_block"]), wrap=jax.checkpoint, **switches)
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


def group_of(path: str) -> str:
    """The group of the leaf at `path`. An expert layer's second norm is
    the held experts': with no shared expert beside them its whole
    gradient comes back through the router and the experts' rows, and a
    token that picks another expert moves it by a whole term as it moves
    theirs (0.10-0.12 on the chip where a dense layer's norm reads 0.03)."""
    if path.startswith("moe.") and path.endswith(".norm2"):
        return "expert_grad_max_rel_err"
    return next((group for part, group in GROUPS.items() if part in path),
                "grad_max_rel_err")


def compare(got: dict, want: dict, blocks, who: str) -> dict:
    """The numbers of `got` against the reference `want`, by name."""
    out = {"ce_rel_err": abs(got["ce"] - want["ce"]) / want["ce"],
           "expert_picks_moved_share": _moved_share(got["picks"],
                                                    want["picks"]),
           **dict.fromkeys(GROUPS.values(), 0.0),
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
