"""Sparse experts: a router over every expert of the model and an expert
layer that is told which of them it holds.

A deployment divides the experts of a layer over chips. Every chip routes
every token over all the experts (`route`: sigmoid scores, a load-balance
bias that only picks, weights normalised over the picked, the sum of
the picked scores alone or, as LFM2's published rule has it, that sum
plus a small epsilon, and scaled; or the top-k of the logits and a
softmax over the picked) and computes the
part of the result that its own experts give (`held_experts`: gated
experts, the gate's activation SiLU or ReLU, or ungated ones, W_down
relu(W_up x)^2, two matrices an expert). What the absent experts would have added is left out;
on one chip the layer runs without its exchange, and nothing here stands
in for the chips that are not there.

`held_experts` is a sort-based dispatch that drops no token: the
(token, pick) pairs whose expert is held here are ordered by expert, each
expert's run padded to whole row blocks, and every block is one grouped
product against its expert's matrices. The number of blocks is read on
the device (a loop with a traced bound), so the work follows the load
and only the buffers have the size of the worst case. The backward pass
is written by hand for the same reason, and recomputes the hidden
activations block by block.

Products take `dtype` operands and accumulate in float32. One thing is
handed on in `dtype` and not as the float32 sum: an expert's output rows
on their way to the combine, and the gradient rows on their way back,
which lie in the row buffers of the worst case (every pick of every
token held here: sixteen times the expected rows, a gigabyte in float32
at 16384 tokens of width 2048, which one chip's step has no room for).
It is what an exchange between chips would carry (DeepSeek-V3 combines
in BF16); the weighted sum over a token's picks is float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def route(x, w_g, bias, top_k: int, scale: float,
          scoring: str = "sigmoid", eps: float = 0.0):
    """Scores of x [T, D] over all the experts of w_g [D, E].

    Returns (idx [T, k] int32, weights [T, k] float32, load [E] int32):
    the picks, their weights, and how many tokens picked each expert.
    `scoring` "sigmoid": the top-k of `sigmoid(x w_g) + bias`, the
    picked scores normalised to sum to one and scaled, w = scale s[idx] /
    (sum(s[idx]) + eps), `eps` 0 for every model but the one whose
    published rule states one (0 adds no op: the weights are the bare
    quotient's bit for bit); the bias decides the pick only
    (`noaux_tc`). "softmax": the top-k of the logits
    x w_g and a softmax over the picked (a softmax over all, picked and
    renormalised, is the same); no bias, no scale. The scores are
    float32 at the highest matmul precision: a pick turns on their last
    digits."""
    logits = jnp.dot(x.astype(jnp.float32), w_g, precision="highest")
    if scoring == "softmax":
        if bias is not None or scale != 1.0 or eps:
            raise ValueError("a softmax router takes no bias, no scale and "
                             "no epsilon")
        picked, idx = jax.lax.top_k(logits, top_k)
        weights = jax.nn.softmax(picked, axis=-1)
    elif scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias)[None, :],
                               top_k)
        picked = jnp.take_along_axis(s, idx, axis=-1)
        scaled = scale * picked  # before the sum: the order the ops had
        total = jnp.sum(picked, axis=-1, keepdims=True)
        weights = scaled / (total + eps if eps else total)
    else:
        raise ValueError(f"no router scoring {scoring!r}")
    load = jnp.zeros(w_g.shape[1], jnp.int32).at[idx.reshape(-1)].add(1)
    return idx.astype(jnp.int32), weights, load


def _plan(idx, first: int, held: int, block: int):
    """Where each (token, pick) pair goes in the row buffer.

    Returns (pos [T, k]: the pair's row, or the buffer's length where its
    expert is not held; tok_of_row [M]: the row's token, T for a padding
    row; group_start [held], n_blocks [held]; counts [held])."""
    t, k = idx.shape
    a = t * k
    m = a + held * block
    local = idx.reshape(-1) - first
    e = jnp.where((local >= 0) & (local < held), local, held)
    counts_all = jnp.zeros(held + 1, jnp.int32).at[e].add(1)
    counts = counts_all[:held]
    n_blocks = (counts + block - 1) // block
    padded = n_blocks * block
    group_start = jnp.cumsum(padded) - padded
    run_start = jnp.cumsum(counts_all) - counts_all
    order = jnp.argsort(e, stable=True)
    e_sorted = e[order]
    rank = jnp.arange(a, dtype=jnp.int32) - run_start[e_sorted]
    dest = jnp.where(e_sorted < held,
                     jnp.append(group_start, 0)[e_sorted] + rank, m)
    pos = jnp.zeros(a, jnp.int32).at[order].set(dest).reshape(t, k)
    tok_of_row = jnp.full(m, t, jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32), mode="drop")
    return pos, tok_of_row, group_start, n_blocks, counts


def _rows(buf, start, block):
    return jax.lax.dynamic_slice_in_dim(buf, start, block, 0)


def _gather_picks(buf, pos, weights):
    """sum_k weights[t, k] * buf[pos[t, k]], a pick at a time; a pair
    that is not held points past the buffer and reads zeros."""
    out = 0.0
    for j in range(pos.shape[1]):
        rows = jnp.take(buf, pos[:, j], axis=0, mode="fill", fill_value=0)
        out = out + rows.astype(jnp.float32) * weights[:, j, None]
    return out


def _silu_gate(g):
    sg = jax.nn.sigmoid(g)
    return g * sg, lambda: sg * (1.0 + g * (1.0 - sg))


def _relu_gate(g):
    on = g > 0
    return jnp.where(on, g, 0.0), lambda: on.astype(g.dtype)


def _gated(act, act_and_slope):
    """A gated expert's hidden activation from h = x [W_gate | W_up]
    [rows, 2F]: (forward: act(g) * u; backward: the same and the
    pullback of its cotangent to h's)."""
    def hidden(h, f):
        return act(h[:, :f]) * h[:, f:]

    def hidden_and_pullback(h, f):
        g, u = h[:, :f], h[:, f:]
        a, slope = act_and_slope(g)
        return a * u, lambda da: jnp.concatenate(
            [da * u * slope(), da * a], axis=1)

    return hidden, hidden_and_pullback


def _relu2(h, f):
    r = jnp.maximum(h, 0.0)
    return r * r


def _relu2_and_pullback(h, f):
    r = jnp.maximum(h, 0.0)
    return r * r, lambda da: da * (2.0 * r)


# an expert's form, by the name of its activation: "silu" and "relu" gate
# (w13 [D, 2F] holds gate and up side by side), "relu2" has no gate (w13
# [D, F] is the up matrix alone: W_down relu(W_up x)^2). (the hidden
# activation from x w13 for the forward pass; the same with the pullback
# of its cotangent for the hand-written backward pass)
GATES = {"silu": _gated(jax.nn.silu, _silu_gate),
         "relu": _gated(jax.nn.relu, _relu_gate),
         "relu2": (_relu2, _relu2_and_pullback)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _held_experts(x, weights, idx, w13, w2, first, block, dtype, scope,
                  gate):
    return _held_fwd(x, weights, idx, w13, w2, first, block, dtype, scope,
                     gate)[0]


def _held_fwd(x, weights, idx, w13, w2, first, block, dtype, scope, gate):
    with jax.named_scope(scope):
        return _held_fwd_scoped(x, weights, idx, w13, w2, first, block,
                                dtype, scope, gate)


def _in_dtype(w13, w2, dtype):
    """The held experts' matrices as operands. The barrier keeps the
    cast where it is: hoisted out of a scan over layers it becomes a
    second copy of every layer's experts, held for the whole step."""
    w13, w2 = jax.lax.optimization_barrier((w13, w2))
    return w13.astype(dtype), w2.astype(dtype)


def _held_fwd_scoped(x, weights, idx, w13, w2, first, block, dtype, scope,
                     gate):
    held, d = w13.shape[0], x.shape[1]
    f = w2.shape[1]
    hidden = GATES[gate][0]
    with jax.named_scope(f"{scope}.plan"):
        pos, tok_of_row, group_start, n_blocks, counts = _plan(
            idx, first, held, block)
    x_pad = jnp.concatenate([x.astype(dtype), jnp.zeros((1, d), dtype)])

    def expert(ys, e):
        w13_e, w2_e, start, n = e

        def one(b, ys):
            at = start + b * block
            xb = jnp.take(x_pad, _rows(tok_of_row, at, block), axis=0)
            h = jnp.dot(xb, w13_e, preferred_element_type=jnp.float32)
            a = hidden(h, f).astype(dtype)
            yb = jnp.dot(a, w2_e, preferred_element_type=jnp.float32)
            return jax.lax.dynamic_update_slice_in_dim(
                ys, yb.astype(dtype), at, 0)

        return jax.lax.fori_loop(0, n, one, ys), None

    ys, _ = jax.lax.scan(
        expert, jnp.zeros((tok_of_row.shape[0], d), dtype),
        _in_dtype(w13, w2, dtype) + (group_start, n_blocks))
    y = _gather_picks(ys, pos, weights)
    return (y, counts), (x, weights, idx, w13, w2)


def _held_bwd(first, block, dtype, scope, gate, res, cot):
    # traced outside the caller's scopes: it opens the one it was given
    with jax.named_scope(scope):
        return _held_bwd_scoped(first, block, dtype, scope, gate, res, cot)


def _held_bwd_scoped(first, block, dtype, scope, gate, res, cot):
    x, weights, idx, w13, w2 = res
    dy = cot[0]
    held, d = w13.shape[0], x.shape[1]
    f = w2.shape[1]
    hidden_and_pullback = GATES[gate][1]
    with jax.named_scope(f"{scope}.plan"):
        pos, tok_of_row, group_start, n_blocks, _ = _plan(idx, first, held,
                                                          block)
    m = tok_of_row.shape[0]
    x_pad = jnp.concatenate([x.astype(dtype), jnp.zeros((1, d), dtype)])
    dy_pad = jnp.concatenate([dy.astype(dtype), jnp.zeros((1, d), dtype)])
    w_rows = jnp.zeros(m, jnp.float32).at[pos.reshape(-1)].set(
        weights.reshape(-1), mode="drop")

    def expert(carry, e):
        w13_e, w2_e, start, n = e

        def one(b, inner):
            dxs, dw_rows, dw13_e, dw2_e = inner
            at = start + b * block
            rows = _rows(tok_of_row, at, block)
            xb, dyb = jnp.take(x_pad, rows, axis=0), jnp.take(dy_pad, rows,
                                                               axis=0)
            h = jnp.dot(xb, w13_e, preferred_element_type=jnp.float32)
            a, pullback = hidden_and_pullback(h, f)
            a = a.astype(dtype)
            yb = jnp.dot(a, w2_e, preferred_element_type=jnp.float32)
            dwr = jnp.sum(yb * dyb.astype(jnp.float32), axis=-1)
            dyw = (dyb.astype(jnp.float32)
                   * _rows(w_rows, at, block)[:, None]).astype(dtype)
            dw2_e = dw2_e + jnp.dot(a.T, dyw,
                                    preferred_element_type=jnp.float32)
            da = jnp.dot(dyw, w2_e.T, preferred_element_type=jnp.float32)
            dh = pullback(da).astype(dtype)
            dw13_e = dw13_e + jnp.dot(xb.T, dh,
                                      preferred_element_type=jnp.float32)
            dxb = jnp.dot(dh, w13_e.T, preferred_element_type=jnp.float32)
            return (jax.lax.dynamic_update_slice_in_dim(
                        dxs, dxb.astype(dtype), at, 0),
                    jax.lax.dynamic_update_slice_in_dim(dw_rows, dwr, at, 0),
                    dw13_e, dw2_e)

        dxs, dw_rows, dw13_e, dw2_e = jax.lax.fori_loop(
            0, n, one, carry + (jnp.zeros(w13_e.shape, jnp.float32),
                                jnp.zeros(w2_e.shape, jnp.float32)))
        return (dxs, dw_rows), (dw13_e, dw2_e)

    (dxs, dw_rows), (dw13, dw2) = jax.lax.scan(
        expert, (jnp.zeros((m, d), dtype), jnp.zeros(m, jnp.float32)),
        _in_dtype(w13, w2, dtype) + (group_start, n_blocks))
    ones = jnp.ones(pos.shape, jnp.float32)
    dx = _gather_picks(dxs, pos, ones)
    dweights = jnp.stack(
        [jnp.take(dw_rows, pos[:, j], mode="fill", fill_value=0.0)
         for j in range(pos.shape[1])], axis=1)
    return (dx.astype(x.dtype), dweights.astype(weights.dtype), None,
            dw13.astype(w13.dtype), dw2.astype(w2.dtype))


_held_experts.defvjp(_held_fwd, _held_bwd)


def held_experts(x, weights, idx, w13, w2, first: int, block: int,
                 dtype=jnp.float32, scope: str = "moe.held_experts",
                 gate: str = "silu"):
    """The held experts' part of an expert layer's result.

    x [T, D]; idx, weights [T, k] from `route`; w13 [held, D, 2F] and w2
    [held, F, D] are the experts `first .. first + held - 1` of the
    layer. Returns (y [T, D] float32: sum over a token's picks that are
    held here of weight * W_down (act(W_gate x) * (W_up x)), act the
    `gate` named ("silu": SwiGLU; "relu"), or with "relu2", w13 [held,
    D, F] the up matrix alone, of weight * W_down relu(W_up x)^2;
    counts [held]: tokens each held expert was sent). Products take `dtype` operands and accumulate
    in float32; the rows travel to the combine in `dtype` (see the
    module's docstring); the ops, those of the backward pass too, are traced
    under `scope`, the dispatch plan's under `<scope>.plan`."""
    if gate not in GATES:
        raise ValueError(f"no expert form {gate!r}: one of "
                         f"{sorted(GATES)}")
    if w13.shape[2] != (1 if gate == "relu2" else 2) * w2.shape[1]:
        raise ValueError(f"{gate!r} experts of width {w2.shape[1]} do not "
                         f"take w13 of {w13.shape[2]} columns")
    y, counts = _held_experts(x, weights, idx, w13, w2, int(first),
                              int(block), jnp.dtype(dtype), scope, gate)
    return y, jax.lax.stop_gradient(counts)
