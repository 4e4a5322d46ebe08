"""Model-axis factor sharding for ALS training (VERDICT r1 #3).

This is config 5's actual capability (rank-128 ML-20M on a v5e-64 pod,
«MLlib ALS.run block partitioning» [U], SURVEY.md §2.6 row 2): both
factor matrices live **row-sharded over the mesh `model` axis** instead
of replicated, so pod-scale factor tables never materialize on one chip.

The TPU-first formulation (no translation of MLlib's in/out-link block
shuffle): normal equations are linear over ratings, so each model shard
computes the contribution of *its* opposing-factor rows to every
solved-for row's (A, b) from purely local gathers, and the shards
combine with two collectives per chunk:

    A_r = Σ_m  Σ_{c ∈ shard m}  w_rc y_c y_cᵀ      (local masked gather
    b_r = Σ_m  Σ_{c ∈ shard m}  w_rc p_rc y_c       + einsum per shard)

    psum_scatter(A, axis='model')   → each shard solves R/m distinct rows
    all_gather(x,  axis='model')    → solved rows rejoin, scatter locally

Traffic per chunk row is K² + K floats (rank 64: 16 KB) — independent of
the row's rating count, vs C·K for a replicated-table gather — and it
rides ICI. Interaction buckets stay sharded over `data` exactly as in
`ops.als`; the whole train loop (lax.scan over iterations) runs inside
ONE `shard_map` + `jit`, so a train is still a single dispatch.

Numerics match the replicated path: same f32 partial accumulation, same
regularization/weighted-λ semantics, same hot-row segment accumulators
(psum'd over both axes at the end of each half-step).
"""

from __future__ import annotations

import functools
import logging
from typing import Optional, Sequence

import numpy as np

from predictionio_tpu.ops import als
from predictionio_tpu.ops.als import ALSConfig, _walk_bucket_chunks
from predictionio_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

log = logging.getLogger(__name__)


def pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def local_row_multiple(n_model: int, base: int = 8) -> int:
    """Per-device row alignment: a multiple of the model-axis size (for
    the per-chunk psum_scatter) that is at least `base`."""
    return pad_to(max(base, n_model), n_model)


def _masked_local_gather(table_local, ids, off, size, k):
    """Gather rows of a model-local [size, K] table by GLOBAL ids,
    zero-filled where the id falls outside this shard. Flat take (the
    fast TPU lowering — arrays here are device-local under shard_map)."""
    import jax.numpy as jnp

    local = ids - off
    ok = (local >= 0) & (local < size)
    flat = jnp.take(table_local, local.clip(0, size - 1).reshape(-1),
                    axis=0, mode="clip").reshape(*ids.shape, k)
    return flat * ok[..., None], ok


@functools.lru_cache(maxsize=32)
def get_train_loop_sharded(
    n_users_pad: int,
    n_items_pad: int,
    cfg: ALSConfig,
    compute_rmse: bool,
    n_steps: int,
    rm_local: int,
    mesh,
    seg_u: tuple,  # per user-bucket: has-segmap flags (pytree spec shape)
    seg_i: tuple,
    n_usplit: int,
    n_isplit: int,
):
    """Jitted n_steps-iteration training loop with factors sharded
    P(model). Inputs/outputs mirror `als._get_train_loop` but factor
    arrays are [n_pad, K] NamedSharding P('model') and bucket arrays are
    sharded P('data') on rows."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n_model = mesh.shape[MODEL_AXIS]
    k = cfg.rank
    f32 = jnp.float32
    scope = jax.named_scope  # the same fixed names as ops/als.py's loop

    def bucket_specs(flags):
        return [
            (P(DATA_AXIS), P(DATA_AXIS, None), P(DATA_AXIS, None),
             P(DATA_AXIS, None), P(DATA_AXIS) if has_seg else None)
            for has_seg in flags
        ]

    def half_step(opposing_local, out_pad: int, buckets, split_rows,
                  n_split: int):
        """Solve every row against the model-sharded opposing table;
        return this shard's [out_pad/m, K] slice of the new factors."""
        m_idx = lax.axis_index(MODEL_AXIS)
        out_size = out_pad // n_model
        out_off = m_idx * out_size
        opp_size = opposing_local.shape[0]
        opp_off = m_idx * opp_size

        dtype = opposing_local.dtype
        with scope("als.scatter"):
            new_local = jnp.zeros((out_size, k), dtype=dtype)
        if n_split:
            with scope("als.split_merge"):
                acc_a = jnp.zeros((n_split, k, k), f32)
                acc_b = jnp.zeros((n_split, k), f32)
                acc_n = jnp.zeros((n_split,), f32)

        gram = None
        if cfg.implicit:
            with scope("als.yty"):
                op_c = opposing_local.astype(jnp.dtype(cfg.compute_dtype))
                ne_einsum = als.normal_eq_einsum(op_c.dtype)
                gram = lax.psum(ne_einsum("ck,cl->kl", op_c, op_c),
                                MODEL_AXIS)

        def finalize(a, b, n):
            # no mesh: device-local, already inside `run`'s shard_map
            return als._regularise_and_solve(a, b, n, gram, cfg, cfg.reg,
                                             dtype)

        def process(sliced, carry):
            rows_c, cols_c, vals_c, mask_c, segmap_c = sliced
            new, accs = carry
            with scope("als.gather_gram"):
                n = mask_c.sum(-1)
                y, _ = _masked_local_gather(opposing_local, cols_c, opp_off,
                                            opp_size, k)
                a_part, b_part = als._partial_normal_eqs(
                    y, vals_c, mask_c, cfg, cfg.alpha)
            rows_eff = rows_c
            if segmap_c is not None:
                with scope("als.split_merge"):
                    acc_a, acc_b, acc_n = accs
                    # model-partial (A, b) accumulate as-is (psum'd over
                    # both axes before the segment solve); counts are
                    # replicated over `model`, so only shard 0
                    # contributes them
                    accs = (acc_a.at[segmap_c].add(a_part, mode="drop"),
                            acc_b.at[segmap_c].add(b_part, mode="drop"),
                            acc_n.at[segmap_c].add(
                                jnp.where(m_idx == 0, n, 0.0), mode="drop"))
                    rows_eff = jnp.where(segmap_c < n_split, out_pad,
                                         rows_c)

            r_chunk = rows_c.shape[0]
            # combine shard contributions; each model shard solves a
            # distinct R/m slice of the chunk, then the solved rows rejoin
            with scope("als.solve"):
                a = lax.psum_scatter(a_part, MODEL_AXIS,
                                     scatter_dimension=0, tiled=True)
                b = lax.psum_scatter(b_part, MODEL_AXIS,
                                     scatter_dimension=0, tiled=True)
                n_loc = lax.dynamic_slice_in_dim(
                    n, m_idx * (r_chunk // n_model), r_chunk // n_model)
                x = lax.all_gather(finalize(a, b, n_loc), MODEL_AXIS,
                                   axis=0, tiled=True)
            with scope("als.scatter"):
                local = rows_eff - out_off
                idx = jnp.where((local >= 0) & (local < out_size), local,
                                out_size)
                new = new.at[idx].set(x.astype(dtype), mode="drop")
            return new, accs

        accs = (acc_a, acc_b, acc_n) if n_split else ()
        for bucket in buckets:
            cap = bucket[1].shape[1]
            new_local, accs = _walk_bucket_chunks(
                bucket, cap, k, rm_local,
                lambda sliced, carry: process(sliced, carry),
                (new_local, accs))

        if n_split:
            with scope("als.split_merge"):
                acc_a = lax.psum(lax.psum(accs[0], DATA_AXIS), MODEL_AXIS)
                acc_b = lax.psum(lax.psum(accs[1], DATA_AXIS), MODEL_AXIS)
                acc_n = lax.psum(lax.psum(accs[2], DATA_AXIS), MODEL_AXIS)
            with scope("als.solve"):
                x_u = finalize(acc_a, acc_b, acc_n)  # [U, K], replicated
            with scope("als.scatter"):
                local = split_rows - out_off
                # x_u is replicated over `data`, but the final psum over
                # `data` merges the per-shard scatters — write it on data
                # shard 0 only or it would be summed n_data times
                d_idx = lax.axis_index(DATA_AXIS)
                idx = jnp.where(
                    (local >= 0) & (local < out_size) & (d_idx == 0),
                    local, out_size)
                new_local = new_local.at[idx].set(x_u.astype(dtype),
                                                  mode="drop")
        # distinct data shards solved distinct rows into disjoint slots;
        # psum over `data` merges them (empty slots are zero)
        with scope("als.scatter"):
            return lax.psum(new_local, DATA_AXIS)

    def sq_err(u_local, i_local, buckets):
        m_idx = lax.axis_index(MODEL_AXIS)
        u_size, i_size = u_local.shape[0], i_local.shape[0]
        u_off, i_off = m_idx * u_size, m_idx * i_size

        def err_chunk(sliced, carry):
            rows_c, cols_c, vals_c, mask_c, _seg = sliced
            total, count = carry
            u_part, _ = _masked_local_gather(
                u_local, rows_c.clip(0, n_users_pad - 1), u_off, u_size, k)
            u = lax.psum(u_part, MODEL_AXIS)  # [R, K]
            v_part, _ = _masked_local_gather(i_local, cols_c, i_off,
                                             i_size, k)
            pred = lax.psum(
                jnp.einsum("rk,rck->rc", u, v_part), MODEL_AXIS)
            err = (pred - vals_c) * mask_c
            # replicated over `model` after the psums: count on shard 0
            gate = jnp.where(m_idx == 0, 1.0, 0.0)
            return (total + gate * jnp.sum(err * err),
                    count + gate * jnp.sum(mask_c))

        total = jnp.zeros((), f32)
        count = jnp.zeros((), f32)
        for bucket in buckets:
            cap = bucket[1].shape[1]
            total, count = _walk_bucket_chunks(bucket, cap, k, rm_local,
                                               err_chunk, (total, count))
        total = lax.psum(lax.psum(total, DATA_AXIS), MODEL_AXIS)
        count = lax.psum(lax.psum(count, DATA_AXIS), MODEL_AXIS)
        return total, count

    def run(item_f0, user_f0, ub, ib, u_split, i_split):
        def body(carry, _):
            user_f, item_f = carry
            user_f = half_step(item_f, n_users_pad, ub, u_split, n_usplit)
            item_f = half_step(user_f, n_items_pad, ib, i_split, n_isplit)
            if compute_rmse:
                with scope("als.rmse"):
                    total, count = sq_err(user_f, item_f, ub)
                    rmse = jnp.sqrt(jnp.maximum(total, 0.0)
                                    / jnp.maximum(count, 1.0))
            else:
                rmse = jnp.zeros((), f32)
            return (user_f, item_f), rmse

        (user_f, item_f), rmses = lax.scan(
            body, (user_f0, item_f0), xs=None, length=n_steps)
        return user_f, item_f, rmses

    factor_spec = P(MODEL_AXIS, None)
    shard = jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(factor_spec, factor_spec, bucket_specs(seg_u),
                  bucket_specs(seg_i), P(), P()),
        out_specs=(factor_spec, factor_spec, P()),
        check_vma=False,  # pallas gj solver carries no vma info
    )
    from predictionio_tpu.utils.profiling import metered_jit

    return metered_jit(shard, label="als_sharded.train_steps")
