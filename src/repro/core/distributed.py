"""SPMD realization of the federated rounds via ``shard_map``.

Workers live on a 1-D ``workers`` mesh axis (on the production mesh this is
the flattened (pod, data) axes — see launch/mesh.py). Each device owns
``local_n = n // axis_size`` workers: its slice of the A_i matrices and of the
per-worker shifts W. The server iterate x is replicated.

Key adaptation (DESIGN.md §2): the downlink messages Q_i(delta) are *not*
moved over the interconnect. The Bernoulli coin, the compressor key and the
replicated delta are shared, so every worker materializes its own message
locally (`zero-byte correlated broadcast`). The only real collectives are the
uplink ``psum`` of subgradients and scalars — exactly what the roofline
measures.

The module exposes:
  * :func:`make_marina_p_spmd_step` — Algorithm 2 as one jitted SPMD program;
  * :func:`make_ef21p_spmd_step`    — Algorithm 1 likewise;
  * both numerically equivalent to the single-process references in
    ef21p.py / marina_p.py (tested in tests/test_distributed.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .compressors import RandK, TopK
from .problems import paper_sign
from .stepsizes import Stepsize


class SpmdMarinaPState(NamedTuple):
    x: jax.Array  # [d] replicated
    W: jax.Array  # [n, d] sharded over workers
    t: jax.Array  # scalar


class SpmdEF21PState(NamedTuple):
    x: jax.Array  # [d] replicated
    w: jax.Array  # [d] replicated (synchronized shift)
    t: jax.Array


# ---------------------------------------------------------------------------
# helpers shared by both algorithms
# ---------------------------------------------------------------------------


def _local_subgrads(A_local, W_local):
    """df_i(w_i) = A_i^T sign(A_i w_i) for the local worker slice."""
    y = jnp.einsum("nij,nj->ni", A_local, W_local)
    g = jnp.einsum("nij,ni->nj", A_local, paper_sign(y))
    f = jnp.sum(jnp.abs(y), axis=-1)
    return g, f


def _randk_mask(key, d, k):
    idx = jax.random.choice(key, d, shape=(k,), replace=False)
    return jnp.zeros((d,)).at[idx].set(1.0)


# ---------------------------------------------------------------------------
# MARINA-P SPMD
# ---------------------------------------------------------------------------


def make_marina_p_spmd_step(
    mesh: Mesh,
    *,
    n: int,
    d: int,
    mode: str,
    k: int,
    p: float,
    stepsize: Stepsize,
    axis: str = "workers",
):
    """One SPMD MARINA-P round. A: [n,d,d] sharded over workers."""
    axis_size = mesh.shape[axis]
    assert n % axis_size == 0, (n, axis_size)
    local_n = n // axis_size

    def round_fn(x, W, t, A, key):
        # everything below runs per-shard; collectives are explicit psums.
        me = jax.lax.axis_index(axis)
        g_local, f_local = _local_subgrads(A, W)  # [local_n, d], [local_n]
        # ---- uplink: exact aggregation (the only real collective) ----------
        g = jax.lax.psum(jnp.sum(g_local, axis=0), axis) / n
        f_w = jax.lax.psum(jnp.sum(f_local), axis) / n
        g_sq_mean = jax.lax.psum(jnp.sum(jnp.sum(g_local**2, axis=-1)), axis) / n
        aux = {"f_w": f_w, "g_norm_sq": jnp.sum(g**2), "g_sq_mean": g_sq_mean}
        gamma = stepsize(t, aux)
        x_new = x - gamma * g
        delta = x_new - x
        # ---- downlink: materialized locally from shared randomness ---------
        k_bern, k_comp = jax.random.split(key)
        c = jax.random.bernoulli(k_bern, p)
        gids = me * local_n + jnp.arange(local_n)  # global worker ids
        if mode == "same":
            mask = _randk_mask(k_comp, d, k)
            Q = jnp.broadcast_to(mask * delta * (d / k), (local_n, d))
        elif mode == "ind":
            # per-worker keys via split, matching marina_p.make_broadcast
            # exactly (fold_in would give different masks than the reference)
            keys = jax.random.split(k_comp, n)

            def one(gid):
                return _randk_mask(keys[gid], d, k) * delta * (d / k)

            Q = jax.vmap(one)(gids)
        elif mode == "perm":
            q = d // n
            perm = jax.random.permutation(k_comp, d)

            def one(gid):
                block = jax.lax.dynamic_slice(perm, (gid * q,), (q,))
                m = jnp.zeros((d,)).at[block].set(1.0)
                rem = d - q * n
                if rem:
                    tail = jax.lax.dynamic_slice(perm, (q * n,), (rem,))
                    m = m + jnp.where(
                        gid == 0, jnp.zeros((d,)).at[tail].set(1.0), jnp.zeros((d,))
                    )
                return m * delta * n

            Q = jax.vmap(one)(gids)
        else:
            raise ValueError(mode)
        W_new = jnp.where(c, jnp.broadcast_to(x_new, W.shape), W + Q)
        metrics = {
            "f_w": f_w,
            "gamma": gamma,
            "full_sync": c.astype(jnp.float32),
            "q_nnz_mean": jax.lax.psum(
                jnp.sum(jnp.sum(Q != 0, axis=-1).astype(jnp.float32)), axis
            )
            / n,
        }
        return x_new, W_new, t + 1, metrics

    sharded = jax.shard_map(
        round_fn,
        mesh=mesh,
        in_specs=(P(), P(axis), P(), P(axis), P()),
        out_specs=(P(), P(axis), P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# EF21-P SPMD
# ---------------------------------------------------------------------------


def make_ef21p_spmd_step(
    mesh: Mesh,
    *,
    n: int,
    d: int,
    k: int,
    stepsize: Stepsize,
    axis: str = "workers",
):
    """One SPMD EF21-P round with TopK downlink. A: [n,d,d] sharded."""
    axis_size = mesh.shape[axis]
    assert n % axis_size == 0
    comp = TopK(k=k)

    def round_fn(x, w, t, A):
        W = jnp.broadcast_to(w, (A.shape[0], d))
        g_local, f_local = _local_subgrads(A, W)
        g = jax.lax.psum(jnp.sum(g_local, axis=0), axis) / n
        f_w = jax.lax.psum(jnp.sum(f_local), axis) / n
        aux = {"f_w": f_w, "g_norm_sq": jnp.sum(g**2)}
        gamma = stepsize(t, aux)
        x_new = x - gamma * g
        # TopK is deterministic: server and every worker compute the same
        # delta from the replicated (x_new - w); zero downlink bytes on-mesh.
        delta = comp(None, x_new - w)
        w_new = w + delta
        metrics = {"f_w": f_w, "gamma": gamma,
                   "delta_nnz": jnp.sum(delta != 0).astype(jnp.float32)}
        return x_new, w_new, t + 1, metrics

    sharded = jax.shard_map(
        round_fn,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(axis)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# convenience: place problem data on the mesh
# ---------------------------------------------------------------------------


def shard_problem(mesh: Mesh, A, axis: str = "workers"):
    sh = NamedSharding(mesh, P(axis))
    return jax.device_put(A, sh)
