"""RotK (cyclic-partition PermK) apply kernel (Pallas TPU).

Fuses the MARINA-P worker update  w += Q_i(delta)  where
Q_i(delta)_j = n * delta_j * [j mod n == (worker + r) mod n]
into a single VMEM pass: iota-compare mask, scale, accumulate. No index
arrays ever touch HBM — the message is materialized from (r, worker, n)
(zero-byte correlated broadcast, DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import resolve_interpret, row_tiling


def _rotk_apply_kernel(w_ref, delta_ref, rot_ref, out_ref, *, n: int, worker: int, block: int):
    i = pl.program_id(0)
    w = w_ref[...]  # [R, b]
    rows = w.shape[0]
    row = i * rows + jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
    gidx = row * block + jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    keep = (gidx % n) == ((worker + rot_ref[0]) % n)
    out_ref[...] = (w + jnp.where(keep, delta_ref[...] * n, 0.0)).astype(out_ref.dtype)


def rotk_apply(w: jax.Array, delta: jax.Array, rotation: jax.Array, *, n: int,
               worker: int, block: int = 1024,
               interpret: bool | None = None) -> jax.Array:
    """w, delta: [d]; rotation: int32 scalar array (read from SMEM).
    Returns w + Q_i(delta)."""
    d = w.shape[-1]
    assert d % block == 0 and block % 128 == 0, (d, block)
    rows, nrows = row_tiling(d // block, block)
    spec = pl.BlockSpec((rows, block), lambda i: (i, 0))
    tile = lambda a: jnp.pad(a, (0, nrows * block - d)).reshape(nrows, block)
    out = pl.pallas_call(
        functools.partial(_rotk_apply_kernel, n=n, worker=worker, block=block),
        grid=(nrows // rows,),
        in_specs=[spec, spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((nrows, block), w.dtype),
        interpret=resolve_interpret(interpret),
    )(tile(w), tile(delta), jnp.asarray(rotation, jnp.int32).reshape(1))
    return out.reshape(-1)[:d]
