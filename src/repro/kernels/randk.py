"""Counter-based Bernoulli-K sparsification kernel (Pallas TPU).

The jit-friendly RandK stand-in (BernK, omega = d/k - 1) regenerated from a
counter hash *inside* the kernel — zero HBM traffic for randomness, the
TPU-native way to materialize the paper's downlink messages from shared
seeds (DESIGN.md §2). Hash: 3-round xorshift-multiply of (seed, worker,
global index); ref.py implements the identical hash in jnp so outputs match
bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import resolve_interpret, row_tiling

_M1 = 2654435761
_M2 = 2246822519


def hash_uniform(idx: jax.Array, seed, worker) -> jax.Array:
    """Deterministic per-index uniform in [0,1). idx: uint32 array."""
    m1 = jnp.asarray(_M1, jnp.uint32)
    m2 = jnp.asarray(_M2, jnp.uint32)
    h = idx.astype(jnp.uint32) * m1
    h = h ^ (jnp.asarray(seed % (1 << 32), jnp.uint32) + jnp.asarray(worker, jnp.uint32) * m2)
    h = h ^ (h >> 15)
    h = h * m2
    h = h ^ (h >> 13)
    h = h * m1
    h = h ^ (h >> 16)
    # float32(h) as two exact 16-bit halves and one rounding of their sum:
    # bit-identical to h.astype(float32), which Mosaic cannot lower
    hi = jax.lax.bitcast_convert_type(h >> 16, jnp.int32).astype(jnp.float32)
    lo = jax.lax.bitcast_convert_type(h & jnp.uint32(0xFFFF), jnp.int32).astype(jnp.float32)
    return (hi * 65536.0 + lo) * (1.0 / 4294967296.0)


def _bernk_kernel(x_ref, w_ref, out_ref, *, keep_prob: float, seed: int, block: int):
    i = pl.program_id(0)
    x = x_ref[...]  # [R, b]: R compression blocks of b
    rows, lanes = x.shape
    row = i * rows + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    gidx = (row * block + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1))
    u = hash_uniform(gidx.astype(jnp.uint32), seed, w_ref[0])
    out_ref[...] = jnp.where(u < keep_prob, x / keep_prob, 0.0).astype(out_ref.dtype)


def bernk_compress(x: jax.Array, *, keep_prob: float, seed: int, worker=0,
                   block: int = 1024, interpret: bool | None = None) -> jax.Array:
    """x: [d] (d % block == 0). ``worker`` is an int or a traced int32
    scalar: it reaches the kernel through SMEM, so one compiled kernel
    serves every worker of a per-worker fan-out."""
    d = x.shape[-1]
    assert d % block == 0 and block % 128 == 0, (d, block)
    rows, nrows = row_tiling(d // block, block)
    spec = pl.BlockSpec((rows, block), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_bernk_kernel, keep_prob=keep_prob, seed=seed, block=block),
        grid=(nrows // rows,),
        in_specs=[spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((nrows, block), x.dtype),
        interpret=resolve_interpret(interpret),
    )(jnp.pad(x, (0, nrows * block - d)).reshape(nrows, block),
      jnp.asarray(worker, jnp.int32).reshape(1))
    return out.reshape(-1)[:d]
