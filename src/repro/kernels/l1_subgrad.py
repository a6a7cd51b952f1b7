"""Fused L1 subgradient kernel:  g = A^T sign(A x)  (Pallas TPU).

The inner oracle of the paper's experiment workload f_i(x) = ||A_i x||_1
(App. A): both matvecs and the sign fused in one kernel so the [d] intermediate
y = A x never round-trips to HBM.

Tiling: grid over row-blocks of A; per step an [R, d] tile of A and the full
x, y_r = A_r x; g accumulates A_r^T sign(y_r) across grid steps (output
revisited each step — Pallas sequential-grid accumulation). R and d must be
multiples of 8/128 respectively; the paper's d=1000 is padded to 1024 by
ops.py. sign(0)=+1 per paper eq. (32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .runtime import resolve_interpret


def _l1_subgrad_kernel(a_ref, x_ref, g_ref):
    i = pl.program_id(0)
    a = a_ref[...]  # [R, d]
    x = x_ref[...]  # [1, d]
    # both products stay 2-D (row vectors), the forms Mosaic lowers to the MXU
    y = jax.lax.dot_general(x, a, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # [1, R]
    s = jnp.where(y >= 0, 1.0, -1.0).astype(a.dtype)
    contrib = jnp.dot(s, a, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)  # [1, d]

    @pl.when(i == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    g_ref[...] += contrib.astype(g_ref.dtype)


def l1_subgrad(A: jax.Array, x: jax.Array, *, row_block: int = 128,
               interpret: bool | None = None) -> jax.Array:
    """A: [m, d] (m % row_block == 0, d % 128 == 0); x: [d] -> g: [d]."""
    interpret = resolve_interpret(interpret)
    m, d = A.shape
    assert m % row_block == 0 and d % 128 == 0, (m, d)
    rows = row_block  # halved while an A tile exceeds 2 MiB of VMEM
    while rows % 16 == 0 and rows * d > 1 << 19:
        rows //= 2
    out = pl.pallas_call(
        _l1_subgrad_kernel,
        grid=(m // rows,),
        in_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(A, x[None, :])
    return out[0]
