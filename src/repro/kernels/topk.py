"""Block-local magnitude TopK compression kernel (Pallas TPU).

TPU adaptation of the paper's TopK contractive compressor (DESIGN.md §2):
global top-k needs a sequential selection over d elements; the TPU-native
variant selects the top ``k`` per contiguous block of ``b`` elements,
entirely in VMEM. Contraction factor alpha = k/b (Definition 3 holds per
block, hence globally).

Selection is exact iterative extraction: k rounds of (masked) argmax with
first-index tie-breaking — the semantics of ``jax.lax.top_k``, so the
pure-jnp oracle in ref.py matches exactly.

Tiling: x is viewed as [nblocks, b], one compression block per row; a grid
step takes a tile of rows (kernels/runtime.row_tiling) and every reduction
runs along the lanes of its own row. b must be a multiple of 128 (lane
width).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .runtime import resolve_interpret, row_tiling

# rows of one block per grid step: the selection loop keeps a few
# [rows, b] f32 arrays live, so tiles stay small
_TOPK_TILE = 1 << 14


def select_topk(absx, k: int):
    """Keep mask (int32 0/1) of the first-index top-``k`` of each row of
    ``absx`` [R, b]: k rounds of per-row masked argmax."""
    b = absx.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, absx.shape, 1)

    def body(_, carry):
        remaining, keep = carry
        # first-index tie-break: pick smallest idx among the row's maxima
        m = jnp.max(remaining, axis=-1, keepdims=True)
        first = jnp.min(jnp.where(remaining == m, idx, b), axis=-1, keepdims=True)
        sel = idx == first
        return jnp.where(sel, -1.0, remaining), jnp.where(sel, 1, keep)

    keep0 = jnp.zeros(absx.shape, jnp.int32)
    _, keep = jax.lax.fori_loop(0, k, body, (absx, keep0))
    return keep


def _topk_block_kernel(x_ref, out_ref, *, k: int):
    x = x_ref[...]  # [R, b]
    keep = select_topk(jnp.abs(x).astype(jnp.float32), k)
    out_ref[...] = jnp.where(keep != 0, x, 0).astype(out_ref.dtype)


def block_topk_compress(x: jax.Array, *, k_per_block: int, block: int = 1024,
                        interpret: bool | None = None) -> jax.Array:
    """x: [d] (d % block == 0). Returns the sparsified vector (dense layout)."""
    d = x.shape[-1]
    assert d % block == 0 and block % 128 == 0, (d, block)
    rows, nrows = row_tiling(d // block, block, _TOPK_TILE)
    xb = jnp.pad(x, (0, nrows * block - d)).reshape(nrows, block)
    spec = pl.BlockSpec((rows, block), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_topk_block_kernel, k=k_per_block),
        grid=(nrows // rows,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((nrows, block), x.dtype),
        interpret=resolve_interpret(interpret),
    )(xb)
    return out.reshape(-1)[:d]
