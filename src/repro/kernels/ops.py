"""Jitted public wrappers for the Pallas kernels.

``interpret`` defaults to the shared policy in kernels/runtime.py: True
off-TPU (this container is CPU-only; the kernel bodies execute in Python
for correctness validation), False on a real TPU backend, overridable via
``REPRO_PALLAS_INTERPRET``. Shapes are padded to tile multiples and
unpadded here so callers can pass arbitrary d (e.g. the paper's d=1000).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import l1_subgrad as _l1
from . import pack as _pack
from . import permk as _permk
from . import randk as _randk
from . import topk as _topk


def _pad_to(x, mult):
    d = x.shape[-1]
    pad = (-d) % mult
    return (jnp.pad(x, (0, pad)), d) if pad else (x, d)


@partial(jax.jit, static_argnames=("k_per_block", "block", "interpret"))
def block_topk(x, *, k_per_block: int, block: int = 1024, interpret: bool | None = None):
    xp, d = _pad_to(x, block)
    out = _topk.block_topk_compress(xp, k_per_block=k_per_block, block=block, interpret=interpret)
    return out[:d]


@partial(jax.jit, static_argnames=("keep_prob", "seed", "worker", "block", "interpret"))
def bernk(x, *, keep_prob: float, seed: int, worker: int = 0, block: int = 1024,
          interpret: bool | None = None):
    xp, d = _pad_to(x, block)
    out = _randk.bernk_compress(
        xp, keep_prob=keep_prob, seed=seed, worker=worker, block=block, interpret=interpret
    )
    return out[:d]


@partial(jax.jit, static_argnames=("n", "worker", "block", "interpret"))
def rotk_apply(w, delta, rotation, *, n: int, worker: int, block: int = 1024,
               interpret: bool | None = None):
    wp, d = _pad_to(w, block)
    dp, _ = _pad_to(delta, block)
    out = _permk.rotk_apply(wp, dp, rotation, n=n, worker=worker, block=block, interpret=interpret)
    return out[:d]


@partial(jax.jit, static_argnames=("width", "interpret"))
def pack_bits(values, *, width: int, interpret: bool | None = None):
    """Bit-pack ``values`` ([n] uint32, each < 2**width) into the
    ceil(n*width/32) uint32 words of the wire/bitstream.py layout."""
    return _pack.pack_bits_device(values, width=width, interpret=interpret)


@partial(jax.jit, static_argnames=("width", "count", "interpret"))
def unpack_bits(words, *, width: int, count: int, interpret: bool | None = None):
    """Inverse of :func:`pack_bits`: read ``count`` values of ``width`` bits."""
    return _pack.unpack_bits_device(words, width=width, count=count,
                                    interpret=interpret)


@partial(jax.jit, static_argnames=("row_block", "interpret"))
def l1_subgrad(A, x, *, row_block: int = 128, interpret: bool | None = None):
    """g = A^T sign(A x), padded to (row_block, 128) tiles. A: [m, d]."""
    m, d = A.shape
    pm, pd = (-m) % row_block, (-d) % 128
    Ap = jnp.pad(A, ((0, pm), (0, pd)))
    xp = jnp.pad(x, (0, pd))
    # NOTE: zero-pad rows give sign(0)=+1 contributions of zero rows => A_pad^T
    # row is zero, so padding is exact.
    g = _l1.l1_subgrad(Ap, xp, row_block=row_block, interpret=interpret)
    return g[:d]
