"""On-device bitstream pack/unpack kernels (Pallas TPU).

Implements the wire format's bit layout (repro/wire/bitstream.py: LSB-first
into little-endian uint32 words) on-device, so index/value streams of a
sparse downlink message can be packed before ever touching the host
(DESIGN.md §3.4). Bit-interchangeable with the host numpy codec — asserted
in tests/test_wire.py.

Tiling: 32 values of ``width`` bits fill exactly ``width`` words, so the
stream splits into independent word-aligned groups of 32 values. A grid step
takes 128 runs of 4096 values (a ``[128, 4096]`` block: 128 groups per run)
and emits their ``128 * width`` word rows of 128 lanes. Inside the step the
runs are moved onto lanes with 128 x 128 XLU transposes, so that every
value slot ``i`` of the 128 groups of all 128 runs is one ``[128, 128]``
strided load; each output word is an OR of a few such shifted loads, stored
with a stride of ``width`` rows and transposed back into stream order. All
vector work is whole-vreg and every index is static: no scatter, no gather,
no cross-lane shuffles beyond the transposes, and both blocks are dense
(8, 128)-tiled arrays, the layout Mosaic needs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import resolve_interpret

GROUP = 32                 # values per word-aligned group: 32 values fill `width` words
RUN = 128 * GROUP          # values per block row (128 groups)
BLOCK = 128 * RUN          # values per grid step


def _pack_kernel(v_ref, out_ref, vt_ref, wt_ref, *, width: int):
    # vt[32A + i, r] = value i of group A in run r (runs moved onto lanes)
    for a in range(RUN // 128):
        vt_ref[128 * a:128 * (a + 1), :] = v_ref[:, 128 * a:128 * (a + 1)].T
    # value i starts at bit i*width: its low part lands in word
    # (i*width)//32 at offset (i*width)%32, the bits past 32 in the next word
    for k in range(width):
        word = None
        for i in range(GROUP):
            lo_word, off = divmod(i * width, 32)
            if lo_word == k:
                part = vt_ref[pl.ds(i, 128, stride=GROUP), :] << off
            elif lo_word + 1 == k and off + width > 32:
                part = vt_ref[pl.ds(i, 128, stride=GROUP), :] >> (32 - off)
            else:
                continue
            word = part if word is None else word | part
        wt_ref[pl.ds(k, 128, stride=width), :] = word  # wt[A*width + k, r]
    # run r's words are wt[:, r] in stream order: 128-word chunk c of it is
    # output row r*width + c
    for c in range(width):
        out_ref[pl.ds(c, 128, stride=width), :] = wt_ref[128 * c:128 * (c + 1), :].T


def _unpack_kernel(w_ref, out_ref, vt_ref, wt_ref, *, width: int):
    for c in range(width):
        wt_ref[128 * c:128 * (c + 1), :] = w_ref[pl.ds(c, 128, stride=width), :].T
    for i in range(GROUP):
        k, off = divmod(i * width, 32)
        v = wt_ref[pl.ds(k, 128, stride=width), :] >> off
        if off + width > 32:
            v = v | (wt_ref[pl.ds(k + 1, 128, stride=width), :] << (32 - off))
        if width < 32:
            v = v & ((1 << width) - 1)
        vt_ref[pl.ds(i, 128, stride=GROUP), :] = v
    for a in range(RUN // 128):
        out_ref[:, 128 * a:128 * (a + 1)] = vt_ref[128 * a:128 * (a + 1), :].T


def _blocked_call(kernel, x, in_block, out_block, width: int, interpret: bool):
    steps = x.shape[0] // in_block[0]
    return pl.pallas_call(
        functools.partial(kernel, width=width),
        grid=(steps,),
        in_specs=[pl.BlockSpec(in_block, lambda j: (j, 0))],
        out_specs=pl.BlockSpec(out_block, lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((steps * out_block[0], out_block[1]),
                                       jnp.uint32),
        scratch_shapes=[pltpu.VMEM((RUN, 128), jnp.uint32),
                        pltpu.VMEM((128 * width, 128), jnp.uint32)],
        interpret=interpret,
    )(x)


def pack_bits_device(values: jax.Array, *, width: int,
                     interpret: bool | None = None) -> jax.Array:
    """values: [n] uint32, each < 2**width. Returns the ceil(n*width/32)
    packed words.

    ``interpret=None`` auto-detects via kernels/runtime.py (compiled on a
    real TPU, interpret under CPU tests; ``REPRO_PALLAS_INTERPRET`` forces).
    """
    n = values.shape[-1]
    if n == 0:
        return jnp.zeros((0,), jnp.uint32)
    steps = -(-n // BLOCK)
    v = jnp.pad(values.astype(jnp.uint32), (0, steps * BLOCK - n))
    out = _blocked_call(_pack_kernel, v.reshape(steps * 128, RUN), (128, RUN),
                        (128 * width, 128), width, resolve_interpret(interpret))
    return out.reshape(-1)[:-(-n * width // 32)]


def unpack_bits_device(words: jax.Array, *, width: int, count: int,
                       interpret: bool | None = None) -> jax.Array:
    """Inverse of :func:`pack_bits_device`: read ``count`` values of
    ``width`` bits from ``words`` ([nw] uint32; missing words read as 0)."""
    if count == 0:
        return jnp.zeros((0,), jnp.uint32)
    steps = -(-count // BLOCK)
    need = steps * BLOCK // 32 * width
    w = words.astype(jnp.uint32)[:need]
    w = jnp.pad(w, (0, need - w.shape[-1]))
    out = _blocked_call(_unpack_kernel, w.reshape(-1, 128), (128 * width, 128),
                        (128, RUN), width, resolve_interpret(interpret))
    return out.reshape(-1)[:count]
