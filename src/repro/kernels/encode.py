"""On-device compressor -> bitstream encode pipelines (Pallas TPU).

The host codecs (repro/wire) top out around ~0.5 GB/s, which makes encoding
the N per-worker compressed broadcasts of a MARINA-P round the downlink
bottleneck at scale (ROADMAP "on-device encode path and codec speed").
The pipelines here run compressor selection, stream extraction and
bit-packing (``kernels/pack.py``) on the device, so the packed uint32 words
leave it send-ready; the host contributes only the 16 fixed header/payload
bytes.

Paths — each **byte-identical** to the host codec on every input
(asserted by the differential harness in tests/test_encode_diff.py):

* :func:`topk_encode`  — one fused kernel selects each block's top-k and
  compacts it into (index, value) slots in index order, then the streams
  are packed: ``== wire.encode_sparse(ops.block_topk(x))``. Selection is
  kernels/topk.py's iterative extraction (first-index tie-break, the
  semantics of ``jax.lax.top_k``).
* :func:`mask_encode`  — the BernK kernel of kernels/randk.py (counter-hash
  mask + scale, seeded on device) feeds the streams, so the mask
  bit-matches the SEED codec's receiver-side rematerialization
  (wire/seedonly.py, BERN family with ``seed + round`` folded by the
  caller).
* :func:`sparse_encode` — streams for an arbitrary already-sparsified
  vector (the ``measure_wire`` call sites hold Q on device already).
* :func:`dense_encode` — DENSE codec payload for full-sync rounds.
* :func:`encode_rows` — the per-worker messages of a MARINA-style round,
  one row at a time; :func:`encode_per_worker` — N BernK streams of one
  shared vector in one device program (a ``lax.map`` over worker ids).

Dynamic sizing: the SPARSE layout is compacted by nonzero count, so one
scalar per message is read back to trim the word streams; everything else
stays on device with static shapes. Compaction is a stable argsort on the
validity mask (kept entries first, ascending index — exactly
``np.nonzero`` order).

``device_encode_enabled`` is the routing policy for the integration points
(wire/registry.py, core runs, train/downlink.py, fleet/cohort.py):
explicit override > ``REPRO_DEVICE_ENCODE`` env (1/0/auto) > backend
auto-detect (on for TPU, off for the interpret-mode CPU fallback, where
the numpy codec is faster).
"""
from __future__ import annotations

import functools
import os
import struct

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.wire import bitstream as bs
from repro.wire.spec import (
    MAG_BITS,
    CodecID,
    MagDType,
    index_width,
    mag_dtype,
    pack_header,
)

from . import pack as _pack
from .randk import bernk_compress
from .runtime import resolve_interpret, row_tiling
from .topk import select_topk

# Payload layouts mirror wire/sparse.py (the single source of the byte
# format is DESIGN.md §3.1/§3.4; these structs must match _PAYLOAD there).
_SPARSE_PAYLOAD = struct.Struct("<BxxxI")  # [u8 mag][pad x3][u32 count]
_DENSE_PAYLOAD = struct.Struct("<Bxxx")    # [u8 mag][pad x3]

DEVICE_ENCODE_ENV = "REPRO_DEVICE_ENCODE"


def device_encode_enabled(override: bool | None = None) -> bool:
    """Should an encode call site route through the fused device path?

    Precedence: explicit ``override`` > ``REPRO_DEVICE_ENCODE`` (1/0/auto)
    > backend auto-detect. Auto is on only for a real TPU backend: in
    interpret mode the Pallas bodies run as traced Python, where the host
    numpy codec is faster — the device path is for real accelerators (and
    for the differential/byte-identity tests, which force it on).
    """
    if override is not None:
        return bool(override)
    v = os.environ.get(DEVICE_ENCODE_ENV, "auto").strip().lower()
    if v in ("1", "true", "on", "yes"):
        return True
    if v in ("0", "false", "off", "no"):
        return False
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# fused top-k kernel
# ---------------------------------------------------------------------------

_ROW_TILE = 1 << 14  # f32 elements per top-k grid step (see kernels/topk.py)


def _topk_streams_kernel(x_ref, idx_ref, bits_ref, *, k: int, block: int):
    """Fused block-TopK: select + compact in one VMEM pass.

    Each row of the tile is one compression block. Selection is the exact
    iterative extraction of kernels/topk.py (k rounds of masked argmax,
    first-index tie-break). The selected entries are then drawn out in
    ascending index order, one slot per round (the smallest index still
    kept, and its f32 bit pattern by an integer compare-and-sum, exact for
    every payload: denormals would not survive a float sum under FTZ), so
    the concatenated per-row slots are already in global np.nonzero order.
    """
    i = pl.program_id(0)
    x = x_ref[...]  # [R, b] f32
    rows, b = x.shape
    ks = idx_ref.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, ks), 1)
    xbits = jax.lax.bitcast_convert_type(x, jnp.int32)

    def draw(s, carry):
        left, local, vbits = carry
        first = jnp.min(jnp.where(left != 0, lane, b), axis=-1, keepdims=True)
        hit = lane == first
        val = jnp.sum(jnp.where(hit, xbits, 0), axis=-1, keepdims=True)
        put = slot == s
        return (jnp.where(hit, 0, left), jnp.where(put, first, local),
                jnp.where(put, val, vbits))

    empty = jnp.zeros((rows, ks), jnp.int32)
    _, local, vbits = jax.lax.fori_loop(
        0, ks, draw, (select_topk(jnp.abs(x), k), empty, empty))
    row = i * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, ks), 0)
    idx_ref[...] = (row * block + local).astype(jnp.uint32)
    bits_ref[...] = jax.lax.bitcast_convert_type(vbits, jnp.uint32)


# ---------------------------------------------------------------------------
# device pipelines (jitted, static shapes; the count is a traced scalar)
# ---------------------------------------------------------------------------


def _pad_to(x, mult):
    return jnp.pad(x, (0, (-x.shape[-1]) % mult))


def _valbits(v, m: MagDType):
    """Bit pattern of ``v`` in the wire magnitude dtype, widened to u32.

    Matches the host codec's ``v.astype(fdt).view(udt)`` exactly: one
    round-to-nearest-even cast, then a pure bitcast.
    """
    if m == MagDType.FP32:
        return jax.lax.bitcast_convert_type(v, jnp.uint32)
    fdt = jnp.float16 if m == MagDType.FP16 else jnp.bfloat16
    return jax.lax.bitcast_convert_type(v.astype(fdt), jnp.uint16).astype(jnp.uint32)


def _pack_sparse(bits, idx, *, iw: int, m: MagDType, interpret: bool):
    """SPARSE streams of the f32 bit patterns ``bits`` ([n] u32): the
    entries with nonzero magnitude bits, in order, with their indices
    ``idx`` (None: the position in ``bits``).

    Works on bits, not floats, because the host codec's primitives are all
    bitwise (np.signbit = bit 31, np.abs = clear bit 31, np.nonzero =
    magnitude bits != 0) while XLA CPU flushes denormals to zero in float
    compares — a ``val != 0`` here would silently elide a denormal payload
    the host codec keeps. NaN/inf/-0.0 fall out exactly: -0.0 has zero
    magnitude bits (elided like the host), NaN magnitude bits are nonzero
    (kept like the host).

    Compaction is a stable argsort on the validity mask (kept entries
    first, ascending index — exactly ``np.nonzero`` order). Everything
    behind the count is zeroed, so packing the full-length streams leaves
    only zero bits past ``count * width``: the host codec's padding.
    """
    magbits = bits & jnp.uint32(0x7FFFFFFF)
    valid = magbits != 0
    order = jnp.argsort(jnp.logical_not(valid), stable=True)
    count = jnp.sum(valid, dtype=jnp.uint32)
    live = jnp.arange(bits.shape[-1], dtype=jnp.uint32) < count
    idx = order.astype(jnp.uint32) if idx is None else idx[order]
    vb = bits[order]
    mag = _valbits(jax.lax.bitcast_convert_type(vb & jnp.uint32(0x7FFFFFFF),
                                                jnp.float32), m)
    zero = jnp.uint32(0)
    pack = functools.partial(_pack.pack_bits_device, interpret=interpret)
    return (
        count,
        pack(jnp.where(live, idx, zero), width=iw),
        pack(jnp.where(live, vb >> jnp.uint32(31), zero), width=1),
        pack(jnp.where(live, mag, zero), width=MAG_BITS[m]),
    )


@functools.partial(jax.jit, static_argnames=("m", "iw", "interpret"))
def _sparse_device(x, *, m: MagDType, iw: int, interpret: bool):
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return _pack_sparse(bits, None, iw=iw, m=m, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("keep_prob", "seed", "m", "block", "iw", "interpret")
)
def _mask_device(x, worker, *, keep_prob: float, seed: int, m: MagDType,
                 block: int, iw: int, interpret: bool):
    """``worker`` is an int32 scalar operand (SMEM in the kernel), so the
    per-worker path maps it without recompiling."""
    vals = bernk_compress(_pad_to(x.astype(jnp.float32), block),
                          keep_prob=keep_prob, seed=seed, worker=worker,
                          block=block, interpret=interpret)
    bits = jax.lax.bitcast_convert_type(vals, jnp.uint32)
    return _pack_sparse(bits, None, iw=iw, m=m, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("k_per_block", "m", "block", "iw", "interpret")
)
def _topk_device(x, *, k_per_block: int, m: MagDType, block: int, iw: int,
                 interpret: bool):
    assert block % 128 == 0, block
    xp = _pad_to(x.astype(jnp.float32), block)
    rows, nrows = row_tiling(xp.shape[-1] // block, block, _ROW_TILE)
    xp = _pad_to(xp, nrows * block).reshape(nrows, block)
    ks = min(k_per_block, block)
    out_spec = pl.BlockSpec((rows, ks), lambda i: (i, 0))
    idx, bits = pl.pallas_call(
        functools.partial(_topk_streams_kernel, k=k_per_block, block=block),
        grid=(nrows // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[out_spec] * 2,
        out_shape=[jax.ShapeDtypeStruct((nrows, ks), jnp.uint32)] * 2,
        interpret=interpret,
    )(xp)
    return _pack_sparse(bits.reshape(-1), idx.reshape(-1), iw=iw, m=m,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def _dense_device(x, *, m: MagDType, interpret: bool):
    return _pack.pack_bits_device(_valbits(x.astype(jnp.float32), m),
                                  width=MAG_BITS[m], interpret=interpret)


# ---------------------------------------------------------------------------
# host assembly (16 fixed bytes + trimmed device words)
# ---------------------------------------------------------------------------


def _assemble_sparse(d: int, m: MagDType, count, widx, wsign, wmag) -> bytes:
    count = int(count)
    head = pack_header(CodecID.SPARSE, d) + _SPARSE_PAYLOAD.pack(int(m), count)
    if count == 0:
        return head
    iw = index_width(d)
    streams = jax.device_get((widx, wsign, wmag))
    return head + b"".join(
        s[: bs.n_words(count, w)].tobytes()
        for s, w in zip(streams, (iw, 1, MAG_BITS[m]))
    )


def sparse_encode(x, *, mag="fp32", block: int = 1024,
                  interpret: bool | None = None) -> bytes:
    """SPARSE-codec encode of an already-sparsified vector, fully on
    device. Byte-identical to ``wire.encode_sparse(np.asarray(x))``.

    ``block`` is the compression block of the compressor paths; a vector
    that arrives sparsified has none, so it does not change the stream."""
    m = mag_dtype(mag)
    x = jnp.asarray(x)
    d = x.shape[-1]
    if d == 0:
        return pack_header(CodecID.SPARSE, 0) + _SPARSE_PAYLOAD.pack(int(m), 0)
    count, widx, wsign, wmag = _sparse_device(
        x, m=m, iw=index_width(d), interpret=resolve_interpret(interpret))
    return _assemble_sparse(d, m, count, widx, wsign, wmag)


def topk_encode(x, *, k_per_block: int, block: int = 1024, mag="fp32",
                interpret: bool | None = None) -> bytes:
    """Fused block-TopK compress + SPARSE encode. Byte-identical to
    ``wire.encode_sparse(ops.block_topk(x, k_per_block=..., block=...))``."""
    m = mag_dtype(mag)
    x = jnp.asarray(x)
    d = x.shape[-1]
    count, widx, wsign, wmag = _topk_device(
        x, k_per_block=k_per_block, m=m, block=block, iw=index_width(d),
        interpret=resolve_interpret(interpret),
    )
    return _assemble_sparse(d, m, count, widx, wsign, wmag)


def mask_encode(x, *, keep_prob: float, seed: int, worker: int = 0,
                block: int = 1024, mag="fp32",
                interpret: bool | None = None) -> bytes:
    """Fused BernK compress + SPARSE encode, seeded on device.

    Byte-identical to ``wire.encode_sparse(ops.bernk(x, keep_prob=...,
    seed=..., worker=...))``; the mask bit-matches the SEED codec's BERN
    rematerialization (pass ``seed = msg.seed + msg.round`` for parity
    with wire/seedonly.apply_seed).
    """
    m = mag_dtype(mag)
    x = jnp.asarray(x)
    d = x.shape[-1]
    count, widx, wsign, wmag = _mask_device(
        x, jnp.int32(worker), keep_prob=keep_prob, seed=seed,
        m=m, block=block, iw=index_width(d),
        interpret=resolve_interpret(interpret),
    )
    return _assemble_sparse(d, m, count, widx, wsign, wmag)


def dense_encode(x, *, mag="fp32", block: int = 1024,
                 interpret: bool | None = None) -> bytes:
    """DENSE-codec encode on device (full-sync broadcast rounds).
    Byte-identical to ``wire.encode_dense(np.asarray(x))``. ``block`` as
    in :func:`sparse_encode`."""
    m = mag_dtype(mag)
    x = jnp.asarray(x)
    words = _dense_device(x, m=m, interpret=resolve_interpret(interpret))
    return (
        pack_header(CodecID.DENSE, x.shape[-1])
        + _DENSE_PAYLOAD.pack(int(m))
        + np.asarray(words).tobytes()
    )


# ---------------------------------------------------------------------------
# batched fan-out paths
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("keep_prob", "seed", "m", "block", "iw", "interpret")
)
def _workers_device(x, workers, *, keep_prob: float, seed: int, m: MagDType,
                    block: int, iw: int, interpret: bool):
    """The fused mask pipeline mapped over the worker ids: one shared input
    vector, N packed streams."""
    return jax.lax.map(
        lambda w: _mask_device(x, w, keep_prob=keep_prob, seed=seed, m=m,
                               block=block, iw=iw, interpret=interpret),
        workers)


def encode_rows(X, *, mag="fp32", block: int = 1024,
                interpret: bool | None = None) -> list[bytes]:
    """:func:`sparse_encode` of each message row of ``X`` ([n, d], or an
    iterable of [d] rows), one row at a time: device memory holds one
    row's streams in flight, not n (the n rows of a real model's broadcast
    do not fit at once). ``block`` as in :func:`sparse_encode`."""
    return [sparse_encode(row, mag=mag, interpret=interpret) for row in X]


def encode_per_worker(x, *, n_workers: int, keep_prob: float, seed: int,
                      mode: str = "ind", block: int = 1024, mag="fp32",
                      interpret: bool | None = None) -> list[bytes]:
    """N per-worker BernK streams from one shared input, batched on device.

    ``mode="ind"`` hashes each worker id independently (MARINA-P ind
    broadcast); ``mode="same"`` encodes worker 0 once and repeats the
    buffer (every message is identical). Each buffer is byte-identical to
    the matching :func:`mask_encode` call.
    """
    m = mag_dtype(mag)
    x = jnp.asarray(x)
    d = x.shape[-1]
    if mode == "same":
        buf = mask_encode(x, keep_prob=keep_prob, seed=seed, worker=0,
                          block=block, mag=mag, interpret=interpret)
        return [buf] * n_workers
    if mode != "ind":
        raise ValueError(f"encode_per_worker mode must be ind|same, got {mode!r}")
    workers = jnp.arange(n_workers, dtype=jnp.int32)
    counts, widx, wsign, wmag = _workers_device(
        x, workers, keep_prob=keep_prob, seed=seed, m=m, block=block,
        iw=index_width(d), interpret=resolve_interpret(interpret),
    )
    return [_assemble_sparse(d, m, c, widx[i], wsign[i], wmag[i])
            for i, c in enumerate(np.asarray(counts))]
