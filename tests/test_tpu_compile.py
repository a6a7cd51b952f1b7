"""Every Pallas kernel compiles for a TPU v5e at real sizes.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology from shapes alone, and refuses what the
chip would refuse (unaligned blocks, unlowerable primitives, too much VMEM).
Interpret-mode tests cannot see any of that. Each test asserts that the
compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and test workers import every file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import encode as kenc
from repro.kernels import l1_subgrad, pack, permk, randk, topk
from repro.wire.spec import MagDType, index_width

D = 1 << 24
# one broadcast row of zamba2-1.2b cut to one pattern period (chip_smoke.py)
ROW = 326_119_488
WIDTHS = [1, 7, 13, 32]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, U32, I32 = jnp.float32, jnp.uint32, jnp.int32


@pytest.mark.parametrize("path", ["sparse", "mask", "topk", "dense", "rows"])
def test_encode_path_compiles(one_chip, path):
    d = ROW if path == "rows" else D
    m, iw = MagDType.FP32, index_width(d)
    sparse = lambda x: kenc._sparse_device(x, m=m, iw=iw, interpret=False)
    fn, shapes = {
        "sparse": (sparse, [((d,), F32)]),
        # encode_rows encodes each row of the broadcast on its own
        "rows": (sparse, [((d,), F32)]),
        "mask": (lambda x, w: kenc._mask_device(
            x, w, keep_prob=0.1, seed=7, m=m, block=1024, iw=iw, interpret=False),
            [((d,), F32), ((), I32)]),
        "topk": (lambda x: kenc._topk_device(
            x, k_per_block=64, m=m, block=1024, iw=iw, interpret=False),
            [((d,), F32)]),
        "dense": (lambda x: kenc._dense_device(x, m=m, interpret=False),
                  [((d,), F32)]),
    }[path]
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("width", WIDTHS)
def test_pack_compiles(one_chip, width):
    fn = lambda v: pack.pack_bits_device(v, width=width, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, ((D,), U32))


@pytest.mark.parametrize("width", WIDTHS)
def test_unpack_compiles(one_chip, width):
    fn = lambda w: pack.unpack_bits_device(w, width=width, count=D, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, ((D * width // 32,), U32))


@pytest.mark.parametrize("kernel", ["topk", "topk_bf16", "bernk", "rotk", "l1_subgrad"])
def test_kernel_compiles(one_chip, kernel):
    fn, shapes = {
        "topk": (lambda x: topk.block_topk_compress(
            x, k_per_block=64, block=1024, interpret=False), [((D,), F32)]),
        "topk_bf16": (lambda x: topk.block_topk_compress(
            x, k_per_block=64, block=1024, interpret=False), [((D,), jnp.bfloat16)]),
        "bernk": (lambda x, w: randk.bernk_compress(
            x, keep_prob=0.1, seed=3, worker=w, block=1024, interpret=False),
            [((D,), F32), ((), I32)]),
        "rotk": (lambda w, dl, r: permk.rotk_apply(
            w, dl, r, n=4, worker=1, block=1024, interpret=False),
            [((D,), F32), ((D,), F32), ((), I32)]),
        # the four-chip run's worker matrices: d = 8192
        "l1_subgrad": (lambda A, x: l1_subgrad.l1_subgrad(A, x, interpret=False),
                       [((8192, 8192), F32), ((8192,), F32)]),
    }[kernel]
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)
