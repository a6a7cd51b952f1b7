"""Differential harness for the fused on-device encode kernels.

Gates kernels/encode.py against two independent implementations of the wire
format: the host numpy codec (wire/bitstream.py + wire/sparse.py) and the
pure-jnp oracle (kernels/ref.py). The contract is **byte identity** — not
allclose — on every case: packed word streams, whole SPARSE/DENSE messages,
weird IEEE payloads (NaN/±inf/−0.0/denormals, which XLA's FTZ would
silently eat in a float-compare implementation), degenerate shapes, and
the seeded BernK path whose mask must match the SEED codec's receiver-side
rematerialization. The fast tier runs a trimmed fuzz; the ``slow`` marker
carries the full sweep (CI tier1-slow).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import wire
from repro.kernels import encode as kenc
from repro.kernels import ops, ref, runtime

WIDTHS = [1, 4, 7, 8, 13, 16, 32]
MAGS = ["fp32", "fp16", "bf16"]

# every IEEE754 corner the stream extraction must pass through unchanged:
# NaN (payload kept), ±inf, -0.0 (zero magnitude bits => elided like
# np.nonzero), fp32 denormals (FTZ hazard), a bf16-rounding victim, and
# plain normals
WEIRD = np.array(
    [np.nan, np.inf, -np.inf, -0.0, 1e-42, -1e-42, 0.0, 6.1e-39,
     1.0000001, -3.5, 65504.0, 2.0],
    dtype=np.float32,
)


def _sparse_vec(rng, d, density):
    x = rng.standard_normal(d).astype(np.float32)
    return np.where(rng.random(d) < density, x, 0.0).astype(np.float32)


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32).tolist()


# -- pack level: host vs device kernel vs jnp oracle --------------------------


@settings(max_examples=20, deadline=None)
@given(width=st.sampled_from(WIDTHS), n=st.integers(1, 300),
       seed=st.integers(0, 2**31 - 1))
def test_pack_three_way_differential(width, n, seed):
    """Host packer, Pallas kernel, and jnp oracle emit identical words for
    arbitrary values — including non-word-aligned tails (n free-form)."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 1 << width, size=n, dtype=np.uint64).astype(np.uint32)
    host = wire.pack_u32(vals, width)
    oracle = np.asarray(ref.pack_bits_ref(jnp.asarray(vals), width))
    dev = np.asarray(ops.pack_bits(jnp.asarray(vals), width=width))
    assert wire.to_bytes(host) == wire.to_bytes(oracle) == wire.to_bytes(dev)
    # and all three unpackers invert to the same values
    for got in (
        wire.unpack_u32(host, width, n),
        np.asarray(ref.unpack_bits_ref(jnp.asarray(host), width, n)),
        np.asarray(ops.unpack_bits(jnp.asarray(host), width=width, count=n)),
    ):
        np.testing.assert_array_equal(got, vals)


# -- message level: fused pipelines vs host codec -----------------------------


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10**6), d=st.sampled_from([1, 5, 33, 100, 257, 512]),
       mag=st.sampled_from(MAGS), dens_pct=st.integers(0, 100))
def test_sparse_encode_differential(seed, d, mag, dens_pct):
    rng = np.random.default_rng(seed)
    x = _sparse_vec(rng, d, dens_pct / 100.0)
    assert kenc.sparse_encode(jnp.asarray(x), mag=mag, block=128) == \
        wire.encode_sparse(x, mag=mag)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10**6), d=st.sampled_from([1, 7, 100, 333]),
       mag=st.sampled_from(MAGS))
def test_dense_encode_differential(seed, d, mag):
    x = np.random.default_rng(seed).standard_normal(d).astype(np.float32)
    assert kenc.dense_encode(jnp.asarray(x), mag=mag, block=128) == \
        wire.encode_dense(x, mag=mag)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10**6), d=st.sampled_from([128, 250, 384]),
       k=st.sampled_from([1, 8, 128, 200]), mag=st.sampled_from(MAGS))
def test_topk_encode_differential(seed, d, k, mag):
    """Fused select+encode == host codec over the standalone TopK kernel —
    including k >= block (selects everything, zeros elided in stream)."""
    x = np.random.default_rng(seed).standard_normal(d).astype(np.float32)
    xj = jnp.asarray(x)
    want = wire.encode_sparse(
        np.asarray(ops.block_topk(xj, k_per_block=k, block=128)), mag=mag)
    assert kenc.topk_encode(xj, k_per_block=k, block=128, mag=mag) == want


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), worker=st.integers(0, 7),
       keep_pct=st.sampled_from([3, 25, 90]), mag=st.sampled_from(MAGS))
def test_mask_encode_differential(seed, worker, keep_pct, mag):
    """Fused BernK mask+encode == host codec over the standalone kernel."""
    d, keep = 384, keep_pct / 100.0
    x = np.random.default_rng(seed % 10**6).standard_normal(d).astype(np.float32)
    xj = jnp.asarray(x)
    want = wire.encode_sparse(
        np.asarray(ops.bernk(xj, keep_prob=keep, seed=seed, worker=worker,
                             block=128)), mag=mag)
    assert kenc.mask_encode(xj, keep_prob=keep, seed=seed, worker=worker,
                            block=128, mag=mag) == want


def test_encode_rows_matches_per_row():
    rng = np.random.default_rng(0)
    X = np.stack([_sparse_vec(rng, 300, 0.1) for _ in range(3)])
    got = kenc.encode_rows(jnp.asarray(X), block=128)
    assert got == [kenc.sparse_encode(jnp.asarray(X[i]), block=128)
                   for i in range(3)]
    assert got == [wire.encode_sparse(X[i]) for i in range(3)]


# -- IEEE edge payloads (byte + decode round-trip agreement) ------------------


@pytest.mark.parametrize("mag", MAGS)
def test_edge_values_sparse(mag):
    buf_host = wire.encode_sparse(WEIRD, mag=mag)
    buf_dev = kenc.sparse_encode(jnp.asarray(WEIRD), mag=mag, block=128)
    assert buf_dev == buf_host
    # decoded values agree bit-for-bit (NaN payloads included)
    assert _bits(wire.decode(buf_dev)) == _bits(wire.decode(buf_host))


@pytest.mark.parametrize("mag", MAGS)
def test_edge_values_dense(mag):
    buf_host = wire.encode_dense(WEIRD, mag=mag)
    buf_dev = kenc.dense_encode(jnp.asarray(WEIRD), mag=mag, block=128)
    assert buf_dev == buf_host
    assert _bits(wire.decode(buf_dev)) == _bits(wire.decode(buf_host))


def test_edge_values_topk():
    """TopK over NaN/inf/denormal payloads: selection and streams match the
    standalone kernel + host codec byte-for-byte."""
    xj = jnp.asarray(WEIRD)
    want = wire.encode_sparse(np.asarray(ops.block_topk(xj, k_per_block=4,
                                                        block=128)))
    assert kenc.topk_encode(xj, k_per_block=4, block=128) == want


def test_all_zero_and_empty_messages():
    z = np.zeros(100, np.float32)
    buf = kenc.sparse_encode(jnp.asarray(z), block=128)
    assert buf == wire.encode_sparse(z)
    np.testing.assert_array_equal(wire.decode(buf), z)
    assert kenc.sparse_encode(jnp.zeros(0, jnp.float32)) == \
        wire.encode_sparse(np.zeros(0, np.float32))


def test_size_one_message():
    for v in (2.5, 0.0, -0.0):
        x = np.array([v], np.float32)
        assert kenc.sparse_encode(jnp.asarray(x)) == wire.encode_sparse(x)
        assert kenc.dense_encode(jnp.asarray(x)) == wire.encode_dense(x)


def test_topk_k_ge_d():
    x = np.random.default_rng(1).standard_normal(96).astype(np.float32)
    xj = jnp.asarray(x)
    want = wire.encode_sparse(np.asarray(ops.block_topk(xj, k_per_block=128,
                                                        block=128)))
    assert kenc.topk_encode(xj, k_per_block=128, block=128) == want


def test_truncated_fused_buffers_raise_typed_errors():
    """Decoding a cut fused buffer fails with the codec's typed errors, not
    garbage output — the device path produces real wire frames."""
    x = _sparse_vec(np.random.default_rng(2), 200, 0.2)
    for buf in (kenc.sparse_encode(jnp.asarray(x), block=128),
                kenc.dense_encode(jnp.asarray(x), block=128)):
        with pytest.raises(wire.TruncatedFrame):
            wire.decode(buf[:-1])
        with pytest.raises(wire.WireError):
            wire.decode(buf[:6])  # inside the common header
        bad = bytearray(buf)
        bad[0] ^= 0xFF  # magic
        with pytest.raises(wire.CorruptFrame):
            wire.decode(bytes(bad))


# -- seeded determinism -------------------------------------------------------


def test_mask_encode_deterministic_across_paths():
    """Same (seed, worker) => identical packed bytes from the scalar path,
    a different block size, explicit interpret, and the vmapped per-worker
    batch — the counter hash is global-index keyed, so layout can't leak
    into the stream."""
    x = np.random.default_rng(3).standard_normal(512).astype(np.float32)
    xj = jnp.asarray(x)
    kw = dict(keep_prob=0.25, seed=42)
    b1 = kenc.mask_encode(xj, worker=3, block=128, **kw)
    assert b1 == kenc.mask_encode(xj, worker=3, block=256, **kw)
    assert b1 == kenc.mask_encode(xj, worker=3, block=128, interpret=True, **kw)
    batch = kenc.encode_per_worker(xj, n_workers=5, mode="ind", block=128, **kw)
    assert batch[3] == b1
    assert len(set(batch)) == 5  # distinct workers => distinct masks
    same = kenc.encode_per_worker(xj, n_workers=4, mode="same", block=128, **kw)
    assert same == [kenc.mask_encode(xj, worker=0, block=128, **kw)] * 4


def test_mask_encode_matches_seed_codec_bern():
    """mask_encode(seed = msg.seed + msg.round) reproduces exactly what a
    SEED-codec receiver rematerializes (wire/seedonly.py BERN family)."""
    delta = np.random.default_rng(4).standard_normal(384).astype(np.float32)
    msg = wire.SeedMessage(family=wire.SeedFamily.BERN, seed=7, round=5,
                           scale=1.0, n=4, worker=2, param=0.25)
    want = wire.apply_seed(msg, delta)
    buf = kenc.mask_encode(jnp.asarray(delta), keep_prob=0.25,
                           seed=msg.seed + msg.round, worker=msg.worker,
                           block=128)
    assert _bits(wire.decode(buf)) == _bits(want)


def test_ind_broadcast_uses_split_not_fold_in():
    """Regression guard for the PR-1 key-derivation fix: ind-mode per-worker
    keys come from jax.random.split, and the SPMD path (core/distributed.py)
    regenerates the same masks from the same keys, so a silent drift on
    either side would desynchronize server and workers.

    With ``jax_threefry_partitionable`` on, ``split(k, n)[i]`` equals
    ``fold_in(k, i)``, so the two derivations can no longer be told apart
    by their output. The guard is therefore the split-derived reference
    plus a cross-check against the SPMD round itself."""
    from jax.sharding import Mesh

    from repro.core import distributed, marina_p, problems, stepsizes
    from repro.core.compressors import RandK
    from repro.core.marina_p import make_broadcast

    n, k, d = 4, 16, 128
    bcast, _ = make_broadcast("ind", n, k)
    key = jax.random.PRNGKey(9)
    delta = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    Q = np.asarray(bcast(key, delta))
    comp = RandK(k=k)
    keys = jax.random.split(key, n)
    want = np.asarray(jax.vmap(lambda kk: comp(kk, delta))(keys))
    np.testing.assert_array_equal(Q, want)
    assert len({tuple(row) for row in (Q != 0)}) == n  # one key per worker

    # one round (p=0: never a sync) of the reference and of the SPMD
    # program on a one-device mesh: the same key must give every worker
    # the same mask on both sides
    prob = problems.generate_problem(n=n, d=d, noise_scale=1.0, seed=3)
    mesh = Mesh(np.array(jax.devices()[:1]), ("workers",))
    ss = stepsizes.Constant(gamma=0.05)
    state = marina_p.init(prob.x0, n)
    ref, _ = marina_p.make_step(prob, "ind", k=k, p=0.0, stepsize=ss)(state, key)
    spmd = distributed.make_marina_p_spmd_step(
        mesh, n=n, d=d, mode="ind", k=k, p=0.0, stepsize=ss)
    _, W, _, _ = spmd(state.x, state.W, state.t, prob.A, key)
    q_ref = np.asarray(ref.W - state.W)
    q_spmd = np.asarray(W - state.W)
    np.testing.assert_array_equal(q_ref != 0, q_spmd != 0)
    np.testing.assert_allclose(q_spmd, q_ref, rtol=1e-5, atol=1e-6)


# -- interpret / device-encode knobs ------------------------------------------


def test_interpret_env_knob(monkeypatch):
    monkeypatch.setenv(runtime.ENV_VAR, "1")
    assert runtime.default_interpret() is True
    monkeypatch.setenv(runtime.ENV_VAR, "off")
    assert runtime.default_interpret() is False
    monkeypatch.setenv(runtime.ENV_VAR, "auto")
    assert runtime.default_interpret() is (jax.default_backend() != "tpu")
    monkeypatch.delenv(runtime.ENV_VAR, raising=False)
    assert runtime.resolve_interpret(None) == runtime.default_interpret()
    assert runtime.resolve_interpret(True) is True
    assert runtime.resolve_interpret(False) is False


def test_device_encode_env_knob(monkeypatch):
    monkeypatch.setenv(kenc.DEVICE_ENCODE_ENV, "1")
    assert kenc.device_encode_enabled() is True
    monkeypatch.setenv(kenc.DEVICE_ENCODE_ENV, "0")
    assert kenc.device_encode_enabled() is False
    assert kenc.device_encode_enabled(True) is True  # override beats env
    monkeypatch.setenv(kenc.DEVICE_ENCODE_ENV, "auto")
    assert kenc.device_encode_enabled() is (jax.default_backend() == "tpu")


def test_registry_device_fast_path():
    """wire.encode(device_encode=True) on a jax array routes through the
    fused kernels and still emits the host codec's exact bytes; numpy
    inputs silently keep the host path."""
    x = _sparse_vec(np.random.default_rng(5), 200, 0.1)
    xj = jnp.asarray(x)
    assert wire.encode(xj, device_encode=True) == wire.encode(x, device_encode=False)
    from repro.core.compressors import Identity

    assert wire.encode(xj, Identity(), device_encode=True) == \
        wire.encode(x, Identity(), device_encode=False)
    assert wire.encode(x, device_encode=True) == wire.encode(x)  # numpy: host


# -- full fuzz sweep (CI tier1-slow) ------------------------------------------


@pytest.mark.slow
@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       d=st.sampled_from([1, 33, 257, 512, 1000, 2048]),
       mag=st.sampled_from(MAGS), dens_pct=st.integers(0, 100),
       block=st.sampled_from([128, 256, 1024]))
def test_sparse_encode_fuzz_sweep(seed, d, mag, dens_pct, block):
    rng = np.random.default_rng(seed)
    x = _sparse_vec(rng, d, dens_pct / 100.0)
    # sprinkle IEEE corners into live coordinates
    live = np.nonzero(x)[0]
    if live.size:
        x[live[: WEIRD.size]] = WEIRD[: live.size]
    assert kenc.sparse_encode(jnp.asarray(x), mag=mag, block=block) == \
        wire.encode_sparse(x, mag=mag)


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), worker=st.integers(0, 31),
       keep_pct=st.integers(1, 99), mag=st.sampled_from(MAGS))
def test_mask_encode_fuzz_sweep(seed, worker, keep_pct, mag):
    d, keep = 1024, keep_pct / 100.0
    x = np.random.default_rng(seed % 10**6).standard_normal(d).astype(np.float32)
    xj = jnp.asarray(x)
    want = wire.encode_sparse(
        np.asarray(ops.bernk(xj, keep_prob=keep, seed=seed, worker=worker)),
        mag=mag)
    assert kenc.mask_encode(xj, keep_prob=keep, seed=seed, worker=worker,
                            mag=mag) == want


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10**6), d=st.sampled_from([512, 1000, 2048]),
       k=st.sampled_from([1, 16, 64, 256, 300]), mag=st.sampled_from(MAGS))
def test_topk_encode_fuzz_sweep(seed, d, k, mag):
    x = np.random.default_rng(seed).standard_normal(d).astype(np.float32)
    xj = jnp.asarray(x)
    want = wire.encode_sparse(
        np.asarray(ops.block_topk(xj, k_per_block=k, block=256)), mag=mag)
    assert kenc.topk_encode(xj, k_per_block=k, block=256, mag=mag) == want


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.sampled_from([2, 5, 8]))
def test_encode_per_worker_fuzz_sweep(seed, n):
    x = np.random.default_rng(seed % 10**6).standard_normal(512).astype(np.float32)
    xj = jnp.asarray(x)
    batch = kenc.encode_per_worker(xj, n_workers=n, keep_prob=0.1, seed=seed,
                                   mode="ind", block=128)
    for w in range(n):
        want = wire.encode_sparse(np.asarray(
            ops.bernk(xj, keep_prob=0.1, seed=seed, worker=w, block=128)))
        assert batch[w] == want
