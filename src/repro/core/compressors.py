"""Compression operators (Definitions 2 & 3 of the paper).

Two families:

* Unbiased ``Q in U(omega)``:  E[Q(x)] = x,  E||Q(x)-x||^2 <= omega ||x||^2.
  Members: RandK (omega = d/K - 1), PermK (omega = n - 1), natural
  compression (omega = 1/8), identity (omega = 0).
* Contractive ``C in B(alpha)``:  E||C(x)-x||^2 <= (1-alpha) ||x||^2.
  Members: TopK (alpha = K/d), block-TopK (alpha = K_b/b per block — the
  TPU-native variant, see DESIGN.md §2), and any scaled unbiased compressor
  ``(omega+1)^{-1} Q in B((omega+1)^{-1})`` (Lemma 8 of Richtarik et al. 2021).

All operators are stateless: randomness comes from an explicit ``jax.random``
key, so the same key on server and worker materializes the same sparse message
without moving indices over the wire (the zero-byte correlated broadcast trick
from DESIGN.md §2). Operators act on flat vectors; :func:`tree_compress`
lifts them to parameter pytrees via ravel/unravel.

Expected density ``zeta`` (Definition 4) is exposed per operator for the
communication model.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np


Array = jax.Array


# ---------------------------------------------------------------------------
# Base classes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Compressor:
    """A (possibly randomized) mapping R^d -> R^d.

    Subclasses implement :meth:`__call__`. ``needs_key`` tells callers
    whether the operator consumes randomness.
    """

    name: str = dataclasses.field(default="compressor", init=False)

    def __call__(self, key: Optional[Array], x: Array) -> Array:  # pragma: no cover
        raise NotImplementedError

    # -- communication accounting -------------------------------------------------
    def expected_density(self, d: int) -> float:
        """zeta: expected number of non-zeros sent per message (Definition 4)."""
        raise NotImplementedError

    # -- theory constants -----------------------------------------------------------
    @property
    def needs_key(self) -> bool:
        return True


@dataclasses.dataclass(frozen=True)
class UnbiasedCompressor(Compressor):
    """Q in U(omega): E[Q(x)] = x and E||Q(x)-x||^2 <= omega ||x||^2."""

    def omega(self, d: int) -> float:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class ContractiveCompressor(Compressor):
    """C in B(alpha): E||C(x)-x||^2 <= (1-alpha) ||x||^2."""

    def alpha(self, d: int) -> float:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Identity
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Identity(UnbiasedCompressor, ContractiveCompressor):
    name: str = dataclasses.field(default="identity", init=False)

    def __call__(self, key, x):
        return x

    def omega(self, d):
        return 0.0

    def alpha(self, d):
        return 1.0

    def expected_density(self, d):
        return float(d)

    @property
    def needs_key(self):
        return False


# ---------------------------------------------------------------------------
# TopK (contractive, Definition 3)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TopK(ContractiveCompressor):
    """Global magnitude Top-K: keep the K largest-|.| coordinates.

    Deterministic; alpha = K/d.
    """

    k: int = 1
    name: str = dataclasses.field(default="topk", init=False)

    def __call__(self, key, x):
        d = x.shape[-1]
        k = min(self.k, d)
        _, idx = jax.lax.top_k(jnp.abs(x), k)
        mask = jnp.zeros_like(x).at[idx].set(1.0)
        return x * mask

    def alpha(self, d):
        return min(self.k, d) / d

    def expected_density(self, d):
        return float(min(self.k, d))

    @property
    def needs_key(self):
        return False


@dataclasses.dataclass(frozen=True)
class BlockTopK(ContractiveCompressor):
    """TPU-native block-local TopK: top-k_b per contiguous block of size b.

    Contractive with alpha = k_b/b (per-block contraction implies global).
    Total kept = k_b * ceil(d/b). This is the semantics the Pallas kernel
    (kernels/topk.py) implements on 8x128 VMEM tiles.
    """

    k_per_block: int = 16
    block: int = 1024
    name: str = dataclasses.field(default="block_topk", init=False)

    def __call__(self, key, x):
        d = x.shape[-1]
        b = self.block
        pad = (-d) % b
        xp = jnp.pad(x, (0, pad))
        xb = xp.reshape(-1, b)
        k = min(self.k_per_block, b)
        _, idx = jax.lax.top_k(jnp.abs(xb), k)
        mask = jnp.zeros_like(xb)
        mask = jax.vmap(lambda m, i: m.at[i].set(1.0))(mask, idx)
        out = (xb * mask).reshape(-1)[:d]
        return out

    def alpha(self, d):
        return min(self.k_per_block, self.block) / self.block

    def expected_density(self, d):
        nblocks = -(-d // self.block)
        return float(min(self.k_per_block, self.block) * nblocks)

    @property
    def needs_key(self):
        return False


# ---------------------------------------------------------------------------
# RandK (unbiased, Definition 2)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RandK(UnbiasedCompressor):
    """Uniform random-K sparsification with (d/K) rescaling.

    E[Q(x)] = x; omega = d/K - 1. A shared key across workers gives the
    paper's ``sameRandK``; per-worker folded keys give ``indRandK``.
    """

    k: int = 1
    name: str = dataclasses.field(default="randk", init=False)

    def __call__(self, key, x):
        d = x.shape[-1]
        k = min(self.k, d)
        idx = jax.random.choice(key, d, shape=(k,), replace=False)
        mask = jnp.zeros_like(x).at[idx].set(1.0)
        return x * mask * (d / k)

    def omega(self, d):
        k = min(self.k, d)
        return d / k - 1.0

    def expected_density(self, d):
        return float(min(self.k, d))


# ---------------------------------------------------------------------------
# PermK (correlated unbiased, Definition 5)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PermK(UnbiasedCompressor):
    """Permutation compressor for worker ``i`` of ``n`` (Definition 5).

    Requires d = q*n (handled by padding in tree_compress when needed).
    Q_i(x) = n * sum_{j in block i of a shared random permutation} x_j e_j.
    Across workers with the same key: (1/n) sum_i Q_i(x) = x exactly.
    omega = n - 1.
    """

    n: int = 1
    worker: int = 0
    name: str = dataclasses.field(default="permk", init=False)

    def __call__(self, key, x):
        d = x.shape[-1]
        q = d // self.n
        perm = jax.random.permutation(key, d)
        block = jax.lax.dynamic_slice(perm, (self.worker * q,), (q,))
        mask = jnp.zeros_like(x).at[block].set(1.0)
        out = x * mask * self.n
        # leftover coordinates (d not divisible by n) are assigned to worker 0
        rem = d - q * self.n
        if rem:
            tail = jax.lax.dynamic_slice(perm, (q * self.n,), (rem,))
            tmask = jnp.zeros_like(x).at[tail].set(1.0)
            out = jnp.where(self.worker == 0, out + x * tmask * self.n, out)
        return out

    def omega(self, d):
        return self.n - 1.0

    def expected_density(self, d):
        return float(-(-d // self.n))


def permk_family(n: int) -> list[PermK]:
    """The n correlated compressors {Q_i} of Definition 5."""
    return [PermK(n=n, worker=i) for i in range(n)]


# ---------------------------------------------------------------------------
# LM-scale jit-friendly variants (hardware adaptation, DESIGN.md §2)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RotK(UnbiasedCompressor):
    """TPU-native PermK: cyclic coordinate partition with a random rotation.

    Worker ``i`` of ``n`` keeps coordinates ``j`` with
    ``j mod n == (i + r) mod n`` where ``r ~ Uniform{0..n-1}`` is shared,
    scaled by ``n``. Properties (proved in tests/test_compressors.py):

    * exact partition:  (1/n) sum_i Q_i(x) = x  (PermK's key identity);
    * unbiased with omega = n - 1 (same as PermK): each coordinate is kept
      w.p. 1/n over the rotation, scaled by n;
    * zero index storage / O(1) mask materialization (iota + compare) — no
      d-sized scatter, so it scales to billions of parameters per leaf.

    vs. Definition 5's PermK: the partition is block-cyclic instead of a
    uniformly random permutation. The variance bound is identical; only the
    coordinate-correlation structure differs (documented in DESIGN.md §2).
    """

    n: int = 1
    worker: int = 0
    name: str = dataclasses.field(default="rotk", init=False)

    def __call__(self, key, x):
        d = x.shape[-1]
        r = jax.random.randint(key, (), 0, self.n)
        idx = jax.lax.iota(jnp.int32, d) % self.n
        mask = (idx == (self.worker + r) % self.n).astype(x.dtype)
        return x * mask * self.n

    def mask_for(self, key, d, worker):
        """Mask for a dynamic (traced) worker index — used by vmapped LM code."""
        r = jax.random.randint(key, (), 0, self.n)
        idx = jax.lax.iota(jnp.int32, d) % self.n
        return (idx == (worker + r) % self.n)

    def omega(self, d):
        return self.n - 1.0

    def expected_density(self, d):
        return float(-(-d // self.n))


@dataclasses.dataclass(frozen=True)
class BernK(UnbiasedCompressor):
    """Bernoulli sparsification: keep each coordinate w.p. q = k/d, scale 1/q.

    Unbiased with omega = d/k - 1 (identical to RandK) and expected density
    k, but mask materialization is a single uniform-compare — no
    no-replacement choice / scatter, so it scales to LM-sized leaves. This is
    the jit-friendly stand-in for indRandK/sameRandK at LM scale.
    """

    k: int = 1
    name: str = dataclasses.field(default="bernk", init=False)

    def __call__(self, key, x):
        d = x.shape[-1]
        q = min(self.k, d) / d
        mask = (jax.random.uniform(key, x.shape) < q).astype(x.dtype)
        return x * mask / q

    def omega(self, d):
        k = min(self.k, d)
        return d / k - 1.0

    def expected_density(self, d):
        return float(min(self.k, d))


# ---------------------------------------------------------------------------
# Natural compression (unbiased, omega = 1/8)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NaturalCompression(UnbiasedCompressor):
    """Stochastic rounding of mantissa to powers of two (Horvath et al. 2022).

    For x != 0: round to 2^floor(log2|x|) or 2^ceil(log2|x|) with
    probabilities making it unbiased; omega = 1/8. Dense (zeta = d) but each
    float costs only 9 bits (sign + exponent).
    """

    name: str = dataclasses.field(default="natural", init=False)
    bits_per_value: int = 9

    def __call__(self, key, x):
        ax = jnp.abs(x)
        lo_exp = jnp.floor(jnp.log2(jnp.where(ax > 0, ax, 1.0)))
        # ldexp sets the exponent directly, so every power of two is exact
        # (exp2 is off by an ulp for many |exponents| >= 13 under XLA)
        lo = jnp.ldexp(jnp.ones_like(ax), lo_exp.astype(jnp.int32))
        hi = lo * 2.0
        # p(hi) chosen so expectation is exact: ax = p*hi + (1-p)*lo
        p_hi = jnp.where(ax > 0, (ax - lo) / (hi - lo), 0.0)
        u = jax.random.uniform(key, x.shape)
        mag = jnp.where(u < p_hi, hi, lo)
        return jnp.where(ax > 0, jnp.sign(x) * mag, 0.0)

    def omega(self, d):
        return 0.125

    def expected_density(self, d):
        return float(d)


# ---------------------------------------------------------------------------
# Scaled unbiased -> contractive (Lemma 8, Richtarik et al. 2021)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScaledUnbiased(ContractiveCompressor):
    """(omega+1)^{-1} Q in B((omega+1)^{-1}) for Q in U(omega)."""

    inner: UnbiasedCompressor = dataclasses.field(default_factory=lambda: RandK(k=1))
    d_hint: int = 1
    name: str = dataclasses.field(default="scaled_unbiased", init=False)

    def __call__(self, key, x):
        w = self.inner.omega(x.shape[-1])
        return self.inner(key, x) / (w + 1.0)

    def alpha(self, d):
        return 1.0 / (self.inner.omega(d) + 1.0)

    def expected_density(self, d):
        return self.inner.expected_density(d)

    @property
    def needs_key(self):
        return self.inner.needs_key


# ---------------------------------------------------------------------------
# Pytree lifting
# ---------------------------------------------------------------------------


def tree_ravel(tree):
    flat, unravel = jax.flatten_util.ravel_pytree(tree)
    return flat, unravel


def tree_compress(comp: Compressor, key: Optional[Array], tree):
    """Apply a flat-vector compressor to a parameter pytree."""
    flat, unravel = jax.flatten_util.ravel_pytree(tree)
    out = comp(key, flat)
    return unravel(out)


# registry used by configs / CLI ------------------------------------------------

def make_compressor(spec: str, *, d: int, n: int = 1, worker: int = 0) -> Compressor:
    """Parse a compressor spec string, e.g. ``topk:32``, ``randk:32``,
    ``permk``, ``block_topk:16:1024``, ``natural``, ``identity``."""
    parts = spec.split(":")
    kind = parts[0]
    if kind == "identity":
        return Identity()
    if kind == "topk":
        return TopK(k=int(parts[1]) if len(parts) > 1 else max(1, d // n))
    if kind == "block_topk":
        kb = int(parts[1]) if len(parts) > 1 else 16
        b = int(parts[2]) if len(parts) > 2 else 1024
        return BlockTopK(k_per_block=kb, block=b)
    if kind == "randk":
        return RandK(k=int(parts[1]) if len(parts) > 1 else max(1, d // n))
    if kind == "permk":
        return PermK(n=n, worker=worker)
    if kind == "natural":
        return NaturalCompression()
    raise ValueError(f"unknown compressor spec: {spec}")
