"""MARINA-P / EF21-P as the model-broadcast layer of LM training.

This is the paper's technique integrated as a first-class feature of the
training runtime: after the server (master) optimizer step, the *model delta*
broadcast to each data-parallel worker replica is compressed.

* :class:`MarinaPDownlink` — Algorithm 2 over parameter pytrees. Worker
  replicas are a leading ``W`` axis; broadcast modes:
    - ``perm``: RotK cyclic-partition PermK (omega = W-1, exact-mean identity)
    - ``ind`` : per-worker Bernoulli-K (omega = d/k - 1)
    - ``same``: shared Bernoulli-K mask
  With probability ``p`` the full model is synchronized (Bernoulli coin).
* :class:`EF21PDownlink` — Algorithm 1 over pytrees with block-TopK. The
  synchronized shift ``w`` is a single tree (all workers identical).

Both track the paper's analytic WAN bits per round (comm_model) as jnp
scalars inside the train state. On the TPU mesh itself the messages cost
zero interconnect bytes (shared-randomness materialization — DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Tuple

import jax
import jax.flatten_util
import jax.numpy as jnp

from repro.core.comm_model import CommModel
from repro.core.compressors import BlockTopK
from repro.obs.trace import maybe_attr, maybe_span

Array = jax.Array


def _leaf_rotk_mask(key, shape, n, worker):
    """RotK mask for one leaf: coordinate j kept iff j % n == (worker+r) % n."""
    size = math.prod(shape) if shape else 1
    r = jax.random.randint(key, (), 0, n)
    idx = jax.lax.iota(jnp.int32, size) % n
    return (idx == (worker + r) % n).reshape(shape)


def _leaf_bern_mask(key, shape, keep_prob):
    return jax.random.uniform(key, shape) < keep_prob


@jax.jit
def _flat_f32(tree) -> Array:
    """The tree raveled to one f32 vector (a full-sync message)."""
    return jax.flatten_util.ravel_pytree(
        jax.tree.map(lambda t: t.astype(jnp.float32), tree))[0]


def tree_size(tree) -> int:
    return sum(math.prod(l.shape) if l.shape else 1 for l in jax.tree.leaves(tree))


def _use_device_encode(device_encode) -> bool:
    """Route a downlink serialization through kernels/encode.py?"""
    from repro.kernels import encode as kenc

    return kenc.device_encode_enabled(device_encode)


def _track_wire(tracker, step, res: dict) -> dict:
    """Log a measure_wire result as downlink/* metrics; returns ``res``."""
    if tracker is not None:
        tracker.log(
            {
                "downlink/wire_bits_mean": res["bits_mean"],
                "downlink/wire_bits_analytic": res["bits_analytic"],
                "downlink/full_sync": res["full_sync"],
                **(
                    {"downlink/wire_bits_seed": res["bits_seed"]}
                    if "bits_seed" in res
                    else {}
                ),
            },
            step=step,
        )
    return res


@dataclasses.dataclass(frozen=True)
class MarinaPDownlink:
    """Compressed server->worker model broadcast (Algorithm 2, pytree form)."""

    n_workers: int
    mode: str = "perm"          # perm | ind | same
    keep_frac: float = 0.0      # bern modes: k/d; default 1/n (PermK-parity)
    p: float = 0.0              # full-sync probability; default 1/n

    @property
    def sync_p(self) -> float:
        return self.p if self.p > 0 else 1.0 / self.n_workers

    @property
    def frac(self) -> float:
        if self.mode == "perm":
            return 1.0 / self.n_workers
        return self.keep_frac if self.keep_frac > 0 else 1.0 / self.n_workers

    def omega(self) -> float:
        if self.mode == "perm":
            return self.n_workers - 1.0
        return 1.0 / self.frac - 1.0

    def init_workers(self, server_params):
        """w_i^0 = x^0 for all i (leading worker axis)."""
        return jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (self.n_workers,) + t.shape), server_params
        )

    def round(self, key, server_new, server_old, worker_params, force_sync=False):
        """One downlink round -> (new worker params, bits/worker this round).

        The Bernoulli branch is a ``lax.cond`` so only one of
        {full-sync broadcast, compressed update} materializes per round
        (§Perf iteration C1 — jnp.where evaluated both, costing ~2x the
        downlink HBM traffic). ``force_sync`` promotes the round to the
        full broadcast unconditionally — the transport layer's resync
        path (DESIGN.md §8.4).
        """
        k_bern, k_comp = jax.random.split(key)
        c = jnp.logical_or(jax.random.bernoulli(k_bern, self.sync_p), force_sync)
        n = self.n_workers

        def sync_branch(operands):
            server_new, worker_params = operands
            return jax.tree.map(
                lambda xn, wp: jnp.broadcast_to(xn.astype(wp.dtype)[None], wp.shape),
                server_new,
                worker_params,
            )

        def compress_branch(operands):
            server_new, worker_params = operands
            leaves_new, treedef = jax.tree.flatten(server_new)
            leaves_old = jax.tree.leaves(server_old)
            leaves_w = jax.tree.leaves(worker_params)
            out = []
            for li, (xn, xo, wp) in enumerate(zip(leaves_new, leaves_old, leaves_w)):
                delta = (xn - xo).astype(wp.dtype)
                lk = jax.random.fold_in(k_comp, li)
                if self.mode == "perm":
                    def q_one(widx):
                        m = _leaf_rotk_mask(lk, xn.shape, n, widx)
                        return jnp.where(m, delta * n, 0)
                elif self.mode == "ind":
                    def q_one(widx):
                        m = _leaf_bern_mask(jax.random.fold_in(lk, widx), xn.shape, self.frac)
                        return jnp.where(m, delta / self.frac, 0)
                else:  # same
                    m_shared = _leaf_bern_mask(lk, xn.shape, self.frac)

                    def q_one(widx):
                        return jnp.where(m_shared, delta / self.frac, 0)

                out.append(wp + jax.vmap(q_one)(jnp.arange(n)))
            return jax.tree.unflatten(treedef, out)

        new_workers = jax.lax.cond(c, sync_branch, compress_branch,
                                   (server_new, worker_params))
        d = tree_size(server_new)
        cm = CommModel(d=d)  # single source of truth for the bit formulas
        bits = jnp.where(c, cm.dense_bits(), cm.sparse_bits(self.frac * d))
        return new_workers, bits

    def worker_drift(self, server_params, worker_params) -> Array:
        """mean_i ||w_i - x||^2 — the Lyapunov drift term of Theorem 2."""
        sq = jax.tree.map(
            lambda w, x: jnp.sum((w.astype(jnp.float32) - x.astype(jnp.float32)[None]) ** 2),
            worker_params,
            server_params,
        )
        return sum(jax.tree.leaves(sq)) / self.n_workers

    def _dense_buf(self, server_new, mag, device_encode=None):
        """Serialize the full model for a sync broadcast."""
        import numpy as np

        from repro import wire

        flat = _flat_f32(server_new)
        if _use_device_encode(device_encode):
            from repro.kernels import encode as kenc

            return kenc.dense_encode(flat, mag=mag)
        return wire.encode_dense(np.asarray(flat), mag=mag)

    @functools.partial(jax.jit, static_argnums=0)
    def _message_row(self, k_comp, server_new, server_old, widx):
        """Worker ``widx``'s compressed delta over the raveled tree (f32),
        replaying :meth:`round`'s randomness."""
        n = self.n_workers
        parts = []
        leaves_old = jax.tree.leaves(server_old)
        for li, (xn, xo) in enumerate(zip(jax.tree.leaves(server_new), leaves_old)):
            delta = (xn - xo).astype(jnp.float32)
            lk = jax.random.fold_in(k_comp, li)
            if self.mode == "perm":
                m = _leaf_rotk_mask(lk, xn.shape, n, widx)
                q = jnp.where(m, delta * n, 0)
            elif self.mode == "ind":
                m = _leaf_bern_mask(jax.random.fold_in(lk, widx), xn.shape, self.frac)
                q = jnp.where(m, delta / self.frac, 0)
            else:  # same
                m = _leaf_bern_mask(lk, xn.shape, self.frac)
                q = jnp.where(m, delta / self.frac, 0)
            parts.append(q.reshape(-1))
        return jnp.concatenate(parts)

    def _sparse_bufs(self, k_comp, server_new, server_old, mag,
                     device_encode=None):
        """Per-worker compressed-delta buffers. 'same' mode encodes once and
        repeats the buffer (every worker's message is identical). Rows are
        built and encoded one at a time, so only one row is alive."""
        import numpy as np

        from repro import wire

        rows = (self._message_row(k_comp, server_new, server_old, w)
                for w in range(1 if self.mode == "same" else self.n_workers))
        if _use_device_encode(device_encode):
            from repro.kernels import encode as kenc

            bufs = kenc.encode_rows(rows, mag=mag)
        else:
            bufs = [wire.encode_sparse(np.asarray(r), mag=mag) for r in rows]
        if self.mode == "same":
            bufs = bufs * self.n_workers
        return bufs

    def measure_wire(self, key, server_new, server_old, *, mag="fp32",
                     device_encode=None, tracker=None, step=None) -> dict:
        """Host-side wire measurement (measure_wire=True path).

        Replays this round's randomness exactly as :meth:`round` consumes it,
        rebuilds each worker's message over the raveled tree, and serializes
        it with the repro.wire codecs. Returns measured bits alongside the
        analytic model's prediction (value_bits matched to ``mag``) and the
        O(1) seed-only alternative (DESIGN.md §3.5). Not jittable — this is
        the accounting/verification path, not the training hot path.
        ``device_encode`` routes serialization through the fused Pallas
        encode kernels (byte-identical; None defers to
        ``REPRO_DEVICE_ENCODE``/backend auto-detect). ``tracker`` logs the
        result as a ``downlink/*`` metric event.
        """
        import numpy as np

        from repro import wire

        n = self.n_workers
        d = tree_size(server_new)
        cm = CommModel(d=d, value_bits=wire.MAG_BITS[wire.mag_dtype(mag)])
        k_bern, k_comp = jax.random.split(key)
        c = bool(jax.random.bernoulli(k_bern, self.sync_p))
        seed_buf = wire.encode_seed(
            wire.SeedMessage(
                family=wire.SeedFamily.ROTK if self.mode == "perm" else wire.SeedFamily.BERN,
                seed=int(np.asarray(
                    jax.random.key_data(k_comp)
                    if jnp.issubdtype(k_comp.dtype, jax.dtypes.prng_key)
                    else k_comp
                ).ravel()[-1]),
                round=0, scale=1.0, n=n, worker=0, param=self.frac,
            ),
            d,
        )
        if c:
            bits = float(wire.measured_bits(
                self._dense_buf(server_new, mag, device_encode)))
            return _track_wire(tracker, step, {
                "full_sync": True, "bits_mean": bits, "bits_per_worker": [bits] * n,
                "bits_seed": float(wire.measured_bits(seed_buf)),
                "bits_analytic": cm.dense_bits()})
        per_worker = [
            float(wire.measured_bits(buf))
            for buf in self._sparse_bufs(k_comp, server_new, server_old, mag,
                                         device_encode)
        ]
        return _track_wire(tracker, step, {
            "full_sync": False,
            "bits_mean": sum(per_worker) / n,
            "bits_per_worker": per_worker,
            "bits_seed": float(wire.measured_bits(seed_buf)),
            "bits_analytic": cm.sparse_bits(self.frac * d),
        })

    def broadcast_via(self, fleet, key, server_new, server_old, *, mag="fp32",
                      device_encode=None, force_sync=False, tracker=None,
                      step=None) -> dict:
        """Push this round's broadcast through a :class:`repro.transport.Fleet`.

        Replays the same randomness :meth:`round` consumed (pass the same
        ``key`` and ``force_sync``), serializes the actual per-worker
        messages, and delivers them over the fault-injected links. Sync
        rounds travel as self-contained SYNC frames (they repair any
        receiver gap). Returns per-worker delivery flags plus whether the
        *next* round must be promoted to a full sync (DESIGN.md §8.4).
        """
        k_bern, k_comp = jax.random.split(key)
        c = bool(jax.random.bernoulli(k_bern, self.sync_p)) or bool(force_sync)
        if tracker is not None:
            fleet.attach_tracker(tracker)
        with maybe_span(tracker, "broadcast", full_sync=c) as bsp:
            with maybe_span(tracker, "encode",
                            device=_use_device_encode(device_encode)):
                if c:
                    payloads = [self._dense_buf(server_new, mag, device_encode)]
                else:
                    payloads = self._sparse_bufs(
                        k_comp, server_new, server_old, mag, device_encode)
            if c:
                oks = fleet.broadcast(payloads[0], sync=True)
            else:
                oks = fleet.send_per_worker(payloads)
            fleet.drain()
            res = {
                "full_sync": c,
                "oks": oks,
                "delivered_frac": sum(oks) / len(oks),
                "resync_needed": fleet.resync_needed or not all(oks),
            }
            maybe_attr(bsp, delivered=int(sum(oks)),
                       resync_next=res["resync_needed"])
        if tracker is not None:
            tracker.log(
                {
                    "downlink/full_sync": c,
                    "downlink/delivered_frac": res["delivered_frac"],
                },
                step=step,
            )
            fleet.log_to(tracker, step=step)
        return res


@dataclasses.dataclass(frozen=True)
class EF21PDownlink:
    """EF21-P over pytrees with block-local TopK (Algorithm 1, pytree form)."""

    n_workers: int
    k_per_block: int = 128
    block: int = 1024

    @property
    def comp(self) -> BlockTopK:
        return BlockTopK(k_per_block=self.k_per_block, block=self.block)

    def init_shift(self, server_params):
        """w^0 = x^0; one tree — workers stay synchronized by construction.
        A copy, not the server's own buffers, so either may be donated."""
        return jax.tree.map(jnp.array, server_params)

    def round(self, key, server_new, shift, force_sync=False):
        """``force_sync`` re-anchors the shift with a dense ``w := x``
        broadcast — the transport layer's resync path (DESIGN.md §8.4)."""
        comp = self.comp
        new_shift = jax.tree.map(
            lambda xn, w: jnp.where(
                force_sync,
                xn.astype(w.dtype),
                w + comp(None, (xn.astype(jnp.float32) - w.astype(jnp.float32)).reshape(-1)).reshape(w.shape).astype(w.dtype),
            ),
            server_new,
            shift,
        )
        d = tree_size(server_new)
        frac = self.k_per_block / self.block
        cm = CommModel(d=d)
        bits = jnp.where(force_sync, cm.dense_bits(), cm.sparse_bits(frac * d))
        return new_shift, bits

    def init_workers(self, server_params):
        return self.init_shift(server_params)

    def _delta_buf(self, server_new, shift, mag, device_encode=None):
        """Serialize the block-TopK compressed difference over the raveled
        tree (the broadcast message, identical for every worker)."""
        import numpy as np

        from repro import wire

        comp = self.comp
        parts = [
            comp(None, (xn.astype(jnp.float32) - w.astype(jnp.float32)).reshape(-1))
            for xn, w in zip(jax.tree.leaves(server_new), jax.tree.leaves(shift))
        ]
        delta = jnp.concatenate(parts)
        if _use_device_encode(device_encode):
            from repro.kernels import encode as kenc

            return kenc.sparse_encode(delta, mag=mag)
        return wire.encode_sparse(np.asarray(delta), mag=mag)

    def measure_wire(self, key, server_new, shift, *, mag="fp32",
                     device_encode=None, tracker=None, step=None) -> dict:
        """Host-side wire measurement of one EF21-P broadcast (the block-TopK
        compressed difference, identical for every worker)."""
        from repro import wire

        d = tree_size(server_new)
        cm = CommModel(d=d, value_bits=wire.MAG_BITS[wire.mag_dtype(mag)])
        buf = self._delta_buf(server_new, shift, mag, device_encode)
        frac = self.k_per_block / self.block
        return _track_wire(tracker, step, {
            "full_sync": False,
            "bits_mean": float(wire.measured_bits(buf)),
            "bits_per_worker": [float(wire.measured_bits(buf))] * self.n_workers,
            "bits_analytic": cm.sparse_bits(frac * d),
        })

    def broadcast_via(self, fleet, key, server_new, shift, *, mag="fp32",
                      device_encode=None, force_sync=False, tracker=None,
                      step=None) -> dict:
        """Deliver one EF21-P broadcast through a transport Fleet.

        A sync round ships the full model (``w := x`` re-anchor) as a
        self-contained SYNC frame; otherwise the block-TopK compressed
        difference, identical for every worker. ``resync_needed`` in the
        result means the caller must pass ``force_sync=True`` to the next
        :meth:`round` (and roll its shift back — DESIGN.md §8.4).
        """
        import numpy as np

        from repro import wire

        if tracker is not None:
            fleet.attach_tracker(tracker)
        with maybe_span(tracker, "broadcast",
                        full_sync=bool(force_sync)) as bsp:
            with maybe_span(tracker, "encode",
                            device=_use_device_encode(device_encode)):
                if force_sync:
                    flat = _flat_f32(server_new)
                    if _use_device_encode(device_encode):
                        from repro.kernels import encode as kenc

                        buf = kenc.dense_encode(flat, mag=mag)
                    else:
                        buf = wire.encode_dense(np.asarray(flat), mag=mag)
                else:
                    buf = self._delta_buf(server_new, shift, mag,
                                          device_encode)
            oks = fleet.broadcast(buf, sync=bool(force_sync))
            fleet.drain()
            res = {
                "full_sync": bool(force_sync),
                "oks": oks,
                "delivered_frac": sum(oks) / len(oks),
                "resync_needed": fleet.resync_needed or not all(oks),
            }
            maybe_attr(bsp, delivered=int(sum(oks)),
                       resync_next=res["resync_needed"])
        if tracker is not None:
            tracker.log(
                {
                    "downlink/full_sync": res["full_sync"],
                    "downlink/delivered_frac": res["delivered_frac"],
                },
                step=step,
            )
            fleet.log_to(tracker, step=step)
        return res

    def worker_drift(self, server_params, shift) -> Array:
        sq = jax.tree.map(
            lambda w, x: jnp.sum((w.astype(jnp.float32) - x.astype(jnp.float32)) ** 2),
            shift,
            server_params,
        )
        return sum(jax.tree.leaves(sq))


def make_downlink(spec: str, n_workers: int):
    """``marina:perm``, ``marina:ind:0.0625``, ``marina:same``, ``ef21p:128:1024``,
    ``none`` (exact broadcast baseline)."""
    parts = spec.split(":")
    if parts[0] == "none":
        return None
    if parts[0] == "marina":
        mode = parts[1] if len(parts) > 1 else "perm"
        keep = float(parts[2]) if len(parts) > 2 else 0.0
        return MarinaPDownlink(n_workers=n_workers, mode=mode, keep_frac=keep)
    if parts[0] == "ef21p":
        kb = int(parts[1]) if len(parts) > 1 else 128
        b = int(parts[2]) if len(parts) > 2 else 1024
        return EF21PDownlink(n_workers=n_workers, k_per_block=kb, block=b)
    raise ValueError(spec)
