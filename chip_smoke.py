"""Chip smoke run: the MARINA-P trainer on one TPU, end to end.

Trains zamba2-1.2b at its published widths, cut to one period of its layer
pattern (6 layers: 5 Mamba2, 1 shared attention), for 4 steps through
``repro.train.train_loop``. The server-to-worker broadcast is MARINA-P
(perm mode, 2 workers) and goes through fault-free transport links, so
every round is serialized by the compiled Pallas encode kernels. Each
broadcast kind is checked byte for byte against the host codec.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # only the SPMD phase: MARINA-P on a
                                       # 4-device mesh vs the 1-device reference

The last line of stdout is ``{"ok": true, "device": {...}}``. The script
exits non-zero without it when JAX finds no TPU, when a Pallas kernel would
run in interpret mode, when an encode goes to the host codec, or when any
check fails. It never carries on on the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))


def _fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        _fail(msg)


def _require_tpu(count: int):
    import jax

    devs = jax.devices()
    _check(devs[0].platform == "tpu",
           f"JAX found no TPU (platform {devs[0].platform!r}); this script "
           "runs only on the chip")
    _check(len(devs) >= count, f"needs {count} TPU devices, found {len(devs)}")
    return devs


def _compile_seconds():
    """Running total of XLA backend compile time in this process."""
    import jax

    total = [0.0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    return total


# ---------------------------------------------------------------------------
# one chip: the trainer
# ---------------------------------------------------------------------------


class _EncodeAudit:
    """Wraps the encode entry points the downlink calls. Every device
    encode of the first compressed and the first sync round is compared
    byte for byte with the host codec on the same rows; a host-codec call
    from the trainer is counted (it must not happen)."""

    def __init__(self):
        from repro import wire
        from repro.kernels import encode as kenc

        self.kenc, self.wire = kenc, wire
        self.rows, self.dense = kenc.encode_rows, kenc.dense_encode
        self.host_sparse, self.host_dense = wire.encode_sparse, wire.encode_dense
        self.sparse_rounds = self.dense_rounds = self.host_calls = 0
        self.checked = []  # (kind, bytes, identical)
        kenc.encode_rows, kenc.dense_encode = self._encode_rows, self._dense_encode
        wire.encode_sparse = self._host("encode_sparse")
        wire.encode_dense = self._host("encode_dense")

    def _host(self, name):
        orig = getattr(self.wire, name)

        def call(*a, **kw):
            self.host_calls += 1
            return orig(*a, **kw)

        return call

    def _encode_rows(self, rows, **kw):
        import numpy as np

        first = self.sparse_rounds == 0
        self.sparse_rounds += 1
        bufs = []
        for row in rows:
            buf = self.rows([row], **kw)[0]
            if first:
                ref = self.host_sparse(np.asarray(row), mag=kw.get("mag", "fp32"))
                self.checked.append(("sparse", len(buf), buf == ref))
            bufs.append(buf)
        return bufs

    def _dense_encode(self, x, **kw):
        import numpy as np

        buf = self.dense(x, **kw)
        if self.dense_rounds == 0:
            ref = self.host_dense(np.asarray(x), mag=kw.get("mag", "fp32"))
            self.checked.append(("dense", len(buf), buf == ref))
        self.dense_rounds += 1
        return buf


def one_chip(args) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import configs, obs
    from repro.data import SyntheticLMData
    from repro.kernels import encode as kenc
    from repro.kernels import pack
    from repro.kernels.runtime import resolve_interpret
    from repro.models import lm
    from repro.optim import make_optimizer
    from repro.optim.schedules import constant_lr
    from repro.train import TrainerConfig, make_downlink, train_loop
    from repro.transport import FaultSpec
    from repro.wire.spec import MagDType, index_width

    dev = _require_tpu(1)[0]
    # a fallback forced from the environment is an error, not a detour:
    # resolve_interpret raises on a TPU when REPRO_PALLAS_INTERPRET forces
    # interpret mode, and REPRO_DEVICE_ENCODE=0 turns the device path off
    _check(resolve_interpret(None) is False, "Pallas kernels resolve to interpret mode")
    _check(kenc.device_encode_enabled() is True,
           f"device encode is off ({kenc.DEVICE_ENCODE_ENV}="
           f"{os.environ.get(kenc.DEVICE_ENCODE_ENV)!r})")
    compile_s = _compile_seconds()

    full = configs.get("zamba2_1p2b")
    layers = 6  # one whole period of the pattern: 5 mamba + 1 shared
    cfg = dataclasses.replace(full, num_layers=layers,
                              block_pattern=full.block_pattern[:layers])
    _check(cfg.block_pattern.count("shared") == 1, "cut is not one pattern period")
    n_params = lm.count_params(cfg)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"reduced: num_layers {full.num_layers}→{layers}")
    print(f"model: {cfg.arch_id} d_model={cfg.d_model} heads={cfg.num_heads}x"
          f"{cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"ssm_state={cfg.mamba.state_dim}; {n_params} parameters")

    # the encode and pack programs of a broadcast row hold the Pallas kernels
    d = n_params
    row = jax.ShapeDtypeStruct((d,), jnp.float32)
    tpu = {"lowering_platforms": ("tpu",)}
    hlo = {
        "sparse": kenc._sparse_device.trace(
            row, m=MagDType.FP32, iw=index_width(d), interpret=False).lower(**tpu),
        "dense": kenc._dense_device.trace(
            row, m=MagDType.FP32, interpret=False).lower(**tpu),
        "pack": jax.jit(lambda v: pack.pack_bits_device(
            v, width=index_width(d), interpret=False)).trace(
            jax.ShapeDtypeStruct((d,), jnp.uint32)).lower(**tpu),
    }
    hlo = {name: low.as_text() for name, low in hlo.items()}
    for name, text in hlo.items():
        _check("tpu_custom_call" in text, f"{name} program has no tpu_custom_call")
    print(f"lowered programs with tpu_custom_call: {sorted(hlo)}")

    audit = _EncodeAudit()
    n_workers, steps = 2, 4
    tcfg = TrainerConfig(n_workers=n_workers)
    downlink = make_downlink("marina:perm", n_workers)
    data = SyntheticLMData(cfg, n_workers, batch_per_worker=1, seq_len=2048,
                           seed=args.seed)
    tracker = obs.MemoryTracker()
    t0 = time.perf_counter()
    train_loop(cfg, tcfg, downlink, make_optimizer("adamw", weight_decay=0.01),
               constant_lr(3e-4), data, steps=steps,
               key=jax.random.PRNGKey(args.seed), tracker=tracker,
               transport=FaultSpec())
    wall = time.perf_counter() - t0

    losses = [e["metrics"]["train/loss"] for e in tracker.events
              if e["kind"] == "metrics" and "train/loss" in e["metrics"]]
    spans = [e for e in tracker.events if e["kind"] == "span"]
    rounds = [e for e in spans if e["name"] == "round"]
    # a step is its jitted call until it returns, then the wait for its outputs
    step_s = [sum(e["t1"] - e["t0"] for e in spans if e["parent"] == r["span_id"]
                  and e["name"] in ("dispatch", "wait")) for r in rounds]
    for i, (loss, s, r) in enumerate(zip(losses, step_s, rounds)):
        print(f"step {i}: loss={loss} step_s={s} round_s={r['t1'] - r['t0']} "
              f"full_sync={r['attrs'].get('full_sync')}")
    print(f"wall time of steps after the first (one run, not a benchmark): "
          f"{step_s[1:]} s; whole run {wall} s")
    print(f"compile seconds: {compile_s[0]}")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    for kind, nbytes, same in audit.checked:
        print(f"byte identity vs host codec: {kind} {nbytes} bytes "
              f"{'identical' if same else 'DIFFERENT'}")

    _check(len(losses) == steps, f"{len(losses)} losses logged for {steps} steps")
    _check(all(math.isfinite(v) for v in losses), f"non-finite loss: {losses}")
    _check(audit.host_calls == 0, f"{audit.host_calls} encodes went to the host codec")
    _check(audit.sparse_rounds > 0 and audit.dense_rounds > 0,
           f"need a compressed and a sync round, got {audit.sparse_rounds} and "
           f"{audit.dense_rounds}; choose another --seed")
    _check(sum(k == "sparse" for k, _, _ in audit.checked) == n_workers,
           "not every row of the compressed round was checked")
    _check(all(n > 16 for k, n, _ in audit.checked if k == "sparse"),
           "the compressed round carried no entries")
    _check(all(same for _, _, same in audit.checked),
           "device encode differs from the host codec")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# four chips: SPMD MARINA-P against the single-device reference
# ---------------------------------------------------------------------------

# Relative L2 error of x and W allowed between the SPMD program and the
# reference. Both run the same f32 round; they differ only in the order of
# the uplink sums (per-shard sums then a psum over 4 devices, against one
# mean over 16 workers), about 16 ulps of g per round, so a clean run sits
# near 1e-7. An isolated sign flip of A_i w_i at a near-zero entry moves a
# few coordinates by ~gamma/n and costs a few 1e-5. A wrong mask or a lost
# worker changes g by ~1/n, far above the bound.
SPMD_RTOL = 1e-4
SPMD_N, SPMD_D, SPMD_ROUNDS, SPMD_P = 16, 8192, 8, 0.25


def _l1_problem(n: int, d: int, seed: int, sharding):
    """Algorithm 3 of the paper at (n, d), built on the device in bulk.

    ``A_i = nu_i * tridiag(d)/4 + shift * I`` with the mean's minimum
    eigenvalue shifted to 1e-6, from the closed-form tridiagonal spectrum
    (``core/problems.generate_problem`` eigensolves on the host instead,
    minutes at d = 8192)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.problems import L1Problem

    rng = np.random.default_rng(seed)
    nus = 1.0 + rng.standard_normal(n)
    eigs = (2.0 - 2.0 * np.cos(np.pi * np.arange(1, d + 1) / (d + 1))) / 4.0
    shift = 1e-6 - (nus.mean() * eigs).min()
    L0i = np.abs(nus[:, None] * eigs[None] + shift).max(axis=-1)

    def build(nu):
        i = jnp.arange(d)
        base = (2.0 * (i[:, None] == i[None]) - (jnp.abs(i[:, None] - i[None]) == 1)) / 4.0
        return nu[:, None, None] * base[None] + shift * jnp.eye(d)[None]

    A = jax.jit(build, out_shardings=sharding)(jnp.asarray(nus, jnp.float32))
    return L1Problem(A=A, x0=jnp.asarray(rng.standard_normal(d), jnp.float32),
                     L0i=jnp.asarray(L0i, jnp.float32),
                     sigma_A=float(np.sqrt(max((L0i**2).mean() - L0i.mean() ** 2, 0.0))))


def _rel_err(a, ref) -> float:
    import numpy as np

    a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


def four_chips(args) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from repro.core import distributed, marina_p, stepsizes
    from repro.launch.mesh import make_worker_mesh

    devs = _require_tpu(4)
    n, d, rounds, p = SPMD_N, SPMD_D, SPMD_ROUNDS, SPMD_P
    print(f"device: {devs[0].platform} {devs[0].device_kind}; jax.device_count()={jax.device_count()}")
    mesh = make_worker_mesh(4)
    ss = stepsizes.Constant(gamma=0.05)
    with jax.default_matmul_precision("highest"):
        ref = _l1_problem(n, d, args.seed, SingleDeviceSharding(devs[0]))
        A = jax.device_put(ref.A, NamedSharding(mesh, P("workers")))
        # A is an argument, not a closure: a closed-over array is inlined
        # into the program as a constant, and this one is 4.3 GB
        ref_step = jax.jit(lambda state, key, A: marina_p.make_step(
            dataclasses.replace(ref, A=A), "perm", k=d // n, p=p, stepsize=ss)(state, key))
        spmd_step = distributed.make_marina_p_spmd_step(
            mesh, n=n, d=d, mode="perm", k=d // n, p=p, stepsize=ss)
        state = marina_p.init(ref.x0, n)
        x = jax.device_put(state.x, NamedSharding(mesh, P()))
        W = jax.device_put(state.W, NamedSharding(mesh, P("workers")))
        t = state.t
        key = jax.random.PRNGKey(args.seed)
        err_x = err_W = 0.0
        syncs = 0
        for i in range(rounds):
            key, sub = jax.random.split(key)
            state, _ = ref_step(state, sub, ref.A)
            x, W, t, m = spmd_step(x, W, t, A, sub)
            syncs += int(m["full_sync"])
            err_x = max(err_x, _rel_err(x, state.x))
            err_W = max(err_W, _rel_err(W, state.W))
    print(f"{rounds} rounds (n={n}, d={d}, perm, p={p}, {syncs} full syncs): "
          f"max relative error x={err_x} W={err_W}, tolerance {SPMD_RTOL}")
    for name, arr, rows in (("A", A, n // 4), ("W", W, n // 4)):
        shards = arr.addressable_shards
        where = sorted(s.device.id for s in shards)
        print(f"{name}: shards {[s.data.shape for s in shards]} on devices {where}")
        _check(len(shards) == 4 and where == sorted(dv.id for dv in devs[:4]),
               f"{name} is not spread over the 4 devices")
        _check(all(s.data.shape[0] == rows for s in shards),
               f"{name} shards are not one quarter each")
    _check(np.isfinite(err_x) and np.isfinite(err_W), "non-finite error")
    _check(err_x <= SPMD_RTOL and err_W <= SPMD_RTOL, "SPMD and reference disagree")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": jax.device_count()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD phase on a 4-device mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    device = four_chips(args) if args.four_chips else one_chip(args)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
