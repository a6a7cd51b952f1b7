"""Training loop wiring: model + optimizer + compressed downlink.

One MARINA-P round per train step (uplink exact, downlink compressed):

    workers:  g_i = grad_{w_i} loss(w_i, batch_i)      [vmap over W axis]
    server:   g = mean_i g_i                           [all-reduce]
              x_new, opt = optimizer(g, x, lr)         [fp32 master, ZeRO-1]
    downlink: w_i += Q_i(x_new - x)  or full sync      [compressed broadcast]

``downlink=None`` is the exact-broadcast baseline (classic data-parallel:
w_i = x always). ``EF21PDownlink`` keeps one synchronized shift tree.
Polyak adaptive LR (the paper's (13), with f* estimate) is available as
``polyak=...`` — it consumes only quantities already on the server.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.comm_model import CommModel
from repro.models import lm
from repro.models.config import ModelConfig
from repro.obs.trace import maybe_attr, span
from repro.optim import Optimizer
from .downlink import EF21PDownlink, MarinaPDownlink, tree_size


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    n_workers: int = 4
    remat: bool = True
    attn_chunk: int = 512
    weight_dtype: Any = jnp.float32       # worker replica dtype
    polyak_factor: float = 0.0            # >0: Polyak LR instead of schedule
    polyak_f_star: float = 0.0
    window_override: Optional[int] = None
    remat_policy: Optional[str] = None    # None/"full" | "dots" (§Perf C2)
    act_spec: Any = None                  # within-worker activation spec (§Perf C3)
    drop_prob: float = 0.0                # legacy shim over BernoulliStragglerPlan
    straggler_cutoff: float = 0.0         # legacy shim over BernoulliStragglerPlan
    participation: Any = None             # repro.fleet ParticipationPlan (None = full)


def init_state(cfg: ModelConfig, tcfg: TrainerConfig, downlink, optimizer: Optimizer, key):
    server = lm.lm_init(cfg, key)
    state = {
        "server": server,
        "opt": optimizer.init(server),
        "step": jnp.zeros((), jnp.int32),
        "bits_per_worker": jnp.zeros((), jnp.float32),
        "uplink_bits_per_worker": jnp.zeros((), jnp.float32),
    }
    if downlink is not None:
        workers = downlink.init_workers(server)
        state["workers"] = jax.tree.map(lambda t: t.astype(tcfg.weight_dtype), workers)
    return state


def make_train_step(
    cfg: ModelConfig,
    tcfg: TrainerConfig,
    downlink,
    optimizer: Optimizer,
    lr_fn: Callable,
):
    """Returns jittable (state, batch, key) -> (state, metrics).

    batch leaves have a leading worker axis [W, B_local, ...].
    """

    def loss_of(params, shard):
        return lm.loss_fn(
            cfg, params, shard,
            chunk=tcfg.attn_chunk, remat=tcfg.remat,
            window_override=tcfg.window_override,
            remat_policy=tcfg.remat_policy,
            act_spec=tcfg.act_spec,
        )

    grad_fn = jax.value_and_grad(loss_of)

    # Participation is a single pluggable hook (repro.fleet.ParticipationPlan).
    # The legacy drop_prob/straggler_cutoff knobs are thin shims over
    # BernoulliStragglerPlan — op-for-op identical to the old inline branch,
    # so legacy configs stay bit-identical to their plan equivalents.
    from repro.fleet.sampler import PARTICIPATION_FOLD, plan_from_legacy

    plan = tcfg.participation
    if plan is not None and (tcfg.drop_prob > 0 or tcfg.straggler_cutoff > 0):
        raise ValueError(
            "TrainerConfig.participation and the legacy drop_prob/"
            "straggler_cutoff knobs are mutually exclusive; the legacy knobs "
            "are shims over BernoulliStragglerPlan — set one or the other."
        )
    if plan is None:
        plan = plan_from_legacy(tcfg.drop_prob, tcfg.straggler_cutoff)
    partial = not plan.is_full

    def train_step(state, batch, key, force_sync=False):
        server = state["server"]
        # ---- workers: forward/backward on their own replica -----------------
        with jax.named_scope("fwd_bwd"):
            if downlink is None:
                losses, grads_w = jax.vmap(lambda shard: grad_fn(server, shard))(batch)
            elif isinstance(downlink, EF21PDownlink):
                shift = state["workers"]
                losses, grads_w = jax.vmap(lambda shard: grad_fn(shift, shard))(batch)
            else:
                workers = state["workers"]
                losses, grads_w = jax.vmap(grad_fn)(workers, batch)
        # ---- uplink: exact aggregation over the round's participants ---------
        # Partial participation (DESIGN.md §8.5/§9.2): the plan maps a
        # participation key to this round's worker mask. Only the uplink
        # aggregation is masked — the downlink still addresses everyone.
        # The participation key is folded off to the side
        # (fold_in(key, PARTICIPATION_FOLD)) so the downlink RNG stream is
        # bit-identical to the full-participation path, and every plan draws
        # from the same folded key so swapping plans never perturbs it.
        with jax.named_scope("aggregate"):
            if partial:
                n = tcfg.n_workers
                k_part = jax.random.fold_in(key, PARTICIPATION_FOLD)
                participate = plan.mask(k_part, n, state["step"])
                n_part = jnp.maximum(jnp.sum(participate), 1)
                w = participate.astype(jnp.float32) / n_part
                grads = jax.tree.map(
                    lambda g: jnp.tensordot(w, g.astype(jnp.float32), axes=1), grads_w
                )
                loss = jnp.sum(w * losses)
            else:
                grads = jax.tree.map(
                    lambda g: jnp.mean(g.astype(jnp.float32), axis=0), grads_w
                )
                loss = jnp.mean(losses)
            gnorm_sq = sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))
        # ---- server master update --------------------------------------------
        with jax.named_scope("optimizer"):
            if tcfg.polyak_factor > 0:
                lr = tcfg.polyak_factor * jnp.maximum(loss - tcfg.polyak_f_star, 0.0) / jnp.maximum(gnorm_sq, 1e-20)
            else:
                lr = lr_fn(state["step"])
            server_new, opt_new = optimizer.update(grads, state["opt"], server, lr)
        # ---- uplink: exact dense gradient per worker (w2s, ROADMAP gap) ------
        d = tree_size(server)
        uplink_bits = state["uplink_bits_per_worker"] + CommModel(d=d).dense_bits()
        new_state = {
            "server": server_new,
            "opt": opt_new,
            "step": state["step"] + 1,
            "bits_per_worker": state["bits_per_worker"],
            "uplink_bits_per_worker": uplink_bits,
        }
        metrics = {"loss": loss, "grad_norm": jnp.sqrt(gnorm_sq), "lr": lr,
                   "uplink_bits_per_worker": uplink_bits}
        if partial:
            metrics["participants"] = jnp.sum(participate).astype(jnp.float32)
        # ---- downlink: compressed broadcast ----------------------------------
        if downlink is None:
            pass
        elif isinstance(downlink, EF21PDownlink):
            with jax.named_scope("downlink"):
                shift_new, bits = downlink.round(
                    key, server_new, state["workers"], force_sync
                )
            new_state["workers"] = shift_new
            new_state["bits_per_worker"] = state["bits_per_worker"] + bits
            with jax.named_scope("drift"):
                metrics["drift"] = downlink.worker_drift(server_new, shift_new)
        else:
            with jax.named_scope("downlink"):
                workers_new, bits = downlink.round(
                    key, server_new, server, state["workers"], force_sync
                )
            new_state["workers"] = workers_new
            new_state["bits_per_worker"] = state["bits_per_worker"] + bits
            with jax.named_scope("drift"):
                metrics["drift"] = downlink.worker_drift(server_new, workers_new)
        metrics["bits_per_worker"] = new_state["bits_per_worker"]
        return new_state, metrics

    return train_step


def train_loop(
    cfg: ModelConfig,
    tcfg: TrainerConfig,
    downlink,
    optimizer: Optimizer,
    lr_fn: Callable,
    data,
    *,
    steps: int,
    key,
    tracker=None,
    log_every: int = 1,
    transport=None,
    wire_mag: str = "fp32",
):
    """Host loop around the jitted step with per-step telemetry.

    Each round is one ``round`` span whose children cover it in order:
    ``feed`` (the batch and the round's key), ``dispatch`` (the step's
    arguments taken from the state, and the jitted call until it returns),
    ``wait`` (``block_until_ready`` on the step's outputs), the broadcast
    spans when a transport is attached, and ``log`` (``tracker.log`` of the
    metrics — loss, grad_norm, lr, drift, bits_per_worker,
    uplink_bits_per_worker — at ``log_every`` cadence, with their
    device-to-host reads). Returns (final_state, last_metrics).

    ``transport`` (a :class:`repro.transport.Fleet` or a
    :class:`repro.transport.FaultSpec`) additionally pushes each round's
    downlink through fault-injected reliable links via the downlink's
    ``broadcast_via``; a round whose delivery degrades (undelivered
    worker or receiver resync request) promotes the *next* round's
    broadcast to a full sync, whose self-contained SYNC frame repairs
    every receiver (DESIGN.md §8.4). The last metrics dict then carries
    the fleet counters under ``"transport"``.
    """
    from repro import obs

    tracker = tracker or obs.NullTracker()
    k_init, k_steps = jax.random.split(key)
    state = init_state(cfg, tcfg, downlink, optimizer, k_init)
    train_step = make_train_step(cfg, tcfg, downlink, optimizer, lr_fn)
    # The broadcast reads the old server (MARINA-P) or the old shift
    # (EF21-P) after the step. Every other buffer of the state is donated,
    # so old and new optimizer moments and replicas are never resident
    # together (at 326M parameters they would not fit one 16 GB chip).
    kept = "workers" if isinstance(downlink, EF21PDownlink) else "server"
    step = jax.jit(
        lambda prev, rest, batch, key, fs: train_step({kept: prev, **rest}, batch, key, fs),
        donate_argnums=1,
    )
    fleet = None
    if transport is not None and downlink is not None:
        from repro.transport import FaultSpec, Fleet

        fleet = (
            Fleet.make(tcfg.n_workers, transport, timeout=2, max_retries=2)
            if isinstance(transport, FaultSpec)
            else transport
        )
    m = {}
    force_sync = False
    for i in range(steps):
        was_forced = force_sync
        with span(tracker, "round", round=i, alg="train") as rsp:
            with span(tracker, "feed"):
                batch = data.batch(i)
                k_step = jax.random.fold_in(k_steps, i)
            with span(tracker, "dispatch"):
                # rebinding prev frees the last round's kept tree: host
                # time that belongs to preparing the call
                prev = state[kept]
                rest = {k: v for k, v in state.items() if k != kept}
                state, m = step(prev, rest, batch, k_step, force_sync)
            with span(tracker, "wait"):
                jax.block_until_ready(m)
            if fleet is not None:
                res = downlink.broadcast_via(
                    fleet, k_step, state["server"], prev,
                    mag=wire_mag, force_sync=force_sync, tracker=tracker,
                    step=i,
                )
                force_sync = res["resync_needed"]
                maybe_attr(rsp, full_sync=res["full_sync"],
                           resync_next=force_sync)
            maybe_attr(rsp, force_sync=was_forced)
            if i % log_every == 0:
                with span(tracker, "log"):
                    tracker.log({"train": m}, step=i)
    if fleet is not None:
        m = dict(m)
        m["transport"] = fleet.stats().as_metrics()
    return state, m
