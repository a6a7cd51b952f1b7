"""Training cells: the program's own loop, ``repro.train.train_loop``, one
call that covers set-up and window alike.

The loop reads its batches from :class:`Feed`, which is also the run's
clock. The first ``checked_rounds`` rounds are set-up: the step compiles in
the first, and the loop's state is read at the start of rounds 0, 1 and
``checked_rounds`` (the initial weights, AdamW's first moment, which is
(1 - b1) times the first gradient, and the weights after the checked
rounds), as host copies whose norms are taken after the window, so that
set-up holds no comparison work. The window opens at the start of the next round and closes at the
start of the first round that begins after ``--seconds`` (a traced run:
after ``trace_rounds`` rounds), so it holds whole rounds only. The loop is
then left by an exception, which frees its state before the reference runs.

The loop hands out no state between rounds, so the feed reads it from the
frame of its caller: the innermost frame up the stack that holds the
trainer's state dict (``server``, ``opt``, ``workers``), whatever the local
is called. A loop that asked for batches ahead of its rounds would break
this read; the run then fails its comparison, it never passes.

Traffic parameters: ``downlink`` (the program's downlink spec),
``batch_per_worker``, ``seq_len``, ``checked_rounds``, ``pool`` (distinct
batches made at set-up; a window that outlasts them cycles), and
``trace_rounds``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import math
import sys
import time
from typing import Dict, List

import numpy as np
from repro.obs import Tracker

from .. import counts
from ..compare import train_checks
from ..harness import BENCH, GcWatch, Outcome, TraceWindow, memory_peak_bytes, seed_key
from ..peaks import peaks
from ..result import Check


class WindowClosed(Exception):
    """Raised from the feed to leave the program's loop at the window's end."""


def model_config(cfg: dict):
    """The program's ModelConfig from the configuration file."""
    from repro.models.config import MambaConfig, ModelConfig

    names = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items() if k in names}
    kw["block_pattern"] = tuple(kw["block_pattern"])
    kw["mamba"] = MambaConfig(**kw["mamba"])
    return ModelConfig(**kw)


def load_reference(cfg: dict):
    path = BENCH / "configs" / cfg["reference"]
    spec = importlib.util.spec_from_file_location(f"bench_ref_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def token_pool(key, count: int, shape, vocab: int) -> List:
    """``count`` distinct batches of token ids, made on the device in one
    call: u**4 scaled to the vocabulary, so that small ids are frequent."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        u = jax.random.uniform(key, (count,) + tuple(shape))
        toks = jnp.minimum((u ** 4 * vocab).astype(jnp.int32), vocab - 1)
        return tuple(toks[i] for i in range(count))

    return list(make(key))


def _is_state(value) -> bool:
    return isinstance(value, dict) and {"server", "opt", "workers"} <= value.keys()


def _loop_state() -> dict:
    """The training loop's state at the start of a round, from the innermost
    frame up the stack that holds it (see the module docstring)."""
    frame = sys._getframe(1)
    while frame is not None:
        found = [v for v in frame.f_locals.values() if _is_state(v)]
        if len(found) == 1:
            return found[0]
        frame = frame.f_back
    raise RuntimeError("no frame up the stack holds the training state")


class Feed:
    """The batches of the run, and its clock (see the module docstring)."""

    def __init__(self, pool, checked: int, seconds: float, trace_rounds: int,
                 tracewin, read_state, watch):
        self.pool, self.checked, self.seconds = pool, checked, seconds
        self.watch = watch
        self.trace_rounds, self.tracewin, self.read_state = trace_rounds, tracewin, read_state
        self.t_open = self.t_close = 0.0
        self.rounds = 0

    def batch(self, i: int):
        if i <= self.checked:
            self.read_state(i, _loop_state())
        if i == self.checked:
            if self.tracewin is not None:
                self.tracewin.start()
            self.watch.start()
            self.t_open = time.perf_counter()
        elif i > self.checked:
            done = i - self.checked
            now = time.perf_counter()
            if (done >= self.trace_rounds if self.tracewin is not None
                    else now - self.t_open >= self.seconds):
                self.t_close, self.rounds = now, done
                self.watch.stop()
                if self.tracewin is not None:
                    self.tracewin.stop(self.t_open, now)
                raise WindowClosed
        return {"tokens": self.pool[i % len(self.pool)]}


class Recorder(Tracker):
    """The tracker handed to the loop: keeps each round's loss and the
    program's spans."""

    def __init__(self):
        self.losses: Dict[int, float] = {}
        self.spans: List[dict] = []

    def emit(self, event):
        if event["kind"] == "span":
            self.spans.append(event)
        elif event["kind"] == "metrics":
            m = event["metrics"]
            if "train/loss" in m:
                self.losses[event["step"]] = float(m["train/loss"])


def run(cell, devices) -> Outcome:
    import jax
    import jax.numpy as jnp
    from repro.optim import make_optimizer
    from repro.optim.schedules import constant_lr
    from repro.train import TrainerConfig, make_downlink, train_loop

    cfg, t = cell.config, cell.traffic
    tr = cfg["train"]
    n, B, S = tr["workers"], t["batch_per_worker"], t["seq_len"]
    checked = t["checked_rounds"]
    mcfg = model_config(cfg)
    key = seed_key(cell.seed)
    k_data = jax.random.fold_in(key, 1)
    pool = token_pool(k_data, t["pool"], (n, B, S), cfg["vocab_size"])

    norms = jax.jit(lambda tree: [jnp.sqrt(jnp.sum(jnp.square(l))) for l in jax.tree.leaves(tree)])
    prog: Dict[str, list] = {"change": []}
    x0 = {}
    ref = load_reference(cfg)

    def read_state(i, state):
        if i == 0:
            x0["tree"] = jax.device_get(state["server"])
        if i == 1:
            prog["grad"] = [float(v) / (1 - tr["b1"]) for v in norms(state["opt"]["m"])]
            prog["m1"] = [np.asarray(l) for l in
                          jax.tree.leaves(jax.device_get(state["opt"]["m"]))]
        if i == checked:  # copied now, before the window donates it; compared after
            x0["after"] = jax.device_get((state["server"], state["workers"]))

    tracewin = TraceWindow() if cell.trace else None
    watch = GcWatch()
    feed = Feed(pool, checked, cell.seconds, t["trace_rounds"], tracewin, read_state, watch)
    rec = Recorder()
    tcfg = TrainerConfig(n_workers=n, remat=tr["remat"])
    opt = make_optimizer(tr["optimizer"], b1=tr["b1"], b2=tr["b2"], eps=tr["eps"],
                         weight_decay=tr["weight_decay"])
    try:
        train_loop(mcfg, tcfg, make_downlink(t["downlink"], n), opt,
                   constant_lr(tr["lr"]), feed, steps=checked + len(pool) * 1000,
                   key=key, tracker=rec)
    except WindowClosed:
        pass
    gc.collect()
    peak = memory_peak_bytes(devices)
    window = feed.t_close - feed.t_open
    rounds = feed.rounds
    in_window = range(checked, checked + rounds)
    losses = [rec.losses.get(i, math.nan) for i in in_window]
    failed = sum(not math.isfinite(v) for v in losses)
    tokens_per_round = n * B * S
    metrics = {
        "train_tokens_per_s": rounds * tokens_per_round / window,
        "peak_hbm_gb": peak / 1e9,
    }
    traced = None
    if tracewin is not None:
        pk = peaks(devices[0].device_kind)
        work = {"rounds": rounds, "tokens": rounds * tokens_per_round,
                "flops_per_token": counts.lm_train_flops_per_token(cfg, S),
                "peak_flops": pk["bf16_flops"]}
        traced = tracewin.reduce(rec.spans, work, len(devices))

    print(f"bench: window {rounds} rounds in {window} s, peak {peak} bytes; "
          "reference follows", file=sys.stderr)
    in_spans = [(s["t0"] - feed.t_open, s["t1"] - s["t0"]) for s in rec.spans
                if s["name"] == "round" and feed.t_open <= s["t0"] < feed.t_close]
    lengths = sorted(d for _, d in in_spans)
    if lengths:
        slow = [(round(t0, 3), d) for t0, d in in_spans if d > 0.25]
        print(f"bench: the window's round spans hold {sum(lengths)} s; per round min "
              f"{lengths[0]}, median {lengths[len(lengths) // 2]}, longest five "
              f"{lengths[-5:]} s; rounds over 0.25 s (start in the window, length) {slow}",
              file=sys.stderr)
    print(f"bench: garbage collector in the window: {watch.summary()}", file=sys.stderr)
    prog["loss"] = [rec.losses.get(i, math.nan) for i in range(checked)]
    if "after" in x0:
        server, workers = x0.pop("after")
        prog["change"] = [ref.leaf_change_norms(server, x0["tree"])] + [
            ref.leaf_change_norms(w, x0["tree"]) for w in ref.worker_trees(workers, n)]
        del server, workers
    x0.clear()
    k_init, k_steps = jax.random.split(key)
    t_ref = time.perf_counter()
    reference = ref.run(cfg, t, pool[:checked], k_init,
                        [jax.random.fold_in(k_steps, i) for i in range(checked)])
    checks = train_checks(prog, reference, cell.limits)
    print(f"bench: reference took {time.perf_counter() - t_ref} s; losses program "
          f"{prog['loss']} reference {reference['loss']}", file=sys.stderr)
    return Outcome(setup_end=feed.t_open, window_end=feed.t_close, metrics=metrics,
                   attempted=rounds, failed=failed, checks=checks, memory_peak_bytes=peak,
                   traced=traced)


def control(cell, devices, levels=("fp8",)) -> Dict[str, List[Check]]:
    """The control: the reference in the program's place, its matmul
    operands in float8 (the precision below the configuration's bfloat16),
    compared as the program's readings are; by level. ``"fault:<name>"``
    reads the reference with that fault planted instead (``half_batch``,
    ``altered``)."""
    import jax

    cfg, t = cell.config, cell.traffic
    n, checked = cfg["train"]["workers"], t["checked_rounds"]
    ref = load_reference(cfg)
    key = seed_key(cell.seed)
    pool = token_pool(jax.random.fold_in(key, 1), checked, (n, t["batch_per_worker"], t["seq_len"]),
                      cfg["vocab_size"])
    k_init, k_steps = jax.random.split(key)
    keys = [jax.random.fold_in(k_steps, i) for i in range(checked)]
    exact = ref.run(cfg, t, pool, k_init, keys)
    out = {}
    for level in levels:
        fault = level[len("fault:"):] if level.startswith("fault:") else ""
        low = ref.run(cfg, t, pool, k_init, keys, matmul="f32" if fault else level, fault=fault)
        out[level] = train_checks(low, exact, cell.limits)
    return out
