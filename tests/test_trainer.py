"""LM trainer + downlink integration, checkpoint/data/serve substrate tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.data import SyntheticLMData, batch_specs
from repro.models import lm
from repro.optim import make_optimizer
from repro.optim.schedules import constant_lr, cosine_warmup, inv_sqrt
from repro.serve import DecodeEngine
from repro.train import TrainerConfig, init_state, make_downlink, make_train_step
from repro.train.downlink import MarinaPDownlink, tree_size


@pytest.fixture(scope="module")
def small():
    cfg = configs.get_smoke("gemma-2b")
    tcfg = TrainerConfig(n_workers=2, attn_chunk=32)
    return cfg, tcfg


def _run(cfg, tcfg, spec, steps=8, polyak=0.0):
    if polyak:
        tcfg = TrainerConfig(n_workers=tcfg.n_workers, attn_chunk=tcfg.attn_chunk,
                             polyak_factor=polyak)
    dl = make_downlink(spec, tcfg.n_workers)
    opt = make_optimizer("adamw")
    state = init_state(cfg, tcfg, dl, opt, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, tcfg, dl, opt, constant_lr(2e-3)))
    data = SyntheticLMData(cfg, tcfg.n_workers, 2, 64)
    hist = []
    for i in range(steps):
        state, m = step(state, data.batch(i), jax.random.fold_in(jax.random.PRNGKey(9), i))
        hist.append(float(m["loss"]))
    return state, hist, m


@pytest.mark.parametrize("spec", ["marina:perm", "marina:ind", "marina:same", "ef21p:16:64", "none"])
def test_loss_decreases_all_downlinks(small, spec):
    cfg, tcfg = small
    _, hist, _ = _run(cfg, tcfg, spec, steps=10)
    assert hist[-1] < hist[0], (spec, hist)
    assert not any(np.isnan(hist))


def test_polyak_lr_runs(small):
    cfg, tcfg = small
    _, hist, m = _run(cfg, tcfg, "marina:perm", steps=6, polyak=0.5)
    assert float(m["lr"]) > 0 and not np.isnan(hist[-1])


def test_marina_workers_average_tracks_server(small):
    """RotK exact-mean: mean_i w_i == x after a no-sync round."""
    cfg, tcfg = small
    dl = MarinaPDownlink(n_workers=4, mode="perm", p=1e-9)  # never full-sync
    server = lm.lm_init(cfg, jax.random.PRNGKey(0))
    workers = dl.init_workers(server)
    delta_tree = jax.tree.map(lambda t: jnp.ones_like(t) * 0.01, server)
    server_new = jax.tree.map(lambda a, b: a + b, server, delta_tree)
    new_workers, bits = dl.round(jax.random.PRNGKey(1), server_new, server, workers)
    mean_w = jax.tree.map(lambda w: jnp.mean(w, axis=0), new_workers)
    err = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(mean_w), jax.tree.leaves(server_new))
    )
    assert err < 1e-5
    assert float(bits) > 0


def test_ef21p_downlink_drift_contracts(small):
    """Repeated rounds at a fixed server point must contract the shift error."""
    cfg, _ = small
    from repro.train.downlink import EF21PDownlink

    dl = EF21PDownlink(n_workers=2, k_per_block=16, block=64)
    server = lm.lm_init(cfg, jax.random.PRNGKey(0))
    target = jax.tree.map(lambda t: t + 0.1, server)
    shift = dl.init_shift(server)
    drifts = []
    for i in range(6):
        shift, _ = dl.round(jax.random.PRNGKey(i), target, shift)
        drifts.append(float(dl.worker_drift(target, shift)))
    assert drifts[-1] < 0.3 * drifts[0]


def test_bits_accounting_formula(small):
    cfg, tcfg = small
    dl = make_downlink("marina:perm", 2)
    d = tree_size(lm.lm_init(cfg, jax.random.PRNGKey(0)))
    state, hist, m = _run(cfg, tcfg, "marina:perm", steps=4)
    bits = float(m["bits_per_worker"])
    # between 4 sparse rounds and 4 dense rounds
    import math
    lo = 4 * (65 + math.log2(d)) * d / 2 * 0.9
    hi = 4 * 64.0 * d * 1.1
    assert lo <= bits <= hi


# -- substrate ---------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path, small):
    cfg, _ = small
    params = lm.lm_init(cfg, jax.random.PRNGKey(0))
    path = os.path.join(tmp_path, "ckpt.npz")
    save_checkpoint(path, params, step=7, extra={"arch": cfg.arch_id})
    restored, meta = load_checkpoint(path, params)
    assert meta["step"] == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_data_deterministic_and_sharded(small):
    cfg, _ = small
    data = SyntheticLMData(cfg, n_workers=3, batch_per_worker=2, seq_len=32)
    b1, b2 = data.batch(5), data.batch(5)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    assert b1["tokens"].shape == (3, 2, 32)
    b3 = data.batch(6)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b3["tokens"]))
    assert int(b1["tokens"].max()) < cfg.vocab_size


def test_batch_specs_match_data(small):
    cfg, _ = small
    spec = batch_specs(cfg, 3, 2, 32)
    data = SyntheticLMData(cfg, 3, 2, 32).batch(0)
    assert jax.tree.structure(spec) == jax.tree.structure(data)
    for s, d in zip(jax.tree.leaves(spec), jax.tree.leaves(data)):
        assert s.shape == d.shape and s.dtype == d.dtype


def test_serve_engine_greedy_deterministic(small):
    cfg, _ = small
    params = lm.lm_init(cfg, jax.random.PRNGKey(0))
    eng = DecodeEngine(cfg, params, cache_len=64, batch_size=2)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    t1 = eng.run(prompts, n_new_tokens=6)
    t2 = eng.run(prompts, n_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert t1.shape == (2, 6)


def test_serve_engine_latency_telemetry(small):
    """Each request logs prefill/decode timers + a tokens/s metric."""
    from repro import obs

    cfg, _ = small
    params = lm.lm_init(cfg, jax.random.PRNGKey(0))
    tr = obs.MemoryTracker()
    eng = DecodeEngine(cfg, params, cache_len=64, batch_size=2, tracker=tr)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    eng.run(prompts, n_new_tokens=4)
    eng.run(prompts, n_new_tokens=4)
    timers = [e["name"] for e in tr.events if e["kind"] == "timer"]
    assert timers == ["serve/prefill", "serve/decode"] * 2
    mets = [e for e in tr.events if e["kind"] == "metrics"]
    assert len(mets) == 2
    assert mets[0]["metrics"]["serve/tokens_per_s"] > 0
    assert mets[0]["metrics"]["serve/batch"] == 2


def test_train_loop_tracker_and_uplink_bits(small):
    """train_loop covers each round with feed/dispatch/wait/log spans and
    logs its metrics; uplink accrues dense bits/step."""
    import math

    from repro import obs
    from repro.data import SyntheticLMData
    from repro.optim import make_optimizer
    from repro.train import train_loop

    cfg, tcfg = small
    dl = make_downlink("marina:perm", tcfg.n_workers)
    tr = obs.MemoryTracker()
    data = SyntheticLMData(cfg, tcfg.n_workers, 2, 64)
    state, m = train_loop(
        cfg, tcfg, dl, make_optimizer("adamw"), constant_lr(2e-3), data,
        steps=3, key=jax.random.PRNGKey(0), tracker=tr,
    )
    spans = [e for e in tr.events if e["kind"] == "span"]
    rounds = [s for s in spans if s["name"] == "round"]
    assert [r["attrs"]["round"] for r in rounds] == [0, 1, 2]
    for r in rounds:
        assert r["parent"] is None and "loss" not in r["attrs"]
        kids = sorted((s for s in spans if s["parent"] == r["span_id"]),
                      key=lambda s: s["t0"])
        assert [k["name"] for k in kids] == ["feed", "dispatch", "wait", "log"]
        edges = [r["t0"]] + [t for k in kids for t in (k["t0"], k["t1"])] + [r["t1"]]
        assert edges == sorted(edges)
        assert all(k["t1"] > k["t0"] for k in kids)
    assert not [e for e in tr.events if e["kind"] == "timer"]
    mets = [e for e in tr.events if e["kind"] == "metrics"]
    d = tree_size(state["server"])
    # uplink = one exact dense (64-bit model) gradient per worker per step
    assert float(m["uplink_bits_per_worker"]) == pytest.approx(3 * 64.0 * d, rel=1e-6)
    assert mets[-1]["metrics"]["train/uplink_bits_per_worker"] == pytest.approx(
        float(m["uplink_bits_per_worker"]), rel=1e-6
    )
    assert "train/loss" in mets[0]["metrics"]


@pytest.mark.parametrize("spec", ["marina:perm", "ef21p:16:64"])
def test_step_ops_carry_named_scopes(small, spec):
    """The compiled step's ops name the phase of the round that made them."""
    import re

    cfg, tcfg = small
    dl = make_downlink(spec, tcfg.n_workers)
    opt = make_optimizer("adamw")
    state = init_state(cfg, tcfg, dl, opt, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, tcfg, dl, opt, constant_lr(2e-3)))
    batch = SyntheticLMData(cfg, tcfg.n_workers, 2, 64).batch(0)
    hlo = step.lower(state, batch, jax.random.PRNGKey(1)).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("fwd_bwd", "aggregate", "optimizer", "downlink", "drift"):
        assert any(f"/{scope}/" in n for n in op_names), scope
    # backward ops are the transposes of the forward pass, inside fwd_bwd
    assert any("/fwd_bwd/" in n and "transpose(" in n for n in op_names)


def test_lr_schedules():
    sch = cosine_warmup(1.0, warmup=10, total=100)
    assert float(sch(jnp.int32(0))) == 0.0
    assert float(sch(jnp.int32(10))) == pytest.approx(1.0)
    assert float(sch(jnp.int32(100))) == pytest.approx(0.1, abs=1e-3)
    d = inv_sqrt(2.0)
    assert float(d(jnp.int32(3))) == pytest.approx(1.0)


def test_optimizers_step():
    from repro.optim import adamw_init, adamw_update, sgd_init, sgd_update

    params = {"w": jnp.ones((4,))}
    grads = {"w": jnp.ones((4,))}
    st = adamw_init(params)
    p2, st = adamw_update(grads, st, params, 0.1)
    assert float(p2["w"][0]) < 1.0
    st2 = sgd_init(params, momentum=0.9)
    p3, st2 = sgd_update(grads, st2, params, 0.1, momentum=0.9)
    np.testing.assert_allclose(np.asarray(p3["w"]), 0.9)
