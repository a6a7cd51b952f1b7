"""The training driver at smoke size on the CPU: a sound run comes out
correct, and a run with the timed path broken underneath comes out not
correct, once for each fault the training cells can have."""
import time

import jax
import pytest

import repro.train.trainer as trainer
from bench.lib import harness
from bench_smoke import zamba2_cell


def _run():
    cell = zamba2_cell()
    out = harness.run_cell(cell, jax.devices()[:1], time.perf_counter())
    return out, {c.name: c for c in out.checks}


def _broken(monkeypatch, fault):
    make = trainer.make_train_step

    def patched(*a, **kw):
        step = make(*a, **kw)

        def broken(state, batch, key, force_sync=False):
            if fault == "half_batch":  # worker 1's rows replaced: the mean is over the rest
                toks = batch["tokens"]
                batch = {"tokens": toks.at[1].set(toks[0])}
            new, m = step(state, batch, key, force_sync)
            if fault == "unchanged":
                return state, m
            if fault == "altered":
                new = dict(new, server=dict(new["server"], ln_f={
                    "scale": new["server"]["ln_f"]["scale"] + 1e-2}))
            return new, m

        return broken

    monkeypatch.setattr(trainer, "make_train_step", patched)


def test_sound_step_run_is_correct():
    out, checks = _run()
    assert all(c.ok for c in checks.values()), checks
    assert out.attempted > 0 and out.failed == 0
    assert out.metrics["train_tokens_per_s"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    _broken(monkeypatch, fault)
    _, checks = _run()
    assert not all(c.ok for c in checks.values()), (fault, checks)
