"""The training driver knows no model: every configuration the program
registers builds through it from a JSON file, a reference module that
lacks an export stops a run at set-up, and the zamba2 reference trained by
the shared round (``bench/lib/reference_train.py``) reads what it always
read."""
import dataclasses
import hashlib
import json
import time

import jax
import numpy as np
import pytest

import repro.train
from bench.lib import harness, reference_train
from bench.lib.drivers import train
from bench_smoke import zamba2_cell
from repro import configs


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_every_smoke_config_builds_from_its_json(arch):
    want = configs.get_smoke(arch)
    as_file = json.loads(json.dumps(dataclasses.asdict(want)))
    as_file.update(source="https://example.org/not-read", reference="not_read.py")
    assert train.model_config(as_file) == want


def test_the_zamba2_file_builds_its_mamba_group_and_no_other():
    from repro.models.config import MambaConfig, ModelConfig

    cfg = zamba2_cell().config
    got = train.model_config(cfg)
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items() if k in fields}
    want = ModelConfig(**dict(kw, block_pattern=tuple(kw["block_pattern"]),
                              mamba=MambaConfig(**kw["mamba"])))
    assert got == want
    assert got.moe is got.mla is got.rwkv is None


def test_a_reference_without_its_flop_count_stops_the_run_at_set_up(tmp_path, monkeypatch):
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "uncounted_reference.py").write_text(
        "def init_params(cfg, key):\n    return {}\n\n\n"
        "def forward_loss(cfg, params, tokens, mm):\n    return 0.0\n")
    monkeypatch.setattr(train, "BENCH", tmp_path)

    def no_round(*a, **kw):
        raise AssertionError("a round ran")

    monkeypatch.setattr(repro.train, "train_loop", no_round)
    cell = zamba2_cell()
    cell.config["reference"] = "uncounted_reference.py"
    with pytest.raises(AttributeError, match=r"uncounted_reference\.py exports no "
                                             r"forward_flops_per_token"):
        harness.run_cell(cell, jax.devices()[:1], time.perf_counter())


# Readings of the zamba2 reference at smoke widths on the CPU, taken with
# ``zamba2_reference.run`` as it stood before the training round moved into
# ``bench/lib/reference_train.py``, on the seed and tokens below. ``m1`` is the
# sha256 of the first moment's leaves' bytes in leaf order; ``change`` that of
# the server's and the workers' change norms as float64.
PINNED = {
    "loss": [6.173543453216553, 6.171448707580566, 6.020638942718506],
    "grad": [17.34733009338379, 0.1631097048521042, 0.41049450635910034,
             0.004389559384435415, 0.2657334804534912, 1.6014912128448486,
             2.776503324508667, 0.005189880263060331, 3.371440887451172,
             0.26443350315093994, 3.082486152648926, 0.04857254773378372,
             0.0003523293707985431, 0.030436232686042786, 0.2701420485973358,
             0.3478035628795624, 0.0006959009333513677, 0.3837134838104248,
             0.034269195050001144, 0.37178054451942444, 0.17630437016487122,
             0.2965370714664459, 0.17573611438274384, 0.29577770829200745,
             0.047510962933301926, 0.06158578395843506, 0.3722565174102783,
             0.36577004194259644, 0.5109126567840576, 0.780199408531189],
    "m1": "984a307885f55e8b10e2601b686ceca2456df0530aac37f2560115e8be620083",
    "change": "6ec3ccdc87bab191cbf0b03ded6a36ccccf0fd50fc437a8fa499bfa5bbf1f308",
}


def test_the_shared_round_reads_the_pinned_zamba2_readings_to_the_bit():
    cell = zamba2_cell()
    cfg, t = cell.config, cell.traffic
    n, checked = cfg["train"]["workers"], t["checked_rounds"]
    key = harness.seed_key(cell.seed)
    pool = train.token_pool(jax.random.fold_in(key, 1), checked,
                            (n, t["batch_per_worker"], t["seq_len"]), cfg["vocab_size"])
    k_init, k_steps = jax.random.split(key)
    keys = [jax.random.fold_in(k_steps, i) for i in range(checked)]
    got = reference_train.run(train.load_reference(cfg), cfg, t, pool, k_init, keys)
    assert got["loss"] == PINNED["loss"]
    assert got["grad"] == PINNED["grad"]
    m1 = b"".join(np.ascontiguousarray(a).tobytes() for a in got["m1"])
    assert hashlib.sha256(m1).hexdigest() == PINNED["m1"]
    change = np.asarray(got["change"], np.float64).tobytes()
    assert hashlib.sha256(change).hexdigest() == PINNED["change"]
