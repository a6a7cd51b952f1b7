"""Operation and byte counts against hand counts, and the peaks table."""
import pytest

from bench.lib import counts
from bench.lib.peaks import peaks
from bench.lib.harness import BENCH, load_json
from bench_smoke import zamba2_cell


def test_lm_flops_per_token_at_smoke_widths():
    cfg = zamba2_cell().config
    # per Mamba2 layer: in_proj 2*64*(2*128+2*16+8) = 37888, conv 2*4*160 = 1280,
    # state 2*2*16*16*8 = 8192, out_proj 2*128*64 = 16384 -> 63744, six of them
    mamba = 6 * 63744
    # shared block: q,k,v,o 2*64*192 + 2*64*64 = 32768, QK and PV 2*2*64*65/2 = 8320,
    # gated MLP 2*3*64*128 = 49152
    shared = 32768 + 8320 + 49152
    head = 2 * 64 * 256
    assert counts.lm_forward_flops_per_token(cfg, 64) == mamba + shared + head == 505472
    assert counts.lm_train_flops_per_token(cfg, 64) == 3 * 505472


def test_lm_flops_per_token_at_the_cell_size():
    cfg = load_json(BENCH / "configs" / "zamba2-1.2b-p1.json")
    # dominated by 2 x (parameters outside the embedding): 2 x 303M, plus
    # attention over 2048 positions, 2 x 2 x 4096 x 1024.5
    per_token = counts.lm_forward_flops_per_token(cfg, 2048)
    assert 6.0e8 < per_token < 6.5e8


def test_peaks_of_the_v5e_and_an_unknown_kind():
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")
