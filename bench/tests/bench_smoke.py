"""The benchmark's cell at a size a CPU test run can hold: the zamba2
configuration at smoke widths, its layer pattern, and so the weights'
layout, kept."""
from bench.lib.harness import BENCH, Cell, load_json

SMOKE_WIDTHS = dict(d_model=64, num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128,
                    vocab_size=256, mamba={"state_dim": 16, "head_dim": 16, "expand": 2,
                                           "chunk": 32, "conv_width": 4})
# The cell's limits are set from chip readings at its own size; at smoke widths
# the program's bfloat16 gaps are wider. These are set the same way from CPU
# readings at smoke size: lower, the largest of 15 program seeds (loss 1.1e-3,
# grad 2.0e-2, grad_diff 5.2e-2, change 1.8e-2); upper, the float8 control's
# grad_diff (0.32) and the half-batch fault (grad 0.55, change 0.21).
SMOKE_LIMITS = {"loss_gap": 0.0035, "grad_gap": 0.1, "grad_diff": 0.14, "change_gap": 0.065}


def zamba2_cell(workload: str = "zamba2-marina-step", seed: int = 2**31 + 17,
                seconds: float = 0.5, trace: bool = False) -> Cell:
    cfg = load_json(BENCH / "configs" / "zamba2-1.2b-p1.json")
    cfg.update(SMOKE_WIDTHS)
    traffic = load_json(BENCH / "traffic" / "marina-step.json")
    traffic.update(seq_len=64, pool=8)
    return Cell(workload, cfg, traffic, dict(SMOKE_LIMITS), 1, seed, seconds, trace)
