"""The whole training step's share of the chip's bf16 peak: model FLOPs per
token (counted from shapes) times tokens per second."""
from bench.lib.readers import model_flops_pct


def read(traced):
    return model_flops_pct(traced)
