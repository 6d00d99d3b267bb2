"""The whole step's share of the chip's peak, %: the model FLOPs the
traced segment's tokens require (2 x matmul weights per token processed, attention
over each token's context, the output head per sampled position;
``harness/stats.py``) over the device's busy seconds in the traced segment
times the chip's peak bf16 FLOP/s. It bounds any later kernel's claim. For
the backlog cells."""
from harness import stats


def read(run):
    if run.reduction is None or run.reduction.busy_s <= 0:
        return None
    f = stats.traced_model_flops(run)
    return 100.0 * f / (run.reduction.busy_s * run.peaks.bf16_flops)
