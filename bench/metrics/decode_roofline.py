"""Fused decode's share of its roofline, %: the least time the traced
segment's fused decode rounds require, over the device time of the programs
that ran inside those stages (profiler trace).
Per round the least time is the larger of FLOPs / peak FLOP/s and bytes /
peak HBM bandwidth; bytes are the weights once, the KV of the tokens in use
and the new KV written (``harness/flops.py``). The gathered copy of each
slot's block table and the layer scan's copy of the pool are not counted:
they are not work the model requires."""
from harness import flops, stats


def read(run):
    if run.reduction is None:
        return None
    secs = stats.stage_device_seconds(run, "decode")
    rounds = stats.decode_round_contexts(run)
    if not rounds or secs <= 0:
        return None
    conf, pk = run.setup.conf, run.peaks
    least = sum(
        flops.least_seconds(*flops.decode_round(conf, ctx), pk.bf16_flops,
                            pk.hbm_bytes_per_s)
        for ctx in rounds
    )
    return 100.0 * least / secs
