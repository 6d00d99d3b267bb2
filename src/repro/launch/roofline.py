"""Roofline analysis from dry-run artifacts (§Roofline in EXPERIMENTS.md).

Reads ``experiments/dryrun/*.json`` (written by ``repro.launch.dryrun``) and
derives the three per-step roofline terms for TPU v5e:

    compute    = HLO_FLOPs_per_chip / 197 TF/s
    memory     = HBM_bytes_per_chip / 819 GB/s
    collective = collective_bytes_per_chip / 50 GB/s

HLO_FLOPs and collective bytes are the trip-count-aware totals from
``hlo_analysis`` (per-device, since the module is the partitioned program).
HBM bytes use a lower-bound traffic model: every while-body iteration must
re-read its live weight shards and stream its major activations — we proxy
this as (argument_bytes + temp_bytes + output_bytes) per step, which is the
buffer-assignment working set. This *underestimates* re-streaming inside
loops, so memory-bound verdicts here are conservative; the dominant-term
analysis in EXPERIMENTS.md discusses this.

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) for training;
2·N[_active]·D for single forward passes (prefill/decode), per chip.
"""
from __future__ import annotations

import argparse
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

from ..configs import SHAPES_BY_NAME, get_config
from .mesh import chip_peaks
from .plan import WHISPER_DECODER_PROMPT

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"
# the dry-run lowers for the v5e production mesh; its placeholder CPU
# devices carry no device kind of their own
PEAKS = chip_peaks("TPU v5 lite")


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops_per_chip: float
    hlo_flops_per_chip: float
    fit_gb: float
    tag: str = ""

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPS — how much lowered compute is useful."""
        if self.hlo_flops_per_chip <= 0:
            return 0.0
        return self.model_flops_per_chip / self.hlo_flops_per_chip

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU bound: useful FLOPs / (bound time × peak)."""
        if self.bound_s <= 0:
            return 0.0
        return self.model_flops_per_chip / (self.bound_s * PEAKS.flops_bf16)


def model_flops_per_chip(arch: str, shape: str, chips: int) -> float:
    """Analytic useful FLOPs per chip for one step of the cell."""
    cfg = get_config(arch)
    cell = SHAPES_BY_NAME[shape]
    n_active = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        total = 6.0 * n_active * tokens
    elif cell.kind == "prefill":
        if cfg.family == "audio":
            # encoder processes seq_len frames; decoder prompt is small
            tokens = cell.global_batch * (cell.seq_len + WHISPER_DECODER_PROMPT)
            total = 2.0 * n_active * tokens  # enc+dec share the 2·N·D model
        else:
            tokens = cell.global_batch * cell.seq_len
            total = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        tokens = cell.global_batch
        total = 2.0 * n_active * tokens
    return total / chips


def load_results(results_dir: Path = RESULTS_DIR) -> List[dict]:
    out = []
    for p in sorted(results_dir.glob("*.json")):
        try:
            out.append(json.loads(p.read_text()))
        except json.JSONDecodeError:
            continue
    return out


def roofline_for(result: dict) -> Optional[Roofline]:
    if result.get("status") != "ok":
        return None
    chips = result["chips"]
    mem = result["memory"]
    # donated outputs alias argument buffers — count them once
    hbm_bytes = (
        mem["argument_bytes"]
        + mem["temp_bytes"]
        + max(0, mem["output_bytes"] - mem["alias_bytes"])
    )
    flops = result["cost"]["flops"]
    coll = result.get("collective_bytes_total", 0.0)
    mf = model_flops_per_chip(result["arch"], result["shape"], chips)
    return Roofline(
        arch=result["arch"],
        shape=result["shape"],
        mesh=result["mesh"],
        compute_s=flops / PEAKS.flops_bf16,
        memory_s=hbm_bytes / PEAKS.hbm_bw,
        collective_s=coll / PEAKS.ici_bw_per_link,
        model_flops_per_chip=mf,
        hlo_flops_per_chip=flops,
        fit_gb=hbm_bytes / 2**30,
        tag=result.get("tag", ""),
    )


def table(results_dir: Path = RESULTS_DIR, mesh: str = "16x16", tag: str = "") -> str:
    rows = []
    for r in load_results(results_dir):
        if r.get("mesh") != mesh or r.get("tag", "") != tag:
            continue
        rl = roofline_for(r)
        if rl is None:
            rows.append(
                f"| {r['arch']} | {r['shape']} | {r.get('status')} | "
                f"{r.get('reason', r.get('error', ''))[:60]} |"
            )
            continue
        rows.append(
            f"| {rl.arch} | {rl.shape} | {rl.compute_s:.4f} | {rl.memory_s:.4f} | "
            f"{rl.collective_s:.4f} | **{rl.dominant}** | {rl.useful_ratio:.3f} | "
            f"{rl.roofline_fraction * 100:.1f}% | {rl.fit_gb:.1f} |"
        )
    header = (
        "| arch | shape | compute (s) | memory (s) | collective (s) | dominant | "
        "MODEL/HLO | roofline frac | fit GB/chip |\n"
        "|---|---|---|---|---|---|---|---|---|"
    )
    return header + "\n" + "\n".join(rows)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--tag", default="")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    if args.json:
        out = []
        for r in load_results():
            rl = roofline_for(r)
            if rl is not None and r.get("mesh") == args.mesh and r.get("tag", "") == args.tag:
                out.append(rl.__dict__ | {
                    "dominant": rl.dominant,
                    "useful_ratio": rl.useful_ratio,
                    "roofline_fraction": rl.roofline_fraction,
                })
        print(json.dumps(out, indent=2))
    else:
        print(table(mesh=args.mesh, tag=args.tag))


if __name__ == "__main__":
    main()
