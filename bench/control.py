"""Readings that set a cell's correctness limit, on the chip, in one process.

    python bench/control.py --workload granite-3-8b.gsm8k-backlog \\
        --seconds 30 --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control-seeds 1 2 3

For every seed: weights from the seed, the cell's own slots and pages (fitted
once), a serve of the cell's traffic for ``--seconds`` of window at the
cell's load, and the check the benchmark makes (``harness/check.py``). It
prints the program's widest token gap for each seed (the lower reading is
the largest of them). For the control seeds it also runs the control, the
fp8 reference in the program's place, on the same prompts and served tokens,
and prints its widest gap (the upper reading is the smallest of them). Each
reading goes through the benchmark's own verdict with the cell's limits: the
program's as ``correct``, the control's (its gap in the program's) as
``control_correct``, which has to come out false. The benchmark's own runs
never run the control. Exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(1, str(ROOT / "src"))

from harness import check, drive  # noqa: E402
from harness.cell import load_cell  # noqa: E402
from harness.peaks import peaks_for  # noqa: E402
from harness.runner import NoChip, compile_cache_dir, devices_for  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        devices = devices_for(cell)
    except NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    compile_cache_dir()
    hbm = peaks_for(devices[0].device_kind).hbm_bytes
    fit = None
    rows = []
    for seed in args.seeds:
        setup = drive.build(cell.config, cell.traffic, seed, hbm, cell.max_slots, fit=fit)
        fit = (setup.n_slots, setup.num_pages, setup.program_bytes)
        run = drive.serve(setup, seed, args.seconds)
        generated = {rid: list(t) for rid, t in setup.engine.generated.items()}
        setup.engine = None
        gc.collect()
        nums = check.check(run, seed, setup.params, generated,
                           control=seed in args.control_seeds)
        row = {"seed": seed, "slots": fit[0], "compiles_in_window": run.window_compiles}
        row.update(nums)
        row["correct"] = check.verdict(nums, cell.limits)[0]
        if "control_gap_max" in nums:
            ctl = dict(nums, token_gap_max=nums["control_gap_max"])
            row["control_correct"] = check.verdict(ctl, cell.limits)[0]
        rows.append(row)
        print("reading " + json.dumps(row), flush=True)
        del setup, run, generated
        gc.collect()
    prog = [r["token_gap_max"] for r in rows if r["token_gap_max"] is not None]
    ctrl = [r["control_gap_max"] for r in rows if r.get("control_gap_max") is not None]
    print("summary " + json.dumps({
        "workload": cell.name, "seeds": len(prog), "limits": cell.limits,
        "lower_reading": max(prog) if prog else None,
        "upper_reading": min(ctrl) if ctrl else None,
        "program": prog, "control": ctrl,
        "program_correct": sum(r["correct"] for r in rows),
        "control_correct": sum(r.get("control_correct", False) for r in rows),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
