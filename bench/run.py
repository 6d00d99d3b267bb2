"""Run one benchmark cell once, on the chips of the machine it starts on.

    python bench/run.py --workload granite-3-8b.gsm8k-backlog --seed 7 \\
        --seconds 30 --trace 0

Builds the cell named in ``BENCHMARK.json`` (its configuration, traffic mix,
metrics and limits are found by name under ``bench/``), serves its traffic
through the program in ``src/`` for ``--seconds`` of measured window, checks
the served tokens against a float32 reference, and prints one JSON object as
the last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics from a profiler trace of the window), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: every number compared
with its limit, which also end standard error.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for, and when the program under ``src/`` is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(1, str(ROOT / "src"))

from harness.cell import load_cell  # noqa: E402
from harness.runner import NoChip, devices_for, print_result, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        devices = devices_for(cell)
    except NoChip as e:
        print(f"run.py: {e}; this benchmark runs only on the chip", file=sys.stderr)
        return 2
    import repro  # noqa: F401  -- fails here, before any work, without src/

    line, check_lines = run_cell(
        cell, args.seed, args.seconds, bool(args.trace), T_START, devices=devices,
    )
    print_result(line, check_lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
