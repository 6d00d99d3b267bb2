"""One run of one cell, from set-up to the result line."""
from __future__ import annotations

import gc
import os
import shutil
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

import jax

from . import check, drive, reduce
from .cell import ROOT, Cell, reader
from .peaks import Peaks, peaks_for


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set (JAX reads it itself), else
    ``.jax_cache/`` at the root of the checkout: a fixed path, since the
    path is part of the cache's key."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = outside or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def devices_for(cell: Cell):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips; JAX found {len(devices)}")
    return devices


def _fmt(v) -> str:
    return repr(v) if isinstance(v, float) else str(v)


def run_cell(
    cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
    devices=None, peaks: Optional[Peaks] = None, log=print,
) -> Tuple[dict, List[str]]:
    """Run ``cell`` once. Returns the result line (a dict) and the check
    lines for the end of standard error. ``devices``/``peaks`` let a test
    drive the run on the CPU; a run from ``run.py`` takes both from the
    chip."""
    devices = devices if devices is not None else devices_for(cell)
    dev = devices[0]
    peaks = peaks or peaks_for(dev.device_kind)
    log(f"compile cache: {compile_cache_dir()}")
    conf, mix = cell.config, cell.traffic
    setup = drive.build(conf, mix, seed, peaks.hbm_bytes, cell.max_slots)
    log(
        f"config {conf['name']}: layers={conf['num_hidden_layers']} "
        f"d={conf['hidden_size']} heads={conf['num_attention_heads']}/"
        f"{conf['num_key_value_heads']} ff={conf['intermediate_size']} "
        f"vocab={conf['vocab_size']}; fit: slots={setup.n_slots} "
        f"pages={setup.num_pages} program_bytes={setup.program_bytes}"
    )
    log(
        f"setup parts s: weights={setup.weights_s:.3f} fit={setup.fit_s:.3f} "
        f"warm={setup.warm_s:.3f}"
    )
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        run = drive.serve(setup, seed, seconds, trace_dir=trace_dir)
        run.setup_s = run.ws - t_start
        run.peaks = peaks
        stats_mem = dev.memory_stats() or {}
        peak_bytes = stats_mem.get("peak_bytes_in_use")
        generated = {rid: list(t) for rid, t in setup.engine.generated.items()}
        # free the KV pool and the engine before the reference runs
        setup.engine = None
        gc.collect()
        if trace_dir:
            run.reduction = reduce.reduce_trace(reduce.load(reduce.find_trace(trace_dir)))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    steps = run.window_steps()
    kinds: Dict[str, List[float]] = {}
    for s in steps:
        row = kinds.setdefault(s.stage.kind.value, [0, 0, 0.0])
        row[0] += 1
        row[1] += s.stage.rounds
        row[2] += s.stage.duration
    log(f"window: {run.window_s:.3f} s, {len(steps)} stages; by kind "
        f"[stages, rounds, dispatch-to-results s]: {kinds}")
    log(f"engine counters (whole serve): {run.meta}")
    log(f"compiles inside the window: {len(run.window_compiles)} {run.window_compiles}")
    log(f"output_tok_s (all cells): {reader('output_tok_s')(run)!r}")
    if run.reduction is not None:
        red = run.reduction
        log(f"trace: busy {red.busy_s!r} s of {red.window_s!r} s on {red.devices} device(s)")
        log(f"trace programs [runs, s]: {red.programs}")

    numbers = check.check(run, seed, setup.params, generated)
    ok, rows = check.verdict(numbers, cell.limits)
    log(f"check: {numbers}")

    attempted = sum(
        1 for r in run.reqs.values()
        if r.admit_step is not None and run.steps[r.admit_step].t1 <= run.we
    )
    failed = numbers["budget_mismatches"]

    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        v = reader(m.name)(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
        "memory_peak_bytes": peak_bytes,
    }
    line = {
        "correct": bool(ok), "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device,
    }
    if run.reduction is not None:
        red = run.reduction
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        line["breakdown"] = {
            "device_ops": [[k, v] for k, v in red.top_ops(10)],
            "idle_gaps": [[k, v] for k, v in red.gaps[:10]],
        }
    line["checks"] = {
        name: {"value": value, "limit": limit} for name, value, limit in rows
    }
    check_lines = [f"check {name}: {_fmt(value)} limit {limit}" for name, value, limit in rows]
    check_lines.append(f"correct: {ok}")
    return line, check_lines


def print_result(line: dict, check_lines: List[str]) -> None:
    import json

    sys.stdout.flush()
    for s in check_lines:
        print(s, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()
