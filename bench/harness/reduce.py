"""Reduction of a profiler trace (the Perfetto JSON that
``jax.profiler`` writes beside its xplane) to device metrics.

What it reads:

* processes named ``/device:<KIND>:<n>`` (not CPU) are devices. On each, the
  thread whose name holds ``Ops`` carries one event per operation that ran,
  and the thread whose name holds ``Modules`` one event per program (jitted
  module) that ran. Without a module thread, programs are read from the
  ``hlo_module`` argument of the operation events. The engine's programs
  are jitted partials, which the trace names ``jit__unknown(<hash>)``, so a
  program run is attributed by time instead: to the harness's step (and so
  the stage kind) it ran in (``stats.stage_device_seconds``);
* the host's spans that the harness writes with ``TraceAnnotation``:
  ``window`` (the measured window, which sets the traced window's bounds),
  ``serve_step`` and ``client``.

What it gives: the union of operation intervals inside the window (busy
time, averaged over the devices), the window's length, device time per
program and per operation name, and each idle gap with what the host was
doing in it. All times are the trace's own microseconds, converted to
seconds.
"""
from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HOST_SPANS = ("serve_step", "client")
_DEVICE = re.compile(r"^/device:([A-Z]+):\d+$")

Interval = Tuple[float, float]


@dataclass
class Reduction:
    window: Interval                          # (start, end), trace us
    devices: int
    busy_s: float                             # mean over devices
    window_s: float
    programs: Dict[str, Tuple[int, float]]    # module -> (runs, seconds)
    ops: Dict[str, float]                     # op name -> seconds
    gaps: List[Tuple[str, float]] = field(default_factory=list)
    runs: List[Interval] = field(default_factory=list)  # first device's programs

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The operations that took most device time; loops and
        conditionals, whose events enclose the operations they run, are
        left out."""
        flat = [
            (k, v) for k, v in self.ops.items()
            if not k.startswith(("while", "conditional"))
        ]
        return sorted(flat, key=lambda kv: -kv[1])[:n]


def load(path: Path) -> dict:
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv: Interval, win: Interval) -> Optional[Interval]:
    a, b = max(iv[0], win[0]), min(iv[1], win[1])
    return (a, b) if b > a else None


def reduce_trace(trace: dict) -> Reduction:
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    procs: Dict[int, str] = {}
    threads: Dict[Tuple[int, int], str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e.get("tid"))] = e["args"]["name"]
    devices = {
        pid for pid, name in procs.items()
        if (m := _DEVICE.match(name)) and m.group(1) != "CPU"
    }
    windows, host, ops, modules = [], [], defaultdict(list), defaultdict(list)
    for e in events:
        if e.get("ph") != "X":
            continue
        iv = (float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0.0)))
        if e["pid"] in devices:
            tname = threads.get((e["pid"], e.get("tid")), "")
            if "Modules" in tname:
                modules[e["pid"]].append((iv, e["name"]))
            elif "Ops" in tname:
                ops[e["pid"]].append((iv, e["name"], (e.get("args") or {}).get("hlo_module")))
        elif e["name"] == "window":
            windows.append(iv)
        elif e["name"] in HOST_SPANS:
            host.append((iv, e["name"]))
    if not windows:
        raise ValueError("trace has no 'window' span")
    if not devices:
        raise ValueError("trace has no device process")
    win = max(windows, key=lambda iv: iv[1] - iv[0])

    busy_total = 0.0
    busy_first: List[Interval] = []
    runs_first: List[Interval] = []
    programs: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    op_time: Dict[str, float] = defaultdict(float)
    for n, pid in enumerate(sorted(devices)):
        inside = [(c, name, mod) for iv, name, mod in ops[pid] if (c := _clip(iv, win))]
        for c, name, mod in inside:
            op_time[name] += (c[1] - c[0]) / 1e6
        mods = [(c, name) for iv, name in modules[pid] if (c := _clip(iv, win))]
        if mods:
            for c, name in mods:
                programs[name][0] += 1
                programs[name][1] += (c[1] - c[0]) / 1e6
        else:
            for c, _, mod in inside:
                if mod:
                    programs[mod][1] += (c[1] - c[0]) / 1e6
        busy = merge([c for c, _, _ in inside] or [c for c, _ in mods])
        busy_total += sum(b - a for a, b in busy) / 1e6
        if n == 0:
            busy_first = busy
            runs_first = sorted(c for c, _ in mods)
    window_s = (win[1] - win[0]) / 1e6
    steps = [iv for iv, name in host if name == "serve_step" and iv[1] <= win[1]]
    if busy_first and steps and steps[-1][1] - busy_first[-1][1] > 1e6:
        raise ValueError(
            "the trace lost device events: the last operation ends "
            f"{(steps[-1][1] - busy_first[-1][1]) / 1e6:.1f} s before the "
            "last serve_step (the profiler's buffer overflowed)"
        )

    # idle gaps of the first device, labelled by the host span that
    # overlaps each most (the harness's spans follow one another, so one
    # pointer walks them alongside the gaps)
    gaps: List[Tuple[str, float]] = []
    edges = [win[0]] + [x for iv in busy_first for x in iv] + [win[1]]
    spans = sorted(host)
    j = 0
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        while j < len(spans) and spans[j][0][1] <= a:
            j += 1
        best, label = 0.0, "other"
        for (s, t), name in spans[j:]:
            if s >= b:
                break
            ov = min(b, t) - max(a, s)
            if ov > best:
                best, label = ov, name
        gaps.append((label, (b - a) / 1e6))
    gaps.sort(key=lambda g: -g[1])
    return Reduction(
        window=win, devices=len(devices), busy_s=busy_total / len(devices),
        window_s=window_s,
        programs={k: (int(v[0]), float(v[1])) for k, v in programs.items()},
        ops=dict(op_time), gaps=gaps, runs=runs_first,
    )


def find_trace(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/perfetto_trace.json.gz"))
    if not found:
        raise FileNotFoundError(f"no perfetto_trace.json.gz under {log_dir}")
    return found[-1]
