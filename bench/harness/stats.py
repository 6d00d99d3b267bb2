"""Arithmetic over what a run recorded, shared by the metric readers in
``bench/metrics/``: percentiles, the gaps between tokens as the host
received them, and the tokens each stage of the window processed."""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Tuple

import numpy as np

from . import flops


def percentile(values, q: float):
    """numpy's linear-interpolation percentile; None for no values."""
    values = list(values)
    return float(np.percentile(values, q)) if values else None


def dispatch_time(run, step_index: int) -> float:
    """When a step's stage was dispatched: its end less the stage's
    dispatch-to-results time."""
    s = run.steps[step_index]
    return s.t1 - s.stage.duration


def token_gaps(run) -> List[float]:
    """Seconds between consecutive output tokens of one request, for every
    token that reached the host inside the window. Tokens that arrive
    together (a fused horizon of K rounds) make gaps of 0 after the first."""
    out: List[float] = []
    for rec in run.reqs.values():
        prev = None
        for t, n in rec.deliveries:
            if run.in_window(t):
                if prev is not None:
                    out.append(t - prev)
                    out.extend([0.0] * (n - 1))
                else:
                    out.extend([0.0] * (n - 1))
            prev = t
    return out


def step_tokens(run) -> Dict[int, List[Tuple[int, int, int]]]:
    """Step index -> [(rid, tokens delivered before it, tokens it
    delivered)]."""
    index = {s.t1: i for i, s in enumerate(run.steps)}
    out: Dict[int, List[Tuple[int, int, int]]] = defaultdict(list)
    for rid, rec in run.reqs.items():
        before = 0
        for t, n in rec.deliveries:
            out[index[t]].append((rid, before, n))
            before += n
    return out


def window_stage_indices(run, kind: str) -> List[int]:
    return [
        i for i, s in enumerate(run.steps)
        if run.in_window(s.t1) and s.stage.kind.value == kind
    ]


def traced_stage_indices(run, kind: str) -> List[int]:
    a, b = run.traced
    return [
        i for i, s in enumerate(run.steps)
        if a < s.t1 <= b and s.stage.kind.value == kind
    ]


def decode_round_contexts(run) -> List[List[int]]:
    """For every fused decode round of the traced segment: the keys each
    active slot's new token attends (prompt plus tokens so far, itself
    included)."""
    per_step = step_tokens(run)
    rounds: List[List[int]] = []
    for i in traced_stage_indices(run, "decode"):
        k = run.steps[i].stage.rounds
        ctx: List[List[int]] = [[] for _ in range(k)]
        for rid, before, n in per_step.get(i, ()):
            p = run.reqs[rid].prompt
            for j in range(min(n, k)):
                ctx[j].append(p + before + j)
        rounds.extend(ctx)
    return rounds


def traced_model_flops(run) -> float:
    """Model FLOPs the traced segment's tokens require: every decode token
    at its context, every prompt token at its causal prefix, and the output
    head for every sampled position."""
    conf = run.setup.conf
    chunk = run.setup.prefill_chunk
    per_step = step_tokens(run)
    total = 0.0
    done: Dict[int, int] = defaultdict(int)      # prompt tokens prefilled
    lin = 2 * conf["num_hidden_layers"] * flops.layer_matmul_params(conf)
    att = flops.attention_flops(conf, 1)          # per key
    head = 2 * conf["hidden_size"] * conf["vocab_size"]
    for i, s in enumerate(run.steps):
        st = s.stage
        kind = st.kind.value
        if kind == "decode":
            rids = []
        elif kind == "mixed":
            rids = list(st.busy_partial.values()) + list(st.prefilled.values())
        else:
            rids = list(st.busy_partial.values()) + list(st.busy.values())
        inside = run.traced[0] < s.t1 <= run.traced[1]
        for rid in rids:
            p = run.reqs[rid].prompt
            a = done[rid]
            n = max(min(chunk, p - a), 0)
            done[rid] = a + n
            if inside and n:
                # positions a..a+n-1 attend a+1..a+n keys
                total += n * lin + att * (n * a + n * (n + 1) / 2)
        if not inside:
            continue
        for rid, before, n in per_step.get(i, ()):
            p = run.reqs[rid].prompt
            total += head * n
            for j in range(n):
                if before + j >= 1:               # decode token
                    total += lin + att * (p + before + j)
    return total


def stage_device_seconds(run, kind: str) -> float:
    """Device seconds of the program runs (trace) that fall inside the
    traced segment's steps whose stage is ``kind``. The harness's ``window``
    span opens at ``run.traced[0]``, which puts the host's clock on the
    trace's."""
    red = run.reduction
    offset = red.window[0] - run.traced[0] * 1e6     # trace us - host us
    steps = [s for s in run.traced_steps() if s.stage.kind.value == kind]
    starts = [s.t0 * 1e6 + offset for s in steps]
    total = 0.0
    for a, b in red.runs:
        i = bisect.bisect_right(starts, (a + b) / 2) - 1
        if i >= 0 and (a + b) / 2 <= steps[i].t1 * 1e6 + offset:
            total += (b - a) / 1e6
    return total
