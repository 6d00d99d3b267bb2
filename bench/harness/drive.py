"""Build the system under test for a cell and drive its traffic on the
host's wall clock.

Set-up: weights from the seed (one jitted call), slots and pages from the
program's own ``fit_slots_and_pool``, every serving shape warmed through
``Engine.warm_serving_shapes``, and the release of a slot. Then the traffic,
a closed backlog with every request due at t=0, runs through
``Engine.begin_serve`` / ``serve_step`` under the hybrid policy (offline LPT
packing, ``SortingPreemptiveScheduler``, ``LagrangianPolicy``). The window
opens once every slot has been admitted once.

Every ``serve_step`` is timed by the host; after each one the harness reads
which tokens reached the host (``Engine.generated``) and which requests were
admitted or finished (the ``Request`` fields the engine fills). A traced run
profiles ``TRACE_SECONDS`` right after its window, at the same load; the
spans ``window`` (the profiled segment), ``serve_step`` and ``client`` are
written as ``jax.profiler.TraceAnnotation`` so the trace can attribute the
device's idle gaps.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax

from . import traffic as traffic_mod
from .model import arch_config, make_params

CHUNK_TOKEN_LEVELS = (32, 64, 128, 256)

#: seconds a traced run records with the profiler, right after its window
#: at the same load (a longer trace overflows the profiler's buffers: a
#: 51-s trace kept only its first ~17 s of device events on a v5e)
TRACE_SECONDS = 10.0

#: the programs compiled (or loaded from the persistent cache) so far in
#: this process, by name (read around the window: nothing may compile inside
#: it)
COMPILED: List[str] = []


def _count_compile(event: str, duration: float, **kwargs) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILED.append(str(kwargs.get("fun_name", "?")))


jax.monitoring.register_event_duration_secs_listener(_count_compile)


@dataclass
class ReqRec:
    """One request as the client saw it (times from ``time.perf_counter``)."""

    rid: int
    prompt: int
    output: int
    admit_step: Optional[int] = None      # index into Run.steps
    first: Optional[float] = None
    done: Optional[float] = None
    deliveries: List[Tuple[float, int]] = field(default_factory=list)


@dataclass
class Step:
    t0: float
    t1: float
    stage: object = None                  # the engine's StageRecord


@dataclass
class Setup:
    conf: dict
    mix: dict
    cfg: object
    model: object
    params: object
    engine: object
    n_slots: int
    num_pages: int
    program_bytes: int
    prefill_chunk: int
    weights_s: float
    fit_s: float
    warm_s: float


@dataclass
class Run:
    setup: Setup
    ws: float                              # window start (absolute)
    we: float                              # window end (absolute)
    end: float                             # when the harness stopped serving
    steps: List[Step]
    reqs: Dict[int, ReqRec]
    meta: dict
    window_compiles: List[str] = field(default_factory=list)
    traced: Optional[Tuple[float, float]] = None   # the profiled segment
    setup_s: float = 0.0                   # process start -> window start
    reduction: object = None               # reduce.Reduction of a traced run
    peaks: object = None                   # peaks.Peaks of the chip

    @property
    def window_s(self) -> float:
        return self.we - self.ws

    def in_window(self, t: float) -> bool:
        return self.ws < t <= self.we

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.in_window(s.t1)]

    def traced_steps(self) -> List[Step]:
        a, b = self.traced
        return [s for s in self.steps if a < s.t1 <= b]


def row_buckets(n_slots: int) -> Tuple[int, ...]:
    """Chunk-row buckets: powers of two below ``n_slots``, then ``n_slots``."""
    out, b = [], 1
    while b < n_slots:
        out.append(b)
        b *= 2
    return tuple(out) + (n_slots,)


def build(conf: dict, mix: dict, seed: int, hbm_bytes: float, max_slots: int,
          fit: Optional[Tuple[int, int, int]] = None) -> Setup:
    """Weights, fit and warm-up. The fit halves from ``max_slots``; ``fit``
    reuses an earlier fit's (slots, pages, program bytes) for the same cell
    (the tools that serve several seeds in one process)."""
    from repro.core import CostModel
    from repro.launch.serve import HBM_FRACTION, fit_slots_and_pool
    from repro.models.transformer import TransformerLM
    from repro.serving.engine import Engine, EngineConfig

    cfg = arch_config(conf)
    model = TransformerLM(cfg)
    t0 = time.perf_counter()
    params = make_params(model, seed)
    t1 = time.perf_counter()
    base = EngineConfig(
        n_slots=max_slots, max_len=mix["max_len"], kv_layout="paged",
        page_reserve="ondemand", mixed_schedule=True,
    )
    n_slots, num_pages, program_bytes = fit or fit_slots_and_pool(
        model, params, base, HBM_FRACTION * hbm_bytes
    )
    t2 = time.perf_counter()
    ecfg = dataclasses.replace(
        base, n_slots=n_slots, num_pages=num_pages,
        prefill_req_buckets=row_buckets(n_slots),
    )
    engine = Engine(model, params, ecfg)
    engine.profiler.cost_model = CostModel(level_caps=CHUNK_TOKEN_LEVELS)
    engine.warm_serving_shapes()
    # a request's completion clears its slot's block-table row and length
    # (two small scatters the serving warm-up does not reach); clearing the
    # empty slot 0 compiles them here rather than inside the window
    engine.slots.free_pages_of(0)
    t3 = time.perf_counter()
    return Setup(
        conf=conf, mix=mix, cfg=cfg, model=model, params=params, engine=engine,
        n_slots=n_slots, num_pages=num_pages, program_bytes=program_bytes,
        prefill_chunk=ecfg.prefill_chunk,
        weights_s=t1 - t0, fit_s=t2 - t1, warm_s=t3 - t2,
    )


class _Driver:
    """One serve of a backlog on a built engine, step by step."""

    def __init__(self, setup: Setup, draws, trace_dir: Optional[str]):
        from repro.core import (
            LagrangianPolicy, SortingPreemptiveScheduler, build_clients,
            solve_offline,
        )
        from repro.core.types import Request

        self.setup, self.engine = setup, setup.engine
        self.trace_dir = trace_dir
        self.requests = [
            Request(rid=d.rid, n_prefill=d.prompt, n_decode=d.output,
                    n_decode_est=d.estimate)
            for d in draws
        ]
        n = setup.n_slots
        asn = solve_offline(self.requests, n, self.engine.profiler.cost_model).assignment
        clients = build_clients(n, self.requests, asn)
        self.sched = SortingPreemptiveScheduler(clients)
        self.engine.begin_serve(self.requests, clients, self.sched,
                                LagrangianPolicy(), policy_name="hybrid")
        self.gen = self.engine.generated
        self.recs: Dict[int, ReqRec] = {
            d.rid: ReqRec(d.rid, d.prompt, d.output) for d in draws
        }
        self.pending: List[object] = list(self.requests)   # not yet admitted
        self.inflight: Dict[int, object] = {}  # admitted, not yet done
        self.seen: Dict[int, int] = {}
        self.steps: List[Step] = []
        self.queued = self.sched.pending_count()

    # -- one iteration ------------------------------------------------------
    def tick(self) -> None:
        """Run one step and read what it delivered."""
        if not self.engine.has_work():
            raise RuntimeError("the backlog ran dry before the run ended")
        with jax.profiler.TraceAnnotation("serve_step"):
            t0 = time.perf_counter()
            status = self.engine.serve_step()
            t1 = time.perf_counter()
        if status == "done":
            return
        if status == "idle":
            raise RuntimeError("engine idle with work pending")
        with jax.profiler.TraceAnnotation("client"):
            self.steps.append(Step(t0, t1))
            self._observe(len(self.steps) - 1, t1)

    def _observe(self, step: int, t: float) -> None:
        queued = self.sched.pending_count()
        if queued != self.queued:              # something was admitted
            self.queued = queued
            still = []
            for r in self.pending:
                if r.t_prefill_start is not None:
                    self.recs[r.rid].admit_step = step
                    self.inflight[r.rid] = r
                    self.seen[r.rid] = 0
                else:
                    still.append(r)
            self.pending = still
        finished = []
        for rid, r in self.inflight.items():
            n = len(self.gen.get(rid, ()))
            if n > self.seen[rid]:
                rec = self.recs[rid]
                if rec.first is None:
                    rec.first = t
                rec.deliveries.append((t, n - self.seen[rid]))
                self.seen[rid] = n
            if r.t_done is not None:
                self.recs[rid].done = t
                finished.append(rid)
        for rid in finished:
            del self.inflight[rid]

    # -- phases -------------------------------------------------------------
    def slots_all_admitted(self) -> bool:
        used = {r.client for r in self.requests if r.client is not None}
        return len(used) >= self.setup.n_slots

    def run(self, seconds: float) -> Run:
        while not self.slots_all_admitted():
            self.tick()
        compiles = len(COMPILED)
        ws = time.perf_counter()
        we = ws + seconds
        while time.perf_counter() < we:
            self.tick()
        compiles = COMPILED[compiles:]
        traced = None
        if self.trace_dir:
            # the profiled segment follows the window, so the window's host
            # clock is never slowed by the profiler
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, create_perfetto_trace=True,
                                     profiler_options=opts)
            with jax.profiler.TraceAnnotation("window"):
                ts = time.perf_counter()
                te = ts + TRACE_SECONDS
                while time.perf_counter() < te:
                    self.tick()
            jax.profiler.stop_trace()
            traced = (ts, te)
        end = time.perf_counter()
        trace = self.engine.finish_serve(validate=False)
        if len(trace.stages) != len(self.steps):
            raise RuntimeError(
                f"{len(trace.stages)} stages for {len(self.steps)} steps"
            )
        for s, st in zip(self.steps, trace.stages):
            s.stage = st
        return Run(
            setup=self.setup, ws=ws, we=we, end=end, steps=self.steps,
            reqs=self.recs, meta=dict(trace.meta),
            window_compiles=compiles, traced=traced,
        )


def serve(setup: Setup, seed: int, seconds: float, trace_dir: Optional[str] = None) -> Run:
    draws = traffic_mod.draw(setup.mix, seed)
    return _Driver(setup, draws, trace_dir).run(seconds)
