"""The serving engine: continuous batching under PD Competition, with the
paper's hybrid offline-online scheduler as the dispatch policy.

This is the real-execution counterpart of ``core.simulator`` — the same
``RequestScheduler`` (offline assignment / Algorithm 1 stealing) and
``IterationPolicy`` (prefill-first / Lagrangian) objects drive actual jitted
model steps:

  * a *prefill stage* packs ≤ 1 new request per idle slot (Eq. 16), pads to
    a bucket shape (the paper's levels ↔ jit compilation buckets), runs
    ``model.prefill`` and scatters the produced KV rows into the slot cache;
  * a *decode round* runs ``model.decode_step`` over all J slots (one token
    per active slot), exactly the paper's iteration granularity;
  * between rounds the iteration policy decides prefill-vs-decode using the
    online profiler's continuously refit cost model.

With ``kv_layout="paged"`` the execution layer swaps to a paged KV pool
(``PagedSlotManager`` + block tables) and *chunked prefill*: prompts are
split into ``prefill_chunk``-token chunks written directly into the slot's
pages by ``model.prefill_chunk`` — no per-prefill throwaway cache, no padded
full-row scatter — and the iteration policy prices inserting *one chunk
round* (``CandidateBatch.chunk_tokens``) instead of a whole prompt, so
decode rounds interleave between a long prompt's chunks instead of stalling
behind it. KV memory is pages-in-use rather than n_slots × max_len, with
admission control against the page pool.

With ``mixed_schedule=True`` (the default for paged layouts) the
prefill-stage / decode-stage *alternation disappears*: whenever prefill work
is pending alongside active decoders, the engine dispatches ONE mixed batch
per iteration (``model.mixed_step``) containing the decode tokens of every
active slot plus a policy-priced share of prefill-chunk tokens written
straight into the paged pool — prefill piggybacks on decode instead of
preempting it. The iteration policy's ``prefill_share`` prices the marginal
chunk token (decode-latency inflation per co-scheduled prefill token, from
the cost model's separable mixed fit t(n_decode, n_prefill_tokens)) instead
of making the paper's binary stage choice, and ``prefill_stall_time`` — the
wall-clock decoders spend frozen behind preempting prefills — goes to ~0 by
construction. Iterations with no prefill in view still take the fused
multi-step decode fast path below.

Decode runs as *fused multi-step stages*: instead of paying one host↔device
round trip per decoded token (dispatch → ``block_until_ready`` → host argmax
→ re-upload), the engine commits to a decode *horizon* of K iterations and
dispatches ONE jitted call (``model.decode_steps``) that loops attention +
KV append + on-device sampling, keeping ``pending_token`` and per-slot stop
state device-resident and syncing to host only at the horizon boundary. The
iteration policy prices K from the cost model (amortized dispatch cost vs
the expected regret of delaying a prefill insertion mid-horizon), and the
online profiler learns per-horizon timings so K adapts to the hardware. A
slot that hits its stop condition mid-horizon becomes a no-op inside the
fused loop rather than forcing an early exit. ``max_decode_horizon=1``
reproduces the legacy per-token loop exactly.

The engine emits the same ``ScheduleTrace`` as the simulator, so utilization
and Gantt accounting are directly comparable, and it can checkpoint/restore
mid-run (slot cache + queues + scheduler state) for fault tolerance.

Serving is *step-driven*: ``serve()`` is a loop over ``begin_serve`` /
``serve_step`` / ``finish_serve``, and a ``serving.fleet.Fleet`` drives many
engines' sessions interleaved in virtual time instead — always stepping the
lowest-clock replica, pushing externally-dispatched arrivals and stolen
requests into the session's scheduler between stages.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cost_model import CostModel
from ..core.iteration import CandidateBatch, IterationPolicy, SystemSnapshot
from ..core.online import RequestScheduler
from ..core.types import (
    ClientState,
    Request,
    ScheduleTrace,
    StageKind,
    StageRecord,
)
from ..obs.phases import Phases
from .kv_slots import PagedSlotManager, SlotManager
from .overload import OverloadPolicy
from .profiler import OnlineProfiler
from .sampler import fold_row_keys, greedy

Tree = Any


@dataclasses.dataclass
class EngineConfig:
    n_slots: int = 8
    max_len: int = 256
    prefill_seq_buckets: Tuple[int, ...] = (32, 64, 128)
    prefill_req_buckets: Tuple[int, ...] = (1, 2, 4, 8)
    eos_id: Optional[int] = None          # None → workload-driven stop
    max_stages: int = 200_000
    # Straggler mitigation: a prefill stage measuring > straggler_factor ×
    # the cost model's prediction halves the packing budget for subsequent
    # stages (smaller stages bound the blast radius of a slow node); the
    # budget recovers by one step per on-prediction stage.
    straggler_factor: float = 3.0
    # KV layout. "dense" preallocates one max_len row per slot and prefills
    # whole (padded) prompts; "paged" shares a pool of page_size-token pages
    # through block tables and prefills in prefill_chunk-token chunks written
    # directly into the slot's pages — decode rounds can interleave between a
    # long prompt's chunks, and KV memory is pages-in-use, not
    # n_slots × max_len. num_pages=None sizes the pool to full capacity;
    # smaller pools trade memory for admission backpressure.
    kv_layout: str = "dense"              # "dense" | "paged"
    page_size: int = 16
    prefill_chunk: int = 32
    num_pages: Optional[int] = None
    # Page reservation discipline (paged layout). "ondemand" grants a new
    # request pages for its *prompt* only and grows the slot page-by-page as
    # decode crosses page boundaries; when the pool genuinely exhausts, the
    # engine preempts the lowest-priority slot — deallocates its pages and
    # re-queues the request with its generated prefix for recompute-on-resume
    # (token streams stay bit-identical: the sampler is a pure function of
    # (seed, rid, token index) and the pre-preemption tokens are restored,
    # never re-sampled). "upfront" reserves prompt + decode bound at
    # admission — no preemption can ever be needed, but the over-reservation
    # backpressures admission long before the pool is actually full; kept as
    # the ablation baseline for benchmarks/overload.py.
    page_reserve: str = "ondemand"        # "ondemand" | "upfront"
    # Fused decode. Each decode stage runs one on-device loop of K
    # iterations (one dispatch, one host sync). ``max_decode_horizon`` caps
    # the policy-priced K; 1 reproduces the per-token baseline exactly.
    # ``decode_horizon`` pins K instead of asking the policy (benchmarks /
    # ablations). Horizons are bucketed down to powers of two so at most
    # log2(K_max)+1 jit variants ever compile, and capped by the largest
    # remaining decode budget so the drain tail never runs all-no-op rounds.
    max_decode_horizon: int = 8
    decode_horizon: Optional[int] = None
    # Mixed-step scheduling (paged layout only). True collapses the
    # prefill-stage / decode-stage alternation into continuous batching:
    # every iteration with prefill work pending dispatches ONE mixed batch
    # (``model.mixed_step``) holding the decode tokens of all active slots
    # plus up to ``prefill_share`` prefill-chunk tokens written straight
    # into the paged pool — prefill piggybacks on decode instead of
    # preempting it, so ``prefill_stall_time`` goes to ~0 by construction.
    # Pure-decode iterations still take the fused ``decode_steps`` fast
    # path. False restores the alternating loop (the ablation baseline in
    # ``benchmarks/mixed_batch.py``); dense layouts always alternate.
    mixed_schedule: bool = True
    # Quantization levels for the chunk-token share of a mixed round (the
    # mixed analogue of the paper's prefill levels): the policy's priced
    # share rounds down to a bucket, and the largest entry caps the budget
    # it may price at all — bounding the worst-case decode-latency
    # inflation a single round can absorb (a small cap protects burst p95,
    # a large one drains prefill faster). Jit shapes are NOT driven by this
    # table — a mixed dispatch is always (n_slots decode lanes) +
    # (prefill_req_buckets rows × prefill_chunk), the same rectangles the
    # alternating chunk rounds compile.
    mixed_token_buckets: Tuple[int, ...] = (16, 32, 64, 128, 256)
    # PRNG seed for stochastic samplers. Token streams are reproducible as a
    # pure function of (seed, request id, token index) — independent of
    # horizon grouping, slot placement, batch composition, or KV layout.
    sample_seed: int = 0
    # Invariant checking (paged layout): assert allocator free-list/free-set
    # consistency plus the host↔device block-table mirror at every stage
    # boundary and every migration export/import. Each check costs a device
    # sync, so it must stay out of timed regions: None resolves from the
    # REPRO_DEBUG_INVARIANTS env var — the test suite turns it on globally
    # (tests/conftest.py), benchmarks leave it off.
    debug_invariants: Optional[bool] = None
    # Prefix caching (paged layout only). True keeps a content-addressed
    # index over completed prompts' FULL KV pages (chained page hashing, à
    # la vLLM): a new prompt sharing a prefix with a cached one adopts the
    # matching pages read-only (refcounted) and copy-on-writes the page
    # holding its first divergent token, so chunked prefill starts at the
    # first uncached token. The index holds one reference per published
    # page; held pages are reclaimed LRU-leaf-first when the free list
    # can't fund an allocation, so a warm cache never deadlocks admission.
    prefix_cache: bool = False
    # Price scheduling by UNCACHED prefill tokens (the work actually
    # computed) instead of nominal prompt length. False is the cache-blind
    # ablation: the cache still serves hits, but the Lagrangian prefill
    # share and the offline packer see full prompt lengths.
    cache_aware_pricing: bool = True
    # Observability sink (a ``repro.obs.Observation``). None — the default —
    # is the zero-cost path: every emission site in the serve loop guards on
    # a single ``is not None`` and a disabled serve executes zero obs
    # callbacks (tests enforce this via Observation.tripwire). Benches and
    # traced serves pass one instance; a Fleet shares the engine config's
    # instance across every replica so request spans chain causally through
    # migrations. One Observation records exactly one serve.
    observe: Optional[Any] = None


# Declarations for the typed metrics registry mirroring the engine's
# ``trace.meta`` counters (units + help text; keys not listed here default
# to unit-less counters). ``summary()`` output is unchanged — the registry
# is the typed, documented view over the same numbers.
_METRIC_SPECS: Dict[str, Tuple[str, str, str]] = {
    "mixed_rounds": ("counter", "", "mixed prefill+decode rounds dispatched"),
    "prefill_stall_time_s": (
        "counter", "s",
        "wall-clock decoders spent frozen behind preempting prefill stages",
    ),
    "decode_dispatches": ("counter", "", "fused decode dispatches"),
    "preemption_events": ("counter", "", "slots preempted by page eviction"),
    "peak_concurrency": (
        "gauge", "", "peak simultaneously in-flight requests on one replica",
    ),
    "offline_deferrals": (
        "counter", "", "offline admissions deferred by overload control",
    ),
    "recomputed_tokens": (
        "counter", "tokens", "tokens re-prefilled on recompute-on-resume",
    ),
    "migrations_in": ("counter", "", "slots imported by page-copy migration"),
    "migrations_out": ("counter", "", "slots exported by page-copy migration"),
    "cached_prefill_tokens": (
        "counter", "tokens", "prompt tokens served from the prefix cache",
    ),
    "shared_pages_peak": (
        "gauge", "pages", "peak KV pages shared read-only across slots",
    ),
    "cow_copies": ("counter", "pages", "copy-on-write page copies"),
    "decoded_tokens": ("counter", "tokens", "tokens decoded"),
}


def _bucket(x: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if x <= b:
            return b
    raise ValueError(
        f"value {x} exceeds the largest bucket {buckets[-1]}; a request "
        f"padded into it would silently overflow the batch — raise the "
        f"bucket table (EngineConfig.prefill_seq_buckets / "
        f"prefill_req_buckets) to cover the workload"
    )


@dataclasses.dataclass
class _ServeSession:
    """Host-side state of one in-progress serve (the step-driven API).

    ``Engine.serve`` is a loop over ``serve_step``; a ``Fleet`` drives many
    engines' sessions interleaved in virtual time instead. ``t`` is the
    session's stage clock (sum of measured stage durations plus any arrival
    fast-forwards), which is what "replicas run in parallel" means for
    fleet accounting: every replica's clock starts at 0.
    """

    trace: ScheduleTrace
    clients: List[ClientState]
    scheduler: RequestScheduler
    policy: IterationPolicy
    t: float = 0.0
    bin_index: int = -1
    stages_run: int = 0
    # adopt requests into the trace as the scheduler commits them (fleet
    # dispatch and work stealing route requests in mid-serve, so the final
    # request set is discovered, not declared)
    track_requests: bool = False


@dataclasses.dataclass
class _ChunkState:
    """One slot's in-flight chunked prefill (paged layout only).

    A *resumed* (previously preempted) request recomputes prompt + generated
    prefix in one pass: ``prompt`` then holds n_prefill + emitted - 1 tokens,
    and the final chunk restores ``(resume_emitted, resume_pending)`` instead
    of sampling — re-sampling the already-emitted token would risk
    FP divergence for zero benefit, so the stream continues bit-identical to
    an unpreempted serve."""

    slot: int
    req: Request
    prompt: np.ndarray
    done: int = 0
    resume_emitted: int = 0               # >0 → recompute of a preemptee
    resume_pending: int = -1              # pending token to restore at bind
    cached: int = 0                       # prompt tokens adopted from cache

    @property
    def total(self) -> int:
        return len(self.prompt)

    @property
    def remaining(self) -> int:
        return self.total - self.done


@dataclasses.dataclass
class SlotCheckpoint:
    """Portable mid-request slot state for live KV migration by page-copy.

    ``Engine.export_slot`` gathers everything a destination engine needs to
    continue a request bit-identically with ZERO recomputed tokens: the
    slot's KV pages (gathered out of the source pool, page-id-agnostic),
    the pending token awaiting its next decode round, the sampler cursor
    (``emitted`` — sampling is a pure function of (seed, rid, token index),
    so the destination resumes the exact stream), the generated-so-far
    prefix (output record + budget bookkeeping), and mid-chunk prefill
    progress for requests migrated before their prompt finished.

    ``prefill_credit`` is the number of prefill completions the request has
    performed on OTHER traces so far: a bound slot has completed all
    ``1 + preemptions`` it will ever need; a mid-chunk prefill has completed
    ``preemptions`` (its current pass is still in flight). The importer
    records it in ``ScheduleTrace.external_prefills`` so exactly-once
    prefill accounting validates on both sides of the move.

    ``checksum`` is the KV payload's content CRC, computed at
    ``export_pages`` and verified at ``import_pages`` — a corrupted
    transfer raises ``PageIntegrityError`` instead of silently resuming a
    poisoned stream. ``src_replica``/``src_epoch`` are the exporter's
    ``(replica, epoch)`` lease, stamped by the fleet: an export from an
    epoch that has since been fenced (the source was condemned mid-flight)
    is discarded at the fleet layer, never imported."""

    req: Request
    kind: str                             # "bound" | "chunking"
    emitted: int                          # sampler cursor (bound slots)
    pending_token: int                    # next decode round's input token
    kv_length: int                        # valid KV entries in the payload
    k_pages: Any                          # (L, n_pages, page_size, KV, D)
    v_pages: Any
    n_pages: int
    prefix: List[int]                     # every token generated so far
    prefill_credit: int
    # mid-chunk prefill progress (kind == "chunking" only)
    chunk_done: int = 0
    resume_emitted: int = 0
    resume_pending: int = -1
    # KV payload integrity (None = exporter predates checksums)
    checksum: Optional[int] = None
    # (replica, epoch) lease of the exporter (fleet-stamped; -1 = unset)
    src_replica: int = -1
    src_epoch: int = -1


def _fused_decode(
    model, sampler, eos_id,
    num_steps, params, tokens, cache, active, budgets, rids, token_idx0,
    base_key,
):
    """Jit target for the fused decode stage (module-level so the partial
    closing over (model, sampler, eos_id) hashes stably across calls)."""
    return model.decode_steps(
        params, tokens, cache,
        num_steps=num_steps, sampler=sampler, active=active, budgets=budgets,
        rids=rids, token_idx0=token_idx0, base_key=base_key, eos_id=eos_id,
    )


def _mixed_round(
    model, sampler,
    params, dec_tokens, cache, chunk_tokens, chunk_slots, chunk_starts,
    chunk_lens, dec_active, rids, token_idx, sample_rows, base_key,
):
    """Jit target for the mixed prefill+decode stage (module-level for the
    same stable-hash reason as ``_fused_decode``)."""
    return model.mixed_step(
        params, dec_tokens, cache, chunk_tokens, chunk_slots, chunk_starts,
        chunk_lens, sampler=sampler, dec_active=dec_active, rids=rids,
        token_idx=token_idx, sample_rows=sample_rows, base_key=base_key,
    )


def _chunk_round(model, params, tokens, cache, slots, starts, lens):
    """Jit target for the paged chunk round."""
    return model.prefill_chunk(params, tokens, cache, slots, starts, lens)


def _dense_prefill(model, params, tokens, cache, lengths):
    """Jit target for the dense layout's whole-prompt prefill."""
    return model.prefill(params, tokens, cache, lengths=lengths)


def _named_jit(name: str, fn, *bound, **jit_kw):
    """``jax.jit`` of ``fn`` with its leading arguments ``bound``, compiled
    as program ``jit_<name>``: the name profiles, compile logs and
    ``memory_analysis`` show. The name goes on the partial itself, not
    through ``functools.update_wrapper``, whose ``__wrapped__`` would show
    jit ``fn``'s whole signature, bound arguments included."""
    target = functools.partial(fn, *bound)
    target.__name__ = name
    return jax.jit(target, **jit_kw)


def serving_jits(model, sampler, eos_id):
    """The engine's jitted dispatches — the paged chunk round, the paged
    mixed round, and fused decode (both layouts) — each donating the KV
    cache and named ``chunk_round``, ``mixed_round`` and ``fused_decode``.
    Exposed so a launcher can compile them against abstract shapes
    (``.lower().compile()``) and size the pool from ``memory_analysis()``
    before allocating it."""
    chunk = _named_jit("chunk_round", _chunk_round, model, donate_argnums=(2,))
    mixed = _named_jit(
        "mixed_round", _mixed_round, model, sampler, donate_argnums=(2,)
    )
    fused = _named_jit(
        "fused_decode", _fused_decode, model, sampler, eos_id,
        static_argnums=(0,), donate_argnums=(3,),
    )
    return chunk, mixed, fused


class Engine:
    def __init__(
        self,
        model,
        params: Tree,
        config: EngineConfig,
        profiler: Optional[OnlineProfiler] = None,
        sampler: Callable = greedy,
        speed_factor: float = 1.0,
        overload_policy: Optional[OverloadPolicy] = None,
    ):
        self.model = model
        self.params = params
        self.cfg = config
        self.profiler = profiler or OnlineProfiler()
        self.sampler = sampler
        if config.page_reserve not in ("ondemand", "upfront"):
            raise ValueError(f"unknown page_reserve {config.page_reserve!r}")
        # Admission-side overload control (None = admit everything the
        # scheduler proposes; see serving.overload for the SLO-aware policy).
        self.overload = overload_policy
        # Relative machine speed for virtual-time accounting: every measured
        # stage duration divides by this before it reaches the session
        # clock, the trace, and the profiler. 1.0 is a no-op (the default,
        # bare-engine case); a heterogeneous Fleet sets it per replica so a
        # mixed-generation fleet is emulatable — and its scheduling
        # decisions deterministically testable — on one host: a
        # speed_factor=0.5 replica *is* a machine whose stages take twice
        # as long, as far as every scheduler and profiler can observe.
        if speed_factor <= 0:
            raise ValueError("speed_factor must be positive")
        self.speed_factor = float(speed_factor)
        if config.prefix_cache and config.kv_layout != "paged":
            raise ValueError(
                "prefix_cache requires kv_layout='paged' — dense layout has "
                "no page identity to share"
            )
        # ONE decode path for both layouts and every horizon: a fused
        # K-iteration on-device loop (K static → one executable per horizon
        # bucket). The cache is donated, so K-step decode updates it in
        # place; tokens stay on device until the horizon boundary.
        self._chunk_jit, self._mixed_jit, self._fused_jit = serving_jits(
            model, sampler, config.eos_id
        )
        if config.kv_layout == "paged":
            self.slots: Any = PagedSlotManager(
                model, config.n_slots, config.max_len,
                config.page_size, config.num_pages,
                prefix_cache=config.prefix_cache,
            )
        elif config.kv_layout == "dense":
            self.slots = SlotManager(model, config.n_slots, config.max_len)
            self._prefill_jit = _named_jit(
                "dense_prefill", _dense_prefill, model, donate_argnums=(2,)
            )
        else:
            raise ValueError(f"unknown kv_layout {config.kv_layout!r}")
        # Stochastic samplers draw per-row keys folded from this base key;
        # greedy engines carry no key (None short-circuits key plumbing).
        self._base_key = (
            jax.random.key(config.sample_seed)
            if getattr(sampler, "stochastic", False) else None
        )
        self.pending_token = np.zeros(config.n_slots, dtype=np.int32)
        # Device-side copy of pending tokens, carried between consecutive
        # decode stages so back-to-back horizons never re-upload (None →
        # stale, rebuild from the host array; prefills invalidate it).
        self._dev_pending: Optional[jax.Array] = None
        # dispatch accounting (the quantity this subsystem optimizes; each
        # dispatch implies exactly one host sync at its horizon boundary)
        self.decode_dispatches = 0
        self.decoded_tokens = 0
        # mixed-step accounting: mixed rounds dispatched, and the wall-clock
        # decoders spent frozen behind a preempting prefill stage (only the
        # alternating path can accumulate it — in mixed mode the stall is
        # structurally impossible, which is the point)
        self.mixed_rounds = 0
        self.prefill_stall_time = 0.0
        self._budget_shift = 0            # straggler mitigation state
        self.straggler_events = 0
        self._chunking: Dict[int, _ChunkState] = {}
        # Preemption-by-eviction bookkeeping: rids whose generated prefix
        # must be recomputed on (re-)admission, and overload counters.
        self._resume_rids: set = set()
        self.preemption_events = 0
        self.offline_deferrals = 0
        # Recovery/migration accounting. ``recomputed_tokens`` counts every
        # token re-prefilled on a recompute-on-resume pass (prompt + restored
        # prefix — work that had already been paid for once); page-copy
        # migration contributes zero here by construction, which is what the
        # chaos bench hard-gates. ``migrated_pages_in/out`` count KV pages
        # that physically moved through export/import.
        self.recomputed_tokens = 0
        self.migrated_pages_in = 0
        self.migrated_pages_out = 0
        self.migrations_in = 0
        self.migrations_out = 0
        # Prefix-cache accounting: prompt tokens served from cached KV pages
        # instead of being computed (adoption at admission).
        self.cache_hit_tokens = 0
        self._use_prefix_cache = (
            config.kv_layout == "paged" and config.prefix_cache
        )
        # Stage-boundary invariant checks (see EngineConfig.debug_invariants)
        self.debug_invariants = (
            config.debug_invariants
            if config.debug_invariants is not None
            else os.environ.get("REPRO_DEBUG_INVARIANTS", "") == "1"
        )
        # High-water mark of simultaneously in-flight requests (bound slots
        # + mid-chunk prefills) — the admission-concurrency metric the
        # on-demand-vs-upfront reservation comparison is judged on.
        self.peak_concurrency = 0
        # Observability (repro.obs.Observation). None (the default) keeps
        # every emission site dead; a Fleet overwrites obs_replica with the
        # engine's replica index after construction.
        self.obs = config.observe
        self.obs_replica = 0
        if config.kv_layout == "paged":
            self.slots.obs = self.obs
        # migrated-in slots awaiting their first post-import dispatch —
        # capacity attribution classifies that wait as "migration"
        self._mig_pending: set = set()
        # rid -> every token this engine sampled for it (parity testing and
        # the place a production engine would stream detokenized output from)
        self.generated: Dict[int, List[int]] = {}
        # the open step-driven serve session (begin_serve → serve_step*
        # → finish_serve); ``serve()`` owns it for closed-loop runs, a
        # Fleet drives it directly for interleaved multi-replica serving
        self._sv: Optional[_ServeSession] = None
        # wall-clock phases of the current serve step (StageRecord.host and
        # the engine.<phase> profiler spans)
        self._phases = Phases()

    # ------------------------------------------------------------------ #
    def _prompt_tokens(self, req: Request) -> np.ndarray:
        """Synthetic prompt tokens derived from the request id (demo data; a
        production engine receives the tokenized prompt here).

        Requests carrying a ``prefix_group`` share their first
        ``prefix_len`` tokens — derived from the group id, not the rid — so
        shared-prefix workloads (system prompts, few-shot templates) exist
        at the token level and survive migration/restore: the prompt is
        reconstructible from the ``Request`` alone on any replica."""
        n = req.n_prefill
        if req.prefix_group is not None and req.prefix_len > 0:
            head_rng = np.random.default_rng(10_000_019 + req.prefix_group)
            head = head_rng.integers(
                1, self._vocab(), size=req.prefix_len
            ).astype(np.int32)
            tail_rng = np.random.default_rng(req.rid)
            tail = tail_rng.integers(
                1, self._vocab(), size=n - req.prefix_len
            ).astype(np.int32)
            return np.concatenate([head, tail])
        rng = np.random.default_rng(req.rid)
        return rng.integers(1, self._vocab(), size=n).astype(np.int32)

    def _sample_first(self, logits, rids: Sequence[int]) -> np.ndarray:
        """Sample each prefill row's first token (token index 0 of its
        request). Per-row keys fold (seed, rid, 0) — the same derivation the
        fused decode loop uses for later indices, so the stream is seamless."""
        if self._base_key is None:
            return np.asarray(self.sampler(logits))
        n_pad = logits.shape[0]
        rid_vec = np.full(n_pad, -1, np.int32)     # pad rows sample garbage
        rid_vec[: len(rids)] = rids
        keys = fold_row_keys(
            self._base_key, jnp.asarray(rid_vec), jnp.zeros(n_pad, jnp.int32)
        )
        return np.asarray(self.sampler(logits, keys))

    def _observe_prefill(self, total_tokens: int, dt: float) -> None:
        """Feed the profiler and run straggler mitigation (request-level
        stealing is Algorithm 1's job; this handles slow *stages*)."""
        self.profiler.record_prefill(total_tokens, dt)
        predicted = self.profiler.cost_model.prefill_time(total_tokens)
        if predicted > 0 and dt > self.cfg.straggler_factor * predicted:
            self._budget_shift = min(self._budget_shift + 1, 3)
            self.straggler_events += 1
        elif self._budget_shift > 0 and dt < 1.5 * predicted:
            self._budget_shift -= 1

    def _run_prefill_stage(self, pairs: List[Tuple[ClientState, Request]]):
        """Execute one packed prefill; returns (duration_s, total_tokens)."""
        reqs = [r for _, r in pairs]
        slots = [c.cid for c, _ in pairs]
        max_len = max(r.n_prefill for r in reqs)
        s_pad = _bucket(max_len, self.cfg.prefill_seq_buckets)
        n_pad = _bucket(len(reqs), self.cfg.prefill_req_buckets)
        tokens = np.zeros((n_pad, s_pad), dtype=np.int32)
        lengths = np.ones(n_pad, dtype=np.int32)
        for i, r in enumerate(reqs):
            tokens[i, : r.n_prefill] = self._prompt_tokens(r)
            lengths[i] = r.n_prefill
        cache = self.model.cache_init(n_pad, s_pad)
        t0 = time.perf_counter()
        self._phases.mark("launch", t0)
        logits, pref_cache = self._prefill_jit(
            self.params, jnp.asarray(tokens), cache, jnp.asarray(lengths)
        )
        self._phases.mark("wait")
        logits.block_until_ready()
        t1 = time.perf_counter()
        self._phases.mark("commit", t1)
        dt = (t1 - t0) / self.speed_factor
        first = self._sample_first(logits, [r.rid for r in reqs])
        self._dev_pending = None          # prefill rewrites pending tokens
        # scatter only the real rows (the batch was padded to a bucket)
        real_cache = jax.tree_util.tree_map(
            lambda x: x[:, : len(slots)] if x.ndim >= 3 else x[: len(slots)],
            pref_cache,
        )
        self.slots.merge_prefill(real_cache, slots)
        for i, (client, req) in enumerate(pairs):
            self.slots.bind(client.cid, req)
            self.slots.emitted[client.cid] = 1     # prefill samples token #1
            self.pending_token[client.cid] = int(first[i])
            self.generated.setdefault(req.rid, []).append(int(first[i]))
            client.current = req
        self._note_concurrency()
        total_tokens = sum(r.n_prefill for r in reqs)
        self._observe_prefill(total_tokens, dt)
        return dt, total_tokens

    def _vocab(self) -> int:
        return self.model.cfg.vocab_size

    # ------------------------------------------------------------------ #
    # Chunked prefill (paged layout)                                      #
    # ------------------------------------------------------------------ #
    def _tokens_bound(self, req: Request) -> int:
        """KV tokens a request can touch over its lifetime: prompt plus the
        decode bound (known output length when the workload drives stops, the
        slot capacity otherwise). Decode round k writes KV position
        n_prefill + k - 1 and the last round only samples, hence the -1."""
        if self.cfg.eos_id is None:
            tokens = req.n_prefill + max(req.n_decode - 1, 0)
        else:
            tokens = self.cfg.max_len
        return min(tokens, self.cfg.max_len)

    def _prompt_total(self, req: Request) -> int:
        """Tokens the request's next (re)prefill will write: the prompt,
        plus — for a preempted request — its recomputed generated prefix
        (emitted - 1 tokens; the last generated token is restored as the
        pending token, never prefilled)."""
        extra = 0
        if req.rid in self._resume_rids:
            extra = max(len(self.generated.get(req.rid, ())) - 1, 0)
        return req.n_prefill + extra

    def _pages_needed(self, req: Request) -> int:
        """Pages admission must secure now: the whole lifetime bound under
        up-front reservation, just the (re)prefill span under on-demand
        paging (decode grows page-by-page later)."""
        if self.cfg.page_reserve == "upfront":
            return self.slots.allocator.pages_for(self._tokens_bound(req))
        return self.slots.allocator.pages_for(self._prompt_total(req))

    def _deadline_class(self, req: Request) -> int:
        """Admission priority class: 1 = online arrival carrying a TTFT
        deadline, 0 = everything else (offline backlog, no-SLO online)."""
        return 1 if (req.ttft_slo_s is not None and req.arrival > 0) else 0

    def _admissible(
        self, pairs: List[Tuple[ClientState, Request]]
    ) -> List[Tuple[ClientState, Request]]:
        """Trim a proposed batch to what the page pool can host.

        Head-of-line rule, re-derived for on-demand paging: admission stays
        FCFS *within* a priority class — a request that doesn't fit blocks
        everything of its own class (and every lower class) behind it, so
        the blocked head always gets in eventually: the pages freed by
        finishing decoders cannot be snapped up by same-class followers (the
        no-starvation guarantee the original stop-at-first-blocked rule
        bought for the whole queue). The one sanctioned bypass: a smaller
        *online* request carrying a TTFT deadline may jump a blocked
        offline head — holding deadline traffic behind backlog work it can
        never overtake would convert pool pressure directly into SLO misses,
        and offline work cannot starve under it because class-1 traffic is
        finite per burst while the pool drains monotonically.

        Under on-demand reservation the budget also sets aside the pages
        active decoders need for their *next* round, so admission cannot
        grab the exact pages whose absence would immediately force a
        preemption."""
        out = []
        # pages the prefix-cache index holds with no other owner count as
        # headroom: the manager reclaims them LRU-leaf-first on demand, so a
        # warm cache holding most of the pool never deadlocks admission
        free = self.slots.allocator.num_free + self.slots.reclaimable_pages()
        if self.cfg.page_reserve != "upfront":
            free -= self._decode_growth_pages(1)
        blocked: set = set()
        for client, req in pairs:
            full = self.slots.allocator.pages_for(self._tokens_bound(req))
            if full > self.slots.allocator.num_pages:
                raise ValueError(
                    f"request {req.rid} needs {full} pages but the pool only "
                    f"has {self.slots.allocator.num_pages}; raise "
                    f"EngineConfig.num_pages"
                )
            cls = self._deadline_class(req)
            if any(b >= cls for b in blocked):
                continue
            need = self._pages_needed(req)
            if need > free:
                blocked.add(cls)
                continue
            out.append((client, req))
            free -= need
        return out

    # ------------------------------------------------------------------ #
    # On-demand page growth + preemption-by-eviction                      #
    # ------------------------------------------------------------------ #
    def _growth_target(self, slot: int, k: int) -> int:
        """KV tokens ``slot`` must own to run ``k`` more decode rounds: at
        emitted e, round j writes position n_prefill + e + j - 2, so k
        rounds need n_prefill + e + k - 1 tokens — capped by the request's
        lifetime bound (budget-exhausted lanes no-op inside the fused
        loop)."""
        req = self.slots.request_of[slot]
        return min(
            req.n_prefill + self.slots.emitted[slot] + k - 1,
            self._tokens_bound(req),
        )

    def _decode_growth_pages(self, k: int) -> int:
        """Pages the active decoders collectively need for ``k`` rounds."""
        return sum(
            self.slots.pages_to_cover(s, self._growth_target(s, k))
            for s in self.slots.active_slots
        )

    def _preemption_victims(self) -> List[int]:
        """Eviction order when the pool genuinely exhausts: offline before
        deadline traffic, then least progress lost (fewest emitted tokens —
        the cheapest recompute), newest rid first as the tie-break."""
        cands = []
        for s in range(self.cfg.n_slots):
            if self.slots.request_of[s] is not None:
                cands.append((s, self.slots.request_of[s]))
            elif s in self._chunking:
                cands.append((s, self._chunking[s].req))
        cands.sort(
            key=lambda sr: (
                self._deadline_class(sr[1]),
                self.slots.emitted[sr[0]],
                -sr[1].rid,
            )
        )
        return [s for s, _ in cands]

    def _preempt_slot(self, slot: int) -> None:
        """Evict ``slot`` (nano-vllm's preempt): deallocate its pages and
        re-queue its request for recompute-on-resume. A bound slot keeps its
        generated prefix in ``generated`` and is marked for resume (the
        prefix is recomputed into KV and the pending token restored, so the
        stream stays bit-identical); a mid-chunk prefill simply restarts."""
        sv = self._sv
        if slot in self._chunking:
            st = self._chunking.pop(slot)
            req = st.req
            if st.resume_emitted > 0:
                # a resumed recompute evicted mid-chunk resumes again later
                self._resume_rids.add(req.rid)
            self.slots.free_pages_of(slot)
        else:
            req = self.slots.request_of[slot]
            if self.generated.get(req.rid):
                self._resume_rids.add(req.rid)
                # its prefill completed once and will complete again —
                # trace validation expects 1 + preemptions completions
                req.preemptions += 1
            self.slots.release(slot)
            sv.clients[slot].current = None
        self.preemption_events += 1
        self._mig_pending.discard(slot)
        if self.obs is not None:
            self.obs.span(
                req.rid, "preempt", sv.t, replica=self.obs_replica,
                slot=slot, reason="page_pressure",
            )
        sv.scheduler.push(req)

    def _ensure_decode_capacity(self, k: int, allow_shrink: bool = False) -> int:
        """Secure pages for ``k`` decode rounds over every active slot.

        Prefers shrinking a policy-driven horizon (halving keeps the
        power-of-two jit buckets) over evicting work; when even k=1 cannot
        be funded it preempts victims lowest-priority-first until growth
        fits. Returns the horizon actually funded. Admission guarantees a
        request's lifetime bound fits the pool, so the last surviving slot
        can always grow once everything else is evicted — the loop
        terminates."""
        if self.cfg.kv_layout != "paged":
            return k
        while True:
            active = self.slots.active_slots
            if not active:
                return k
            headroom = (
                self.slots.allocator.num_free + self.slots.reclaimable_pages()
            )
            if self._decode_growth_pages(k) <= headroom:
                # ensure_tokens reclaims index-held pages on demand, so
                # eviction of live work stays the last resort
                for s in active:
                    self.slots.ensure_tokens(s, self._growth_target(s, k))
                return k
            if allow_shrink and k > 1:
                k //= 2
                continue
            victims = self._preemption_victims()
            if not victims:
                return k
            self._preempt_slot(victims[0])

    def _note_concurrency(self) -> None:
        cur = len(self.slots.active_slots) + len(self._chunking)
        if cur > self.peak_concurrency:
            self.peak_concurrency = cur

    def _note_first_token(self, req: Request, t: float) -> None:
        """Pin TTFT to the FIRST prefill completion (a preemption recomputes
        the prefill later, which must not move it) and feed the overload
        policy's attainment window."""
        if req.t_first_token is None:
            req.t_first_token = t
            if self.overload is not None and req.ttft_slo_s is not None:
                self.overload.record_ttft(t - req.arrival, req.ttft_slo_s)
            if self.obs is not None:
                self.obs.span(
                    req.rid, "first_token", t, replica=self.obs_replica,
                    slot=req.client, ttft_s=round(t - req.arrival, 6),
                )

    # ------------------------------------------------------------------ #
    # Observability emission (every call site guards on self.obs)         #
    # ------------------------------------------------------------------ #
    def _obs_admit(
        self, req: Request, t: float, slot: int, resumed: bool, cached: int
    ) -> None:
        """Admission span; a request's first-ever event is its arrival."""
        if not self.obs.spans.has(req.rid):
            self.obs.span(
                req.rid, "arrival", max(req.arrival, 0.0),
                replica=self.obs_replica,
            )
        self.obs.span(
            req.rid, "resume" if resumed else "admit", t,
            replica=self.obs_replica, slot=slot,
            cached_tokens=cached, prefill_tokens=req.n_prefill,
        )

    def _obs_complete(self, req: Request, t: float, slot: int) -> None:
        self.obs.span(
            req.rid, "complete", t, replica=self.obs_replica, slot=slot,
            decoded=req.decoded,
        )

    def _capacity_classes(
        self, busy: Dict[int, int], busy_partial: Dict[int, int], dt: float
    ) -> Dict[str, float]:
        """Classify every slot's share of one stage: each of ``n_slots``
        slots contributes exactly ``dt`` to exactly one class, so the sample
        sums to ``dt × n_slots`` by construction (the conservation the
        capacity-attribution rollup hard-checks)."""
        cls: Dict[str, float] = {}
        for s in range(self.cfg.n_slots):
            if s in busy or s in busy_partial:
                st = self._chunking.get(s)
                if st is not None and st.resume_emitted > 0:
                    c = "preempted"        # recomputing an evicted request
                elif st is not None and st.cached > 0:
                    c = "cache_hit"        # prefill riding adopted pages
                else:
                    c = "busy"
                self._mig_pending.discard(s)
            elif s in self._mig_pending:
                c = "migration"            # imported, not yet dispatched
            elif self.slots.request_of[s] is not None or s in self._chunking:
                c = "stall"                # holds work but was not dispatched
            else:
                c = "idle_gap"             # free slot during the stage
            cls[c] = cls.get(c, 0.0) + dt
        return cls

    def _obs_finish(self, trace: ScheduleTrace) -> None:
        """Mirror the trace's meta counters into the typed registry and
        record this replica's capacity denominator."""
        obs = self.obs
        for k, v in trace.meta.items():
            kind, unit, help_ = _METRIC_SPECS.get(
                k, ("counter", "", "engine meta counter")
            )
            obs.declare(k, kind, unit=unit, help=help_)
            if kind == "counter":
                obs.inc(k, float(v))
            else:
                # fleet semantics for per-replica peaks: the registry keeps
                # the max across replicas
                obs.set(k, max(obs.registry.value(k), float(v)))
        kind, unit, help_ = _METRIC_SPECS["decoded_tokens"]
        obs.declare("decoded_tokens", kind, unit=unit, help=help_)
        obs.inc("decoded_tokens", float(self.decoded_tokens))
        obs.finish_replica(self.obs_replica, trace.makespan, self.cfg.n_slots)

    def _start_chunked_batch(
        self, pairs: List[Tuple[ClientState, Request]], bin_index: int, now: float
    ) -> None:
        for client, req in pairs:
            prompt = self._prompt_tokens(req)
            resume_emitted = 0
            resume_pending = -1
            resumed = False
            if req.rid in self._resume_rids:
                self._resume_rids.discard(req.rid)
                prefix = self.generated.get(req.rid, [])
                if prefix:
                    resumed = True
                    resume_emitted = len(prefix)
                    resume_pending = int(prefix[-1])
                    if len(prefix) > 1:
                        prompt = np.concatenate(
                            [prompt, np.asarray(prefix[:-1], np.int32)]
                        )
            if self.cfg.page_reserve == "upfront":
                span = self._tokens_bound(req)
            else:
                span = len(prompt)
            if self._use_prefix_cache:
                # adopt cached full pages read-only (COW at the divergence
                # page); chunked prefill starts at the first uncached token
                cached = self.slots.reserve_with_prefix(
                    client.cid, prompt, span
                )
            else:
                self.slots.reserve(client.cid, span)
                cached = 0
            if resumed:
                # the re-prefilled span (prompt + prefix) is work this
                # request already paid for once — the cost page-copy
                # migration exists to avoid; cache hits shrink it further
                self.recomputed_tokens += len(prompt) - cached
            req.cached_prefill = min(cached, req.n_prefill)
            self.cache_hit_tokens += cached
            if self.obs is not None:
                self._obs_admit(req, now, client.cid, resumed, cached)
            self._chunking[client.cid] = _ChunkState(
                slot=client.cid, req=req, prompt=prompt, done=cached,
                resume_emitted=resume_emitted, resume_pending=resume_pending,
                cached=cached,
            )
            req.client = client.cid
            req.prefill_bin = bin_index
            req.t_prefill_start = now
        self._note_concurrency()

    def _next_chunk_tokens(self) -> int:
        return sum(
            min(self.cfg.prefill_chunk, st.remaining)
            for st in self._chunking.values()
        )

    def _dispatch_chunks(self, rows: Sequence[Tuple[int, np.ndarray, int]]):
        """One chunk-round dispatch over ``rows`` of (slot, chunk tokens,
        start offset), padded to a row bucket. Returns (logits, per-row
        real lengths, duration)."""
        c = self.cfg.prefill_chunk
        n_pad = _bucket(len(rows), self.cfg.prefill_req_buckets)
        tokens = np.zeros((n_pad, c), dtype=np.int32)
        # pad rows point one past the last slot: their (len-0) writes drop
        slot_ids = np.full(n_pad, self.cfg.n_slots, dtype=np.int32)
        starts = np.zeros(n_pad, dtype=np.int32)
        lens = np.zeros(n_pad, dtype=np.int32)
        for i, (slot, toks, start) in enumerate(rows):
            tokens[i, : len(toks)] = toks
            slot_ids[i] = slot
            starts[i] = start
            lens[i] = len(toks)
        t0 = time.perf_counter()
        self._phases.mark("launch", t0)
        logits, self.slots.cache = self._chunk_jit(
            self.params, jnp.asarray(tokens), self.slots.cache,
            jnp.asarray(slot_ids), jnp.asarray(starts), jnp.asarray(lens),
        )
        self._phases.mark("wait")
        logits.block_until_ready()
        t1 = time.perf_counter()
        self._phases.mark("commit", t1)
        return logits, lens, t1 - t0

    def prefill_logits(self, prompts: Sequence[np.ndarray]) -> np.ndarray:
        """Last-token logits (len(prompts), V) of each prompt through the
        engine's own paged chunked prefill — the chunk-round program, on
        free slots of the live pool, whose pages are freed afterwards. For
        use between serves: it is how the serving path's numerics are
        checked against a cache-free reference forward."""
        if self.cfg.kv_layout != "paged":
            raise ValueError("prefill_logits needs kv_layout='paged'")
        slots = self.slots.free_slots[: len(prompts)]
        if len(slots) < len(prompts):
            raise RuntimeError(
                f"{len(prompts)} prompts but {len(slots)} free slots"
            )
        for slot, prompt in zip(slots, prompts):
            self.slots.reserve(slot, len(prompt))
        c = self.cfg.prefill_chunk
        out: List[Optional[np.ndarray]] = [None] * len(prompts)
        for start in range(0, max(len(p) for p in prompts), c):
            live = [i for i, p in enumerate(prompts) if start < len(p)]
            logits, _, _ = self._dispatch_chunks(
                [(slots[i], prompts[i][start : start + c], start) for i in live]
            )
            logits = np.asarray(logits)
            for row, i in enumerate(live):
                if start + c >= len(prompts[i]):
                    out[i] = logits[row]
        for slot in slots:
            self.slots.free_pages_of(slot)
        return np.stack(out)

    def _run_chunk_round(self):
        """One chunk round over every mid-prefill slot; returns
        (duration, chunk_tokens, finished_slots, busy, busy_partial)."""
        states = [self._chunking[s] for s in sorted(self._chunking)]
        c = self.cfg.prefill_chunk
        logits, lens, dt = self._dispatch_chunks([
            (st.slot, st.prompt[st.done : st.done + c], st.done)
            for st in states
        ])
        dt /= self.speed_factor
        first = self._sample_first(logits, [st.req.rid for st in states])
        self._dev_pending = None          # prefill rewrites pending tokens
        busy: Dict[int, int] = {}
        busy_partial: Dict[int, int] = {}
        finished: List[int] = []
        chunk_tokens = int(lens.sum())
        for i, st in enumerate(states):
            slot = st.slot
            st.done += int(lens[i])
            if st.done >= st.total:
                self.slots.bind(slot, st.req)
                if self._use_prefix_cache:
                    # publish the prompt's FULL pages (the partial last page
                    # still takes decode writes and must stay private)
                    self.slots.publish_prefix(slot, st.prompt)
                if st.resume_emitted > 0:
                    # recompute complete: restore the pre-preemption stream
                    # state instead of sampling (bit-identical continuation)
                    self.slots.emitted[slot] = st.resume_emitted
                    self.pending_token[slot] = st.resume_pending
                else:
                    self.slots.emitted[slot] = 1   # final chunk samples token #1
                    self.pending_token[slot] = int(first[i])
                    self.generated.setdefault(st.req.rid, []).append(int(first[i]))
                busy[slot] = st.req.rid
                finished.append(slot)
                del self._chunking[slot]
            else:
                busy_partial[slot] = st.req.rid
        self._observe_prefill(chunk_tokens, dt)
        return dt, chunk_tokens, finished, busy, busy_partial

    # ------------------------------------------------------------------ #
    # Mixed prefill+decode rounds (paged layout, mixed_schedule=True)     #
    # ------------------------------------------------------------------ #
    def _plan_mixed_round(
        self, pairs: List[Tuple[ClientState, Request]], share: int
    ) -> Tuple[List[Tuple[_ChunkState, int]], List[Tuple[ClientState, Request, int]]]:
        """Split the policy-priced chunk-token share across prefill work.

        Grants are WHOLE chunks (a prompt's final partial chunk excepted):
        a mixed dispatch pays for full ``prefill_chunk``-wide rows whatever
        they hold, so funding a fraction of a chunk burns the same compute
        for half the prefill progress — under sustained arrivals that can
        push prefill supply below demand and grow the queue without bound.
        The share therefore picks *how many* chunk rows ride along (the
        last grant may overshoot it), not where inside a chunk to stop.

        Continuations of in-flight chunked prefills are funded first (finish
        what holds pages before opening new prompts), then new admissions —
        the rest stay queued for a later round. Returns the per-state token
        counts for this round and the admissions to commit.
        """
        plan: List[Tuple[_ChunkState, int]] = []
        budget = share
        for slot in sorted(self._chunking):
            if budget <= 0:
                break
            st = self._chunking[slot]
            n = min(self.cfg.prefill_chunk, st.remaining)
            if n > 0:
                plan.append((st, n))
                budget -= n
        admitted: List[Tuple[ClientState, Request, int]] = []
        for client, req in pairs:
            if budget <= 0:
                break
            n = min(self.cfg.prefill_chunk, req.n_prefill)
            admitted.append((client, req, n))
            budget -= n
        return plan, admitted

    def _run_mixed_stage(self, plan: List[Tuple[_ChunkState, int]]):
        """ONE unified dispatch: a decode round over every active slot plus
        the planned prefill-chunk rows, written straight into the paged
        pool. Decode lanes sample their next token on device; a prompt whose
        final chunk lands this round emits its first token in the same call.
        Returns (duration, finished_decode_slots, decode_tokens,
        chunk_tokens, finished_chunk_slots, busy, busy_partial).
        """
        cfg = self.cfg
        j = cfg.n_slots
        decode_slots = self.slots.active_slots
        n_chunk = sum(n for _, n in plan)
        c = cfg.prefill_chunk
        # chunk rows pad to the same rectangles the alternating chunk round
        # compiles — no extra jit variants for the mixed path
        r_pad = _bucket(max(len(plan), 1), cfg.prefill_req_buckets)
        chunk_tokens = np.zeros((r_pad, c), dtype=np.int32)
        chunk_slots = np.full(r_pad, j, dtype=np.int32)    # j → pad row
        starts = np.zeros(r_pad, dtype=np.int32)
        lens = np.zeros(r_pad, dtype=np.int32)
        dec_active = np.zeros(j, dtype=bool)
        sample_rows = np.zeros(j + r_pad, dtype=bool)
        rids = np.full(j + r_pad, -1, dtype=np.int32)
        token_idx = np.zeros(j + r_pad, dtype=np.int32)
        budgets: Dict[int, int] = {}
        for slot in decode_slots:
            req = self.slots.request_of[slot]
            dec_active[slot] = True
            sample_rows[slot] = True
            rids[slot] = req.rid
            token_idx[slot] = self.slots.emitted[slot]
            budgets[slot] = self._decode_budget(slot)
        final_row: Dict[int, int] = {}     # slot → sample row of final chunk
        for i, (st, n) in enumerate(plan):
            chunk_tokens[i, :n] = st.prompt[st.done : st.done + n]
            chunk_slots[i] = st.slot
            starts[i] = st.done
            lens[i] = n
            if st.done + n >= st.total:
                if st.resume_emitted == 0:
                    # resumed rows never sample: their first token already
                    # exists and is restored, not re-drawn
                    sample_rows[j + i] = True
                    rids[j + i] = st.req.rid
                final_row[st.slot] = j + i
        pending = (
            self._dev_pending if self._dev_pending is not None
            else jnp.asarray(self.pending_token)
        )
        t0 = time.perf_counter()
        self._phases.mark("launch", t0)
        sampled, self.slots.cache = self._mixed_jit(
            self.params, pending, self.slots.cache,
            jnp.asarray(chunk_tokens), jnp.asarray(chunk_slots),
            jnp.asarray(starts), jnp.asarray(lens),
            jnp.asarray(dec_active), jnp.asarray(rids),
            jnp.asarray(token_idx), jnp.asarray(sample_rows), self._base_key,
        )
        self._phases.mark("wait")
        sampled = np.asarray(sampled)      # the ONE host sync for this round
        t1 = time.perf_counter()
        self._phases.mark("commit", t1)
        dt = (t1 - t0) / self.speed_factor
        self._dev_pending = None           # pending rebuilt from host below

        finished_decode: List[int] = []
        decode_tokens = 0
        busy: Dict[int, int] = {}
        busy_partial: Dict[int, int] = {}
        for slot in decode_slots:
            tok = int(sampled[slot])
            req = self.slots.request_of[slot]
            self.slots.emitted[slot] += 1
            self.pending_token[slot] = tok
            self.generated.setdefault(req.rid, []).append(tok)
            req.decoded = self.slots.emitted[slot]
            decode_tokens += 1
            busy[slot] = req.rid
            if budgets[slot] <= 1 or (
                cfg.eos_id is not None and tok == cfg.eos_id
            ):
                finished_decode.append(slot)
        finished_chunks: List[int] = []
        for st, n in plan:
            st.done += n
            slot = st.slot
            if st.done >= st.total:
                self.slots.bind(slot, st.req)
                if self._use_prefix_cache:
                    self.slots.publish_prefix(slot, st.prompt)
                if st.resume_emitted > 0:
                    self.slots.emitted[slot] = st.resume_emitted
                    self.pending_token[slot] = st.resume_pending
                else:
                    self.slots.emitted[slot] = 1   # final chunk samples token #1
                    first = int(sampled[final_row[slot]])
                    self.pending_token[slot] = first
                    self.generated.setdefault(st.req.rid, []).append(first)
                busy[slot] = st.req.rid
                finished_chunks.append(slot)
                del self._chunking[slot]
            else:
                busy_partial[slot] = st.req.rid
        self.mixed_rounds += 1
        if decode_slots:
            self.decode_dispatches += 1
            self.decoded_tokens += decode_tokens
        # rounds with no active decoders route to _run_chunk_round in the
        # serve loop, so every mixed sample carries real decode lanes
        self.profiler.record_mixed(len(decode_slots), n_chunk, dt)
        return (
            dt, finished_decode, decode_tokens, n_chunk, finished_chunks,
            busy, busy_partial,
        )

    def _finish_prefills(
        self, slots: List[int], clients: List[ClientState], t: float
    ) -> None:
        """Post-stage bookkeeping for requests whose final chunk just
        landed (shared by the mixed and alternating chunk-round branches)."""
        for slot in slots:
            req = self.slots.request_of[slot]
            clients[slot].current = req
            req.t_prefill_end = t
            # resumed slots re-enter decode at their pre-preemption count
            req.decoded = self.slots.emitted[slot]
            if self.obs is not None:
                self.obs.span(
                    req.rid, "prefill_done", t, replica=self.obs_replica,
                    slot=slot,
                )
            self._note_first_token(req, t)
            # requests with n_decode == 1 finish at prefill
            if self.cfg.eos_id is None and req.n_decode <= 1:
                req.t_done = t
                self.slots.release(slot)
                clients[slot].current = None
                if self.obs is not None:
                    self._obs_complete(req, t, slot)

    def warm_serving_shapes(self) -> None:
        """Pre-compile every paged serving-dispatch variant the scheduler
        can reach — mixed-round row buckets, chunk-round rectangles, and
        fused-decode horizons — with all-pad / all-inactive no-op calls
        (writes dropped, lengths untouched, nothing recorded).

        Which variant a stage lands in depends on live policy decisions
        that shift with the online fit, so a measured serve can hit a shape
        its warm pass never saw — and one first-hit compile dwarfs every
        real stage. Benchmarks call this after their warm pass so the timed
        serve only sees compiled code."""
        if self.cfg.kv_layout != "paged":
            return
        cfg = self.cfg
        j = cfg.n_slots
        row_buckets = sorted({
            _bucket(rows, cfg.prefill_req_buckets)
            for rows in range(1, j + 1)
        })
        for r_pad in row_buckets:
            if cfg.mixed_schedule:
                # mixed round: j decode lanes + r_pad chunk rows, padded out
                # (unreachable — and so not warmed — in alternating mode)
                sampled, self.slots.cache = self._mixed_jit(
                    self.params,
                    jnp.zeros(j, jnp.int32), self.slots.cache,
                    jnp.zeros((r_pad, cfg.prefill_chunk), jnp.int32),
                    jnp.full(r_pad, j, jnp.int32),
                    jnp.zeros(r_pad, jnp.int32), jnp.zeros(r_pad, jnp.int32),
                    jnp.zeros(j, bool), jnp.full(j + r_pad, -1, jnp.int32),
                    jnp.zeros(j + r_pad, jnp.int32),
                    jnp.zeros(j + r_pad, bool),
                    self._base_key,
                )
                sampled.block_until_ready()
            # chunk round: r_pad prompt rows, all padded out
            logits, self.slots.cache = self._chunk_jit(
                self.params,
                jnp.zeros((r_pad, cfg.prefill_chunk), jnp.int32),
                self.slots.cache,
                jnp.full(r_pad, j, jnp.int32),
                jnp.zeros(r_pad, jnp.int32), jnp.zeros(r_pad, jnp.int32),
            )
            logits.block_until_ready()
        k_cap = max(cfg.decode_horizon or cfg.max_decode_horizon, 1)
        horizons = {k_cap}                 # a pinned K dispatches exactly
        k = 1
        while k <= k_cap:                  # plus the power-of-two buckets
            horizons.add(k)
            k *= 2
        for k in sorted(horizons):
            # fused decode at horizon k, every slot inactive
            out = self._fused_jit(
                k, self.params, jnp.zeros(j, jnp.int32), self.slots.cache,
                jnp.zeros(j, bool), jnp.zeros(j, jnp.int32),
                jnp.zeros(j, jnp.int32), jnp.zeros(j, jnp.int32),
                self._base_key,
            )
            self.slots.cache = out[-1]
            out[0].block_until_ready()

    def _choose_horizon(self, policy_horizon: int) -> int:
        """Final decode horizon, capped by the largest remaining per-slot
        budget (no all-no-op tail rounds). A pinned ``decode_horizon`` is
        honored exactly (ablations must measure the K they asked for); the
        policy-driven path buckets down to a power of two so at most
        log2(K_max)+1 jit variants ever compile."""
        cfg = self.cfg
        rem = max(
            (self._decode_budget(s) for s in self.slots.active_slots),
            default=1,
        )
        if cfg.decode_horizon is not None:
            k = max(1, min(cfg.decode_horizon, rem))
            # run the pinned K exactly while budgets allow; bucket only the
            # drain tail (rem < K), else every distinct tail value would
            # compile a fresh executable inside a measured region
            return k if k == cfg.decode_horizon else 1 << (k.bit_length() - 1)
        k = max(1, min(policy_horizon, cfg.max_decode_horizon, rem))
        return 1 << (k.bit_length() - 1)

    def _decode_budget(self, slot: int) -> int:
        """Tokens this slot may still emit: its known output budget, or (eos
        mode) the KV capacity left — round r writes position
        n_prefill + emitted - 1, which must stay below max_len."""
        req = self.slots.request_of[slot]
        emitted = self.slots.emitted[slot]
        if self.cfg.eos_id is None:
            return max(1, req.n_decode - emitted)
        return max(1, self.cfg.max_len - (req.n_prefill + emitted - 1))

    def _run_decode_stage(self, k: int) -> Tuple[float, List[int], int]:
        """One fused decode stage of ``k`` iterations over all active slots:
        ONE device dispatch, ONE host sync at the horizon boundary. Returns
        (duration, finished slots, tokens emitted)."""
        cfg = self.cfg
        slots = self.slots.active_slots
        active = np.zeros(cfg.n_slots, dtype=bool)
        budgets = np.zeros(cfg.n_slots, dtype=np.int32)
        rids = np.zeros(cfg.n_slots, dtype=np.int32)
        emit0 = np.zeros(cfg.n_slots, dtype=np.int32)
        for slot in slots:
            active[slot] = True
            budgets[slot] = self._decode_budget(slot)
            rids[slot] = self.slots.request_of[slot].rid
            emit0[slot] = self.slots.emitted[slot]
        pending = (
            self._dev_pending if self._dev_pending is not None
            else jnp.asarray(self.pending_token)
        )
        t0 = time.perf_counter()
        self._phases.mark("launch", t0)
        token_block, emitted_k, active_out, last_tok, self.slots.cache = (
            self._fused_jit(
                k, self.params, pending, self.slots.cache,
                jnp.asarray(active), jnp.asarray(budgets), jnp.asarray(rids),
                jnp.asarray(emit0), self._base_key,
            )
        )
        self._phases.mark("wait")
        # the ONE host sync for this horizon: everything the scheduler needs
        block = np.asarray(token_block)                    # (K, n_slots)
        emitted_k = np.asarray(emitted_k)
        active_out = np.asarray(active_out)
        t1 = time.perf_counter()
        self._phases.mark("commit", t1)
        dt = (t1 - t0) / self.speed_factor
        self._dev_pending = last_tok      # stays device-resident across stages
        self.decode_dispatches += 1
        finished: List[int] = []
        total = 0
        for slot in slots:
            cnt = int(emitted_k[slot])
            req = self.slots.request_of[slot]
            toks = block[:cnt, slot]
            self.slots.emitted[slot] += cnt
            self.pending_token[slot] = int(toks[-1])
            self.generated.setdefault(req.rid, []).extend(int(x) for x in toks)
            req.decoded = self.slots.emitted[slot]
            total += cnt
            if not bool(active_out[slot]):
                finished.append(slot)
        self.decoded_tokens += total
        self.profiler.record_decode(len(slots), dt, rounds=k)
        return dt, finished, total

    # ------------------------------------------------------------------ #
    def begin_serve(
        self,
        requests: Sequence[Request],
        clients: List[ClientState],
        request_scheduler: RequestScheduler,
        iteration_policy: IterationPolicy,
        policy_name: str = "",
        track_requests: bool = False,
    ) -> None:
        """Open a step-driven serve session (``serve_step`` runs stages one
        at a time; ``finish_serve`` closes the trace).

        ``serve()`` wraps the three; a ``Fleet`` drives many engines'
        sessions interleaved by virtual time instead, routing arrivals and
        stolen requests in mid-serve via the scheduler's ``push``. With
        ``track_requests=True`` the trace adopts requests as the scheduler
        commits them (the request set is discovered, not declared — fleet
        dispatch and stealing decide placement while the serve runs)."""
        cfg = self.cfg
        if len(clients) != cfg.n_slots:
            raise ValueError("clients must match n_slots")
        trace = ScheduleTrace(
            num_clients=cfg.n_slots,
            requests=list(requests),
            policy_name=policy_name or f"engine/{iteration_policy.name}",
        )
        for r in requests:
            r.reset()
        # per-serve output record (rids repeat across workloads; in-flight
        # _chunking state is deliberately NOT cleared — it's the resume path)
        self.generated = {}
        self.decode_dispatches = 0
        self.decoded_tokens = 0
        self.mixed_rounds = 0
        self.prefill_stall_time = 0.0
        self.preemption_events = 0
        self.offline_deferrals = 0
        self.peak_concurrency = 0
        self.recomputed_tokens = 0
        self.migrated_pages_in = 0
        self.migrated_pages_out = 0
        self.migrations_in = 0
        self.migrations_out = 0
        self.cache_hit_tokens = 0
        self._mig_pending = set()
        self._sv = _ServeSession(
            trace=trace, clients=clients, scheduler=request_scheduler,
            policy=iteration_policy, track_requests=track_requests,
        )

    def has_work(self) -> bool:
        """Anything to run right now or later: a bound slot, an in-flight
        chunked prefill, or a queued request (arrived or future)."""
        return (
            bool(self.slots.active_slots)
            or bool(self._chunking)
            or self._sv.scheduler.has_pending()
        )

    @property
    def clock(self) -> float:
        """The open session's stage clock (virtual serve time)."""
        return self._sv.t

    def advance_clock(self, t: float) -> None:
        """Fast-forward the session clock (fleet-level idle gaps — the fleet
        routes arrivals itself, so the engine never sees them coming)."""
        if t > self._sv.t:
            self._sv.t = t

    def _commit_pairs(self, pairs: List[Tuple[ClientState, Request]]) -> None:
        sv = self._sv
        sv.scheduler.commit_batch(pairs)
        if sv.track_requests:
            # a preempted request is committed again on resume — adopt each
            # request into the trace once
            known = {r.rid for r in sv.trace.requests}
            sv.trace.requests.extend(
                r for _, r in pairs if r.rid not in known
            )

    def queued_requests(self) -> Tuple[Request, ...]:
        """Not-yet-admitted requests of the open session (overload policies
        inspect these for queue pressure)."""
        if self._sv is None:
            return ()
        return self._sv.scheduler.queued

    def adopt_resume(self, req: Request, prefix: List[int]) -> None:
        """Adopt a request recovered from another replica mid-decode (fleet
        fault recovery): seed its generated-so-far prefix and queue it for
        recompute-on-resume — the same path a locally preempted request
        takes, so the resumed stream is bit-identical to an uninterrupted
        serve."""
        self.generated[req.rid] = list(prefix)
        self._resume_rids.add(req.rid)
        self._sv.scheduler.push(req)

    # ------------------------------------------------------------------ #
    # Live migration by page-copy (fleet drain / rebalancing / recovery)  #
    # ------------------------------------------------------------------ #
    def _check_invariants(self) -> None:
        """debug_invariants hook: allocator free-list/free-set consistency,
        the host↔device block-table mirror, and per-page refcount agreement
        (block-table multiplicity + prefix-index holds) — paged layout
        only."""
        if self.cfg.kv_layout != "paged":
            return
        self.slots.allocator.check_consistency()
        self.slots.check_block_table_mirror()
        self.slots.check_refcounts()

    def _local_prefill_completions(self, rid: int) -> int:
        """Prefill completions for ``rid`` recorded in THIS session's trace
        so far — the same counting rule ``ScheduleTrace.validate`` applies.
        An import must subtract these from the checkpoint's total credit so
        a request that leaves and later returns is not double-counted."""
        cnt = 0
        for s in self._sv.trace.stages:
            if s.kind is StageKind.PREFILL:
                cnt += sum(1 for r in s.busy.values() if r == rid)
            elif s.kind is StageKind.MIXED:
                cnt += sum(1 for r in s.prefilled.values() if r == rid)
        return cnt

    def can_import(self, n_pages: int) -> bool:
        """Whether this engine can host a migrated slot of ``n_pages`` right
        now: a truly free slot, and pool headroom beyond the pages its own
        active decoders need for their next round — an import must never be
        the thing that immediately forces a preemption here."""
        if self.cfg.kv_layout != "paged" or self._sv is None:
            return False
        if not any(s not in self._chunking for s in self.slots.free_slots):
            return False
        free = (
            self.slots.allocator.num_free + self.slots.reclaimable_pages()
            - self._decode_growth_pages(1)
        )
        return n_pages <= free

    def slot_pages(self, slot: int) -> int:
        """Pages ``slot`` currently owns (capacity probe for migration)."""
        return len(self.slots.tables[slot])

    def export_slot(self, slot: int) -> SlotCheckpoint:
        """Extract ``slot``'s full mid-request state as a portable
        ``SlotCheckpoint`` and release the slot: gather its KV pages off the
        pool, capture the pending token / sampler cursor / generated prefix
        (and mid-chunk prefill progress), free the pages, and drop the
        request from this trace — it continues its life, exactly-once, on
        whichever engine imports the checkpoint."""
        sv = self._sv
        if slot in self._chunking:
            st = self._chunking[slot]
            req = st.req
            kind = "chunking"
            emitted = st.resume_emitted
            pending = st.resume_pending
            chunk_done = st.done
            resume_emitted = st.resume_emitted
            resume_pending = st.resume_pending
            # the in-flight pass hasn't completed; earlier passes number
            # exactly req.preemptions (0 for a fresh prompt)
            credit = req.preemptions
        elif self.slots.request_of[slot] is not None:
            req = self.slots.request_of[slot]
            kind = "bound"
            emitted = self.slots.emitted[slot]
            pending = int(self.pending_token[slot])
            chunk_done = 0
            resume_emitted = 0
            resume_pending = -1
            # a bound slot has completed every prefill it will ever need
            credit = 1 + req.preemptions
        else:
            raise RuntimeError(f"slot {slot} holds no in-flight request")
        pages, k_pages, v_pages, kv_length, checksum = (
            self.slots.export_pages(slot)
        )
        if kind == "chunking":
            del self._chunking[slot]
            self.slots.free_pages_of(slot)
        else:
            self.slots.release(slot)
            sv.clients[slot].current = None
        prefix = self.generated.pop(req.rid, [])
        sv.trace.requests = [r for r in sv.trace.requests if r.rid != req.rid]
        sv.trace.external_prefills.pop(req.rid, None)
        self.migrations_out += 1
        self.migrated_pages_out += len(pages)
        self._mig_pending.discard(slot)
        if self.obs is not None:
            self.obs.span(
                req.rid, "migrate_out", sv.t, replica=self.obs_replica,
                slot=slot, pages=len(pages), state=kind,
            )
        if self.debug_invariants:
            self._check_invariants()
        return SlotCheckpoint(
            req=req, kind=kind, emitted=emitted, pending_token=pending,
            kv_length=kv_length, k_pages=k_pages, v_pages=v_pages,
            n_pages=len(pages), prefix=list(prefix), prefill_credit=credit,
            chunk_done=chunk_done, resume_emitted=resume_emitted,
            resume_pending=resume_pending, checksum=checksum,
        )

    def import_slot(self, ckpt: SlotCheckpoint) -> int:
        """Land a migrated slot in this engine: allocate fresh pages,
        scatter the KV payload, and rebind the request exactly where it
        left off — same pending token, same sampler cursor, so the stream
        continues bit-identical with zero recomputed tokens. Returns the
        destination slot. Callers gate on ``can_import`` first."""
        sv = self._sv
        free = [s for s in self.slots.free_slots if s not in self._chunking]
        if not free:
            raise RuntimeError("no free slot to import into")
        slot = free[0]
        # verifies the payload CRC before any pool state changes — a
        # corrupted transfer raises PageIntegrityError with nothing bound
        self.slots.import_pages(
            slot, ckpt.k_pages, ckpt.v_pages, ckpt.kv_length,
            checksum=ckpt.checksum,
        )
        req = ckpt.req
        if ckpt.prefix:
            self.generated[req.rid] = list(ckpt.prefix)
        if ckpt.kind == "bound":
            self.slots.bind(slot, req)
            self.slots.emitted[slot] = ckpt.emitted
            self.pending_token[slot] = ckpt.pending_token
            # decode stages read pending tokens from the device copy when
            # one is live — it predates this import and must be rebuilt
            self._dev_pending = None
            sv.clients[slot].current = req
            req.decoded = ckpt.emitted
        else:
            prompt = self._prompt_tokens(req)
            if ckpt.resume_emitted > 1:
                prompt = np.concatenate(
                    [prompt, np.asarray(ckpt.prefix[:-1], np.int32)]
                )
            self._chunking[slot] = _ChunkState(
                slot=slot, req=req, prompt=prompt, done=ckpt.chunk_done,
                resume_emitted=ckpt.resume_emitted,
                resume_pending=ckpt.resume_pending,
            )
        req.client = slot
        known = {r.rid for r in sv.trace.requests}
        if req.rid not in known:
            sv.trace.requests.append(req)
        # credit only the completions THIS trace hasn't recorded locally (a
        # request can leave and come back; its earlier local stages remain)
        sv.trace.external_prefills[req.rid] = (
            ckpt.prefill_credit - self._local_prefill_completions(req.rid)
        )
        self._note_concurrency()
        self.migrations_in += 1
        self.migrated_pages_in += ckpt.n_pages
        self._mig_pending.add(slot)
        if self.obs is not None:
            self.obs.span(
                req.rid, "migrate_in", sv.t, replica=self.obs_replica,
                slot=slot, pages=ckpt.n_pages, state=ckpt.kind,
            )
        if self.debug_invariants:
            self._check_invariants()
        return slot

    def _filter_overload(
        self,
        pairs: List[Tuple[ClientState, Request]],
        idle: List[ClientState],
        max_cap: int,
        request_scheduler: RequestScheduler,
        t: float,
    ) -> List[Tuple[ClientState, Request]]:
        """Run the overload policy over the proposed admissions, re-proposing
        for any client whose candidate was deferred with the deferred rids
        excluded — in an FCFS queue a deferred offline head must not shadow
        an admissible (online) request queued behind it."""
        kept = self.overload.filter_admissions(pairs, t, self)
        deferred = {r.rid for _, r in pairs} - {r.rid for _, r in kept}
        if not deferred:
            return kept
        self.offline_deferrals += len(deferred)
        while True:
            taken = {id(c) for c, _ in kept}
            freed = [c for c in idle if id(c) not in taken]
            budget = max_cap - sum(r.n_prefill for _, r in kept)
            if not freed or budget <= 0:
                return kept
            extra = request_scheduler.propose_batch(
                freed, budget,
                exclude=deferred | {r.rid for _, r in kept},
            )
            if not extra:
                return kept
            kept_extra = self.overload.filter_admissions(extra, t, self)
            newly = {r.rid for _, r in extra} - {r.rid for _, r in kept_extra}
            self.offline_deferrals += len(newly)
            kept = kept + kept_extra
            if not newly:
                return kept
            deferred |= newly

    def serve_step(self) -> str:
        """Run at most one stage of the open session. Returns:

        * ``"ran"``  — executed a stage (or made clock progress);
        * ``"done"`` — no active work and the scheduler has nothing pending
          (a fleet may push more work and call again);
        * ``"idle"`` — pending work exists but nothing can run and no
          arrival is known to wait for (closed-loop callers treat this as a
          deadlock; a fleet decides what happens next).

        A step that runs a stage stamps the stage's record with the wall
        seconds of its phases (``StageRecord.host``: ``schedule``, ``kv``,
        ``prepare``, ``launch``, ``wait``, ``commit``, in that order; a retry
        after an eviction adds to them) and the KV pool's device writes in
        the step (``StageRecord.pool_writes``: the block-table upload at the
        end of ``kv``, eager length writes and page copies). Each phase is
        also a ``jax.profiler.TraceAnnotation`` named ``engine.<phase>``.
        """
        trace = self._sv.trace
        n_stages = len(trace.stages)
        paged = self.cfg.kv_layout == "paged"
        writes = self.slots.device_writes if paged else 0
        self._phases.start()
        self._phases.mark("schedule")
        try:
            status = self._serve_step()
        finally:
            host = self._phases.stop()
        if len(trace.stages) > n_stages:
            rec = trace.stages[-1]
            rec.host = host
            if paged:
                rec.pool_writes = self.slots.device_writes - writes
        return status

    def _serve_step(self) -> str:
        sv = self._sv
        cfg = self.cfg
        paged = cfg.kv_layout == "paged"
        mixed = paged and cfg.mixed_schedule
        clients = sv.clients
        request_scheduler = sv.scheduler
        iteration_policy = sv.policy
        trace = sv.trace
        ph = self._phases
        for _attempt in range(4):
            ph.mark("schedule")
            if sv.stages_run >= cfg.max_stages:
                raise RuntimeError("max_stages exceeded")
            t = sv.t
            if self.obs is not None and paged:
                # COW copies fire inside reserve_with_prefix; stamp them
                # with the current virtual time
                self.slots.obs_now = t
            max_cap = max(
                self.profiler.cost_model.max_level.cap_tokens >> self._budget_shift,
                self.profiler.cost_model.level_caps[0],
            )
            active = [c for c in clients if c.current is not None]
            idle = [
                c for c in clients
                if c.current is None and c.cid not in self._chunking
            ]
            if (
                not active and not self._chunking
                and not request_scheduler.has_pending()
            ):
                return "done"
            # arrival-aware schedulers gate their queue on the stage clock
            if hasattr(request_scheduler, "set_now"):
                request_scheduler.set_now(t)
            pairs = request_scheduler.propose_batch(idle, max_cap)
            if self.overload is not None and pairs:
                pairs = self._filter_overload(
                    pairs, idle, max_cap, request_scheduler, t
                )
            if paged and pairs:
                pairs = self._admissible(pairs)
            if paged:
                # the candidate stage is one chunk round: continuations of
                # any in-flight prefills plus first chunks of new admissions
                # (idle slots keep admitting while long prompts chunk)
                cont = sorted(self._chunking)
                cached_est = 0
                if self._use_prefix_cache and cfg.cache_aware_pricing:
                    # tokens of this candidate the cache will serve: known
                    # exactly for in-flight prefills, probed (read-only) for
                    # proposed admissions — so the Lagrangian share prices
                    # the prefill work actually computed
                    cached_est = sum(
                        self._chunking[s].cached for s in cont
                    ) + sum(
                        self.slots.probe_prefix(self._prompt_tokens(r))
                        for _, r in pairs
                    )
                candidate = CandidateBatch(
                    requests=[self._chunking[s].req for s in cont]
                    + [r for _, r in pairs],
                    client_ids=cont + [c.cid for c, _ in pairs],
                    chunk_tokens=self._next_chunk_tokens()
                    + sum(min(cfg.prefill_chunk, r.n_prefill) for _, r in pairs),
                    cached_tokens=cached_est,
                )
            else:
                candidate = CandidateBatch(
                    requests=[r for _, r in pairs],
                    client_ids=[c.cid for c, _ in pairs],
                )
            snap = SystemSnapshot(
                n_clients=cfg.n_slots,
                n_active=len(active),
                n_idle=len(idle),
                active_remaining_est=sum(
                    max(0, (c.current.n_decode_est or 0) - c.current.decoded)
                    for c in active
                ),
                pending_requests=request_scheduler.pending_count(),
                candidate=candidate,
                now=t,
            )
            # actionable prefill work in flight or in view → the
            # latency-sensitive "burst" window (queued-but-unproposable
            # requests don't count: no prefill can preempt decode for them)
            burst = bool(self._chunking or pairs)
            mixed_budget: Optional[int] = None
            if mixed:
                avail = self._next_chunk_tokens() + sum(
                    min(cfg.prefill_chunk, r.n_prefill) for _, r in pairs
                )
                mixed_budget = min(avail, cfg.mixed_token_buckets[-1])
            explain = (
                {} if (self.obs is not None and mixed_budget is not None)
                else None
            )
            t0 = time.perf_counter()
            decision = iteration_policy.decide(
                snap, self.profiler.cost_model,
                k_max=cfg.decode_horizon or cfg.max_decode_horizon,
                mixed_budget=mixed_budget,
                explain=explain,
            )
            do_prefill = decision.prefill
            trace.decision_times_ms.append((time.perf_counter() - t0) * 1e3)
            if explain:
                self.obs.audit_record(
                    "prefill_share", t, self.obs_replica, explain,
                    explain.get("share", decision.chunk_tokens),
                )

            if mixed and decision.chunk_tokens > 0 and active:
                # quantize the priced share down to the bucket table (the
                # mixed analogue of the paper's prefill levels — stable
                # round compositions; sub-bucket shares round up to the
                # smallest bucket so small candidates still make progress)
                fitting = [
                    b for b in cfg.mixed_token_buckets
                    if b <= decision.chunk_tokens
                ]
                share = fitting[-1] if fitting else cfg.mixed_token_buckets[0]
                plan, admitted = self._plan_mixed_round(pairs, share)
                ph.mark("kv")
                if admitted:
                    new_pairs = [(c, r) for c, r, _ in admitted]
                    self._commit_pairs(new_pairs)
                    sv.bin_index += 1
                    self._start_chunked_batch(new_pairs, sv.bin_index, t)
                    for c, _, n in admitted:
                        st = self._chunking[c.cid]
                        # a prefix-cache hit shrinks the first chunk below
                        # the planned grant — clamp to what actually remains
                        plan.append((st, min(n, st.remaining)))
                if cfg.page_reserve != "upfront":
                    # fund every decode lane's next-round KV write, evicting
                    # victims if the pool exhausts — an evicted mid-chunk
                    # prefill drops out of this round's plan
                    self._ensure_decode_capacity(1)
                    plan = [
                        (st, n) for st, n in plan
                        if self._chunking.get(st.slot) is st
                    ]
                    if not self.slots.active_slots:
                        continue   # every decode lane was evicted — re-plan
                self.slots.flush_block_tables()
                ph.mark("prepare")
                (
                    dt, fin_decode, decode_tok, chunk_tok, fin_chunks,
                    busy, busy_partial,
                ) = self._run_mixed_stage(plan)
                trace.stages.append(
                    StageRecord(
                        kind=StageKind.MIXED,
                        t_start=t, t_end=t + dt,
                        bin_index=max(sv.bin_index, 0),
                        busy=busy, busy_partial=busy_partial,
                        tokens=decode_tok + chunk_tok,
                        chunk_tokens=chunk_tok, rounds=1, burst=True,
                        prefilled={
                            s: self.slots.request_of[s].rid for s in fin_chunks
                        },
                    )
                )
                sv.t = t + dt
                if self.obs is not None:
                    self.obs.capacity(
                        self.obs_replica, t, sv.t,
                        self._capacity_classes(busy, busy_partial, dt),
                    )
                self._finish_prefills(fin_chunks, clients, sv.t)
                for slot in fin_decode:
                    req = self.slots.release(slot)
                    req.t_done = sv.t
                    clients[slot].current = None
                    if self.obs is not None:
                        self._obs_complete(req, sv.t, slot)
            elif (
                candidate and paged
                and (do_prefill or (mixed and decision.chunk_tokens > 0))
            ):
                # no decoders are running, so a "mixed" round would only
                # carry dead decode lanes — run the plain chunk round (same
                # per-row math and jit shapes, honest prefill timing for
                # the cost model and straggler predictor)
                ph.mark("kv")
                if pairs:
                    self._commit_pairs(pairs)
                    sv.bin_index += 1
                    self._start_chunked_batch(pairs, sv.bin_index, t)
                self.slots.flush_block_tables()
                ph.mark("prepare")
                dt, tok, finished, busy, busy_partial = self._run_chunk_round()
                if active:
                    # decoders froze for the whole preempting chunk round
                    self.prefill_stall_time += dt
                trace.stages.append(
                    StageRecord(
                        kind=StageKind.PREFILL,
                        t_start=t, t_end=t + dt,
                        bin_index=max(sv.bin_index, 0),
                        busy=busy, busy_partial=busy_partial, tokens=tok,
                        level=self.profiler.cost_model.level_for(
                            min(tok, max_cap)
                        ).index,
                    )
                )
                sv.t = t + dt
                if self.obs is not None:
                    self.obs.capacity(
                        self.obs_replica, t, sv.t,
                        self._capacity_classes(busy, busy_partial, dt),
                    )
                self._finish_prefills(finished, clients, sv.t)
            elif do_prefill and candidate:
                ph.mark("kv")
                self._commit_pairs(pairs)
                sv.bin_index += 1
                ph.mark("prepare")
                dt, tok = self._run_prefill_stage(pairs)
                if active:
                    self.prefill_stall_time += dt
                busy = {}
                for client, req in pairs:
                    req.client = client.cid
                    req.prefill_bin = sv.bin_index
                    req.t_prefill_start = t
                    req.t_prefill_end = t + dt
                    req.decoded = 1
                    if self.obs is not None:
                        self._obs_admit(req, t, client.cid, False, 0)
                    self._note_first_token(req, t + dt)
                    busy[client.cid] = req.rid
                trace.stages.append(
                    StageRecord(
                        kind=StageKind.PREFILL,
                        t_start=t, t_end=t + dt,
                        bin_index=sv.bin_index, busy=busy, tokens=tok,
                        level=self.profiler.cost_model.level_for(
                            min(tok, max_cap)
                        ).index,
                    )
                )
                sv.t = t + dt
                if self.obs is not None:
                    self.obs.capacity(
                        self.obs_replica, t, sv.t,
                        self._capacity_classes(busy, {}, dt),
                    )
                # requests with n_decode == 1 finish at prefill
                for client, req in pairs:
                    if self.cfg.eos_id is None and req.n_decode <= 1:
                        req.t_done = sv.t
                        self.slots.release(client.cid)
                        client.current = None
                        if self.obs is not None:
                            self._obs_complete(req, sv.t, client.cid)
            elif active:
                k = self._choose_horizon(decision.horizon)
                ph.mark("kv")
                if paged and cfg.page_reserve != "upfront":
                    # a pinned decode_horizon must run the K it asked for, so
                    # only policy-driven horizons may shrink before evicting
                    k = self._ensure_decode_capacity(
                        k, allow_shrink=cfg.decode_horizon is None
                    )
                    if not self.slots.active_slots:
                        continue   # every decode lane was evicted — re-plan
                if paged:
                    self.slots.flush_block_tables()
                ph.mark("prepare")
                dt, finished, tokens = self._run_decode_stage(k)
                # the stage right after a preempting prefill carries the
                # stall in its first-token gap — it belongs to the burst
                if trace.stages and trace.stages[-1].kind is StageKind.PREFILL:
                    burst = True
                busy = {
                    c.cid: c.current.rid for c in active if c.current is not None
                }
                trace.stages.append(
                    StageRecord(
                        kind=StageKind.DECODE,
                        t_start=t, t_end=t + dt,
                        bin_index=max(sv.bin_index, 0), busy=busy,
                        tokens=tokens, rounds=k, burst=burst,
                    )
                )
                sv.t = t + dt
                if self.obs is not None:
                    self.obs.capacity(
                        self.obs_replica, t, sv.t,
                        self._capacity_classes(busy, {}, dt),
                    )
                for slot in finished:
                    req = self.slots.release(slot)
                    req.t_done = sv.t
                    clients[slot].current = None
                    if self.obs is not None:
                        self._obs_complete(req, sv.t, slot)
            else:
                if candidate:
                    continue  # policy refused but nothing to decode: retry
                nxt = getattr(request_scheduler, "next_arrival", None)
                arrival = nxt() if callable(nxt) else None
                if arrival is not None and arrival > t:
                    sv.t = arrival    # idle gap: fast-forward to the arrival
                    return "ran"      # clock progress counts as progress
                return "idle"
            sv.stages_run += 1
            if self.debug_invariants:
                self._check_invariants()
            return "ran"
        raise RuntimeError(
            "engine livelock: policy kept refusing the only runnable stage"
        )

    def finish_serve(self, validate: bool = True) -> ScheduleTrace:
        """Close the session: merge executor counters into the trace and
        (by default) check the trace invariants. Fleet resume paths skip
        validation — a restored replica's trace only covers post-restore
        stages, so 'every request prefilled exactly once' cannot hold."""
        trace = self._sv.trace
        trace.meta.update(
            mixed_rounds=self.mixed_rounds,
            prefill_stall_time_s=round(self.prefill_stall_time, 6),
            decode_dispatches=self.decode_dispatches,
            preemption_events=self.preemption_events,
            peak_concurrency=self.peak_concurrency,
            offline_deferrals=self.offline_deferrals,
            recomputed_tokens=self.recomputed_tokens,
            migrations_in=self.migrations_in,
            migrations_out=self.migrations_out,
            cached_prefill_tokens=self.cache_hit_tokens,
        )
        if self.cfg.kv_layout == "paged":
            trace.meta.update(
                shared_pages_peak=self.slots.shared_pages_peak,
                cow_copies=self.slots.cow_copies,
            )
        if self.obs is not None:
            self._obs_finish(trace)
        if validate:
            trace.validate()
        return trace

    def serve(
        self,
        requests: Sequence[Request],
        clients: List[ClientState],
        request_scheduler: RequestScheduler,
        iteration_policy: IterationPolicy,
        policy_name: str = "",
    ) -> ScheduleTrace:
        """Serve a request set to completion; returns the execution trace."""
        self.begin_serve(
            requests, clients, request_scheduler, iteration_policy,
            policy_name=policy_name,
        )
        while True:
            status = self.serve_step()
            if status == "done":
                break
            if status == "idle":
                raise RuntimeError("engine deadlock: pending but no candidate")
        return self.finish_serve()

    # ------------------------------------------------------------------ #
    # Checkpoint / restore (fault tolerance)                              #
    # ------------------------------------------------------------------ #
    def state_dict(self) -> Dict[str, Any]:
        # in-flight chunked prefills, as fixed-shape per-slot arrays so the
        # checkpoint tree structure is stable across saves (a mid-chunk slot
        # holds pages but is not yet bound — without this a restore would
        # strand its pages and forget the half-prefilled request)
        chunk_rid = np.full(self.cfg.n_slots, -1, np.int32)
        chunk_done = np.zeros(self.cfg.n_slots, np.int32)
        chunk_resume = np.zeros(self.cfg.n_slots, np.int32)
        chunk_pending = np.full(self.cfg.n_slots, -1, np.int32)
        chunk_cached = np.zeros(self.cfg.n_slots, np.int32)
        for slot, st in self._chunking.items():
            chunk_rid[slot] = st.req.rid
            chunk_done[slot] = st.done
            chunk_resume[slot] = st.resume_emitted
            chunk_pending[slot] = st.resume_pending
            chunk_cached[slot] = st.cached
        return {
            "cache": jax.tree_util.tree_map(np.asarray, self.slots.cache),
            "request_of": [
                (r.rid if r is not None else -1) for r in self.slots.request_of
            ],
            "emitted": list(self.slots.emitted),
            "pending_token": self.pending_token.copy(),
            # straggler-mitigation state: a restored engine must remember it
            # was throttling, or one slow node re-eats the full blast radius
            "budget_shift": self._budget_shift,
            "straggler_events": self.straggler_events,
            "chunk_rid": chunk_rid,
            "chunk_done": chunk_done,
            "chunk_resume": chunk_resume,
            "chunk_pending": chunk_pending,
            "chunk_cached": chunk_cached,
            # preempted-and-requeued rids awaiting recompute (their prefixes
            # live in ``generated``, which the fleet checkpoints separately)
            "resume_rids": np.asarray(sorted(self._resume_rids), np.int32),
        }

    def load_state_dict(self, state: Dict[str, Any], requests_by_rid) -> None:
        """Restore engine state. To *resume* serving afterwards, pass the
        request scheduler only the requests that had not yet started —
        restored in-flight work (bound slots, mid-chunk prefills) continues
        from engine state, and re-queueing it would prefill it twice."""
        self.slots.cache = jax.tree_util.tree_map(
            jnp.asarray, state["cache"]
        )
        # rids arrive as arrays from the checkpoint reader — int() them
        # before hashing (a bound slot used to crash the restore here)
        self.slots.request_of = [
            (requests_by_rid[int(rid)] if int(rid) >= 0 else None)
            for rid in state["request_of"]
        ]
        self.slots.emitted = [int(e) for e in state["emitted"]]
        # np.array (not asarray): checkpoint leaves can be read-only views,
        # and the engine writes pending tokens in place every decode stage
        self.pending_token = np.array(state["pending_token"], dtype=np.int32)
        self._dev_pending = None          # rebuild from the restored host copy
        self._budget_shift = int(state.get("budget_shift", 0))
        self.straggler_events = int(state.get("straggler_events", 0))
        self._resume_rids = {
            int(r) for r in np.asarray(state.get("resume_rids", [])).ravel()
        }
        self._chunking = {}
        chunk_rid = np.asarray(state.get("chunk_rid", []))
        chunk_done = np.asarray(state.get("chunk_done", []))
        chunk_resume = np.asarray(state.get("chunk_resume", []))
        chunk_pending = np.asarray(state.get("chunk_pending", []))
        chunk_cached = np.asarray(state.get("chunk_cached", []))
        for slot, rid in enumerate(chunk_rid):
            if rid >= 0:
                req = requests_by_rid[int(rid)]
                prompt = self._prompt_tokens(req)
                re_cnt = int(chunk_resume[slot]) if chunk_resume.size else 0
                re_pend = int(chunk_pending[slot]) if chunk_pending.size else -1
                if re_cnt > 1:
                    # recompute prompt includes the generated prefix — the
                    # caller must restore ``generated`` before engine state
                    # (the fleet does)
                    prefix = list(self.generated.get(int(rid), ()))[:re_cnt]
                    prompt = np.concatenate(
                        [prompt, np.asarray(prefix[:-1], np.int32)]
                    )
                self._chunking[slot] = _ChunkState(
                    slot=slot, req=req, prompt=prompt,
                    done=int(chunk_done[slot]),
                    resume_emitted=re_cnt, resume_pending=re_pend,
                    cached=int(chunk_cached[slot]) if chunk_cached.size else 0,
                )
        if self.cfg.kv_layout == "paged":
            # the device block table is the durable page-ownership record
            self.slots.sync_from_device()
