"""Serving launcher — the paper's system, end to end on a real model.

CPU smoke run (a 2-layer toy of the chosen family; the tests' default):

    python -m repro.launch.serve --arch qwen3_8b --policy hybrid \\
        --requests 32 --slots 8

One TPU chip at a configuration's published widths, with the depth cut to
the layers one chip holds (one stage of a pipeline over the full depth):

    python -m repro.launch.serve --arch llama_65b --published

The published run takes the serving path users get: paged KV pool, mixed
prefill+decode rounds, on-demand page reserve, and the hybrid policy
(offline LPT packing plus the Lagrangian iteration policy) over the paper's
GSM8K-like lengths (Table III). The slot count and the page pool are sized
from the compiled programs' ``memory_analysis()`` so that the largest
serving program fits ``HBM_FRACTION`` of the chip's HBM, and every serving
shape is compiled before the timed serve. The served tokens are then
checked against a float32 reference forward (``check_published_serve``);
``chip_smoke.py`` runs this same serve and these same checks.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..configs.base import ArchConfig
from ..core import (
    CostModel,
    GlobalQueueScheduler,
    LagrangianPolicy,
    PrefillFirstPolicy,
    SortingPreemptiveScheduler,
    StaticBacklogScheduler,
    build_clients,
    solve_offline,
)
from ..core.gantt import ascii_gantt
from ..core.types import Request, ScheduleTrace
from ..data import WorkloadSpec, gsm8k_like_workload
from ..data.workload import PAPER_PREDICTOR_NOISE_STD
from ..models.layers import init_params
from ..models.registry import get_model
from ..models.transformer import TransformerLM
from ..serving.engine import Engine, EngineConfig, serving_jits
from ..serving.sampler import greedy
from .mesh import chip_peaks

#: the compile cache's place when JAX_COMPILATION_CACHE_DIR is not set: a
#: fixed directory at the root of the checkout (the path is part of the
#: cache key, so a directory that moves never hits)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

#: layers of each published config that one chip holds next to its KV pool.
#: LLaMA-65B keeps 4 of 80 layers (8 GB of bf16 weights on a 16 GB v5e): one
#: stage of a 20-stage pipeline, embedding and head kept. Only the dense
#: ``TransformerLM`` family serves this way.
ONE_CHIP_LAYERS = {"llama_65b": 4}

#: the most slots the fit tries; it halves from here until the programs fit
MAX_SLOTS = 16

#: tokens a slot holds: prompt plus output of the paper's GSM8K-like
#: traffic (outputs capped at 512) with room to spare
MAX_LEN = 1024

#: share of the chip's HBM the largest serving program (arguments + outputs
#: not aliased to them + temporaries) may take; the rest is headroom for
#: buffers outside any one program (executables, host-to-device transfers)
HBM_FRACTION = 0.9

#: relative RMS error allowed between the engine's bf16 prefill logits and
#: the float32 reference. bf16 keeps 8 significant bits (unit roundoff
#: 2^-8 ≈ 0.4%); rounding every activation, K/V entry and weight of a few
#: layers to it leaves logits about 1% off, so 3% passes bf16 with margin,
#: while fp8 (4 significant bits, 6% per rounding) would fail it.
PREFILL_REL_RMS_TOL = 0.03

#: how far below the float32 reference's best logit the engine's greedy
#: token may score, at each position of a served request, in units of the
#: standard deviation of that position's logits. A bf16 engine picks the
#: reference's argmax or a near-tie: the gap is at most twice the largest
#: per-logit error, about 2 × 5% of the spread at the ~1% RMS error above.
#: A token from another slot's context or from a wrong KV page scores like
#: a random logit, about 4 standard deviations below the best of 32,000.
DECODE_MARGIN_TOL = 0.25


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile. A ``JAX_COMPILATION_CACHE_DIR`` set from outside is JAX's own
    setting and is left alone; otherwise the cache goes to ``CACHE_DIR``.
    Returns the directory in use."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def build_scheduling(mode, reqs, n_slots, cm):
    if mode == "baseline":
        return build_clients(n_slots, reqs, None), GlobalQueueScheduler(reqs), PrefillFirstPolicy()
    if mode == "offline":
        asn = solve_offline(reqs, n_slots, cm).assignment
        clients = build_clients(n_slots, reqs, asn)
        return clients, StaticBacklogScheduler(clients), PrefillFirstPolicy()
    if mode == "online":
        clients = build_clients(
            n_slots, reqs, [[r.rid for r in reqs[j::n_slots]] for j in range(n_slots)]
        )
        return clients, SortingPreemptiveScheduler(clients), LagrangianPolicy()
    if mode == "hybrid":
        asn = solve_offline(reqs, n_slots, cm).assignment
        clients = build_clients(n_slots, reqs, asn)
        return clients, SortingPreemptiveScheduler(clients), LagrangianPolicy()
    raise ValueError(mode)


ENGINE_ARCHS = [a for a in ARCH_IDS if a != "whisper_small"]
# whisper is enc-dec: its prefill consumes frame embeddings the demo engine
# does not synthesize; all decoder-only/recurrent families serve fine.


# --------------------------------------------------------------------------- #
# Published widths on one chip                                                #
# --------------------------------------------------------------------------- #
def published_config(arch: str) -> Tuple[ArchConfig, str]:
    """``arch`` at every published width, its depth cut to the layers one
    chip holds (``ONE_CHIP_LAYERS``). Returns the config and the cut as
    text (``n_layers 80→4``)."""
    if arch not in ONE_CHIP_LAYERS:
        raise ValueError(
            f"{arch}: no one-chip depth; published serves: "
            f"{sorted(ONE_CHIP_LAYERS)}"
        )
    cfg = get_config(arch)
    layers = ONE_CHIP_LAYERS[arch]
    return (
        dataclasses.replace(cfg, n_layers=layers),
        f"n_layers {cfg.n_layers}→{layers}",
    )


def _row_buckets(n_slots: int) -> Tuple[int, ...]:
    """Powers of two below ``n_slots``, then ``n_slots``: the chunk-row
    buckets, covering every count of mid-prefill slots."""
    out, b = [], 1
    while b < n_slots:
        out.append(b)
        b *= 2
    return tuple(out) + (n_slots,)


def _program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    )


def fit_slots_and_pool(
    model: TransformerLM, params, base: EngineConfig, budget_bytes: float,
) -> Tuple[int, int, int]:
    """Largest slot count (halving from ``base.n_slots``) and, for it, the
    largest page pool (full capacity, then 3/4, then 1/2 of it) whose
    largest serving program fits ``budget_bytes``. The programs — fused
    decode at the top horizon, and the mixed and chunk rounds at the top
    row bucket — are compiled against abstract shapes, so nothing is
    allocated. Returns (n_slots, num_pages, program bytes)."""
    chunk, mixed, fused = serving_jits(model, greedy, None)
    mb = -(-base.max_len // base.page_size)
    k, c = base.max_decode_horizon, base.prefill_chunk
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)      # noqa: E731
    flag = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)     # noqa: E731
    n = base.n_slots
    while n >= 1:
        for frac in (1.0, 0.75, 0.5):
            pages = max(int(n * mb * frac), mb)
            cache = jax.eval_shape(
                lambda: model.paged_cache_init(pages, base.page_size, n, mb)
            )
            programs = (
                lambda: fused.lower(
                    k, params, i32(n), cache, flag(n), i32(n), i32(n), i32(n),
                    None,
                ),
                lambda: mixed.lower(
                    params, i32(n), cache, i32(n, c), i32(n), i32(n), i32(n),
                    flag(n), i32(2 * n), i32(2 * n), flag(2 * n), None,
                ),
                lambda: chunk.lower(
                    params, i32(n, c), cache, i32(n), i32(n), i32(n)
                ),
            )
            peak = 0
            for lower in programs:
                peak = max(peak, _program_bytes(lower().compile()))
                if peak > budget_bytes:
                    break
            print(
                f"fit: n_slots={n} num_pages={pages} program bytes {peak} "
                f"vs budget {int(budget_bytes)}"
            )
            if peak <= budget_bytes:
                return n, pages, peak
        n //= 2
    raise RuntimeError(
        f"no slot count fits {int(budget_bytes)} bytes: cut the depth further"
    )


@dataclasses.dataclass
class PublishedServe:
    """What one published-width serve produced, for reporting and checks."""

    cfg: ArchConfig
    reduced: str
    model: TransformerLM
    params: object
    engine: Engine
    requests: List[Request]
    trace: ScheduleTrace
    init_s: float             # weights generated on the device
    compile_warm_s: float     # pool sizing compiles + warm_serving_shapes()
    serve_s: float            # the timed serve, wall clock


def serve_published(
    cfg: ArchConfig,
    reduced: str,
    *,
    n_requests: int = 32,
    seed: int = 0,
    policy: str = "hybrid",
) -> PublishedServe:
    """Serve ``n_requests`` GSM8K-like requests on ``cfg`` through the paged
    + mixed + on-demand path under ``policy``, with at most ``MAX_SLOTS``
    slots of ``MAX_LEN`` tokens, sized against the device's HBM from the
    peaks table (an unknown device kind raises)."""
    hbm_bytes = chip_peaks(jax.devices()[0].device_kind).hbm_bytes
    print(
        f"config {cfg.name}: d_model={cfg.d_model} n_heads={cfg.n_heads} "
        f"n_kv_heads={cfg.n_kv_heads} head_dim={cfg.resolved_head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} n_layers={cfg.n_layers} "
        f"dtype={cfg.dtype}; reduced: {reduced}"
    )
    model = TransformerLM(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(jax.random.key(seed), model.param_defs())
    )
    t1 = time.perf_counter()
    base = EngineConfig(
        n_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
        page_reserve="ondemand", mixed_schedule=True,
    )
    n_slots, num_pages, program_bytes = fit_slots_and_pool(
        model, params, base, HBM_FRACTION * hbm_bytes
    )
    ecfg = dataclasses.replace(
        base, n_slots=n_slots, num_pages=num_pages,
        prefill_req_buckets=_row_buckets(n_slots),
    )
    cm = CostModel(level_caps=(32, 64, 128, 256))
    engine = Engine(model, params, ecfg)
    engine.profiler.cost_model = cm
    engine.warm_serving_shapes()
    t2 = time.perf_counter()
    print(
        f"engine: n_slots={n_slots} max_len={ecfg.max_len} "
        f"page_size={ecfg.page_size} num_pages={num_pages} "
        f"prefill_chunk={ecfg.prefill_chunk} policy={policy}; largest "
        f"program {program_bytes} B of {int(hbm_bytes)} B HBM"
    )
    reqs = gsm8k_like_workload(
        WorkloadSpec(n_requests=n_requests), seed=seed,
        estimate_noise_std=PAPER_PREDICTOR_NOISE_STD,
    )
    clients, sched, pol = build_scheduling(policy, reqs, n_slots, cm)
    t3 = time.perf_counter()
    trace = engine.serve(reqs, clients, sched, pol, policy_name=policy)
    t4 = time.perf_counter()
    return PublishedServe(
        cfg=cfg, reduced=reduced, model=model, params=params, engine=engine,
        requests=reqs, trace=trace, init_s=t1 - t0, compile_warm_s=t2 - t1,
        serve_s=t4 - t3,
    )


def reference_logits(
    cfg: ArchConfig, params, seqs: Sequence[np.ndarray]
) -> List[np.ndarray]:
    """Logits at every position, (len(seq), V) per sequence, from the plain
    float32, cache-free ``TransformerLM.forward`` of the same weights
    (matmuls at full float32 precision; each layer's weights are widened
    inside the layer scan, so only one layer's float32 copy exists at a
    time)."""
    ref = TransformerLM(dataclasses.replace(cfg, dtype="float32"))
    lens = [len(s) for s in seqs]
    tokens = np.zeros((len(seqs), max(lens)), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, : len(s)] = s
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: ref.forward(p, t, remat=False)[0])(
            params, jnp.asarray(tokens)
        )
    logits = np.asarray(logits)
    return [logits[i, :n] for i, n in enumerate(lens)]


def check_published_serve(run: PublishedServe) -> Dict[str, float]:
    """The correctness checks of a published serve; raises RuntimeError on
    the first that fails.

    * every request completed with exactly its token budget;
    * every sampled logit row was finite (the greedy sampler turns a
      non-finite row into token id V, outside the vocabulary);
    * two served requests — the longest output and the last admitted —
      are replayed through the float32 reference with their own tokens
      (teacher forcing):
      - the engine's paged chunked-prefill logits of their prompts are
        within ``PREFILL_REL_RMS_TOL`` of the reference;
      - every token the engine emitted for them, through prefill, mixed
        and fused decode rounds, is within ``DECODE_MARGIN_TOL`` of the
        reference's best logit at its position.
    """
    vocab = run.cfg.vocab_size
    for r in run.requests:
        toks = run.engine.generated.get(r.rid, [])
        if r.t_done is None or len(toks) != r.n_decode:
            raise RuntimeError(
                f"request {r.rid}: {len(toks)} tokens of {r.n_decode}, "
                f"done={r.t_done is not None}"
            )
        if any(not 0 <= t < vocab for t in toks):
            raise RuntimeError(f"request {r.rid}: a non-finite logit row")
    longest = max(run.requests, key=lambda r: (r.n_decode, -r.rid))
    latest = max(
        (r for r in run.requests if r is not longest),
        key=lambda r: (r.t_prefill_start, r.rid),
    )
    prompts = [run.engine._prompt_tokens(r) for r in (longest, latest)]
    streams = [
        np.asarray(run.engine.generated[r.rid], np.int32)
        for r in (longest, latest)
    ]
    ref = reference_logits(
        run.cfg, run.params,
        [np.concatenate([p, g[:-1]]) for p, g in zip(prompts, streams)],
    )
    got = run.engine.prefill_logits(prompts)
    want = np.stack([lg[len(p) - 1] for lg, p in zip(ref, prompts)])
    if not np.isfinite(got).all():
        raise RuntimeError("non-finite prefill logits")
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    # row j of a stream's reference logits is the one its token j came from
    margins, agree = [], 0
    for lg, p, g in zip(ref, prompts, streams):
        rows = lg[len(p) - 1:]
        picked = rows[np.arange(len(g)), g]
        margins.append((rows.max(-1) - picked) / rows.std(-1))
        agree += int((rows.argmax(-1) == g).sum())
    margins = np.concatenate(margins)
    out = {
        "requests_completed": float(len(run.requests)),
        "output_tokens": float(sum(r.n_decode for r in run.requests)),
        "prefill_rel_rms_max": float(rel.max()),
        "prefill_max_abs_err": float(np.abs(got - want).max()),
        "decode_tokens_checked": float(len(margins)),
        "decode_argmax_agreement": agree / len(margins),
        "decode_margin_max": float(margins.max()),
    }
    if rel.max() > PREFILL_REL_RMS_TOL:
        raise RuntimeError(
            f"prefill logits off the float32 reference: relative RMS "
            f"{rel.tolist()} > {PREFILL_REL_RMS_TOL}"
        )
    if margins.max() > DECODE_MARGIN_TOL:
        raise RuntimeError(
            f"served tokens off the float32 reference: a token scores "
            f"{margins.max():.3f} standard deviations below the best logit "
            f"> {DECODE_MARGIN_TOL} (requests {longest.rid}, {latest.rid})"
        )
    return out


def stage_breakdown(trace: ScheduleTrace) -> Dict[str, Dict[str, float]]:
    """Per stage kind: stages, decode rounds, tokens and seconds. A stage's
    seconds run from its dispatch to the host holding its results, so
    their sum against the serve's wall clock splits device-bound time from
    host time between dispatches."""
    out: Dict[str, Dict[str, float]] = {}
    for st in trace.stages:
        row = out.setdefault(
            st.kind.value, {"stages": 0, "rounds": 0, "tokens": 0, "seconds": 0.0}
        )
        row["stages"] += 1
        row["rounds"] += st.rounds
        row["tokens"] += st.tokens
        row["seconds"] += st.duration
    return out


def print_published_checks(run: PublishedServe, checks: Dict[str, float]) -> None:
    """The serve's counters, its stage breakdown and its check results."""
    meta = run.trace.meta
    print(
        f"decode dispatches: {meta['decode_dispatches']}  "
        f"mixed rounds: {meta['mixed_rounds']}  "
        f"preemptions: {meta['preemption_events']}"
    )
    for kind, row in stage_breakdown(run.trace).items():
        print(
            f"stages {kind}: {row['stages']} stages, {row['rounds']} rounds, "
            f"{row['tokens']} tokens, {row['seconds']:.6f} s"
        )
    print(
        f"requests completed: {int(checks['requests_completed'])}  "
        f"output tokens: {int(checks['output_tokens'])}"
    )
    print(
        f"prefill logits vs float32 reference: relative RMS "
        f"{checks['prefill_rel_rms_max']:.6f} (tolerance {PREFILL_REL_RMS_TOL}), "
        f"max abs error {checks['prefill_max_abs_err']:.6f}"
    )
    print(
        f"served tokens vs float32 reference (teacher-forced, "
        f"{int(checks['decode_tokens_checked'])} tokens): worst margin "
        f"{checks['decode_margin_max']:.6f} std (tolerance {DECODE_MARGIN_TOL}), "
        f"argmax agreement {checks['decode_argmax_agreement']:.6f}"
    )


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ENGINE_ARCHS + ["llama_65b"], default="qwen3_8b")
    ap.add_argument("--policy", choices=["baseline", "offline", "online", "hybrid"],
                    default="hybrid")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=None,
                    help="slots of the smoke serve (default 8); --published "
                         "fits them to the chip's HBM")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gantt", action="store_true")
    ap.add_argument("--published", action="store_true",
                    help="serve the published widths on the chip, at the depth "
                         "one chip holds, and check the served tokens against "
                         "a float32 reference")
    args = ap.parse_args()
    if args.published and args.arch not in ONE_CHIP_LAYERS:
        ap.error(f"--published serves {sorted(ONE_CHIP_LAYERS)}, not {args.arch}")
    if args.published and args.slots is not None:
        ap.error("--published fits the slot count to the chip; drop --slots")

    print(f"compile cache: {enable_compile_cache()}")
    if args.published:
        cfg, reduced = published_config(args.arch)
        run = serve_published(
            cfg, reduced, n_requests=args.requests, seed=args.seed,
            policy=args.policy,
        )
        trace, n_slots = run.trace, run.engine.cfg.n_slots
        print(
            f"init {run.init_s:.3f}s compile+warm {run.compile_warm_s:.3f}s "
            f"serve {run.serve_s:.3f}s"
        )
        print_published_checks(run, check_published_serve(run))
    else:
        cfg = get_smoke_config(args.arch)
        model = get_model(cfg)
        params = init_params(jax.random.key(0), model.param_defs())
        spec = WorkloadSpec(
            n_requests=args.requests, input_mean=20, input_std=6,
            output_mean=24, output_std=10, output_max=48, input_max=30,
        )
        reqs = gsm8k_like_workload(spec, seed=args.seed, known_lengths=True)
        cm = CostModel(level_caps=(32, 64, 128, 256))
        n_slots = args.slots or 8
        clients, sched, pol = build_scheduling(args.policy, reqs, n_slots, cm)
        eng = Engine(
            model, params,
            EngineConfig(n_slots=n_slots, max_len=128, prefill_seq_buckets=(32,)),
        )
        eng.profiler.cost_model = cm
        trace = eng.serve(reqs, clients, sched, pol, policy_name=args.policy)
    s = trace.summary()
    print(
        f"policy={args.policy} util={s['utilization'] * 100:.1f}% "
        f"makespan={s['makespan_s']:.2f}s speed={s['generation_speed_tok_s']:.0f} tok/s "
        f"bins={s['num_bins']} decisions p50={s['mean_decision_ms']:.3f}ms"
    )
    if args.gantt:
        print(ascii_gantt(trace, width=90, max_clients=n_slots))


if __name__ == "__main__":
    main()
