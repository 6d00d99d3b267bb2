"""Fused on-device multi-step decode: exact token parity with the per-token
baseline (greedy and seeded top-p, dense and paged layouts), mid-horizon stop
handling (budget and EOS), checkpoint at a horizon boundary, horizon pricing,
and the per-horizon cost-model fit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core import (
    CostModel,
    GlobalQueueScheduler,
    LagrangianPolicy,
    PrefillFirstPolicy,
    build_clients,
)
from repro.core.iteration import CandidateBatch, SystemSnapshot
from repro.core.types import Request
from repro.data import WorkloadSpec, gsm8k_like_workload
from repro.models.layers import init_params
from repro.models.transformer import TransformerLM
from repro.serving.engine import Engine, EngineConfig
from repro.serving.profiler import OnlineProfiler
from repro.serving.sampler import GreedySampler, TopPSampler, fold_row_keys, greedy

CFG = ArchConfig(
    name="demo", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=256,
)
# mixed prompt/decode lengths so slots hit their stop conditions at
# different iterations inside a shared horizon
SPEC = WorkloadSpec(
    n_requests=10, input_mean=18, input_std=6, output_mean=12,
    output_std=8, output_max=24, input_max=28,
)
CM = CostModel(level_caps=(32, 64, 128))


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(CFG)
    params = init_params(jax.random.key(0), model.param_defs())
    return model, params


def _engine(model, params, horizon, layout="dense", sampler=greedy, **kw):
    if layout == "paged":
        kw.setdefault("page_size", 16)
        kw.setdefault("prefill_chunk", 24)
        kw.setdefault("num_pages", 16)
    eng = Engine(
        model, params,
        EngineConfig(
            n_slots=4, max_len=64, prefill_seq_buckets=(32,),
            kv_layout=layout, decode_horizon=horizon, **kw,
        ),
        sampler=sampler,
    )
    eng.profiler.cost_model = CM
    return eng


def _serve(eng, seed=0):
    reqs = gsm8k_like_workload(SPEC, seed=seed, known_lengths=True)
    clients = build_clients(4, reqs, None)
    tr = eng.serve(reqs, clients, GlobalQueueScheduler(reqs), PrefillFirstPolicy())
    tr.validate()
    return tr


# --------------------------------------------------------------------------- #
# Token-stream parity: fused K vs per-token baseline                          #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.slow
def test_fused_greedy_matches_per_token(model_and_params, layout):
    model, params = model_and_params
    base = _engine(model, params, horizon=1, layout=layout)
    _serve(base)
    fused = _engine(model, params, horizon=8, layout=layout)
    _serve(fused)
    assert base.generated.keys() == fused.generated.keys()
    for rid in base.generated:
        assert base.generated[rid] == fused.generated[rid], f"rid {rid}"
    # the point of the subsystem: ≤ ⌈1/K⌉ host syncs per decoded token
    # (each dispatch syncs exactly once, at its horizon boundary)
    assert fused.decode_dispatches < base.decode_dispatches
    assert fused.decode_dispatches / fused.decoded_tokens < 0.3


@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.slow
def test_fused_seeded_top_p_matches_per_token(model_and_params, layout):
    model, params = model_and_params
    samp = TopPSampler(top_p=0.95)
    runs = {}
    for k in (1, 8):
        eng = _engine(
            model, params, horizon=k, layout=layout, sampler=samp, sample_seed=3
        )
        _serve(eng)
        runs[k] = eng.generated
    assert runs[1].keys() == runs[8].keys()
    for rid in runs[1]:
        assert runs[1][rid] == runs[8][rid], f"rid {rid}"


@pytest.mark.slow
def test_stream_is_pure_function_of_seed_and_rid(model_and_params):
    """Dense vs paged, K=1 vs K=8, same seed → identical streams; different
    seed → different streams (the (seed, rid, token_index) key contract)."""
    model, params = model_and_params
    samp = TopPSampler(top_p=0.95)
    a = _engine(model, params, horizon=8, layout="dense", sampler=samp, sample_seed=3)
    _serve(a)
    b = _engine(model, params, horizon=4, layout="paged", sampler=samp, sample_seed=3)
    _serve(b)
    c = _engine(model, params, horizon=8, layout="dense", sampler=samp, sample_seed=4)
    _serve(c)
    for rid in a.generated:
        assert a.generated[rid] == b.generated[rid]
    assert any(a.generated[r] != c.generated[r] for r in a.generated)


def test_fused_ring_cache_matches_per_token(model_and_params):
    """Sliding-window (ring cache) dense path through the fused loop."""
    cfg = ArchConfig(
        name="swa", family="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=256, sliding_window=24,
    )
    model = TransformerLM(cfg)
    params = init_params(jax.random.key(1), model.param_defs())
    base = _engine(model, params, horizon=1)
    _serve(base)
    fused = _engine(model, params, horizon=4)
    _serve(fused)
    for rid in base.generated:
        assert base.generated[rid] == fused.generated[rid], f"rid {rid}"


# --------------------------------------------------------------------------- #
# Mid-horizon stops                                                           #
# --------------------------------------------------------------------------- #
def test_slot_stopping_mid_horizon_is_noop_not_early_exit(model_and_params):
    """A short request exhausting its budget mid-horizon must freeze (no KV
    write, no length growth) while its batch-mates keep decoding — and the
    long request's stream must equal the per-token baseline's."""
    model, params = model_and_params

    def run(k):
        reqs = [
            Request(rid=0, n_prefill=8, n_decode=2),    # stops at iteration 1
            Request(rid=1, n_prefill=9, n_decode=14),   # spans two horizons
        ]
        eng = _engine(model, params, horizon=k)
        clients = build_clients(4, reqs, None)
        tr = eng.serve(
            reqs, clients, GlobalQueueScheduler(reqs), PrefillFirstPolicy()
        )
        tr.validate()
        return eng, tr

    base, _ = run(1)
    fused, tr = run(8)
    assert fused.generated[0] == base.generated[0]
    assert fused.generated[1] == base.generated[1]
    assert len(fused.generated[0]) == 2 and len(fused.generated[1]) == 14
    # both requests decoded inside far fewer dispatches than tokens
    decode_stages = [s for s in tr.stages if s.kind.value == "decode"]
    assert len(decode_stages) < 14
    # a fused stage emits fewer tokens than rounds × slots once rid 0 stops
    assert any(s.tokens < s.rounds * len(s.busy) for s in decode_stages)


def test_eos_mid_horizon_stops_stream(model_and_params):
    """With eos_id set, a slot sampling EOS mid-horizon must stop exactly
    there — the stream equals the no-EOS stream truncated after the EOS."""
    model, params = model_and_params
    req = Request(rid=0, n_prefill=8, n_decode=12)
    # reference stream without EOS handling
    base = _engine(model, params, horizon=1)
    clients = build_clients(4, [req], None)
    base.serve([req], clients, GlobalQueueScheduler([req]), PrefillFirstPolicy())
    stream = base.generated[0]
    eos = stream[5]                     # force a stop 6 tokens in
    cut = stream.index(eos)             # first occurrence is where it stops

    req2 = Request(rid=0, n_prefill=8, n_decode=12)
    eng = _engine(model, params, horizon=8, eos_id=int(eos))
    clients2 = build_clients(4, [req2], None)
    eng._run_prefill_stage([(clients2[0], req2)])
    _, finished, _ = eng._run_decode_stage(8)
    assert eng.generated[0] == stream[: cut + 1]
    assert finished == [0]


# --------------------------------------------------------------------------- #
# Checkpoint at a horizon boundary                                            #
# --------------------------------------------------------------------------- #
def test_checkpoint_restore_at_horizon_boundary(model_and_params, tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint

    model, params = model_and_params
    reqs = [
        Request(rid=0, n_prefill=8, n_decode=12),
        Request(rid=1, n_prefill=6, n_decode=12),
    ]
    eng = _engine(model, params, horizon=4)
    clients = build_clients(4, reqs, None)
    eng._run_prefill_stage([(clients[0], reqs[0]), (clients[1], reqs[1])])
    eng._run_decode_stage(4)                      # horizon boundary
    state = eng.state_dict()
    save_checkpoint(tmp_path, 1, state)

    eng._run_decode_stage(4)                      # original continues

    eng2 = _engine(model, params, horizon=4)
    restored, _ = restore_checkpoint(tmp_path, 1, eng2.state_dict())
    eng2.load_state_dict(restored, {r.rid: r for r in reqs})
    assert eng2.slots.emitted == [5, 5, 0, 0]     # 1 prefill + 4 decode tokens
    eng2._run_decode_stage(4)                     # restored continues

    # the restored engine's post-boundary tokens == the original's
    for rid in (0, 1):
        assert eng2.generated[rid] == eng.generated[rid][5:9]
    for a, b in zip(
        jax.tree_util.tree_leaves(eng.slots.cache),
        jax.tree_util.tree_leaves(eng2.slots.cache),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------- #
# Samplers                                                                    #
# --------------------------------------------------------------------------- #
def test_sampler_objects_jit_and_key_threading():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(3, 32)) * 3)
    g = GreedySampler()
    np.testing.assert_array_equal(
        np.asarray(g(logits)), np.argmax(np.asarray(logits), axis=-1)
    )
    base = jax.random.key(0)
    rids = jnp.asarray([7, 7, 9], jnp.int32)
    steps = jnp.asarray([0, 1, 0], jnp.int32)
    keys = fold_row_keys(base, rids, steps)
    t = TopPSampler(top_p=0.9)
    a = np.asarray(t(logits, keys))
    b = np.asarray(jax.jit(t)(logits, keys))      # jit-composable, same draw
    np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="per-row PRNG keys"):
        t(logits)
    # near-degenerate nucleus → the argmax token
    tiny = TopPSampler(top_p=1e-6)
    np.testing.assert_array_equal(
        np.asarray(tiny(logits, keys)), np.argmax(np.asarray(logits), axis=-1)
    )


def test_greedy_marks_non_finite_rows_out_of_vocabulary():
    logits = np.random.default_rng(1).normal(size=(4, 32)).astype(np.float32)
    logits[1, 7] = np.nan
    logits[3, 0] = np.inf
    out = np.asarray(jax.jit(GreedySampler())(jnp.asarray(logits)))
    assert out[1] == 32 and out[3] == 32
    np.testing.assert_array_equal(out[[0, 2]], np.argmax(logits[[0, 2]], axis=-1))


# --------------------------------------------------------------------------- #
# Horizon pricing + per-horizon cost model                                    #
# --------------------------------------------------------------------------- #
def _snap(pending, n_active=4, n_clients=4, n_cand=0):
    cand = [Request(rid=i, n_prefill=4, n_decode=4) for i in range(n_cand)]
    return SystemSnapshot(
        n_clients=n_clients, n_active=n_active, n_idle=n_clients - n_active,
        active_remaining_est=64, pending_requests=pending,
        candidate=CandidateBatch(requests=cand, client_ids=list(range(n_cand))),
        now=0.0,
    )


def test_policy_horizon_pricing():
    pol = LagrangianPolicy()
    cm = CostModel(level_caps=(64,))
    # no pending work → nothing to preempt for → saturate the horizon
    assert pol.decode_horizon(_snap(pending=0), cm, k_max=16) == 16
    # a drained queue but a live candidate (e.g. a long prompt's remaining
    # chunks) is still preemptible work — the horizon must stay priced
    assert pol.decode_horizon(_snap(pending=0, n_cand=2), cm, k_max=16) < 16
    # heavy admission pressure → per-iteration granularity
    k_hot = pol.decode_horizon(_snap(pending=100), cm, k_max=16)
    # dispatch cost dominating the round time → fuse deeper
    cm_slow_dispatch = CostModel(decode_dispatch=0.5, level_caps=(64,))
    k_deep = pol.decode_horizon(_snap(pending=100), cm_slow_dispatch, k_max=16)
    assert k_deep > k_hot
    assert 1 <= k_hot <= k_deep <= 16
    # k_max=1 is the hard per-token cap
    assert pol.decode_horizon(_snap(pending=0), cm, k_max=1) == 1


def test_cost_model_fused_fit_recovers_dispatch():
    true = CostModel(
        prefill_per_token=2e-3, prefill_overhead=5e-3,
        decode_per_token=1e-3, decode_overhead=4e-3, decode_dispatch=3e-3,
        level_caps=(64, 128),
    )
    prefill = [(n, true.prefill_time(n)) for n in (16, 32, 64)]
    decode = [
        (n, k, true.fused_decode_time(n, k))
        for n in (2, 4, 8) for k in (1, 2, 4, 8)
    ]
    fit = CostModel.fit(prefill, decode, level_caps=(64, 128))
    assert fit.decode_dispatch == pytest.approx(3e-3, rel=1e-6)
    assert fit.decode_overhead == pytest.approx(4e-3, rel=1e-6)
    assert fit.decode_per_token == pytest.approx(1e-3, rel=1e-6)
    # single-horizon samples: dispatch not identifiable → prior retained,
    # per-round model still fit (the paper's 2-parameter calibration)
    fit2 = CostModel.fit(
        prefill, [(n, 1, true.fused_decode_time(n, 1)) for n in (2, 4, 8)],
        level_caps=(64, 128), decode_dispatch=7e-3,
    )
    assert fit2.decode_dispatch == pytest.approx(7e-3)
    assert fit2.decode_per_token == pytest.approx(1e-3, rel=1e-6)


def test_profiler_learns_per_horizon_timings():
    prof = OnlineProfiler(initial=CostModel(level_caps=(64, 128)), refit_every=4)
    true = CostModel(
        prefill_per_token=2e-3, prefill_overhead=5e-3,
        decode_per_token=1e-3, decode_overhead=4e-3, decode_dispatch=6e-3,
        level_caps=(64, 128),
    )
    for n, k in ((2, 1), (4, 2), (8, 4), (2, 8), (4, 1), (8, 8)):
        prof.record_prefill(16 * n, true.prefill_time(16 * n))
        prof.record_decode(n, true.fused_decode_time(n, k), rounds=k)
    assert prof.fits >= 1
    assert prof.cost_model.decode_dispatch == pytest.approx(6e-3, rel=1e-3)
    assert prof.cost_model.decode_per_token == pytest.approx(1e-3, rel=1e-3)
