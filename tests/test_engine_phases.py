"""Wall-clock phases of a serve step and the KV pool's device-write counter:
every stage record carries all six phases with launch + wait equal to the
stage's measured time; the host owns the block table, so page growth and
release write nothing to the device until a flush, which uploads every
changed row in one write (a release also writes its length once); no
serving program launches on a stale table, and the served tokens match the
dense layout's; the serving programs compile under their own names, and the
always-on phases fire no observability callback."""
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
    Request,
    build_clients,
)
from repro.core.types import StageKind
from repro.data import WorkloadSpec, gsm8k_like_workload
from repro.models.layers import init_params
from repro.models.transformer import TransformerLM
from repro.obs import Observation, Phases
from repro.serving.engine import Engine, EngineConfig, serving_jits
from repro.serving.kv_slots import PagedSlotManager
from repro.serving.sampler import greedy

CFG = ArchConfig(
    name="demo", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=256,
)
SPEC = WorkloadSpec(
    n_requests=10, input_mean=30, input_std=20, output_mean=10,
    output_std=6, output_max=16, input_max=60,
)
PHASES = {"schedule", "kv", "prepare", "launch", "wait", "commit"}
LAYOUTS = {
    "paged-mixed": dict(kv_layout="paged", mixed_schedule=True, num_pages=24),
    "paged-alternating": dict(
        kv_layout="paged", mixed_schedule=False, num_pages=24
    ),
    "dense": dict(kv_layout="dense"),
}


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(CFG)
    params = init_params(jax.random.key(0), model.param_defs())
    return model, params


def _engine(model, params, **kw):
    eng = Engine(
        model, params,
        EngineConfig(
            n_slots=4, max_len=80, prefill_seq_buckets=(32, 64),
            page_size=16, prefill_chunk=16, **kw,
        ),
    )
    eng.profiler.cost_model = CostModel(level_caps=(32, 64, 128))
    return eng


def _serve(eng, policy):
    reqs = gsm8k_like_workload(SPEC, seed=5, known_lengths=True)
    return eng.serve(
        reqs, build_clients(4, reqs, None), GlobalQueueScheduler(reqs), policy
    )


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_every_stage_carries_six_phases(model_and_params, layout):
    eng = _engine(*model_and_params, **LAYOUTS[layout])
    policy = PrefillFirstPolicy() if layout == "dense" else LagrangianPolicy()
    trace = _serve(eng, policy)
    kinds = {s.kind for s in trace.stages}
    assert StageKind.DECODE in kinds
    if layout == "paged-mixed":
        assert StageKind.MIXED in kinds
    else:
        assert StageKind.PREFILL in kinds
    for s in trace.stages:
        assert set(s.host) == PHASES
        assert all(v >= 0.0 for v in s.host.values())
        # the stage's own dispatch-to-results reading is the launch..wait
        # span (speed factor 1)
        assert s.host["launch"] + s.host["wait"] == pytest.approx(
            s.duration, rel=1e-9, abs=1e-12
        )


def test_step_pool_writes_add_up_to_the_pool_counter(model_and_params):
    # the debug invariant check reads the device table at the end of every
    # step, which flushes a release there instead of at the next step's kv
    # phase: count what a serve without it writes
    eng = _engine(
        *model_and_params, **LAYOUTS["paged-mixed"], debug_invariants=False
    )
    pool = eng.slots
    frees = []
    free_pages_of = pool.free_pages_of

    def counting_free(slot):
        frees.append(slot)
        free_pages_of(slot)

    pool.free_pages_of = counting_free
    before = pool.device_writes
    trace = _serve(eng, LagrangianPolicy())
    total = sum(s.pool_writes for s in trace.stages)
    assert total == pool.device_writes - before
    # every request was released; each release writes its length once, and
    # a step uploads the block table at most once
    assert len(frees) >= SPEC.n_requests
    assert len(frees) < total <= len(trace.stages) + len(frees)
    # a pure decode round that crosses no page boundary and finishes no
    # request writes nothing to the pool
    assert any(
        s.kind is StageKind.DECODE and s.pool_writes == 0 for s in trace.stages
    )


def _device_rows(pool):
    """The device block table as it stands, without flushing."""
    return np.array(pool._cache["block_tables"])


def test_page_growth_writes_one_and_release_writes_two(model_and_params):
    """Growth and release edit host rows only; one flush uploads them all
    as one write, a clean flush writes nothing, and a release's one eager
    write is its length. A growth then costs one write and a release two."""
    model, _ = model_and_params
    pool = PagedSlotManager(model, n_slots=2, max_len=64, page_size=16)
    pool.reserve(0, 16)
    pool.bind(0, Request(rid=1, n_prefill=16, n_decode=4))
    pool.flush_block_tables()
    w = pool.device_writes
    pool.flush_block_tables()                      # nothing dirty: no write
    assert pool.device_writes == w
    assert pool.ensure_tokens(0, 16) == 0          # covered: nothing changes
    assert not pool.tables_dirty
    stale = _device_rows(pool)
    assert pool.ensure_tokens(0, 17) == 1          # one more page
    assert pool.ensure_tokens(0, 40) == 1          # and another
    pool.reserve(1, 20)
    assert pool.device_writes == w and pool.tables_dirty
    np.testing.assert_array_equal(_device_rows(pool), stale)
    pool.flush_block_tables()                      # three row changes
    assert pool.device_writes == w + 1 and not pool.tables_dirty
    np.testing.assert_array_equal(_device_rows(pool), pool.host_tables)
    for slot, pages in enumerate(pool.tables):
        assert _device_rows(pool)[slot, : len(pages)].tolist() == pages
    # the upload is a copy: editing the host array leaves the device alone
    pool.host_tables[1, 0] += 1
    assert _device_rows(pool)[1, 0] == pool.tables[1][0]
    pool.host_tables[1, 0] -= 1
    pool.release(0)                                # its length: one write
    assert pool.device_writes == w + 2 and pool.tables_dirty
    assert (_device_rows(pool)[0] >= 0).sum() == 3
    assert int(pool._cache["length"][0]) == 0
    pool.flush_block_tables()
    assert pool.device_writes == w + 3
    assert (_device_rows(pool)[0] == -1).all()


@pytest.fixture(scope="module")
def dense_tokens(model_and_params):
    eng = _engine(*model_and_params, **LAYOUTS["dense"])
    _serve(eng, PrefillFirstPolicy())
    return eng.generated


@pytest.mark.parametrize("layout", ["paged-mixed", "paged-alternating"])
def test_no_program_launches_on_a_stale_block_table(
    model_and_params, dense_tokens, layout
):
    """At every launch of a serving program the host table is clean and the
    table the program gets equals the host rows; the upload happens in the
    step's kv phase (or at the end-of-step debug check), never at launch;
    and the served tokens are the dense layout's."""
    eng = _engine(*model_and_params, **LAYOUTS[layout])
    pool = eng.slots
    launches = []

    def spy(name, program, cache_arg):
        def launch(*args):
            assert not pool.tables_dirty, name
            np.testing.assert_array_equal(
                np.array(args[cache_arg]["block_tables"]), pool.host_tables
            )
            launches.append(name)
            return program(*args)
        return launch

    eng._chunk_jit = spy("chunk_round", eng._chunk_jit, 2)
    eng._mixed_jit = spy("mixed_round", eng._mixed_jit, 2)
    eng._fused_jit = spy("fused_decode", eng._fused_jit, 3)
    uploads = []
    flush = pool.flush_block_tables

    def spied_flush():
        if pool.tables_dirty:
            uploads.append(eng._phases._name)
        flush()

    pool.flush_block_tables = spied_flush
    _serve(eng, PrefillFirstPolicy())
    assert "fused_decode" in launches
    assert ("mixed_round" if layout == "paged-mixed" else "chunk_round") in (
        launches
    )
    assert "kv" in uploads
    assert set(uploads) <= {"kv", "commit"}, uploads
    assert eng.generated == dense_tokens


def test_serving_programs_lower_under_their_names(model_and_params):
    model, params = model_and_params
    chunk, mixed, fused = serving_jits(model, greedy, None)
    j, r, c, mb = 4, 2, 16, 4
    cache = jax.eval_shape(lambda: model.paged_cache_init(j * mb, 16, j, mb))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)   # noqa: E731
    b = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bool_)     # noqa: E731
    texts = {
        "chunk_round": chunk.lower(
            params, i32(r, c), cache, i32(r), i32(r), i32(r)
        ).as_text(),
        "mixed_round": mixed.lower(
            params, i32(j), cache, i32(r, c), i32(r), i32(r), i32(r), b(j),
            i32(j + r), i32(j + r), b(j + r), None,
        ).as_text(),
        "fused_decode": fused.lower(
            2, params, i32(j), cache, b(j), i32(j), i32(j), i32(j), None,
        ).as_text(),
    }
    dense = _engine(model, params, kv_layout="dense")
    texts["dense_prefill"] = dense._prefill_jit.lower(
        params, i32(2, 32), model.cache_init(2, 32), i32(2)
    ).as_text()
    for name, text in texts.items():
        module = text.split("\n", 1)[0]
        assert f"jit_{name}" in module
        assert "unknown" not in module


def test_phases_fire_no_obs_callback(model_and_params):
    calls = []
    Observation.tripwire = staticmethod(lambda: calls.append(1))
    try:
        eng = _engine(*model_and_params, **LAYOUTS["paged-mixed"])
        trace = _serve(eng, LagrangianPolicy())
        assert all(set(s.host) == PHASES for s in trace.stages)
        assert calls == []
    finally:
        Observation.tripwire = None


def test_phases_accumulate_and_ignore_marks_outside_a_step():
    ph = Phases()
    ph.mark("schedule")                            # not started: no effect
    assert ph.stop() == {}
    ph.start()
    ph.mark("schedule", 10.0)
    ph.mark("kv", 10.5)
    ph.mark("schedule", 11.0)                      # a retry adds to schedule
    ph.mark("commit", 12.0)
    out = ph.stop()
    assert out["schedule"] == pytest.approx(1.5)
    assert out["kv"] == pytest.approx(0.5)
    assert set(out) == {"schedule", "kv", "commit"}
    ph.mark("kv")                                  # stopped: no effect
    assert set(ph.stop()) == {"schedule", "kv", "commit"}
