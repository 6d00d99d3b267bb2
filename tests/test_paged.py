"""Paged KV cache + chunked prefill: allocator invariants, model-level
dense/paged parity, engine token parity on a mixed-length workload, KV
memory accounting, checkpoint state, and dense-path regressions
(_bucket overflow, _scatter_cache edge shapes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core import (
    BalancedLagrangianPolicy,
    CostModel,
    GlobalQueueScheduler,
    PrefillFirstPolicy,
    build_clients,
)
from repro.core.types import Request
from repro.data import WorkloadSpec, gsm8k_like_workload
from repro.models.layers import init_params
from repro.models.transformer import TransformerLM
from repro.serving.engine import Engine, EngineConfig, _bucket
from repro.serving.kv_slots import BlockAllocator, PagedSlotManager, _scatter_cache

CFG = ArchConfig(
    name="demo", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=256,
)
# mixed prompt lengths: short conversational next to long-document prompts —
# the workload shape the dense row-per-slot layout over-allocates worst on
MIXED_SPEC = WorkloadSpec(
    n_requests=10, input_mean=30, input_std=20, output_mean=10,
    output_std=6, output_max=16, input_max=60,
)
CM = CostModel(level_caps=(32, 64, 128))


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(CFG)
    params = init_params(jax.random.key(0), model.param_defs())
    return model, params


def _engine(model, params, layout, **kw):
    eng = Engine(
        model, params,
        EngineConfig(
            n_slots=4, max_len=80, prefill_seq_buckets=(32, 64),
            kv_layout=layout, **kw,
        ),
    )
    eng.profiler.cost_model = CM
    return eng


# --------------------------------------------------------------------------- #
# BlockAllocator                                                              #
# --------------------------------------------------------------------------- #
def test_allocator_allocate_free_cycle():
    a = BlockAllocator(num_pages=8, page_size=16)
    assert a.pages_for(1) == 1 and a.pages_for(16) == 1 and a.pages_for(17) == 2
    p1 = a.allocate(3)
    p2 = a.allocate(2)
    assert len(set(p1) | set(p2)) == 5          # no page handed out twice
    assert a.num_free == 3 and a.num_used == 5
    a.free(p1)
    assert a.num_free == 6
    with pytest.raises(RuntimeError):
        a.free(p1)                               # double free
    with pytest.raises(RuntimeError):
        a.allocate(7)                            # exhaustion
    a.free(p2)
    assert a.num_free == 8


def test_paged_slot_manager_reserve_release(model_and_params):
    model, _ = model_and_params
    mgr = PagedSlotManager(model, n_slots=4, max_len=64, page_size=16, num_pages=8)
    mgr.reserve(0, 40)                           # 3 pages
    assert mgr.allocator.num_used == 3
    row = np.asarray(mgr.cache["block_tables"][0])
    assert (row[:3] >= 0).all() and (row[3:] == -1).all()
    assert mgr.kv_bytes_in_use() > 0
    mgr.bind(0, Request(rid=0, n_prefill=8, n_decode=4))
    mgr.release(0)
    assert mgr.allocator.num_used == 0
    assert (np.asarray(mgr.cache["block_tables"][0]) == -1).all()
    assert int(mgr.cache["length"][0]) == 0


@pytest.mark.parametrize("carrier", ["export_import", "state_dict"])
def test_grown_row_is_carried_without_an_explicit_flush(
    model_and_params, carrier
):
    """Page growth only edits the host row; migration and checkpoint read
    the device table through the flushing ``cache``, so a row grown just
    before them arrives whole on the other side."""
    model, params = model_and_params
    kw = dict(page_size=16, prefill_chunk=16, num_pages=16)
    eng = _engine(model, params, "paged", **kw)
    req = Request(rid=0, n_prefill=40, n_decode=20)
    clients = build_clients(4, [req], None)
    eng._start_chunked_batch([(clients[0], req)], 0, 0.0)
    eng._run_chunk_round()                        # flushed at launch
    assert eng.slots.ensure_tokens(0, 60) == 1    # 3 → 4 pages, host only
    assert eng.slots.tables_dirty
    grown = list(eng.slots.tables[0])
    assert len(grown) == 4
    eng2 = _engine(model, params, "paged", **kw)
    if carrier == "export_import":
        pages, k, v, length, crc = eng.slots.export_pages(0)
        assert pages == grown and k.shape[1] == 4
        new = eng2.slots.import_pages(1, k, v, length, checksum=crc)
        got = np.asarray(eng2.slots.cache["block_tables"][1])
        assert got[:4].tolist() == new and (got[4:] == -1).all()
    else:
        eng2.load_state_dict(eng.state_dict(), {0: req})
        assert eng2.slots.tables[0] == grown
        assert not eng2.slots.tables_dirty
        np.testing.assert_array_equal(
            eng2.slots.host_tables, eng.slots.host_tables
        )
    assert not eng.slots.tables_dirty
    np.testing.assert_array_equal(
        np.asarray(eng.slots.cache["block_tables"])[0, :4], grown
    )
    eng.slots.check_block_table_mirror()
    eng2.slots.check_block_table_mirror()


# --------------------------------------------------------------------------- #
# Model-level parity: chunked paged prefill + paged decode == dense           #
# --------------------------------------------------------------------------- #
@pytest.mark.slow
def test_paged_chunked_prefill_and_decode_match_dense(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(0)
    lens = [13, 7, 21]
    prompts = [rng.integers(1, CFG.vocab_size, size=n).astype(np.int32) for n in lens]

    n_slots = 4
    dense = model.cache_init(n_slots, 32)
    toks = np.zeros((n_slots, 32), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : len(p)] = p
    logits_d, dense = model.prefill(
        params, jnp.asarray(toks), dense,
        lengths=jnp.asarray(lens + [1], jnp.int32),
    )

    page_size, num_pages, mb = 8, 24, 8
    cache = model.paged_cache_init(num_pages, page_size, n_slots, mb)
    bt = np.full((n_slots, mb), -1, np.int32)
    nxt = 0
    for i, n in enumerate(lens):
        need = -(-(n + 8) // page_size)
        bt[i, :need] = range(nxt, nxt + need)
        nxt += need
    cache["block_tables"] = jnp.asarray(bt)

    chunk = 8
    done = [0] * 3
    logits_p = [None] * 3
    while any(d < n for d, n in zip(done, lens)):
        rows = [i for i in range(3) if done[i] < lens[i]]
        b = len(rows)
        t = np.zeros((b, chunk), np.int32)
        sid = np.zeros(b, np.int32)
        st = np.zeros(b, np.int32)
        cl = np.zeros(b, np.int32)
        for r, i in enumerate(rows):
            n = min(chunk, lens[i] - done[i])
            t[r, :n] = prompts[i][done[i] : done[i] + n]
            sid[r], st[r], cl[r] = i, done[i], n
        lg, cache = model.prefill_chunk(
            params, jnp.asarray(t), cache, jnp.asarray(sid),
            jnp.asarray(st), jnp.asarray(cl),
        )
        for r, i in enumerate(rows):
            done[i] += int(cl[r])
            if done[i] >= lens[i]:
                logits_p[i] = np.asarray(lg[r])

    for i in range(3):
        np.testing.assert_allclose(
            np.asarray(logits_d[i]), logits_p[i], rtol=2e-5, atol=2e-5
        )

    # decode: 4 steps, slot 3 inactive (its paged row must not write)
    active = jnp.asarray([True, True, True, False])
    pend = np.argmax(np.asarray(logits_d)[:3], axis=1).astype(np.int32)
    pend_p = pend.copy()
    dtoks = np.zeros(4, np.int32)
    ptoks = np.zeros(4, np.int32)
    for _ in range(4):
        dtoks[:3], ptoks[:3] = pend, pend_p
        ld, dense = model.decode_step(params, jnp.asarray(dtoks), dense)
        lp, cache = model.decode_step(params, jnp.asarray(ptoks), cache, active=active)
        np.testing.assert_allclose(
            np.asarray(ld)[:3], np.asarray(lp)[:3], rtol=2e-5, atol=2e-5
        )
        pend = np.argmax(np.asarray(ld)[:3], axis=1).astype(np.int32)
        pend_p = np.argmax(np.asarray(lp)[:3], axis=1).astype(np.int32)
        np.testing.assert_array_equal(pend, pend_p)


# --------------------------------------------------------------------------- #
# Engine: paged + chunked serve == dense serve, with less KV memory           #
# --------------------------------------------------------------------------- #
def _serve(eng, seed, policy):
    reqs = gsm8k_like_workload(MIXED_SPEC, seed=seed, known_lengths=True)
    clients = build_clients(4, reqs, None)
    tr = eng.serve(reqs, clients, GlobalQueueScheduler(reqs), policy)
    tr.validate()
    return tr


@pytest.mark.slow
def test_engine_paged_matches_dense_tokens(model_and_params):
    model, params = model_and_params
    eng_d = _engine(model, params, "dense")
    tr_d = _serve(eng_d, 5, PrefillFirstPolicy())
    eng_p = _engine(
        model, params, "paged", page_size=16, prefill_chunk=24, num_pages=16
    )
    tr_p = _serve(eng_p, 5, PrefillFirstPolicy())
    assert eng_d.generated.keys() == eng_p.generated.keys()
    for rid in eng_d.generated:
        assert eng_d.generated[rid] == eng_p.generated[rid], f"rid {rid}"
    # strictly fewer KV bytes than the dense n_slots × max_len layout
    dense_bytes = eng_d.slots.cache["k"].nbytes + eng_d.slots.cache["v"].nbytes
    assert eng_p.slots.kv_bytes_capacity() < dense_bytes
    # all pages returned to the pool at drain
    assert eng_p.slots.allocator.num_free == eng_p.slots.allocator.num_pages
    # chunked prefill really split prompts: some stages carry partial slots
    assert any(s.busy_partial for s in tr_p.stages)


@pytest.mark.slow
def test_engine_paged_lagrangian_chunk_pricing(model_and_params):
    """The Lagrangian policy must serve a valid trace when the candidate is
    priced per chunk (chunk_tokens set) and interleave decode with chunking
    (the alternating-stage path; mixed-step pricing is covered in
    tests/test_mixed_batch.py)."""
    model, params = model_and_params
    eng = _engine(
        model, params, "paged", page_size=16, prefill_chunk=24, num_pages=16,
        mixed_schedule=False,
    )
    tr = _serve(eng, 6, BalancedLagrangianPolicy())
    assert tr.utilization > 0.2
    kinds = [s.kind.value for s in tr.stages]
    assert "prefill" in kinds and "decode" in kinds


@pytest.mark.slow
def test_engine_paged_checkpoint_roundtrip(model_and_params, tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint

    model, params = model_and_params
    eng = _engine(
        model, params, "paged", page_size=16, prefill_chunk=24, num_pages=16
    )
    _serve(eng, 3, PrefillFirstPolicy())
    eng._budget_shift = 2
    eng.straggler_events = 5
    state = eng.state_dict()
    save_checkpoint(tmp_path, 1, state)
    eng2 = _engine(
        model, params, "paged", page_size=16, prefill_chunk=24, num_pages=16
    )
    restored, _ = restore_checkpoint(tmp_path, 1, eng2.state_dict())
    reqs = gsm8k_like_workload(MIXED_SPEC, seed=3, known_lengths=True)
    eng2.load_state_dict(restored, {r.rid: r for r in reqs})
    # straggler-mitigation state survives the round trip (regression: it
    # used to be dropped, so a restored engine forgot it was throttling)
    assert eng2._budget_shift == 2
    assert eng2.straggler_events == 5
    for a, b in zip(
        jax.tree_util.tree_leaves(eng.slots.cache),
        jax.tree_util.tree_leaves(eng2.slots.cache),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # host-side page bookkeeping rebuilt from the device block table
    assert eng2.slots.tables == eng.slots.tables
    assert eng2.slots.allocator.num_free == eng.slots.allocator.num_free


def test_engine_paged_checkpoint_restores_mid_chunk_state(model_and_params):
    """A checkpoint taken while a prompt is half-prefilled must restore the
    chunk cursor and page ownership — otherwise the request is forgotten and
    its pages leak (regression)."""
    model, params = model_and_params
    eng = _engine(
        model, params, "paged", page_size=16, prefill_chunk=16, num_pages=16
    )
    req = Request(rid=0, n_prefill=40, n_decode=4)
    clients = build_clients(4, [req], None)
    eng._start_chunked_batch([(clients[0], req)], 0, 0.0)
    eng._run_chunk_round()                        # done = 16 < 40
    assert eng._chunking[0].done == 16
    state = eng.state_dict()
    eng2 = _engine(
        model, params, "paged", page_size=16, prefill_chunk=16, num_pages=16
    )
    eng2.load_state_dict(state, {0: req})
    assert 0 in eng2._chunking
    assert eng2._chunking[0].done == 16
    assert eng2._chunking[0].req is req
    assert eng2.slots.tables[0] == eng.slots.tables[0]
    assert eng2.slots.allocator.num_free == eng.slots.allocator.num_free


def test_engine_paged_admits_while_chunking(model_and_params):
    """Idle slots must keep admitting new prompts while a long prompt is
    mid-chunk — a prefill stage may carry a finishing short prompt (busy)
    alongside the long one still chunking (busy_partial)."""
    model, params = model_and_params
    reqs = [
        Request(rid=0, n_prefill=60, n_decode=12),   # 3 chunks of 24
        Request(rid=1, n_prefill=10, n_decode=12),
        Request(rid=2, n_prefill=10, n_decode=12),
    ]
    eng = _engine(
        model, params, "paged", page_size=16, prefill_chunk=24, num_pages=20
    )
    clients = build_clients(4, reqs, None)
    tr = eng.serve(reqs, clients, GlobalQueueScheduler(reqs), PrefillFirstPolicy())
    tr.validate()
    assert any(
        s.busy and s.busy_partial for s in tr.stages
    ), "short prompts should finish prefill in a stage the long prompt is still chunking"


def test_engine_dense_checkpoint_keeps_straggler_state(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params, "dense")
    eng._budget_shift = 1
    eng.straggler_events = 3
    state = eng.state_dict()
    eng2 = _engine(model, params, "dense")
    reqs = gsm8k_like_workload(MIXED_SPEC, seed=3, known_lengths=True)
    eng2.load_state_dict(state, {r.rid: r for r in reqs})
    assert eng2._budget_shift == 1
    assert eng2.straggler_events == 3


# --------------------------------------------------------------------------- #
# Pool layout (L, P, bs, KV, D): write / write_token / gather vs NumPy        #
# --------------------------------------------------------------------------- #
L_, P_, BS_, KV_, D_ = 3, 10, 4, 2, 3
# slot 0: pages 7, 2; slot 1: page 5 then an unallocated block; slot 2: none
TABLES = np.array([[7, 2, -1], [5, -1, -1], [-1, -1, -1]], np.int32)


def _random_pool(seed):
    rng = np.random.default_rng(seed)
    return (
        rng.standard_normal((L_, P_, BS_, KV_, D_)).astype(np.float32),
        rng.standard_normal((L_, P_, BS_, KV_, D_)).astype(np.float32),
    )


def _np_write(pool, layer, new, tables, starts, lens):
    """Reference scatter: token t of row b lands at position starts[b] + t,
    in page tables[b, pos // bs] at offset pos % bs, unless it lies past
    lens[b], past the table, or on an unallocated page."""
    out = pool.copy()
    mb = tables.shape[1]
    for b in range(new.shape[0]):
        for t in range(int(lens[b])):
            pos = int(starts[b]) + t
            blk = pos // BS_
            if blk < mb and tables[b, blk] >= 0:
                out[layer, tables[b, blk], pos % BS_] = new[b, t]
    return out


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_cache_write_matches_numpy_scatter(layer):
    from repro.models.cache import paged_cache_write

    k, v = _random_pool(layer)
    rng = np.random.default_rng(10 + layer)
    k_new = rng.standard_normal((3, 5, KV_, D_)).astype(np.float32)
    v_new = rng.standard_normal((3, 5, KV_, D_)).astype(np.float32)
    # row 0 crosses from page 7 into page 2 (positions 2..6); row 1 runs off
    # its only page into an unallocated block (dropped); row 2 owns no page
    starts = np.array([2, 1, 0], np.int32)
    lens = np.array([5, 5, 3], np.int32)
    kw, vw = paged_cache_write(
        jnp.asarray(k), jnp.asarray(v), jnp.int32(layer), jnp.asarray(k_new),
        jnp.asarray(v_new), jnp.asarray(TABLES), jnp.asarray(starts),
        jnp.asarray(lens),
    )
    np.testing.assert_array_equal(np.asarray(kw), _np_write(k, layer, k_new, TABLES, starts, lens))
    np.testing.assert_array_equal(np.asarray(vw), _np_write(v, layer, v_new, TABLES, starts, lens))
    assert (np.asarray(kw)[layer, 2, :3] == k_new[0, 2:]).all()   # page boundary


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_cache_write_token_matches_numpy_scatter(layer):
    from repro.models.cache import paged_cache_write_token

    k, v = _random_pool(20 + layer)
    rng = np.random.default_rng(30 + layer)
    k_new = rng.standard_normal((3, 1, KV_, D_)).astype(np.float32)
    v_new = rng.standard_normal((3, 1, KV_, D_)).astype(np.float32)
    # slot 0 writes the first token of its second page; slot 1 is inactive
    # (its write must drop); slot 2 is active but owns no page (drops too)
    positions = np.array([BS_, 2, 0], np.int32)
    active = np.array([True, False, True])
    kw, vw = paged_cache_write_token(
        jnp.asarray(k), jnp.asarray(v), jnp.int32(layer), jnp.asarray(k_new),
        jnp.asarray(v_new), jnp.asarray(TABLES), jnp.asarray(positions),
        jnp.asarray(active),
    )
    lens = active.astype(np.int32)
    np.testing.assert_array_equal(np.asarray(kw), _np_write(k, layer, k_new, TABLES, positions, lens))
    np.testing.assert_array_equal(np.asarray(vw), _np_write(v, layer, v_new, TABLES, positions, lens))
    changed = np.argwhere((np.asarray(kw) != k).any(axis=(3, 4)))
    assert changed.tolist() == [[layer, 2, 0]]     # one (KV, D) row, nothing else


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_gather_kv_matches_numpy_gather(layer):
    from repro.models.cache import paged_gather_kv

    k, v = _random_pool(40 + layer)
    kg, vg = paged_gather_kv(
        jnp.asarray(k), jnp.asarray(v), jnp.int32(layer), jnp.asarray(TABLES)
    )
    pages = np.maximum(TABLES, 0)                    # unallocated → page 0
    shape = (TABLES.shape[0], TABLES.shape[1] * BS_, KV_, D_)
    np.testing.assert_array_equal(np.asarray(kg), k[layer][pages].reshape(shape))
    np.testing.assert_array_equal(np.asarray(vg), v[layer][pages].reshape(shape))
    # token 5 of slot 0 is offset 1 of its second page
    np.testing.assert_array_equal(np.asarray(kg)[0, 5], k[layer, 2, 1])


# --------------------------------------------------------------------------- #
# Dense-path regressions riding along                                         #
# --------------------------------------------------------------------------- #
def test_bucket_overflow_raises():
    assert _bucket(30, (32, 64)) == 32
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        _bucket(65, (32, 64))


def test_engine_rejects_oversize_prompt(model_and_params):
    """A prompt bigger than the top seq bucket used to be silently truncated
    to buckets[-1] and then overflow the padded token write."""
    model, params = model_and_params
    reqs = [Request(rid=0, n_prefill=100, n_decode=4)]
    eng = _engine(model, params, "dense")
    clients = build_clients(4, reqs, None)
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        eng.serve(reqs, clients, GlobalQueueScheduler(reqs), PrefillFirstPolicy())


def test_scatter_cache_ring_pos_shorter_bucket():
    """Ring 'pos' rows from a shorter prefill bucket must be padded with -1
    (invalid), and rank-1 leaves scattered per batch row."""
    main = {
        "pos": jnp.zeros((4, 8), jnp.int32),
        "length": jnp.zeros((4,), jnp.int32),
    }
    pref = {
        "pos": jnp.asarray([[3, 1], [0, 2]], jnp.int32),   # bucket W=2 < 8
        "length": jnp.asarray([2, 2], jnp.int32),
    }
    out = _scatter_cache(main, pref, jnp.asarray([1, 3], jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(out["pos"][1]), [3, 1, -1, -1, -1, -1, -1, -1]
    )
    np.testing.assert_array_equal(
        np.asarray(out["pos"][3]), [0, 2, -1, -1, -1, -1, -1, -1]
    )
    np.testing.assert_array_equal(np.asarray(out["pos"][0]), np.zeros(8))
    np.testing.assert_array_equal(np.asarray(out["length"]), [0, 2, 0, 2])


def test_scatter_cache_seq_bucket_zero_fills_stale_rows():
    """A shorter seq-bucket prefill must zero the row beyond its prefix so no
    stale K/V from a previous occupant survives."""
    main = {"k": jnp.full((2, 4, 8, 1, 2), 7.0)}          # stale values
    pref = {"k": jnp.ones((2, 2, 4, 1, 2))}               # bucket S=4 < 8
    out = _scatter_cache(main, pref, jnp.asarray([0, 2], jnp.int32))
    k = np.asarray(out["k"])
    assert (k[:, 0, :4] == 1).all() and (k[:, 0, 4:] == 0).all()
    assert (k[:, 2, :4] == 1).all() and (k[:, 2, 4:] == 0).all()
    assert (k[:, 1] == 7).all() and (k[:, 3] == 7).all()   # untouched slots
