"""Prefix caching with refcounted copy-on-write paged KV.

Covers the whole stack: BlockAllocator refcount semantics, the
content-addressed PrefixCacheIndex (chained page hashing, LRU leaf-first
eviction), COW adoption at every divergence point (chunk boundaries and
mid-page, greedy and seeded top-p), a 500-step random share/COW/evict churn
with refcount invariants at every step, migration of shared pages
(checksums preserved, never double-freed), cache-aware pricing through
iteration/offline/hetero, and the shared-prefix workload generator."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core import (
    BalancedLagrangianPolicy,
    CostModel,
    GlobalQueueScheduler,
    build_clients,
)
from repro.core.hetero import hetero_weights, replica_request_weight
from repro.core.iteration import CandidateBatch
from repro.core.offline import request_weights
from repro.core.types import Request
from repro.data import WorkloadSpec, shared_prefix_workload
from repro.models.layers import init_params
from repro.models.transformer import TransformerLM
from repro.serving.engine import Engine, EngineConfig
from repro.serving.kv_slots import (
    BlockAllocator,
    PageIntegrityError,
    PagedSlotManager,
    PrefixCacheIndex,
)
from repro.serving.sampler import TopPSampler

CFG = ArchConfig(
    name="demo", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=256,
)
CM = CostModel(level_caps=(32, 64, 128))


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(CFG)
    params = init_params(jax.random.key(0), model.param_defs())
    return model, params


class _StubModel:
    """Just enough model for a PagedSlotManager: a tiny paged cache."""

    def __init__(self, n_layers=1, kv_heads=1):
        self.n_layers, self.kv_heads = n_layers, kv_heads

    def paged_cache_init(self, num_pages, page_size, n_slots, mb):
        from repro.models.cache import paged_cache_init

        return paged_cache_init(
            self.n_layers, num_pages, page_size, self.kv_heads, 4, n_slots, mb
        )


def _mgr(n_slots=4, max_len=64, page_size=4, num_pages=32, prefix_cache=True,
         model=None):
    return PagedSlotManager(
        model or _StubModel(), n_slots, max_len, page_size, num_pages,
        prefix_cache=prefix_cache,
    )


def _fill_random(mgr, seed):
    """Distinct random K/V in every page, layer and head of the pool."""
    rng = np.random.default_rng(seed)
    for name in ("k", "v"):
        a = mgr.cache[name]
        mgr.cache[name] = jnp.asarray(rng.standard_normal(a.shape), a.dtype)


def _check_all(mgr):
    mgr.allocator.check_consistency()
    mgr.check_block_table_mirror()
    mgr.check_refcounts()


# --------------------------------------------------------------------------- #
# Refcounted BlockAllocator                                                   #
# --------------------------------------------------------------------------- #
def test_allocator_share_release_refcounts():
    a = BlockAllocator(num_pages=8, page_size=16)
    pages = a.allocate(2)
    assert all(a.ref_count(p) == 1 for p in pages)
    a.share(pages)
    assert all(a.ref_count(p) == 2 for p in pages)
    assert a.num_shared() == 2
    assert a.release(pages) == []          # one owner left — nothing freed
    assert a.num_used == 2
    assert sorted(a.release(pages)) == sorted(pages)   # last owner
    assert a.num_used == 0
    with pytest.raises(RuntimeError, match="double free"):
        a.release(pages)
    with pytest.raises(RuntimeError, match="share of free"):
        a.share(pages)
    a.check_consistency()


def test_allocator_reset_multiplicity_is_refcount():
    a = BlockAllocator(num_pages=8, page_size=16)
    a.reset(in_use=[3, 3, 5])              # page 3 shared by two rows
    assert a.ref_count(3) == 2 and a.ref_count(5) == 1
    assert a.num_used == 2 and a.num_free == 6
    a.check_consistency()


# --------------------------------------------------------------------------- #
# PrefixCacheIndex: chained hashing, partial match, leaf-first eviction       #
# --------------------------------------------------------------------------- #
def test_index_full_and_partial_match():
    a = BlockAllocator(num_pages=16, page_size=4)
    idx = PrefixCacheIndex(a, page_size=4)
    toks = np.arange(1, 13, dtype=np.int32)            # 3 full pages
    pages = a.allocate(3)
    assert idx.insert(toks, pages) == 3
    assert idx.insert(toks, pages) == 0                # idempotent republish
    full, partial = idx.match(toks)
    assert full == pages and partial is None
    # diverge inside page 2 (tokens 8..11): first 2 pages full, page 3 is
    # the COW source with 2 matched tokens
    probe = toks.copy()
    probe[10:] = 99
    full, partial = idx.match(probe)
    assert full == pages[:2]
    assert partial == (pages[2], 2)
    # clean miss on the very first page — no full pages, partial inside it
    probe2 = toks.copy()
    probe2[0] = 77
    full, partial = idx.match(probe2)
    assert full == [] and partial is None


def test_index_eviction_is_leaf_first_and_refcount_gated():
    a = BlockAllocator(num_pages=16, page_size=4)
    idx = PrefixCacheIndex(a, page_size=4)
    toks = np.arange(1, 13, dtype=np.int32)
    pages = a.allocate(3)
    idx.insert(toks, pages)
    a.free(pages)                                      # index is sole owner
    # page 0 is the parent of a chain — reclaim(1) must take the leaf
    assert idx.reclaim(1) == 1
    assert len(idx) == 2
    full, _ = idx.match(toks)
    assert full == pages[:2]                           # prefix still serves
    # a page some slot still shares (ref 2) is not evictable
    a.reset()
    idx.invalidate()
    pages = a.allocate(2)
    idx.insert(toks[:8], pages)
    # simulate a slot adoption: pages gain an owner beyond the index
    a.share(pages)
    a.free(pages)                                      # publisher released
    assert idx.reclaimable_pages() == 0                # still co-owned
    assert idx.reclaim(10) == 0
    a.free(pages)                                      # adopter released
    assert idx.reclaimable_pages() == 2
    assert idx.reclaim(10) == 2
    assert a.num_used == 0


# --------------------------------------------------------------------------- #
# COW adoption: every divergence point (page boundary, chunk boundary,       #
# mid-page), via the manager's block-table arithmetic                         #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("div", list(range(1, 16)))
def test_cow_divergence_matrix_manager(div):
    ps = 4
    mgr = _mgr(page_size=ps, num_pages=32)
    prompt = np.arange(1, 17, dtype=np.int32)          # 16 tokens, 4 pages
    mgr.reserve_with_prefix(0, prompt, len(prompt))
    mgr.bind(0, Request(rid=0, n_prefill=16, n_decode=2))
    assert mgr.publish_prefix(0, prompt) == 4
    other = prompt.copy()
    other[div:] = other[div:] + 100                    # diverge at ``div``
    before = mgr.cow_copies
    cached = mgr.reserve_with_prefix(1, other, len(other))
    assert cached == min(div, len(other) - 1)
    n_shared = cached // ps
    # fully matched pages are the publisher's very pages, shared read-only
    assert mgr.tables[1][:n_shared] == mgr.tables[0][:n_shared]
    for p in mgr.tables[1][:n_shared]:
        assert mgr.allocator.ref_count(p) >= 3         # slot0 + slot1 + index
    # everything from the divergence page on is private to the adopter
    assert not set(mgr.tables[1][n_shared:]) & set(mgr.tables[0])
    if cached % ps:
        assert mgr.cow_copies == before + 1            # divergence page copied
    _check_all(mgr)
    # release both slots; the index keeps the published pages alive
    mgr.release(0)
    mgr.free_pages_of(1)
    _check_all(mgr)
    assert mgr.allocator.num_used == 4                 # the index's holds
    assert mgr.prefix_index.clear() == 4
    assert mgr.allocator.num_used == 0


def test_adoption_clamps_to_recompute_last_token():
    # a full-prompt cache hit must still recompute ≥ 1 token: the final
    # token's logits seed the first output token
    mgr = _mgr(page_size=4, num_pages=32)
    prompt = np.arange(1, 17, dtype=np.int32)
    mgr.reserve_with_prefix(0, prompt, len(prompt))
    mgr.bind(0, Request(rid=0, n_prefill=16, n_decode=2))
    mgr.publish_prefix(0, prompt)
    cached = mgr.reserve_with_prefix(1, prompt, len(prompt))
    assert cached == len(prompt) - 1
    _check_all(mgr)


# --------------------------------------------------------------------------- #
# 500-step random share / COW / evict churn (satellite a)                     #
# --------------------------------------------------------------------------- #
def test_refcount_churn_500_steps():
    rng = np.random.default_rng(0)
    ps = 4
    mgr = _mgr(n_slots=6, max_len=32, page_size=ps, num_pages=48)
    heads = [
        rng.integers(1, 200, size=12).astype(np.int32) for _ in range(3)
    ]
    live: dict = {}
    for step in range(500):
        op = rng.random()
        free = [s for s in range(6) if s not in live]
        if op < 0.55 and free:
            slot = int(rng.choice(free))
            head = heads[int(rng.integers(0, 3))]
            tail = rng.integers(200, 250, size=int(rng.integers(1, 16)))
            prompt = np.concatenate([head, tail.astype(np.int32)])
            prompt = prompt[: mgr.max_len]
            try:
                mgr.reserve_with_prefix(slot, prompt, len(prompt))
            except RuntimeError:
                if live:                       # pool exhausted — evict someone
                    victim = int(rng.choice(list(live)))
                    mgr.free_pages_of(victim)
                    del live[victim]
                continue
            live[slot] = prompt
            if rng.random() < 0.7:             # most prompts complete+publish
                mgr.publish_prefix(slot, prompt)
        elif op < 0.75 and live:
            slot = int(rng.choice(list(live)))
            mgr.free_pages_of(slot)
            del live[slot]
        elif op < 0.85 and live:
            slot = int(rng.choice(list(live)))  # decode growth
            try:
                mgr.ensure_tokens(slot, min(len(live[slot]) + 8, mgr.max_len))
            except RuntimeError:
                pass
        else:
            mgr.prefix_index.reclaim(int(rng.integers(1, 5)))
        _check_all(mgr)                        # invariants EVERY step
    for slot in list(live):
        mgr.free_pages_of(slot)
    _check_all(mgr)
    held = len(mgr.prefix_index.held_pages())
    assert mgr.allocator.num_used == held      # only index holds remain
    assert mgr.prefix_index.clear() == held
    assert mgr.allocator.num_used == 0         # refcount-clean pool


# --------------------------------------------------------------------------- #
# Migration of shared pages (satellite b)                                     #
# --------------------------------------------------------------------------- #
def test_export_import_shared_pages_preserves_checksum():
    src = _mgr(page_size=4, num_pages=32)
    dst = _mgr(page_size=4, num_pages=32)
    prompt = np.arange(1, 17, dtype=np.int32)
    src.reserve_with_prefix(0, prompt, len(prompt))
    src.bind(0, Request(rid=0, n_prefill=16, n_decode=2))
    src.publish_prefix(0, prompt)
    cached = src.reserve_with_prefix(1, prompt, len(prompt))
    assert cached > 0                          # slot 1 SHARES slot 0's pages
    pages, k, v, length, crc = src.export_pages(1)
    dst.import_pages(0, k, v, length, checksum=crc)
    # the import landed on fresh private pages — shared-ness never crosses
    assert all(dst.allocator.ref_count(p) == 1 for p in dst.tables[0])
    # freeing the exporter's slot decrements, never double-frees: the
    # publisher and the index still co-own the shared prefix pages
    src.free_pages_of(1)
    _check_all(src)
    src.release(0)
    _check_all(src)
    assert src.allocator.num_used == len(src.prefix_index.held_pages())
    src.prefix_index.clear()
    assert src.allocator.num_used == 0
    _check_all(dst)


def test_export_import_round_trips_kv_bit_exact():
    """Migration under the (L, P, bs, KV, D) pool: the destination's new
    pages hold exactly the source pages' K/V in every layer and head, in
    the slot's page order, and no other destination page changes."""
    stub = _StubModel(n_layers=3, kv_heads=2)
    src = _mgr(page_size=4, num_pages=16, prefix_cache=False, model=stub)
    dst = _mgr(page_size=4, num_pages=16, prefix_cache=False, model=stub)
    _fill_random(src, 1)
    _fill_random(dst, 2)
    dst.reserve(1, 6)                          # dst's new pages are not src's
    src.reserve(2, 10)                         # 3 pages
    src.cache["length"] = src.cache["length"].at[2].set(10)
    before = {n: np.asarray(dst.cache[n]) for n in ("k", "v")}
    pages, k, v, length, crc = src.export_pages(2)
    new = dst.import_pages(0, k, v, length, checksum=crc)
    assert length == 10 and len(new) == len(pages) == 3
    assert set(new).isdisjoint(dst.tables[1])
    for n in ("k", "v"):
        got, want = np.asarray(dst.cache[n]), np.asarray(src.cache[n])
        np.testing.assert_array_equal(got[:, new], want[:, pages])
        others = [p for p in range(16) if p not in new]
        np.testing.assert_array_equal(got[:, others], before[n][:, others])
    assert int(dst.cache["length"][0]) == 10
    _check_all(dst)


def test_copy_page_copies_one_page_in_every_layer_and_head():
    mgr = _mgr(page_size=4, num_pages=8, model=_StubModel(n_layers=3, kv_heads=2))
    _fill_random(mgr, 3)
    before = {n: np.asarray(mgr.cache[n]) for n in ("k", "v")}
    mgr._copy_page(5, 2)
    for n in ("k", "v"):
        want = before[n].copy()
        want[:, 2] = before[n][:, 5]
        np.testing.assert_array_equal(np.asarray(mgr.cache[n]), want)
    assert mgr.cow_copies == 1


def test_import_bit_flip_rejected_pool_untouched():
    src = _mgr(page_size=4, num_pages=32)
    dst = _mgr(page_size=4, num_pages=32)
    prompt = np.arange(1, 17, dtype=np.int32)
    src.reserve_with_prefix(0, prompt, len(prompt))
    src.bind(0, Request(rid=0, n_prefill=16, n_decode=2))
    pages, k, v, length, crc = src.export_pages(0)
    k_bad = k.at[0, 0, 0, 0, 0].add(1.0)       # one flipped element
    used = dst.allocator.num_used
    with pytest.raises(PageIntegrityError):
        dst.import_pages(0, k_bad, v, length, checksum=crc)
    assert dst.allocator.num_used == used      # nothing allocated
    assert dst.tables[0] == []
    _check_all(dst)


def test_double_free_of_shared_page_raises():
    mgr = _mgr(page_size=4, num_pages=32)
    prompt = np.arange(1, 18, dtype=np.int32)  # 17 tokens: 5 pages, 4 full
    mgr.reserve_with_prefix(0, prompt, len(prompt))
    mgr.bind(0, Request(rid=0, n_prefill=17, n_decode=2))
    mgr.publish_prefix(0, prompt)              # partial last page NOT indexed
    pages = list(mgr.tables[0])
    mgr.release(0)                             # frees only the partial page
    # the naive "free the block table twice" bug: the slot's ids are stale —
    # its partial page is already on the free list, so a second release of
    # the row must raise instead of silently stripping the index's holds
    with pytest.raises(RuntimeError, match="double free"):
        mgr.allocator.release(pages)
    mgr.check_refcounts()                      # the raise left state intact
    mgr.prefix_index.clear()
    assert mgr.allocator.num_used == 0


# --------------------------------------------------------------------------- #
# Engine end-to-end: bit-identical streams at every divergence point,        #
# greedy and seeded top-p (satellite c)                                       #
# --------------------------------------------------------------------------- #
def _grouped_requests(prefix_lens, per_group=2, n_prefill=40, n_decode=5):
    # group members are a full pass apart in FCFS order, so a group's first
    # member publishes its prefix before its second member admits
    reqs = []
    rid = 0
    for _ in range(per_group):
        for g, plen in enumerate(prefix_lens):
            reqs.append(
                Request(
                    rid=rid, n_prefill=n_prefill, n_decode=n_decode,
                    prefix_group=g, prefix_len=plen,
                )
            )
            rid += 1
    return reqs


def _serve(model, params, reqs, prefix_cache, sampler=None, **cfg_kw):
    kw = dict(
        n_slots=4, max_len=128, kv_layout="paged", page_size=8,
        prefill_chunk=16, num_pages=128, prefix_cache=prefix_cache,
    )
    kw.update(cfg_kw)
    eng = Engine(
        model, params, EngineConfig(**kw),
        **({"sampler": sampler} if sampler is not None else {}),
    )
    eng.profiler.cost_model = CM
    trace = eng.serve(
        reqs, build_clients(kw["n_slots"], reqs),
        GlobalQueueScheduler(reqs), BalancedLagrangianPolicy(),
    )
    return eng, trace

def test_engine_parity_every_divergence_point(model_and_params):
    model, params = model_and_params
    # divergence at page boundaries (8, 24), chunk boundaries (16, 32),
    # mid-page (5, 13, 27), and a near-full-prompt prefix (39)
    prefix_lens = [5, 8, 13, 16, 24, 27, 32, 39]
    e0, t0 = _serve(model, params, _grouped_requests(prefix_lens), False)
    e1, t1 = _serve(model, params, _grouped_requests(prefix_lens), True)
    assert e0.generated == e1.generated        # bit-identical token streams
    assert e1.cache_hit_tokens > 0
    assert t1.computed_prefill_tokens < t0.computed_prefill_tokens
    # every prompt token is either computed or served from cache
    assert (
        t1.computed_prefill_tokens + e1.cache_hit_tokens
        == t0.computed_prefill_tokens
    )
    assert t1.meta["cached_prefill_tokens"] == e1.cache_hit_tokens
    assert t1.summary()["cached_prefill_tokens"] == e1.cache_hit_tokens
    assert t1.summary()["computed_prefill_tokens"] == t1.computed_prefill_tokens
    # pool ends refcount-clean: all remaining pages are index holds
    e1.slots.check_refcounts()
    held = len(e1.slots.prefix_index.held_pages())
    assert e1.slots.allocator.num_used == held
    assert e1.slots.prefix_index.clear() == held
    assert e1.slots.allocator.num_used == 0


def test_engine_parity_seeded_top_p(model_and_params):
    model, params = model_and_params
    reqs_fn = lambda: _grouped_requests([16, 27], per_group=3)  # noqa: E731
    e0, _ = _serve(
        model, params, reqs_fn(), False, sampler=TopPSampler(top_p=0.9)
    )
    e1, _ = _serve(
        model, params, reqs_fn(), True, sampler=TopPSampler(top_p=0.9)
    )
    assert e0.generated == e1.generated
    assert e1.cache_hit_tokens > 0


def test_dense_layout_unaffected(model_and_params):
    model, params = model_and_params
    reqs = _grouped_requests([16], per_group=2, n_prefill=24, n_decode=4)
    eng = Engine(
        model, params,
        EngineConfig(n_slots=4, max_len=64, kv_layout="dense"),
    )
    eng.profiler.cost_model = CM
    trace = eng.serve(
        reqs, build_clients(4, reqs), GlobalQueueScheduler(reqs),
        BalancedLagrangianPolicy(),
    )
    trace.validate()
    assert eng.cache_hit_tokens == 0
    assert trace.meta["cached_prefill_tokens"] == 0
    # dense prompts share the same group-derived tokens, so a paged
    # cache-on serve of the same workload emits the same streams
    e1, _ = _serve(
        model, params,
        _grouped_requests([16], per_group=2, n_prefill=24, n_decode=4),
        True, max_len=64,
    )
    assert eng.generated == e1.generated


def test_prefix_cache_requires_paged_layout(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="prefix_cache"):
        Engine(
            model, params,
            EngineConfig(kv_layout="dense", prefix_cache=True),
        )


# --------------------------------------------------------------------------- #
# Cache-aware pricing (iteration / offline / hetero)                          #
# --------------------------------------------------------------------------- #
def test_request_uncached_prefill_accounting():
    r = Request(rid=0, n_prefill=100, n_decode=10, prefix_group=1, prefix_len=40)
    assert r.uncached_prefill == 100
    r.cached_prefill = 60
    assert r.uncached_prefill == 40
    r.reset()
    assert r.cached_prefill == 0               # execution state clears
    assert r.prefix_group == 1 and r.prefix_len == 40   # identity survives
    with pytest.raises(ValueError):
        Request(rid=1, n_prefill=10, n_decode=1, prefix_len=11)


def test_candidate_batch_uncached_tokens():
    reqs = [Request(rid=i, n_prefill=50, n_decode=5) for i in range(2)]
    cb = CandidateBatch(requests=reqs, client_ids=[0, 1], cached_tokens=60)
    assert cb.total_prefill_tokens == 100
    assert cb.uncached_prefill_tokens == 40
    cb_over = CandidateBatch(requests=reqs, client_ids=[0, 1], cached_tokens=999)
    assert cb_over.uncached_prefill_tokens == 0


def test_offline_weights_cache_aware_vs_blind():
    reqs = [Request(rid=0, n_prefill=200, n_decode=10)]
    reqs[0].cached_prefill = 150
    aware = request_weights(reqs, CM, 1, include_prefill=True, cache_aware=True)
    blind = request_weights(reqs, CM, 1, include_prefill=True, cache_aware=False)
    assert aware[0] < blind[0]
    assert blind[0] - aware[0] == pytest.approx(
        CM.prefill_time(200) - CM.prefill_time(50)
    )


def test_hetero_weights_take_cached_matrix():
    reqs = [Request(rid=0, n_prefill=100, n_decode=10, n_decode_est=10)]
    cold = replica_request_weight(reqs[0], CM, 4)
    warm = replica_request_weight(reqs[0], CM, 4, cached_prefill=80)
    assert warm < cold
    w_cold = hetero_weights(reqs, [CM, CM], 4)
    w_warm = hetero_weights(
        reqs, [CM, CM], 4, cached_tokens=np.array([[80, 0]])
    )
    assert w_warm[0, 0] < w_cold[0, 0]         # replica 0 is warm
    assert w_warm[0, 1] == pytest.approx(w_cold[0, 1])
    with pytest.raises(ValueError):
        hetero_weights(reqs, [CM, CM], 4, cached_tokens=np.zeros((2, 2)))


# --------------------------------------------------------------------------- #
# Shared-prefix workload generator                                            #
# --------------------------------------------------------------------------- #
def test_shared_prefix_workload_shape():
    spec = WorkloadSpec(n_requests=200, input_mean=60, input_std=20)
    reqs = sorted(
        shared_prefix_workload(spec, seed=3, n_groups=4),
        key=lambda r: r.rid,
    )
    assert len(reqs) == 200
    groups = {}
    for r in reqs:
        assert r.prefix_group is not None and 0 <= r.prefix_group < 4
        assert 0 < r.prefix_len < r.n_prefill
        groups.setdefault(r.prefix_group, []).append(r.prefix_len)
    # one prefix length per group, Zipf skew makes group 0 the hottest
    for plens in groups.values():
        assert len(set(plens)) == 1
    counts = {g: len(v) for g, v in groups.items()}
    assert counts[0] == max(counts.values())
