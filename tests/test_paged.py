"""Paged KV cache: allocator/prefix-cache units, the page-granular
reservation regression, paged-vs-dense exact equivalence through the
serving engine (greedy, speculative, mp-sharded), and the Pallas decode
kernel's numerics under the interpreter.

Lean by design (tier-1 is compile-bound on the CPU): the fast subset is the
pure-numpy/jnp units plus the two acceptance-critical tiny-GPT engine
runs (paged-vs-dense equivalence, prefix reuse); every other
engine-compiling test (spec verify, mp sharding, admission backpressure,
the invariant tripwire, the interpreter-run kernel) is slow-marked.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu.incubate.nn.kernels import paged_attention as pa
from paddle_hackathon_tpu.inference import (PagePool, PrefixCache,
                                            ServingEngine, pages_for)
from paddle_hackathon_tpu.inference.paged import NULL_PAGE
from paddle_hackathon_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                             param_sharding_spec)


# ---------------------------------------------------------------- units
def test_pages_for_counts_the_straddling_page():
    """The submit-reservation regression (PR 6 bugfix): the write window
    must be counted by its FINAL ROW index — a reserve narrower than a
    page still straddles a boundary when the committed length sits near
    one, and counting whole-request tokens (ceil(need/P)) undercounts by
    exactly the straddled page."""
    # need=8 fills page 0; the reserve window writes rows [7..11] into
    # page 1 — one page is NOT enough (the undercount corrupted row 7)
    assert pages_for(8, 4, 8) == 2
    assert math.ceil(8 / 8) == 1  # what the token-count reservation gave
    # boundary-exact: window ends on the last row of a page — no extra
    assert pages_for(5, 4, 8) == 1
    assert pages_for(16, 16, 16) == 2
    # sweep: every (need, reserve, P) must cover rows [0, need+reserve-2]
    for P in (4, 8, 16):
        for need in range(1, 40):
            for reserve in range(1, 20):
                n = pages_for(need, reserve, P)
                assert n * P > need + reserve - 2, (need, reserve, P)
                assert (n - 1) * P <= need + reserve - 2, "overcount"


def test_page_pool_alloc_free_refcount():
    pool = PagePool(8, 4)
    assert pool.usable == 7 and pool.free == 7 and pool.allocated == 0
    a = pool.alloc(3)
    assert len(a) == 3 and NULL_PAGE not in a
    assert pool.allocated == 3 and pool.free == 4
    pool.incref(a[0])
    assert pool.refcount(a[0]) == 2
    pool.decref(a)
    assert pool.refcount(a[0]) == 1 and pool.allocated == 1
    pool.decref(a[0])
    assert pool.allocated == 0 and pool.free == 7
    with pytest.raises(ValueError):
        pool.decref(a[0])            # double free
    with pytest.raises(ValueError):
        pool.incref(a[1])            # incref of freed page
    with pytest.raises(ValueError):
        pool.decref(NULL_PAGE)       # the null page is never allocated


def test_page_pool_exhaustion_and_cow():
    pool = PagePool(4, 4)            # 3 usable
    a = pool.alloc(3)
    assert pool.alloc(1) is None     # exhausted: caller may evict+retry
    # exclusive page: cow is a no-op
    pg, forked = pool.cow(a[0])
    assert pg == a[0] and not forked
    # shared page: fork trades our ref for a fresh page... but the pool
    # is full, so cow reports failure and keeps the original ref
    pool.incref(a[1])
    assert pool.cow(a[1]) is None
    assert pool.refcount(a[1]) == 2
    pool.decref(a[2])                # make room
    pg, forked = pool.cow(a[1])
    assert forked and pg != a[1]
    assert pool.refcount(a[1]) == 1 and pool.refcount(pg) == 1


def test_prefix_cache_match_insert_evict():
    pool = PagePool(16, 4)
    cache = PrefixCache(pool)
    prompt = np.arange(11, dtype=np.int32)       # 2 full pages + tail 3
    pages = pool.alloc(3)
    cache.insert(prompt, pages, n_full=2)
    assert len(cache) == 2
    assert pool.refcount(pages[0]) == 2          # slot ref + cache ref
    # exact-prefix match is capped at (len-1)//P full pages: the engine
    # must re-prefill at least the last prompt token for logits
    hit = cache.match(prompt)
    assert hit == pages[:2]
    assert pool.refcount(pages[0]) == 3          # matched ref for caller
    hits = [hit]
    hits.append(cache.match(np.arange(9, dtype=np.int32)))
    assert hits[-1] == pages[:2]
    hits.append(cache.match(np.arange(8, dtype=np.int32)))
    assert hits[-1] == pages[:1]
    # diverging second page: only the first matches
    other = prompt.copy()
    other[5] += 1
    hits.append(cache.match(other))
    assert hits[-1] == pages[:1]
    for h in hits:
        pool.decref(h)
    # eviction only reclaims leaves nobody else references
    pool.decref(pages)                           # slot frees
    assert cache.cached_only() == 2              # pages[2] went back free
    assert cache.evict(5) == 2                   # leaf-then-parent
    assert len(cache) == 0 and pool.allocated == 0


def test_cached_only_excludes_pinned_subtrees():
    """Concurrent-prefill insert collision: two slots prefill
    overlapping prompts at once (neither hits), the longer one's insert
    hangs its novel tail page under the shorter one's registered nodes.
    Those ancestors are refcount-1 but UNEVICTABLE while the tail's slot
    lives — cached_only must not promise them to the admission guard."""
    pool = PagePool(16, 8)
    cache = PrefixCache(pool)
    pA = pool.alloc(2)
    prompt_a = np.arange(16, dtype=np.int32)
    cache.insert(prompt_a, pA, 2)
    pB = pool.alloc(3)                       # B prefilled privately
    prompt_b = np.concatenate(
        [prompt_a, np.arange(8, dtype=np.int32) + 90])
    cache.insert(prompt_b, pB, 3)            # first-wins: adopts pB[2] only
    assert len(cache) == 3
    assert pool.refcount(pB[0]) == 1         # loser pages stay private
    pool.decref(pA)                          # A's slot frees
    assert cache.cached_only() == 0          # pinned under B's live tail
    assert cache.evict(5) == 0
    pool.decref(pB)                          # B frees (pB[0:2] go free)
    assert cache.cached_only() == 3
    assert cache.evict(5) == 3
    assert pool.allocated == 0


def test_prefix_cache_drop_releases_everything():
    pool = PagePool(8, 4)
    cache = PrefixCache(pool)
    pages = pool.alloc(2)
    cache.insert(np.arange(8, dtype=np.int32), pages, n_full=2)
    pool.decref(pages)
    assert pool.allocated == 2                   # cache-held only
    assert cache.drop() == 2
    assert pool.allocated == 0 and len(cache) == 0


def test_paged_write_straddles_page_boundary():
    """One scatter writes a window that spans two physical pages."""
    P, H, D = 4, 2, 8
    pool = jnp.zeros((4, P, H, D), jnp.float32)
    pt = jnp.asarray([[2, 1, 0]], jnp.int32)     # logical rows 0-7 live
    vals = jnp.asarray(np.arange(3 * H * D, dtype=np.float32)
                       .reshape(1, 3, H, D))
    out = pa.paged_write(pool, vals, pt, jnp.asarray([3], jnp.int32))
    out = np.asarray(out)
    # rows 3 -> page 2 row 3; rows 4,5 -> page 1 rows 0,1
    np.testing.assert_array_equal(out[2, 3], np.asarray(vals)[0, 0])
    np.testing.assert_array_equal(out[1, 0], np.asarray(vals)[0, 1])
    np.testing.assert_array_equal(out[1, 1], np.asarray(vals)[0, 2])
    assert np.all(out[3] == 0)                   # untouched page


def test_paged_attention_ref_matches_dense_composition():
    """The jnp reference path IS the dense static-cache math (same
    einsums, mask, softmax) behind a gather — checked against a direct
    numpy recomputation at ragged per-slot lengths."""
    rng = np.random.RandomState(0)
    B, P, H, D, maxp = 3, 4, 2, 8, 4
    N = 1 + B * maxp
    k_pool = jnp.zeros((N, P, H, D), jnp.float32)
    v_pool = jnp.zeros((N, P, H, D), jnp.float32)
    pt = jnp.asarray(np.arange(1, N).reshape(B, maxp).astype(np.int32))
    lengths = np.asarray([5, 13, 0], np.int32)
    hist_k = rng.randn(B, maxp * P, H, D).astype(np.float32)
    hist_v = rng.randn(B, maxp * P, H, D).astype(np.float32)
    for b, L in enumerate(lengths):
        if L:
            z = jnp.asarray([0], jnp.int32)
            k_pool = pa.paged_write(k_pool, jnp.asarray(hist_k[b:b + 1, :L]),
                                    pt[b:b + 1], z)
            v_pool = pa.paged_write(v_pool, jnp.asarray(hist_v[b:b + 1, :L]),
                                    pt[b:b + 1], z)
    q = jnp.asarray(rng.randn(B, 1, H, D).astype(np.float32))
    kc = jnp.asarray(rng.randn(B, 1, H, D).astype(np.float32))
    vc = jnp.asarray(rng.randn(B, 1, H, D).astype(np.float32))
    lens_j = jnp.asarray(lengths)
    k_pool = pa.paged_write(k_pool, kc, pt, lens_j)
    v_pool = pa.paged_write(v_pool, vc, pt, lens_j)
    out = np.asarray(pa.paged_attention_ref(q, k_pool, v_pool, pt, lens_j))
    for b in range(B):
        L = int(lengths[b])
        kb = np.concatenate([hist_k[b, :L], np.asarray(kc)[b]], 0)
        vb = np.concatenate([hist_v[b, :L], np.asarray(vc)[b]], 0)
        logits = np.einsum("he,the->ht", np.asarray(q)[b, 0], kb)
        logits /= math.sqrt(D)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(out[b, 0], np.einsum("ht,the->he", p, vb),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_decode_kernel_matches_ref_under_interpreter():
    """The Pallas width-1 decode kernel (grid-level page gather + online
    softmax) against the reference path, run under the Pallas
    interpreter on CPU."""
    rng = np.random.RandomState(1)
    B, P, H, D, maxp = 2, 8, 2, 16, 3
    N = 1 + B * maxp
    pt = jnp.asarray(np.arange(1, N).reshape(B, maxp).astype(np.int32))
    lengths = jnp.asarray([11, 0], jnp.int32)
    k_pool = jnp.asarray(rng.randn(N, P, H, D).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(N, P, H, D).astype(np.float32))
    q = jnp.asarray(rng.randn(B, 1, H, D).astype(np.float32))
    ref = pa.paged_attention_ref(q, k_pool, v_pool, pt, lengths)
    out = pa.paged_attention_decode(q, k_pool, v_pool, pt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------- engines
def _model(num_layers=2):
    paddle.seed(3)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=num_layers,
                    num_heads=4, max_position_embeddings=128,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompts(k, lens=(6, 9, 5, 11)):
    rs = np.random.RandomState(5)
    return [rs.randint(0, 128, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(k)]


def test_paged_engine_token_exact_vs_dense_and_no_leak():
    """The tentpole acceptance: paged greedy decode is token-exact
    against the dense engine (page-boundary-unaligned prompt lengths,
    chunked prefill, multi-step decode window), requests straddle page
    boundaries mid-flight, and the pool drains back to 0 allocated."""
    m = _model()
    prompts = _prompts(4)
    dense = ServingEngine(m, max_slots=4, max_len=64, chunk=4,
                          auto_run=False)
    reqs = [dense.submit(p, 8) for p in prompts]
    dense.run_until_idle()
    refs = [r.result() for r in reqs]

    # page_size=8 with 5..11-token prompts + chunk-4 windows: prefill
    # chunks and the decode window straddle page boundaries constantly
    eng = ServingEngine(m, max_slots=4, max_len=64, chunk=4,
                        auto_run=False, cache_mode="paged", page_size=8)
    reqs = [eng.submit(p, 8) for p in prompts]
    eng.run_until_idle()
    for r, ref in zip(reqs, refs):
        np.testing.assert_array_equal(r.result(), ref)
    # full footprint reserved at admit: pages_for(need, chunk, 8) each
    for i in range(4):
        assert len(eng._slot_pages[i]) == 0      # released on finish
    assert eng.kv_pages_in_use == len(eng._prefix.pages)
    eng.drop_prefix_cache()
    assert eng.kv_pages_in_use == 0              # the leak assert
    assert eng.stats["tokens"] == dense.stats["tokens"]

    # straddle regression (the submit bugfix): a request whose committed
    # length fills its last page exactly still has table pages for the
    # in-flight window rows past it — prompt 4 + new 4 = need 8 = one
    # full page at page_size=8, reserve(chunk)=4 writes rows [7..11)
    p = np.arange(4, dtype=np.int32) + 7
    ref = dense.submit(p, 4)
    dense.run_until_idle()
    req = eng.submit(p, 4)
    eng.run_until_idle()
    np.testing.assert_array_equal(req.result(), ref.result())
    dense.shutdown()
    eng.shutdown()


def test_prefix_cache_skips_reprefill_and_stays_exact():
    """Second request sharing a page-aligned prompt prefix maps the
    cached pages (refcounted) and prefills ONLY the suffix — fewer
    prefill ticks, identical tokens."""
    m = _model()
    rs = np.random.RandomState(7)
    prompt = rs.randint(0, 128, (21,)).astype(np.int32)
    eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                        auto_run=False, cache_mode="paged", page_size=8)
    r1 = eng.submit(prompt, 6)
    eng.run_until_idle()
    ticks1 = eng.stats["ticks"]
    assert eng.stats["prefix_hit_tokens"] == 0
    assert len(eng._prefix) == 2                 # 21 tokens = 2 full pages

    r2 = eng.submit(prompt, 6)                   # identical prompt
    eng.run_until_idle()
    ticks2 = eng.stats["ticks"] - ticks1
    np.testing.assert_array_equal(r2.result(), r1.result())
    assert eng.stats["prefix_hit_tokens"] == 16  # 2 pages skipped
    assert 0 < eng.stats["prefix_hit_rate"] < 1
    # 16 of 21 prompt tokens skipped: 2 prefill ticks (5 tokens) vs 6
    assert ticks2 < ticks1

    # a prompt diverging inside page 2 reuses only page 1
    p3 = prompt.copy()
    p3[12] = (p3[12] + 1) % 128
    hits_before = eng.stats["prefix_hit_tokens"]
    r3 = eng.submit(p3, 4)
    eng.run_until_idle()
    assert r3.done
    assert eng.stats["prefix_hit_tokens"] - hits_before == 8
    eng.shutdown()


@pytest.mark.slow
def test_paged_admission_queues_until_pages_free():
    """Page-aware admission control: a free SLOT is not capacity — the
    queue head waits until the pool can hold its footprint, then admits
    (no deadlock, FIFO preserved, everything completes)."""
    m = _model()
    prompts = _prompts(4)
    # pool of 8 usable pages; each request footprints 2-3 pages at
    # page_size=8 (need 13-19 rows + chunk-4 reserve) — 4 slots exist
    # but only ~3 requests' pages fit at once
    eng = ServingEngine(m, max_slots=4, max_len=64, chunk=4,
                        auto_run=False, cache_mode="paged", page_size=8,
                        num_pages=9, prefix_cache=False)
    reqs = [eng.submit(p, 8) for p in prompts]
    occupied = []
    for _ in range(200):
        if not eng.step():
            break
        occupied.append(sum(s.req is not None for s in eng._slots))
    assert all(r.done for r in reqs)
    assert max(occupied) < 4                     # never all 4 slots live
    assert eng.kv_pages_in_use == 0
    eng.shutdown()


def test_admission_never_flushes_cache_futilely():
    """An unadmittable FIFO head must NOT evict the prefix cache unless
    eviction actually covers its shortfall — flushing a hot system
    prompt while still not admitting would trade future hits for
    nothing.  Host-only: no tick runs, so nothing compiles."""
    m = _model()
    eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                        auto_run=False, cache_mode="paged", page_size=8,
                        num_pages=9)                 # 8 usable pages
    pinned = eng._pool.alloc(6)                      # live-slot stand-in
    cached = eng._pool.alloc(2)
    eng._prefix.insert(np.arange(16, dtype=np.int32), cached, 2)
    eng._pool.decref(cached)                         # cache-only now
    assert eng._prefix.cached_only() == 2
    # tokens disjoint from the cached prompt: no accidental prefix hit
    req = eng.submit(np.arange(9, dtype=np.int32) + 50, 8)  # 3 pages
    eng._admit()
    assert eng._slots[0].req is None                 # 0 free + 2 < 3
    assert len(eng._prefix) == 2                     # cache untouched
    eng._pool.decref(pinned[:1])                     # 1 free + 2 == 3
    eng._admit()
    assert eng._slots[0].req is req                  # admitted...
    assert len(eng._prefix) == 0                     # ...by evicting


def test_submit_rejects_footprint_larger_than_pool():
    m = _model()
    eng = ServingEngine(m, max_slots=2, max_len=64, chunk=4,
                        auto_run=False, cache_mode="paged", page_size=8,
                        num_pages=3)
    with pytest.raises(ValueError, match="KV pages"):
        eng.submit(np.arange(20, dtype=np.int32), 20)
    eng.shutdown()


@pytest.mark.slow
def test_paged_spec_decode_token_exact_vs_dense():
    """Speculative draft-and-verify over the paged cache: the K+1-wide
    verify window rewrites [length, length+K] through the page table
    (boundary straddles included) and stays token-exact vs the dense
    engine — the rollback-survives-indirection acceptance."""
    m = _model()
    rs = np.random.RandomState(9)
    base = rs.randint(0, 128, (8,)).astype(np.int32)
    prompts = [np.tile(base, 3) for _ in range(2)]  # repeats: ngram fires
    dense = ServingEngine(m, max_slots=2, max_len=96, chunk=4,
                          auto_run=False)
    reqs = [dense.submit(p, 12) for p in prompts]
    dense.run_until_idle()
    refs = [r.result() for r in reqs]
    dense.shutdown()

    # spec_k=4 > chunk=4 - 1: reserve is spec-width-driven, and with
    # page_size=8 the verify window [length, length+5) straddles pages
    eng = ServingEngine(m, max_slots=2, max_len=96, chunk=4,
                        auto_run=False, cache_mode="paged", page_size=8,
                        spec_k=4)
    reqs = [eng.submit(p, 12) for p in prompts]
    eng.run_until_idle()
    for r, ref in zip(reqs, refs):
        np.testing.assert_array_equal(r.result(), ref)
    assert eng.stats["spec_ticks"] > 0           # speculation engaged
    # prefix hit + spec together: the skipped prompt rows are replayed
    # into the drafter's mirror at admit, and decode stays token-exact
    r3 = eng.submit(prompts[0], 12)
    eng.run_until_idle()
    np.testing.assert_array_equal(r3.result(), refs[0])
    assert eng.stats["prefix_hit_tokens"] > 0
    eng.drop_prefix_cache()
    assert eng.kv_pages_in_use == 0
    eng.shutdown()


@pytest.mark.slow
def test_mp_sharded_paged_engine_parity():
    """TP-sharded paged serving: the page pools shard heads on 'mp'
    (parallel/api.py page_pool_sharding), batch replicates — same
    tokens as the unsharded model's generate()."""
    from paddle_hackathon_tpu import parallel
    from paddle_hackathon_tpu.core.tensor import Tensor

    m = _model()
    prompts = _prompts(2)
    refs = [np.asarray(m.generate(Tensor(jnp.asarray(p[None, :])),
                                  max_new_tokens=8,
                                  temperature=0.0).numpy())[0]
            for p in prompts]
    mesh = parallel.create_mesh({"dp": 2, "mp": 2},
                                devices=jax.devices()[:4])
    try:
        parallel.shard_params(m, mesh, rule=param_sharding_spec)
        assert m._param_mesh() is not None
        eng = ServingEngine(m, max_slots=4, max_len=64, chunk=4,
                            auto_run=False, cache_mode="paged",
                            page_size=8)
        reqs = [eng.submit(p, 8) for p in prompts]
        eng.run_until_idle()
        for r, ref in zip(reqs, refs):
            np.testing.assert_array_equal(r.result(), ref)
        eng.shutdown()
    finally:
        parallel.set_mesh(None)


@pytest.mark.slow
def test_write_window_invariant_tripwire():
    """A refcount bug that maps a SHARED page under a slot's write
    window must fail the tick loudly (corrupt-KV tripwire), not serve."""
    m = _model()
    eng = ServingEngine(m, max_slots=1, max_len=64, chunk=4,
                        auto_run=False, cache_mode="paged", page_size=8)
    req = eng.submit(np.arange(6, dtype=np.int32), 8)
    assert eng.step()
    # simulate the bug: alias the slot's current write-window page into
    # the prefix cache (refcount 2) — the next tick must refuse
    pg = int(eng._page_tables[0, int(eng._lengths[0]) // 8])
    eng._pool.incref(pg)
    try:
        with pytest.raises(RuntimeError, match="shared page"):
            eng.step()
    finally:
        eng._pool.decref(pg)
        req.error = RuntimeError("aborted by test")
        req._event.set()
