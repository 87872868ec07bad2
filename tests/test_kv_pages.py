"""Host-side KV page pool + prefix index (``models/kv_pages.py``):
allocation, chained-hash prefix matching, refcounts, LRU eviction —
the accounting layer under the paged ``ContinuousBatcher`` (its
device-side exactness is locked by ``tests/test_serving.py``)."""

import numpy as np
import pytest

from tensorflowonspark_tpu.models.kv_pages import KVPagePool


def _p(*toks):
    return np.asarray(toks, np.int32)


def test_validation():
    with pytest.raises(ValueError, match="total_pages"):
        KVPagePool(0, 8)
    with pytest.raises(ValueError, match="power of two"):
        KVPagePool(4, 6)
    pool = KVPagePool(4, 8)
    with pytest.raises(ValueError, match="bad lease"):
        pool.admit(_p(), 4)
    with pytest.raises(ValueError, match="bad lease"):
        pool.admit(_p(1, 2, 3), 2)     # total < prompt


def test_miss_commit_release_then_hit_shares_pages():
    pool = KVPagePool(8, 4)
    prompt = np.arange(10, dtype=np.int32)      # 2 full pages + tail 2
    a = pool.admit(prompt, 14)                  # 4 logical pages
    assert a is not None and a.n_shared == 0 and a.outcome == "miss"
    assert a.tail_start == 0 and len(a.page_ids) == 4
    assert pool.free_pages() == 4
    pool.commit(a)
    assert pool.stats()["miss"] == 1
    b = pool.admit(prompt, 14)
    assert b.outcome == "hit" and b.n_shared == 2 and b.tail_start == 8
    assert b.page_ids[:2] == a.page_ids[:2], "prefix pages not shared"
    assert set(b.page_ids[2:]).isdisjoint(a.page_ids), \
        "tail pages must be private"
    pool.commit(b)
    assert pool.stats()["hit"] == 1
    pool.release(a)
    # b still holds the shared pages: they must not become evictable
    assert pool.cached_pages() == 0
    pool.release(b)
    # all pages back (2 indexed ones parked in the LRU, still cached)
    assert pool.free_pages() == 8 and pool.cached_pages() == 2
    pool.release(b)                             # idempotent
    assert pool.free_pages() == 8


def test_exact_multiple_prompt_never_shares_its_last_page():
    """A prompt of exactly k full pages caps its match at k-1: at least
    one token must be re-run for the first generated token's logits,
    and a shared page is read-only."""
    pool = KVPagePool(8, 4)
    prompt = np.arange(8, dtype=np.int32)       # exactly 2 pages
    a = pool.admit(prompt, 10)
    pool.commit(a)
    b = pool.admit(prompt, 10)
    assert b.n_shared == 1 and b.tail_start == 4
    assert b.outcome == "hit"                   # all SHAREABLE pages hit


def test_mid_page_divergence_is_copy_on_write():
    pool = KVPagePool(12, 4)
    A = np.arange(12, dtype=np.int32)
    B = A.copy()
    B[6] = 99                                   # diverges inside page 2
    a = pool.admit(A, 14)
    pool.commit(a)
    b = pool.admit(B, 14)
    assert b.outcome == "partial" and b.n_shared == 1
    assert b.page_ids[0] == a.page_ids[0]
    assert b.page_ids[1] != a.page_ids[1], "divergent page must be a copy"
    pool.commit(b)
    # the original chain is intact: A still fully hits
    c = pool.admit(A, 14)
    assert c.outcome == "hit" and c.page_ids[:2] == a.page_ids[:2]


def test_chained_hash_blocks_suffix_only_matches():
    """Page 2 of prompt A must not match page 2 of prompt B when their
    page-1 contents differ, even if the page-2 TOKENS are identical —
    the chain key digests the whole prefix."""
    pool = KVPagePool(8, 4)
    tail = [7, 7, 7, 7]
    a = pool.admit(_p(1, 2, 3, 4, *tail, 9), 12)
    pool.commit(a)
    b = pool.admit(_p(5, 6, 7, 8, *tail, 9), 12)
    assert b.outcome == "miss" and b.n_shared == 0


def test_backpressure_and_lru_eviction_order():
    pool = KVPagePool(4, 4)
    a = pool.admit(np.arange(8, dtype=np.int32), 12)     # 3 pages
    pool.commit(a)
    assert pool.admit(np.arange(8, dtype=np.int32) + 50, 12) is None, \
        "pool must refuse when free+evictable cannot cover the tail"
    pool.release(a)                 # 2 pages parked indexed, 3rd freed
    assert pool.free_pages() == 4 and pool.cached_pages() == 2
    # a new 3-page lease: takes the free pages then evicts the OLDEST
    # cached page; the newer cached page survives
    b = pool.admit(np.arange(8, dtype=np.int32) + 50, 12)
    assert b is not None
    assert pool.stats()["evictions"] >= 1
    # A's chain is now broken at its first page: at best a miss
    c = pool.admit(np.arange(8, dtype=np.int32), 12)
    assert c is None or c.outcome == "miss"


def test_matched_pages_are_protected_from_same_lease_eviction():
    """An admission whose tail allocation triggers eviction must not
    evict the very pages its own prefix match selected."""
    pool = KVPagePool(4, 4)
    a = pool.admit(np.arange(9, dtype=np.int32), 9)      # 3 pages, 2 full
    pool.commit(a)
    pool.release(a)                                      # 2 cached, 2 free
    b = pool.admit(np.arange(9, dtype=np.int32), 16)     # 4 logical pages
    assert b is not None and b.n_shared == 2
    assert set(b.page_ids[2:]).isdisjoint(b.page_ids[:2])
    assert pool.stats()["evictions"] == 0                # free pages sufficed


def test_duplicate_commit_keeps_first_copy():
    pool = KVPagePool(8, 4)
    prompt = np.arange(9, dtype=np.int32)
    a = pool.admit(prompt, 9)       # both admitted before either commits
    b = pool.admit(prompt, 9)
    assert b.outcome == "miss", "uncommitted pages must not be matchable"
    pool.commit(a)
    pool.commit(b)                  # loser: duplicate stays private
    c = pool.admit(prompt, 9)
    assert c.page_ids[:2] == a.page_ids[:2]
    pool.release(a)
    pool.release(b)
    pool.release(c)
    assert pool.free_pages() == 8


def test_abandoned_uncommitted_lease_returns_everything():
    pool = KVPagePool(8, 4)
    a = pool.admit(np.arange(9, dtype=np.int32), 12)
    pool.commit(a)
    b = pool.admit(np.arange(9, dtype=np.int32), 12)     # holds 2 shared
    pool.release(b)                 # abandoned before commit
    st = pool.stats()
    assert st["hit"] + st["miss"] + st["partial"] == 1, \
        "an uncommitted lease must not count an outcome"
    pool.release(a)
    assert pool.free_pages() == 8


def test_match_tokens_peek_is_side_effect_free():
    """The chunked-skip decision uses ``match_tokens``: it must report
    the admit-time match WITHOUT touching refcounts, stats, the LRU, or
    the free list (a trial lease could evict cached pages)."""
    pool = KVPagePool(8, 4)
    prompt = np.arange(10, dtype=np.int32)
    assert pool.match_tokens(prompt) == 0
    a = pool.admit(prompt, 14)
    pool.commit(a)
    before = (pool.free_pages(), pool.cached_pages(), pool.stats())
    assert pool.match_tokens(prompt) == 8
    assert (pool.free_pages(), pool.cached_pages(),
            pool.stats()) == before
    # exact-multiple prompts peek with the same shareable cap admit uses
    assert pool.match_tokens(prompt[:8]) == 4
    assert KVPagePool(8, 4, prefix_cache=False).match_tokens(prompt) == 0


def test_prefix_cache_disabled_never_shares():
    pool = KVPagePool(8, 4, prefix_cache=False)
    prompt = np.arange(9, dtype=np.int32)
    a = pool.admit(prompt, 9)
    pool.commit(a)
    b = pool.admit(prompt, 9)
    assert b.outcome == "miss" and b.n_shared == 0
    pool.release(a)
    pool.release(b)
    assert pool.free_pages() == 8 and pool.cached_pages() == 0


def test_adopt_indexes_every_full_prompt_page():
    """Session adoption has no ">= 1 token re-runs" cap: an exact
    k-page prompt shares/indexes ALL k pages (nothing is prefilled; the
    session already carries its first token)."""
    pool = KVPagePool(8, 4)
    prompt = np.arange(8, dtype=np.int32)       # exactly 2 pages
    a = pool.adopt(prompt, 12)                  # 3 logical pages
    assert a is not None and a.n_shared == 0 and a.outcome == "miss"
    assert len(a.page_ids) == 3
    pool.commit(a)
    # both full pages are matchable now (probe with a tail so the
    # admit-side peek's own re-run cap doesn't hide the second page)
    probe = np.concatenate([prompt, np.asarray([99], np.int32)])
    assert pool.match_tokens(probe) == 8
    b = pool.admit(prompt, 12)
    assert b.n_shared == 1, "admit must keep its re-run cap"
    pool.release(b)
    c = pool.adopt(prompt, 12)
    assert c.outcome == "hit" and c.n_shared == 2
    assert c.page_ids[:2] == a.page_ids[:2]
    pool.release(c)
    pool.release(a)


def test_adopt_matches_seeded_prefix_and_imports_only_the_tail():
    """An adopt against a pool already holding the session's system
    prefix shares those pages — the handoff imports only the unmatched
    remainder."""
    pool = KVPagePool(16, 4)
    sysp = np.arange(8, dtype=np.int32)
    seeded = pool.adopt(sysp, 8)
    pool.commit(seeded)
    pool.release(seeded)
    prompt = np.concatenate([sysp, np.asarray([9, 10], np.int32)])
    a = pool.adopt(prompt, 14)
    assert a.outcome == "hit" and a.n_shared == 2
    # pages to import = ceil(10/4) - 2 = 1 (the partial tail page)
    n_pp = -(-prompt.size // 4)
    assert len(a.page_ids[a.n_shared:n_pp]) == 1
    pool.release(a)


def test_adopt_backpressures_when_pool_dry():
    pool = KVPagePool(2, 4)
    a = pool.adopt(np.arange(4, dtype=np.int32), 8)
    assert a is not None
    assert pool.adopt(np.arange(4, dtype=np.int32) + 50, 8) is None
    pool.release(a)
    assert pool.adopt(np.arange(4, dtype=np.int32) + 50, 8) is not None


def test_adopt_cached_imports_in_order_and_respects_capacity():
    """Bare cached-page import (the standby prefix-cache clone): pages
    land in the LRU at refcount 0 — matchable immediately, evictable
    under pressure — and capacity truncation keeps chains reachable."""
    from tensorflowonspark_tpu.models.kv_pages import chain_keys

    donor = KVPagePool(8, 4)
    prompt = np.arange(12, dtype=np.int32)      # 3 full pages
    a = donor.adopt(prompt, 12)
    donor.commit(a)
    donor.release(a)
    keys = [k for k, _ in donor.export_index()]
    assert keys == chain_keys(prompt, 4)

    probe = np.concatenate([prompt, np.asarray([99], np.int32)])
    imp = KVPagePool(8, 4)
    got = imp.adopt_cached(keys)
    assert len(got) == 3 and imp.cached_pages() == 3
    assert imp.free_pages() == 8                # cached pages evictable
    assert imp.match_tokens(probe) == 12
    # re-import is a no-op (keys already indexed)
    assert imp.adopt_cached(keys) == {}

    tiny = KVPagePool(2, 4)
    trunc = tiny.adopt_cached(keys)
    assert len(trunc) == 2, "capacity truncation"
    # the truncated import keeps the chain PREFIX: 2 pages matchable
    assert tiny.match_tokens(probe) == 8


@pytest.mark.parametrize("stack", [(), (3,)], ids=["leaf", "scan_layers"])
def test_hash_page_data_detects_single_byte_corruption(stack):
    """Exported pages are ``[..., n, page_tokens, W]`` (one token's heads
    in one row), a ``scan_layers`` stack leading: page axis -3."""
    from tensorflowonspark_tpu.models.kv_pages import hash_page_data

    shape = stack + (2, 4, 6)
    arrays = [np.arange(np.prod(shape), dtype=np.float32).reshape(shape)]
    good = hash_page_data(arrays, 2)
    bad = [np.array(arrays[0], copy=True)]
    bad[0][..., 1, 2, 3] += 1e-3
    hashes = hash_page_data(bad, 2)
    assert hashes[0] == good[0] and hashes[1] != good[1]
