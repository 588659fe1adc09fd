"""PageAllocator lifecycle tests.

Regression for the round-3 corruption find: eviction must only take
INACTIVE pages (registered, refcount 0) — never a page a live sequence
still holds, even if that page is registered in the prefix cache
(reference block lifecycle, lib/llm/src/block_manager/pool/managed.rs).
"""

from dynamo_tpu.engine.kv_cache import PageAllocator


def test_basic_alloc_release_cycle():
    a = PageAllocator(num_pages=5, page_size=16)  # 4 usable (page 0 scratch)
    pages = a.allocate(4)
    assert len(pages) == 4 and 0 not in pages
    assert a.allocate(1) is None
    a.release(pages)
    assert a.num_free == 4


def test_active_registered_page_never_evicted():
    """A live sequence's registered page must not be evicted and handed to
    another allocation (would double-assign the page -> KV corruption)."""
    a = PageAllocator(num_pages=4, page_size=16)  # 3 usable
    held = a.allocate(2)
    # The live request's completed blocks get registered mid-flight.
    a.register(held[0], 111)
    a.register(held[1], 222)
    third = a.allocate(1)
    assert third is not None
    # Pool is now truly exhausted: held pages are active+registered, the
    # third is active. Nothing is evictable.
    assert a.allocate(1) is None
    assert a.num_free == 0
    assert set(held).isdisjoint(set(third))


def test_inactive_page_evicted_lru():
    a = PageAllocator(num_pages=4, page_size=16)
    p = a.allocate(3)
    a.register(p[0], 1)
    a.register(p[1], 2)
    a.register(p[2], 3)
    a.release(p)  # all inactive now, LRU order: 1, 2, 3
    assert a.num_free == 3
    # Touch hash 1 (acquire + release) -> becomes most recent.
    got = a.acquire_cached([1])
    assert got == [p[0]]
    a.release(got)
    fresh = a.allocate(2)  # evicts 2 then 3, not 1
    assert set(fresh) == {p[1], p[2]}
    assert a.lookup([1]) == [p[0]]
    assert a.lookup([2]) == []


def test_shared_prefix_refcounting():
    a = PageAllocator(num_pages=4, page_size=16)
    p = a.allocate(1)
    a.register(p[0], 7)
    # Second sequence pins the same block.
    q = a.acquire_cached([7])
    assert q == p
    a.release(p)  # first seq done; still held by second
    assert a.allocate(3) is None  # page not reusable yet: 2 free + p active
    a.release(q)
    assert a.num_free == 3


def test_unregister_returns_inactive_page_to_free():
    a = PageAllocator(num_pages=3, page_size=16)
    p = a.allocate(1)
    a.register(p[0], 9)
    a.release(p)
    assert a.num_free == 2
    a.unregister(p)
    assert a.lookup([9]) == []
    got = a.allocate(2)
    assert p[0] in got


def test_reregister_duplicate_hash_does_not_leak_page():
    """Re-registering an inactive page under a hash another page already
    holds must return it to the free pool, not orphan it."""
    a = PageAllocator(num_pages=4, page_size=16)
    p = a.allocate(2)
    a.register(p[0], 1)
    a.register(p[1], 2)
    a.release(p)  # both inactive
    a.register(p[1], 1)  # hash 1 already held by p[0]
    assert a.num_free == 3  # p[1] back in free, p[0] inactive, 1 untouched
    got = a.allocate(3)
    assert set(got) >= {p[0], p[1]}


def test_failed_request_unregister_then_release():
    """Engine failure path: unregister while still held, release later —
    page must come back exactly once."""
    a = PageAllocator(num_pages=3, page_size=16)
    p = a.allocate(2)
    a.register(p[0], 5)
    a.unregister(p)   # contents suspect; still referenced
    a.release(p)      # deferred release
    assert sorted(a.allocate(2)) == sorted(p)


# -- drain_events / clear_inactive / telemetry edge cases ----------------------
# These semantics back the dynamo_tpu_kv_* reuse counters and the
# router's index (stored/removed events): pin them (PR 8 satellite).


def test_release_while_cached_emits_no_removed_event():
    """Releasing a still-registered page moves it ACTIVE -> INACTIVE:
    the block stays served from this worker, so the router must NOT see
    a removed event (it would mis-route the next same-prefix request)."""
    a = PageAllocator(num_pages=3, page_size=16)
    p = a.allocate(1)
    a.register(p[0], 42)
    stored, removed = a.drain_events()
    assert stored == [42] and removed == []
    a.release(p)
    stored, removed = a.drain_events()
    assert stored == [] and removed == []
    assert a.lookup([42]) == [p[0]]  # still reusable


def test_reregister_of_evicted_hash_emits_stored_again():
    """Evict a hash, then a later sequence completes the same block on a
    different page: the router's view must go stored -> removed ->
    stored (not deduped away), or the fleet index goes stale."""
    a = PageAllocator(num_pages=3, page_size=16)
    p = a.allocate(2)
    a.register(p[0], 7)
    a.register(p[1], 8)
    a.release(p)
    a.drain_events()
    fresh = a.allocate(2)  # evicts both (LRU): removed events for 7, 8
    _, removed = a.drain_events()
    assert set(removed) == {7, 8}
    assert a.evicted_blocks == 2
    a.register(fresh[0], 7)  # same content recomputed on a new page
    stored, removed = a.drain_events()
    assert stored == [7] and removed == []
    assert a.lookup([7]) == [fresh[0]]


def test_clear_inactive_spares_active_and_counts():
    """clear_inactive drops ONLY inactive registrations (live pages keep
    theirs) and the reclaim counters feed kv_cleared_blocks_total."""
    a = PageAllocator(num_pages=4, page_size=16)
    p = a.allocate(3)
    a.register(p[0], 1)
    a.register(p[1], 2)
    a.register(p[2], 3)
    a.release([p[0], p[1]])  # 1, 2 inactive; 3 still active
    a.drain_events()
    assert a.clear_inactive() == 2
    _, removed = a.drain_events()
    assert set(removed) == {1, 2}
    assert a.cleared_blocks == 2 and a.clear_inactive_calls == 1
    # The active page's registration survives the admin clear.
    assert a.lookup([3]) == [p[2]]
    stats = a.stats()
    assert stats["pages_active"] == 1 and stats["pages_free"] == 2


def test_reuse_counters_track_hits_and_lookups():
    a = PageAllocator(num_pages=4, page_size=16)
    p = a.allocate(2)
    a.register(p[0], 10)
    a.register(p[1], 11)
    a.release(p)
    got = a.acquire_cached([10, 11, 12])  # 2 hits out of 3 probed
    assert got == p
    assert a.reuse_hit_blocks == 2
    assert a.reuse_lookup_blocks == 3
    a.release(got)
    stats = a.stats()
    assert stats["reuse_hit_blocks"] == 2
    assert stats["reuse_lookup_blocks"] == 3


def test_page_being_appended_to_belongs_to_one_sequence():
    """What the in-place window commit rests on
    (attention.commit_window_pallas reads, merges and rewrites whole
    pages): a page a sequence still appends to is its own. Only a FULL
    block has a hash to register (TokenBlockSequence hashes complete
    blocks), so a prefix hit pins full pages and the open page is found
    by no lookup until its owner fills it."""
    from dynamo_tpu.llm.tokens import TokenBlockSequence
    a = PageAllocator(num_pages=6, page_size=4)
    owner = TokenBlockSequence(4, [1, 2, 3, 4, 5, 6])  # one full block + 2
    pages = a.allocate(2)
    assert owner.num_complete_blocks == 1
    for page, h in zip(pages, owner.block_hashes):
        a.register(page, h)
    full, open_page = pages
    # The same prompt again: the full page is shared, the open one is not.
    twin = TokenBlockSequence(4, [1, 2, 3, 4, 5, 6])
    assert a.acquire_cached(twin.block_hashes) == [full]
    assert a.refs[full] == 2 and a.refs[open_page] == 1
    assert open_page not in a.cached_by_page
    fresh = a.allocate(1)  # the twin's own open page
    assert fresh and fresh[0] not in (full, open_page)
    # The owner fills its page: only now can it be registered and shared,
    # and from now on the owner writes the NEXT page.
    assert owner.append(7) is None
    a.register(open_page, owner.append(8))
    assert a.lookup(owner.block_hashes) == [full, open_page]
