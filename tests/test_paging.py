"""PageAllocator: the host-side state machine under the paged KV cache.

The allocator is pure bookkeeping (no device traffic), which makes it
cheap to hammer: the randomized trace test below replays thousands of
admit / grow / COW-split / evict / preempt transitions — the exact
moves ``core/serving.py`` makes between decode ticks — and asserts
:meth:`PageAllocator.check`'s invariants after every single one. The
deterministic tests pin each transition's contract on its own.
"""

import numpy as np
import pytest

from paddlefleetx_tpu.core.paging import (
    NULL_PAGE, PageAllocator, PagePoolExhausted, page_prefix_keys,
    prompt_key,
)


# -- content keys ------------------------------------------------------


def test_page_prefix_keys_chain_over_full_pages():
    toks = list(range(300))
    keys = page_prefix_keys(toks, 128)
    assert len(keys) == 2  # 300 // 128 full pages; the tail hashes not
    # chain property: key j digests pages 0..j, so sharing any prefix
    # of full pages means sharing the leading keys
    other = toks[:256] + [999] * 44
    assert page_prefix_keys(other, 128) == keys
    diverge = toks[:128] + [7] + toks[129:]
    keys2 = page_prefix_keys(diverge, 128)
    assert keys2[0] == keys[0] and keys2[1] != keys[1]


def test_prompt_key_is_length_tagged():
    a, b = list(range(10)), list(range(12))
    assert prompt_key(a) != prompt_key(b)
    assert prompt_key(a) == prompt_key(list(range(10)))
    assert prompt_key(a).startswith("L10:")


# -- allocator basics --------------------------------------------------


def test_alloc_release_roundtrip():
    a = PageAllocator(num_pages=4, page_size=128)
    assert a.free_pages == 3 and a.pages_in_use == 0
    p1, p2 = a.alloc(), a.alloc()
    assert NULL_PAGE not in (p1, p2) and p1 != p2
    assert a.refcount(p1) == 1 and a.pages_in_use == 2
    assert a.release(p1) is True  # freed
    assert a.refcount(p1) == 0 and a.free_pages == 2
    a.check()


def test_alloc_is_deterministic_low_ids_first():
    a = PageAllocator(num_pages=5, page_size=128)
    assert [a.alloc() for _ in range(4)] == [1, 2, 3, 4]
    with pytest.raises(PagePoolExhausted):
        a.alloc()
    assert a.try_alloc() is None


def test_retain_release_refcounting():
    a = PageAllocator(num_pages=3, page_size=128)
    p = a.alloc()
    assert a.retain(p) == 2
    assert a.release(p) is False  # still referenced
    assert a.refcount(p) == 1
    assert a.release(p) is True
    with pytest.raises(ValueError):
        a.release(p)  # double free
    with pytest.raises(ValueError):
        a.retain(p)  # retain of a free page
    a.check()


def test_constructor_validation():
    with pytest.raises(ValueError):
        PageAllocator(num_pages=1, page_size=128)  # only the null page
    with pytest.raises(ValueError):
        PageAllocator(num_pages=4, page_size=0)


# -- registries --------------------------------------------------------


def test_prefix_registry_first_writer_wins_and_dies_with_page():
    a = PageAllocator(num_pages=5, page_size=2)
    p1, p2 = a.alloc(), a.alloc()
    a.register_prefix("k", p1)
    a.register_prefix("k", p2)  # late duplicate: ignored
    assert a.lookup_prefix("k") == p1
    a.release(p1)
    assert a.lookup_prefix("k") is None  # entry died with the page
    a.check()
    with pytest.raises(ValueError):
        a.register_prefix("k2", p1)  # page is free now


def test_prompt_registry_shares_pages_and_payload():
    a = PageAllocator(num_pages=6, page_size=2)
    pages = [a.alloc(), a.alloc()]
    a.register_prompt("P", pages, payload="logits-row")
    got = a.lookup_prompt("P")
    assert got == (tuple(pages), "logits-row")
    # consumer retains every page it maps (the documented contract)
    for p in got[0]:
        a.retain(p)
    # producer evicts; the entry survives because the consumer's refs
    # keep every member page live
    for p in pages:
        assert a.release(p) is False
    assert a.lookup_prompt("P") is not None
    a.check()
    # consumer evicts too -> pages free -> entry and its reverse maps
    # on OTHER member pages are dropped
    for p in pages:
        assert a.release(p) is True
    assert a.lookup_prompt("P") is None
    a.check()


def test_prompt_registry_partial_release_drops_whole_entry():
    # one member page dying invalidates the page list, so the entry
    # must vanish even though the other page is still live
    a = PageAllocator(num_pages=6, page_size=2)
    p1, p2 = a.alloc(), a.alloc()
    a.register_prompt("P", [p1, p2], payload=None)
    a.release(p1)
    assert a.lookup_prompt("P") is None
    assert a.refcount(p2) == 1  # survivor unaffected
    a.check()


def test_register_prompt_rejects_free_pages():
    a = PageAllocator(num_pages=4, page_size=2)
    p = a.alloc()
    a.release(p)
    with pytest.raises(ValueError):
        a.register_prompt("P", [p], payload=None)


# -- cross-server handoff invariants -----------------------------------
#
# core/fleet.py moves a prefilled sequence between two GenerationServers
# by (a) retaining the source pages for the duration of the export
# (kv_export), (b) allocating fresh ids on the destination pool and
# registering the same content keys there (kv_import), and (c) pinning
# the imported pages until the request finishes (kv_import_release).
# These tests replay that dance at the allocator level and run
# ``check()`` on both sides after every phase.


def test_export_retain_keeps_registry_alive_past_source_release():
    src = PageAllocator(num_pages=6, page_size=2)
    toks = [3, 1, 4, 1]  # two full pages
    pages = [src.alloc(), src.alloc()]
    for key, page in zip(page_prefix_keys(toks, 2), pages):
        src.register_prefix(key, page)
    src.register_prompt(prompt_key(toks), pages, payload="last-logits")
    # export pins every page (what kv_export does)
    for p in pages:
        src.retain(p)
    # the source request finishes and its slot is evicted
    for p in pages:
        assert src.release(p) is False
    # registries must survive on the strength of the export pins alone
    assert src.lookup_prompt(prompt_key(toks)) is not None
    assert src.lookup_prefix(page_prefix_keys(toks, 2)[0]) == pages[0]
    src.check()
    # export done (gather dispatched) -> drop the pins -> all gone
    for p in pages:
        assert src.release(p) is True
    assert src.lookup_prompt(prompt_key(toks)) is None
    src.check()


def test_import_remaps_page_ids_and_pins_until_release():
    toks = [3, 1, 4, 1]
    src = PageAllocator(num_pages=6, page_size=2)
    src_pages = [src.alloc(), src.alloc()]
    src.register_prompt(prompt_key(toks), src_pages, payload="logits")

    # destination pool has different occupancy, so the same content
    # lands on different page ids — the page table must be remapped,
    # never copied verbatim
    dst = PageAllocator(num_pages=8, page_size=2)
    occupied = [dst.alloc() for _ in range(3)]
    dst_pages = [dst.alloc() for _ in src_pages]
    assert set(dst_pages).isdisjoint(src_pages[:1]) or \
        dst_pages != src_pages  # ids genuinely remapped
    for key, page in zip(page_prefix_keys(toks, 2), dst_pages):
        dst.register_prefix(key, page)
    dst.register_prompt(prompt_key(toks), dst_pages, payload="logits")
    src.check()
    dst.check()

    # a consumer on the destination admits via the registry and retains
    got_pages, payload = dst.lookup_prompt(prompt_key(toks))
    assert got_pages == tuple(dst_pages) and payload == "logits"
    for p in got_pages:
        dst.retain(p)
    # import pin drops (kv_import_release); consumer refs keep it live
    for p in dst_pages:
        assert dst.release(p) is False
    assert dst.lookup_prompt(prompt_key(toks)) is not None
    dst.check()
    # consumer finishes -> content evaporates from the destination
    for p in got_pages:
        assert dst.release(p) is True
    assert dst.lookup_prompt(prompt_key(toks)) is None
    assert dst.lookup_prefix(page_prefix_keys(toks, 2)[0]) is None
    for p in occupied:
        dst.release(p)
    dst.check()
    # ...and the source was never perturbed by any of it
    assert src.lookup_prompt(prompt_key(toks)) is not None
    src.check()


def test_import_is_idempotent_under_registry_collision():
    # two routers racing the same prefix into one destination: the
    # second register_prefix is a no-op (first writer wins) and both
    # sides can release their own pages without corrupting the winner
    toks = list(range(4))
    key = page_prefix_keys(toks, 2)[0]
    dst = PageAllocator(num_pages=6, page_size=2)
    p_win, p_lose = dst.alloc(), dst.alloc()
    dst.register_prefix(key, p_win)
    dst.register_prefix(key, p_lose)  # ignored
    assert dst.lookup_prefix(key) == p_win
    assert dst.release(p_lose) is True  # loser frees its copy
    assert dst.lookup_prefix(key) == p_win
    dst.check()
    dst.release(p_win)
    assert dst.lookup_prefix(key) is None
    dst.check()


# -- host tier: spill / promote / LRU / snapshot -----------------------
#
# The hierarchical cache (docs/inference.md) moves a registered page's
# REGISTRATIONS to a host page id at refcount zero instead of dropping
# them; a later registry hit promotes them back onto a fresh device id.
# Host ids live in ``num_pages .. num_pages + host_pages - 1`` and are
# never mapped by a page table, so COW safety is structural.


def test_spill_moves_registrations_and_frees_device_page():
    a = PageAllocator(num_pages=4, page_size=2, host_pages=2)
    p = a.alloc()
    a.register_prefix("k", p)
    a.register_prompt("P", [p], payload="row")
    hpid = a.spill(p)
    assert hpid is not None and hpid >= 4 and a.is_host(hpid)
    assert a.refcount(p) == 0 and a.free_pages == 3  # device page freed
    assert a.lookup_prefix("k") == hpid
    assert a.lookup_prompt("P") == ((hpid,), "row")
    assert a.page_registered(hpid) and a.host_pages_resident == 1
    assert a.stats["spills"] == 1
    a.check()


def test_spill_rejects_bad_refcounts_and_unregistered_pages():
    a = PageAllocator(num_pages=4, page_size=2, host_pages=2)
    p = a.alloc()
    a.retain(p)
    with pytest.raises(ValueError):
        a.spill(p)  # refcount 2: someone still maps it
    a.release(p)
    # unregistered page: nothing to keep warm — caller must release()
    assert a.spill(p) is None
    assert a.refcount(p) == 1  # NOT freed by the refusal
    a.release(p)
    # no tier configured: spill is always a refusal
    b = PageAllocator(num_pages=4, page_size=2)
    q = b.alloc()
    b.register_prefix("k", q)
    assert b.spill(q) is None
    a.check()
    b.check()


def test_promote_restores_device_residency():
    a = PageAllocator(num_pages=4, page_size=2, host_pages=2)
    p = a.alloc()
    a.register_prefix("k", p)
    hpid = a.spill(p)
    fresh = a.alloc()  # the admitting request's page
    a.promote(hpid, fresh)
    assert a.lookup_prefix("k") == fresh
    assert a.host_pages_resident == 0 and not a.is_host(hpid)
    assert a.refcount(fresh) == 1  # the admitter's reference
    assert a.stats["rehydrates"] == 1
    a.check()
    with pytest.raises(ValueError):
        a.promote(hpid, fresh)  # hpid no longer resident


def test_host_tier_lru_eviction_drops_oldest_registrations():
    a = PageAllocator(num_pages=6, page_size=2, host_pages=2)
    pids = [a.alloc() for _ in range(3)]
    for i, p in enumerate(pids):
        a.register_prefix(f"k{i}", p)
    h0 = a.spill(pids[0])
    a.spill(pids[1])
    a.spill(pids[2])  # tier full: h0 (oldest) is evicted to make room
    assert a.lookup_prefix("k0") is None
    assert a.lookup_prefix("k1") is not None
    assert a.lookup_prefix("k2") is not None
    assert a.pop_host_evicted() == [h0]
    assert a.pop_host_evicted() == []  # return-and-clear
    assert a.stats["host_evictions"] == 1
    a.check()


def test_prompt_entry_spanning_tiers_cascades_on_member_death():
    # a prompt entry with one hosted and one live member: the live
    # member dying invalidates the page list, and the hosted member —
    # now carrying no registration — must be evicted from the tier,
    # not leak in it
    a = PageAllocator(num_pages=6, page_size=2, host_pages=2)
    p1, p2 = a.alloc(), a.alloc()
    a.register_prompt("P", [p1, p2], payload=None)
    a.register_prefix("k", p1)  # keeps p1 spillable on its own
    h1 = a.spill(p1)
    assert a.lookup_prompt("P") == ((h1, p2), None)
    a.release(p2)
    assert a.lookup_prompt("P") is None
    assert a.host_pages_resident == 1  # h1 lives on via its prefix key
    # now kill the prefix entry's only registration via a live page
    fresh = a.alloc()
    a.promote(h1, fresh)
    a.release(fresh)
    assert a.host_pages_resident == 0 and a.lookup_prefix("k") is None
    a.check()


def test_host_snapshot_and_import_roundtrip():
    a = PageAllocator(num_pages=4, page_size=2, host_pages=3)
    p1, p2 = a.alloc(), a.alloc()
    a.register_prefix("k", p1)
    a.register_prompt("P", [p1, p2], payload="row")
    h1 = a.spill(p1)
    h2 = a.spill(p2)
    prefixes, prompts = a.host_snapshot()
    assert prefixes == {"k": h1}
    assert prompts == {"P": ([h1, h2], "row")}
    a.check()
    # a fresh allocator (the restarted replica) adopts the snapshot
    b = PageAllocator(num_pages=4, page_size=2, host_pages=2)
    nh1, nh2 = b.host_import(), b.host_import()
    assert nh1 is not None and nh2 is not None
    assert b.host_import() is None  # full: import never evicts
    b.register_prefix("k", nh1)
    b.register_prompt("P", [nh1, nh2], payload="row")
    assert b.lookup_prefix("k") == nh1
    assert b.host_pages_resident == 2
    b.check()
    # orphan sweep: an imported page that ended up unregistered goes
    c = PageAllocator(num_pages=4, page_size=2, host_pages=2)
    orphan = c.host_import()
    assert orphan is not None
    c.sweep_host_orphans()
    assert c.host_pages_resident == 0
    assert c.pop_host_evicted() == [orphan]
    c.check()


def test_host_generation_tags_residencies_and_evict_host():
    # host ids are recycled by the LRU, so ids alone cannot name a
    # residency: host_generation must differ across recycles (the
    # byte-store owner's stale-spill guard), and evict_host must let
    # the owner retire a residency whose bytes it lost (failed spill)
    a = PageAllocator(num_pages=4, page_size=2, host_pages=1)
    p = a.alloc()
    a.register_prefix("k", p)
    h = a.spill(p)
    g1 = a.host_generation(h)
    assert g1 is not None
    a.evict_host(h)
    assert a.host_generation(h) is None  # non-resident: no generation
    assert a.lookup_prefix("k") is None  # registrations died with it
    assert a.pop_host_evicted() == [h]
    a.evict_host(h)  # already gone: a no-op, not an error
    assert a.pop_host_evicted() == []
    p2 = a.alloc()
    a.register_prefix("k2", p2)
    h2 = a.spill(p2)
    assert h2 == h  # the id was recycled...
    assert a.host_generation(h2) > g1  # ...under a NEW generation
    a.check()


def test_check_catches_cross_tier_corruption():
    a = PageAllocator(num_pages=4, page_size=2, host_pages=2)
    p = a.alloc()
    a.register_prefix("k", p)
    hpid = a.spill(p)
    # no pid may be simultaneously free and host-resident
    a._free.append(hpid)
    with pytest.raises(AssertionError):
        a.check()
    a._free.remove(hpid)
    a.check()
    # ...nor live (refcounted) and host-resident
    a._ref[hpid] = 1
    with pytest.raises(AssertionError):
        a.check()
    del a._ref[hpid]
    a.check()
    # a hosted page carrying no registration is a leak
    a._page_prefix_keys.pop(hpid)
    with pytest.raises(AssertionError):
        a.check()


# -- randomized state-machine trace ------------------------------------


def test_randomized_admit_evict_preempt_trace():
    """Replay the server's transition mix against a model: admissions
    that share via both registries, decode growth, COW splits, and
    evict/preempt (both release), with ``check()`` after every step
    and an independent per-request page ledger cross-checked at the
    end of every request's life."""
    rng = np.random.default_rng(0)
    page = 4
    a = PageAllocator(num_pages=17, page_size=page)  # 16 usable
    live = {}  # req id -> list of (pid, shared_bool at map time)
    next_id = 0
    for step in range(3000):
        op = rng.choice(["admit", "grow", "cow", "evict"])
        if op == "admit":
            # random prompt from a tiny pool so prefix/prompt hits occur
            base = rng.integers(0, 3)
            L = int(rng.integers(1, 3 * page + 1))
            toks = [int(base)] * L  # content-determined sharing
            hit = a.lookup_prompt(prompt_key(toks))
            pages = []
            if hit is not None:
                for pid in hit[0]:
                    a.retain(pid)
                    pages.append(pid)
            else:
                keys = page_prefix_keys(toks, page)[:(L - 1) // page]
                owned_from = 0
                for k in keys:
                    pid = a.lookup_prefix(k)
                    if pid is None:
                        break
                    a.retain(pid)
                    pages.append(pid)
                    owned_from += 1
                need = -(-L // page) - owned_from
                got = []
                for _ in range(need):
                    pid = a.try_alloc()
                    if pid is None:
                        break
                    got.append(pid)
                if len(got) < need:  # pool full: roll back this admit
                    for pid in got + pages:
                        a.release(pid)
                    a.check()
                    continue
                pages += got
                for j, k in enumerate(keys):
                    a.register_prefix(k, pages[j])
                a.register_prompt(prompt_key(toks), pages, payload=L)
            live[next_id] = pages
            next_id += 1
        elif op == "grow" and live:
            rid = int(rng.choice(list(live)))
            pid = a.try_alloc()
            if pid is not None:
                live[rid].append(pid)
        elif op == "cow" and live:
            rid = int(rng.choice(list(live)))
            pages = live[rid]
            j = int(rng.integers(0, len(pages)))
            if a.refcount(pages[j]) > 1:  # the server's write gate
                new = a.try_alloc()
                if new is not None:
                    a.release(pages[j])
                    pages[j] = new
                    a.stats["cow_splits"] += 1
        elif op == "evict" and live:
            rid = int(rng.choice(list(live)))
            for pid in live.pop(rid):
                a.release(pid)
        a.check()
        # cross-check: pages_in_use equals the distinct pages the
        # ledger references, and every refcount matches the ledger
        refs = {}
        for pages in live.values():
            for pid in pages:
                refs[pid] = refs.get(pid, 0) + 1
        assert a.pages_in_use == len(refs)
        for pid, n in refs.items():
            assert a.refcount(pid) == n, (step, pid)
    # drain everything: the pool must come back whole
    for rid in list(live):
        for pid in live.pop(rid):
            a.release(pid)
    a.check()
    assert a.pages_in_use == 0 and a.free_pages == 16
    assert a.stats["allocs"] == a.stats["frees"]


def test_randomized_tiered_trace_spill_rehydrate_cow():
    """The same transition mix over a TWO-tier allocator: evictions of
    registered last-ref pages spill instead of freeing (what
    ``core/host_tier.py::HostSpillTier.collect`` does), registry hits that land
    on host ids rehydrate through ``try_alloc`` + ``promote`` (what
    ``_rehydrate`` does), and COW stays device-only structurally —
    the ledger never references a host id. ``check()``'s cross-tier
    invariant runs after every step; the final drain proves neither
    tier leaks."""
    rng = np.random.default_rng(7)
    page = 4
    a = PageAllocator(num_pages=13, page_size=page, host_pages=4)
    live = {}
    next_id = 0
    spills = rehydrates = 0
    for step in range(3000):
        op = rng.choice(["admit", "grow", "cow", "evict"])
        if op == "admit":
            base = rng.integers(0, 3)
            L = int(rng.integers(1, 3 * page + 1))
            toks = [int(base)] * L
            hit = a.lookup_prompt(prompt_key(toks))
            pages = []
            ok = True
            if hit is not None:
                for pid in hit[0]:
                    if a.is_host(pid):
                        fresh = a.try_alloc()
                        if fresh is None:
                            ok = False
                            break
                        a.promote(pid, fresh)
                        rehydrates += 1
                        pages.append(fresh)
                    else:
                        a.retain(pid)
                        pages.append(pid)
                if not ok:  # pool full mid-rehydrate: roll back
                    for pid in pages:
                        a.release(pid)
                    a.check()
                    continue
            else:
                keys = page_prefix_keys(toks, page)[:(L - 1) // page]
                owned_from = 0
                for k in keys:
                    pid = a.lookup_prefix(k)
                    if pid is None:
                        break
                    if a.is_host(pid):
                        fresh = a.try_alloc()
                        if fresh is None:
                            break
                        a.promote(pid, fresh)
                        rehydrates += 1
                        pages.append(fresh)
                    else:
                        a.retain(pid)
                        pages.append(pid)
                    owned_from += 1
                need = -(-L // page) - owned_from
                got = []
                for _ in range(need):
                    pid = a.try_alloc()
                    if pid is None:
                        break
                    got.append(pid)
                if len(got) < need:
                    for pid in got + pages:
                        a.release(pid)
                    a.check()
                    continue
                pages += got
                for j, k in enumerate(keys):
                    a.register_prefix(k, pages[j])
                a.register_prompt(prompt_key(toks), pages, payload=L)
            live[next_id] = pages
            next_id += 1
        elif op == "grow" and live:
            rid = int(rng.choice(list(live)))
            pid = a.try_alloc()
            if pid is not None:
                live[rid].append(pid)
        elif op == "cow" and live:
            rid = int(rng.choice(list(live)))
            pages = live[rid]
            j = int(rng.integers(0, len(pages)))
            assert not a.is_host(pages[j])  # structural COW safety
            if a.refcount(pages[j]) > 1:
                new = a.try_alloc()
                if new is not None:
                    a.release(pages[j])
                    pages[j] = new
                    a.stats["cow_splits"] += 1
        elif op == "evict" and live:
            rid = int(rng.choice(list(live)))
            for pid in live.pop(rid):
                # the serving release path: last ref on a registered
                # page tiers down (sometimes — admission pressure can
                # also just release, e.g. _alloc_or_preempt reclaims)
                if a.refcount(pid) == 1 and a.page_registered(pid) \
                        and rng.random() < 0.7:
                    if a.spill(pid) is not None:
                        spills += 1
                        continue
                a.release(pid)
        a.check()
        refs = {}
        for pages in live.values():
            for pid in pages:
                assert not a.is_host(pid)  # host ids never mapped
                refs[pid] = refs.get(pid, 0) + 1
        assert a.pages_in_use == len(refs)
        for pid, n in refs.items():
            assert a.refcount(pid) == n, (step, pid)
    # the trace must actually have exercised the tier
    assert spills > 100 and rehydrates > 10
    assert a.stats["spills"] == spills
    assert a.stats["rehydrates"] == rehydrates
    # drain: device pool comes back whole; hosted pages all remain
    # registered (check() proved that each step) and evict cleanly
    for rid in list(live):
        for pid in live.pop(rid):
            a.release(pid)
    a.check()
    assert a.pages_in_use == 0 and a.free_pages == 12
