"""``core/host_tier.py::HostSpillTier`` on its own: a ``PageAllocator``,
a fake page pool (one numpy KV leaf) and no model — the seam the
server calls it through is all it needs."""

import threading

import jax
import numpy as np
import pytest

from paddlefleetx_tpu.core.host_tier import HostSpillTier, RehydrateMiss
from paddlefleetx_tpu.core.paging import PageAllocator
from paddlefleetx_tpu.observability import metrics


class _Pool:
    """A device page pool's stand-in: ``[pages, heads, dim, page]``,
    page ``p`` filled with whatever the test wrote there."""

    def __init__(self, pages=6):
        self.tree = {"cached_key": np.zeros((pages, 1, 2, 4),
                                            np.float32)}

    def read(self, pids):
        return {"cached_key": self.tree["cached_key"][np.asarray(pids)]}

    def write(self, stacked, pids):
        self.tree["cached_key"][np.asarray(pids)] = \
            np.asarray(stacked["cached_key"])

    def fill(self, pid, value):
        self.tree["cached_key"][pid] = value

    def page(self, pid):
        return float(self.tree["cached_key"][pid].mean())


@pytest.fixture
def make_tier():
    made = []

    def make(host_pages=4, fingerprint="model-a", page_size=4):
        alloc = PageAllocator(6, page_size, host_pages=host_pages)
        pool, events = _Pool(), []
        tier = HostSpillTier(
            alloc, 1 << 20, "bf16", fingerprint, pool.read, pool.write,
            lambda event, **f: events.append((event, f)),
            metrics.MetricsRegistry(enabled=True))
        made.append(tier)
        return tier, alloc, pool, events
    yield make
    for tier in made:
        tier.close()


def _spill(tier, alloc, pool, key, value, ship=True):
    """A registered page holding ``value``, released to its last
    reference and collected; its host id."""
    pid = alloc.alloc()
    pool.fill(pid, value)
    alloc.register_prefix(key, pid)
    tier.release(pid)
    assert tier.pinned == 1 and alloc.refcount(pid) == 1
    tier.collect(0, 0)
    if ship:
        tier.ship()
        tier.await_writer()
    hpid = alloc.lookup_prefix(key)
    assert alloc.is_host(hpid)
    return hpid


def test_spill_then_rehydrate_moves_the_bytes_and_the_registration(
        make_tier):
    tier, alloc, pool, events = make_tier()
    hpid = _spill(tier, alloc, pool, "a", 7.0)
    assert not tier.work_pending() and alloc.pages_in_use == 0
    (pid,) = tier.rehydrate([hpid], ticks=3)
    assert pool.page(pid) == 7.0
    assert alloc.lookup_prefix("a") == pid and alloc.refcount(pid) == 1
    assert [e for e, _ in events] == [
        "serving_yield", "serving_spill", "serving_rehydrate"]
    assert events[-1][1]["ticks"] == 3
    assert tier.summary() == {"tiered": True, "host_pool_bytes": 1 << 20,
                              "host_pages_cap": 4, "host_pages": 0}
    alloc.check()


def test_recycled_host_id_never_serves_the_older_generations_bytes(
        make_tier):
    """A one-page host tier: B's spill evicts A's residency and
    recycles its id while B's bytes are still in the outbox. A late
    publish of A's bytes under that id is discarded, and the
    rehydrate serves B from the pending gather."""
    tier, alloc, pool, _ = make_tier(host_pages=1)
    h_a = _spill(tier, alloc, pool, "a", 1.0)
    gen_a = alloc.host_generation(h_a)
    stale = tier._pop_host_bytes(h_a, gen_a)
    assert stale is not None
    h_b = _spill(tier, alloc, pool, "b", 2.0, ship=False)
    assert h_b == h_a and alloc.host_generation(h_b) > gen_a
    assert alloc.lookup_prefix("a") is None       # LRU-evicted
    with tier._lock:                              # the late publish
        tier._host_data[h_b] = (gen_a, stale)
    (pid,) = tier.rehydrate([h_b], ticks=0)
    assert pool.page(pid) == 2.0
    with tier._lock:
        assert h_b not in tier._host_data
    alloc.check()


def test_failed_writer_item_is_reaped_at_the_next_collect(
        make_tier, monkeypatch):
    tier, alloc, pool, _ = make_tier()
    real = jax.device_get

    def boom(x):
        if threading.current_thread().name == "kv-spill-writer":
            raise RuntimeError("injected spill-stage failure")
        return real(x)
    monkeypatch.setattr(jax, "device_get", boom)
    hpid = _spill(tier, alloc, pool, "a", 5.0)
    assert alloc.host_pages_resident == 1         # not reaped yet
    with pytest.raises(RehydrateMiss):
        # a hit before the reap unwinds: the page is evicted, its
        # registration gone, the prompt re-prefills cold
        tier.rehydrate([hpid], ticks=0)
    assert alloc.lookup_prefix("a") is None
    _spill(tier, alloc, pool, "b", 6.0)           # fails the same way
    assert alloc.host_pages_resident == 1
    tier.collect(0, 0)                            # the yield point
    assert alloc.host_pages_resident == 0
    assert alloc.lookup_prefix("b") is None
    assert tier._writer_thread.is_alive()
    alloc.check()


def test_pinned_pages_go_back_to_the_pool_oldest_first(make_tier):
    tier, alloc, pool, _ = make_tier()
    pids = [alloc.alloc() for _ in range(2)]
    for i, pid in enumerate(pids):
        alloc.register_prefix(f"k{i}", pid)
        tier.release(pid)
    plain = alloc.alloc()
    tier.release(plain)                  # unregistered: simply freed
    assert tier.pinned == 2 and tier.work_pending()
    free = alloc.free_pages
    assert tier.reclaim_pin() and alloc.free_pages == free + 1
    assert alloc.lookup_prefix("k0") is None
    assert alloc.lookup_prefix("k1") == pids[1]
    assert tier.reclaim_pin() and not tier.reclaim_pin()
    assert alloc.free_pages == free + 2 and not tier.work_pending()
    alloc.check()


def test_prefix_store_round_trips_and_refuses_a_foreign_model(
        make_tier):
    src, alloc, pool, _ = make_tier()
    hpids = [_spill(src, alloc, pool, k, v)
             for k, v in (("a", 3.0), ("b", 4.0))]
    alloc.register_prompt("p", hpids, "last-logits")
    store = src.export_store()
    assert sorted(store["pages"]) == sorted(hpids)
    assert store["model_fingerprint"] == "model-a"
    treedef = jax.tree_util.tree_structure(pool.tree)

    dst, alloc2, pool2, events = make_tier()
    assert dst.import_store(store, treedef) == 2
    assert events[-1][0] == "serving_prefix_store_import"
    pages, payload = alloc2.lookup_prompt("p")
    assert payload == "last-logits"
    new = dst.rehydrate(list(pages), ticks=0)
    assert [pool2.page(p) for p in new] == [3.0, 4.0]
    assert alloc2.lookup_prefix("b") == new[1]
    alloc2.check()

    for kw in ({"fingerprint": "model-b"}, {"page_size": 8}):
        other, alloc3, _, _ = make_tier(**kw)
        assert other.import_store(store, treedef) == 0
        assert alloc3.host_pages_resident == 0
    assert dst.import_store(None, treedef) == 0
