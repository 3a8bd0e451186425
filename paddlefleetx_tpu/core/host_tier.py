"""The host spill tier of the paged KV cache (docs/inference.md,
"Hierarchical KV cache"): a bounded pinned-host store under the HBM
page pool, owned by one :class:`HostSpillTier` per tiered
``GenerationServer``.

A REGISTERED page's last reference is never dropped outright:
:meth:`HostSpillTier.release` keeps it as a spill pin, and
:meth:`HostSpillTier.collect` — called only at the host yield point
(step entry, between device launches) — gathers the page's KV on
device, moves its registrations onto a host-tier id
(``PageAllocator.spill``) and frees the HBM page. The blocking
device->host copy happens on a background writer thread, so decode
ticks never wait on a spill. A later registry hit rehydrates
(:meth:`HostSpillTier.rehydrate`): fresh HBM pages, scatter the staged
bytes, move the registrations back (promote) — the same export-pin ->
gather -> remap -> scatter contract as the fleet KV handoff, pointed at
the server's own host tier. COW safety is structural: host ids never
appear in any page table, so a divergent write can only target an HBM
page and the host copy is never mutated.

The tier reaches the server only through what it is handed: the
``PageAllocator``, ``read_pages`` / ``write_pages`` (a stacked gather
from, and scatter into, the server's cache) and the server's event
and histogram sinks. Every method but :meth:`ship`,
:meth:`await_writer` and :meth:`close` runs under the CALLER's lock
(the server's surface lock), like the allocator's; those three are
the cross-thread edges and run after that lock is released. The
writer thread touches ONLY the queue and the ``_lock``-guarded staged
bytes; allocator, cache, and telemetry stay with the caller.
"""

from __future__ import annotations

import dataclasses as _dc
import hashlib
import json
import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.gpt.generation import split_kv_pages, stack_kv_pages
from ..observability import metrics
from ..observability import timeline
from ..utils.log import logger
from .paging import PageAllocator


class RehydrateMiss(Exception):
    """A host page's staged bytes are gone because its spill stage
    failed on the writer thread; the page has been evicted (reaped)
    and admission must unwind whatever it already mapped and retry
    the request — it re-prefills cold on the next pass."""


def model_fingerprint(config, params) -> str:
    """Identity of a served model: a digest over the config plus
    every parameter leaf's path, shape, dtype and fp32 sum — cheap
    (one scalar reduction per leaf, one host transfer),
    deterministic, and different whenever the weights are. Stamped
    into every exported prefix store and checked on import, so KV
    persisted under one deploy can never warm-start a model with
    different weights."""
    h = hashlib.sha256()
    cfg_d = _dc.asdict(config) if _dc.is_dataclass(config) \
        else vars(config)
    h.update(json.dumps({k: str(v) for k, v in cfg_d.items()},
                        sort_keys=True).encode())
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    sums = jax.device_get(
        [jnp.sum(jnp.asarray(leaf, jnp.float32))
         for _, leaf in leaves])
    for (path, leaf), s in zip(leaves, sums):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str((tuple(leaf.shape), str(leaf.dtype))).encode())
        h.update(np.float32(s).tobytes())
    return h.hexdigest()[:16]


class HostSpillTier:
    """Spill pins, staged host bytes, the writer thread and the
    restart-persistent prefix store of one paged server."""

    #: upper bound on waiting for the writer to publish a page's
    #: bytes at rehydrate/export time — generous next to a single
    #: device_get, only ever reached if the writer thread died
    _SPILL_WAIT_S = 30.0

    def __init__(self, alloc: PageAllocator, pool_bytes: int,
                 kv_cache_dtype: str, fingerprint: str,
                 read_pages: Callable, write_pages: Callable,
                 emit: Callable, registry: metrics.MetricsRegistry):
        """``fingerprint`` is :func:`model_fingerprint` of what the
        server serves, computed by the caller OUTSIDE any lock (it
        reads the device). ``read_pages(pids)`` is one stacked
        gather of those pages from the server's cache,
        ``write_pages(stacked, pids)`` one stacked scatter into it;
        ``emit(event, **fields)`` is the server's flight recorder,
        ``registry`` its always-on histogram registry."""
        self._alloc = alloc
        self.pool_bytes = int(pool_bytes)
        self._kv_cache_dtype = kv_cache_dtype
        self.fingerprint = fingerprint
        self._read_pages = read_pages
        self._write_pages = write_pages
        self._emit = emit
        self._metrics = registry
        # pages whose LAST reference is held back as a spill pin
        # until the next yield-point collect (insertion order = spill
        # order)
        self._pin: Dict[int, None] = {}
        # host id -> (residency generation, device_get'd page tree);
        # shared with the writer thread, every access under _lock.
        # The generation tag keeps a recycled host id's stale bytes
        # (an old spill still in the writer queue when the LRU
        # evicted and reused the id) from ever rehydrating as the new
        # page's KV.
        self._host_data: Dict[int, Tuple[int, object]] = {}
        # (hpid, gen) pairs whose device_get failed on the writer;
        # the caller's loop evicts them at the next yield point
        # (_reap_failed). Under _lock.
        self._failed: List[Tuple[int, int]] = []
        # a Condition, not a bare Lock: the rehydrate slow path and
        # prefix-store export WAIT on it for the writer's publishes
        # instead of joining the queue, so the wait works from under
        # the caller's lock (the writer never takes that lock)
        self._lock = threading.Condition()
        #: writer items shipped but not yet published/failed; guarded
        #: by _lock, notified on every change
        self._outstanding = 0
        #: batched writer items collect() gathered, awaiting ship();
        #: under _lock (collect runs under the caller's lock, ship
        #: after it is released)
        self._outbox: List[tuple] = []
        self._q: queue.Queue = queue.Queue()
        self._writer_thread: Optional[threading.Thread] = \
            threading.Thread(target=self._writer,
                             name="kv-spill-writer", daemon=True)
        self._writer_thread.start()

    # -- the writer thread --------------------------------------------

    def _writer(self) -> None:
        """Background spill writer: stage each batched writer item —
        ONE stacked gather tree covering every page of a yield's
        collect — to host memory with a single ``jax.device_get``
        (the device sync the decode tick must never pay), split it
        back into per-page trees, and publish each under the spill
        condition, tagged with its host id's residency generation.
        The outstanding count drops and the condition notifies on
        EVERY path, success or failure: the rehydrate slow path and
        prefix-store export wait for ``outstanding == 0`` instead of
        joining the queue, and a writer that died mid-item must never
        strand them. A failed stage records every page of the batch
        instead (the caller's loop evicts those host pages at the
        next yield point, so the loss surfaces as a cold re-prefill,
        never a hang or wrong KV). ``None`` is the shutdown sentinel
        (:meth:`close`)."""
        tl = timeline.track("kv-spill-writer")
        while True:
            t0 = tl.begin()
            item = self._q.get()
            tl.add("idle", t0)
            if item is None:
                return
            entries, data = item
            t0 = tl.begin()
            try:
                host = jax.device_get(data)
                pages = split_kv_pages(host, len(entries))
            except Exception:
                logger.exception(
                    "kv-spill-writer: staging %d host pages failed; "
                    "their KV is lost and the pages will be evicted",
                    len(entries))
                with self._lock:
                    self._failed.extend(entries)
                    self._outstanding -= 1
                    self._lock.notify_all()
                tl.add("spill_device_get", t0)
                continue
            with self._lock:
                for (hpid, gen), page in zip(entries, pages):
                    cur = self._host_data.get(hpid)
                    if cur is None or cur[0] <= gen:
                        # never let a stale residency's late publish
                        # clobber a recycled id's fresher bytes
                        self._host_data[hpid] = (gen, page)
                self._outstanding -= 1
                self._lock.notify_all()
            tl.add("spill_device_get", t0)

    def ship(self) -> None:
        """Hand the writer items :meth:`collect` gathered to the
        spill queue. Called AFTER the caller's lock is released — the
        outstanding-count bump and the queue puts are the only
        cross-thread edges, and neither runs under it."""
        with self._lock:
            items, self._outbox = self._outbox, []
            self._outstanding += len(items)
        for item in items:
            self._q.put(item)

    def await_writer(self) -> None:
        """Wait (bounded) for the writer to finish every shipped item
        — the prefix-store export's quiesce point. Runs at an
        UNLOCKED position: the writer never needs the caller's lock,
        but waiting under it would still stall a concurrently ticking
        fleet worker for the whole device_get."""
        deadline = time.monotonic() + self._SPILL_WAIT_S
        with self._lock:
            while self._outstanding > 0 and \
                    time.monotonic() < deadline:
                self._lock.wait(timeout=0.05)

    def close(self) -> None:
        """Ship what is still outboxed, then stop the writer (the
        sentinel queues behind the last items). Idempotent."""
        self.ship()
        if self._writer_thread is not None:
            self._q.put(None)
            self._writer_thread.join(timeout=10.0)
            self._writer_thread = None

    # -- pins ---------------------------------------------------------

    @property
    def pinned(self) -> int:
        """Pages held back as spill pins, awaiting :meth:`collect`."""
        return len(self._pin)

    def work_pending(self) -> bool:
        """Pinned pages awaiting their yield-point collect, or
        collected writer items awaiting shipment."""
        with self._lock:
            return bool(self._pin or self._outbox)

    def release(self, pid: int) -> None:
        """Release one reference to a slot-mapped page. A registered
        page's LAST reference becomes a spill pin instead of freeing
        — the page stays whole until :meth:`collect` moves it to the
        host tier at the next yield point."""
        if pid not in self._pin and \
                self._alloc.refcount(pid) == 1 and \
                self._alloc.page_registered(pid):
            self._pin[pid] = None
            return
        self._alloc.release(pid)
        self._drop_evicted()

    def reclaim_pin(self) -> bool:
        """Give the oldest pinned page back to the pool, if there is
        one: a pinned to-be-spilled page is idle KV, so reclaiming it
        under pool pressure costs one lost spill, never a preemption
        (and keeps the pin set from deadlocking the pool)."""
        if not self._pin:
            return False
        held = next(iter(self._pin))
        del self._pin[held]
        self._alloc.release(held)
        self._drop_evicted()
        return True

    # -- staged bytes -------------------------------------------------

    def _drop_evicted(self) -> None:
        """Forget the staged bytes of host pages the allocator evicted
        (LRU pressure, orphan sweep, failed spill) — before their ids
        are reused. Generation-checked: if an evicted id was already
        recycled AND the writer already published the new residency's
        bytes, those bytes are live and must survive this drain."""
        evicted = self._alloc.pop_host_evicted()
        if not evicted:
            return
        with self._lock:
            for hpid in evicted:
                entry = self._host_data.get(hpid)
                if entry is not None and \
                        entry[0] != self._alloc.host_generation(hpid):
                    del self._host_data[hpid]

    def _reap_failed(self) -> None:
        """Evict host pages whose spill stage failed on the writer
        thread (their bytes never reached host memory): drop the
        registrations pointing at them so no lookup can hand out a
        page that cannot rehydrate. Caller's loop only — the writer
        records failures, it never touches the allocator."""
        with self._lock:
            failed, self._failed = self._failed, []
        for hpid, gen in failed:
            # gen guard: the failed residency may already be gone and
            # the id recycled — never evict the successor
            if self._alloc.host_generation(hpid) == gen:
                self._alloc.evict_host(hpid)
                metrics.inc("serving/spill_failed")
        if failed:
            self._drop_evicted()

    def _pop_host_bytes(self, hpid: int, gen: int):
        """Pop the staged bytes of the CURRENT residency of ``hpid``,
        or None when they are not published yet. An entry tagged with
        an older generation is a recycled id's stale spill whose
        publish raced the eviction drain — discard it (its residency
        is dead) and report a miss; the writer queue is FIFO, so once
        the writer is idle the live generation's bytes are the ones
        in place."""
        with self._lock:
            entry = self._host_data.get(hpid)
            if entry is None:
                return None
            del self._host_data[hpid]
            if entry[0] != gen:
                return None
            return entry[1]

    def _outbox_page(self, hpid: int, gen: int):
        """A page's device tree from a writer item still sitting in
        the outbox — a spill collected THIS step entry whose ship
        happens only after the caller's lock releases. Rehydrating
        straight from the pending gather skips the host round trip;
        the item stays queued untouched (its eventual publish of this
        residency is discarded by the generation guards once the
        promote recycles the id)."""
        with self._lock:
            items = list(self._outbox)
        for entries, data in items:
            for i, (h, g) in enumerate(entries):
                if h == hpid and g == gen:
                    return split_kv_pages(data, len(entries))[i]
        return None

    def _await_host_bytes(self, hpid: int, gen: int):
        """Wait (admission time only, never between decode ticks) for
        the writer to publish the CURRENT residency of ``hpid`` and
        pop it. None once the bytes are known gone: the residency's
        failure was recorded, a fresher residency owns the id, the
        writer went idle with nothing published, or the wait timed
        out. Waits on the spill condition — the writer publishes
        under it and never takes the caller's lock, so waiting here
        from under that lock cannot deadlock."""
        deadline = time.monotonic() + self._SPILL_WAIT_S
        with self._lock:
            while True:
                entry = self._host_data.get(hpid)
                if entry is not None:
                    if entry[0] == gen:
                        del self._host_data[hpid]
                        return entry[1]
                    if entry[0] < gen:
                        # a recycled id's stale spill raced the
                        # eviction drain: discard, keep waiting
                        del self._host_data[hpid]
                    else:
                        return None   # this residency is dead
                elif (hpid, gen) in self._failed:
                    return None
                elif self._outstanding == 0:
                    return None
                if time.monotonic() >= deadline:
                    return None
                self._lock.wait(timeout=0.05)

    # -- the two tier moves -------------------------------------------

    def collect(self, ticks: int, roundtrips: int) -> None:
        """Collect every pinned spill into ONE batched writer item:
        per page, move its registrations to a host id and free the
        HBM page; then gather ALL spilled pages' KV in a single
        stacked dispatch (async — the blocking copy runs on the
        writer thread) and append the item to the outbox. Runs under
        the caller's lock at the step-entry yield point only; the
        caller ships the outbox to the writer queue AFTER releasing
        the lock (:meth:`ship`), so the queue put never runs under
        it. Every ``serving_spill`` pairs with the ``serving_yield``
        that opened the collect (``ticks`` / ``roundtrips`` stamp
        both). Freeing the page ids before the gather is safe —
        nothing allocates between, and later decode writes build NEW
        functional cache arrays while the dispatched gather keeps
        referencing these buffers."""
        self._reap_failed()
        if not self._pin:
            return
        self._emit("serving_yield", ticks=ticks, roundtrips=roundtrips,
                   pending_spills=len(self._pin))
        spilled: List[int] = []
        entries: List[Tuple[int, int]] = []
        while self._pin:
            pid = next(iter(self._pin))   # FIFO: oldest pin first
            del self._pin[pid]
            if self._alloc.refcount(pid) > 1:
                # re-shared while pinned: drop the pin, stay in HBM
                self._alloc.release(pid)
                continue
            hpid = self._alloc.spill(pid)
            if hpid is None:
                # registrations died while pinned (a co-member freed);
                # the release can cascade host evictions of its own —
                # drain them now, not at some later call, so staged
                # bytes never outlive their residency
                self._alloc.release(pid)
                self._drop_evicted()
                continue
            gen = self._alloc.host_generation(hpid)
            self._drop_evicted()
            spilled.append(pid)
            entries.append((hpid, gen))
            metrics.inc("serving/spill")
            self._emit("serving_spill", page=pid, host_page=hpid,
                       ticks=ticks, roundtrips=roundtrips)
        if spilled:
            data = self._read_pages(spilled)
            with self._lock:
                self._outbox.append((entries, data))
        metrics.get_registry().set_gauge(
            "serving/host_pages", self._alloc.host_pages_resident)

    def rehydrate(self, hpids: Sequence[int], ticks: int) -> List[int]:
        """Bring N host-resident pages back into HBM with ONE stacked
        scatter: pop (or await) every page's staged bytes, allocate N
        fresh page ids, scatter the stacked tree in a single
        dispatch, and move each page's registrations back. Every
        fresh page's refcount-1 reference belongs to the admitting
        request; the callers check ``free_pages`` first, so the
        allocs always succeed. Raises :class:`RehydrateMiss` — with
        every already-popped page's bytes restored, those residencies
        stay live — when any page's stage failed; the caller unwinds
        and retries cold."""
        if not hpids:
            return []
        t0 = time.time()
        popped: List[Tuple[int, int, object]] = []
        miss: Optional[int] = None
        for hpid in hpids:
            gen = self._alloc.host_generation(hpid)
            data = self._pop_host_bytes(hpid, gen)
            if data is None:
                data = self._outbox_page(hpid, gen)
            if data is None:
                data = self._await_host_bytes(hpid, gen)
            if data is None:
                miss = hpid
                break
            popped.append((hpid, gen, data))
        if miss is not None:
            with self._lock:
                for hpid, gen, data in popped:
                    self._host_data[hpid] = (gen, data)
            # the one legitimate way here: the spill's device_get
            # failed on the writer after this page was looked up but
            # before the failure was reaped. Reap now (evicts the
            # page, drops its registrations) and let admission unwind
            # — the prompt re-prefills cold. Anything else is an
            # invariant bug and must fail loudly.
            self._reap_failed()
            if self._alloc.is_host(miss):
                raise RuntimeError(
                    f"host page {miss} resident but its bytes are "
                    f"gone")
            self._drop_evicted()
            raise RehydrateMiss(miss)
        pids = self._alloc.alloc_many(len(popped))
        self._write_pages(stack_kv_pages([d for _, _, d in popped]),
                          pids)
        for (hpid, _, _), pid in zip(popped, pids):
            self._alloc.promote(hpid, pid)
            self._emit("serving_rehydrate", host_page=hpid, page=pid,
                       ticks=ticks)
        metrics.inc("serving/rehydrate", len(pids))
        self._metrics.observe("serving/rehydrate_ms",
                              (time.time() - t0) * 1000.0)
        metrics.get_registry().set_gauge(
            "serving/host_pages", self._alloc.host_pages_resident)
        return pids

    def summary(self) -> dict:
        """The tier's lines of ``GenerationServer.summary()``."""
        return {"tiered": True, "host_pool_bytes": self.pool_bytes,
                "host_pages_cap": self._alloc.host_pages,
                "host_pages": self._alloc.host_pages_resident}

    # -- restart-persistent prefix store ------------------------------
    #
    # A drained tiered server's shareable KV is (by construction) all
    # host-resident: every registered page released to its last
    # reference spilled. export_store snapshots that tier — staged
    # bytes + the registry entries that reach them — as a plain dict;
    # core/checkpoint.py's save/load_prefix_store round it through a
    # committed-last manifest directory, and
    # FleetRouter.restart_replica hands it to the restarted replica's
    # import_prefix_store so it serves its first request warm.

    def export_store(self) -> dict:
        """Page bytes (flat numpy leaf lists in cache tree order)
        plus the host-resident registry entries. The caller has
        collected the pending pins, shipped them and waited out the
        writer (:meth:`await_writer`) first: that quiesce flushed
        every publish AND every failure record — reap now so dead
        pages drop out of the snapshot."""
        self._reap_failed()
        prefixes, prompts = self._alloc.host_snapshot()
        needed = set(prefixes.values())
        for pages, _ in prompts.values():
            needed.update(pages)
        with self._lock:
            data = {h: self._host_data[h][1] for h in needed
                    if h in self._host_data and self._host_data[h][0]
                    == self._alloc.host_generation(h)}
        store = {
            "page_size": self._alloc.page_size,
            "kv_cache_dtype": self._kv_cache_dtype,
            "model_fingerprint": self.fingerprint,
            "pages": {h: jax.tree_util.tree_leaves(t)
                      for h, t in data.items()},
            "prefixes": {k: h for k, h in prefixes.items()
                         if h in data},
            "prompts": {k: (pages, payload)
                        for k, (pages, payload) in prompts.items()
                        if all(p in data for p in pages)},
        }
        self._emit("serving_prefix_store_export",
                   pages=len(store["pages"]),
                   prefixes=len(store["prefixes"]),
                   prompts=len(store["prompts"]))
        return store

    def import_store(self, store: Optional[dict], treedef) -> int:
        """Adopt an exported prefix store (``treedef``: the cache's
        tree structure, to rebuild page trees from leaf lists): fill
        free host slots with the saved pages and re-register their
        content keys. A geometry mismatch (page size, KV dtype)
        imports nothing — the bytes would be garbage — and so does a
        model-identity mismatch: KV computed by DIFFERENT weights
        under identical geometry scatters cleanly but serves silently
        wrong attention, the one failure mode a disk round-trip
        across deploys invites. Returns the pages adopted."""
        if not store:
            return 0
        page = self._alloc.page_size
        if store.get("page_size") != page or \
                store.get("kv_cache_dtype") != self._kv_cache_dtype:
            logger.warning(
                "prefix store geometry mismatch (page %s dtype %s vs "
                "page %d dtype %s): starting cold",
                store.get("page_size"), store.get("kv_cache_dtype"),
                page, self._kv_cache_dtype)
            return 0
        if store.get("model_fingerprint") != self.fingerprint:
            logger.warning(
                "prefix store model fingerprint mismatch (%s vs %s): "
                "its KV was computed by different weights — starting "
                "cold", store.get("model_fingerprint"),
                self.fingerprint)
            return 0
        remap: Dict[int, int] = {}

        def _adopt(old: int) -> Optional[int]:
            if old in remap:
                return remap[old]
            leaves = store["pages"].get(old)
            if leaves is None:
                return None
            hpid = self._alloc.host_import()
            if hpid is None:   # tier full: import what fits, stop
                return None
            gen = self._alloc.host_generation(hpid)
            with self._lock:
                self._host_data[hpid] = (
                    gen, jax.tree_util.tree_unflatten(treedef, leaves))
            remap[old] = hpid
            return hpid

        for key, old in store.get("prefixes", {}).items():
            hpid = _adopt(old)
            if hpid is not None:
                self._alloc.register_prefix(key, hpid)
        for key, (pages, payload) in store.get("prompts", {}).items():
            new_pages = [_adopt(p) for p in pages]
            if all(p is not None for p in new_pages):
                self._alloc.register_prompt(key, new_pages, payload)
        # a page adopted for a prompt entry that then failed to fully
        # remap may be unreachable — evict such orphans right away
        self._alloc.sweep_host_orphans()
        self._drop_evicted()
        adopted = self._alloc.host_pages_resident
        metrics.get_registry().set_gauge("serving/host_pages", adopted)
        self._emit("serving_prefix_store_import", pages=adopted,
                   prefixes=len(store.get("prefixes", {})),
                   prompts=len(store.get("prompts", {})))
        return adopted
