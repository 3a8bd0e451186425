"""Continuous-batching generation server over slot-managed KV cache.

The lockstep ``generate()`` path (``models/gpt/generation.py``) runs a
batch at the speed of its longest request and admits nothing until the
whole batch drains. ``GenerationServer`` keeps decode rolling instead:
a persistent ``[slots, ...]`` KV cache lives on device, the host owns a
request queue and admits each request into a free slot (a bucketed
``prefill_into_slots`` — one compiled shape per prompt-length bucket),
and ONE jitted SPMD ``decode_step`` ticks every occupied slot forward a
token with per-slot lengths/sampling state through the ragged attention
dispatch (``flash_decode_ragged`` or the XLA per-row-offset fallback —
dispatch matrix in docs/inference.md). Finished slots are evicted
between ticks and their completions returned, so new requests ride in
as soon as capacity frees and throughput never drops to the slowest
request.

Slot-for-slot parity: greedy completions match the lockstep
``generate()`` exactly, whatever the admission order or prompt-length
mix (pinned by tests/test_serving.py's parity matrix).

Paged mode (``page_size``/``pool_pages``, or a config with
``kv_page_size``/``kv_pool_pages`` set): instead of one contiguous
``cache_capacity`` row per slot, the KV store is a global pool of
fixed-size pages reached through a slot->page table
(``core/paging.py``), which buys three things at once:

- **Density** — a slot holds only the pages its tokens actually fill,
  so a pool sized well below ``slots * capacity`` serves the same slot
  count (the 2-4x-slots-per-HBM headline; pool exhaustion preempts the
  youngest slot back to the queue head instead of OOMing).
- **Prefix sharing** — full prompt pages are content-addressed
  (chain hash), so requests sharing a system prompt prefill it once
  and map the same physical pages; an IDENTICAL prompt admits with
  zero prefill through the whole-prompt registry. Shared pages split
  copy-on-write at the first divergent decode write.
- **Chunked prefill** — long admissions run as page-aligned chunks,
  at most one per ``step()``, interleaved with decode ticks
  (``prefill_chunk_paged``), so admitting a long prompt never stalls
  tokens/s for running slots.

Hierarchical KV cache (``host_pool_bytes``, docs/inference.md): a
bounded pinned-host spill tier under the HBM pool. A registered
prefix/prompt page's last reference is pinned instead of freed, and at
the next step-entry yield point its KV is gathered on device and
staged to host memory by a background writer thread while the
registries keep pointing at it across the tier move
(``PageAllocator.spill``); a later registry hit scatters the host copy
back into a fresh HBM page (``serving/rehydrate``) instead of
re-prefilling. Decode ticks never block on the swap, COW splits only
ever touch HBM pages, and ``export_prefix_store`` /
``import_prefix_store`` carry the tier across rolling restarts
(``core/checkpoint.py`` manifest path + ``FleetRouter``).

Speculative decoding (``GenerationConfig.spec_method``/``spec_tokens``):
decode at small batch is latency-bound on the per-step collectives, so
the tick instead drafts ``k`` tokens per slot from a host draft source
(``core/spec.py`` — n-gram self-speculation by default), scores the
whole ``[slots, k+1]`` window in ONE jitted forward (``verify_step``'s
within-window causal mask over the same ragged/paged attention), and
commits the per-slot accepted prefix — 1..k+1 tokens per tick, so
accepting slots advance by different counts (the per-row lengths and
page tables above are exactly the substrate this needs; pages past a
slot's accepted point are handed straight back to the pool). Greedy
speculative output is token-exact vs the non-speculative server.

Device-resident decode (``device_loop_ticks=T``): with T > 1 every
:meth:`GenerationServer.step` launches ONE fused
``decode_loop``/``verify_loop`` program running up to T ticks
on-device (``lax.while_loop`` over the same tick bodies), exiting
early when a slot finishes or exhausts its budget, or after one tick
when the host flagged pending scheduling work at launch — admission,
drain, chunked prefill, or page-pool pressure. The host then replays
the returned per-tick token buffers so committed tokens, traces, and
histograms stay tick-accurate, paying one dispatch/fetch/schedule
round-trip per up-to-T ticks instead of per tick — the host-overhead
kill for latency-bound small-batch decode (docs/inference.md
"Device-resident decode"). T=1 (the default) is byte-identical to the
pre-loop server; any T commits the same tokens.

Graceful degradation (docs/robustness.md): per-request deadlines/TTL
(``submit(deadline_s=...)`` or a server-wide ``request_ttl_s``) evict
expired requests with a ``deadline_exceeded`` result; a bounded queue
(``max_queue_depth``) sheds excess submits with :class:`RequestShed`
and the ``serving/shed`` counter; :meth:`GenerationServer.drain` (or a
SIGTERM under ``drain_on_sigterm=True``) stops admitting, finishes or
preempts in-flight slots, and returns partials — committed tokens are
never lost, and ``submit(resume_tokens=...)`` re-enters a partial on a
restarted paged server token-exactly (the same prompt+tokens re-prefill
contract slot preemption uses).

Telemetry (docs/observability.md): ``serving/slot_occupancy`` and
``serving/pages_in_use`` gauges, ``serving/admitted`` /
``serving/evicted`` / ``serving/preempted`` / ``serving/prefix_hits``
/ ``serving/cow_splits`` / ``serving/prefill_chunks`` /
``serving/decode_tokens`` counters (committed tokens, NOT ticks — with
spec decode 1 tick != 1 token), the tiered ``serving/spill`` /
``serving/rehydrate`` counters + ``serving/host_pages`` gauge +
``serving/rehydrate_ms`` histogram, the ``serving/spec_drafted`` /
``serving/spec_accepted`` counters + ``serving/spec_accept_rate``
gauge, the ``serving/device_ticks`` counter and per-reason
``serving/loop_exit/{finished,admission,budget,drain}`` counters of
the fused loop, the ``serving/slow_steps`` /
``serving/slow_step/<phase>`` counters of the slow-step record, and a
tokens/s + TTFT p50/p99 summary;
an optional flight recorder mirrors admissions/evictions to an
``events.jsonl`` stream CI's failure-diagnostics artifact collects.

Latency percentiles ride fixed-memory log-bucketed histograms in a
server-local registry (``serving/ttft_ms``, ``serving/queue_wait_ms``,
``serving/tpot_ms``, ``serving/tick_ms``,
``serving/host_roundtrip_ms`` — O(buckets) forever, no
unbounded sample lists), and with ``events_path`` set every request
gets a TRACE: a ``serving/request`` root span with
``serving/queue`` → ``serving/prefill`` → ``serving/decode`` phase
children and a ``serving/first_token`` point, preemption ending the
decode phase and re-opening a queue phase UNDER THE SAME trace id —
so one grep of events.jsonl (or the live ``/trace`` endpoint)
reconstructs a request's whole life, submit through evict. With
``PFX_METRICS_PORT`` set the server also exposes live ``/metrics``,
``/vars``, ``/healthz`` (drain-aware: 503 while draining) and
``/trace`` endpoints (``observability/server.py``).

Host phases: every line of ``step()`` / ``prefill_step()`` runs
inside one ``serving/step/<phase>`` annotation under the root
``serving/step`` (``observability/trace.py``: a profiler annotation
on the device trace's clock, and the seconds of the step's
:class:`StepRecord`). The record feeds ``serving/tick_ms``,
``serving/host_roundtrip_ms`` and ``summary()``'s decode time, and
names the phase of a slow step (docs/observability.md, "Host phases").
"""

from __future__ import annotations

import dataclasses as _dc
import hashlib
import json
import queue
import signal
import statistics
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.gpt.generation import (
    LOOP_EXIT_BUDGET, LOOP_EXIT_FINISHED, GenerationConfig,
    _unrolled_twin, activate_slot, copy_kv_pages, decode_loop,
    decode_step, gather_kv_pages, init_page_pool, init_slot_cache,
    init_slot_state, prefill_chunk_paged, prefill_into_slots,
    scatter_kv_pages, split_kv_pages, stack_kv_pages, verify_loop,
    verify_step,
)
from ..observability import metrics
from ..observability import server as obs_server
from ..observability import timeline
from ..observability.recorder import FlightRecorder
from ..observability.spans import Tracer
from ..observability.trace import annotate, unaccounted
from ..utils.log import logger
from .adapters import AdapterCache, AdapterCacheFull, insert_adapter
from .paging import (
    NULL_PAGE, PageAllocator, PagePoolExhausted, page_prefix_keys,
    pool_pages_for_bytes, prompt_key,
)
from .resilience import FaultInjector, StepWatchdog
from .spec import make_draft_source


class RequestShed(RuntimeError):
    """Admission refused: the queue is at ``max_queue_depth``, the
    server is draining, or an ``admit_fail`` fault fired. The caller
    should back off and retry elsewhere — everything already admitted
    is unaffected."""


class _RehydrateMiss(Exception):
    """A host page's staged bytes are gone because its spill stage
    failed on the writer thread; the page has been evicted (reaped)
    and admission must unwind whatever it already mapped and retry
    the request — it re-prefills cold on the next pass. Internal to
    the admission loop, never escapes :meth:`GenerationServer.step`."""


def default_prefill_buckets(max_prompt_len: int) -> Tuple[int, ...]:
    """Powers of two from 16 up to ``max_prompt_len``, which is always
    included — a handful of compiled prefill shapes covers every
    admissible prompt length."""
    out = []
    b = 16
    while b < max_prompt_len:
        out.append(b)
        b *= 2
    out.append(max_prompt_len)
    return tuple(out)


#: the root annotation of one ``step()`` / ``prefill_step()``; its
#: children are ``serving/step/<phase>``
STEP = "serving/step"
#: a step is SLOW when it took longer than both this many seconds and
#: ``SLOW_STEP_FACTOR`` x the median of the last ``SLOW_STEP_HISTORY``
#: decoding steps (judged once ``SLOW_STEP_MIN_HISTORY`` of them are
#: in — the first steps of a server compile)
SLOW_STEP_SECONDS = 0.25
SLOW_STEP_FACTOR = 5.0
SLOW_STEP_HISTORY = 64
SLOW_STEP_MIN_HISTORY = 8


class StepRecord:
    """The host's account of one ``step()``: wall time of its start,
    seconds by phase (``phases``, filled by ``annotate``; the root's
    duration under ``STEP``), and what the step did."""

    __slots__ = ("start", "phases", "live", "queued", "chunks",
                 "ticks", "tokens")

    def __init__(self):
        self.start = time.time()
        self.phases: Dict[str, float] = {}
        self.live = 0        # slots the decode launch ticked
        self.queued = 0      # queue depth once admission had run
        self.chunks = 0      # prefill chunks dispatched
        self.ticks = 0       # decode ticks run on the device
        self.tokens = 0      # tokens committed

    @property
    def seconds(self) -> float:
        """The root's duration."""
        return self.phases.get(STEP, 0.0)

    def tick_seconds(self) -> float:
        """The decode launch as the host saw it: dispatch, then the
        wait for its tokens."""
        return self.phases.get(STEP + "/decode_dispatch", 0.0) + \
            self.phases.get(STEP + "/decode_harvest", 0.0)

    def phases_ms(self) -> Dict[str, float]:
        """Milliseconds by phase, short names, ``unaccounted`` (the
        root's time no phase covers) among them: they sum to the
        root's duration."""
        out = {k[len(STEP) + 1:]: round(v * 1e3, 3)
               for k, v in self.phases.items() if k != STEP}
        out["unaccounted"] = round(
            unaccounted(self.phases, STEP) * 1e3, 3)
        return out

    def as_dict(self) -> dict:
        """The whole record, as the slow-step log line and event
        carry it."""
        return {"start": round(self.start, 3),
                "dur_ms": round(self.seconds * 1e3, 3),
                "phases_ms": self.phases_ms(), "live": self.live,
                "queued": self.queued, "chunks": self.chunks,
                "ticks": self.ticks, "tokens": self.tokens}


@dataclass
class Completion:
    """One finished request as returned by :meth:`GenerationServer.step`."""
    request_id: int
    prompt: List[int]
    #: emitted tokens in order, EOS included when hit (identical to the
    #: lockstep ``generate()`` row before its pad tail)
    tokens: List[int]
    #: "eos" | "length" (hit max_dec_len) | "preempted" |
    #: "deadline_exceeded" (TTL expired; ``tokens`` holds the partial)
    finish_reason: str
    #: the request's trace id (None without an event stream); pass it
    #: back to ``submit(resume_tokens=..., trace_id=...)`` so the
    #: resumed request's spans link to the original timeline
    trace_id: Optional[str] = None
    #: time-to-first-token of THIS server lifetime in ms (None when the
    #: request never decoded here) — the fleet router aggregates these
    #: into its own latency histogram (core/fleet.py)
    ttft_ms: Optional[float] = None


class GenerationServer:
    """Host-side queue/admit/evict loop around the jitted slot
    primitives (``models/gpt/generation.py``).

    ``model``/``params`` are the live flax model and its parameters
    (the layer loop is unrolled and params cast to the compute dtype
    once, exactly as ``generate()`` prepares them). Sampling and greedy
    strategies are served; beam search stays on the lockstep path.
    """

    def __init__(self, model, params, gen_cfg: GenerationConfig,
                 num_slots: int = 4,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 rng: Optional[jax.Array] = None,
                 events_path: Optional[str] = None,
                 page_size: Optional[int] = None,
                 pool_pages: Optional[int] = None,
                 prefill_chunk_pages: int = 2,
                 prefix_sharing: bool = True,
                 host_pool_bytes: Optional[int] = None,
                 request_ttl_s: Optional[float] = None,
                 max_queue_depth: Optional[int] = None,
                 drain_on_sigterm: bool = False,
                 fault_injector: Optional[FaultInjector] = None,
                 device_loop_ticks: int = 1,
                 adapter_source=None):
        if gen_cfg.decode_strategy == "beam_search":
            raise ValueError(
                "GenerationServer serves sampling/greedy_search; beam "
                "search reorders the batch every step and stays on the "
                "lockstep generate() path")
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if device_loop_ticks < 1:
            raise ValueError(
                f"device_loop_ticks must be >= 1, got "
                f"{device_loop_ticks}")
        # device-resident decode: T > 1 routes step() through ONE
        # jitted decode_loop/verify_loop launch of up to T ticks per
        # host round-trip (docs/inference.md "Device-resident decode");
        # T = 1 keeps the original one-tick step() path byte-for-byte
        self._loop_ticks = int(device_loop_ticks)
        self._roundtrips = 0
        self._tiered = False
        model, params = _unrolled_twin(model, params)
        cfg = model.config
        # paged mode: explicit kwargs win, else the config's own
        # kv_page_size/kv_pool_pages turn it on; either way the model
        # is rebuilt on a twin config that carries the final values (a
        # pure dispatch change — parameters are untouched) and
        # GPTConfig.__post_init__ validates the composition
        self.paged = bool(page_size or pool_pages or cfg.kv_page_size)
        if self.paged:
            page_size = int(page_size or cfg.kv_page_size)
            if not pool_pages:
                # default pool: the contiguous layout's exact HBM
                # footprint (every slot at full capacity) + the null
                # page — same memory, paged indirection; density wins
                # come from passing a smaller pool explicitly
                pool_pages = cfg.kv_pool_pages or (
                    num_slots * (cfg.cache_capacity
                                 // max(page_size, 1)) + 1)
            cfg = _dc.replace(cfg, kv_page_size=page_size,
                              kv_pool_pages=int(pool_pages))
            model = type(model)(cfg)
            if prefill_chunk_pages < 1:
                raise ValueError(
                    f"prefill_chunk_pages must be >= 1, got "
                    f"{prefill_chunk_pages}")
            if cfg.max_kv_pages % prefill_chunk_pages:
                raise ValueError(
                    f"prefill_chunk_pages ({prefill_chunk_pages}) must "
                    f"divide max_kv_pages ({cfg.max_kv_pages}) so a "
                    f"padded prefill never outgrows the page table")
            self._page = cfg.kv_page_size
            self._max_pages = cfg.max_kv_pages
            self._chunk = self._page * prefill_chunk_pages
            if self._chunk > cfg.max_position_embeddings:
                raise ValueError(
                    f"prefill chunk ({self._chunk} tokens) exceeds "
                    f"max_position_embeddings "
                    f"{cfg.max_position_embeddings}")
            self._prefix_sharing = bool(prefix_sharing)
            # hierarchical KV cache (docs/inference.md): a bounded
            # pinned-host spill tier sized dtype-aware from a BYTE
            # budget, so int8 KV doubles its page capacity for free
            host_pages = 0
            if host_pool_bytes:
                if not self._prefix_sharing:
                    raise ValueError(
                        "host_pool_bytes requires prefix_sharing: the "
                        "spill tier holds only registry-reachable "
                        "pages")
                host_pages = pool_pages_for_bytes(
                    int(host_pool_bytes), cfg.num_layers,
                    cfg.num_attention_heads, cfg.head_dim, self._page,
                    cfg.kv_cache_dtype)
                if host_pages < 1:
                    raise ValueError(
                        f"host_pool_bytes ({host_pool_bytes}) smaller "
                        f"than one KV page")
            self._tiered = host_pages > 0
            self._alloc = PageAllocator(cfg.kv_pool_pages, self._page,
                                        host_pages=host_pages)
            if self._tiered:
                self._host_pool_bytes = int(host_pool_bytes)
                # pages whose LAST reference is held back as a spill
                # pin until the next yield-point drain (insertion
                # order = spill order)
                self._spill_pin: Dict[int, None] = {}
                # host id -> (residency generation, device_get'd page
                # tree); shared with the spill writer thread, every
                # access under _spill_lock. The generation tag keeps a
                # recycled host id's stale bytes (an old spill still
                # in the writer queue when the LRU evicted and reused
                # the id) from ever rehydrating as the new page's KV.
                self._host_data: Dict[int, Tuple[int, object]] = {}
                # (hpid, gen) pairs whose device_get failed on the
                # writer; the main loop evicts them at the next yield
                # point (_reap_failed_spills). Under _spill_lock.
                self._spill_failed: List[Tuple[int, int]] = []
                # a Condition, not a bare Lock: the rehydrate slow
                # path and prefix-store export WAIT on it for the
                # writer's publishes instead of joining the queue, so
                # the wait works from under the surface lock (the
                # writer never takes that lock)
                self._spill_lock = threading.Condition()
                #: writer items shipped but not yet published/failed;
                #: guarded by _spill_lock, notified on every change
                self._spill_outstanding = 0
                self._spill_q: queue.Queue = queue.Queue()
                self._spill_writer_thread = threading.Thread(
                    target=self._spill_writer, name="kv-spill-writer",
                    daemon=True)
                self._spill_writer_thread.start()
            self._pt = np.full((num_slots, self._max_pages), NULL_PAGE,
                               np.int32)
            self._pt_dev = jnp.asarray(self._pt)
            self._pt_dev_dec = self._pt_dev
            self._pt_dirty = False
            self._prefilling: deque = deque()
            self._admit_seq = 0
            self._prefill_chunk_count = 0
            #: prompt_key -> imported page ids pinned by kv_import
            #: until kv_import_release (cross-server KV handoff)
            self._imports: Dict[str, List[int]] = {}
        elif host_pool_bytes:
            raise ValueError(
                "host_pool_bytes requires paged mode (page_size/"
                "pool_pages): the spill tier holds KV pages")
        compute_dtype = jnp.dtype(cfg.dtype)
        if compute_dtype != jnp.float32:
            # same one-time cast as generate(): halve the per-token
            # parameter bandwidth of the decode tick; int8 kernels and
            # their fp32 "kernel_scale" dequant grids pass through
            # (quant_execution, docs/quantization.md)
            def _cast(path, p):
                name = getattr(path[-1], "key", "")
                if name == "kernel_scale" or not jnp.issubdtype(
                        p.dtype, jnp.floating):
                    return p
                return p.astype(compute_dtype)
            params = jax.tree_util.tree_map_with_path(_cast, params)
        self.model, self.params = model, params
        self._model_fp: Optional[str] = None
        self.gen_cfg = gen_cfg
        self.num_slots = num_slots
        # speculative decoding: the host draft source proposes, the
        # jitted verify_step scores/commits; spec-off is the plain
        # decode_step tick
        self.spec = gen_cfg.spec_method is not None
        self._spec_k = gen_cfg.spec_tokens
        self._draft = make_draft_source(gen_cfg.spec_method) \
            if self.spec else None
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._max_prompt = cfg.max_position_embeddings - gen_cfg.max_dec_len
        if self._max_prompt < 1:
            raise ValueError(
                f"max_dec_len ({gen_cfg.max_dec_len}) leaves no room "
                f"for prompts under max_position_embeddings "
                f"{cfg.max_position_embeddings}")
        buckets = tuple(sorted(set(
            prefill_buckets or default_prefill_buckets(self._max_prompt))))
        if buckets[-1] < self._max_prompt:
            buckets = buckets + (self._max_prompt,)
        self._buckets = buckets
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._cache = init_page_pool(model, params, num_slots) \
            if self.paged else init_slot_cache(model, params, num_slots)
        self._state = init_slot_state(num_slots, cfg.vocab_size)
        self._queue: deque = deque()
        self._slots: List[Optional[dict]] = [None] * num_slots
        self._next_id = 0
        self._nonce = 0
        self._counts = {"admitted": 0, "evicted": 0, "preempted": 0,
                        "shed": 0, "deadline_exceeded": 0}
        # multi-tenant LoRA (docs/lora.md): adapter_source maps
        # adapter id -> canonical adapter tree (core/adapters.py);
        # the cache LRUs loaded adapters in the params' HBM bank rows
        # with KV-page-style refcounts, and each slot's bank row rides
        # down with every tick as a traced [slots] array (the
        # per-slot adapter ids of the grouped LoRA GEMM). Without a
        # source the server serves the base model (adapter_ids=None —
        # zero delta, no grouped dispatch).
        self._adapters: Optional[AdapterCache] = None
        if adapter_source is not None:
            if not cfg.lora_rank:
                raise ValueError(
                    "adapter_source requires a LoRA model "
                    "(lora_rank > 0)")
            self._adapters = AdapterCache(cfg.lora_num_adapters,
                                          adapter_source)
            self._aid_np = np.zeros((num_slots,), np.int32)
            self._aid_dev = jnp.asarray(self._aid_np)
            self._aid_dirty = False
        #: admission-time request failures (e.g. unknown adapter id)
        #: surfaced as completions from the next step()
        self._dead: List[Completion] = []
        self._ticks = 0
        # graceful degradation (docs/robustness.md)
        self.request_ttl_s = request_ttl_s
        self.max_queue_depth = max_queue_depth
        self._draining = False
        self._submits = 0
        self._prev_sigterm = None
        self._sigterm_installed = False
        if drain_on_sigterm:
            try:
                self._prev_sigterm = signal.signal(
                    signal.SIGTERM, self._on_sigterm)
                self._sigterm_installed = True
            except ValueError:
                logger.warning(
                    "drain_on_sigterm: cannot install SIGTERM handler "
                    "outside the main thread; call drain() explicitly")
        self._decode_tokens = 0
        self._tick_time = 0.0
        #: the newest finished step's record (always on, in memory)
        self.last_step: Optional[StepRecord] = None
        #: durations of the last decoding steps: the slow-step baseline
        self._recent_steps: deque = deque(maxlen=SLOW_STEP_HISTORY)
        # latency histograms live in a server-local always-on registry
        # (summary percentiles must work with global telemetry off);
        # fixed-memory log buckets replace the old unbounded TTFT list
        self._metrics = metrics.MetricsRegistry(enabled=True)
        self._recorder = FlightRecorder(events_path) if events_path \
            else None
        self._tracer = Tracer(self._recorder)
        # async fleet surface (docs/fleet_serving.md "Async router"):
        # every public entry point that touches queue/slot/pool state
        # serializes on this re-entrant lock, so a fleet worker
        # thread can drive step()/prefill_step() while the router
        # thread calls submit()/kv_*()/summary() concurrently.
        # Blocking primitives never run under it: _drain_spills only
        # COLLECTS writer items into _spill_outbox, and the public
        # wrappers ship them to the spill queue after releasing the
        # lock (_ship_spills); writer waits go through the
        # _spill_lock condition, which the writer thread can always
        # take.
        self._surface_lock = threading.RLock()
        self._closed = False
        #: batched writer items _drain_spills collected this entry —
        #: surface-lock state, drained by _ship_spills
        self._spill_outbox: List[tuple] = []
        # /healthz is answered on the metrics server's per-request
        # threads while the main loop mutates queue/slot state, so the
        # payload is an immutable snapshot the main loop republishes
        # (_refresh_health) at its choke points; HTTP threads read the
        # snapshot under _health_lock and never touch live state
        self._health_lock = threading.Lock()
        self._health_snapshot = {
            "status": "ok", "slots": num_slots, "occupancy": 0,
            "pending": 0, "ticks": 0}
        # live /metrics + drain-aware /healthz when PFX_METRICS_PORT
        # is set; a no-op otherwise (docs/observability.md)
        self._metrics_server = obs_server.start_from_env(
            registry=self._metrics, health=self._health_state,
            events_path=events_path)
        self._faults = fault_injector if fault_injector is not None \
            else FaultInjector.from_env(recorder=self._recorder)
        self._watchdog = StepWatchdog.from_env(name="decode_tick",
                                               recorder=self._recorder)
        if self._tiered:
            # computed eagerly: the fingerprint's jax.device_get must
            # never run under the surface lock, so the locked
            # prefix-store paths read the cached value
            self._model_fingerprint()
        self._emit("serving_start", slots=num_slots,
                   buckets=list(buckets),
                   max_dec_len=gen_cfg.max_dec_len,
                   paged=self.paged,
                   page_size=self._page if self.paged else 0,
                   pool_pages=cfg.kv_pool_pages if self.paged else 0,
                   host_pages=self._alloc.host_pages
                   if self.paged else 0,
                   spec=self.spec,
                   spec_tokens=self._spec_k if self.spec else 0,
                   loop_ticks=self._loop_ticks,
                   adapter_rows=self._adapters.capacity
                   if self._adapters else 0)
        if self.paged:
            logger.info(
                "GenerationServer (paged): %d slots, %d-page pool of "
                "%d-token pages (capacity %d = %d pages/slot max), "
                "prefill chunk %d tokens, prefix sharing %s",
                num_slots, cfg.kv_pool_pages, self._page,
                cfg.cache_capacity, self._max_pages, self._chunk,
                self._prefix_sharing)
        else:
            logger.info(
                "GenerationServer: %d slots, prefill buckets %s, "
                "capacity %d (max_position_embeddings %d)", num_slots,
                list(buckets), cfg.cache_capacity,
                cfg.max_position_embeddings)

    # -- host bookkeeping ---------------------------------------------

    def _emit(self, event: str, **fields) -> None:
        if self._recorder is not None:
            self._recorder.emit(event, **fields)

    def _refresh_health(self) -> None:
        """Rebuild the ``/healthz`` payload from live state — main
        thread only — and publish it under the health lock. Called at
        the loop's choke points (submit, step end, drain entry,
        SIGTERM), so the served payload is at most one step stale."""
        payload = {"status": "draining" if self._draining else "ok",
                   "slots": self.num_slots,
                   "occupancy": self.occupancy,
                   "pending": self.pending, "ticks": self._ticks}
        with self._health_lock:
            self._health_snapshot = payload

    def _health_state(self) -> dict:
        """The ``/healthz`` payload: ``status`` flips to ``draining``
        the moment drain mode is entered (SIGTERM or :meth:`drain`),
        which answers HTTP 503 — the load balancer's stop-routing
        signal. Runs on HTTP threads: serves the last published
        snapshot, never live serving state."""
        with self._health_lock:
            return dict(self._health_snapshot)

    def health_snapshot(self) -> dict:
        """Thread-safe view of this server's health (the fleet router
        builds its own ``/healthz`` payload from these)."""
        return self._health_state()

    # -- per-request tracing (docs/observability.md) ------------------
    #
    # Every request owns a root span (req["span"]) plus ONE open phase
    # child (req["phase"]): queue -> prefill -> decode, looping back
    # to queue on preemption under the SAME trace id. With no event
    # stream the tracer hands out NULL_SPAN and all of this is no-op
    # attribute calls.

    def _begin_trace(self, req: dict,
                     trace_id: Optional[str] = None) -> None:
        req["span"] = self._tracer.start_trace(
            "serving/request", trace_id=trace_id, request=req["id"],
            prompt_len=len(req["prompt"]),
            resumed=bool(req["tokens"]) or None)
        req["phase"] = req["span"].start_span("serving/queue")
        req["queue_t0"] = time.time()

    def _phase(self, req: dict, name: str, **attrs) -> None:
        """End the open phase child and begin the next one."""
        req["phase"].end()
        req["phase"] = req["span"].start_span(name, **attrs)

    def _trace_id(self, req: dict) -> Optional[str]:
        span = req.get("span")
        return span.trace_id if span is not None else None

    def _observe_queue_wait(self, req: dict) -> None:
        """This queue EPISODE's wait (re-queues reset the clock)."""
        self._metrics.observe(
            "serving/queue_wait_ms",
            (time.time() - req.get("queue_t0", req["submit_t"]))
            * 1000.0)

    def _end_request_spans(self, req: dict, reason: str) -> None:
        """Close the open phase and the root span (idempotent; safe on
        requests that never had spans)."""
        phase = req.pop("phase", None)
        if phase is not None:
            phase.end(reason=reason)
        span = req.pop("span", None)
        if span is not None:
            span.end(reason=reason, tokens=len(req["tokens"]))
            req["span"] = span   # keep for _trace_id after eviction

    @property
    def occupancy(self) -> int:
        """Number of slots currently holding a live request."""
        with self._surface_lock:
            return sum(s is not None for s in self._slots)

    @property
    def pending(self) -> int:
        """Number of submitted requests still waiting for a slot."""
        with self._surface_lock:
            return len(self._queue)

    @property
    def draining(self) -> bool:
        """True once drain mode is entered (SIGTERM or :meth:`drain`)
        — the fleet router stops routing to a draining replica."""
        with self._surface_lock:
            return self._draining

    def work_pending(self) -> bool:
        """True while a :meth:`step` could make progress: queued
        admissions, an occupied slot, an unfinished chunked prefill,
        or tiered spill work (pinned pages awaiting their yield-point
        drain, or collected writer items awaiting shipment). Async
        fleet worker threads poll this to park when their replica is
        idle (docs/fleet_serving.md "Async router")."""
        with self._surface_lock:
            if self._queue or any(s is not None for s in self._slots):
                return True
            if self.paged and self._prefilling:
                return True
            if self._tiered and (self._spill_pin or
                                 self._spill_outbox):
                return True
            if self._dead:
                return True
            return False

    def check_alloc(self) -> None:
        """Assert the page allocator's invariants under the surface
        lock — the thread-safe spelling of the ``_alloc.check()``
        test hook (async fleet worker ticks mutate the allocator
        concurrently, so bare allocator reads race)."""
        with self._surface_lock:
            if self.paged:
                self._alloc.check()

    def submit(self, prompt: Sequence[int],
               deadline_s: Optional[float] = None,
               resume_tokens: Optional[Sequence[int]] = None,
               trace_id: Optional[str] = None,
               nonce: Optional[int] = None,
               adapter_id: int = 0) -> int:
        """Queue a request; returns its id. Raises ``ValueError`` when
        the prompt can never fit (``prompt + max_dec_len >
        max_position_embeddings``) — an oversized request must fail
        loudly at the door, not stall the queue — and
        :class:`RequestShed` when admission is refused (queue at
        ``max_queue_depth``, server draining, or an injected
        ``admit_fail`` fault).

        ``deadline_s`` bounds THIS request's wall-clock lifetime
        (queued time included), overriding the server-wide
        ``request_ttl_s``; on expiry it completes as
        ``deadline_exceeded`` with whatever tokens it earned.
        ``resume_tokens`` re-enters a partial from a drained/preempted
        completion (paged OR contiguous servers): admission re-prefills
        prompt+tokens and the sampling stream resumes at the preserved
        decode count, so a greedy resume is token-exact with the
        uninterrupted run. ``trace_id`` (with an event stream) links
        the new request's spans to an earlier timeline — pass
        ``Completion.trace_id`` back with ``resume_tokens`` so a
        drained-then-resumed request reads as ONE trace. ``nonce``
        overrides the server's own per-request sampling-nonce counter:
        a fleet router (core/fleet.py) assigns nonces in GLOBAL
        submission order so sampled draws are replica-independent and
        a failed-over request keeps its stream — leave it None
        everywhere else.

        ``adapter_id`` serves the request through that LoRA adapter
        (0 = base model): admission pins the adapter's bank row until
        eviction, and preemption/resume re-pins it, so a resumed
        request keeps decoding under the same weights token-exactly
        (docs/lora.md). Requires an ``adapter_source``.

        Thread-safe: serialized on the surface lock against a
        concurrently ticking fleet worker thread."""
        with self._surface_lock:
            return self._submit_impl(prompt, deadline_s, resume_tokens,
                                     trace_id, nonce, adapter_id)

    def _submit_impl(self, prompt: Sequence[int],
                     deadline_s: Optional[float],
                     resume_tokens: Optional[Sequence[int]],
                     trace_id: Optional[str],
                     nonce: Optional[int],
                     adapter_id: int = 0) -> int:
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self._max_prompt:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_dec_len "
                f"({self.gen_cfg.max_dec_len}) exceeds "
                f"max_position_embeddings "
                f"{self.model.config.max_position_embeddings}")
        tokens = [int(t) for t in resume_tokens or []]
        if tokens and len(tokens) >= self.gen_cfg.max_dec_len:
            raise ValueError(
                f"resume_tokens ({len(tokens)}) already meets "
                f"max_dec_len ({self.gen_cfg.max_dec_len})")
        adapter_id = int(adapter_id)
        if adapter_id < 0:
            raise ValueError(f"adapter_id must be >= 0, got "
                             f"{adapter_id}")
        if adapter_id and self._adapters is None:
            raise ValueError(
                "adapter_id requires an adapter_source (this server "
                "serves the base model only)")
        self._submits += 1
        if self._draining:
            return self._shed("draining")
        if self._faults is not None and \
                self._faults.fire("req", self._submits) == "admit_fail":
            return self._shed("fault")
        if self.max_queue_depth is not None and \
                len(self._queue) >= self.max_queue_depth:
            return self._shed("queue_depth")
        rid = self._next_id
        self._next_id += 1
        ttl = deadline_s if deadline_s is not None else \
            self.request_ttl_s
        req = {"id": rid, "prompt": prompt, "tokens": tokens,
               "adapter_id": adapter_id,
               "submit_t": time.time(),
               "deadline": time.time() + ttl
               if ttl is not None else None}
        if nonce is not None:
            # router-assigned: _place/_admit skip their own counter
            req["nonce"] = int(nonce)
        self._begin_trace(req, trace_id)
        self._queue.append(req)
        self._refresh_health()
        return rid

    def _shed(self, reason: str) -> int:
        """Refuse admission: count it, record it, raise."""
        self._counts["shed"] += 1
        metrics.inc("serving/shed")
        self._emit("serving_shed", reason=reason,
                   pending=self.pending, occupancy=self.occupancy)
        raise RequestShed(
            f"request shed ({reason}): {self.pending} queued, "
            f"{self.occupancy}/{self.num_slots} slots busy")

    def _on_sigterm(self, signum, frame) -> None:
        """Preemption notice: flip into drain mode — the in-progress
        :meth:`run`/:meth:`step` driver stops admitting and returns
        partials (mirroring the Engine's save-on-preemption
        contract). The surface lock is re-entrant, so a signal landing
        mid-step on the main thread re-acquires it safely."""
        with self._surface_lock:
            self._draining = True
            self._refresh_health()
            self._emit("serving_drain_start", signum=signum,
                       pending=self.pending, occupancy=self.occupancy)

    def _expire_deadlines(self) -> List[Completion]:
        """Evict every queued/running request whose deadline passed;
        the partial completes as ``deadline_exceeded`` — expiry is a
        RESULT the client sees, not a silent drop."""
        now = time.time()
        out: List[Completion] = []
        if any(r.get("deadline") is not None and now > r["deadline"]
               for r in self._queue):
            keep: deque = deque()
            for req in self._queue:
                dl = req.get("deadline")
                if dl is not None and now > dl:
                    self._counts["deadline_exceeded"] += 1
                    metrics.inc("serving/deadline_exceeded")
                    self._end_request_spans(req, "deadline_exceeded")
                    self._emit("serving_evict", request=req["id"],
                               slot=-1, reason="deadline_exceeded",
                               tokens=len(req["tokens"]),
                               trace=self._trace_id(req))
                    out.append(Completion(
                        request_id=req["id"], prompt=req["prompt"],
                        tokens=req["tokens"],
                        finish_reason="deadline_exceeded",
                        trace_id=self._trace_id(req)))
                else:
                    keep.append(req)
            self._queue = keep
        for slot, req in enumerate(self._slots):
            if req is not None and req.get("deadline") is not None \
                    and now > req["deadline"]:
                self._counts["deadline_exceeded"] += 1
                metrics.inc("serving/deadline_exceeded")
                out.append(self._evict(slot, "deadline_exceeded"))
        return out

    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        # buckets cover PROMPT lengths; a resume's prompt+tokens can
        # exceed the largest one — compile that exact shape (resumes
        # are rare enough that a one-off shape beats a new bucket)
        return n

    # -- adapter cache (multi-tenant LoRA, docs/lora.md) --------------
    #
    # The host maps each slot to the bank ROW of its request's adapter
    # (_aid_np, row 0 = base/zero adapter) and uploads the int32
    # [slots] array to ride down with every tick — the grouped LoRA
    # GEMM's per-slot ids. Rows are refcounted by the AdapterCache:
    # pinned at admission, released at evict/preempt, LRU-evicted only
    # at refcount 0. A request whose adapter cannot claim a row yet
    # blocks the queue HEAD, exactly like page starvation.

    def _adapter_admissible(self, req: dict) -> bool:
        aid = req.get("adapter_id", 0)
        if not aid or self._adapters is None:
            return True
        return self._adapters.can_admit(aid)

    def _acquire_adapter(self, req: dict, slot: int) -> None:
        """Pin the request's adapter and point ``slot`` at its bank
        row (row 0 for base requests). On a miss the loaded tree is
        written into the live params' bank. Raises ``KeyError`` for
        an unknown adapter id — the caller fails the admission."""
        if self._adapters is None:
            return
        aid = req.get("adapter_id", 0)
        if not aid:
            if self._aid_np[slot] != 0:
                self._aid_np[slot] = 0
                self._aid_dirty = True
            return
        lease = self._adapters.acquire(aid)
        if lease.evicted is not None:
            self._emit("serving_adapter_evict", adapter=lease.evicted,
                       row=lease.row)
        if lease.tree is not None:
            # cast-on-insert: the bank leaves already carry the
            # server's compute dtype. The unlocked params read in
            # _model_fingerprint cannot race this write: the
            # fingerprint is computed eagerly at __init__, before any
            # request (or router thread) exists.
            self.params = insert_adapter(  # pfxlint: disable=PFX301
                self.params, lease.tree, lease.row)
            self._emit("serving_adapter_load", adapter=aid,
                       row=lease.row, request=req["id"])
        if self._aid_np[slot] != lease.row:
            self._aid_np[slot] = lease.row
            self._aid_dirty = True

    def _release_adapter(self, slot: int, req: dict) -> None:
        """Unpin a departing request's adapter (stays resident/warm at
        refcount 0) and park the slot back on the zero row."""
        if self._adapters is None:
            return
        aid = req.get("adapter_id", 0)
        if aid:
            self._adapters.release(aid)
        if self._aid_np[slot] != 0:
            self._aid_np[slot] = 0
            self._aid_dirty = True

    def _fail_admission(self, req: dict, reason: str) -> None:
        """An admission-time request failure (unknown adapter id):
        complete the request with its partial tokens instead of
        wedging the queue."""
        self._counts["evicted"] += 1
        metrics.inc("serving/evicted")
        self._end_request_spans(req, reason)
        self._emit("serving_evict", request=req["id"], slot=-1,
                   reason=reason, tokens=len(req["tokens"]),
                   trace=self._trace_id(req))
        self._dead.append(Completion(
            request_id=req["id"], prompt=req["prompt"],
            tokens=req["tokens"], finish_reason=reason,
            trace_id=self._trace_id(req)))

    def _take_dead(self) -> List[Completion]:
        out, self._dead = self._dead, []
        return out

    def _sync_aid(self) -> None:
        if self._adapters is not None and self._aid_dirty:
            self._aid_dev = jnp.asarray(self._aid_np)
            self._aid_dirty = False

    def _aid_arg(self):
        """The traced per-slot adapter-row array for tick launches —
        None on base-only servers (skips the LoRA compute entirely)."""
        return self._aid_dev if self._adapters is not None else None

    def _admit(self) -> None:
        """Move queued requests into free slots."""
        if self.paged:
            self._admit_paged()
            return
        while self._queue and None in self._slots:
            req = self._queue[0]
            if not self._adapter_admissible(req):
                # every bank row pinned by a live slot: block the
                # queue head until an eviction releases one (the
                # page-starvation rule)
                break
            self._queue.popleft()
            slot = self._slots.index(None)
            try:
                self._acquire_adapter(req, slot)
            except KeyError:
                self._fail_admission(req, "adapter_missing")
                continue
            # resume re-entry: prefill prompt + already-emitted tokens
            # (same contract as paged re-admission), then restore the
            # decode count below so the sampling stream and length
            # budget continue exactly where the partial stopped
            seq = req["prompt"] + req["tokens"]
            bucket = self._bucket_for(len(seq))
            self._observe_queue_wait(req)
            self._phase(req, "serving/prefill", slot=slot)
            row = np.full((1, bucket), self.gen_cfg.pad_token_id,
                          np.int32)
            row[0, :len(seq)] = seq
            if "nonce" not in req:
                req["nonce"] = self._nonce
                self._nonce += 1
            self._cache, self._state = prefill_into_slots(
                self.model, self.params, self._cache, self._state,
                jnp.asarray([slot], jnp.int32), jnp.asarray(row),
                jnp.asarray([len(seq)], jnp.int32),
                jnp.asarray([req["nonce"]], jnp.int32),
                jnp.asarray([int(self._aid_np[slot])], jnp.int32)
                if self._adapters is not None else None)
            if req["tokens"]:
                self._state = self._state._replace(
                    dec_count=self._state.dec_count.at[slot].set(
                        len(req["tokens"])))
            self._slots[slot] = req
            self._counts["admitted"] += 1
            metrics.inc("serving/admitted")
            self._emit("serving_admit", request=req["id"], slot=slot,
                       prompt_len=len(req["prompt"]), bucket=bucket,
                       trace=self._trace_id(req))
            self._phase(req, "serving/decode", slot=slot)

    # -- paged scheduling ---------------------------------------------
    #
    # The host is the single owner of every paging decision: the numpy
    # page-table master + PageAllocator refcounts live here, and the
    # device only ever sees shape-stable jitted ops (chunk prefill,
    # page copy, decode tick) driven by uploaded int32 tables. Two
    # device views of the table exist: the full one (prefill reads
    # shared/owned pages of a still-inactive slot) and the decode one,
    # where every non-ACTIVE slot's row is nulled so an inactive slot's
    # dead decode write lands in the reserved garbage page instead of
    # a page another request is still prefilling or sharing.

    def _sync_pt(self) -> None:
        if not self._pt_dirty:
            return
        self._pt_dev = jnp.asarray(self._pt)
        act = np.zeros((self.num_slots, 1), bool)
        for s, r in enumerate(self._slots):
            if r is not None and r.get("active"):
                act[s, 0] = True
        self._pt_dev_dec = jnp.asarray(
            np.where(act, self._pt, NULL_PAGE).astype(np.int32))
        self._pt_dirty = False

    def _place(self, req: dict, slot: int, num_pages: int) -> None:
        """Common bookkeeping of both paged admission paths."""
        if "nonce" not in req:
            # assigned once per REQUEST: a preempted-then-readmitted
            # request keeps its nonce (and its dec_count = emitted
            # tokens), so its sampling stream resumes exactly where
            # preemption cut it
            req["nonce"] = self._nonce
            self._nonce += 1
        req["num_pages"] = num_pages
        req["active"] = False
        req["admit_seq"] = self._admit_seq
        self._admit_seq += 1
        self._slots[slot] = req
        self._counts["admitted"] += 1
        metrics.inc("serving/admitted")
        self._observe_queue_wait(req)
        self._phase(req, "serving/prefill", slot=slot)

    def _activate(self, slot: int, last_logits_row) -> None:
        """Flip a placed slot live: per-slot SlotState from the host's
        view of the request (seq = prompt + already-emitted tokens, so
        resumes re-enter mid-request)."""
        req = self._slots[slot]
        seq = req["prompt"] + req["tokens"]
        appeared = np.zeros((self.model.config.vocab_size,), bool)
        appeared[np.asarray(seq, np.int64)] = True
        self._state = activate_slot(
            self._state, jnp.int32(slot), jnp.int32(len(seq)),
            jnp.int32(len(req["tokens"])), jnp.int32(req["nonce"]),
            jnp.asarray(appeared),
            jnp.asarray(last_logits_row, jnp.float32),
            jnp.int32(req.pop("spec_rejected", -1)))
        req["active"] = True
        req["cur_len"] = len(seq)
        self._pt_dirty = True   # decode view must unhide this row
        self._phase(req, "serving/decode", slot=slot)

    def _admit_paged(self) -> None:
        """Paged admission: whole-prompt registry hit -> share every
        page and activate with zero prefill; else map shared prefix
        pages + freshly allocated owned pages and queue the slot for
        chunked prefill. The queue HEAD blocks when the pool cannot
        cover its owned pages yet — admitting smaller later requests
        over it would starve long prompts."""
        while self._queue and None in self._slots:
            req = self._queue[0]
            if not self._adapter_admissible(req):
                # every adapter row pinned: block the queue head until
                # an eviction releases one (the starvation rule shared
                # with the owned-pages check below)
                break
            seq = req["prompt"] + req["tokens"]
            L = len(seq)
            slot = self._slots.index(None)
            # prefix/prompt registries hold BASE-model KV: a non-zero
            # adapter changes every layer's KV for the same tokens, so
            # adapter requests neither share nor (in _prefill_pump)
            # register pages — correctness, not policy (docs/lora.md)
            share = self._prefix_sharing and not req.get("adapter_id")
            hit = self._alloc.lookup_prompt(prompt_key(seq)) \
                if share else None
            if hit is not None:
                pages, last = hit
                host_ids = [p for p in pages
                            if self._alloc.is_host(p)]
                n_host = len(host_ids)
                if n_host and self._alloc.free_pages < n_host:
                    # rehydration needs fresh HBM pages — block the
                    # queue head until they free (same starvation rule
                    # as the chunked path's owned-pages check)
                    break
                self._queue.popleft()
                try:
                    self._acquire_adapter(req, slot)
                except KeyError:
                    self._fail_admission(req, "adapter_missing")
                    continue
                try:
                    # every spilled page of the hit comes back in ONE
                    # stacked scatter; each fresh id's refcount-1
                    # reference belongs to this request
                    promoted = dict(zip(
                        host_ids, self._rehydrate_many(host_ids)))
                except _RehydrateMiss:
                    # a failed spill surfaced mid-batch: nothing was
                    # mapped yet (the batch allocates only once every
                    # page's bytes arrived) and the reap dropped the
                    # dead page's registrations, so the retry
                    # re-prefills cold on the next pass
                    self._drop_evicted_host_data()
                    self._release_adapter(slot, req)
                    self._queue.appendleft(req)
                    continue
                mapped = []
                for pid in pages:
                    if pid in promoted:
                        mapped.append(promoted[pid])
                    else:
                        self._alloc.retain(pid)
                        mapped.append(pid)
                self._pt[slot, :] = NULL_PAGE
                self._pt[slot, :len(mapped)] = mapped
                self._pt_dirty = True
                self._alloc.stats["prompt_hits"] += 1
                metrics.inc("serving/prefix_hits")
                self._place(req, slot, num_pages=len(mapped))
                self._activate(slot, last)
                self._emit("serving_admit", request=req["id"],
                           slot=slot, prompt_len=L, mode="prompt_hit",
                           shared_pages=len(mapped),
                           rehydrated=n_host or None,
                           trace=self._trace_id(req))
                continue
            shared_pids: List[int] = []
            if share:
                # share only FULL pages strictly before the one
                # holding the last prompt token: that page must
                # recompute locally so the first sampling logits exist
                for kk in page_prefix_keys(
                        seq, self._page)[:(L - 1) // self._page]:
                    pid = self._alloc.lookup_prefix(kk)
                    if pid is None:
                        break
                    shared_pids.append(pid)
                # chunked prefill resumes at a CHUNK boundary: keep
                # only a chunk-aligned count of shared pages, or the
                # chunk-rounded tail below outgrows the page table
                # (start + n_chunks*chunk can exceed cache_capacity
                # when start is mid-chunk) — the dropped pages just
                # recompute locally with the rest of the prompt
                cpp = self._chunk // self._page
                del shared_pids[len(shared_pids) - len(shared_pids) % cpp:]
            start = len(shared_pids) * self._page
            n_chunks = -(-(L - start) // self._chunk)
            total_pages = (start + n_chunks * self._chunk) // self._page
            n_host = sum(1 for p in shared_pids
                         if self._alloc.is_host(p))
            # host-resident shared pages need fresh HBM ids on top of
            # the owned pages the chunked tail allocates
            if self._alloc.free_pages < \
                    total_pages - len(shared_pids) + n_host:
                break
            self._queue.popleft()
            try:
                self._acquire_adapter(req, slot)
            except KeyError:
                self._fail_admission(req, "adapter_missing")
                continue
            self._pt[slot, :] = NULL_PAGE
            host_ids = [p for p in shared_pids
                        if self._alloc.is_host(p)]
            try:
                promoted = dict(zip(
                    host_ids, self._rehydrate_many(host_ids)))
            except _RehydrateMiss:
                # same unwind as the prompt-hit path: the dead prefix
                # page's registration is gone, so the retry shares
                # fewer pages and prefills the rest
                self._drop_evicted_host_data()
                self._release_adapter(slot, req)
                self._queue.appendleft(req)
                continue
            for j, pid in enumerate(shared_pids):
                if pid in promoted:
                    pid = promoted[pid]
                else:
                    self._alloc.retain(pid)
                self._pt[slot, j] = pid
            for j in range(len(shared_pids), total_pages):
                self._pt[slot, j] = self._alloc.alloc()
            self._pt_dirty = True
            if shared_pids:
                self._alloc.stats["prefix_hits"] += len(shared_pids)
                metrics.inc("serving/prefix_hits", len(shared_pids))
            self._place(req, slot, num_pages=total_pages)
            req["prefill_pos"] = start
            self._prefilling.append(slot)
            self._emit("serving_admit", request=req["id"], slot=slot,
                       prompt_len=L, mode="chunked",
                       shared_pages=len(shared_pids), chunks=n_chunks,
                       rehydrated=n_host or None,
                       trace=self._trace_id(req))

    def _prefill_pump(self, rec: StepRecord) -> None:
        """Run at most ONE page-aligned prefill chunk per step — the
        oldest still-prefilling slot advances while everyone else's
        decode tick proceeds, so a long admission never freezes
        tokens/s (the chunked-prefill contract of ROADMAP item 1).

        Phases: ``prefill_pump`` is the host side up to and including
        the chunk's dispatch, and the slot's activation after a
        prompt's last chunk; ``prefill_harvest`` between them is the
        read of that chunk's last logits row, a device sync."""
        ph = rec.phases
        with annotate("serving/step/prefill_pump", ph):
            if not self._prefilling:
                return
            slot = self._prefilling[0]
            req = self._slots[slot]
            seq = req["prompt"] + req["tokens"]
            L = len(seq)
            c0 = req["prefill_pos"]
            row = np.full((1, self._chunk), self.gen_cfg.pad_token_id,
                          np.int32)
            row[0, :len(seq[c0:c0 + self._chunk])] = \
                seq[c0:c0 + self._chunk]
            self._sync_pt()
            self._cache, logits = prefill_chunk_paged(
                self.model, self.params, self._cache, jnp.asarray(row),
                jnp.asarray([c0], jnp.int32),
                self._pt_dev[slot:slot + 1],
                jnp.asarray([int(self._aid_np[slot])], jnp.int32)
                if self._adapters is not None else None)
            req["prefill_pos"] = c0 + self._chunk
            self._prefill_chunk_count += 1
            rec.chunks += 1
            metrics.inc("serving/prefill_chunks")
            self._emit("serving_prefill_chunk", request=req["id"],
                       slot=slot, start=c0,
                       tokens=min(self._chunk, L - c0),
                       trace=self._trace_id(req))
            if req["prefill_pos"] < L:
                return
            self._prefilling.popleft()
            del req["prefill_pos"]
            # the chunk-rounded admission allocated pages for the final
            # chunk's pad tail too; that KV is never read, so hand
            # those pages straight back to the pool instead of pinning
            # them (and the registries below) until evict
            used = -(-L // self._page)
            if used < req["num_pages"]:
                for j in range(used, req["num_pages"]):
                    self._release_page(int(self._pt[slot, j]))
                    self._pt[slot, j] = NULL_PAGE
                req["num_pages"] = used
                self._pt_dirty = True
        with annotate("serving/step/prefill_harvest", ph):
            # the last real token sits at chunk row L - 1 - c0
            last = np.asarray(logits[0, L - 1 - c0])
        with annotate("serving/step/prefill_pump", ph):
            self._activate(slot, last)
            # adapter-tinted KV must never enter the shared registries
            # (_admit_paged's share rule — base-only content
            # addressing)
            if self._prefix_sharing and not req.get("adapter_id"):
                keys = page_prefix_keys(seq, self._page)
                for j, kk in enumerate(keys):
                    self._alloc.register_prefix(
                        kk, int(self._pt[slot, j]))
                self._alloc.register_prompt(
                    prompt_key(seq),
                    [int(p) for p in self._pt[slot, :req["num_pages"]]],
                    last)

    def _release_pages(self, slot: int) -> None:
        req = self._slots[slot]
        for j in range(req.get("num_pages", 0)):
            pid = int(self._pt[slot, j])
            if pid != NULL_PAGE:
                self._release_page(pid)
        self._pt[slot, :] = NULL_PAGE
        self._pt_dirty = True
        req["num_pages"] = 0

    # -- hierarchical KV cache: HBM -> pinned-host spill tier ---------
    #
    # With host_pool_bytes set, a REGISTERED page's last reference is
    # never dropped outright: _release_page keeps it as a spill pin,
    # and _drain_spills — called only at the host yield point (step
    # entry, between device launches) — gathers the page's KV on
    # device, moves its registrations onto a host-tier id
    # (PageAllocator.spill) and frees the HBM page. The blocking
    # device->host copy happens on a background writer thread
    # (_spill_writer), so decode ticks never wait on a spill. A later
    # registry hit rehydrates: fresh HBM page, scatter the staged
    # bytes, move the registrations back (promote) — the same
    # export-pin -> gather -> remap -> scatter contract as the fleet
    # KV handoff, pointed at this server's own host tier. COW safety
    # is structural: host ids never appear in any page table, so a
    # divergent write can only target an HBM page and the host copy is
    # never mutated. Thread discipline mirrors _health_lock: the
    # writer touches ONLY the spill queue and the _spill_lock-guarded
    # _host_data dict; allocator, cache, and telemetry stay with the
    # main loop.

    def _spill_writer(self) -> None:
        """Background spill writer: stage each batched writer item —
        ONE stacked :func:`gather_kv_pages` tree covering every page
        of a yield's drain — to host memory with a single
        ``jax.device_get`` (the device sync the decode tick must
        never pay), split it back into per-page trees, and publish
        each under the spill condition, tagged with its host id's
        residency generation. The outstanding count drops and the
        condition notifies on EVERY path, success or failure: the
        rehydrate slow path and prefix-store export wait for
        ``outstanding == 0`` instead of joining the queue, and a
        writer that died mid-item must never strand them. A failed
        stage records every page of the batch instead (the main loop
        evicts those host pages at the next yield point, so the loss
        surfaces as a cold re-prefill, never a hang or wrong KV).
        ``None`` is the shutdown sentinel (:meth:`close`)."""
        tl = timeline.track("kv-spill-writer")
        while True:
            t0 = tl.begin()
            item = self._spill_q.get()
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
                with self._spill_lock:
                    self._spill_failed.extend(entries)
                    self._spill_outstanding -= 1
                    self._spill_lock.notify_all()
                tl.add("spill_device_get", t0)
                continue
            with self._spill_lock:
                for (hpid, gen), page in zip(entries, pages):
                    cur = self._host_data.get(hpid)
                    if cur is None or cur[0] <= gen:
                        # never let a stale residency's late publish
                        # clobber a recycled id's fresher bytes
                        self._host_data[hpid] = (gen, page)
                self._spill_outstanding -= 1
                self._spill_lock.notify_all()
            tl.add("spill_device_get", t0)

    def _release_page(self, pid: int) -> None:
        """Release one reference to a slot-mapped page. In tiered mode
        a registered page's LAST reference becomes a spill pin instead
        of freeing — the page stays whole until :meth:`_drain_spills`
        moves it to the host tier at the next yield point."""
        if self._tiered and pid not in self._spill_pin and \
                self._alloc.refcount(pid) == 1 and \
                self._alloc.page_registered(pid):
            self._spill_pin[pid] = None
            return
        self._alloc.release(pid)
        if self._tiered:
            self._drop_evicted_host_data()

    def _drop_evicted_host_data(self) -> None:
        """Forget the staged bytes of host pages the allocator evicted
        (LRU pressure, orphan sweep, failed spill) — before their ids
        are reused. Generation-checked: if an evicted id was already
        recycled AND the writer already published the new residency's
        bytes, those bytes are live and must survive this drain."""
        evicted = self._alloc.pop_host_evicted()
        if not evicted:
            return
        with self._spill_lock:
            for hpid in evicted:
                entry = self._host_data.get(hpid)
                if entry is not None and \
                        entry[0] != self._alloc.host_generation(hpid):
                    del self._host_data[hpid]

    def _reap_failed_spills(self) -> None:
        """Evict host pages whose spill stage failed on the writer
        thread (their bytes never reached host memory): drop the
        registrations pointing at them so no lookup can hand out a
        page that cannot rehydrate. Main loop only — the writer
        records failures, it never touches the allocator."""
        with self._spill_lock:
            failed, self._spill_failed = self._spill_failed, []
        for hpid, gen in failed:
            # gen guard: the failed residency may already be gone and
            # the id recycled — never evict the successor
            if self._alloc.host_generation(hpid) == gen:
                self._alloc.evict_host(hpid)
                metrics.inc("serving/spill_failed")
        if failed:
            self._drop_evicted_host_data()

    def _pop_host_bytes(self, hpid: int, gen: int):
        """Pop the staged bytes of the CURRENT residency of ``hpid``,
        or None when they are not published yet. An entry tagged with
        an older generation is a recycled id's stale spill whose
        publish raced the eviction drain — discard it (its residency
        is dead) and report a miss; the writer queue is FIFO, so after
        ``_spill_q.join()`` the live generation's bytes are the ones
        in place."""
        with self._spill_lock:
            entry = self._host_data.get(hpid)
            if entry is None:
                return None
            del self._host_data[hpid]
            if entry[0] != gen:
                return None
            return entry[1]

    def _drain_spills(self) -> None:
        """Collect every pinned spill into ONE batched writer item:
        per page, move its registrations to a host id and free the
        HBM page; then gather ALL spilled pages' KV in a single
        stacked dispatch (async — the blocking copy runs on the
        writer thread) and append the item to the spill outbox. Runs
        under the surface lock at the step-entry yield point only;
        the public wrappers ship the outbox to the writer queue AFTER
        releasing the lock (:meth:`_ship_spills`), so the queue put
        never runs under a lock. The event-timeline contract is
        unchanged: every ``serving_spill`` pairs with the
        ``serving_yield`` that opened the drain. Freeing the page ids
        before the gather is safe — nothing allocates between, and
        later decode writes build NEW functional cache arrays while
        the dispatched gather keeps referencing these buffers."""
        if not self._tiered:
            return
        self._reap_failed_spills()
        if not self._spill_pin:
            return
        self._emit("serving_yield", ticks=self._ticks,
                   roundtrips=self._roundtrips,
                   pending_spills=len(self._spill_pin))
        spilled: List[int] = []
        entries: List[Tuple[int, int]] = []
        while self._spill_pin:
            pid = next(iter(self._spill_pin))   # FIFO: oldest pin first
            del self._spill_pin[pid]
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
                self._drop_evicted_host_data()
                continue
            gen = self._alloc.host_generation(hpid)
            self._drop_evicted_host_data()
            spilled.append(pid)
            entries.append((hpid, gen))
            metrics.inc("serving/spill")
            self._emit("serving_spill", page=pid, host_page=hpid,
                       ticks=self._ticks, roundtrips=self._roundtrips)
        if spilled:
            data = gather_kv_pages(self._cache,
                                   jnp.asarray(spilled, jnp.int32))
            self._spill_outbox.append((entries, data))
        metrics.get_registry().set_gauge(
            "serving/host_pages", self._alloc.host_pages_resident)

    def _ship_spills(self) -> None:
        """Hand the writer items :meth:`_drain_spills` collected to
        the spill queue. Called by the public wrappers AFTER the
        surface lock is released — the outstanding-count bump and the
        queue puts are the only cross-thread edges, and neither runs
        under it."""
        with self._surface_lock:
            items, self._spill_outbox = self._spill_outbox, []
        if not items:
            return
        with self._spill_lock:
            self._spill_outstanding += len(items)
        for item in items:
            self._spill_q.put(item)

    #: upper bound on waiting for the writer to publish a page's
    #: bytes at rehydrate/export time — generous next to a single
    #: device_get, only ever reached if the writer thread died
    _SPILL_WAIT_S = 30.0

    def _outbox_page(self, hpid: int, gen: int):
        """A page's device tree from a writer item still sitting in
        the spill outbox — a spill collected THIS step entry whose
        ship happens only after the surface lock releases. Rehydrating
        straight from the pending gather skips the host round trip;
        the item stays queued untouched (its eventual publish of this
        residency is discarded by the generation guards once the
        promote recycles the id)."""
        for entries, data in self._spill_outbox:
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
        under it and never takes the surface lock, so waiting here
        from under the surface lock cannot deadlock."""
        deadline = time.monotonic() + self._SPILL_WAIT_S
        with self._spill_lock:
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
                elif (hpid, gen) in self._spill_failed:
                    return None
                elif self._spill_outstanding == 0:
                    return None
                if time.monotonic() >= deadline:
                    return None
                self._spill_lock.wait(timeout=0.05)

    def _rehydrate_many(self, hpids: Sequence[int]) -> List[int]:
        """Bring N host-resident pages back into HBM with ONE stacked
        scatter: pop (or await) every page's staged bytes, allocate N
        fresh page ids, scatter the stacked tree in a single
        dispatch, and move each page's registrations back. Every
        fresh page's refcount-1 reference belongs to the admitting
        request; the callers check ``free_pages`` first, so the
        allocs always succeed. Raises :class:`_RehydrateMiss` — with
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
            with self._spill_lock:
                for hpid, gen, data in popped:
                    self._host_data[hpid] = (gen, data)
            # the one legitimate way here: the spill's device_get
            # failed on the writer after this page was looked up but
            # before the failure was reaped. Reap now (evicts the
            # page, drops its registrations) and let admission unwind
            # — the prompt re-prefills cold. Anything else is an
            # invariant bug and must fail loudly.
            self._reap_failed_spills()
            if self._alloc.is_host(miss):
                raise RuntimeError(
                    f"host page {miss} resident but its bytes are "
                    f"gone")
            raise _RehydrateMiss(miss)
        pids = self._alloc.alloc_many(len(popped))
        stacked = stack_kv_pages([d for _, _, d in popped])
        self._cache = scatter_kv_pages(
            self._cache, stacked, jnp.asarray(pids, jnp.int32))
        for (hpid, _, _), pid in zip(popped, pids):
            self._alloc.promote(hpid, pid)
            self._emit("serving_rehydrate", host_page=hpid, page=pid,
                       ticks=self._ticks)
        metrics.inc("serving/rehydrate", len(pids))
        self._metrics.observe("serving/rehydrate_ms",
                              (time.time() - t0) * 1000.0)
        metrics.get_registry().set_gauge(
            "serving/host_pages", self._alloc.host_pages_resident)
        return pids

    def _alloc_or_preempt(self, needy_slot: int) -> int:
        """A free page, preempting the youngest OTHER occupied slot
        (whole request back to the queue HEAD, pages released) until
        one exists. Config validation guarantees a lone slot can
        always grow to its maximum length, so this terminates."""
        pid = self._alloc.try_alloc()
        while pid is None:
            if self._tiered and self._spill_pin:
                # a pinned to-be-spilled page is idle KV: reclaiming
                # it costs one lost spill, never a preemption (and
                # keeps the pin set from deadlocking the pool)
                held = next(iter(self._spill_pin))
                del self._spill_pin[held]
                self._alloc.release(held)
                self._drop_evicted_host_data()
                pid = self._alloc.try_alloc()
                continue
            victims = [s for s, r in enumerate(self._slots)
                       if r is not None and s != needy_slot]
            if not victims:
                raise PagePoolExhausted(
                    f"slot {needy_slot} needs a page with none free "
                    f"and no one to preempt (pool "
                    f"{self._alloc.num_pages} pages)")
            victim = max(victims,
                         key=lambda s: self._slots[s]["admit_seq"])
            self._preempt_slot(victim)
            pid = self._alloc.try_alloc()
        return pid

    def _preempt_slot(self, victim: int) -> None:
        """Kick a request off the device to reclaim its pages, keeping
        its host state (emitted tokens, nonce) intact; re-admission
        prefills prompt+tokens and resumes the sampling stream at the
        preserved dec_count — token-for-token as if never preempted."""
        req = self._slots[victim]
        if req.get("active") and self.spec:
            # a pending rejection-residual exclusion must survive the
            # round trip or the resumed stream's next draw is biased
            req["spec_rejected"] = int(
                np.asarray(self._state.rejected)[victim])
        self._release_pages(victim)
        # the pin drops but the adapter stays resident/warm —
        # re-admission re-pins it (a hit) and resumes token-exactly
        self._release_adapter(victim, req)
        if victim in self._prefilling:
            self._prefilling.remove(victim)
        self._slots[victim] = None
        self._state = self._state._replace(
            active=self._state.active.at[victim].set(False),
            finished=self._state.finished.at[victim].set(False))
        req["active"] = False
        req.pop("prefill_pos", None)
        # the SAME root span survives the round trip: the running
        # phase ends as preempted and a fresh queue phase opens, so
        # the whole preempt-resume life is one trace id
        self._phase(req, "serving/queue", requeued=True)
        req["queue_t0"] = time.time()
        self._queue.appendleft(req)
        self._counts["preempted"] += 1
        metrics.inc("serving/preempted")
        self._emit("serving_preempt", request=req["id"], slot=victim,
                   reason="pages", tokens=len(req["tokens"]),
                   trace=self._trace_id(req))

    def _page_maintenance(self, window: int = 1) -> None:
        """Before every decode tick: each active slot's next ``window``
        write positions (``cur_len .. cur_len + window - 1`` — one for
        a plain tick, k+1 for a verify tick) must land in pages it owns
        exclusively — map fresh pages at page boundaries, and split
        shared pages copy-on-write (device page copy + host refcount
        handoff) at the first divergent write. Pages mapped for window
        positions past a verify tick's accepted point are returned to
        the pool by the post-tick rollback in :meth:`step`."""
        for slot in range(self.num_slots):
            req = self._slots[slot]
            if req is None or not req.get("active"):
                continue
            for w in range(window):
                pos = req["cur_len"] + w
                if pos >= self.model.config.cache_capacity:
                    # length bound enforced at submit; a verify
                    # window's tail past capacity clips to
                    # capacity - 1 and is never committed (mmax)
                    break
                j = pos // self._page
                if j >= req["num_pages"]:
                    self._pt[slot, j] = self._alloc_or_preempt(slot)
                    req["num_pages"] = j + 1
                    self._pt_dirty = True
                else:
                    pid = int(self._pt[slot, j])
                    if self._alloc.refcount(pid) > 1:
                        new = self._alloc_or_preempt(slot)
                        self._cache = copy_kv_pages(
                            self._cache, jnp.asarray([pid], jnp.int32),
                            jnp.asarray([new], jnp.int32))
                        self._release_page(pid)
                        self._pt[slot, j] = new
                        self._pt_dirty = True
                        self._alloc.stats["cow_splits"] += 1
                        metrics.inc("serving/cow_splits")
                        self._emit("serving_cow_split",
                                   request=req["id"], slot=slot,
                                   page=j, src=pid, dst=new)

    def _evict(self, slot: int, reason: str) -> Completion:
        req = self._slots[slot]
        if self.paged:
            self._release_pages(slot)
            if slot in self._prefilling:
                self._prefilling.remove(slot)
        self._release_adapter(slot, req)
        self._slots[slot] = None
        self._state = self._state._replace(
            active=self._state.active.at[slot].set(False),
            finished=self._state.finished.at[slot].set(False))
        self._counts["evicted"] += 1
        metrics.inc("serving/evicted")
        if reason == "preempted":
            self._counts["preempted"] += 1
            metrics.inc("serving/preempted")
        ft = req.get("first_tok_t")
        if ft is not None and len(req["tokens"]) > 1:
            # steady-state decode latency: wall time past the first
            # token over the tokens it bought
            self._metrics.observe(
                "serving/tpot_ms",
                (time.time() - ft) * 1000.0
                / (len(req["tokens"]) - 1))
        self._end_request_spans(req, reason)
        self._emit("serving_evict", request=req["id"], slot=slot,
                   reason=reason, tokens=len(req["tokens"]),
                   trace=self._trace_id(req))
        return Completion(request_id=req["id"], prompt=req["prompt"],
                          tokens=req["tokens"], finish_reason=reason,
                          trace_id=self._trace_id(req),
                          ttft_ms=round(req["ttft"] * 1000.0, 3)
                          if "ttft" in req else None)

    def preempt(self, request_id: int) -> Optional[Completion]:
        """Cancel a request (client abort / scheduler decision): evict
        its slot — or drop it from the queue — and return the partial
        completion. None when the id is unknown/already finished."""
        with self._surface_lock:
            return self._preempt_impl(request_id)

    def _preempt_impl(self, request_id: int) -> Optional[Completion]:
        for slot, req in enumerate(self._slots):
            if req is not None and req["id"] == request_id:
                return self._evict(slot, "preempted")
        for i, req in enumerate(self._queue):
            if req["id"] == request_id:
                del self._queue[i]
                self._counts["preempted"] += 1
                metrics.inc("serving/preempted")
                self._end_request_spans(req, "preempted")
                self._emit("serving_evict", request=request_id,
                           slot=-1, reason="preempted", tokens=0,
                           trace=self._trace_id(req))
                return Completion(request_id=request_id,
                                  prompt=req["prompt"], tokens=[],
                                  finish_reason="preempted",
                                  trace_id=self._trace_id(req))
        return None

    # -- fleet hooks (core/fleet.py, docs/fleet_serving.md) -----------
    #
    # The narrow surface a FleetRouter drives: score a prompt against
    # this replica's registries (prefix_affinity), run prefill without
    # decoding (prefill_step, the prefill half of disaggregation), and
    # move finished-prefill KV pages between replicas' pools
    # (kv_export / kv_page_data -> scatter on the peer via kv_import).
    # Everything stays host-orchestrated: the device only sees the
    # jitted gather/scatter ops, and all refcount/registry bookkeeping
    # lands in this server's own PageAllocator.

    @property
    def has_adapters(self) -> bool:
        """Whether this server can serve non-zero adapter ids at all
        (LoRA banks + an adapter source). The router filters adapter
        requests to capable replicas with this — a base-only server
        would reject them with ValueError, not a shed."""
        return self._adapters is not None

    def adapter_affinity(self, adapter_id: int) -> int:
        """Router scoring hook, the adapter twin of
        :meth:`prefix_affinity`: 1 when this replica already holds
        ``adapter_id`` resident in its HBM bank (admission is a hit —
        no load, no eviction pressure), else 0. Base requests
        (``adapter_id`` 0) and base-only servers score 0 everywhere —
        adapter affinity then never tilts the ranking."""
        with self._surface_lock:
            if not adapter_id or self._adapters is None:
                return 0
            return int(self._adapters.is_resident(adapter_id))

    def prefix_affinity(self, tokens: Sequence[int]) -> int:
        """Router scoring hook: how much of ``tokens`` this replica
        could map from its registries without prefill — the count of
        leading full-page prefix-registry hits, or past-the-table
        ``max_kv_pages + 1`` for a whole-prompt registry hit (zero
        prefill beats any partial share). 0 on contiguous servers."""
        with self._surface_lock:
            if not self.paged or not self._prefix_sharing:
                return 0
            seq = [int(t) for t in tokens]
            if self._alloc.lookup_prompt(prompt_key(seq)) is not None:
                return self._max_pages + 1
            n = 0
            for kk in page_prefix_keys(seq, self._page):
                if self._alloc.lookup_prefix(kk) is None:
                    break
                n += 1
            return n

    def prefill_step(self) -> bool:
        """Admission plus at most one prefill chunk, NO decode tick —
        the drive loop of a prefill-role replica in a disaggregated
        fleet: the router calls this until :meth:`prompt_ready`, then
        exports the KV and hands the request to a decode replica
        before a single token is decoded here.

        Returns:
            True when the call made progress — admitted a request or
            advanced a prefill chunk. The async fleet worker uses
            False (queue head blocked on pool pages, nothing to do)
            to back off instead of spinning, and to keep no-op polls
            off the thread timeline."""
        rec = StepRecord()
        with annotate("serving/step", rec.phases):
            with self._surface_lock:
                if self._closed:
                    return False
                with annotate("serving/step/admit", rec.phases):
                    q0 = len(self._queue)
                    if not self._draining:
                        self._admit()
                    rec.queued = len(self._queue)
                if self.paged:
                    self._prefill_pump(rec)
                    metrics.get_registry().set_gauge(
                        "serving/pages_in_use",
                        self._alloc.pages_in_use)
                progress = rec.queued != q0 or rec.chunks > 0
            with annotate("serving/step/ship_spills", rec.phases):
                self._ship_spills()
        with self._surface_lock:
            self._account_step(rec)
        return progress

    def prompt_ready(self, tokens: Sequence[int]) -> bool:
        """True when a finished prefill of exactly ``tokens`` sits in
        the prompt registry — i.e. :meth:`kv_export` would succeed."""
        with self._surface_lock:
            return bool(
                self.paged and self._prefix_sharing and
                self._alloc.lookup_prompt(
                    prompt_key([int(t) for t in tokens])) is not None)

    def kv_export(self, tokens: Sequence[int]):
        """Pin a finished prefill for handoff: look ``tokens`` up in
        the prompt registry and RETAIN every page so the KV survives
        the source request's eviction while the transfer is in
        flight. Returns ``(pages, last_logits)`` or None on a miss;
        the caller must :meth:`kv_export_release` the pages once the
        peer holds a copy (or on any failure path)."""
        with self._surface_lock:
            if not self.paged:
                return None
            hit = self._alloc.lookup_prompt(
                prompt_key([int(t) for t in tokens]))
            if hit is None:
                return None
            pages, last = hit
            # one batched pin for the whole page set — the export
            # half of the d2d handoff never loops the allocator
            self._alloc.retain_many(pages)
            self._emit("serving_kv_export", pages=len(pages))
            return list(pages), last

    def kv_export_release(self, pages: Sequence[int]) -> None:
        """Drop the transfer references :meth:`kv_export` took (in
        tiered mode a registered page's last pin spills instead of
        freeing, keeping the exported prefix warm)."""
        with self._surface_lock:
            for pid in pages:
                self._release_page(int(pid))

    def kv_page_data(self, pages: Sequence[int]):
        """Device-side gather of ``pages``' contents (KV plus int8
        scale leaves) as a cache-shaped tree — ONE stacked dispatch
        whatever the page count. Hand it to a peer's
        :meth:`kv_import` directly (same devices, the d2d path) or
        via ``jax.device_get`` (host-staged, foreign mesh)."""
        with self._surface_lock:
            return gather_kv_pages(self._cache,
                                   jnp.asarray(list(pages), jnp.int32))

    def kv_import(self, tokens: Sequence[int], page_data,
                  last_logits, n_pages: int) -> bool:
        """Adopt a peer's finished prefill: allocate ``n_pages`` local
        pages (the page-table REMAP — destination ids owe nothing to
        the source's), scatter ``page_data`` into them, and register
        the prompt + its full-page prefixes so the very next
        ``submit()`` of these ``tokens`` admits with zero prefill.
        The import itself holds one reference per page (dropped by
        :meth:`kv_import_release`), so the registry entry outlives
        request churn. False — caller falls back to plain re-prefill
        — when this server is not paged/sharing, the pool cannot host
        ``n_pages`` (free pages, or the one-slot growth reserve that
        import pins must not eat), or the prompt is already
        resident."""
        with self._surface_lock:
            if not self.paged or not self._prefix_sharing:
                return False
            seq = [int(t) for t in tokens]
            key = prompt_key(seq)
            if self._alloc.lookup_prompt(key) is not None:
                return False
            if n_pages > self._max_pages or \
                    self._alloc.free_pages < n_pages:
                return False
            # import pins live outside every slot, where preemption
            # cannot reclaim them (_alloc_or_preempt evicts slots,
            # not imports). Config validation promises that a lone
            # slot can always grow to max_kv_pages — the pins must
            # leave that much of the pool alone, or a fast prefill
            # peer (the async router) fills the pool with imports and
            # the first admitted slot has no page to grow into.
            pinned = sum(len(p) for p in self._imports.values())
            if (self._alloc.num_pages - 1) - pinned - n_pages \
                    < self._max_pages:
                return False
            pids = self._alloc.alloc_many(n_pages)
            self._cache = scatter_kv_pages(
                self._cache, page_data, jnp.asarray(pids, jnp.int32))
            for j, kk in enumerate(page_prefix_keys(seq, self._page)):
                self._alloc.register_prefix(kk, pids[j])
            self._alloc.register_prompt(
                key, pids, np.asarray(last_logits, np.float32))
            self._imports[key] = pids
            self._emit("serving_kv_import", pages=n_pages)
            return True

    def kv_import_release(self, tokens: Sequence[int]) -> None:
        """Unpin an import once the handed-off request completed (or
        to evict a stale shared prefix): the registry entries fall
        away with the last reference. No-op on unknown keys."""
        with self._surface_lock:
            if not self.paged:
                return
            pids = self._imports.pop(
                prompt_key([int(t) for t in tokens]), None)
            for pid in pids or ():
                self._release_page(pid)

    # -- restart-persistent prefix store ------------------------------
    #
    # A drained tiered server's shareable KV is (by construction) all
    # host-resident: every registered page released to its last
    # reference spilled. export_prefix_store snapshots that tier —
    # staged bytes + the registry entries that reach them — as a
    # plain dict; core/checkpoint.py's save/load_prefix_store round it
    # through a committed-last manifest directory, and
    # FleetRouter.restart_replica hands it to the restarted replica's
    # import_prefix_store so it serves its first request warm.

    def _model_fingerprint(self) -> str:
        """Identity of the model this server serves: a digest over
        the config plus every parameter leaf's path, shape, dtype and
        fp32 sum — cheap (one scalar reduction per leaf, one host
        transfer), deterministic, and different whenever the weights
        are. Stamped into every exported prefix store and checked on
        import, so KV persisted under one deploy can never warm-start
        a model with different weights. Computed once and cached."""
        if self._model_fp is None:
            h = hashlib.sha256()
            cfg = self.model.config
            cfg_d = _dc.asdict(cfg) if _dc.is_dataclass(cfg) \
                else vars(cfg)
            h.update(json.dumps({k: str(v) for k, v in cfg_d.items()},
                                sort_keys=True).encode())
            leaves = jax.tree_util.tree_flatten_with_path(
                self.params)[0]
            sums = jax.device_get(
                [jnp.sum(jnp.asarray(leaf, jnp.float32))
                 for _, leaf in leaves])
            for (path, leaf), s in zip(leaves, sums):
                h.update(jax.tree_util.keystr(path).encode())
                h.update(str((tuple(leaf.shape),
                              str(leaf.dtype))).encode())
                h.update(np.float32(s).tobytes())
            self._model_fp = h.hexdigest()[:16]
        return self._model_fp

    def _await_spill_writer(self) -> None:
        """Wait (bounded) for the writer to finish every shipped item
        — the prefix-store export's quiesce point, replacing the old
        queue join. Runs at an UNLOCKED position: the writer never
        needs the surface lock, but waiting under it would still
        stall a concurrently ticking fleet worker for the whole
        device_get."""
        deadline = time.monotonic() + self._SPILL_WAIT_S
        with self._spill_lock:
            while self._spill_outstanding > 0 and \
                    time.monotonic() < deadline:
                self._spill_lock.wait(timeout=0.05)

    def export_prefix_store(self) -> Optional[dict]:
        """Snapshot the host tier for a restart warm start: drain any
        pending spill pins first (a just-drained server's shareable
        pages are still pinned), ship the batch and wait out the
        writer, and return page bytes (flat numpy leaf lists in cache
        tree order) plus the host-resident registry entries. None on
        non-tiered servers."""
        with self._surface_lock:
            if not self.paged or not self._tiered:
                return None
            self._drain_spills()
        self._ship_spills()
        self._await_spill_writer()
        with self._surface_lock:
            return self._export_prefix_store_impl()

    def _export_prefix_store_impl(self) -> dict:
        # the writer quiesce flushed every publish AND every failure
        # record — reap now so dead pages drop out of the snapshot
        self._reap_failed_spills()
        prefixes, prompts = self._alloc.host_snapshot()
        needed = set(prefixes.values())
        for pages, _ in prompts.values():
            needed.update(pages)
        with self._spill_lock:
            data = {h: self._host_data[h][1] for h in needed
                    if h in self._host_data and self._host_data[h][0]
                    == self._alloc.host_generation(h)}
        cfg = self.model.config
        store = {
            "page_size": self._page,
            "kv_cache_dtype": cfg.kv_cache_dtype,
            # cached at construction (tiered servers fingerprint
            # eagerly) — the device_get inside _model_fingerprint
            # must not run under the surface lock
            "model_fingerprint": self._model_fp,
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

    def import_prefix_store(self, store: Optional[dict]) -> int:
        """Adopt an exported prefix store on a fresh server (the
        restart warm start): fill free host slots with the saved pages
        and re-register their content keys, so the next admission of
        a covered prompt rehydrates instead of re-prefilling. A
        geometry mismatch (page size, KV dtype) imports nothing — the
        bytes would be garbage — and so does a model-identity
        mismatch: KV computed by DIFFERENT weights under identical
        geometry scatters cleanly but serves silently wrong
        attention, the one failure mode a disk round-trip across
        deploys invites. Returns the pages adopted."""
        with self._surface_lock:
            return self._import_prefix_store_impl(store)

    def _import_prefix_store_impl(self, store: Optional[dict]) -> int:
        if not store or not self.paged or not self._tiered:
            return 0
        cfg = self.model.config
        if store.get("page_size") != self._page or \
                store.get("kv_cache_dtype") != cfg.kv_cache_dtype:
            logger.warning(
                "prefix store geometry mismatch (page %s dtype %s vs "
                "page %d dtype %s): starting cold",
                store.get("page_size"), store.get("kv_cache_dtype"),
                self._page, cfg.kv_cache_dtype)
            return 0
        fp = self._model_fp
        if store.get("model_fingerprint") != fp:
            logger.warning(
                "prefix store model fingerprint mismatch (%s vs %s): "
                "its KV was computed by different weights — starting "
                "cold", store.get("model_fingerprint"), fp)
            return 0
        treedef = jax.tree_util.tree_structure(self._cache)
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
            with self._spill_lock:
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
        self._drop_evicted_host_data()
        adopted = self._alloc.host_pages_resident
        metrics.get_registry().set_gauge("serving/host_pages", adopted)
        self._emit("serving_prefix_store_import", pages=adopted,
                   prefixes=len(store.get("prefixes", {})),
                   prompts=len(store.get("prompts", {})))
        return adopted

    # -- the serving loop ---------------------------------------------

    def step(self) -> List[Completion]:
        """Admit what fits, advance at most one prefill chunk (paged),
        tick every ACTIVE slot — one token plain, 1..k+1 committed
        tokens speculative — then evict and return whatever finished
        (deadline-expired requests included, as ``deadline_exceeded``
        partials). While draining, admission is skipped.

        With ``device_loop_ticks > 1`` one call runs up to that many
        ticks in a single fused device program (:meth:`_step_loop`) —
        same committed tokens, T× fewer host round-trips.

        Thread-safe: the whole tick runs under the surface lock;
        spill shipping (the one blocking queue put) happens after the
        lock is released so the writer thread can never be fed from
        inside the critical section."""
        rec = StepRecord()
        with annotate("serving/step", rec.phases):
            with self._surface_lock:
                if self._closed:
                    return []
                if self._loop_ticks > 1:
                    out = self._step_loop(rec)
                    with annotate("serving/step/commit", rec.phases):
                        self._refresh_health()
                else:
                    out = self._step_impl(rec)
            with annotate("serving/step/ship_spills", rec.phases):
                self._ship_spills()
        with self._surface_lock:
            self._account_step(rec)
        return out

    def _account_step(self, rec: StepRecord) -> None:
        """Feed one finished step's record to what is kept of the
        series (each interval was clocked once, by ``annotate``) and
        judge it against the slow-step thresholds. Under the surface
        lock, like every other write to the server's counters."""
        self.last_step = rec
        seconds = rec.seconds
        if rec.ticks:
            tick_s = rec.tick_seconds()
            self._tick_time += tick_s
            for _ in range(rec.ticks):
                # a fused launch spreads its wall time over its ticks
                self._metrics.observe("serving/tick_ms",
                                      tick_s * 1000.0 / rec.ticks)
            # one round-trip's full host cost (admit + draft +
            # dispatch + fetch + replay) — the series the T-sweep
            # compares against tick_ms to show the amortization win
            self._metrics.observe("serving/host_roundtrip_ms",
                                  seconds * 1000.0)
        recent = self._recent_steps
        if seconds > SLOW_STEP_SECONDS and \
                len(recent) >= SLOW_STEP_MIN_HISTORY:
            median = statistics.median(recent)
            if seconds > SLOW_STEP_FACTOR * median:
                self._slow_step(rec, median)
        if rec.ticks:
            recent.append(seconds)

    def _slow_step(self, rec: StepRecord, median_s: float) -> None:
        """Put a slow step on the record: which phase took most of
        it, and the whole per-phase account."""
        record = rec.as_dict()
        phases = record["phases_ms"]
        worst = max(phases, key=phases.get)
        metrics.inc("serving/slow_steps")
        metrics.inc("serving/slow_step/" + worst)
        logger.warning(
            "slow step(): %.0f ms against a median of %.0f ms over "
            "the last %d decoding steps, most of it in %s: %s",
            record["dur_ms"], median_s * 1e3, len(self._recent_steps),
            worst, json.dumps(record))
        self._emit("serving_slow_step", worst=worst,
                   median_ms=round(median_s * 1e3, 3), **record)

    def _schedule(self, rec: StepRecord
                  ) -> Tuple[List[Completion], List[int]]:
        """What every ``step()`` opens with, a phase each: expire
        deadlines, drain pinned spills, admit what fits, pump one
        prefill chunk, settle which slots the launch will tick.
        Returns the expired completions and those slots."""
        ph = rec.phases
        with annotate("serving/step/expire", ph):
            expired = self._expire_deadlines()
            if self._faults is not None:
                self._faults.fire("tick", self._ticks + 1)
        with annotate("serving/step/spill_drain", ph):
            # host yield point: between device launches is the ONLY
            # place pinned spills move to the host tier (decode never
            # blocks); a pending pin capped the previous fused launch
            # at one tick via _loop_host_flag
            self._drain_spills()
        with annotate("serving/step/admit", ph):
            if not self._draining:
                self._admit()
            rec.queued = len(self._queue)
        if self.paged:
            self._prefill_pump(rec)
        with annotate("serving/step/table_sync", ph):
            if self.paged:
                metrics.get_registry().set_gauge(
                    "serving/pages_in_use", self._alloc.pages_in_use)
            live = [s for s, r in enumerate(self._slots)
                    if r is not None
                    and (not self.paged or r.get("active"))]
            rec.live = len(live)
            if live:
                self._sync_aid()
        return expired, live

    def _count_decode_walk(self, live: List[int], window: int,
                           ahead=None) -> None:
        """One tick of the paged decode kernel, as the host knows it
        without a device read: the slots it walks of those it was
        launched for, and the pages (its blocks, at the cells' page
        size) it walks of the table's capacity. Of ``live`` only the
        slots still active count (page maintenance may have preempted
        one: its row went down nulled); ``ahead [slots]`` are tokens a
        fused launch committed in its earlier ticks."""
        lengths = [
            req["cur_len"] + (int(ahead[s]) if ahead is not None else 0)
            for s in live
            if (req := self._slots[s]) is not None and req.get("active")]
        last = self._max_pages - 1
        metrics.inc("serving/decode_rows_live", len(lengths))
        metrics.inc("serving/decode_rows_slots", self.num_slots)
        metrics.inc("serving/decode_blocks_live", sum(
            min((n + window - 1) // self._page, last) + 1
            for n in lengths))
        metrics.inc("serving/decode_blocks_capacity",
                    self.num_slots * self._max_pages)

    def _idle_step(self, rec: StepRecord, expired: List[Completion]
                   ) -> List[Completion]:
        """The end of a step with nothing decodable yet (empty, or
        every occupant is still mid-chunked-prefill) — the pump still
        made progress."""
        with annotate("serving/step/commit", rec.phases):
            metrics.get_registry().set_gauge(
                "serving/slot_occupancy", self.occupancy)
            return expired + self._take_dead()

    def _step_impl(self, rec: StepRecord) -> List[Completion]:
        ph = rec.phases
        expired, live = self._schedule(rec)
        if not live:
            return self._idle_step(rec, expired)
        if self._watchdog is not None:
            self._watchdog.arm(tag=f"tick {self._ticks + 1}")
        k = self._spec_k if self.spec else 0
        if self.spec:
            with annotate("serving/step/draft", ph):
                # host drafts ride down with the tick; inactive rows
                # are zeros the verify mask never commits
                drafts = np.zeros((self.num_slots, k), np.int32)
                for slot in live:
                    req = self._slots[slot]
                    drafts[slot] = self._draft.propose(
                        req["prompt"] + req["tokens"], k)
        if self.paged:
            with annotate("serving/step/page_maintenance", ph):
                # growth/COW decisions against the PRE-tick lengths,
                # over the tick's whole write window (k+1 tokens
                # speculative) — then one table upload
                self._page_maintenance(window=k + 1)
            with annotate("serving/step/table_sync", ph):
                self._sync_pt()
        with annotate("serving/step/decode_dispatch", ph):
            pt = self._pt_dev_dec if self.paged else None
            if self.spec:
                self._cache, self._state, window, counts = \
                    verify_step(
                        self.model, self.params, self._cache,
                        self._state, jnp.asarray(drafts), self._rng,
                        self.gen_cfg, pt, self._aid_arg())
            else:
                self._cache, self._state, tok = decode_step(
                    self.model, self.params, self._cache,
                    self._state, self._rng, self.gen_cfg, pt,
                    self._aid_arg())
        with annotate("serving/step/decode_harvest", ph):
            # the host blocked on the tick
            if self.spec:
                window = np.asarray(window)
                counts = np.asarray(counts)
            else:
                window = np.asarray(tok)[:, None]
                counts = np.ones((self.num_slots,), np.int32)
        with annotate("serving/step/state_fetch", ph):
            finished = np.asarray(self._state.finished)
            dec_count = np.asarray(self._state.dec_count)
        with annotate("serving/step/commit", ph):
            if self._watchdog is not None:
                self._watchdog.disarm()
            self._ticks += 1
            self._roundtrips += 1
            rec.ticks = 1
            metrics.inc("serving/device_ticks")
            if self.paged:
                self._count_decode_walk(live, k + 1)
            reg = metrics.get_registry()
            done: List[Completion] = []
            now = time.time()
            committed = 0
            ticked = 0
            for slot in live:
                req = self._slots[slot]
                if req is None or \
                        (self.paged and not req.get("active")):
                    # preempted out from under the tick by page
                    # maintenance (pool exhaustion) — nothing committed
                    continue
                ticked += 1
                m = int(counts[slot])
                req["tokens"].extend(int(t) for t in window[slot, :m])
                if "ttft" not in req:
                    req["ttft"] = now - req["submit_t"]
                    req["first_tok_t"] = now
                    self._metrics.observe("serving/ttft_ms",
                                          req["ttft"] * 1000.0)
                    req["span"].span_point(
                        "serving/first_token",
                        ttft_ms=round(req["ttft"] * 1000.0, 3))
                if self.paged:
                    req["cur_len"] += m
                    if self.spec:
                        # rejected-KV rollback: pages wholly past the
                        # accepted point go straight back to the pool
                        # (the partial page's stale columns sit past
                        # cur_len and are overwritten before any
                        # masked read)
                        used = -(-req["cur_len"] // self._page)
                        if used < req["num_pages"]:
                            for j in range(used, req["num_pages"]):
                                self._release_page(
                                    int(self._pt[slot, j]))
                                self._pt[slot, j] = NULL_PAGE
                            req["num_pages"] = used
                            self._pt_dirty = True
                committed += m
                self._decode_tokens += m
                if finished[slot]:
                    done.append(self._evict(slot, "eos"))
                elif dec_count[slot] >= self.gen_cfg.max_dec_len:
                    done.append(self._evict(slot, "length"))
            rec.tokens = committed
            metrics.inc("serving/decode_tokens", committed)
            if self.spec:
                drafted = self._spec_k * ticked
                accepted = committed - ticked      # t0s are not drafts
                self._spec_drafted += drafted
                self._spec_accepted += accepted
                metrics.inc("serving/spec_drafted", drafted)
                metrics.inc("serving/spec_accepted", accepted)
                reg.set_gauge(
                    "serving/spec_accept_rate",
                    self._spec_accepted / max(self._spec_drafted, 1))
                self._emit("serving_spec", drafted=drafted,
                           accepted=accepted, committed=committed)
            reg.set_gauge("serving/slot_occupancy", self.occupancy)
            return expired + self._take_dead() + done

    # -- device-resident decode (device_loop_ticks > 1) ---------------
    #
    # One step() call launches ONE fused decode_loop/verify_loop of up
    # to T ticks; the host amortizes admission, drafting, deadline/TTL
    # checks, page maintenance, and telemetry over the ticks it gets
    # back. The loop exits early (ticks_run < T) when a slot finishes
    # or runs out of budget — eviction can't wait — or when the host
    # flagged pending scheduling work at launch, in which case exactly
    # one tick runs and the host resumes control, so drain(max_ticks)
    # and chunked prefill keep their one-unit-of-progress-per-step
    # contracts.

    def _loop_host_flag(self, live: List[int]) -> bool:
        """Should the fused loop hand control back after ONE tick?
        True while draining (drain()'s tick bound counts step calls),
        while admission work is pending — ANY queued request: a full-T
        launch would defer its admission, deadline/TTL expiry, and
        shed decisions by T ticks, so queued work caps the loop at one
        tick (the T=1 scheduling cadence) until the queue empties —
        while a chunked prefill is unfinished (paged), or when the
        page pool can't cover the full T-tick write window for every
        live slot without preempting (better one short loop than an
        avoidable preemption)."""
        if self._draining:
            return True
        if self._queue:
            return True
        if self.paged:
            if self._prefilling:
                return True
            if self._tiered and self._spill_pin:
                # pinned spills drain at step entry — exit after one
                # tick so the writer gets its work this round-trip
                return True
            per_tick = (self._spec_k + 1) if self.spec else 1
            span = self._loop_ticks * per_tick
            cap = self.model.config.cache_capacity
            need = 0
            for slot in live:
                req = self._slots[slot]
                first = req["cur_len"] // self._page
                last = -(-min(req["cur_len"] + span, cap) // self._page)
                for j in range(first, last):
                    if j >= req["num_pages"] or self._alloc.refcount(
                            int(self._pt[slot, j])) > 1:
                        need += 1   # fresh map, or a COW split's copy
            if need > self._alloc.free_pages:
                return True
        return False

    def _step_loop(self, rec: StepRecord) -> List[Completion]:
        """The ``device_loop_ticks > 1`` body of :meth:`step`: one
        fused multi-tick launch, then a per-tick replay of the
        returned token buffers so ``serving/decode_tokens``, TTFT/TPOT
        timestamps (interpolated across the loop's wall time),
        ``serving/tick_ms`` and the per-tick ``serving_spec`` events
        stay tick-accurate. Greedy/seeded output is token-exact vs the
        T=1 path (tests/test_serving.py parity matrix)."""
        ph = rec.phases
        expired, live = self._schedule(rec)
        if not live:
            return self._idle_step(rec, expired)
        T = self._loop_ticks
        with annotate("serving/step/page_maintenance", ph):
            host_flag = self._loop_host_flag(live)
        # flag up -> the loop exits after one tick, so drafting and
        # page pre-mapping cover one tick's window only (the launch
        # shape stays [slots, T, ...]: loop_ticks is static, the flag
        # is traced, nothing recompiles)
        eff_ticks = 1 if host_flag else T
        if self._watchdog is not None:
            self._watchdog.arm(
                tag=f"ticks {self._ticks + 1}..{self._ticks + T}")
        k = self._spec_k if self.spec else 0
        if self.spec:
            with annotate("serving/step/draft", ph):
                drafts = np.zeros((self.num_slots, T, k), np.int32)
                for slot in live:
                    req = self._slots[slot]
                    # k·T drafts per round-trip, all proposed from the
                    # pre-loop history; tick j verifies chunk j
                    drafts[slot, :eff_ticks] = np.asarray(
                        self._draft.propose(
                            req["prompt"] + req["tokens"],
                            k * eff_ticks),
                        np.int32).reshape(eff_ticks, k)
        if self.paged:
            with annotate("serving/step/page_maintenance", ph):
                self._page_maintenance(window=eff_ticks * (k + 1))
            with annotate("serving/step/table_sync", ph):
                self._sync_pt()
        with annotate("serving/step/decode_dispatch", ph):
            pt = self._pt_dev_dec if self.paged else None
            if self.spec:
                (self._cache, self._state, window_buf, counts_buf,
                 ticks_run, exit_code) = verify_loop(
                    self.model, self.params, self._cache, self._state,
                    jnp.asarray(drafts), self._rng, self.gen_cfg,
                    jnp.int32(host_flag), pt, self._aid_arg(),
                    loop_ticks=T)
            else:
                (self._cache, self._state, tokens_buf, ticks_run,
                 exit_code) = decode_loop(
                    self.model, self.params, self._cache, self._state,
                    self._rng, self.gen_cfg, jnp.int32(host_flag), pt,
                    self._aid_arg(), loop_ticks=T)
        with annotate("serving/step/decode_harvest", ph):
            # the host blocked on the launch
            n_ticks = int(ticks_run)
            if self.spec:
                window_np = np.asarray(window_buf)
                counts_np = np.asarray(counts_buf)
            else:
                window_np = np.asarray(tokens_buf)[:, :, None]
                counts_np = np.zeros((self.num_slots, T), np.int32)
                counts_np[:, :n_ticks] = 1
            exit_code = int(exit_code)
            t_end = time.time()
        with annotate("serving/step/state_fetch", ph):
            finished = np.asarray(self._state.finished)
            dec_count = np.asarray(self._state.dec_count)
        with annotate("serving/step/commit", ph):
            if self._watchdog is not None:
                self._watchdog.disarm()
            self._ticks += n_ticks
            self._roundtrips += 1
            rec.ticks = n_ticks
            metrics.inc("serving/device_ticks", n_ticks)
            metrics.inc(
                "serving/loop_exit/finished"
                if exit_code == LOOP_EXIT_FINISHED
                else "serving/loop_exit/budget"
                if exit_code == LOOP_EXIT_BUDGET
                else ("serving/loop_exit/drain" if self._draining
                      else "serving/loop_exit/admission"))
            reg = metrics.get_registry()
            per_tick_s = rec.tick_seconds() / n_ticks
            done: List[Completion] = []
            committed = 0
            for j in range(n_ticks):
                # the loop is one opaque device program; per-tick
                # timestamps interpolate its wall time so TTFT/TPOT
                # stay comparable with the T=1 histograms
                t_j = t_end - (n_ticks - 1 - j) * per_tick_s
                if self.paged:
                    self._count_decode_walk(
                        live, k + 1, counts_np[:, :j].sum(axis=1))
                tick_committed = 0
                ticked = 0
                for slot in live:
                    req = self._slots[slot]
                    if req is None or \
                            (self.paged and not req.get("active")):
                        # preempted out from under the launch by page
                        # pre-mapping (pool exhaustion) — nothing
                        # committed
                        continue
                    ticked += 1
                    m = int(counts_np[slot, j])
                    req["tokens"].extend(
                        int(t) for t in window_np[slot, j, :m])
                    if "ttft" not in req:
                        req["ttft"] = t_j - req["submit_t"]
                        req["first_tok_t"] = t_j
                        self._metrics.observe("serving/ttft_ms",
                                              req["ttft"] * 1000.0)
                        req["span"].span_point(
                            "serving/first_token",
                            ttft_ms=round(req["ttft"] * 1000.0, 3))
                    tick_committed += m
                committed += tick_committed
                self._decode_tokens += tick_committed
                if self.spec and ticked:
                    drafted = self._spec_k * ticked
                    accepted = tick_committed - ticked
                    self._spec_drafted += drafted
                    self._spec_accepted += accepted
                    metrics.inc("serving/spec_drafted", drafted)
                    metrics.inc("serving/spec_accepted", accepted)
                    self._emit("serving_spec", drafted=drafted,
                               accepted=accepted,
                               committed=tick_committed)
            rec.tokens = committed
            metrics.inc("serving/decode_tokens", committed)
            if self.spec:
                reg.set_gauge(
                    "serving/spec_accept_rate",
                    self._spec_accepted / max(self._spec_drafted, 1))
            if self.paged:
                # advance each slot past its committed tokens and hand
                # pages wholly past that point back to the pool — both
                # the pre-mapped-but-unused tail of an early exit and
                # spec's rejected-KV rollback
                for slot in live:
                    req = self._slots[slot]
                    if req is None or not req.get("active"):
                        continue
                    req["cur_len"] += int(
                        counts_np[slot, :n_ticks].sum())
                    used = -(-req["cur_len"] // self._page)
                    if used < req["num_pages"]:
                        for j in range(used, req["num_pages"]):
                            self._release_page(int(self._pt[slot, j]))
                            self._pt[slot, j] = NULL_PAGE
                        req["num_pages"] = used
                        self._pt_dirty = True
            for slot in live:
                req = self._slots[slot]
                if req is None or \
                        (self.paged and not req.get("active")):
                    continue
                if finished[slot]:
                    done.append(self._evict(slot, "eos"))
                elif dec_count[slot] >= self.gen_cfg.max_dec_len:
                    done.append(self._evict(slot, "length"))
            reg.set_gauge("serving/slot_occupancy", self.occupancy)
            return expired + self._take_dead() + done

    def drain(self, max_ticks: Optional[int] = None
              ) -> List[Completion]:
        """Graceful shutdown: stop admitting, return every QUEUED
        request immediately as a ``preempted`` partial (committed
        tokens intact), tick in-flight slots to completion — bounded
        by ``max_ticks``, past which survivors are preempted too — and
        return all resulting completions. ``max_ticks=0`` preempts
        everything at once. Partials re-enter a restarted paged server
        via ``submit(resume_tokens=...)`` with no committed token
        lost."""
        with self._surface_lock:
            out = self._drain_impl(max_ticks)
        self._ship_spills()
        return out

    def _drain_impl(self, max_ticks: Optional[int]
                    ) -> List[Completion]:
        if not self._draining:
            self._draining = True
            self._refresh_health()
            self._emit("serving_drain_start", signum=None,
                       pending=self.pending, occupancy=self.occupancy)
        out: List[Completion] = self._flush_queue()
        ticks = 0
        while not self._closed and self.occupancy and \
                (max_ticks is None or ticks < max_ticks):
            out.extend(self.step())
            ticks += 1
        for slot in range(self.num_slots):
            if self._slots[slot] is not None:
                out.append(self._evict(slot, "preempted"))
        # a pool-exhaustion preempt during the tick loop requeues to
        # the (no longer admitting) queue — hand those back too
        out.extend(self._flush_queue())
        out.extend(self._take_dead())
        self._refresh_health()
        self._emit("serving_drain_end", completions=len(out),
                   ticks=ticks)
        return out

    def _flush_queue(self) -> List[Completion]:
        """Every queued request back to its client as a ``preempted``
        partial (committed tokens kept)."""
        out: List[Completion] = []
        while self._queue:
            req = self._queue.popleft()
            self._counts["preempted"] += 1
            metrics.inc("serving/preempted")
            self._end_request_spans(req, "preempted")
            self._emit("serving_evict", request=req["id"], slot=-1,
                       reason="preempted", tokens=len(req["tokens"]),
                       trace=self._trace_id(req))
            out.append(Completion(request_id=req["id"],
                                  prompt=req["prompt"],
                                  tokens=req["tokens"],
                                  finish_reason="preempted",
                                  trace_id=self._trace_id(req)))
        return out

    def close(self) -> None:
        """Detach OS-level hooks: stop the watchdog and spill-writer
        threads and restore a ``drain_on_sigterm`` handler. Marks the
        server closed — a racing step() from another thread returns
        [] instead of touching torn-down state. Idempotent."""
        with self._surface_lock:
            self._closed = True
        # last outboxed spills still reach the writer before the
        # sentinel below shuts it down
        self._ship_spills()
        if self._watchdog is not None:
            self._watchdog.stop()
        if self._tiered and self._spill_writer_thread is not None:
            self._spill_q.put(None)
            self._spill_writer_thread.join(timeout=10.0)
            self._spill_writer_thread = None
        if self._sigterm_installed:
            signal.signal(signal.SIGTERM, self._prev_sigterm)
            self._sigterm_installed = False

    def run(self, prompts: Sequence[Sequence[int]],
            adapter_ids: Optional[Sequence[int]] = None
            ) -> List[Completion]:
        """Serve a batch of prompts to completion; completions return
        in SUBMISSION order (slot/finish order is an implementation
        detail the caller should not see). ``adapter_ids`` optionally
        pairs each prompt with a LoRA adapter (0 = base model). A
        drain — SIGTERM under ``drain_on_sigterm``, or a concurrent
        :meth:`drain` — ends the loop early with partials in place of
        unfinished requests."""
        if adapter_ids is None:
            adapter_ids = [0] * len(prompts)
        ids = [self.submit(p, adapter_id=a)
               for p, a in zip(prompts, adapter_ids)]
        done: Dict[int, Completion] = {}
        while self.pending or self.occupancy:
            if self.draining:
                for c in self.drain():
                    done[c.request_id] = c
                break
            for c in self.step():
                done[c.request_id] = c
        return [done[i] for i in ids]

    def summary(self) -> dict:
        """Counters + decode tokens/s + TTFT percentiles for the
        server's lifetime so far (also emitted to the flight
        recorder). Paged servers add pool occupancy and the allocator
        sharing stats."""
        with self._surface_lock:
            return self._summary_impl()

    def _summary_impl(self) -> dict:
        tps = self._decode_tokens / self._tick_time \
            if self._tick_time > 0 else 0.0
        s = {"slots": self.num_slots, "occupancy": self.occupancy,
             "pending": self.pending, "decode_ticks": self._ticks,
             "decode_tokens": self._decode_tokens,
             "decode_time_sec": round(self._tick_time, 4),
             "tokens_per_sec": round(tps, 2),
             # the host-overhead line: device ticks vs host
             # round-trips — equal at T=1, ticks/roundtrips ≈ T when
             # the fused loop is winning (docs/inference.md)
             "device_loop_ticks": self._loop_ticks,
             "device_ticks": self._ticks,
             "host_roundtrips": self._roundtrips, **self._counts}
        # percentiles from the fixed-memory histograms — field names
        # ttft_p50_ms/ttft_p99_ms are a pinned contract
        for prefix, series in (("ttft", "serving/ttft_ms"),
                               ("queue_wait", "serving/queue_wait_ms"),
                               ("tpot", "serving/tpot_ms"),
                               ("tick", "serving/tick_ms"),
                               ("host_roundtrip",
                                "serving/host_roundtrip_ms"),
                               ("rehydrate", "serving/rehydrate_ms")):
            h = self._metrics.histogram(series)
            if h is not None and h.count:
                s[f"{prefix}_p50_ms"] = round(h.percentile(50), 3)
                s[f"{prefix}_p99_ms"] = round(h.percentile(99), 3)
        if self.spec:
            s["spec_tokens"] = self._spec_k
            s["spec_drafted"] = self._spec_drafted
            s["spec_accepted"] = self._spec_accepted
            s["spec_accept_rate"] = round(
                self._spec_accepted / max(self._spec_drafted, 1), 4)
        if self.paged:
            from .paging import pool_bytes
            mcfg = self.model.config
            s["paged"] = True
            s["page_size"] = self._page
            s["pool_pages"] = self._alloc.num_pages
            s["pages_in_use"] = self._alloc.pages_in_use
            s["prefill_chunks"] = self._prefill_chunk_count
            # density accounting (docs/quantization.md): same pool
            # BYTES admit ~1.9x the pages under int8 + fp32 scales
            s["kv_cache_dtype"] = mcfg.kv_cache_dtype
            s["pool_bytes"] = pool_bytes(
                mcfg.num_layers, mcfg.num_attention_heads,
                mcfg.head_dim, self._page, self._alloc.num_pages,
                mcfg.kv_cache_dtype)
            if self._tiered:
                s["tiered"] = True
                s["host_pool_bytes"] = self._host_pool_bytes
                s["host_pages_cap"] = self._alloc.host_pages
                s["host_pages"] = self._alloc.host_pages_resident
            s.update(self._alloc.stats)
        if self._adapters is not None:
            s["adapter_rows"] = self._adapters.capacity
            s["adapters_resident"] = self._adapters.resident
            s.update(self._adapters.stats)
        self._emit("serving_summary", **s)
        return s
