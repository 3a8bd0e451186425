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
bounded pinned-host spill tier under the HBM pool, one
``core/host_tier.py::HostSpillTier`` the server calls at its yield
points. A registered prefix/prompt page's last reference spills to
host memory instead of freeing, a later registry hit scatters it back
into a fresh HBM page instead of re-prefilling, decode ticks never
block on the swap, and ``export_prefix_store`` /
``import_prefix_store`` carry the tier across rolling restarts.

Speculative decoding (``GenerationConfig.spec_method``/``spec_tokens``):
decode at small batch is latency-bound on the per-step collectives, so
the tick instead takes ``k`` drafted tokens per slot from a draft
source (``core/spec.py``: a host object the server asks before the
launch — n-gram self-speculation — or the model's own
multi-token-prediction block, which drafts inside the tick program),
scores the
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
"Device-resident decode"). T=1 (the default) launches the one-tick
``decode_step``/``verify_step`` programs from the same step body; any
T commits the same tokens. Whichever of the four programs runs, the
host reads the device ONCE a launch: tokens, counts, ``finished``,
``dec_count``, ticks run and exit code come home in one int32 array
(``generation.pack_harvest``) whose copy ``_launch`` asks for at the
dispatch (``copy_to_host_async``), so it starts when the program ends
and not when the host has noticed (docs/inference.md "The harvest").
A fused launch is read in the step that made it (below).

Deferred harvest (the plain one-tick server; docs/inference.md "The
harvest"): a decoding ``step()`` launches tick n BEFORE it reads tick
n-1 — schedule, prefill chunk, page maintenance, table upload,
``decode_dispatch`` of n, then ``decode_harvest`` and ``commit`` of
n-1 — so the device always has a tick queued while the host reads,
commits, returns to its caller, schedules and dispatches. One launch
deep, never deeper: the host runs at most one tick ahead of what it
has read. The scheduler's contract under it:

- **What the host decides on is one tick old.** ``finished`` and
  ``dec_count`` of tick n-1 arrive after tick n went down, so a
  request that ended on EOS in n-1 is ticked once more. On the device
  that row is finished and emits ``pad``; on the host it is VOID: a
  harvest is committed to the ``(slot, request)`` pairs it was
  launched for, and a row whose slot no longer holds that request
  commits nothing (``serving/harvest_rows_void``). Its one write, at
  the request's last length, went into a page that was the slot's
  alone at the launch and that the eviction has released since (or
  the slot's ring, or its state row); whoever is handed any of them
  next got there through a LATER launch, which the device runs after
  it (:meth:`GenerationServer._read_launch`).
- **A budget is known a tick ahead.** The one-tick program checks no
  ``max_dec_len``; the host does not launch a row whose tick in
  flight is the last its budget buys (``_retire``: tokens committed
  plus ticks unread), so no token past the budget is ever computed,
  let alone committed.
- **Pages follow the device's length.** The host's ``cur_len`` is a
  tick old at the launch: page growth and copy-on-write are decided
  at ``cur_len + ahead``, the position THIS launch writes, and the
  commit's trim keeps the page the launch in flight is writing into
  (at a page boundary it holds nothing committed yet). A verify tick
  commits 1..k+1 tokens, so under a source on the device the length
  the device holds is only bounded: pages are mapped from ``cur_len +
  ahead`` up to ``cur_len + (k + 1) * ahead`` plus the window, and the
  commit's trim returns what was not used.
- **What must see a request's newest token reads first** (a flush,
  ``serving/harvest_flushed/<why>``): :meth:`~GenerationServer.drain`
  (and every step while draining: ``max_ticks`` counts ticks),
  :meth:`~GenerationServer.preempt`, page-pool exhaustion before it
  preempts anyone (``preempt``; the read may free what it needs),
  :meth:`~GenerationServer.close`, and the last launch before the
  server runs empty (``idle``). What needs NO flush: deadline expiry
  (the partial holds what was committed; the row in flight goes
  void), and the KV handoff's and spill tier's page reads, which are
  device programs queued behind the tick in flight and read registry
  pages no decode tick writes.
- **Who never defers.** A HOST draft source proposes from the newest
  committed tokens, so its server reads every launch at once
  (``harvest_flushed/spec``); a source on the device
  (``spec_method="mtp"``) needs nothing from the tick in flight and
  defers like a plain server. ``device_loop_ticks > 1`` reads at once too
  (``harvest_flushed/loop``), whose fused launch already stops itself
  on a finished slot or a spent budget and gives the host back its
  round trip T ticks at a time. Properties the server observes of
  itself (``_read_now``): there is no option.
- **Times.** A token's stamp is when the host received it, one step
  after its tick, for every token alike: ``tpot`` keeps its meaning,
  ``ttft`` (``serving/ttft_ms``, the ``first_token`` point,
  ``Completion.ttft_ms``) grows by at most one step. The prefill
  path reads nothing: a prompt's last logits row stays on the device
  from the chunk that made it to the slot state that samples from it
  (``generation.activate_slot`` picks it), and the prompt registry's
  copy comes home behind the step (``_land_rows``).

What a launch passes (docs/inference.md "What a launch passes"): a
jitted call handles its arguments leaf by leaf, whatever their size,
and on a small model that handling, not the tick, is the step. So the
server keeps its parameters as the arrays its launches pass
(``generation.LaunchParams``, built once by ``pack_launch_params``
and again by every assignment to :attr:`GenerationServer.params`):
leaves that agree in shape, dtype and sharding ride STACKED in one
``[n, ...]`` array — every layer's norm scales, biases, router and
state rows; a model that arrives scan-stacked keeps such stacks as
they came — and each slot primitive slices the per-layer tree out of
them inside the program (``generation.launch_tree``), where the slice
is fused into what reads it. NOT stacked: a leaf over
``generation.STACK_LEAF_BYTES`` (1 MiB: every matrix. A stacked
matrix is still read in place, but is no longer a buffer of its own
that XLA prefetches ahead of its product: the 345M tick ran 1.34 ms
for 1.15, PERF.md 6, PR 45), a leaf alone in its group, a leaf spread
over more than one device; the page pool and the slot cache (the
write and decode kernels alias each leaf in place: a stacked pool is
the scan's carry again, ~40% of a step); the slot state. The rule
reads the leaf and nothing else: there is no option.
``server.params`` still reads and assigns the per-layer tree;
``serving/launch_leaves/{decode,prefill}`` count the array leaves of
every launch.

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
the fused loop, the ``serving/d2h_reads`` counter (arrays pulled to
the host inside ``step()``: one a launch, so on a plain server it
equals ``serving/device_ticks``), the
``serving/activations/{device_row,host_row}`` counters (where the
first logits of an activated slot came from), the
``serving/launch_leaves/{decode,prefill}`` counters (array leaves the
launches passed), the
``serving/harvest_deferred`` / ``serving/harvest_flushed/<why>`` /
``serving/harvest_rows_void`` counters of the deferred harvest, the
``serving/slow_steps`` / ``serving/slow_step/<phase>`` /
``serving/slow_step_cause/{host_busy,host_waiting}`` counters of the
slow-step record, and a
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
``serving/host_roundtrip_ms`` and ``summary()``'s decode time, names
the phase of a slow step and, from the driving thread's CPU seconds,
whether the host was busy or waiting in it; while a profiler session
runs its counts follow the root onto the trace as one
``serving/step_account ticks=.. chunks=.. live=.. committed=..``
point, so a trace's reader can tell a step that carried a prefill
chunk from one that did not, knows the batch each tick ran at and
what a verify tick committed
(docs/observability.md, "Host phases").
"""

from __future__ import annotations

import dataclasses as _dc
import json
import signal
import statistics
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.gpt.generation import (
    LOOP_EXIT_BUDGET, LOOP_EXIT_FINISHED, LOOP_EXIT_NONE,
    GenerationConfig, _compute_params, activate_slot, copy_kv_pages,
    decode_loop, decode_step, gather_kv_pages, init_page_pool,
    init_slot_cache, init_slot_state, pack_launch_params,
    prefill_chunk_paged, prefill_into_slots, scatter_kv_pages,
    unpack_harvest, verify_loop, verify_step,
)
from ..observability import metrics
from ..observability import server as obs_server
from ..observability.recorder import FlightRecorder
from ..observability.spans import Tracer
from ..observability.trace import annotate, point, unaccounted
from ..utils.log import logger
from .adapters import AdapterCache, AdapterCacheFull, insert_adapter
from .host_tier import HostSpillTier, RehydrateMiss, model_fingerprint
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
#: decoding steps of its own kind, with a prefill chunk or without
#: (judged once ``SLOW_STEP_MIN_HISTORY`` of them are in — the first
#: steps of a server compile). The floor lies under the 110-145 ms
#: steps that set a serving cell's spread and over every step the
#: benchmark's cells take in the normal way (3-48 ms)
SLOW_STEP_SECONDS = 0.05
SLOW_STEP_FACTOR = 5.0
SLOW_STEP_HISTORY = 64
SLOW_STEP_MIN_HISTORY = 8
#: the point that puts a step's counts on a profiler session's clock
#: (``observability/trace.py::point``); no ``serving/step`` or
#: ``serving/step/*`` pattern matches it
STEP_ACCOUNT = "serving/step_account ticks=%d chunks=%d live=%d " \
    "committed=%d"
_thread = threading.local()


def _cpu_mark() -> Tuple[float, float]:
    """``(perf_counter, thread_time)`` of the calling thread, read
    together at most ``SLOW_STEP_SECONDS`` ago. The thread's CPU clock
    is a system call (6 us on the chip's host, and more in place), so
    a step does not read it: it takes the thread's last reading, and
    reads anew only when that is older than the slow-step floor."""
    now = time.perf_counter()
    mark = getattr(_thread, "cpu_mark", None)
    if mark is None or now - mark[0] > SLOW_STEP_SECONDS:
        mark = _thread.cpu_mark = (now, time.thread_time())
    return mark


class StepRecord:
    """The host's account of one ``step()``: wall time of its start,
    seconds by phase (``phases``, filled by ``annotate``; the root's
    duration under ``STEP``), what the step did, and, for a step over
    the slow-step floor, the driving thread's CPU seconds over
    ``cpu_span``: the root and what lay between it and the thread's
    last reading of its CPU clock, under ``SLOW_STEP_SECONDS`` and so
    the lesser part."""

    __slots__ = ("start", "phases", "live", "queued", "chunks",
                 "ticks", "tokens", "cpu_seconds", "cpu_span")

    def __init__(self):
        self.start = time.time()
        self.phases: Dict[str, float] = {}
        #: rows of the launch whose harvest the step committed (of
        #: its own launch where it committed none)
        self.live = 0
        self.queued = 0      # queue depth once admission had run
        self.chunks = 0      # prefill chunks dispatched
        self.ticks = 0       # decode ticks whose harvest it committed
        self.tokens = 0      # tokens committed
        #: None on a step under the slow-step floor
        self.cpu_seconds: Optional[float] = None
        self.cpu_span: Optional[float] = None

    @property
    def seconds(self) -> float:
        """The root's duration."""
        return self.phases.get(STEP, 0.0)

    def tick_seconds(self) -> float:
        """What the decode launches cost the host in this step: the
        dispatch of its own, and the wait for the tokens of the one
        it read (the same launch on a synchronous step; the PREVIOUS
        one, long finished as a rule, where the read is deferred)."""
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
        def ms(seconds):
            return None if seconds is None else round(seconds * 1e3, 3)
        return {"start": round(self.start, 3),
                "dur_ms": ms(self.seconds),
                "cpu_ms": ms(self.cpu_seconds),
                "cpu_span_ms": ms(self.cpu_span),
                "phases_ms": self.phases_ms(), "live": self.live,
                "queued": self.queued, "chunks": self.chunks,
                "ticks": self.ticks, "tokens": self.tokens}


class _Launch(NamedTuple):
    """One decode launch the host has not read yet."""
    #: the launch's harvest array, its copy to the host asked for
    harvest: jax.Array
    #: ``(slot, request)`` of every row it ticked, as launched: by the
    #: time it is read a slot may hold no one, or someone else
    rows: List[Tuple[int, dict]]


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
    #: a speculative server's drafts for this request, each ``(i, d)``:
    #: the source proposed ``d`` for ``tokens[i]`` (the verify tick
    #: whose sampled token is ``tokens[i - 1]``), accepted or not; a
    #: re-admitted request's run on. None without speculation.
    drafts: Optional[List[Tuple[int, int]]] = None


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
        # device-resident decode: with T > 1 a step()'s launch is ONE
        # jitted decode_loop/verify_loop of up to T ticks per host
        # round-trip (docs/inference.md "Device-resident decode")
        self._loop_ticks = int(device_loop_ticks)
        self._roundtrips = 0
        # the same one-time cast as generate()'s
        params = _compute_params(params, jnp.dtype(model.config.dtype))
        # the layer loop unrolled, and the parameters as a launch
        # passes them: the small leaves stacked (module docstring,
        # "What a launch passes")
        model, self._launch_params = pack_launch_params(model, params)
        del params
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
            if prefill_chunk_pages < 1:
                raise ValueError(
                    f"prefill_chunk_pages must be >= 1, got "
                    f"{prefill_chunk_pages}")
            cfg = _dc.replace(cfg, kv_page_size=page_size,
                              kv_pool_pages=int(pool_pages))
            if hasattr(cfg, "window_class"):
                # a model with sliding-window layers brings a second
                # page class: a ring of pages a slot (below)
                cfg = cfg.window_class(num_slots,
                                       page_size * prefill_chunk_pages)
            if hasattr(cfg, "state_class"):
                # a model with recurrent layers (linear-attention,
                # state-space) brings a third kind of cache: a row of
                # recurrent state a slot (below)
                cfg = cfg.state_class(num_slots)
            model = type(model)(cfg)
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
            # the window class (models/smallthinker): each slot owns a
            # static ring of ``_ring`` pages on every window layer,
            # whose ids ride behind the global columns of the device
            # table (_sync_pt). The ring is the slot's alone, so a
            # prefix hit on the global class could not serve the window
            # layers: a model with window layers shares no prefix, and
            # each admission it would have looked up is counted
            # (serving/prefix_refused_window)
            self._ring = getattr(cfg, "window_ring_pages", 0)
            self._window_layers = getattr(cfg, "window_layers", 0)
            self._ring_cols = (
                1 + np.arange(num_slots, dtype=np.int32)[:, None]
                * self._ring + np.arange(self._ring, dtype=np.int32))
            # the state class (models/solar_open2's delta-rule layers,
            # models/granite_hybrid's state-space layers): each slot
            # owns ONE row of every recurrent layer's state leaves (a
            # float32 state a head and a convolution tail; not paged,
            # not growing; the model's config gives their count and
            # bytes: state_layers, state_row_bytes), row 0 the null
            # row. The row's id rides in
            # one more column of the device table (_sync_pt): 1 + slot
            # in the prefill view, 0 for a non-active slot in the
            # decode view, so a tick leaves a free or still-prefilling
            # slot's state alone. The model zeroes a row where a
            # sequence starts (chunk_start == 0), on the device. A
            # state is the whole prefix folded together: a page
            # registry cannot hand one over, so such a model shares no
            # prefix (serving/prefix_refused_recurrent), verifies no
            # drafts (a rejected draft would need the state rolled
            # back) and hands no KV to a peer
            self._state_layers = getattr(cfg, "state_layers", 0)
            self._state_cols = 1 + np.arange(
                num_slots, dtype=np.int32)[:, None]
            #: layers whose K/V live in the allocator's pages
            self._kv_layers = getattr(cfg, "kv_layers", cfg.num_layers) \
                - self._window_layers
            unshared = "window" if self._ring else \
                "recurrent" if self._state_layers else None
            #: why a server asked to share prefixes does not
            self._prefix_refused = unshared if prefix_sharing else None
            self._prefix_sharing = bool(prefix_sharing) and not unshared
            if self._state_layers and gen_cfg.spec_method is not None:
                raise ValueError(
                    "spec_method is refused on a model with recurrent "
                    "state: a rejected draft has already been folded "
                    "into the state, and there is no rollback")
            self._moe_published = np.zeros((6,), np.int64)
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
                    cfg.num_kv_heads, cfg.head_dim, self._page,
                    cfg.kv_cache_dtype)
                if host_pages < 1:
                    raise ValueError(
                        f"host_pool_bytes ({host_pool_bytes}) smaller "
                        f"than one KV page")
            self._alloc = PageAllocator(cfg.kv_pool_pages, self._page,
                                        host_pages=host_pages)
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
        self.model = model
        self.gen_cfg = gen_cfg
        self.num_slots = num_slots
        # speculative decoding: the draft source proposes (a host
        # object, or the model's own block inside the tick program),
        # the jitted verify_step scores/commits; spec-off is the plain
        # decode_step tick
        self.spec = gen_cfg.spec_method is not None
        self._spec_k = gen_cfg.spec_tokens
        self._draft = make_draft_source(gen_cfg.spec_method, model=model) \
            if self.spec else None
        #: the source drafts inside the tick program: the host fills no
        #: draft array and has no reason to read a launch at once
        self._device_draft = getattr(self._draft, "on_device", False)
        if self._device_draft:
            if self._spec_k != self._draft.tokens:
                raise ValueError(
                    f"spec_tokens ({self._spec_k}) must be what the "
                    f"model's multi-token-prediction blocks draft a "
                    f"tick ({self._draft.tokens})")
            if not self.paged or self._prefix_sharing or \
                    self._loop_ticks > 1:
                raise ValueError(
                    "spec_method='mtp' is served paged, one tick a "
                    "launch and without prefix sharing: the block's "
                    "cache is prefilled beside the model's own, and a "
                    "fused launch read late is not implemented")
        if self.spec:
            metrics.inc("serving/spec_source/" + gen_cfg.spec_method)
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
        self._cache = init_page_pool(
            model, self._launch_params, num_slots) if self.paged else \
            init_slot_cache(model, self._launch_params, num_slots)
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
        # array leaves a launch passes beside the parameters' arrays
        # (serving/launch_leaves/*): the cache's; for a tick the slot
        # state, the key, the page table, a host source's drafts and
        # the fused loop's flag; for a chunk its tokens, start, table
        # row, valid count and a device source's (next tokens, slots);
        # for an admission into contiguous rows the state, slots,
        # tokens, lengths and nonces
        held = len(jax.tree.leaves(self._cache)) + \
            (self._adapters is not None)
        state = len(jax.tree.leaves(self._state))
        self._decode_extra = held + state + 1 + self.paged + \
            (self.spec and not self._device_draft) + \
            (self._loop_ticks > 1)
        self._prefill_extra = held + (
            4 + 2 * self._device_draft if self.paged else state + 4)
        #: completions the next step() hands out: admission-time
        #: request failures (e.g. unknown adapter id), and what a read
        #: outside step() found finished (_flush)
        self._done: List[Completion] = []
        #: the decode launch whose harvest has not been read (module
        #: docstring, "Deferred harvest"); never more than one
        self._inflight: Optional[_Launch] = None
        #: ``(prompt key, row)`` of every registered prompt whose last
        #: logits row the registry still holds on the device
        #: (:meth:`_land_rows`)
        self._rows_out: deque = deque()
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
        #: durations of the last decoding steps, those with no prefill
        #: chunk and those with one: the slow-step baselines
        self._recent_steps = (deque(maxlen=SLOW_STEP_HISTORY),
                              deque(maxlen=SLOW_STEP_HISTORY))
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
        # Blocking primitives never run under it: the spill tier only
        # COLLECTS writer items under it, and the public wrappers
        # ship them to its writer after releasing the lock
        # (HostSpillTier.ship); writer waits go through the tier's
        # own condition, which the writer thread can always take.
        self._surface_lock = threading.RLock()
        self._closed = False
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
        #: the hierarchical KV cache's host spill tier
        #: (core/host_tier.py); None unless host_pool_bytes is set
        self._tier: Optional[HostSpillTier] = None
        if self.paged and self._alloc.host_pages:
            # the fingerprint is computed here, eagerly: its
            # jax.device_get must never run under the surface lock
            self._tier = HostSpillTier(
                self._alloc, int(host_pool_bytes), cfg.kv_cache_dtype,
                model_fingerprint(cfg, self.params),
                self._read_pages, self._write_pages, self._emit,
                self._metrics)
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

    @property
    def params(self):
        """The per-layer parameter tree, as the model's ``apply`` takes
        it. Reading it slices the launch's stacks apart (set-up and
        tests do; a step never does); assigning it packs the tree
        again, keeping every stack none of whose leaves is another
        array than the last read handed out, so ``srv.params =
        f(srv.params)`` costs what ``f`` changed."""
        with self._surface_lock:
            return self._launch_params.tree()

    @params.setter
    def params(self, tree) -> None:
        with self._surface_lock:
            self._launch_params = self._launch_params.assign(tree)

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
        admissions, an occupied slot, a decode launch not read yet,
        an unfinished chunked prefill, or tiered spill work (pinned
        pages awaiting their yield-point drain, or collected writer
        items awaiting shipment). Async
        fleet worker threads poll this to park when their replica is
        idle (docs/fleet_serving.md "Async router")."""
        with self._surface_lock:
            if self._queue or any(s is not None for s in self._slots):
                return True
            if self._inflight is not None:
                return True
            if self.paged and self._prefilling:
                return True
            if self._tier is not None and self._tier.work_pending():
                return True
            if self._done:
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
            # server's compute dtype
            self.params = insert_adapter(
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
        self._done.append(Completion(
            request_id=req["id"], prompt=req["prompt"],
            tokens=req["tokens"], finish_reason=reason,
            trace_id=self._trace_id(req)))

    def _take_done(self) -> List[Completion]:
        out, self._done = self._done, []
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
                self.model, self._launch_params, self._cache,
                self._state,
                jnp.asarray([slot], jnp.int32), jnp.asarray(row),
                jnp.asarray([len(seq)], jnp.int32),
                jnp.asarray([req["nonce"]], jnp.int32),
                jnp.asarray([int(self._aid_np[slot])], jnp.int32)
                if self._adapters is not None else None)
            if req["tokens"]:
                self._state = self._state._replace(
                    dec_count=self._state.dec_count.at[slot].set(
                        len(req["tokens"])))
            metrics.inc("serving/launch_leaves/prefill",
                        len(self._launch_params.arrays)
                        + self._prefill_extra)
            req["active"], req["ahead"] = True, 0
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
        act = np.zeros((self.num_slots, 1), bool)
        for s, r in enumerate(self._slots):
            if r is not None and r.get("active"):
                act[s, 0] = True
        dec = np.where(act, self._pt, NULL_PAGE).astype(np.int32)
        full = self._pt
        if self._ring:
            # the slots' ring pages behind the global columns
            full = np.concatenate([full, self._ring_cols], axis=1)
            dec = np.concatenate([dec, self._ring_cols], axis=1)
        if self._state_layers:
            # the slots' state rows behind those: the null row for a
            # slot the tick must not touch
            full = np.concatenate([full, self._state_cols], axis=1)
            dec = np.concatenate(
                [dec, np.where(act, self._state_cols, 0)], axis=1)
        self._pt_dev = jnp.asarray(full)
        self._pt_dev_dec = jnp.asarray(dec.astype(np.int32))
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

    def _activate(self, slot: int, logits, row: int = 0) -> jax.Array:
        """Flip a placed slot live: per-slot SlotState from the host's
        view of the request (seq = prompt + already-emitted tokens, so
        resumes re-enter mid-request). The first sampling logits are
        row ``row`` of ``logits``: a last chunk's output as the chunk
        left it on the device, or a registered prompt's row wherever
        the registry holds it. The row is picked inside the one jitted
        call and read by no one here; it is returned, a device array,
        for the registry."""
        req = self._slots[slot]
        seq = req["prompt"] + req["tokens"]
        appeared = np.zeros((self.model.config.vocab_size,), bool)
        appeared[np.asarray(seq, np.int64)] = True
        # at 0 too, so that a reader tells "never" from "no such
        # counter": a host row is uploaded, a device row goes nowhere
        on_device = isinstance(logits, jax.Array)
        metrics.inc("serving/activations/device_row", int(on_device))
        metrics.inc("serving/activations/host_row", int(not on_device))
        self._state, last = activate_slot(
            self._state, np.int32(slot), np.int32(len(seq)),
            np.int32(len(req["tokens"])), np.int32(req["nonce"]),
            appeared, logits, np.int32(row),
            np.int32(req.pop("spec_rejected", -1)))
        req["active"], req["ahead"] = True, 0
        req["cur_len"] = len(seq)
        self._pt_dirty = True   # decode view must unhide this row
        self._phase(req, "serving/decode", slot=slot)
        return last

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
                    promoted = self._rehydrate(host_ids)
                except RehydrateMiss:
                    # a failed spill surfaced mid-batch: nothing was
                    # mapped yet (the batch allocates only once every
                    # page's bytes arrived) and the reap dropped the
                    # dead page's registrations, so the retry
                    # re-prefills cold on the next pass
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
            if self._prefix_refused == "window":
                metrics.inc("serving/prefix_refused_window")
            elif self._prefix_refused == "recurrent":
                metrics.inc("serving/prefix_refused_recurrent")
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
                promoted = self._rehydrate(host_ids)
            except RehydrateMiss:
                # same unwind as the prompt-hit path: the dead prefix
                # page's registration is gone, so the retry shares
                # fewer pages and prefills the rest
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

        Phases, siblings under the root: ``prefill_pump`` is the
        host's bookkeeping (the chunk's row built in numpy, the page
        table's upload, counters, and after a prompt's last chunk the
        slot's activation and the registries); ``prefill_dispatch`` is
        the chunk's jitted call with the upload of its operands, there
        if and only if the step launched a chunk. Nothing here reads
        the device: a prompt's last logits row goes from the chunk's
        output to the slot's state inside the activation's program
        (:meth:`_activate`), so the step that ends a prompt launches
        its tick, the new slot in it, without waiting for the
        chunk."""
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
            real = min(self._chunk, L - c0)
            row[0, :real] = seq[c0:c0 + real]
            shifted = None
            if self._device_draft:
                # the block at position i reads token i + 1: the same
                # row a token on (the prompt's last position has none
                # yet: the first tick folds it)
                shifted = np.full_like(row, self.gen_cfg.pad_token_id)
                nxt = seq[c0 + 1:c0 + real + 1]
                shifted[0, :len(nxt)] = nxt
                metrics.inc("serving/mtp_positions/prefill", len(nxt))
            self._sync_pt()
        with annotate("serving/step/prefill_dispatch", ph):
            self._cache, logits = prefill_chunk_paged(
                self.model, self._launch_params, self._cache,
                jnp.asarray(row),
                jnp.asarray([c0], jnp.int32),
                self._pt_dev[slot:slot + 1],
                jnp.asarray([int(self._aid_np[slot])], jnp.int32)
                if self._adapters is not None else None,
                jnp.asarray([real], jnp.int32),
                None if shifted is None else
                (jnp.asarray(shifted), jnp.asarray([slot], jnp.int32)))
        with annotate("serving/step/prefill_pump", ph):
            req["prefill_pos"] = c0 + self._chunk
            if self._state_layers and c0 == 0:
                # the model zeroed the slot's rows on the device
                metrics.inc("serving/state_resets", self._state_layers)
            if self._ring:
                # the chunk's pages past the ring's first lap each went
                # over a page that had fallen behind the window
                metrics.inc(
                    "serving/window_pages_reused", self._window_layers
                    * sum(j >= self._ring for j in range(
                        c0 // self._page, (c0 + self._chunk) // self._page)))
            self._prefill_chunk_count += 1
            rec.chunks += 1
            metrics.inc("serving/prefill_chunks")
            metrics.inc("serving/launch_leaves/prefill",
                        len(self._launch_params.arrays)
                        + self._prefill_extra)
            self._emit("serving_prefill_chunk", request=req["id"],
                       slot=slot, start=c0, tokens=real,
                       trace=self._trace_id(req))
            if req["prefill_pos"] < L:
                return
            self._prefilling.popleft()
            del req["prefill_pos"]
            # the chunk-rounded admission allocated pages for the final
            # chunk's pad tail too; that KV is never read, so hand
            # those pages straight back to the pool instead of pinning
            # them (and the registries below) until evict
            self._trim_pages(slot, req, -(-L // self._page))
            # the last real token sits at chunk row L - 1 - c0; the
            # row goes from the chunk to the slot's state on the device
            last = self._activate(slot, logits, L - 1 - c0)
            # adapter-tinted KV must never enter the shared registries
            # (_admit_paged's share rule — base-only content
            # addressing)
            if self._prefix_sharing and not req.get("adapter_id"):
                keys = page_prefix_keys(seq, self._page)
                for j, kk in enumerate(keys):
                    self._alloc.register_prefix(
                        kk, int(self._pt[slot, j]))
                key = prompt_key(seq)
                self._alloc.register_prompt(
                    key,
                    [int(p) for p in self._pt[slot, :req["num_pages"]]],
                    last)
                # the registry keeps a HOST row (a registered prompt
                # costs no HBM): the copy starts when the chunk ends
                # and is taken where it is home (_land_rows)
                last.copy_to_host_async()
                self._rows_out.append((key, last))

    def _land_rows(self, wait: bool = False) -> None:
        """Hand the prompt registry the host's copy of every logits
        row that is home, oldest first, and let the device's go. What
        a step's commit and ``prefill_step`` end with: a row is home
        once the launch queued behind its chunk has been read (the
        step after the one that made it, on a decoding server), and a
        row whose chunk is still running is left for the next call:
        nothing here waits for the device, unless ``wait`` says so
        (outside ``step()``: what serialises the registry)."""
        while self._rows_out and (
                wait or self._rows_out[0][1].is_ready()):
            key, row = self._rows_out.popleft()
            self._alloc.replace_prompt_payload(key, row, np.asarray(row))

    def _trim_pages(self, slot: int, req: dict, used: int) -> None:
        """Hand the slot's pages past its first ``used`` back to the
        pool."""
        if used < req["num_pages"]:
            for j in range(used, req["num_pages"]):
                self._release_page(int(self._pt[slot, j]))
                self._pt[slot, j] = NULL_PAGE
            req["num_pages"] = used
            self._pt_dirty = True

    def _release_pages(self, slot: int) -> None:
        req = self._slots[slot]
        for j in range(req.get("num_pages", 0)):
            pid = int(self._pt[slot, j])
            if pid != NULL_PAGE:
                self._release_page(pid)
        self._pt[slot, :] = NULL_PAGE
        self._pt_dirty = True
        req["num_pages"] = 0

    def _release_page(self, pid: int) -> None:
        """Release one reference to a slot-mapped page (a tiered
        server keeps a registered page's last one as a spill pin)."""
        if self._tier is not None:
            self._tier.release(pid)
        else:
            self._alloc.release(pid)

    # the spill tier's (and the KV handoff's) two ways to the device
    # pages: ONE stacked dispatch each; the surface lock is re-entrant

    def _read_pages(self, pids: Sequence[int]):
        with self._surface_lock:
            return gather_kv_pages(
                self._cache, jnp.asarray(list(pids), jnp.int32))

    def _write_pages(self, stacked, pids: Sequence[int]) -> None:
        with self._surface_lock:
            self._cache = scatter_kv_pages(
                self._cache, stacked, jnp.asarray(pids, jnp.int32))

    def _rehydrate(self, host_ids: List[int]) -> Dict[int, int]:
        """Host id -> the fresh HBM page its KV came back into."""
        if not host_ids:
            return {}
        return dict(zip(host_ids,
                        self._tier.rehydrate(host_ids, self._ticks)))

    def _alloc_or_preempt(self, needy_slot: int) -> int:
        """A free page, preempting the youngest OTHER occupied slot
        (whole request back to the queue HEAD, pages released) until
        one exists. Config validation guarantees a lone slot can
        always grow to its maximum length, so this terminates."""
        pid = self._alloc.try_alloc()
        while pid is None:
            if self._tier is not None and self._tier.reclaim_pin():
                # a pinned to-be-spilled page went back to the pool:
                # one lost spill, never a preemption
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
            metrics.inc("serving/d2h_reads")
        self._release_pages(victim)
        # the pin drops but the adapter stays resident/warm —
        # re-admission re-pins it (a hit) and resumes token-exactly
        self._release_adapter(victim, req)
        if victim in self._prefilling:
            self._prefilling.remove(victim)
        self._slots[victim] = None
        self._deactivate(victim)
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

    def _page_needs(self, live: List[int], window: int
                    ) -> List[Tuple[int, dict, int]]:
        """``(slot, request, column)`` of every page the launch's
        write window still wants: each live slot's next ``window``
        positions (one for a plain tick, k+1 a verify tick) start at
        the length the DEVICE holds: ``cur_len + ahead`` at least
        (``ahead`` counts the row's launches the host has not read: 1
        while a one-tick launch is in flight, the only kind ever left
        unread, and the host's ``cur_len`` then a tick old; 0 whenever
        a host-drafting or fused server maps pages) and, where the
        unread launch is a verify tick that may have committed k+1,
        ``cur_len + (k + 1) * ahead`` at most; they must land in
        pages the slot owns alone: a column past its pages wants a
        fresh one, a shared page a copy. Columns in rising order a
        slot; at most one page a need."""
        cap = self.model.config.cache_capacity
        most = (self._spec_k + 1) if self.spec else 1
        out = []
        for slot in live:
            req = self._slots[slot]
            pos = req["cur_len"] + req["ahead"]
            end = req["cur_len"] + most * req["ahead"] + window
            # length bound enforced at submit; a verify window's tail
            # past capacity clips to capacity - 1 and is never
            # committed (mmax)
            for j in range(pos // self._page,
                           -(-min(end, cap) // self._page)):
                if j >= req["num_pages"] or self._alloc.refcount(
                        int(self._pt[slot, j])) > 1:
                    out.append((slot, req, j))
        return out

    def _page_maintenance(self, needs: List[Tuple[int, dict, int]]
                          ) -> None:
        """Before every decode launch: serve its ``_page_needs`` —
        map fresh pages at page boundaries, and split shared pages
        copy-on-write (device page copy + host refcount handoff) at
        the first divergent write; the pool running dry preempts the
        youngest other slot (:meth:`_alloc_or_preempt`), which only
        happens with no launch unread (:meth:`_run_step` reads it
        first). Pages mapped for window positions past a verify
        tick's accepted point are returned to the pool by the commit's
        trim."""
        for slot, req, j in needs:
            if self._slots[slot] is not req:
                continue    # preempted for an earlier need
            if j >= req["num_pages"]:
                self._pt[slot, j] = self._alloc_or_preempt(slot)
                req["num_pages"] = j + 1
                self._pt_dirty = True
                continue
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
        self._deactivate(slot)
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
                          if "ttft" in req else None,
                          drafts=req.get("drafts"))

    def preempt(self, request_id: int) -> Optional[Completion]:
        """Cancel a request (client abort / scheduler decision): evict
        its slot — or drop it from the queue — and return the partial
        completion. None when the id is unknown/already finished."""
        with self._surface_lock:
            return self._preempt_impl(request_id)

    def _preempt_impl(self, request_id: int) -> Optional[Completion]:
        # the partial holds the request's newest token: read what is
        # in flight first. If that read finished the request, its
        # completion is the answer
        self._flush("preempt")
        for i, comp in enumerate(self._done):
            if comp.request_id == request_id:
                return self._done.pop(i)
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
        mark = _cpu_mark()
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
                    with annotate("serving/step/commit", rec.phases):
                        self._land_rows()
                    metrics.get_registry().set_gauge(
                        "serving/pages_in_use",
                        self._alloc.pages_in_use)
                progress = rec.queued != q0 or rec.chunks > 0
            with annotate("serving/step/ship_spills", rec.phases):
                if self._tier is not None:
                    self._tier.ship()
        with self._surface_lock:
            self._account_step(rec, mark)
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
        flight. Returns ``(pages, last_logits)`` or None on a miss
        (the row as the registry holds it: still a device array for a
        prompt that ended a step ago, numpy from then on;
        :meth:`kv_import` takes either); the caller must
        :meth:`kv_export_release` the pages once the peer holds a copy
        (or on any failure path)."""
        with self._surface_lock:
            if not self.paged:
                return None
            if self._state_layers:
                # pages are not the whole of such a model's cache
                metrics.inc("serving/kv_handoff_refused_recurrent")
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
        return self._read_pages(pages)

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
            if self.paged and self._state_layers:
                metrics.inc("serving/kv_handoff_refused_recurrent")
                return False
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
            self._write_pages(page_data, pids)
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

    # -- restart-persistent prefix store (core/host_tier.py) ----------

    def export_prefix_store(self) -> Optional[dict]:
        """Snapshot the host tier for a restart warm start: collect
        any pending spill pins first (a just-drained server's
        shareable pages are still pinned), ship the batch and wait
        out the writer — both with the surface lock released — and
        return page bytes (flat numpy leaf lists in cache tree order)
        plus the host-resident registry entries. None on non-tiered
        servers."""
        if self._tier is None:
            return None
        with self._surface_lock:
            self._land_rows(wait=True)
            self._tier.collect(self._ticks, self._roundtrips)
        self._tier.ship()
        self._tier.await_writer()
        with self._surface_lock:
            return self._tier.export_store()

    def import_prefix_store(self, store: Optional[dict]) -> int:
        """Adopt an exported prefix store on a fresh server, so the
        next admission of a covered prompt rehydrates instead of
        re-prefilling. A store of another geometry or another model
        (fingerprint) imports nothing. Returns the pages adopted."""
        if self._tier is None:
            return 0
        with self._surface_lock:
            return self._tier.import_store(
                store, jax.tree_util.tree_structure(self._cache))

    # -- the serving loop ---------------------------------------------

    def step(self) -> List[Completion]:
        """Admit what fits, advance at most one prefill chunk (paged),
        launch a tick of every ACTIVE slot — one token plain, 1..k+1
        committed tokens speculative — then read and commit the launch
        BEFORE this one (this one on a speculative, fused-loop or
        draining server: module docstring, "Deferred harvest"), evict
        and return whatever that found finished (deadline-expired
        requests included, as ``deadline_exceeded`` partials). A
        completion so comes out of the ``step()`` after the one whose
        tick ended it. While draining, admission is skipped.

        With ``device_loop_ticks > 1`` one call runs up to that many
        ticks in a single fused device program (:meth:`_launch`) —
        same committed tokens, T× fewer host round-trips.

        Thread-safe: the whole tick runs under the surface lock;
        spill shipping (the one blocking queue put) happens after the
        lock is released so the writer thread can never be fed from
        inside the critical section."""
        rec = StepRecord()
        mark = _cpu_mark()
        with annotate("serving/step", rec.phases):
            with self._surface_lock:
                if self._closed:
                    return []
                out = self._run_step(rec)
            with annotate("serving/step/ship_spills", rec.phases):
                if self._tier is not None:
                    self._tier.ship()
        with self._surface_lock:
            self._account_step(rec, mark)
        return out

    def _account_step(self, rec: StepRecord,
                      mark: Tuple[float, float]) -> None:
        """Feed one finished step's record to what is kept of the
        series (each interval was clocked once, by ``annotate``),
        put its counts on a running profiler session's clock, directly
        after the root they belong to, and judge it against the
        slow-step thresholds: its own floor and the median of the last
        decoding steps of its kind, since a step that carries a
        prefill chunk is a few times one that does not. ``mark`` is
        the driving thread's ``_cpu_mark()`` from before the root;
        only a step over the floor reads the thread's CPU clock, here.
        Under the surface lock, like every other write to the
        server's counters."""
        # what a burst does to a server whose slots are bounded (by the
        # state rows, on a model with recurrent state); counted at 0
        # too, so that a reader tells "never" from "no such counter"
        metrics.inc("serving/slots_full_steps",
                    int(bool(self._queue) and None not in self._slots))
        seconds = rec.seconds
        over_floor = seconds > SLOW_STEP_SECONDS
        if over_floor:
            now = _thread.cpu_mark = (time.perf_counter(),
                                      time.thread_time())
            rec.cpu_span = now[0] - mark[0]
            rec.cpu_seconds = now[1] - mark[1]
        self.last_step = rec
        point(STEP_ACCOUNT, rec.ticks, rec.chunks, rec.live, rec.tokens)
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
        recent = self._recent_steps[rec.chunks > 0]
        if over_floor and len(recent) >= SLOW_STEP_MIN_HISTORY:
            median = statistics.median(recent)
            if seconds > SLOW_STEP_FACTOR * median:
                self._slow_step(rec, median, len(recent))
        if rec.ticks:
            recent.append(seconds)

    def _slow_step(self, rec: StepRecord, median_s: float,
                   history: int) -> None:
        """Put a slow step on the record: which phase took most of
        it, whether the thread was at work in it (CPU time at least
        half of ``cpu_span``, of which the step is the greater part:
        ``host_busy``) or was not (``host_waiting``: blocked in the
        runtime, or not scheduled), and the whole account."""
        record = rec.as_dict()
        phases = record["phases_ms"]
        worst = max(phases, key=phases.get)
        cause = "host_busy" if rec.cpu_seconds >= 0.5 * rec.cpu_span \
            else "host_waiting"
        metrics.inc("serving/slow_steps")
        metrics.inc("serving/slow_step/" + worst)
        metrics.inc("serving/slow_step_cause/" + cause)
        logger.warning(
            "slow step(): %.0f ms (%.0f ms of CPU in %.0f ms: %s) "
            "against a median of %.0f ms over the last %d decoding "
            "steps %s a chunk, most of it in %s: %s",
            record["dur_ms"], record["cpu_ms"], record["cpu_span_ms"],
            cause, median_s * 1e3, history,
            "with" if rec.chunks else "without", worst,
            json.dumps(record))
        self._emit("serving_slow_step", worst=worst, cause=cause,
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
            if self._tier is not None:
                self._tier.collect(self._ticks, self._roundtrips)
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
            live = []
            for slot, req in enumerate(self._slots):
                if req is None or not req.get("active"):
                    continue
                if len(req["tokens"]) + req["ahead"] >= \
                        self.gen_cfg.max_dec_len:
                    # the tick in flight is the last its budget buys
                    self._retire(slot, req)
                    continue
                live.append(slot)
            rec.live = len(live)
            if live:
                self._sync_aid()
        return expired, live

    def _retire(self, slot: int, req: dict) -> None:
        """Take a row off the device whose LAST tick is still unread:
        with it the request has all the tokens ``max_dec_len`` allows,
        whatever it holds, so no launch ticks the row again (the
        one-tick program checks no budget; a tick past it would write
        one position past what submit() bounded). The slot keeps the
        request until that harvest is committed."""
        req["active"] = False
        self._deactivate(slot)
        if self.paged:
            self._pt_dirty = True   # the decode view nulls the row

    def _deactivate(self, slot: int) -> None:
        """The device's side of a slot given up: no tick advances the
        row, and no stale ``finished`` meets its next request."""
        self._state = self._state._replace(
            active=self._state.active.at[slot].set(False),
            finished=self._state.finished.at[slot].set(False))

    def _count_decode_walk(self, rows: List[Tuple[int, dict]],
                           window: int) -> None:
        """One tick of the paged decode kernel, as the host knows it
        without a device read: the rows it walks (``rows`` of the
        launch, at their lengths before the tick; a void row was
        walked like any other) of the slots it was launched for, and
        the pages (its blocks, at the cells' page size) it walks of
        the table's capacity."""
        last = self._max_pages - 1
        metrics.inc("serving/decode_rows_live", len(rows))
        metrics.inc("serving/decode_rows_slots", self.num_slots)
        metrics.inc("serving/decode_blocks_live", sum(
            min((req["cur_len"] + window - 1) // self._page, last) + 1
            for _, req in rows))
        metrics.inc("serving/decode_blocks_capacity",
                    self.num_slots * self._max_pages)
        # what the walked slots hold, by class: allocator pages on the
        # layers that keep whole sequences, a row of state on the
        # recurrent layers (ring pages: _count_page_classes)
        metrics.inc("serving/pages_global_held", self._kv_layers * sum(
            req["num_pages"] for _, req in rows))
        if self._state_layers:
            metrics.inc("serving/state_rows_held",
                        self._state_layers * len(rows))
        if self._ring:
            self._count_page_classes(rows, window)

    def _count_page_classes(self, rows: List[Tuple[int, dict]],
                            window: int) -> None:
        """The same tick by page class (a model with window layers):
        pages held by the slots it walks, global layers whole
        sequences and window layers no more than their ring; the
        blocks the walk visits against what it would without windows;
        ring pages written over for the first time this tick."""
        glob = self._kv_layers
        reach = self.model.config.sliding_window_size
        held = whole = walked = reused = 0
        for _, req in rows:
            cur, n = req["cur_len"], req["num_pages"]
            held += min(n, self._ring)
            last = min((cur + window - 1) // self._page,
                       self._max_pages - 1)
            whole += last + 1
            walked += last + 1 - max((cur + 1 - reach) // self._page, 0)
            reused += cur % self._page == 0 and cur // self._page \
                >= self._ring
        metrics.inc("serving/pages_window_held",
                    self._window_layers * held)
        metrics.inc("serving/window_pages_reused",
                    self._window_layers * reused)
        metrics.inc("serving/kv_blocks_whole",
                    (glob + self._window_layers) * whole)
        metrics.inc("serving/kv_blocks_walked",
                    glob * whole + self._window_layers * walked)

    # -- the decoding step --------------------------------------------
    #
    # One step() makes ONE device launch: a one-tick program, or with
    # device_loop_ticks > 1 a fused loop (module docstring,
    # "Device-resident decode"), and as a rule ONE read: of the launch
    # before it, or of its own ("Deferred harvest"). When the host
    # flags pending scheduling work the loop runs exactly one tick, so
    # drain(max_ticks) and chunked prefill keep their
    # one-unit-of-progress-per-step contracts.

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
        if self._draining or self._queue:
            return True
        if self.paged:
            if self._prefilling:
                return True
            if self._tier is not None and self._tier.pinned:
                # pinned spills drain at step entry — exit after one
                # tick so the writer gets its work this round-trip
                return True
            per_tick = (self._spec_k + 1) if self.spec else 1
            return len(self._page_needs(
                live, self._loop_ticks * per_tick)) \
                > self._alloc.free_pages
        return False

    def _launch(self, drafts, host_flag: bool) -> jax.Array:
        """The step's one device launch, and the only place that
        knows which of the four tick programs runs. Hands back the
        launch's harvest array (``generation.pack_harvest``: tokens,
        counts, ``finished``, ``dec_count``, ticks run, exit code)
        with its copy to the host already asked for: the copy is
        queued behind the program and starts when it ends, without
        waiting for the host to notice."""
        T = self._loop_ticks
        pt = self._pt_dev_dec if self.paged else None
        if T == 1 and self.spec:
            self._cache, self._state, harvest = verify_step(
                self.model, self._launch_params, self._cache,
                self._state,
                None if drafts is None else jnp.asarray(drafts[:, 0]),
                self._rng, self.gen_cfg, pt, self._aid_arg())
        elif T == 1:
            self._cache, self._state, harvest = decode_step(
                self.model, self._launch_params, self._cache,
                self._state,
                self._rng, self.gen_cfg, pt, self._aid_arg())
        elif self.spec:
            self._cache, self._state, harvest = verify_loop(
                self.model, self._launch_params, self._cache,
                self._state,
                jnp.asarray(drafts), self._rng, self.gen_cfg,
                jnp.int32(host_flag), pt, self._aid_arg(),
                loop_ticks=T)
        else:
            self._cache, self._state, harvest = decode_loop(
                self.model, self._launch_params, self._cache,
                self._state,
                self._rng, self.gen_cfg, jnp.int32(host_flag), pt,
                self._aid_arg(), loop_ticks=T)
        harvest.copy_to_host_async()
        metrics.inc("serving/launch_leaves/decode",
                    len(self._launch_params.arrays) + self._decode_extra)
        return harvest

    def _read_now(self) -> Optional[str]:
        """Why this server's launches are read in the step that makes
        them, or None where the read waits for the next launch. What
        the server observes of itself, not an option: a host draft
        source proposes from the newest committed tokens (a source on
        the device needs nothing from the tick in flight); a fused
        loop's launch stops itself on what the host would otherwise
        learn a launch late; drain() counts ticks and ends on an empty
        server."""
        if self.spec and not self._device_draft:
            return "spec"
        if self._loop_ticks > 1:
            return "loop"
        if self._draining:
            return "drain"
        return None

    def _run_step(self, rec: StepRecord) -> List[Completion]:
        """The body of :meth:`step`: schedule, draft, map pages,
        launch, THEN read and commit the launch before this one
        (module docstring, "Deferred harvest"), or this one where
        :meth:`_read_now` names a cause. Greedy/seeded output is
        token-exact whatever the order of read and launch and whatever
        ``device_loop_ticks`` is (tests/test_serving.py parity
        matrices)."""
        ph = rec.phases
        expired, live = self._schedule(rec)
        T = self._loop_ticks
        k = self._spec_k if self.spec else 0
        launch = None
        if live:
            host_flag = False
            if T > 1:
                with annotate("serving/step/page_maintenance", ph):
                    host_flag = self._loop_host_flag(live)
            # flag up -> the loop exits after one tick, so drafting
            # and page pre-mapping cover one tick's window only (the
            # launch shape stays [slots, T, ...]: loop_ticks is
            # static, the flag is traced, nothing recompiles)
            eff_ticks = 1 if host_flag else T
            if self.paged:
                live = self._map_pages(rec, live, eff_ticks * (k + 1))
        if live:
            drafts = None
            if self.spec and not self._device_draft:
                with annotate("serving/step/draft", ph):
                    # host drafts ride down with the launch, k per
                    # tick, all proposed from the pre-launch history
                    # (tick j verifies chunk j); inactive rows are
                    # zeros the verify mask never commits
                    drafts = np.zeros((self.num_slots, T, k), np.int32)
                    for slot in live:
                        req = self._slots[slot]
                        drafts[slot, :eff_ticks] = np.asarray(
                            self._draft.propose(
                                req["prompt"] + req["tokens"],
                                k * eff_ticks),
                            np.int32).reshape(eff_ticks, k)
            if self.paged:
                with annotate("serving/step/table_sync", ph):
                    self._sync_pt()
            with annotate("serving/step/decode_dispatch", ph):
                launch = _Launch(self._launch(drafts, host_flag),
                                 [(s, self._slots[s]) for s in live])
                for _, req in launch.rows:
                    req["ahead"] += 1
        if self._inflight is not None:
            # the launch above is queued behind it on the device
            self._read_launch(self._inflight, rec,
                              None if launch else "idle")
        if launch is not None:
            why = self._read_now()
            if why is None:
                self._inflight = launch
            else:
                self._read_launch(launch, rec, why)
        with annotate("serving/step/commit", ph):
            self._land_rows()
            metrics.get_registry().set_gauge(
                "serving/slot_occupancy", self.occupancy)
            self._refresh_health()
            return expired + self._take_done()

    def _map_pages(self, rec: StepRecord, live: List[int],
                   window: int) -> List[int]:
        """Page growth and copy-on-write for the launch's write
        window, decided against the lengths the device holds
        (:meth:`_page_needs`); returns the slots still to launch. A
        slot preempted out from under the launch (pool exhaustion)
        goes down nulled: nothing is drafted for it and nothing of it
        is committed. The pool is only ever robbed with no launch
        unread: where the needs outrun the free pages the launch in
        flight is read first, which frees what it finished and leaves
        every victim's newest token committed."""
        ph = rec.phases

        def still_live(slots):
            return [s for s in slots
                    if (req := self._slots[s]) is not None
                    and req.get("active")]
        with annotate("serving/step/page_maintenance", ph):
            needs = self._page_needs(live, window)
            read_first = self._inflight is not None and \
                len(needs) > self._alloc.free_pages
            if not read_first:
                self._page_maintenance(needs)
                live = still_live(live)
        if read_first:
            self._read_launch(self._inflight, rec, "preempt")
            with annotate("serving/step/page_maintenance", ph):
                live = still_live(live)
                self._page_maintenance(self._page_needs(live, window))
                live = still_live(live)
        rec.live = len(live)
        return live

    def _read_launch(self, launch: _Launch, rec: StepRecord,
                     why: Optional[str]) -> None:
        """Read ``launch``'s harvest, the ONE read of the device a
        launch costs, and commit it: replay what came back one tick at
        a time so ``serving/decode_tokens``, the TTFT stamps (a fused
        launch's are interpolated across its wall time),
        ``serving/tick_ms`` and the per-tick ``serving_spec`` events
        stay tick-accurate, then evict what finished; its completions
        join ``_done``. ``why`` names the cause of a read that did not
        wait for the next launch (None: it did).

        A row is committed to the request it was launched for. By the
        time of the read its slot may hold no one, or someone else:
        the commit of the launch before found the request finished,
        its deadline passed, a client cancelled it. Such a row is
        VOID: nothing of it is committed. What it wrote is one column
        at its request's last length, in a page that was the slot's
        alone when it was launched (:meth:`_page_needs`) and that the
        eviction has since released, in the slot's ring, or in its
        state row; whoever holds any of them next got it through a
        LATER launch (a prefill chunk, a page copy, a scatter, a
        tick), which the device runs after this one, and reads no
        position it has not itself written since."""
        ph = rec.phases
        if launch is self._inflight:
            self._inflight = None
        T = self._loop_ticks
        k = self._spec_k if self.spec else 0
        with annotate("serving/step/decode_harvest", ph):
            if self._watchdog is not None:
                self._watchdog.arm(
                    tag=f"ticks {self._ticks + 1}..{self._ticks + T}")
            window, counts, finished, dec_count, n_ticks, exit_code = \
                unpack_harvest(np.asarray(launch.harvest),
                               self.num_slots, T, k)
            metrics.inc("serving/d2h_reads")
            if self._watchdog is not None:
                self._watchdog.disarm()
        with annotate("serving/step/commit", ph):
            if why is None:
                metrics.inc("serving/harvest_deferred")
            else:
                metrics.inc("serving/harvest_flushed/" + why)
            self._ticks += n_ticks
            self._roundtrips += 1
            rec.ticks += n_ticks
            rec.live = len(launch.rows)
            metrics.inc("serving/device_ticks", n_ticks)
            if exit_code != LOOP_EXIT_NONE:
                metrics.inc(
                    "serving/loop_exit/finished"
                    if exit_code == LOOP_EXIT_FINISHED
                    else "serving/loop_exit/budget"
                    if exit_code == LOOP_EXIT_BUDGET
                    else ("serving/loop_exit/drain" if self._draining
                          else "serving/loop_exit/admission"))
            valid = [(slot, req) for slot, req in launch.rows
                     if self._slots[slot] is req]
            if len(valid) < len(launch.rows):
                metrics.inc("serving/harvest_rows_void",
                            len(launch.rows) - len(valid))
            # a launch is one opaque device program; the stamps of a
            # fused one's earlier ticks interpolate its wall time so
            # TTFT/TPOT stay comparable across device_loop_ticks. A
            # token's stamp is when the host received it: one step
            # after its tick where the read is deferred, for every
            # token alike
            now = time.time()
            per_tick_s = rec.tick_seconds() / n_ticks
            committed = 0
            for j in range(n_ticks):
                t_j = now - (n_ticks - 1 - j) * per_tick_s
                if self.paged:
                    self._count_decode_walk(launch.rows, k + 1)
                tick_committed = 0
                for slot, req in valid:
                    m = int(counts[slot, j])
                    if k:
                        at = len(req["tokens"])
                        req.setdefault("drafts", []).extend(
                            (at + i, int(window[slot, j, i]))
                            for i in range(1, k + 1))
                    req["tokens"].extend(
                        int(t) for t in window[slot, j, :m])
                    if "ttft" not in req:
                        req["ttft"] = t_j - req["submit_t"]
                        req["first_tok_t"] = t_j
                        self._metrics.observe("serving/ttft_ms",
                                              req["ttft"] * 1000.0)
                        req["span"].span_point(
                            "serving/first_token",
                            ttft_ms=round(req["ttft"] * 1000.0, 3))
                    if self.paged:
                        req["cur_len"] += m
                    tick_committed += m
                committed += tick_committed
                self._decode_tokens += tick_committed
                if self.spec:
                    drafted = k * len(valid)
                    # each slot's t0 is sampled, not drafted
                    accepted = tick_committed - len(valid)
                    self._spec_drafted += drafted
                    self._spec_accepted += accepted
                    metrics.inc("serving/spec_drafted", drafted)
                    metrics.inc("serving/spec_accepted", accepted)
                    # window columns written and not committed: what
                    # the next tick writes over
                    metrics.inc("serving/spec_rollback_columns",
                                (k + 1) * len(valid) - tick_committed)
                    if self._device_draft:
                        # what this tick committed the block folds in
                        # the next
                        metrics.inc("serving/mtp_positions/tick",
                                    tick_committed)
                    self._emit("serving_spec", drafted=drafted,
                               accepted=accepted,
                               committed=tick_committed)
            rec.tokens += committed
            metrics.inc("serving/decode_tokens", committed)
            if self.spec:
                metrics.get_registry().set_gauge(
                    "serving/spec_accept_rate",
                    self._spec_accepted / max(self._spec_drafted, 1))
            for slot, req in valid:
                req["ahead"] -= 1
                if self.paged:
                    # pages wholly past what the device holds go
                    # straight back to the pool: the pre-mapped tail
                    # of an early exit, and spec's rejected KV (the
                    # partial page's stale columns sit past cur_len
                    # and are overwritten before any masked read).
                    # The pages a launch still unread writes into
                    # stay (k+1 columns of a verify tick): at a page
                    # boundary they hold nothing committed yet
                    self._trim_pages(slot, req, -(
                        -(req["cur_len"] + (k + 1) * req["ahead"])
                        // self._page))
                if finished[slot]:
                    self._done.append(self._evict(slot, "eos"))
                elif dec_count[slot] >= self.gen_cfg.max_dec_len:
                    self._done.append(self._evict(slot, "length"))

    def _flush(self, why: str) -> None:
        """Read and commit the launch in flight NOW, outside
        :meth:`step`, as a short step of its own (a root, an account):
        what must see every request's newest token calls this first —
        :meth:`drain`, :meth:`preempt`, :meth:`close`. What finished
        comes out of the next ``step()`` (or of the caller, which
        knows where to look). Rare; the common path never flushes."""
        if self._inflight is None:
            return
        rec = StepRecord()
        mark = _cpu_mark()
        with annotate("serving/step", rec.phases):
            self._read_launch(self._inflight, rec, why)
        self._account_step(rec, mark)

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
        if self._tier is not None:
            self._tier.ship()
        return out

    def _drain_impl(self, max_ticks: Optional[int]
                    ) -> List[Completion]:
        if not self._draining:
            self._draining = True
            self._refresh_health()
            self._emit("serving_drain_start", signum=None,
                       pending=self.pending, occupancy=self.occupancy)
        # no committed token is lost and max_ticks counts ticks: what
        # is in flight is read before anything is handed back, and
        # every step below reads its own launch (_read_now)
        self._flush("drain")
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
        out.extend(self._take_done())
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
            if not self._closed:
                self._flush("close")   # leave nothing queued unread
            self._closed = True
        if self._watchdog is not None:
            self._watchdog.stop()
        if self._tier is not None:
            self._tier.close()
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
        with self._surface_lock:
            # a launch made before the last EOS was read: all void
            self._flush("idle")
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
            s["spec_method"] = self.gen_cfg.spec_method
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
            # cache bytes by class: the allocator's pages on the
            # layers that hold K/V, the rings, the state rows
            s["pool_bytes"] = pool_bytes(
                self._kv_layers, mcfg.num_kv_heads,
                mcfg.head_dim, self._page, self._alloc.num_pages,
                mcfg.kv_cache_dtype)
            if self._ring:
                s["window_ring_pages"] = self._ring
                s["window_pool_bytes"] = pool_bytes(
                    self._window_layers, mcfg.num_kv_heads,
                    mcfg.head_dim, self._page, mcfg.window_pool_pages,
                    mcfg.kv_cache_dtype)
                s["prefix_refused_window"] = \
                    self._prefix_refused == "window"
            if self._state_layers:
                s["state_bytes"] = self._state_layers \
                    * mcfg.state_rows * mcfg.state_row_bytes
                s["state_rows_held"] = self._state_layers * self.occupancy
                s["prefix_refused_recurrent"] = \
                    self._prefix_refused == "recurrent"
            if "moe_stats" in self._cache:
                # what the jitted ticks counted on the device, read
                # here, outside any tick: picks dispatched, distinct
                # experts touched and row tiles the grouped products
                # walked (a layer, summed over layers), of the decode
                # ticks and of the prefill chunks. An expert no row
                # picked has no tile, so decode_tiles over
                # experts_touched is 1.0 while a group fits a tile
                total = np.asarray(self._cache["moe_stats"], np.int64)
                grown = [int(n) for n in total - self._moe_published]
                self._moe_published = total
                metrics.inc("moe/decode_picks", grown[0])
                metrics.inc("moe/experts_touched", grown[1])
                metrics.inc("moe/decode_tiles", grown[2])
                metrics.inc("moe/prefill_picks", grown[3])
                metrics.inc("moe/prefill_touched", grown[4])
                metrics.inc("moe/prefill_tiles", grown[5])
                (s["moe_decode_picks"], s["moe_experts_touched"],
                 s["moe_decode_tiles"], s["moe_prefill_picks"],
                 s["moe_prefill_touched"], s["moe_prefill_tiles"]) = (
                     int(n) for n in total)
            if self._tier is not None:
                s.update(self._tier.summary())
            s.update(self._alloc.stats)
        if self._adapters is not None:
            s["adapter_rows"] = self._adapters.capacity
            s["adapters_resident"] = self._adapters.resident
            s.update(self._adapters.stats)
        self._emit("serving_summary", **s)
        return s
