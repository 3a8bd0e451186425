"""FleetRouter: the fleet must be invisible in the tokens.

The acceptance bar for multi-replica serving (docs/fleet_serving.md):
whatever the router does — affinity routing, spillover, prefill/decode
disaggregation with KV-page handoff, rolling restarts with failover —
every completion must equal its single-server lockstep row, greedy AND
sampled. The tests below pin that parity contract plus the fleet's own
bookkeeping: refcount/registry cleanliness on BOTH sides of a handoff,
one trace id per request across failover, and the aggregated
/metrics + /healthz endpoint.
"""

import json
import os
import re
import urllib.error
import urllib.request

os.environ.setdefault("PFX_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.core.fleet import FleetReplica, FleetRouter
from paddlefleetx_tpu.core.paging import page_prefix_keys
from paddlefleetx_tpu.core.serving import GenerationServer, RequestShed
from paddlefleetx_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddlefleetx_tpu.models.gpt.generation import (
    GenerationConfig, generate, left_pad_batch,
)
from paddlefleetx_tpu.observability import export
from paddlefleetx_tpu.observability import metrics
from paddlefleetx_tpu.observability import server as obs_server
from paddlefleetx_tpu.observability import timeline
from paddlefleetx_tpu.observability.recorder import read_events

CFG = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                num_attention_heads=4, max_position_embeddings=48,
                hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0)
# multi-page capacity for the disaggregation tests: prompts span >1
# 128-token page so a handoff actually moves a page list
PCFG512 = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_attention_heads=4,
                    max_position_embeddings=512,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
EOS = PAD = 95

PROMPTS = [[5, 9, 2, 7, 1], [11, 3], [4, 4, 8, 1, 2, 6, 9],
           [13, 2, 2], [1], [7, 8]]


@pytest.fixture(scope="module")
def model_and_params():
    model = GPTForPretraining(CFG)
    variables = model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32))
    return model, variables["params"]


@pytest.fixture(scope="module")
def paged512_model_and_params():
    model = GPTForPretraining(PCFG512)
    variables = model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32))
    return model, variables["params"]


def _greedy_cfg(max_dec=8):
    return GenerationConfig(max_dec_len=max_dec,
                            decode_strategy="greedy_search",
                            eos_token_id=EOS, pad_token_id=PAD)


def _sampling_cfg(max_dec=8):
    return GenerationConfig(max_dec_len=max_dec,
                            decode_strategy="sampling",
                            top_k=8, top_p=0.9, temperature=0.7,
                            eos_token_id=EOS, pad_token_id=PAD)


def _lockstep(model, params, prompts, gen_cfg):
    ids, mask = left_pad_batch(prompts, PAD)
    out = np.asarray(generate(model, params, jnp.asarray(ids),
                              jnp.asarray(mask), jax.random.key(0),
                              gen_cfg))
    rows = []
    for row in out:
        toks = []
        for t in row:
            toks.append(int(t))
            if int(t) == EOS:
                break
        rows.append(toks)
    return rows


def _mixed_factory(model, params, gen_cfg, **kw):
    """Identical-replica factory — the fleet's parity boundary."""
    def factory(name):
        return GenerationServer(model, params, gen_cfg, num_slots=2,
                                rng=jax.random.PRNGKey(7), **kw)
    return factory


def _drain_fleet(fleet, done):
    while fleet.busy:
        for c in fleet.step():
            done[c.request_id] = c
    return done


def _long_prompts(seed=3):
    """Multi-page prompts (2 pages each) for the handoff tests."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, EOS, n).tolist() for n in (200, 210, 220)]


# -- parity: the fleet is invisible in the tokens ----------------------


def test_fleet_parity_greedy(model_and_params):
    """A 2-replica mixed fleet serves PROMPTS token-identically to
    the single lockstep batch, whatever replica each request lands
    on."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS, gen_cfg)
    fleet = FleetRouter(_mixed_factory(model, params, gen_cfg), 2)
    comps = fleet.run(PROMPTS)
    assert [c.tokens for c in comps] == ref
    assert all(c.finish_reason in ("eos", "length") for c in comps)
    summ = fleet.summary()
    assert summ["submitted"] == 6 and summ["shed"] == 0
    # both replicas actually served
    assert all(r["decode_tokens"] > 0 for r in summ["per_replica"])
    fleet.close()


def test_fleet_parity_sampled(model_and_params):
    """Sampled parity: router-assigned nonces in global submission
    order make the fleet reproduce a single server's draws exactly —
    the replica a request lands on must not change its stream."""
    model, params = model_and_params
    gen_cfg = _sampling_cfg()
    single = GenerationServer(model, params, gen_cfg, num_slots=6,
                              rng=jax.random.PRNGKey(7))
    ref = [c.tokens for c in single.run(PROMPTS)]
    fleet = FleetRouter(_mixed_factory(model, params, gen_cfg), 2)
    comps = fleet.run(PROMPTS)
    assert [c.tokens for c in comps] == ref
    fleet.close()


@pytest.mark.parametrize("make_cfg", [_greedy_cfg, _sampling_cfg],
                         ids=["greedy", "sampled"])
def test_fleet_failover_parity(model_and_params, make_cfg):
    """Mid-run restart of a replica: its partials fail over to the
    peer via submit(resume_tokens=..., nonce=...) and the stitched
    streams stay token-exact — zero dropped committed tokens, zero
    shed, greedy and sampled alike."""
    model, params = model_and_params
    gen_cfg = make_cfg()
    if make_cfg is _greedy_cfg:
        ref = _lockstep(model, params, PROMPTS, gen_cfg)
    else:
        single = GenerationServer(model, params, gen_cfg,
                                  num_slots=6,
                                  rng=jax.random.PRNGKey(7))
        ref = [c.tokens for c in single.run(PROMPTS)]
    fleet = FleetRouter(_mixed_factory(model, params, gen_cfg), 2)
    ids = [fleet.submit(p) for p in PROMPTS]
    done = {}
    for _ in range(2):                      # some tokens commit first
        for c in fleet.step():
            done[c.request_id] = c
    for c in fleet.restart_replica(0):
        done[c.request_id] = c
    _drain_fleet(fleet, done)
    assert [done[i].tokens for i in ids] == ref
    summ = fleet.summary()
    assert summ["failovers"] >= 1 and summ["shed"] == 0
    assert summ["restarts"] == 1
    assert fleet.replicas[0].restarts == 1
    fleet.close()


# -- async router: overlapped worker ticks, identical tokens -----------


@pytest.mark.parametrize("tiered", [False, True],
                         ids=["plain", "tiered"])
@pytest.mark.parametrize("failover", [False, True],
                         ids=["steady", "failover"])
@pytest.mark.parametrize("make_cfg", [_greedy_cfg, _sampling_cfg],
                         ids=["greedy", "sampled"])
def test_fleet_async_parity_matrix(model_and_params,
                                   paged512_model_and_params,
                                   make_cfg, failover, tiered):
    """The async acceptance pin: an ``async_workers=True`` fleet —
    every replica ticking on its own worker thread, interleaving
    however the scheduler pleases — produces token-identical output
    to the lockstep fleet AND to a single server, greedy and sampled,
    with and without a mid-run rolling restart, with and without the
    tiered host pool underneath."""
    gen_cfg = make_cfg(max_dec=4)
    if tiered:
        model, params = paged512_model_and_params
        rng = np.random.default_rng(21)
        system = rng.integers(0, EOS, 130).tolist()
        prompts = [system + rng.integers(0, EOS, 7 + i).tolist()
                   for i in range(4)]
        kw = dict(page_size=128, pool_pages=5,
                  prefill_chunk_pages=1, prefix_sharing=True,
                  host_pool_bytes=1 << 20)
        single = GenerationServer(model, params, gen_cfg,
                                  num_slots=4,
                                  rng=jax.random.PRNGKey(7),
                                  page_size=128, pool_pages=64,
                                  prefill_chunk_pages=1,
                                  prefix_sharing=True)
    else:
        model, params = model_and_params
        prompts = PROMPTS
        kw = {}
        single = GenerationServer(model, params, gen_cfg,
                                  num_slots=6,
                                  rng=jax.random.PRNGKey(7))
    ref = [c.tokens for c in single.run(prompts)]
    single.close()
    factory = _mixed_factory(model, params, gen_cfg, **kw)

    def serve(async_workers):
        fleet = FleetRouter(factory, 2, async_workers=async_workers)
        ids = [fleet.submit(p) for p in prompts]
        done = {}
        if failover:
            for _ in range(2):
                for c in fleet.step():
                    done[c.request_id] = c
            for c in fleet.restart_replica(0):
                done[c.request_id] = c
        _drain_fleet(fleet, done)
        summ = fleet.summary()
        fleet.close()
        return [done[i].tokens for i in ids], summ

    lock_toks, _ = serve(async_workers=False)
    async_toks, summ = serve(async_workers=True)
    assert lock_toks == ref
    assert async_toks == ref
    assert summ["async_workers"] is True and summ["shed"] == 0
    if failover:
        assert summ["restarts"] == 1


def test_fleet_async_trace_span_ordering(model_and_params, tmp_path):
    """Trace reconstruction under interleaved worker ticks: the
    recorder's per-request story must stay causally ordered even
    though replica ticks come from N threads — for every request
    trace, the fleet/route span opens before any serving/request
    lifetime, every span's begin precedes its end, and the first
    serving/first_token point lands inside its request lifetime."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    events = tmp_path / "events.jsonl"

    def factory(name):
        return GenerationServer(model, params, gen_cfg, num_slots=2,
                                rng=jax.random.PRNGKey(7),
                                events_path=str(events))

    fleet = FleetRouter(factory, 2, events_path=str(events),
                        async_workers=True)
    ids = [fleet.submit(p) for p in PROMPTS]
    done = _drain_fleet(fleet, {})
    fleet.close()
    assert set(done) == set(ids)
    evs = read_events(str(events))
    traces = {done[i].trace_id for i in ids}
    assert len(traces) == len(ids)
    for tid in traces:
        tevs = [(n, e) for n, e in enumerate(evs)
                if e.get("trace") == tid]
        routes = [n for n, e in tevs if e["event"] == "span_begin"
                  and e["name"] == "fleet/route"]
        req_begins = [n for n, e in tevs
                      if e["event"] == "span_begin"
                      and e["name"] == "serving/request"]
        req_ends = [n for n, e in tevs if e["event"] == "span_end"
                    and e["name"] == "serving/request"]
        firsts = [n for n, e in tevs if e["event"] == "span_point"
                  and e["name"] == "serving/first_token"]
        assert len(routes) == 1
        assert len(req_begins) == len(req_ends) == 1
        assert routes[0] < req_begins[0] < req_ends[0]
        assert firsts and req_begins[0] < firsts[0] < req_ends[0]


# -- prefill/decode disaggregation -------------------------------------


def test_kv_import_leaves_one_slot_its_growth(
        paged512_model_and_params):
    """Import pins sit outside every slot, where preemption cannot
    reclaim them: on a pool of two slots' worst case a third import
    would leave an admitted request fewer pages than one slot's
    maximum to grow into (PagePoolExhausted under the async router,
    whose prefill worker runs ahead). The import is refused instead
    — the caller re-prefills — and every request is still served to
    its lockstep row."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg()
    prompts = _long_prompts()
    ref = _lockstep(model, params, prompts, gen_cfg)
    kw = dict(page_size=128, prefill_chunk_pages=1,
              rng=jax.random.PRNGKey(7))
    src = GenerationServer(model, params, gen_cfg, num_slots=3,
                           pool_pages=17, **kw)
    # 8 usable pages, 4 per slot at most: two 2-page imports leave
    # exactly one slot's maximum; a third would eat into it
    dst = GenerationServer(model, params, gen_cfg, num_slots=2,
                           pool_pages=9, **kw)
    for p in prompts:
        src.submit(p)
    took = []
    for p in prompts:                 # export as each prefill lands
        for _ in range(50):
            if src.prompt_ready(p):
                break
            src.step()
        pages, last = src.kv_export(p)
        took.append(dst.kv_import(p, src.kv_page_data(pages), last,
                                  len(pages)))
        src.kv_export_release(pages)
    assert took == [True, True, False]
    comps = dst.run(prompts)          # two imported, one re-prefilled
    assert [c.tokens for c in comps] == ref
    for p in prompts[:2]:
        dst.kv_import_release(p)
    dst._alloc.check()
    for srv in (src, dst):
        srv.close()


@pytest.mark.parametrize("handoff", ["device", "host"])
def test_fleet_split_handoff_parity(paged512_model_and_params,
                                    handoff):
    """1 prefill + 1 decode replica: every prompt prefills on one
    server, its KV pages move across pools (device-direct and
    host-staged), and decode on the peer still produces the lockstep
    rows. Both allocators end clean — nothing leaked on either side
    of any handoff."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg()
    prompts = _long_prompts()
    ref = _lockstep(model, params, prompts, gen_cfg)
    factory = _mixed_factory(model, params, gen_cfg, page_size=128,
                             pool_pages=17, prefill_chunk_pages=1)
    fleet = FleetRouter(factory, 2, prefill_replicas=1,
                        handoff=handoff)
    comps = fleet.run(prompts)
    assert [c.tokens for c in comps] == ref
    summ = fleet.summary()
    assert summ["handoffs"] == 3 and summ["shed"] == 0
    assert summ["handoff_pages"] >= summ["handoffs"] * 2  # 2pp each
    # decode landed on the decode replica, prefill never decoded
    roles = {r["role"]: r for r in summ["per_replica"]}
    assert roles["decode"]["decode_tokens"] > 0
    assert roles["prefill"]["decode_tokens"] == 0
    for rep in fleet.replicas:
        rep.server._alloc.check()
        assert rep.server._alloc.pages_in_use == 0
    fleet.close()


def test_fleet_async_d2d_handoff_smoke(paged512_model_and_params,
                                       tmp_path, monkeypatch):
    """CI smoke (`-k smoke`), async d2d edition: a 1 prefill + 1
    decode ASYNC fleet moves every KV handoff device-to-device with
    ZERO host copies — `jax.device_get` never runs for a handoff (the
    handoff-writer thread stays idle and is counted), the d2d/host
    counters split 3/0, the handoff histogram fills, no
    `serving_spill`-style host staging appears on the trace, and the
    tokens still equal the lockstep rows. events.jsonl lands under
    tmp_path for CI's failure-diagnostics artifact."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg()
    prompts = _long_prompts()
    ref = _lockstep(model, params, prompts, gen_cfg)
    events = tmp_path / "events.jsonl"
    host_copies = []
    real = jax.device_get

    def counting_get(x):
        import threading as _t
        name = _t.current_thread().name
        if name.startswith("fleet-"):
            host_copies.append(name)
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting_get)

    def factory(name):
        return GenerationServer(model, params, gen_cfg, num_slots=2,
                                rng=jax.random.PRNGKey(7),
                                page_size=128, pool_pages=17,
                                prefill_chunk_pages=1,
                                events_path=str(events))

    fleet = FleetRouter(factory, 2, prefill_replicas=1,
                        handoff="device", async_workers=True,
                        events_path=str(events))
    comps = fleet.run(prompts)
    summ = fleet.summary()
    fleet.close()
    assert [c.tokens for c in comps] == ref
    assert summ["handoffs"] == 3
    assert summ["handoff_d2d"] == 3      # every handoff stayed d2d
    assert summ["handoff_host"] == 0
    assert summ["handoff_p99_ms"] >= summ["handoff_p50_ms"] > 0
    assert not host_copies               # zero host copies, any thread
    for rep in fleet.replicas:
        rep.server.check_alloc()        # the surface-locked spelling
        assert rep.server._alloc.pages_in_use == 0
    evs = read_events(str(events))
    kinds = {e["event"] for e in evs}
    assert "fleet_handoff" in kinds
    # no host staging anywhere near the handoff trace: neither the
    # fleet's staging stage nor a serving-side spill ever fired
    assert "fleet_handoff_staged" not in kinds
    assert "serving_spill" not in kinds
    for e in evs:
        if e["event"] == "fleet_handoff":
            assert e["mode"] == "device"


def test_fleet_async_overlap_ratio_beats_lockstep(model_and_params,
                                                  tmp_path):
    """The overlap A/B pin (docs/observability.md, "Thread
    timeline"): serving the SAME trace, the lockstep router scores
    exactly 1/N on ``overlap_ratio`` (one lane mid-tick at a time by
    construction) and the async router must score STRICTLY more —
    worker threads whose tick intervals never overlap would mean the
    async fleet is lockstep with extra steps. Also pins the
    ``summary()`` plumbing the fleet bench records ride
    (``overlap_ratio`` + per-thread ``thread_util``) and dumps the
    async run's merged Perfetto timeline as timeline_fleet_async.json
    for CI's failure-diagnostics artifact."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    factory = _mixed_factory(model, params, gen_cfg)
    was = timeline.enabled()
    timeline.set_enabled(True)
    try:
        def serve(async_workers):
            fleet = FleetRouter(factory, 2,
                                async_workers=async_workers)
            ids = [fleet.submit(p) for p in PROMPTS]
            done = _drain_fleet(fleet, {})
            assert set(done) == set(ids)
            summ = fleet.summary()
            snap = timeline.get_timeline().snapshot(since=fleet._t0)
            fleet.close()
            return summ, snap

        lock_summ, _ = serve(async_workers=False)
        async_summ, async_snap = serve(async_workers=True)
    finally:
        timeline.set_enabled(was)

    # lockstep floor: depth never exceeds 1 => exactly 1/N
    assert lock_summ["overlap_ratio"] == pytest.approx(1 / 2)
    # the tentpole claim, falsifiable: async genuinely overlaps
    assert async_summ["overlap_ratio"] > lock_summ["overlap_ratio"]
    assert async_summ["overlap_ratio"] <= 1.0
    # per-thread utilization rides the same summary
    util = async_summ["thread_util"]
    assert {"fleet-worker-0", "fleet-worker-1"} <= set(util)
    assert all(0.0 <= u <= 1.0 for u in util.values())

    # one Perfetto thread row per instrumented thread, artifact-ready
    trace = export.chrome_trace([], timeline=async_snap)
    rows = {e["args"]["name"] for e in trace["traceEvents"]
            if e.get("name") == "thread_name"}
    assert {"fleet-router", "fleet-worker-0",
            "fleet-worker-1"} <= rows
    out = tmp_path / "timeline_fleet_async.json"
    out.write_text(json.dumps(trace))
    assert json.loads(out.read_text())["displayTimeUnit"] == "ms"


def test_fleet_async_handoff_reconstructs_from_timeline(
        paged512_model_and_params, tmp_path):
    """Handoff reconstruction from the thread timeline ALONE — the
    event stream only mints the trace ids: each host-staged handoff
    shows up as a trace-tagged ``handoff_host`` interval on the
    writer track, preceded by prefill-lane tick work and followed by
    decode-lane tick work, with the router's harvest waits accounted
    on its own track."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg()
    prompts = _long_prompts()
    events = tmp_path / "events.jsonl"
    factory = _mixed_factory(model, params, gen_cfg, page_size=128,
                             pool_pages=17, prefill_chunk_pages=1)
    was = timeline.enabled()
    timeline.set_enabled(True)
    try:
        fleet = FleetRouter(factory, 2, prefill_replicas=1,
                            handoff="host", async_workers=True,
                            events_path=str(events))
        comps = fleet.run(prompts)
        summ = fleet.summary()
        snap = timeline.get_timeline().snapshot(since=fleet._t0)
        fleet.close()
    finally:
        timeline.set_enabled(was)

    assert summ["handoffs"] == 3 and summ["handoff_host"] == 3
    traces = {c.trace_id for c in comps}
    handoffs = [iv for iv in snap["fleet-handoff-writer"]
                if iv[0] == "handoff_host"]
    # one staged interval per handoff, each tagged with the trace id
    # of a real completion — and all three requests distinct
    assert len(handoffs) == 3
    assert {iv[3] for iv in handoffs} <= traces
    assert len({iv[3] for iv in handoffs}) == 3
    roles = [r["role"] for r in summ["per_replica"]]
    pticks = [iv for iv in snap[f"fleet-worker-{roles.index('prefill')}"]
              if iv[0] == "tick"]
    dticks = [iv for iv in snap[f"fleet-worker-{roles.index('decode')}"]
              if iv[0] == "tick"]
    for _, h0, h1, tr in handoffs:
        assert h1 >= h0 and tr is not None
        # the prefill lane was ticking before the staging began, and
        # the decode lane ticked on past its completion — the
        # prefill -> stage -> decode story reads off the intervals
        assert any(t0 < h0 for _, t0, _, _ in pticks)
        assert any(t1 > h1 for _, _, t1, _ in dticks)
    # the writer's idle waits and the router's harvest waits are
    # attributed, not invisible
    assert any(iv[0] == "idle" for iv in snap["fleet-handoff-writer"])
    assert any(iv[0] == "harvest_wait"
               for iv in snap["fleet-router"])


def test_fleet_split_handoff_int8_scales(paged512_model_and_params):
    """The handoff tree carries the int8 pools' scale leaves: a
    disaggregated fleet over kv_cache_dtype="int8" replicas stays
    token-exact with the bf16 lockstep reference (per-token abs-max
    quantization is argmax-invisible, and a round-trip through
    gather -> host staging -> scatter must keep it so)."""
    model, params = paged512_model_and_params
    icfg = GPTConfig(**{**PCFG512.__dict__, "kv_cache_dtype": "int8"})
    imodel = GPTForPretraining(icfg)
    gen_cfg = _greedy_cfg()
    prompts = _long_prompts(seed=4)
    ref = _lockstep(model, params, prompts, gen_cfg)
    factory = _mixed_factory(imodel, params, gen_cfg, page_size=128,
                             pool_pages=17, prefill_chunk_pages=1)
    fleet = FleetRouter(factory, 2, prefill_replicas=1,
                        handoff="host")
    comps = fleet.run(prompts)
    assert [c.tokens for c in comps] == ref
    assert fleet.summary()["handoffs"] == 3
    for rep in fleet.replicas:
        rep.server._alloc.check()
        assert rep.server._alloc.pages_in_use == 0
    fleet.close()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_kv_page_gather_scatter_roundtrip_across_pools(kv_dtype):
    """The handoff's device ops, pinned at the array level: pages
    gathered from one pool land byte-identical in ANOTHER pool under
    remapped page ids (including a host-staging hop), int8 pools move
    their fp32 scale pages in the same tree, and non-pool leaves plus
    untouched destination pages are left alone."""
    from paddlefleetx_tpu.models.gpt.generation import (
        gather_kv_pages, scatter_kv_pages,
    )
    rng = np.random.default_rng(0)
    names = ["cached_key", "cached_value"]
    if kv_dtype == "int8":
        names += ["cached_key_scale", "cached_value_scale"]

    def pool(n_pages, fill):
        layer = {}
        for name in names:
            if name.endswith("_scale"):
                shape, dt = (n_pages, 2, 1, 128), np.float32
            else:
                shape, dt = (n_pages, 2, 128, 4), (
                    np.int8 if kv_dtype == "int8" else np.float32)
            arr = rng.normal(0, 20, shape) if fill else np.zeros(shape)
            layer[name] = jnp.asarray(arr.astype(dt))
        layer["cache_index"] = jnp.asarray([7], jnp.int32)
        return {"layer_0": layer}

    src, dst = pool(6, fill=True), pool(8, fill=False)
    src_pids, dst_pids = [2, 5], [7, 1]         # the remap
    data = gather_kv_pages(src, jnp.asarray(src_pids, jnp.int32))
    staged = jax.device_get(data)               # host-staging hop
    out = scatter_kv_pages(dst, staged,
                           jnp.asarray(dst_pids, jnp.int32))
    for name in names:
        got = np.asarray(out["layer_0"][name])
        want = np.asarray(src["layer_0"][name])
        for d, s in zip(dst_pids, src_pids):
            np.testing.assert_array_equal(got[d], want[s])
        untouched = [p for p in range(8) if p not in dst_pids]
        assert not np.asarray(got[untouched]).any()
    np.testing.assert_array_equal(
        np.asarray(out["layer_0"]["cache_index"]), [7])


# -- routing: affinity, spillover, shed --------------------------------


def test_fleet_affinity_routes_to_prefix_holder(
        paged512_model_and_params):
    """A request sharing a live system prefix routes to the replica
    already holding those pages, even when the peer is emptier —
    registry affinity beats least-depth."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg()
    rng = np.random.default_rng(11)
    system = rng.integers(0, EOS, 130).tolist()     # 1 full page
    p1 = system + rng.integers(0, EOS, 40).tolist()
    p2 = system + rng.integers(0, EOS, 10).tolist()
    ref = _lockstep(model, params, [p1, p2], gen_cfg)
    factory = _mixed_factory(model, params, gen_cfg, page_size=128,
                             pool_pages=17, prefill_chunk_pages=1)
    fleet = FleetRouter(factory, 2)
    g1 = fleet.submit(p1)
    home = fleet._reqs[g1]["replica"]
    done = {}
    sys_key = page_prefix_keys(p1, 128)[0]
    for _ in range(6):          # prefill publishes the system page
        for c in fleet.step():
            done[c.request_id] = c
        alloc = fleet.replicas[home].server._alloc
        if alloc.lookup_prefix(sys_key) is not None:
            break
    assert fleet.replicas[home].server._alloc.lookup_prefix(
        sys_key) is not None
    g2 = fleet.submit(p2)
    assert fleet._reqs[g2]["replica"] == home   # affinity won
    _drain_fleet(fleet, done)
    assert [done[g1].tokens, done[g2].tokens] == ref
    summ = fleet.summary()
    assert summ["routed_affinity"] >= 1
    assert summ["shed"] == 0
    fleet.close()


def test_fleet_spillover_preserves_sampled_parity(model_and_params):
    """An admission refusal spills to the next-ranked replica and the
    nonce is consumed only on the successful admit — the sampled
    stream is unchanged by where (or on which attempt) a request
    lands."""
    from paddlefleetx_tpu.core.resilience import FaultInjector
    model, params = model_and_params
    gen_cfg = _sampling_cfg()
    single = GenerationServer(model, params, gen_cfg, num_slots=6,
                              rng=jax.random.PRNGKey(7))
    ref = [c.tokens for c in single.run(PROMPTS)]

    def factory(name):
        # replica0's first submit fails -> the router must spill that
        # request over to replica1 without burning its nonce
        faults = FaultInjector("admit_fail@req=1", kill_mode="raise") \
            if name == "replica0" else None
        return GenerationServer(model, params, gen_cfg, num_slots=2,
                                rng=jax.random.PRNGKey(7),
                                fault_injector=faults)

    fleet = FleetRouter(factory, 2)
    comps = fleet.run(PROMPTS)
    assert [c.tokens for c in comps] == ref
    summ = fleet.summary()
    assert summ["spillover"] >= 1 and summ["shed"] == 0
    fleet.close()


def test_fleet_sheds_only_when_all_refuse(model_and_params):
    """RequestShed surfaces only after EVERY replica refused; a shed
    must not burn a sampling nonce (the next admitted request draws
    exactly what it would have without the shed)."""
    from paddlefleetx_tpu.core.resilience import FaultInjector
    model, params = model_and_params
    gen_cfg = _sampling_cfg()
    single = GenerationServer(model, params, gen_cfg, num_slots=6,
                              rng=jax.random.PRNGKey(7))
    ref = [c.tokens for c in single.run(PROMPTS[1:])]

    def factory(name):
        return GenerationServer(
            model, params, gen_cfg, num_slots=2,
            rng=jax.random.PRNGKey(7),
            fault_injector=FaultInjector("admit_fail@req=1",
                                         kill_mode="raise"))

    fleet = FleetRouter(factory, 1)
    with pytest.raises(RequestShed, match="every eligible replica"):
        fleet.submit(PROMPTS[0])
    comps = fleet.run(PROMPTS[1:])
    assert [c.tokens for c in comps] == ref     # nonce 0 not burned
    summ = fleet.summary()
    assert summ["shed"] == 1 and summ["submitted"] == 6
    fleet.close()


# -- observability: one trace per request, live fleet endpoint ---------


def test_fleet_failover_trace_continuity(model_and_params, tmp_path):
    """events.jsonl alone reconstructs a failover: each failed-over
    request reads as ONE trace id with a fleet/route root, TWO
    serving/request lifetimes (original + resumed) and a
    fleet/failover span between them."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    events = tmp_path / "events.jsonl"

    def factory(name):
        return GenerationServer(model, params, gen_cfg, num_slots=2,
                                rng=jax.random.PRNGKey(7),
                                events_path=str(events))

    fleet = FleetRouter(factory, 2, events_path=str(events))
    ids = [fleet.submit(p) for p in PROMPTS]
    done = {}
    for _ in range(2):
        for c in fleet.step():
            done[c.request_id] = c
    for c in fleet.restart_replica(0):
        done[c.request_id] = c
    _drain_fleet(fleet, done)
    assert fleet.summary()["failovers"] >= 1
    fleet.close()

    # every request: one distinct trace, rooted in fleet/route
    assert len({done[i].trace_id for i in ids}) == len(ids)
    evs = read_events(str(events))
    failed_over = [e for e in evs if e["event"] == "fleet_failover"]
    assert failed_over
    for ev in failed_over:
        tid = ev["trace"]
        routes = [e for e in evs if e["event"] == "span_begin"
                  and e["name"] == "fleet/route"
                  and e["trace"] == tid]
        lives = [e for e in evs if e["event"] == "span_begin"
                 and e["name"] == "serving/request"
                 and e["trace"] == tid]
        fails = [e for e in evs if e["event"] == "span_begin"
                 and e["name"] == "fleet/failover"
                 and e["trace"] == tid]
        assert len(routes) == 1
        assert len(lives) == 2      # original + resumed lifetime
        assert len(fails) == 1
        assert lives[0]["span"] != lives[1]["span"]


#: one Prometheus 0.0.4 sample line (# TYPE comments aside)
_PROM_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_][a-zA-Z0-9_]*(\{le="[^"]+"\})? [-+0-9.einfE]+$')


def test_fleet_metrics_endpoint_smoke(paged512_model_and_params,
                                      tmp_path, monkeypatch):
    """CI smoke (`-k smoke`), fleet edition: two paged interpret-mode
    replicas behind the router with PFX_METRICS_PORT=0, a shared
    system prefix in the trace, one drain->failover rolling restart
    mid-run; /metrics scrapes as Prometheus text with the fleet
    gauges/histogram present and /healthz aggregates per-replica
    state (ok while ANY replica serves). Scraped bodies land as
    metrics_scrape_fleet_* files for CI's failure-diagnostics
    artifact."""
    model, params = paged512_model_and_params
    monkeypatch.setenv("PFX_METRICS_PORT", "0")
    obs_server.stop()              # a fresh singleton for this test
    events = tmp_path / "events.jsonl"
    gen_cfg = _greedy_cfg(max_dec=6)
    rng = np.random.default_rng(9)
    system = rng.integers(0, EOS, 130).tolist()
    prompts = [system + rng.integers(0, EOS, n).tolist()
               for n in (40, 20, 10, 30)]
    ref = _lockstep(model, params, prompts, gen_cfg)

    def get(url_path):
        try:
            with urllib.request.urlopen(msrv.url(url_path),
                                        timeout=10) as resp:
                return resp.status, resp.read().decode("utf-8")
        except urllib.error.HTTPError as err:
            return err.code, err.read().decode("utf-8")

    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    try:
        factory = _mixed_factory(model, params, gen_cfg,
                                 page_size=128, pool_pages=17,
                                 prefill_chunk_pages=1,
                                 events_path=str(events))
        fleet = FleetRouter(factory, 2, events_path=str(events))
        msrv = obs_server.get_server()
        assert msrv is not None and msrv.port > 0
        ids = [fleet.submit(p) for p in prompts[:2]]
        done = {}
        # step until some replica published the shared system page,
        # then submit the followers — they route by prefix affinity
        sys_key = page_prefix_keys(prompts[0], 128)[0]
        for _ in range(6):            # prefill + first decode ticks
            for c in fleet.step():
                done[c.request_id] = c
            if any(r.server._alloc.lookup_prefix(sys_key) is not None
                   for r in fleet.replicas):
                break
        ids += [fleet.submit(p) for p in prompts[2:]]

        # mid-run: exposition parses, fleet gauges are live
        code, mbody = get("/metrics")
        assert code == 200
        for line in mbody.splitlines():
            assert line.startswith("# TYPE ") or \
                _PROM_SAMPLE_RE.match(line), \
                f"bad exposition line: {line!r}"
        assert "pfx_fleet_replicas_ok 2" in mbody
        assert "pfx_fleet_submitted" in mbody
        code, hbody = get("/healthz")
        assert code == 200
        health = json.loads(hbody)
        assert health["status"] == "ok"
        assert health["replicas_ok"] == 2
        assert [r["name"] for r in health["replicas"]] == \
            ["replica0", "replica1"]
        (tmp_path / "metrics_scrape_fleet_metrics.txt"
         ).write_text(mbody)
        (tmp_path / "metrics_scrape_fleet_healthz.json"
         ).write_text(hbody)

        # one rolling restart mid-run: drain -> failover -> fresh
        # server, and the fleet endpoint survives the swap
        for c in fleet.restart_replica(0):
            done[c.request_id] = c
        code, hbody = get("/healthz")
        assert code == 200            # the peer kept serving
        assert json.loads(hbody)["replicas"][0]["restarts"] == 1
        _drain_fleet(fleet, done)
        assert [done[i].tokens for i in ids] == ref

        # finished fleet: TTFT histogram exported, healthz flips 503
        # only once EVERY replica drains
        code, mbody = get("/metrics")
        assert code == 200
        assert "pfx_fleet_ttft_ms_bucket" in mbody
        assert 'le="+Inf"' in mbody
        summ = fleet.summary()
        assert summ["failovers"] >= 1 and summ["shed"] == 0
        assert summ["routed_affinity"] >= 1     # shared system prefix
        assert summ["ttft_p99_ms"] >= summ["ttft_p50_ms"] > 0
        fleet.replicas[0].server.drain()
        code, _ = get("/healthz")
        assert code == 200
        fleet.replicas[1].server.drain()
        code, hbody = get("/healthz")
        assert code == 503
        assert json.loads(hbody)["status"] == "draining"
        (tmp_path / "metrics_scrape_fleet_healthz_draining.json"
         ).write_text(hbody)
        evs = read_events(str(events))
        kinds = {e["event"] for e in evs}
        assert {"fleet_start", "fleet_route", "fleet_restart_begin",
                "fleet_restart_end", "fleet_failover",
                "serving_start"} <= kinds
        fleet.close()
    finally:
        obs_server.stop()
        metrics.set_enabled(False)
        reg.reset()
    assert obs_server.get_server() is None


# -- construction contracts --------------------------------------------


def test_fleet_constructor_validation(model_and_params):
    model, params = model_and_params
    factory = _mixed_factory(model, params, _greedy_cfg())
    with pytest.raises(ValueError, match="num_replicas"):
        FleetRouter(factory, 0)
    with pytest.raises(ValueError, match="prefill_replicas"):
        FleetRouter(factory, 2, prefill_replicas=2)
    with pytest.raises(ValueError, match="handoff"):
        FleetRouter(factory, 2, handoff="rdma")
    fleet = FleetRouter(factory, 2, prefill_replicas=1)
    assert [r.role for r in fleet.replicas] == ["prefill", "decode"]
    assert isinstance(fleet.replicas[0], FleetReplica)
    fleet.close()


# -- tiered replicas: warm rolling restarts ----------------------------


def test_rolling_restart_warm_prefix_store(paged512_model_and_params,
                                           tmp_path):
    """A rolling restart of tiered replicas hands each one's hot
    prefix store to its replacement through the checkpoint-manifest
    round trip (docs/fleet_serving.md, "Warm starts"): the second
    wave of conversations — resubmitted after EVERY replica was
    swapped — is served token-identically to an untiered unlimited
    fleet, with the restarted replicas rehydrating instead of
    re-prefilling."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=6)
    rng = np.random.default_rng(11)
    system = rng.integers(0, EOS, 130).tolist()
    prompts = [system + rng.integers(0, EOS, 7 + i).tolist()
               for i in range(3)]

    def run_fleet(factory, store_dir):
        fleet = FleetRouter(factory, 2, prefix_store_dir=store_dir)
        done = {}
        for p in prompts:
            done[fleet.submit(p)] = None
        _drain_fleet(fleet, done)
        fleet.rolling_restart()
        for p in prompts:
            done[fleet.submit(p)] = None
        _drain_fleet(fleet, done)
        reps = [(r.restarts, r.server.summary())
                for r in fleet.replicas]
        toks = [done[i].tokens for i in sorted(done)]
        fleet.close()
        return toks, reps

    tiered_kw = dict(page_size=128, pool_pages=5,
                     prefill_chunk_pages=1, prefix_sharing=True,
                     host_pool_bytes=1 << 20)
    t_toks, t_reps = run_fleet(
        _mixed_factory(model, params, gen_cfg, **tiered_kw),
        str(tmp_path))
    u_toks, _ = run_fleet(
        _mixed_factory(model, params, gen_cfg, page_size=128,
                       pool_pages=64, prefill_chunk_pages=1,
                       prefix_sharing=True), None)
    assert t_toks == u_toks
    # every replica was swapped, the store round-tripped through disk
    # (committed-last manifest), and the fresh servers served wave 2
    # from rehydration
    assert all(restarts == 1 for restarts, _ in t_reps)
    assert all((tmp_path / f"replica{i}_prefix_store" /
                "pfx_manifest.json").exists() for i in range(2))
    assert sum(s["rehydrates"] for _, s in t_reps) > 0
    assert all(s["prefill_chunks"] == 0 for _, s in t_reps
               if s["rehydrates"] > 0)
