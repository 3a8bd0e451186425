"""The reader of the server's ``serving/step_account`` points
(``readers/step_account.py``) on a hand-made host line with steps of
both kinds, and, through ``run.py --rehearse --trace 1``, that the
metrics built on it and on the ``prefill_dispatch`` phase are printed
for a serving cell. Run by hand:

    python -m pytest chipbench/tests/test_step_account.py -q
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _run import ROOT, bench, run_cell  # noqa: E402

sys.path.insert(0, ROOT)
from chipbench import trace_reduce  # noqa: E402
from chipbench.readers import host_phase, step_account  # noqa: E402

MS = 1e6
NEW = {"chunk_step_share_pct", "live_rows_per_tick",
       "step_ms_decode_only", "step_ms_with_chunk", "prefill_dispatch_ms"}
DECODING = {"ticks": [1, None]}


def spec(name):
    with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def account(t, **counts):
    fields = dict(ticks=1, chunks=0, live=4)
    fields.update(counts)
    return ("serving/step_account " + " ".join(
        f"{k}={v}" for k, v in fields.items()), t, 0.001 * MS)


def hand_made(plain=6, chunked=5, points=True):
    """A host line of ``plain`` decoding steps of 10 ms at 4 live rows
    and ``chunked`` of 30 ms at 8 that also launched a chunk (2 ms of
    ``prefill_dispatch`` in each), alternating, 1 ms apart; one step
    that only pumped a chunk; one decoding step cut by each marker.
    Every root is followed by its account 0.01 ms later."""
    host, modules, t = [], [], 5 * MS

    def step(dur_ms, **counts):
        nonlocal t
        host.append(("serving/step", t, dur_ms * MS))
        if counts.get("chunks"):
            host.append(("serving/step/prefill_dispatch", t + 1 * MS,
                         2 * MS))
            modules.append(("jit_prefill_chunk_paged(11)", t + 2 * MS,
                            15 * MS))
        if counts.get("ticks", 1):
            modules.append(("jit_decode_step(7)", t + dur_ms * MS / 2,
                            4 * MS))
        t += dur_ms * MS
        if points:
            host.append(account(t + 0.01 * MS, **counts))
        t += 1 * MS
    step(10)                                # cut by the begin marker
    lo = t - 3 * MS
    for i in range(max(plain, chunked)):
        if i < plain:
            step(10)
        if i < chunked:
            step(30, chunks=1, live=8)
    step(20, ticks=0, chunks=1, live=0)
    hi = t + 4 * MS
    step(10)                                # cut by the end marker
    host += [(trace_reduce.BEGIN_MARK, lo - 1.0, 1.0),
             (trace_reduce.END_MARK, hi, 1.0)]
    modules = [m for m in modules if lo <= m[1] and m[1] + m[2] <= hi]
    return {"trace": {"host": host,
                      "devices": [{"busy": [], "modules": modules}]}}


def test_steps_of_both_kinds_are_told_apart():
    run = hand_made()
    share, note = step_account.read(spec("chunk_step_share_pct")["params"],
                                    run)
    assert share == pytest.approx(100.0 * 5 / 11)
    # the two cut steps are out, the chunk-only step is no decoding step
    assert note["steps_read"] == 11 and note["steps_in_span"] == 12
    assert note["decoding_steps"] == 11 and note["with_a_chunk"] == 5
    assert note["in_span"] == {"ticks": 11, "chunks": 6}
    assert "device_launches" not in note
    rows, note = step_account.read(spec("live_rows_per_tick")["params"],
                                   run)
    assert rows == pytest.approx((6 * 4 + 5 * 8) / 11)
    # the host's account is the device's module line in the same span:
    # this metric's note carries the check for all
    assert note["device_launches"]["jit_decode_step"][0] == 11
    assert note["device_launches"]["jit_prefill_chunk_paged"] == \
        [6, pytest.approx(15.0)]
    # a trace with no device plane has no launches to set beside it
    del run["trace"]["devices"]
    same, note = step_account.read(spec("live_rows_per_tick")["params"],
                                   run)
    assert same == rows and note["device_launches"] == {}
    only, note = step_account.read(spec("step_ms_decode_only")["params"],
                                   run)
    assert only == pytest.approx(10.0) and note["steps_read"] == 6
    with_chunk, note = step_account.read(
        spec("step_ms_with_chunk")["params"], run)
    assert with_chunk == pytest.approx(30.0) and note["steps_read"] == 5
    # the dispatch's own phase, on the reader that was there
    got, note = host_phase.read(spec("prefill_dispatch_ms")["params"], run)
    assert got == pytest.approx(2.0) and "6 x" in note


def test_a_fused_launch_weighs_its_rows_by_its_ticks():
    run = hand_made(plain=5, chunked=0)
    host = run["trace"]["host"]
    at = next(i for i, ev in enumerate(host)
              if ev[0].startswith("serving/step_account")
              and ev[1] > 20 * MS)
    host[at] = account(host[at][1], ticks=4, live=16)
    rows, _ = step_account.read(
        {"steps": DECODING, "value": "per_tick", "field": "live"}, run)
    assert rows == pytest.approx((4 * 4 + 4 * 16) / (4 + 4))


def test_a_trace_without_points_reads_nothing():
    """The parent's program: the roots and phases are there, the
    points are not."""
    run = hand_made(points=False)
    for name in NEW - {"prefill_dispatch_ms"}:
        assert step_account.read(spec(name)["params"], run) is None
    # and a parent of THIS change has no dispatch phase either
    run["trace"]["host"] = [ev for ev in run["trace"]["host"]
                            if ev[0] != "serving/step/prefill_dispatch"]
    assert host_phase.read(spec("prefill_dispatch_ms")["params"],
                           run) is None


def test_fewer_than_five_steps_of_a_kind_read_nothing():
    run = hand_made(plain=6, chunked=4)
    assert step_account.read(spec("step_ms_with_chunk")["params"],
                             run) is None
    only, _ = step_account.read(spec("step_ms_decode_only")["params"], run)
    assert only == pytest.approx(10.0)
    share, _ = step_account.read(spec("chunk_step_share_pct")["params"],
                                 run)
    assert share == pytest.approx(40.0)
    run = hand_made(plain=4, chunked=0)
    assert step_account.read(spec("live_rows_per_tick")["params"],
                             run) is None


def test_a_point_without_its_root_is_left_out():
    """The session began inside a step: its point has no root on the
    line, and the next root is not charged with it."""
    run = hand_made()
    host = run["trace"]["host"]
    first = min(s for n, s, _ in host if n == "serving/step")
    host.append(account(first - 1 * MS, ticks=9, live=60))
    rows, note = step_account.read(spec("live_rows_per_tick")["params"],
                                   run)
    assert rows == pytest.approx((6 * 4 + 5 * 8) / 11)
    assert note["steps_in_span"] == 12


CELLS = {w["name"]: {m["name"] for m in bench()["per_layer"]
                     if w["name"] in m.get("workloads", ())} & NEW
         for w in bench()["workloads"]}


def test_every_new_metric_is_listed_by_some_cell():
    assert set().union(*CELLS.values()) == NEW
    serving = {w["name"] for w in bench()["workloads"]
               if "serve" in w["traffic"] or "open-loop" in w["traffic"]}
    assert {c for c, m in CELLS.items() if m} == serving
    for name in NEW:
        entry = next(m for m in bench()["per_layer"] if m["name"] == name)
        assert spec(name)["reader"] == (
            "host_phase" if name == "prefill_dispatch_ms"
            else "step_account")
        assert entry["source"] == "program_span"
        assert entry["layer"] == spec(name)["layer"] == "server"
        assert entry["moves"] == spec(name)["moves"] == "tpot_p95_ms"
        assert entry["workloads"] == spec(name)["workloads"]
        assert entry["unit"] == spec(name)["unit"]


def test_a_traced_rehearsal_prints_the_new_metrics():
    """The cell with long prompts: most decoding steps of a rehearsed
    span carry a chunk, so it reads every metric listed for it but the
    median over decoding steps WITHOUT one, of which a two-second
    rehearsal holds fewer than five."""
    cell = "smallthinker.serve-mixed-len"
    rc, lines, last, err = run_cell(cell, seed=2 ** 31 + 29, trace=1)
    assert rc == 0, err[-2000:]
    line = json.loads(last)
    assert line["correct"]
    want = CELLS[cell] - {"step_ms_decode_only"}
    assert want <= set(line["metrics"]), line["metrics"]
    notes = {x["metric"]: x["note"] for x in lines if "metric" in x}
    note = notes["live_rows_per_tick"]
    assert note["steps_read"] == note["decoding_steps"] >= 5
    assert note["in_span"]["chunks"] >= note["with_a_chunk"] >= 5
    m = line["metrics"]
    assert 0.0 < m["chunk_step_share_pct"]["value"] <= 100.0
    assert m["chunk_step_share_pct"]["value"] == pytest.approx(
        100.0 * note["with_a_chunk"] / note["decoding_steps"])
    assert 1.0 <= m["live_rows_per_tick"]["value"] <= 48.0
    assert 0.0 < m["prefill_dispatch_ms"]["value"] \
        < m["step_ms_with_chunk"]["value"]
