"""The two readers of the program's host annotations
(``readers/host_phase.py``, ``readers/idle_by_phase.py``) on a
synthetic ``run["trace"]`` with nested annotations and known gaps,
and, through ``run.py --rehearse --trace 1``, that the metrics built on
them are printed for the cells that list them. Run by hand:

    python -m pytest chipbench/tests/test_span_readers.py -q
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _run import ROOT, bench, run_cell  # noqa: E402

sys.path.insert(0, ROOT)
from chipbench import trace_reduce  # noqa: E402
from chipbench.readers import host_phase, idle_by_phase  # noqa: E402

MS = 1e6
SPANS = ["serving/step", "serving/step/*"]
WAITS = ["serving/step/decode_harvest", "serving/step/state_fetch"]
NEW = {"server_host_self_ms", "decode_harvest_wait_ms",
       "host_held_idle_pct.serve", "input_host_ms", "device_put_ms",
       "step_dispatch_ms", "host_held_idle_pct.train"}


def synthetic(steps=5, shift=0.0, settle=0.1 * MS):
    """``steps`` round trips of 100 ms on a host clock ``shift`` ns
    ahead of the device's: admit 10, dispatch 5, harvest 60, state
    fetch 5, commit 10, and 10 in no phase. The device is busy from
    1 ms after the dispatch began until ``settle`` before the harvest
    ends; 20 ms lie between two steps."""
    host = [(trace_reduce.BEGIN_MARK, 0.0, 1.0),
            (trace_reduce.END_MARK, (steps * 120 + 20) * MS, 1.0)]
    busy, modules = [], []
    for i in range(steps):
        t = (10 + 120 * i) * MS
        h = t + shift
        host += [
            ("serving/step", h, 100 * MS),
            ("serving/step/admit", h, 10 * MS),
            # the runtime's own event inside a phase: no child of it
            ("PjitFunction(activate)", h + 1 * MS, 2 * MS),
            ("serving/step/decode_dispatch", h + 10 * MS, 5 * MS),
            ("serving/step/decode_harvest", h + 15 * MS, 60 * MS),
            ("serving/step/state_fetch", h + 75 * MS, 5 * MS),
            ("serving/step/commit", h + 80 * MS, 10 * MS)]
        b0, b1 = t + 11 * MS, t + 75 * MS - settle
        busy.append((b0, b1))
        modules.append(("jit_decode_step(7)", b0, b1 - b0))
    # a step that only pumped a prefill chunk: no harvest
    host.append(("serving/step", (steps * 120 + 12) * MS + shift, 3 * MS))
    return {"trace": {"host": host,
                      "devices": [{"busy": busy, "modules": modules}]}}


def params(**kw):
    return dict({"spans": SPANS, "patterns": SPANS, "per": "serving/step",
                 "stat": "median"}, **kw)


def test_self_time_excludes_children_and_only_them():
    run = synthetic()
    # the root alone: 100 - (10 + 5 + 60 + 5 + 10)
    got, note = host_phase.read(
        params(patterns=["serving/step"],
               having="serving/step/decode_harvest"), run)
    assert got == pytest.approx(10.0) and "5 x" in note
    # root and phases less the device waits: 100 - 60 - 5; the
    # runtime's PjitFunction inside admit is admit's own work
    got, _ = host_phase.read(
        params(exclude=WAITS, having="serving/step/decode_harvest"), run)
    assert got == pytest.approx(35.0)
    got, _ = host_phase.read(
        params(patterns=["serving/step/decode_harvest"],
               having="serving/step/decode_harvest"), run)
    assert got == pytest.approx(60.0)
    # without `having` the chunk-only step counts too: 6 occurrences
    got, note = host_phase.read(params(stat="mean"), run)
    assert "6 x" in note
    assert got == pytest.approx((5 * 100 + 3) / 6)


def test_an_occurrence_cut_by_the_window_is_left_out():
    run = synthetic()
    host = run["trace"]["host"]
    host.append(("serving/step", -50 * MS, 100 * MS))   # straddles begin
    got, note = host_phase.read(
        params(patterns=["serving/step"], stat="mean"), run)
    assert "6 x" in note


def test_a_trace_without_the_annotations_reads_nothing():
    run = synthetic()
    run["trace"]["host"] = [ev for ev in run["trace"]["host"]
                            if not ev[0].startswith("serving/")]
    assert host_phase.read(params(), run) is None
    # the parent of the PR that brought the phases: the root's name is
    # there (as ``h2d`` was), the children that are summed are not
    run["trace"]["host"].append(("serving/step", 10 * MS, 50 * MS))
    assert host_phase.read(
        params(patterns=["serving/step/admit"]), run) is None
    run["trace"]["host"].pop()
    assert idle_by_phase.read(
        {"spans": SPANS, "device_waits": WAITS,
         "sync_spans": ["serving/step/decode_harvest"],
         "sync_modules": ["jit_decode_step*"]}, run) is None


IDLE = {"spans": SPANS, "device_waits": WAITS,
        "sync_spans": ["serving/step/decode_harvest"],
        "sync_modules": ["jit_decode_step*"]}


def test_a_gap_straddling_phases_is_split_between_them():
    value, note = idle_by_phase.read(IDLE, synthetic(settle=0.0))
    by = note["idle_ms_by_phase"]
    # each step's gap runs from the end of the harvest (device done)
    # over state_fetch 5, commit 10, the root's tail 10, 20 between
    # steps (no annotation), admit 10, 1 ms into the dispatch
    assert by["serving/step/state_fetch"] == pytest.approx(25.0)
    assert by["serving/step/commit"] == pytest.approx(50.0)
    assert by["serving/step"] == pytest.approx(50.0 + 3.0)
    assert by["serving/step/admit"] == pytest.approx(50.0)
    assert by["serving/step/decode_dispatch"] == pytest.approx(5.0)
    assert "serving/step/decode_harvest" not in by
    assert note["clock_offset_ms"] == pytest.approx(0.0)
    assert note["pairs"] == 5 and note["offset_applied"]
    window = 5 * 120 + 20
    idle = window - 5 * 64.0
    assert note["idle_ms"] == pytest.approx(idle, abs=1e-3)
    assert note["unattributed_ms"] == pytest.approx(
        idle - sum(by.values()), abs=1e-3)
    assert note["charged_to_a_phase_pct_of_idle"] == pytest.approx(
        100.0 * sum(by.values()) / idle, abs=1e-3)
    # held by the host: everything charged but the device waits
    held = sum(v for k, v in by.items() if k not in WAITS)
    assert value == pytest.approx(100.0 * held / window, abs=1e-3)


@pytest.mark.parametrize("shift_ms", [1.5, -2.0])
def test_a_shifted_host_clock_is_recovered(shift_ms):
    """The offset read is the injected shift plus the shortest true
    delay between a launch's end and its harvest's (0.1 ms here), and
    the table comes out as on the unshifted clock to within that."""
    _, plain = idle_by_phase.read(IDLE, synthetic())
    value, note = idle_by_phase.read(IDLE, synthetic(shift=shift_ms * MS))
    assert note["pairs"] == 5 and note["offset_applied"]
    assert note["clock_offset_ms"] == pytest.approx(shift_ms + 0.1)
    assert abs(note["clock_offset_ms"] - shift_ms) <= abs(shift_ms)
    for name, ms in plain["idle_ms_by_phase"].items():
        assert note["idle_ms_by_phase"][name] == \
            pytest.approx(ms, abs=5 * 0.1 + 1e-3), name
    # a residue no clock has is a failed pairing: printed, not applied
    _, far = idle_by_phase.read(IDLE, synthetic(shift=40 * MS))
    assert far["clock_offset_ms"] == pytest.approx(40.1)
    assert not far["offset_applied"]


CELLS = {w["name"]: {m["name"] for m in bench()["per_layer"]
                     if w["name"] in m.get("workloads", ())} & NEW
         for w in bench()["workloads"]}


def test_every_new_metric_is_listed_by_some_cell():
    assert set().union(*CELLS.values()) == NEW
    for name in NEW:
        spec = json.load(open(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".json")))
        assert spec["reader"] in ("host_phase", "idle_by_phase")
        entry = next(m for m in bench()["per_layer"] if m["name"] == name)
        assert entry["source"] == "program_span"
        assert entry["workloads"] == spec["workloads"]


@pytest.mark.parametrize("cell", sorted(c for c, m in CELLS.items() if m))
def test_a_traced_rehearsal_prints_the_new_metrics(cell):
    rc, lines, last, err = run_cell(cell, seed=2 ** 31 + 23, trace=1)
    assert rc == 0, err[-2000:]
    line = json.loads(last)
    assert CELLS[cell] <= set(line["metrics"]), line["metrics"]
    notes = {x["metric"]: x["note"] for x in lines if "metric" in x}
    idle = next(n for n in CELLS[cell] if n.startswith("host_held_idle"))
    assert "clock_offset_ms" in notes[idle]
    assert "idle_ms_by_phase" in notes[idle]
