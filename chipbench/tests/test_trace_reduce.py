"""The reduction from a trace to numbers, held to a recorded trace.

``chipbench/testdata/small.xplane.pb`` was recorded on a TPU v5e by
``record_small_trace.py``: six launches of one jitted program (a matmul,
then a ``fori_loop`` of three matmuls), each followed by a host sleep, the
whole between the two markers. Known by construction: six launches, the
loop's three matmuls nested inside one ``while`` on the op line, idle
gaps owned by ``host/sleep``.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import trace_reduce as tr  # noqa: E402

SMALL = os.path.join(ROOT, "chipbench", "testdata", "small.xplane.pb")
US = 1e-6


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(tr.load(SMALL))


def test_busy_is_the_union_of_the_op_line_not_the_sum_of_all_lines(reduced):
    launches = tr.module_launches(reduced, ["jit_small_program*"])
    assert len(launches) == 6
    assert all(55 * US < x < 56 * US for x in launches)
    # busy = the six launches, to a tenth of a microsecond
    assert reduced["busy_s"] == pytest.approx(sum(launches), abs=0.2 * US)
    assert reduced["busy_s"] == pytest.approx(332.28 * US, abs=0.05 * US)
    # what scripts/trace_step.py does: every line of the plane summed.
    # Steps/modules/ops/async ops cover the same nanoseconds three times
    from jax.profiler import ProfileData
    naive = sum(e.duration_ns for pl in ProfileData.from_file(SMALL).planes
                if pl.name.startswith(tr.DEVICE_PREFIX)
                for ln in pl.lines for e in ln.events) / 1e9
    assert naive == pytest.approx(996.54 * US, abs=0.05 * US)
    assert naive > 2.9 * reduced["busy_s"]


def test_nested_ops_are_charged_once(reduced):
    dev = reduced["devices"][0]
    # the while encloses 18 launches of fusion.11 (6 x 3 iterations):
    # its total is theirs plus the loop's own few hundred nanoseconds
    loop = tr.kernel_seconds(reduced, ["while while"])
    body = tr.kernel_seconds(reduced, ["fusion.11 fusion"]) \
        + tr.kernel_seconds(reduced, ["copy.9 copy"])
    assert loop == pytest.approx(217.93 * US, abs=0.05 * US)
    assert body == pytest.approx(loop, abs=0.5 * US)
    assert dev["op_self_s"]["while while"] < 0.5 * US
    assert dev["op_self_s"]["fusion.11 fusion"] == pytest.approx(
        208.05 * US, abs=0.05 * US)
    # self times add up to the busy time: nothing counted twice
    assert sum(dev["op_self_s"].values()) == pytest.approx(
        reduced["busy_s"], abs=0.01 * US)
    # the breakdown is by family, with the count of events
    assert reduced["device_ops"][0][0] == "fusion x24"
    assert reduced["device_ops"][0][1] == pytest.approx(
        302.72 * US, abs=0.05 * US)
    assert tr.family("self_attn.78 custom-call") == "self_attn custom-call"
    assert tr.family("copy.9 copy") == "copy"
    assert len(reduced["device_ops"]) <= 10


def test_window_idle_share_and_gap_owners(reduced):
    # markers are 54 ms apart; the device worked for a third of a
    # millisecond of it
    assert reduced["window_s"] == pytest.approx(0.054018, abs=1e-5)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.99385, abs=1e-4)
    owners = [name for name, _ in reduced["idle_gaps"]]
    assert owners[0].startswith("host/sleep")
    assert all(s > 0 for _, s in reduced["idle_gaps"])
    assert len(reduced["idle_gaps"]) <= 5


def test_short_names():
    assert tr.short_name(
        "%fusion.11 = bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)} fusion("
        "bf16[1024,1024]{1,0} %copy.9), kind=kOutput") == "fusion.11 fusion"
    assert tr.short_name(
        "%self_attn.78 = (bf16[128,1024,64]{2,1,0}, bf16[128,1024,64]"
        "{2,1,0}) custom-call(bf16[128,1024,64]{2,1,0} %x), "
        "custom_call_target=\"tpu_custom_call\"") == \
        "self_attn.78 custom-call"
    assert tr.short_name("jit_train_step(123)") == "jit_train_step(123)"
    assert tr._is_collective("all-reduce.5 all-reduce")
    assert tr._is_collective("ar.1 all-gather-start")
    assert not tr._is_collective("fusion.3 fusion")


def test_exposed_collective_time_is_what_compute_does_not_cover():
    ops = [("fusion.1 fusion", 0, 50), ("all-reduce.1 all-reduce", 40, 30),
           ("fusion.2 fusion", 60, 20), ("all-gather.2 all-gather", 90, 10)]
    trace = {"devices": {"/device:TPU:0": {tr.OPS_LINE: ops,
                                           tr.MODULES_LINE: []},
                         "/device:TPU:1": {tr.OPS_LINE: ops[:1],
                                           tr.MODULES_LINE: []}},
             "host": []}
    r = tr.reduce_trace(trace)
    d0 = r["devices"][0]
    # all-reduce 40..70: 40..50 under fusion.1, 60..70 under fusion.2,
    # 50..60 exposed; the all-gather 90..100 wholly exposed
    assert d0["collective_s"] == pytest.approx(40e-9)
    assert d0["collective_exposed_s"] == pytest.approx(20e-9)
    assert r["busy_s"] == pytest.approx((90e-9 + 50e-9) / 2)
