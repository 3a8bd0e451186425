"""What the server's steps say of themselves, on the trace's clock.

After every ``step()`` the program writes one point onto the line of
the thread that drives it (``paddlefleetx_tpu/core/serving.py::
STEP_ACCOUNT``, through ``observability/trace.py::point``):

    serving/step_account ticks=1 chunks=0 live=17

directly after the ``serving/step`` annotation it belongs to. The counts
ride in the NAME because ``trace_reduce`` keeps ``(name, start,
duration)`` of an event and drops its metadata. Each point is paired
with the ``serving/step`` that ends at or just before it; steps cut by
the harness's two markers (``host_phase.window``) are left out.

params: ``steps``, the kind of step read, as ``{field: [least, most]}``
(``null`` = no bound); ``value``, one of

  ``share_pct``  of those steps, the % that also pass ``having``
                 (bounds like ``steps``)
  ``per_tick``   sum of ``field`` x ``ticks`` over the sum of ``ticks``
  ``step_ms``    median duration of their ``serving/step`` annotations

and ``launches``, whether the note also counts the launches on the
first chip's module line in the same span, by program: the account's
ticks and chunks are the device's launches (one metric carries the
check for all).

Returns ``None`` where the trace holds no such point (a program from
before it) or fewer than ``MIN_STEPS`` steps of the kind. The note says
how many steps were read and what the whole span held.
"""

import bisect
from statistics import median

from chipbench.readers import host_phase

POINT = "serving/step_account "
STEP = "serving/step"
MIN_STEPS = 5


def accounts(host):
    """``[(start_ns, duration_ns, {field: count})]`` of the steps that
    lie wholly between the markers and have their account, in time
    order."""
    lo, hi = host_phase.window(host)
    steps = sorted((s + d, s) for n, s, d in host if n == STEP)
    ends = [e for e, _ in steps]
    out, taken = [], set()
    for name, t, _ in sorted((ev for ev in host
                              if ev[0].startswith(POINT)),
                             key=lambda ev: ev[1]):
        k = bisect.bisect_right(ends, t) - 1
        if k < 0 or k in taken:
            continue            # its root began before the session
        taken.add(k)
        end, start = steps[k]
        if start >= lo and end <= hi:
            out.append((start, end - start, {
                key: int(v) for key, v in (
                    f.split("=") for f in name[len(POINT):].split())}))
    return out


def passes(counts, bounds):
    """Whether every bounded field lies in its ``[least, most]``."""
    return all((lo is None or counts[f] >= lo)
               and (hi is None or counts[f] <= hi)
               for f, (lo, hi) in bounds.items())


def launches(trace):
    """``{program: [launches, median ms]}`` of the first chip's module
    line inside the span, the program's fingerprint taken off the
    name (``jit_decode_step(8763630088389675191)``)."""
    by = {}
    for name, _, d in (trace.get("devices") or [{}])[0].get(
            "modules", ()):
        by.setdefault(name.split("(")[0], []).append(d / 1e6)
    return {n: [len(v), median(v)] for n, v in sorted(by.items())}


def read(params, run):
    """``(value, note)`` as the module docstring defines them, or
    ``None``."""
    trace = run["trace"]
    span = accounts(trace["host"])
    kind = [(d, c) for _, d, c in span if passes(c, params["steps"])]
    if len(kind) < MIN_STEPS:
        return None
    if params["value"] == "share_pct":
        value = 100.0 * sum(passes(c, params["having"])
                            for _, c in kind) / len(kind)
    elif params["value"] == "per_tick":
        value = sum(c[params["field"]] * c["ticks"] for _, c in kind) \
            / sum(c["ticks"] for _, c in kind)
    elif params["value"] == "step_ms":
        value = median(d for d, _ in kind) / 1e6
    else:
        raise ValueError(f"unknown value {params['value']!r}")
    note = {"steps_read": len(kind), "steps_in_span": len(span),
            "in_span": {f: sum(c[f] for _, _, c in span)
                        for f in ("ticks", "chunks")},
            "decoding_steps": sum(c["ticks"] >= 1 for _, _, c in span),
            "with_a_chunk": sum(c["ticks"] >= 1 and c["chunks"] >= 1
                                for _, _, c in span)}
    if params.get("launches"):
        note["device_launches"] = launches(trace)
    return value, note
