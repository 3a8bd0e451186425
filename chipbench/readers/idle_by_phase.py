"""Device idle time, charged to what the host was doing.

Every idle gap of the first chip's op line inside the traced window
(not the longest five of ``trace_reduce.reduce_trace``) is cut at the
boundaries of the program's own host annotations (``spans``) and each
piece charged to the innermost annotation covering it; what no
annotation covers is ``unattributed`` (the harness between two calls
into the program).

Host and device lines of one profiler session share a time base only
up to a millisecond or two, so the residue is measured per run and not
assumed: every ``sync_spans`` annotation (a host block that returns
when a launch has finished: the server's ``decode_harvest``, the
Engine's ``engine/log_sync``) ends just after the last ``sync_modules``
launch that started before it ends on the device's module line. The
smallest such difference over the window bounds the offset
(``clock_offset_ms``); host intervals are shifted by it before gaps
are charged. A residue beyond ``MAX_OFFSET_NS`` is a failed pairing,
not a clock: it is printed and not applied.

The metric is the idle charged to annotations that are NOT
``device_waits``, as % of the traced window: the device idle while the
host was at work of its own. The note carries the table by phase (ms),
the share of idle charged to a named phase, the unattributed rest, and
``clock_offset_ms`` with the count of pairs it was taken from.

params: ``spans``, ``device_waits``, ``sync_spans``, ``sync_modules``.
Returns ``None`` where the trace holds none of ``spans`` (a program
from before the annotations).
"""

import bisect

from chipbench.readers import host_phase

MAX_OFFSET_NS = 5e6


def clock_offset(host, modules, sync_spans, sync_modules, lo, hi):
    """``(offset_ns, pairs)``: the smallest (end of a sync annotation
    - end of the launch it waited for). 0 with no pair."""
    launches = sorted((s, s + d) for n, s, d in modules
                      if host_phase.matches(n, sync_modules))
    starts = [s for s, _ in launches]
    diffs = []
    for name, s, d in host:
        if not host_phase.matches(name, sync_spans) or s < lo \
                or s + d > hi:
            continue
        k = bisect.bisect_left(starts, s + d) - 1
        if k >= 0:
            diffs.append(s + d - launches[k][1])
    return (min(diffs), len(diffs)) if diffs else (0.0, 0)


def innermost(events):
    """Disjoint ``[(start, end, name)]``: at each instant the innermost
    of the (nested) ``events``, a ``host_phase.tree``."""
    out, stack, t = [], [], None

    def close(upto):
        nonlocal t
        while stack and stack[-1][1] <= upto:
            name, end = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
    for name, s, e in events:
        close(s)
        if stack and s > t:
            out.append((t, s, stack[-1][0]))
        t = s
        stack.append((name, e))
    close(float("inf"))
    return out


def charge(gaps, segments):
    """``({name: ns}, unattributed ns)``: each gap cut at the segment
    boundaries, each piece charged to its segment."""
    by, covered, j = {}, 0.0, 0
    for gs, ge in gaps:
        while j < len(segments) and segments[j][1] <= gs:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < ge:
            s, e, name = segments[k]
            piece = min(e, ge) - max(s, gs)
            if piece > 0:
                by[name] = by.get(name, 0.0) + piece
                covered += piece
            k += 1
    return by, sum(e - s for s, e in gaps) - covered


def read(params, run):
    """``(% of the window, note)`` as the module docstring defines
    them, or ``None``."""
    trace = run["trace"]
    host = trace["host"]
    if not any(host_phase.matches(n, params["spans"])
               for n, _, _ in host):
        return None
    lo, hi = host_phase.window(host)
    chip = trace["devices"][0]
    offset, pairs = clock_offset(
        host, chip["modules"], params["sync_spans"],
        params["sync_modules"], lo, hi)
    applied = abs(offset) <= MAX_OFFSET_NS
    shifted = [(n, s - offset if applied else s, d) for n, s, d in host]
    # an annotation cut by the window's edge still owns its part of it
    segments = innermost(host_phase.tree(
        shifted, params["spans"], float("-inf"), float("inf")))
    edges = [(lo, lo)] + list(chip["busy"]) + [(hi, hi)]
    gaps = [(a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    by, unattributed = charge(gaps, segments)
    idle = sum(e - s for s, e in gaps)
    held = sum(t for n, t in by.items()
               if not host_phase.matches(n, params["device_waits"]))
    note = {
        "clock_offset_ms": offset / 1e6, "pairs": pairs,
        "offset_applied": applied,
        "gaps": len(gaps), "idle_ms": idle / 1e6,
        "idle_pct_of_window": 100.0 * idle / (hi - lo),
        "charged_to_a_phase_pct_of_idle":
            100.0 * (idle - unattributed) / idle if idle else None,
        "unattributed_ms": unattributed / 1e6,
        "idle_ms_by_phase": {n: t / 1e6 for n, t in sorted(
            by.items(), key=lambda kv: -kv[1])}}
    return 100.0 * held / (hi - lo), note
