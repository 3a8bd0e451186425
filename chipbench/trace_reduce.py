"""From a profiler trace (``.xplane.pb``) to numbers.

One reduction for every cell and every PR. A TPU plane carries several
*lines* over the same wall time — steps, XLA modules, XLA ops, name
scopes — so summing every line of a plane counts the same nanosecond
three or four times (``scripts/trace_step.py`` does). Here one line per
device is chosen BY NAME:

  ``XLA Ops``      leaf work; busy time is the union of its intervals,
                   per-op time is self time (an op that encloses others,
                   a ``while`` or a ``call``, is charged only for what
                   its children do not cover)
  ``XLA Modules``  one event per launch of a jitted program

Host lines give the annotations (``jax.profiler.TraceAnnotation``) that
name what the host was doing in each idle gap of the device. The traced
window is bounded by the two marker annotations the harness writes
(``chipbench/trace_begin`` / ``chipbench/trace_end``), each after a
``block_until_ready``; without markers it is the span of the device ops.
"""

import fnmatch
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BEGIN_MARK = "chipbench/trace_begin"
END_MARK = "chipbench/trace_end"
COLLECTIVE_PATTERNS = ("all-reduce*", "all-gather*", "reduce-scatter*",
                       "collective-permute*", "all-to-all*",
                       "collective-broadcast*", "ragged-all-to-all*")


def start(directory):
    """Start the profiler as every traced run does: python tracer off
    (its per-call events would swamp a window), no HLO dump."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(directory, profiler_options=opts)


def mark(name):
    """Write a marker annotation (``BEGIN_MARK`` / ``END_MARK``) into
    the running trace; returns the wall time."""
    import time

    import jax
    with jax.profiler.TraceAnnotation(name):
        pass
    return time.time()


def find_xplane(directory):
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` log dir."""
    hits = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(hits, key=os.path.getmtime)


_HLO = re.compile(r"^%?(\S+) = .*?([a-z][a-z\-]*)\(")


def short_name(text):
    """``"%fusion.11 = bf16[..] fusion(..), kind=.."`` -> ``"fusion.11
    fusion"``: the instruction's name and its opcode. The TPU's op line
    carries the whole HLO text of each instruction; a Pallas kernel is a
    ``custom-call`` named after the module that called it
    (``self_attn.78 custom-call``). Any other event keeps its name."""
    m = _HLO.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text


def _events(line):
    return [(short_name(e.name), float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def load(path, rehearsal=False):
    """``{"devices": {plane: {line: [(name, start_ns, dur_ns)]}},
    "host": [(name, start_ns, dur_ns)]}`` with only the lines the
    reduction reads. ``rehearsal`` (CPU test runs, which have no device
    plane) stands the XLA:CPU executor threads in for one device so the
    readers' control flow can be driven; nothing it yields is a device
    number."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    if rehearsal:
        ops, modules = [], []
        for plane in data.planes:
            for ln in plane.lines:
                evs = _events(ln)
                if any(n == BEGIN_MARK for n, _, _ in evs):
                    host.extend(ev for ev in evs
                                if not ev[0].startswith("$"))
        for plane in data.planes:
            for ln in plane.lines:
                evs = _events(ln)
                if ln.name.startswith("tf_XLAPjRtCpuClient"):
                    ops += [(n + " op", s, d) for n, s, d in evs
                            if d > 0 and not n.startswith(
                                ("end: ", "Threadpool", "ThunkExecutor"))]
                modules += [(n[len("PjitFunction("):-1], s, d)
                            for n, s, d in evs
                            if n.startswith("PjitFunction(")]
        devices["/host:CPU (rehearsal)"] = {OPS_LINE: ops,
                                            MODULES_LINE: modules}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                # a chip that ran nothing in the window has no op line
                if not any(True for ln in plane.lines for _ in ln.events):
                    continue
                raise ValueError(
                    f"plane {plane.name} has no {OPS_LINE!r} line: "
                    f"{sorted(lines)}")
            devices[plane.name] = {
                OPS_LINE: _events(lines[OPS_LINE]),
                MODULES_LINE: _events(lines[MODULES_LINE])
                if MODULES_LINE in lines else []}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = _events(ln)
                # the thread that wrote the markers is the one that
                # drives the program: its annotations name the gaps.
                # "$file:line fn" events are the python tracer's; names
                # with "::" or "=>" are the runtime's own internals
                if any(n == BEGIN_MARK for n, _, _ in evs):
                    host.extend(ev for ev in evs if not (
                        ev[0].startswith("$") or "::" in ev[0]
                        or "=>" in ev[0] or ev[0].startswith("PJRT_")))
    return {"devices": devices, "host": host}


def union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _clip(events, lo, hi):
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def self_times(events):
    """``{name: ns}`` where an event that encloses later ones on the
    same line is charged only for what they leave uncovered."""
    total = {}
    stack = []            # (name, end, covered_by_children)
    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, start, covered = stack.pop()
            total[name] = total.get(name, 0.0) + (end - start) - covered
            if stack:
                stack[-1][3] += end - start
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        stack.append([name, s + d, s, 0.0])
    close(float("inf"))
    return total


def _subtract(a, b):
    """Length of union ``a`` not covered by union ``b``."""
    out, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out += e - cur
    return out


def _is_collective(name):
    kind = name.split(" ")[-1]
    return any(fnmatch.fnmatch(kind, p) for p in COLLECTIVE_PATTERNS)


def _match(name, patterns):
    return any(fnmatch.fnmatch(name, p) for p in patterns)


def _window(trace):
    begins = [s + d for n, s, d in trace["host"] if n == BEGIN_MARK]
    ends = [s for n, s, d in trace["host"] if n == END_MARK]
    if begins and ends and max(ends) > min(begins):
        return min(begins), max(ends)
    ops = [ev for dev in trace["devices"].values() for ev in dev[OPS_LINE]]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(s for _, s, _ in ops), max(s + d for _, s, d in ops)


def _gap_owner(host, s, e):
    """The host annotation that covers most of the gap; the shorter one
    on a tie (the innermost of nested annotations)."""
    best, best_key = "unannotated", (0.0, 0.0)
    for name, hs, hd in host:
        ov = min(e, hs + hd) - max(s, hs)
        if ov > 0:
            key = (ov, -hd)
            if key > best_key:
                best, best_key = name, key
    return best


def reduce_trace(trace, top_ops=10, top_gaps=5):
    """The numbers every reader starts from. Seconds throughout."""
    lo, hi = _window(trace)
    window = hi - lo
    per_device = []
    for plane in sorted(trace["devices"]):
        ops = _clip(trace["devices"][plane][OPS_LINE], lo, hi)
        busy = union((s, s + d) for _, s, d in ops)
        selfs = self_times(ops)
        coll = union((s, s + d) for n, s, d in ops if _is_collective(n))
        # an enclosing while/call would hide every gap inside it
        compute = union((s, s + d) for n, s, d in ops
                        if not _is_collective(n) and not _encloses(n))
        per_device.append({
            "plane": plane, "busy": busy,
            "busy_s": _length(busy) / 1e9,
            "op_self_s": {n: t / 1e9 for n, t in selfs.items()},
            "ops": ops,
            "modules": _clip(trace["devices"][plane][MODULES_LINE], lo, hi),
            "collective_s": _length(coll) / 1e9,
            "collective_exposed_s": _subtract(coll, compute) / 1e9})
    if not per_device:
        raise ValueError("the trace holds no TPU device plane with work")
    n = len(per_device)
    # by family: "fusion.5426 fusion" and "fusion.31 fusion" are both
    # "fusion"; the ten busiest single instructions of one step are as
    # a rule forty copies of one kind and say less than their sum
    op_total, op_count = {}, {}
    for dev in per_device:
        for name, t in dev["op_self_s"].items():
            fam = family(name)
            op_total[fam] = op_total.get(fam, 0.0) + t / n
        for name, _, _ in dev["ops"]:
            fam = family(name)
            op_count[fam] = op_count.get(fam, 0) + 1 / n
    first = per_device[0]
    edges = [(lo, lo)] + first["busy"] + [(hi, hi)]
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(edges, edges[1:]) if b[0] > a[1]),
                  reverse=True)[:top_gaps]
    by_owner = {}
    for length, s, e in gaps:
        by_owner.setdefault(_gap_owner(trace["host"], s, e), []).append(
            length / 1e9)
    return {
        "window_s": window / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "devices": per_device,
        "device_ops": [(f"{fam} x{round(op_count[fam])}", t) for fam, t in
                       sorted(op_total.items(), key=lambda kv: -kv[1])[
                           :top_ops]],
        "idle_gaps": [[f"{owner} (longest of {len(v)})" if len(v) > 1
                       else owner, max(v)]
                      for owner, v in sorted(by_owner.items(),
                                             key=lambda kv: -max(kv[1]))],
        "host": trace["host"]}


def family(name):
    """``"self_attn.78 custom-call"`` -> ``"self_attn custom-call"``:
    the instruction's name without its number, and its opcode where
    that says more than the name repeated."""
    base, _, kind = name.rpartition(" ")
    base = re.sub(r"[.\d]+$", "", base)
    return base if base == kind or not base else f"{base} {kind}"


def _encloses(name):
    return name.split(" ")[-1] in ("while", "call", "conditional")


def kernel_seconds(reduced, patterns):
    """Device seconds of the ops matching ``patterns`` (fnmatch on the
    event name), averaged over the chips."""
    return sum(d / 1e9 for dev in reduced["devices"]
               for n, _, d in dev["ops"] if _match(n, patterns)) \
        / len(reduced["devices"])


def module_launches(reduced, patterns):
    """Durations (seconds) of every launch of the matching modules on
    the first chip."""
    return [d / 1e9 for n, _, d in reduced["devices"][0]["modules"]
            if _match(n, patterns)]


def describe(path, limit=40):
    """Planes, lines and the most frequent event names: what to look at
    by hand before writing a pattern."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for ln in plane.lines:
            evs = _events(ln)
            out.append(f"  LINE {ln.name!r}: {len(evs)} events, "
                       f"{sum(d for _, _, d in evs) / 1e6:.3f} ms summed")
            agg = {}
            for n, _, d in evs:
                c = agg.setdefault(n, [0, 0.0])
                c[0] += 1
                c[1] += d
            for n, (c, d) in sorted(agg.items(),
                                    key=lambda kv: -kv[1][1])[:limit]:
                out.append(f"      {d / 1e6:10.3f} ms x{c:<6} {n[:150]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
