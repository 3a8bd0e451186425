"""Self time of the program's own host annotations (``paddlefleetx_tpu/
observability/trace.py::annotate``: TraceMes on the profiler's clock,
on the line of the thread that drives the program), per occurrence of
one of them.

An annotation's self time is its duration less what its child
annotations cover (``choosing-metrics`` guide, section 4). Only the
annotations matching ``spans`` form the tree: the runtime's own events
inside a phase (``PjitFunction(..)``, ``np.asarray(jax.Array)``) are
that phase's work, not its children. Per occurrence of the ``per``
annotation (only those that hold a ``having`` annotation, where given)
the self times of the annotations inside it that match ``patterns``
and not ``exclude`` are summed; the metric is the ``stat`` (median |
mean) of those sums, in milliseconds. Occurrences cut by the traced
window's edges are left out.

params: ``spans``, ``patterns``, ``per``, ``stat``; optional
``exclude``, ``having``. Returns ``None`` where the trace holds no
``per`` annotation or none that is summed (a program from before the
annotations: the parent of the PR that brought them has ``h2d`` but
not its children).
"""

import bisect
from fnmatch import fnmatch
from statistics import mean, median

from chipbench import trace_reduce


def window(host):
    """``(lo, hi)`` between the harness's two markers, in the host
    line's nanoseconds; the whole line without them."""
    begins = [s + d for n, s, d in host if n == trace_reduce.BEGIN_MARK]
    ends = [s for n, s, d in host if n == trace_reduce.END_MARK]
    if begins and ends and max(ends) > min(begins):
        return min(begins), max(ends)
    return (min(s for _, s, _ in host), max(s + d for _, s, d in host))


def matches(name, patterns):
    """Whether ``name`` matches any of the fnmatch ``patterns``."""
    return any(fnmatch(name, p) for p in patterns)


def tree(host, spans, lo, hi):
    """``[(name, start, end)]`` of the annotations matching ``spans``
    that lie wholly inside ``[lo, hi]``, parents before children."""
    got = [(n, s, s + d) for n, s, d in host
           if matches(n, spans) and s >= lo and s + d <= hi]
    return sorted(got, key=lambda ev: (ev[1], -ev[2]))


def self_times(events):
    """Self time of each event of a ``tree``, in its order."""
    out = [0.0] * len(events)
    stack = []                  # [index, end, covered by children]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            i, end, covered = stack.pop()
            dur = end - events[i][1]
            out[i] = dur - covered
            if stack:
                stack[-1][2] += dur
    for i, (_, s, e) in enumerate(events):
        close(s)
        stack.append([i, e, 0.0])
    close(float("inf"))
    return out


def read(params, run):
    """``(milliseconds, note)`` as the module docstring defines them,
    or ``None``."""
    host = run["trace"]["host"]

    def summed(name):
        return matches(name, params["patterns"]) and \
            not matches(name, params.get("exclude", ()))
    names = {n for n, _, _ in host}
    if params["per"] not in names or not any(map(summed, names)):
        return None
    lo, hi = window(host)
    events = tree(host, params["spans"], lo, hi)
    selfs = self_times(events)
    per = [(s, e) for n, s, e in events if n == params["per"]]
    starts = [s for s, _ in per]
    sums = [0.0] * len(per)
    held = [params.get("having") is None] * len(per)
    for (name, s, e), own in zip(events, selfs):
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or e > per[k][1]:
            continue
        if name == params.get("having"):
            held[k] = True
        if summed(name):
            sums[k] += own
    values = [v for v, ok in zip(sums, held) if ok]
    if not values:
        return None
    stat = {"median": median, "mean": mean}[params["stat"]]
    return stat(values) / 1e6, \
        f"{params['stat']} over {len(values)} x {params['per']}"
