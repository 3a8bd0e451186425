"""``kernel_roofline`` with the FLOPs module a parameter: a kernel's
share of its roofline, the work function taken from
``chipbench/<module>.py`` (``kernel_roofline.py`` reaches ``flops.py``
only).

params: ``module`` (a file beside ``flops.py``), ``work`` (its
function), ``patterns`` (fnmatch on XLA-op event names), ``args`` (a
number, a key of the run's data, ``config.<key>``, or
``trace_events_mean.<field>``: the mean of a field of the
``step_window`` records whose step lies in the traced span), ``times``
(optional key of the run's data).
Anything it needs that the run lacks (no such ops in the trace, a
program without those records) reads as nothing: ``None``."""

import importlib

from chipbench import flops, trace_reduce


def _arg(spec, run):
    if not isinstance(spec, str):
        return spec
    if spec.startswith("config."):
        return run["config"].get(spec[len("config."):])
    if spec.startswith("trace_events_mean."):
        field = spec[len("trace_events_mean."):]
        values = [e[field] for e in run.get("trace_events", [])
                  if field in e]
        return sum(values) / len(values) if values else None
    return run.get(spec)


def read(params, run):
    """``(share of the roofline in %, note)`` or ``None``."""
    seconds = trace_reduce.kernel_seconds(run["trace"], params["patterns"])
    args = {k: _arg(v, run) for k, v in params["args"].items()}
    times = _arg(params.get("times", 1), run)
    if seconds <= 0 or times is None or any(
            v is None for v in args.values()) or not run["peaks"]:
        return None
    work = getattr(importlib.import_module("chipbench." + params["module"]),
                   params["work"])
    ops, nbytes = work(**args)
    least, bound = flops.roofline(ops * times, nbytes * times, run["peaks"])
    return 100.0 * least / seconds, \
        f"{bound}-bound: least {least:.6f} s of {seconds:.6f} s on the device"
