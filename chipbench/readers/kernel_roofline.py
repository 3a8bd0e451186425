"""A kernel's share of its roofline: the least time the chip could take
for the work the algorithm needs (``chipbench/flops.py::<work>``, the
larger of FLOPs / peak FLOP/s and bytes / peak bytes/s, from
``chipbench/peaks.json``) over the device time of the trace events that
match ``patterns``. The note says which bound applies.

params: ``patterns`` (fnmatch on XLA-op event names), ``work`` (a
function of flops.py), ``args`` (its arguments: a number, a key of the
run's data, or ``config.<key>``), ``times`` (optional key of the run's
data: how many times the traced window did that work).
"""

from chipbench import flops, trace_reduce


def _arg(spec, run):
    if not isinstance(spec, str):
        return spec
    if spec.startswith("config."):
        return run["config"][spec[len("config."):]]
    return run.get(spec)


def read(params, run):
    seconds = trace_reduce.kernel_seconds(run["trace"], params["patterns"])
    args = {k: _arg(v, run) for k, v in params["args"].items()}
    times = _arg(params.get("times", 1), run)
    if seconds <= 0 or times is None or any(
            v is None for v in args.values()) or not run["peaks"]:
        return None
    ops, nbytes = getattr(flops, params["work"])(**args)
    least, bound = flops.roofline(ops * times, nbytes * times, run["peaks"])
    return 100.0 * least / seconds, \
        f"{bound}-bound: least {least:.6f} s of {seconds:.6f} s on the device"
