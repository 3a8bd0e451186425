"""Share of the device's busy time spent in the ops that match
``patterns`` (fnmatch on XLA-op event names), in percent; ``None`` where
the trace holds no such op."""

from chipbench import trace_reduce


def read(params, run):
    """``(share of busy time in %, note)`` or ``None``."""
    seconds = trace_reduce.kernel_seconds(run["trace"], params["patterns"])
    busy = run["trace"]["busy_s"]          # both are means over the chips
    if seconds <= 0 or busy <= 0:
        return None
    return 100.0 * seconds / busy, f"{seconds:.6f} s of {busy:.6f} s busy"
