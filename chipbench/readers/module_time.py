"""Device time of one launch of a jitted program, from the trace's
``XLA Modules`` line on the first chip: the median over the launches
that match ``patterns``, in milliseconds."""

from statistics import median

from chipbench import trace_reduce


def read(params, run):
    launches = trace_reduce.module_launches(run["trace"], params["patterns"])
    if not launches:
        return None
    return 1e3 * median(launches), f"{len(launches)} launches"
