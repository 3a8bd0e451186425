"""A percentile of exact samples the harness took on its own clock.
params: ``key`` (a list in the run's data), ``percentile``."""

from chipbench import traffic_gen


def read(params, run):
    values = run.get(params["key"]) or []
    if not values:
        return None
    return traffic_gen.percentile(values, params["percentile"]), \
        f"{len(values)} samples"
