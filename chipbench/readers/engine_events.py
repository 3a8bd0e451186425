"""A statistic of one field of the Engine's own ``step_window`` records
(the flight recorder, host clock around synced work) over the window.
params: ``field``, ``stat`` (median | mean), ``scale``."""

from statistics import mean, median


def read(params, run):
    values = [e[params["field"]] for e in run.get("events", [])
              if params["field"] in e]
    if not values:
        return None
    stat = {"median": median, "mean": mean}[params["stat"]]
    return stat(values) * params.get("scale", 1.0), f"{len(values)} windows"
