"""A number the driver worked out from the program's own counters and
put into the run's data. params: ``key``, ``scale`` (optional). A run
whose program has no such counter carries no such key: ``None``."""


def read(params, run):
    value = run.get(params["key"])
    if value is None:
        return None
    return value * params.get("scale", 1.0)
