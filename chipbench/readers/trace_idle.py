"""Device idle share: 1 - (union of the device's busy intervals, averaged
over the chips) / traced window."""


def read(params, run):
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
