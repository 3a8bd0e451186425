"""Time a collective runs on a device while no compute op does, over the
traced window; the worst chip. From the trace's XLA-op line."""


def read(params, run):
    t = run["trace"]
    if len(t["devices"]) < 2:
        return None
    worst = max(d["collective_exposed_s"] for d in t["devices"])
    total = max(d["collective_s"] for d in t["devices"])
    return 100.0 * worst / t["window_s"], \
        f"collectives run {total:.6f} s, {worst:.6f} s of it exposed"
