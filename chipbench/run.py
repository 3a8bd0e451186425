#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time, on a machine that holds the cell's chips. Every
name is data: the cell is looked up in ``BENCHMARK.json``, its
configuration in the file that entry names, its traffic mix in
``chipbench/traffic/<traffic>.json``, the mix's driver in
``chipbench/drivers/<driver>.py``, each per-layer metric in
``chipbench/layer_metrics/<metric>.json`` and its reader in
``chipbench/readers/<reader>.py``. No cell, configuration, mix or metric
is named in this file.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``). With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics. Any failure
to start (no TPU, too few chips, an unknown name) exits non-zero with no
such line. ``--rehearse`` (tests only; the driver never passes it) runs
the same control flow on the CPU at a tiny size and says so in
``device``. ``--control fp8`` (limit-setting and tests only) also prints
what the reference computed in that lower precision reads in the
program's place, on earlier lines; it changes nothing on the last one.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

T_PROCESS = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Refused(Exception):
    """The run cannot start; exit non-zero, print no result."""


def load_json(*parts):
    path = os.path.join(ROOT, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise Refused(f"cannot read {path}: {e}")


def load_module(kind, name):
    """``chipbench/<kind>/<name>.py`` as a module."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no {kind[:-1]} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"unknown {what} {name!r}; BENCHMARK.json has "
                  f"{[e['name'] for e in entries]}")


def applies(metric, cell, bench):
    """Whether ``cell`` reports ``metric``: it is listed, or the metric
    lists no cells and the cell reports the metric it moves."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    moved = metric.get("moves")
    if moved is None:
        return True
    return applies(named(bench["end_to_end"], moved, "metric"), cell, bench)


def load_cell(workload, rehearse=False):
    """``(bench, cell, config, mix, extra overrides)`` of one cell, each
    from the file its name leads to; ``rehearse`` lays the tiny sizes of
    ``tests/rehearse.json`` over configuration and mix."""
    bench = load_json("BENCHMARK.json")
    cell = named(bench["workloads"], workload, "workload")
    config = load_json(named(bench["configs"], cell["config"],
                             "configuration")["file"])
    mix = load_json("chipbench", "traffic", cell["traffic"] + ".json")
    extra = []
    if rehearse:
        tiny = load_json("chipbench", "tests", "rehearse.json")
        config.update(tiny["config"])
        extra = tiny["overrides"]
        for key, value in tiny["traffic"].get(mix["driver"], {}).items():
            if isinstance(value, dict) and isinstance(mix.get(key), dict):
                mix[key].update(value)
            else:
                mix[key] = value
    return bench, cell, config, mix, extra


class Context:
    """What a driver is given, and the few things it calls back."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.setup_s = None

    def log(self, obj):
        """One JSON line, stamped with the seconds since the process
        started."""
        print(json.dumps(dict(obj, t=round(time.time() - T_PROCESS, 2)),
                         default=float), flush=True)

    def setup_done(self, t_first_measured):
        """Called by the driver at the first measured step / first due
        request: set-up is everything from process start to here."""
        self.setup_s = t_first_measured - T_PROCESS

    def watch_compiles(self):
        """Record every compilation from now on: ``(wall time, event,
        seconds)``. Nothing should compile inside a measured window."""
        import jax
        self.compiles = []

        def seen(event, duration, **_):
            if "compile" in event and "backend" in event:
                self.compiles.append((time.time(), event, duration))
        jax.monitoring.register_event_duration_secs_listener(seen)

    def compiles_between(self, t0, t1):
        return [{"at_s": round(t - t0, 3), "seconds": round(d, 3)}
                for t, _, d in self.compiles if t0 <= t <= t1]

    def memory_peak(self):
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks) if peaks else 0


def gate_devices(chips, rehearse):
    """The chips of the cell, or ``Refused``. JAX is first touched
    here."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PFX_PALLAS_INTERPRET"] = "1"
    import jax
    if rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", chips)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no device: {e}")
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        raise Refused(f"JAX found platform {platform!r}, not 'tpu': the "
                      f"benchmark measures the chip and nothing else")
    if len(devices) < chips:
        raise Refused(f"{len(devices)} chips visible, the cell needs "
                      f"{chips}")
    return devices[:chips]


def main(argv=None):
    """Resolve the cell, gate the device, run its driver, read its
    per-layer metrics, print the result line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: CPU, interpret mode, tiny sizes")
    ap.add_argument("--control", default=None,
                    help="limit-setting and tests only: also put the "
                         "reference at this lower precision in the "
                         "program's place and print what it reads")
    args = ap.parse_args(argv)

    bench, cell, config, mix, extra = load_cell(args.workload, args.rehearse)
    driver = load_module("drivers", mix["driver"])
    # the program's own modules (the system under test) import from here
    sys.path.insert(0, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "paddlefleetx_tpu")):
        raise Refused(f"no program to measure under {ROOT}")

    devices = gate_devices(int(cell["chips"]), args.rehearse)
    from chipbench import flops, trace_reduce
    from paddlefleetx_tpu.utils.env import setup_compilation_cache
    cache_dir = setup_compilation_cache()
    kind = devices[0].device_kind
    peaks = flops.peaks_for(kind) if devices[0].platform == "tpu" else None
    workdir = os.path.join(ROOT, "output", "chipbench", cell["name"])
    ctx = Context(
        workload=cell["name"], config=config, mix=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace),
        rehearse=args.rehearse, control=args.control,
        chips=int(cell["chips"]), devices=devices,
        root=ROOT, workdir=workdir, extra_overrides=extra, peaks=peaks,
        trace_dir=os.path.join(workdir + ".trace"))
    ctx.watch_compiles()
    if ctx.trace:
        import shutil
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    ctx.log({"cell": cell["name"], "config": cell["config"],
             "traffic": cell["traffic"], "driver": mix["driver"],
             "seed": args.seed, "seconds": args.seconds,
             "trace": args.trace, "compile_cache": cache_dir,
             "device_kind": kind, "chips": len(devices)})

    result = driver.run(ctx)
    if ctx.setup_s is None:
        raise Refused("the driver never opened its window")

    for name, value, limit, ok in result["checks"]:
        ctx.log({"compared": name, "value": value, "limit": limit,
                 "ok": bool(ok)})
    correct = all(ok for *_, ok in result["checks"])
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    if args.rehearse:
        device["rehearsal"] = "CPU, interpret mode, tiny sizes: no " \
            "number on this line is a measurement"
    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": device}
    values = dict(result["metrics"], setup_s=ctx.setup_s)
    ctx.log({"end_to_end": values})
    if not ctx.trace:
        for m in bench["end_to_end"]:
            if applies(m, cell, bench) and m["name"] in values:
                line["metrics"][m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"]}
    else:
        reduced = trace_reduce.reduce_trace(trace_reduce.load(
            trace_reduce.find_xplane(ctx.trace_dir), args.rehearse))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        run_data = dict(result["data"], trace=reduced, peaks=peaks,
                        chips=ctx.chips, config=config, mix=mix,
                        memory_peak_bytes=result["memory_peak_bytes"])
        for m in bench["per_layer"]:
            if not applies(m, cell, bench):
                continue
            spec = load_json("chipbench", "layer_metrics",
                             m["name"] + ".json")
            reader = load_module("readers", spec["reader"])
            got = reader.read(spec.get("params", {}), run_data)
            if got is None:
                continue
            value, note = got if isinstance(got, tuple) else (got, None)
            if note:
                ctx.log({"metric": m["name"], "note": note})
            line["metrics"][m["name"]] = {"value": value,
                                          "unit": m["unit"]}
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced["device_ops"]],
            "idle_gaps": reduced["idle_gaps"]}
    print(json.dumps(line, default=float), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as e:
        sys.stderr.write(f"chipbench: {e}\n")
        sys.exit(2)
