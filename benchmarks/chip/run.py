#!/usr/bin/env python3
"""Measure one cell of BENCHMARK.json on the machine this runs on.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>
    python3 benchmarks/chip/run.py --selfcheck        # no chip needed
    JAX_PLATFORMS=cpu python3 benchmarks/chip/run.py --rehearse ...

Everything that belongs to one cell, configuration, driver or per-layer
metric lives in a file of its own, found by the name BENCHMARK.json
gives; this file names none of them (README.md). One process, no
child. Without `--rehearse` a run needs a TPU and as many chips as the
cell asks for, or it exits 1 and prints no result.

The last line of standard output is the result: one JSON object with
`correct`, `attempted`, `failed`, `metrics`, `device` (with `--trace 1`
also `breakdown`) and, last, `compared`: each number that decided
`correct` beside its limit. The same numbers end standard error.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

# libtpu writes its logs under /tmp unless told otherwise; a run writes
# nothing outside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)  # the program under test, as a user imports it


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """The module `<kind>/<name>.py` of this directory."""
    path = os.path.join(HERE, *kind.split("/"), name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_%s_%s" % (kind.replace("/", "_"), name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Context(object):
    """What a driver and a reader get from the harness."""

    def __init__(self, args, manifest, cell, workload, config, devices,
                 device, peaks):
        self.args, self.cell, self.devices = args, cell, devices
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.manifest = manifest
        self.workload = workload
        self.config = config
        self.device = device
        self.peaks = peaks
        self.t_start = _T_START
        self.log = log
        self.load_module = load_module

    # --- the profiler, for the drivers -----------------------------------
    def trace_dir(self):
        return os.path.join(ROOT, ".chipbench_trace")

    def start_trace(self):
        import jax

        shutil.rmtree(self.trace_dir(), ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans come from annotations
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir(), profiler_options=opts)

    def stop_trace(self):
        import jax

        jax.profiler.stop_trace()

    def span(self, name):
        """A host span in the profiler's own trace (a no-op cost when
        no trace is running)."""
        import jax

        return jax.profiler.TraceAnnotation(name)


def pick_device(chips, rehearse):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:  # the backend's own start-up failure
        log("no device: %s: %s" % (type(e).__name__, e))
        return None
    dev = devices[0]
    if dev.platform != "tpu" and not rehearse:
        log("no TPU: JAX found %d x %s (%s); --rehearse is the only CPU "
            "mode" % (len(devices), dev.device_kind, dev.platform))
        return None
    if len(devices) < chips:
        log("the cell asks for %d chips, JAX found %d" % (chips, len(devices)))
        return None
    return devices[:chips]


def place_compile_cache():
    """JAX's persistent cache: where JAX_COMPILATION_CACHE_DIR says if
    set, else a fixed directory in the checkout (the path is part of
    every key). Every program is kept, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def memory_peak(devices):
    """Peak bytes on the fullest chip. The TPU runtime keeps a running
    program's temporaries in a region it counts as `reserved`, apart
    from the buffers `in_use`: the peak of device memory is both."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}  # None on the CPU backend
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


def prepare(argv=None):
    """Parse the arguments, find the cell's files and the device
    -> (context, driver), or (None, None) where a run cannot start."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes from the rehearse/ files on whatever "
                         "JAX finds; the result says so and is never recorded")
    ap.add_argument("--selfcheck", action="store_true",
                    help="check the trace reduction and the cost functions "
                         "against the recorded trace and hand counts")
    args = ap.parse_args(argv)
    if args.selfcheck:
        sys.exit(load_module("selfcheck", "check").main())
    if not args.workload:
        ap.error("--workload is required")

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        log("no cell %r in BENCHMARK.json" % args.workload)
        return None, None
    cell = cells[args.workload]
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    sub = ("rehearse",) if args.rehearse else ()
    workload = load_json("workloads", *sub, cell["name"] + ".json")
    config = load_json("configs", *sub, cell["config"] + ".json")
    workload["traffic"] = load_json("traffic", *sub, cell["traffic"] + ".json")

    devices = pick_device(int(cell["chips"]), args.rehearse)
    if devices is None:
        return None, None
    from lib.peaks import device_peaks

    kind = devices[0].device_kind
    # a rehearsal on a CPU has no peaks: shares of a peak are not read
    peaks = device_peaks(kind) if devices[0].platform == "tpu" else None
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices)}
    if devices[0].platform == "tpu":  # a CPU rehearsal keeps no cache
        cache_dir = place_compile_cache()
        log("device %r, compile cache %s (%d entries)"
            % (device, cache_dir, len(glob.glob(cache_dir + "/*"))))

    ctx = Context(args, manifest, cell, workload, config, devices, device,
                  peaks)
    return ctx, load_module("drivers", workload["driver"]).Driver(ctx)


def drive(argv, tweak=None):
    """A whole run up to the check, for the tools and tests beside this
    file: set-up, window, release -> (driver, what the window returned),
    or (None, None). `tweak(ctx, driver)` runs before set-up."""
    ctx, driver = prepare(argv)
    if driver is None:
        return None, None
    if tweak is not None:
        tweak(ctx, driver)
    driver.setup()
    run = driver.window()
    driver.release()
    gc.collect()
    return driver, run


def main(argv=None):
    ctx, driver = prepare(argv)
    if driver is None:
        return 1
    args, cell, manifest = ctx.args, ctx.cell, ctx.manifest
    devices, device = ctx.devices, ctx.device
    driver.setup()
    run = driver.window()  # opens the window itself: see Driver.window
    device["memory_peak_bytes"] = memory_peak(devices)
    driver.release()
    gc.collect()
    t0 = time.monotonic()
    correct, compared = driver.check()
    log("check took %.1f s" % (time.monotonic() - t0))

    wanted = manifest["per_layer"] if args.trace else manifest["end_to_end"]
    wanted = [m for m in wanted
              if cell["name"] in m.get("workloads", [cell["name"]])]
    metrics, breakdown = {}, None
    if args.trace:
        from lib import xplane

        t0 = time.monotonic()
        try:
            trace = xplane.reduce(ctx.trace_dir(), len(devices),
                                  run.get("traced_span"))
        except RuntimeError:
            if not args.rehearse:  # a CPU has no device plane to read
                raise
            trace = None
        if trace is not None:
            device["busy_s"] = trace.busy_s
            if trace.window_s is not None:  # the span's own length
                run["traced_window_s"] = trace.window_s
            device["window_s"] = run["traced_window_s"]
            breakdown = trace.breakdown()
        for m in wanted:
            spec = load_json("layer_metrics", m["name"] + ".json")
            reader = load_module("layer_metrics/readers", spec["reader"])
            value = reader.read(trace, run, spec.get("args", {}), ctx)
            if value is not None:  # nothing to read: left out of the line
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        shutil.rmtree(ctx.trace_dir(), ignore_errors=True)
        log("trace reduction took %.1f s" % (time.monotonic() - t0))
    else:
        for m in wanted:
            metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                  "unit": m["unit"]}

    result = {"correct": bool(correct), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["rehearsal"] = True
    result["notes"] = run.get("notes", {})
    result["compared"] = compared
    sys.stdout.flush()
    log("compared: " + json.dumps(compared))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
