"""Run one benchmark cell once and print its result as one JSON line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``. It names its configuration
(``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``) and its driver
(``bench/drivers/<driver>.py``); a per-layer metric ``<m>`` of
``BENCHMARK.json`` is read by ``bench/metrics/<m>.py``. So a new cell,
traffic mix or metric is a new file, found by its name.

A run: set-up (inputs from the seed, the programs warmed and compiled),
then a closed loop of the driver's unit of work for ``--seconds``
seconds, then the check of what the window produced against the plain
reference (``bench/reference.py``). With ``--trace 1`` the window runs
under the JAX profiler and the per-layer metrics are read from the trace
and the driver's host timings; otherwise the end-to-end metrics are
printed. The run refuses to start unless JAX's first device is a TPU and
there are as many as the cell asks for.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import resource
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "bench"
#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class CompileClock:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reading(self) -> tuple:
        return self.compiles, self.compile_s, self.cache_hits


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, overrides: dict | None = None) -> dict:
    """The cell ``name`` with its configuration and traffic mix loaded;
    ``overrides`` ({"config": {...}, "traffic": {...}}) replace keys, for
    tests at small sizes."""
    cell = load_json(HERE / "workloads" / f"{name}.json")
    cell["name"] = name
    cell["config_data"] = load_json(HERE / "configs" / f"{cell['config']}.json")
    cell["traffic_data"] = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    for key, extra in (overrides or {}).items():
        cell[f"{key}_data"].update(extra)
    return cell


def cell_metrics(name: str, bench: dict) -> tuple[list, list]:
    """The end-to-end and per-layer metrics of ``BENCHMARK.json`` that the
    cell ``name`` reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return e2e, layer


def load_metric_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", HERE / "metrics" / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def require_chips(n_chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench: JAX's first device is {devices[0].platform!r}, not a "
            f"TPU; no run was made"
        )
    if len(devices) < n_chips:
        raise SystemExit(
            f"bench: the cell needs {n_chips} TPU chips; JAX sees "
            f"{len(devices)}"
        )
    return devices


def enable_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peak_bytes(devices) -> int:
    return max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    )


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, overrides: dict | None = None,
             bench: dict | None = None, control: bool = False) -> dict:
    """One run of one cell; returns the result object that ``main``
    prints. ``require_chip=False`` and ``overrides`` are for tests;
    ``control=True`` checks the reference's control in the program's
    place (``bench/control.py``)."""
    t_start = time.perf_counter()
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    cell = load_cell(workload, overrides)
    e2e, per_layer = cell_metrics(workload, bench)
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    import repro.lease_array  # noqa: F401  (the system under test)

    enable_compile_cache()
    devices = require_chips(cell["chips"]) if require_chip else jax.devices()
    clock = CompileClock()
    driver = importlib.import_module(f"bench.drivers.{cell['driver']}")
    with jax.profiler.TraceAnnotation("bench.setup"):
        run = driver.Cell(cell, seed)
    setup_compiles = clock.reading()
    setup_s = time.perf_counter() - t_start

    trace_path = None
    if trace:
        trace_path = TRACE_DIR / workload
        shutil.rmtree(trace_path, ignore_errors=True)
        # host spans and device ops only: no Python call tracing, no HLO
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_path), profiler_options=options)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t0 < seconds:
            run.step()
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    window_compiles = [b - a for a, b in zip(setup_compiles, clock.reading())]
    memory_peak = peak_bytes(devices)
    metrics_e2e = run.end_to_end(window_s)
    metrics_e2e["setup_s"] = setup_s

    run.free()
    t_check = time.perf_counter()
    checks = run.check(control=control)
    check_s = time.perf_counter() - t_check
    correct = all(c["ok"] for c in checks)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(memory_peak),
    }
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed}
    if trace:
        from bench.trace_reduce import reduce_trace

        reduced = reduce_trace(trace_path)
        ctx = {
            "trace": reduced, "run": run, "cell": cell,
            "device_kind": devices[0].device_kind,
            "setup_compile_s": setup_compiles[1],
        }
        metrics = {}
        for m in per_layer:
            value = load_metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced.devices:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
        result["metrics"] = metrics
        result["breakdown"] = reduced.breakdown()
    else:
        result["metrics"] = {
            m["name"]: {"value": metrics_e2e[m["name"]], "unit": m["unit"]}
            for m in e2e
        }
    result["device"] = device
    result["window"] = {
        "seconds": window_s, "units": run.attempted, "setup_s": setup_s,
        "check_s": check_s,
        "host_peak_bytes": 1024 * resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss,
        "compiles": window_compiles[0], "cache_loads": window_compiles[2],
    }
    result["checks"] = {
        c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks
    }
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    w = result["window"]
    print(
        f"window: {w['seconds']:.3f} s, {w['units']} units, "
        f"{w['compiles']} compiles and {w['cache_loads']} cache loads inside",
        file=sys.stderr,
    )
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
