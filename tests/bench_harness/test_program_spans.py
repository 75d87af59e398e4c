"""The reader of the program's own ``lease.*`` spans
(``bench/program_spans.py``) and the per-layer metrics built on it:
nesting by time, self time, idle stretches put down to the span over
them, and the metrics a traced run reports."""
import gzip
import json
from pathlib import Path

import pytest

from bench import program_spans, run as bench_run
from bench.program_spans import Span, idle_by_span, nest, self_s
from bench.trace_reduce import Reduced
from bench_small import SMALL

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MS = 1e6  # ns
NEW = {m["name"]: m["workloads"] for m in BENCH["per_layer"]
       if m["source"] == "program_span" and m["name"] != "setup.compile_s"}


def _span(name, start_ms, end_ms, **stats):
    return Span(name, start_ms * MS, end_ms * MS, stats)


def _synthetic():
    # two directory ticks, 0-50 and 50-100 ms; the first asks the engine
    # for its ticks left (2-10, waiting 4-8) and steps it (30-48, waiting
    # 40-46); the device runs 8-9, 41-45 and 60-70
    spans = nest([
        _span("lease.dir.tick", 0, 50, t=0, extends=3),
        _span("lease.ticks_left", 2, 10),
        _span("lease.wait", 4, 8),
        _span("lease.dir.renew", 12, 28, candidates=5, extends=3),
        _span("lease.step", 30, 48, windows=4, skipped=1),
        _span("lease.wait", 40, 46),
        _span("lease.dir.tick", 50, 100, t=1, extends=0),
    ])
    ops = {"/device:TPU:0": [("fusion", 8 * MS, 9 * MS),
                             ("kernel", 41 * MS, 45 * MS),
                             ("fusion", 60 * MS, 70 * MS)]}
    trace = Reduced(ops, [("bench.window", 0.0, 100 * MS)])
    return spans, trace


def test_nesting_by_time():
    spans, _ = _synthetic()
    tick, left, wait1, renew, step, wait2, tick2 = spans
    assert tick.parent is None and tick2.parent is None
    assert [c.name for c in tick.children] == [
        "lease.ticks_left", "lease.dir.renew", "lease.step"]
    assert wait1.parent is left and wait2.parent is step
    assert renew.stats == {"candidates": 5, "extends": 3}
    assert tick.named("lease.step") == [step]


def test_self_time_leaves_out_the_children():
    spans, _ = _synthetic()
    tick, left, _, renew, step, _, tick2 = spans
    assert self_s(tick) == pytest.approx(0.050 - 0.008 - 0.016 - 0.018)
    assert self_s(left) == pytest.approx(0.004)
    assert self_s(step) == pytest.approx(0.012)
    assert self_s(renew) == renew.seconds == pytest.approx(0.016)
    assert self_s(tick2) == pytest.approx(0.050)


def test_idle_stretches_go_to_the_innermost_span_over_their_middle():
    spans, trace = _synthetic()
    idle = idle_by_span(trace, spans)
    # 0-8 (middle 4: the wait at 4-8), 9-41 (middle 25: the renewal),
    # 45-60 (middle 52.5: the second tick), 70-100 (middle 85: the same)
    assert idle == pytest.approx({"lease.wait": 0.008,
                                  "lease.dir.renew": 0.032,
                                  "lease.dir.tick": 0.045})
    assert sum(idle.values()) == pytest.approx(trace.window_s - trace.busy_s)
    # a stretch whose middle no span covers goes under None
    late = Reduced({"/device:TPU:0": [("fusion", 0.0, 1 * MS)]},
                   [("bench.window", 0.0, 300 * MS)])
    assert idle_by_span(late, spans) == pytest.approx({None: 0.299})


def test_skip_share_and_per_call_means():
    spans, _ = _synthetic()
    assert program_spans.skip_share(spans, "lease.step") == 25.0
    assert program_spans.skip_share(spans, "lease.run_trace") is None
    assert program_spans.mean_child_ms(
        spans, "lease.dir.tick", "lease.ticks_left") == pytest.approx(4.0)
    assert program_spans.mean_child_ms(
        spans, "lease.run_trace", "lease.validate") is None


def test_a_trace_without_program_spans_reads_none(tmp_path, monkeypatch):
    # the trace recorded before the program had spans of its own
    cell = "replay.keyspace_master"
    trace = tmp_path / cell / "replay.xplane.pb"
    trace.parent.mkdir()
    trace.write_bytes(gzip.decompress(
        (ROOT / "bench" / "fixtures" / "replay_trace.xplane.pb.gz").read_bytes()
    ))
    monkeypatch.setattr(bench_run, "TRACE_DIR", tmp_path)
    assert program_spans.spans(cell) == []
    ctx = {"cell": {"name": cell}}
    for name in NEW:
        assert bench_run.load_metric_reader(name)(ctx) is None, name


# interpret mode runs the window kernel, whose counters the skip shares
# read; the jnp scan runs none, and those two read nothing
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("cell", sorted(SMALL.keys() - {"sweep.keyspace_master"}))
def test_a_traced_run_reports_every_metric_whose_spans_exist(
        jax_settings, monkeypatch, cell, backend):
    from repro.lease_array import engine

    monkeypatch.setattr(engine, "resolve_backend", lambda b=None: backend)
    result = bench_run.run_cell(cell, 4294967311, 0.3, True,
                                require_chip=False, overrides=SMALL[cell])
    assert result["correct"]
    want = {n for n, cells in NEW.items() if cell in cells}
    if backend == "jnp":
        want = {n for n in want if not n.startswith("kernel.skip_share")}
    got = {n for n in result["metrics"] if n in NEW}
    assert got == want
    assert all(result["metrics"][n]["value"] >= 0 for n in got)


# Short traced runs of each cell at its SMALL size on one TPU v5e ("TPU
# v5 lite"): `run_cell(cell, seed, seconds, True, overrides=SMALL[cell])`
# with seed 3000000013 for 0.3 s (replay: 22 run_trace calls) and seed
# 3000000017 for 0.6 s (directory: 40 ticks). Per cell: the fixture, the
# outer span of one unit of work, its children, and the units.
CHIP_RUNS = {
    "replay.keyspace_master": (
        "replay_spans", "lease.run_trace", ["lease.validate", "lease.upload",
        "lease.dispatch", "lease.wait", "lease.download"], 22),
    "directory.chubby_directory": (
        "directory_spans", "lease.dir.tick", ["lease.ticks_left",
        "lease.dir.shed", "lease.dir.renew", "lease.dir.assign",
        "lease.dir.make_tick", "lease.step"], 40),
}


@pytest.fixture(scope="module")
def chip_traces(tmp_path_factory):
    out = {}
    for cell, (stem, *_) in CHIP_RUNS.items():
        path = tmp_path_factory.mktemp("trace") / f"{stem}.xplane.pb"
        path.write_bytes(gzip.decompress(
            (ROOT / "bench" / "fixtures" / f"{stem}.xplane.pb.gz").read_bytes()
        ))
        out[cell] = path
    return out


@pytest.mark.parametrize("cell", sorted(CHIP_RUNS))
def test_chip_trace_planes_lines_and_kernel_name(chip_traces, cell):
    import re
    import warnings

    from jax.profiler import ProfileData

    from bench.kernel_bytes import KERNEL_EVENT
    from bench.trace_reduce import reduce_trace

    path, units = chip_traces[cell], CHIP_RUNS[cell][3]
    data = ProfileData.from_file(str(path))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        lines = {(p.name, ln.name) for p in data.planes for ln in p.lines
                 if any(e.name.startswith("lease.") for e in ln.events)}
    # every span is on the host plane's main Python thread
    assert lines == {("/host:CPU", "python3")}
    trace = reduce_trace(path)
    assert trace.devices == ["/device:TPU:0"]
    # the kernel's name is the HLO name of its custom call, which starts
    # the device event's name; the custom call target still matches
    kernels = [n for n, _ in trace.by_name() if re.search(KERNEL_EVENT, n)]
    assert kernels and all(n.startswith("%lease_window_delayed.1 = ")
                           for n in kernels)
    assert trace.op_count(KERNEL_EVENT) == units  # one launch per unit


@pytest.mark.parametrize("cell", sorted(CHIP_RUNS))
def test_chip_trace_span_tree_counters_and_idle(chip_traces, cell):
    from bench.trace_reduce import reduce_trace

    path = chip_traces[cell]
    _, outer, children, units = CHIP_RUNS[cell]
    spans = program_spans.read_file(path)
    roots = {s.name for s in spans if s.parent is None}
    assert roots == {"lease.init", outer}
    calls = program_spans.named(spans, outer)
    assert len(calls) == units
    assert all([c.name for c in s.children] == children for s in calls)
    # 1,024 cells are two blocks of 512: a replay's 48 ticks make three
    # 16-tick windows, a directory step one window
    steps = program_spans.named(spans, "lease.run_trace" if outer ==
                                "lease.run_trace" else "lease.step")
    assert {s.stats["windows"] for s in steps} == (
        {6} if outer == "lease.run_trace" else {2})
    assert all(0 <= s.stats["skipped"] <= s.stats["windows"] for s in steps)
    trace = reduce_trace(path)
    idle = idle_by_span(trace, spans)
    assert sum(idle.values()) == pytest.approx(trace.window_s - trace.busy_s)
    assert idle.get(None, 0.0) <= 0.01 * sum(idle.values())
