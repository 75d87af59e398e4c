"""The reduction from a profiler trace to per-layer numbers
(``bench/trace_reduce.py``): busy union, idle share, kernel events by
name and idle gaps named by the host span that covers them."""
import gzip
from pathlib import Path

import pytest

from bench.kernel_bytes import KERNEL_EVENT
from bench.trace_reduce import Reduced, _union, reduce_trace

MS = 1e6  # ns


def _synthetic():
    # a 100 ms window; device ops at 10-30 (kernel), 20-40 (overlap),
    # 60-70; host spans: a tick 0-50 with an engine step 5-45 inside,
    # and a tick 50-100
    ops = {"/device:TPU:0": [
        ('%run.1 = custom-call(), custom_call_target="tpu_custom_call"', 10 * MS, 30 * MS),
        ("copy.1", 20 * MS, 40 * MS),
        ("fusion.2", 60 * MS, 70 * MS),
    ], "/device:TPU:1": []}
    spans = [
        ("bench.window", 0.0, 100 * MS),
        ("bench.dir_tick", 0.0, 50 * MS),
        ("bench.engine_step", 5 * MS, 45 * MS),
        ("bench.dir_tick", 50 * MS, 100 * MS),
    ]
    return Reduced(ops, spans)


def test_union_merges_and_clips():
    assert _union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == [[1, 4], [5, 10]]


def test_busy_union_and_idle_share():
    r = _synthetic()
    assert r.devices == ["/device:TPU:0"]  # a device with no ops is unused
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.040)  # 10-40 and 60-70
    assert r.idle_share == pytest.approx(0.6)


def test_kernel_events_by_name():
    r = _synthetic()
    assert r.op_seconds(KERNEL_EVENT) == pytest.approx(0.020)
    assert r.op_count(KERNEL_EVENT) == 1
    assert r.op_seconds() == pytest.approx(0.050)
    top = dict(r.by_name()[:2])
    assert top["copy.1"] == pytest.approx(0.020)
    assert len(top) == 2 and sum(top.values()) == pytest.approx(0.040)


def test_idle_gaps_named_by_innermost_host_span():
    gaps = _synthetic().idle_gaps()
    # 70-100 in the second tick; 40-60 has its middle at 50, where the
    # second tick starts; 0-10 has its middle at 5, where the engine
    # step inside the first tick starts
    assert [g[0] for g in gaps] == [
        "bench.dir_tick", "bench.dir_tick", "bench.engine_step"
    ]
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.020, 0.010])
    assert sum(g[1] for g in gaps) == pytest.approx(0.060)


# A trace recorded on one TPU v5e ("TPU v5 lite") by
# `python3 -m bench.run --workload replay.keyspace_master --seed 1002
# --seconds 8 --trace 1`: five run_trace replays in a 9.16 s window.
FIXTURE = Path(__file__).resolve().parents[2] / "bench" / "fixtures" / (
    "replay_trace.xplane.pb.gz"
)


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "replay.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return reduce_trace(path)


def test_chip_trace_busy_union_and_idle_share(chip_trace):
    r = chip_trace
    assert r.devices == ["/device:TPU:0"]
    assert r.window_s == pytest.approx(9.158784374, rel=1e-9)
    assert r.busy_s == pytest.approx(1.239202546, rel=1e-9)
    assert r.busy_s <= r.op_seconds()  # overlapping ops count once
    assert r.idle_share == pytest.approx(1 - 1.239202546 / 9.158784374)


def test_chip_trace_kernel_events_by_name(chip_trace):
    # one window-kernel custom call per replay
    assert chip_trace.op_count(KERNEL_EVENT) == 5
    assert chip_trace.op_seconds(KERNEL_EVENT) == pytest.approx(0.9717, abs=1e-3)
    assert chip_trace.by_name()[0][0].startswith("%run.1 = ")


def test_chip_trace_gaps_named_by_host_spans(chip_trace):
    gaps = chip_trace.idle_gaps()
    assert {name for name, _ in gaps[:10]} == {"bench.run_trace"}
    assert sum(s for _, s in gaps) == pytest.approx(
        chip_trace.window_s - chip_trace.busy_s, rel=1e-6
    )
    spans = [n for n, _, _ in chip_trace.spans]
    assert spans.count("bench.run_trace") == 5
