"""The benchmark's harness: its file keeps to the contract's characters,
cells are found by name, the end-to-end arithmetic, the byte count of the
window kernel, and a run that refuses to start off a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import run as bench_run
from bench.kernel_bytes import delayed_window_bytes
from bench.stats import rate, tail
from bench_small import SMALL

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and not re.search(
        r"[\n\t]", text
    )


def test_names_units_and_texts_keep_to_the_allowed_characters():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    names += [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for group in (metrics, BENCH["configs"], BENCH["workloads"]):
        assert len({m["name"] for m in group}) == len(group)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    texts = [w["why"] for w in BENCH["workloads"]]
    texts += [c["source"] for c in BENCH["configs"]]
    texts += [m["layer"] for m in BENCH["per_layer"]]
    texts += BENCH["command"]
    assert all(_line(t) for t in texts), texts


def test_benchmark_file_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_every_cell_is_found_by_name_and_reports_its_metrics(cell):
    found = bench_run.load_cell(cell["name"])
    for key in ("config", "traffic", "chips", "why"):
        assert found[key] == cell[key], key
    assert (ROOT / "bench" / "drivers" / f"{found['driver']}.py").is_file()
    e2e, layer = bench_run.cell_metrics(cell["name"], BENCH)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert layer


def test_a_dropped_in_cell_file_is_found_and_runs(jax_settings):
    name = f"replay.dropped_in_{os.getpid()}"
    path = ROOT / "bench" / "workloads" / f"{name}.json"
    shutil.copy(ROOT / "bench" / "workloads" / "replay.keyspace_master.json",
                path)
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": name})
    for m in bench["end_to_end"]:
        if "workloads" in m and "replay.keyspace_master" in m["workloads"]:
            m["workloads"].append(name)
    try:
        result = bench_run.run_cell(
            name, 5, 0.2, False, require_chip=False,
            overrides=SMALL["replay.keyspace_master"], bench=bench,
        )
    finally:
        path.unlink()
    assert result["correct"]
    assert set(result["metrics"]) == {"cell_ticks_per_s", "setup_s"}


def test_rate_is_all_work_over_the_whole_window():
    assert rate(7, 1_048_576 * 256, 30.5) == pytest.approx(
        7 * 1_048_576 * 256 / 30.5
    )


def test_tail_is_over_every_tick():
    ticks = np.r_[np.full(94, 10.0), np.full(6, 300.0)]
    assert tail(ticks, 95) == pytest.approx(np.percentile(ticks, 95))
    assert tail(ticks, 95) == 300.0  # 6% of slow ticks set the p95
    assert tail(np.r_[np.full(96, 10.0), np.full(4, 300.0)], 95) == 10.0
    with pytest.raises(ValueError):
        tail([], 95)


def test_window_kernel_bytes_by_hand():
    # T=2 ticks, N=3 cells, A=3 acceptors, P=2 proposers, with extends:
    # cell streams 3*2*3 = 18; tick streams 2*(3+3+2+2*3) = 28;
    # outputs 2*2*3 = 12; state (2*3+2 + 6*3+6) * 3 = 96, in and out 192
    assert delayed_window_bytes(2, 3, 3, 2, extends=True) == 4 * (
        18 + 28 + 12 + 192
    )
    assert delayed_window_bytes(2, 3, 3, 2, extends=False) == 4 * (
        12 + 28 + 12 + 192
    )


def _cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "replay.keyspace_master", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_refuses_to_run_off_a_tpu():
    proc = _cli(ROOT)
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert "correct" not in proc.stdout


def test_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    proc = _cli(tmp_path)
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert "correct" not in proc.stdout
