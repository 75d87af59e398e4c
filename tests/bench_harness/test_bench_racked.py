"""The placed fleet-replay cell, ``replay.racked_masters``, at small sizes
on the CPU: found by name with its metrics, traced with the placement's
span, correct on both backends, and refused by its controls (the deaf
window left out, the placement shifted by one node, the restarts left
out) and by the comparison it adds (restarts compared, shards failed
over)."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import kernel_bytes_placed, reference_placed, run as bench_run
from bench.drivers.placed_replay import Cell
from bench.kernel_bytes import delayed_window_bytes
from bench.kernel_bytes_placed import placed_window_bytes
from bench.traffic.racks import chained_placement, rack_restart_trace

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "replay.racked_masters"
#: 512 shards in 64 ranges on 64 nodes in 8 racks, 112 ticks, racks
#: restarting at ticks 24 and 64 (each restart's failover ends by 108);
#: M = 32 ticks, so the first rack's deaf window closes before the second
#: rack restarts and the failover (29 ticks on) falls inside each window,
#: as in the cell
SMALL = {
    "config": {"n_cells": 512, "n_nodes": 64, "n_racks": 8,
               "n_ranges": 64, "max_lease_ticks": 32},
    "traffic": {"n_ticks": 112, "restart_ticks": [24, 64]},
}


def _cell(seed, **config):
    over = {"config": {**SMALL["config"], **config},
            "traffic": SMALL["traffic"]}
    cell = bench_run.load_cell(NAME, over)
    return cell, Cell(cell, seed)


def test_the_cell_is_found_with_its_metrics():
    found = bench_run.load_cell(NAME)
    assert found["driver"] == "placed_replay"
    e2e, layer = bench_run.cell_metrics(NAME, BENCH)
    assert {m["name"] for m in e2e} == {"cell_ticks_per_s", "setup_s"}
    assert {m["name"] for m in layer} == {
        "kernel.hbm_share", "device.idle_share.replay", "engine.checks_ms",
        "engine.upload_ms", "engine.download_ms", "engine.placement_ms",
        "scan.outside_kernel_ms", "kernel.skip_share.replay",
        "setup.compile_s",
    }
    cfg = found["config_data"]
    assert cfg["reduced"] == [] and cfg["n_cells"] == 1_048_576
    assert cfg["n_nodes"] * cfg["n_ranges"] == cfg["n_cells"]


def test_chained_declustering_spreads_each_shard_over_three_racks():
    place = chained_placement(1024, 64, 64, 3)
    assert place.shape == (3, 1024)
    assert (place[1] == (place[0] + 1) % 64).all()
    assert (place[:, :16] == place[:, :1]).all()  # a range shares nodes
    racks = place % 8
    assert (racks[0] != racks[1]).all() and (racks[0] != racks[2]).all()


def test_the_traffic_restarts_two_racks_and_fails_their_shards_over():
    planes, place, restarts = rack_restart_trace(
        2**31 + 11, n_ticks=112, n_cells=512, n_acceptors=3,
        n_proposers=3, lease_ticks=28, renew=0.4, max_delay_ticks=1,
        p_drop=0.01, n_nodes=64, n_racks=8, n_ranges=64,
        restart_ticks=[24, 64], failover_after=29,
    )
    arst = planes["acc_restart"]
    assert arst.shape == (112, 64) and (planes["prop_restart"] == arst).all()
    assert [t for t, _ in restarts] == [24, 64]
    assert restarts[0][1] != restarts[1][1]
    for t, rack in restarts:
        assert set(np.flatnonzero(arst[t])) == set(range(rack, 64, 8))
    assert arst.sum() == 16
    # each hit shard's two other replicas try once, a round apart, after
    # the deaf window, and never on a renewal tick
    att = planes["attempts"]
    late = att[24 + 29:]
    tried = np.flatnonzero((late >= 0).any(axis=0))
    hit = ((place % 8)[:, None, :] == np.array([r for _, r in restarts])
           [None, :, None]).any(axis=(0, 1))
    assert set(tried) == set(np.flatnonzero(hit))
    for n in tried[:40]:
        ticks = np.flatnonzero(att[:, n] >= 0)
        ticks = ticks[ticks >= 24 + 29]
        assert (planes["extends"][ticks, n] < 0).all()
        assert np.diff(ticks)[0] == 5


def test_kernel_bytes_add_the_restart_table():
    from repro.lease_array import netplane, state

    assert placed_window_bytes(2, 3, 3, 3, True) == (
        delayed_window_bytes(2, 3, 3, 3, True) + 4 * 8 * 3 * 3
    )
    # the table's layout as the program has it
    assert kernel_bytes_placed.MAX_RESTARTS == state.MAX_RESTARTS
    assert kernel_bytes_placed.TABLE_FIELDS == netplane.RT_FIELDS


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_a_run_is_correct_on_both_backends(jax_settings, monkeypatch,
                                           backend):
    from repro.lease_array import engine

    monkeypatch.setattr(engine, "resolve_backend", lambda b=None: backend)
    result = bench_run.run_cell(NAME, 3000000029, 0.3, False,
                                require_chip=False, overrides=SMALL)
    assert result["correct"], result["checks"]
    checks = result["checks"]
    assert checks["restarted_cells_compared"]["value"] >= 1
    assert checks["failed_over_cells"]["value"] >= 1
    assert set(result["metrics"]) == {"cell_ticks_per_s", "setup_s"}


def test_a_traced_run_reports_the_placement_span(jax_settings):
    result = bench_run.run_cell(NAME, 4294967311, 0.3, True,
                                require_chip=False, overrides=SMALL)
    assert result["correct"]
    metrics = result["metrics"]
    assert metrics["engine.placement_ms"]["value"] > 0
    for name in ("engine.checks_ms", "engine.upload_ms",
                 "engine.download_ms", "setup.compile_s"):
        assert name in metrics


@pytest.mark.parametrize("control", ["no_deaf", "shifted", "no_restarts"])
def test_the_controls_are_not_correct(jax_settings, control):
    _, run = _cell(2718281831)
    run.step()
    checks = {c["name"]: c for c in run.check(control=control)}
    assert not all(c["ok"] for c in checks.values())
    assert checks["owner_mismatches"]["value"] > 0
    if control == "no_restarts":
        assert not checks["restarted_cells_compared"]["ok"]
        assert not checks["failed_over_cells"]["ok"]


def test_the_reference_control_default_is_the_configurations(jax_settings):
    cell, run = _cell(2718281831)
    run.step()
    by_default = {c["name"]: c for c in run.check(control=True)}
    assert cell["config_data"]["control"] == "no_deaf"
    assert by_default["owner_mismatches"]["value"] > 0
    with pytest.raises(ValueError, match="unknown control"):
        reference_placed.replay(run.planes, run.placement, n_nodes=64,
                                n_proposers=3, lease_ticks=28,
                                max_lease_ticks=32, round_ticks=5,
                                control="late")
