"""Placed fleet replay: back-to-back ``LeaseArrayEngine.run_trace`` calls
of a fleet whose replicas are placed on nodes, through which whole racks
restart.

Set-up makes one scenario from the seed (``bench/traffic/racks.py``: the
fleet replay's start-up election and renewals, the chained-declustering
placement, two rack restarts as ``[T, K]`` node rows and the failover
tries) and replays it once, which compiles the dispatch. Each unit of
work in the window is a fresh engine's ``run_trace`` of the scenario, as
in ``bench/drivers/replay.py``; two replays are kept for the check, which
compares them with ``bench/reference_placed.py`` and counts the restarts
they were given and the shards that failed over.
"""
from __future__ import annotations

import numpy as np

from bench import reference_placed
from bench.drivers import replay
from bench.drivers.replay import engine_kwargs
from bench.kernel_bytes_placed import placed_window_bytes
from bench.traffic.racks import rack_restart_trace


def placed_planes(cfg: dict, traffic: dict, seed: int) -> tuple:
    return rack_restart_trace(
        seed, n_ticks=traffic["n_ticks"], n_cells=cfg["n_cells"],
        n_acceptors=cfg["n_acceptors"], n_proposers=cfg["n_proposers"],
        lease_ticks=cfg["lease_ticks"], renew=cfg["renew_fraction"],
        max_delay_ticks=cfg["max_delay_ticks"], p_drop=cfg["p_drop"],
        n_nodes=cfg["n_nodes"], n_racks=cfg["n_racks"],
        n_ranges=cfg["n_ranges"], restart_ticks=traffic["restart_ticks"],
        failover_after=traffic["failover_after"],
    )


class Cell(replay.Cell):
    def __init__(self, cell: dict, seed: int) -> None:
        from repro.lease_array import LeaseArrayEngine, Scenario

        self.cfg = cfg = cell["config_data"]
        traffic = cell["traffic_data"]
        self.n_cells, self.n_ticks = cfg["n_cells"], traffic["n_ticks"]
        self.planes, self.placement, self.restarts = placed_planes(
            cfg, traffic, seed
        )
        self.scenario = Scenario.build(
            n_cells=self.n_cells, n_acceptors=cfg["n_acceptors"],
            n_proposers=cfg["n_proposers"], n_nodes=cfg["n_nodes"],
            placement=self.placement,
            **{k: v for k, v in self.planes.items() if v is not None},
        )
        self._engine = lambda: LeaseArrayEngine(
            self.n_cells, max_lease_ticks=cfg["max_lease_ticks"],
            **engine_kwargs(cfg),
        )
        self.kernel_bytes_per_call = placed_window_bytes(
            self.n_ticks, self.n_cells, cfg["n_acceptors"],
            cfg["n_proposers"], extends=True,
        )
        self.sample = int(np.random.default_rng(seed).integers(0, 4))
        self.kept = {}
        self.attempted = self.failed = 0
        self._replay()  # compiles (or loads) the dispatch

    def check(self, control=False) -> list:
        """Compare the kept replays with the reference. With ``control``
        a control of the reference (the configuration's, or the one
        named) stands in the program's place."""
        cfg = self.cfg
        kw = dict(n_nodes=cfg["n_nodes"], n_proposers=cfg["n_proposers"],
                  lease_ticks=cfg["lease_ticks"],
                  max_lease_ticks=cfg["max_lease_ticks"],
                  round_ticks=cfg["round_ticks"])
        ref_owners, ref_counts = reference_placed.replay(
            self.planes, self.placement, **kw
        )
        last_index, *last = self.kept.pop("last")
        runs = dict(self.kept)
        runs[last_index] = tuple(last)
        planes = self.planes
        if control:
            name = cfg["control"] if control is True else control
            runs = {0: reference_placed.replay(
                self.planes, self.placement, control=name, **kw
            )}
            if name == "no_restarts":
                planes = {k: v for k, v in planes.items()
                          if k not in ("acc_restart", "prop_restart")}
        restarted = reference_placed.restarted_cells(
            planes, self.placement, cfg["n_nodes"]
        )
        owner_miss = count_miss = failed_over = 0
        max_count = 0
        for owners, counts in runs.values():
            o = int(np.count_nonzero(owners != ref_owners))
            c = int(np.count_nonzero(counts != ref_counts))
            owner_miss += o
            count_miss += c
            max_count = max(max_count, int(counts.max()))
            failed_over += reference_placed.failed_over(
                owners, self.placement, self.restarts, cfg["n_racks"]
            )
            self.failed += bool(o or c or counts.max() > 1)
        limit = cfg["guarantees"]["owners_per_cell_tick_max"]
        return [
            {"name": "replays_compared", "value": len(runs), "limit": 1,
             "ok": len(runs) >= 1},
            {"name": "owner_mismatches", "value": owner_miss, "limit": 0,
             "ok": owner_miss == 0},
            {"name": "count_mismatches", "value": count_miss, "limit": 0,
             "ok": count_miss == 0},
            {"name": "max_owner_count", "value": max_count, "limit": limit,
             "ok": max_count <= limit},
            {"name": "restarted_cells_compared",
             "value": restarted * len(runs), "limit": 1,
             "ok": restarted * len(runs) >= 1},
            {"name": "failed_over_cells", "value": failed_over, "limit": 1,
             "ok": failed_over >= 1},
        ]
