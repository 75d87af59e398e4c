"""Fleet replay: back-to-back ``LeaseArrayEngine.run_trace`` calls.

Set-up makes one scenario of the whole fleet from the seed (a start-up
election and steady renewals, ``bench/traffic/generate.py``) and replays it
once, which compiles the dispatch. Each unit of work in the window is what
a replay user's call is: a fresh engine, ``run_trace`` of the scenario
(its planes uploaded, the owners and owner counts brought back). Two
replays are kept for the check: one whose index is drawn from the seed
among the first four, and the last.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import reference
from bench.kernel_bytes import delayed_window_bytes
from bench.stats import rate
from bench.traffic.generate import startup_trace


def fleet_planes(cfg: dict, traffic: dict, seed: int, n_cells: int,
                 n_ticks: int, p_drop: float) -> dict:
    return startup_trace(
        seed, n_ticks=n_ticks, n_cells=n_cells,
        n_acceptors=cfg["n_acceptors"], n_proposers=cfg["n_proposers"],
        lease_ticks=cfg["lease_ticks"], renew=cfg["renew_fraction"],
        max_delay_ticks=cfg["max_delay_ticks"], p_drop=p_drop,
    )


def engine_kwargs(cfg: dict) -> dict:
    return dict(
        n_acceptors=cfg["n_acceptors"], n_proposers=cfg["n_proposers"],
        lease_ticks=cfg["lease_ticks"], round_ticks=cfg["round_ticks"],
    )


def build_scenario(cfg: dict, planes: dict):
    from repro.lease_array import Scenario

    return Scenario.build(
        n_cells=planes["attempts"].shape[1], n_acceptors=cfg["n_acceptors"],
        n_proposers=cfg["n_proposers"],
        **{k: v for k, v in planes.items() if v is not None},
    )


class Cell:
    def __init__(self, cell: dict, seed: int) -> None:
        from repro.lease_array import LeaseArrayEngine

        self.cfg = cfg = cell["config_data"]
        traffic = cell["traffic_data"]
        self.n_cells, self.n_ticks = cfg["n_cells"], traffic["n_ticks"]
        self.planes = fleet_planes(
            cfg, traffic, seed, self.n_cells, self.n_ticks, cfg["p_drop"]
        )
        self.scenario = build_scenario(cfg, self.planes)
        self._engine = lambda: LeaseArrayEngine(
            self.n_cells, **engine_kwargs(cfg)
        )
        self.kernel_bytes_per_call = delayed_window_bytes(
            self.n_ticks, self.n_cells, cfg["n_acceptors"],
            cfg["n_proposers"], extends=True,
        )
        self.sample = int(np.random.default_rng(seed).integers(0, 4))
        self.kept = {}
        self.attempted = self.failed = 0
        self._replay()  # compiles (or loads) the dispatch

    def _replay(self) -> tuple:
        with jax.profiler.TraceAnnotation("bench.run_trace"):
            return self._engine().run_trace(self.scenario)

    def step(self) -> None:
        owners, counts = self._replay()
        if self.attempted == self.sample:
            self.kept[self.attempted] = (owners, counts)
        self.kept["last"] = (self.attempted, owners, counts)
        self.attempted += 1

    def end_to_end(self, window_s: float) -> dict:
        return {"cell_ticks_per_s": rate(
            self.attempted, self.n_cells * self.n_ticks, window_s
        )}

    def free(self) -> None:
        self.scenario = None

    def check(self, control: bool = False) -> list:
        """Compare the kept replays with the reference; with ``control``
        the reference's control stands in the program's place."""
        cfg = self.cfg
        kw = dict(n_proposers=cfg["n_proposers"],
                  lease_ticks=cfg["lease_ticks"],
                  round_ticks=cfg["round_ticks"])
        ref_owners, ref_counts = reference.replay(self.planes, **kw)
        last_index, *last = self.kept.pop("last")
        runs = dict(self.kept)
        runs[last_index] = tuple(last)
        if control:
            runs = {0: reference.replay(self.planes, control=cfg["control"], **kw)}
        owner_miss = count_miss = 0
        max_count = 0
        for owners, counts in runs.values():
            o = int(np.count_nonzero(owners != ref_owners))
            c = int(np.count_nonzero(counts != ref_counts))
            owner_miss += o
            count_miss += c
            max_count = max(max_count, int(counts.max()))
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
        ]
