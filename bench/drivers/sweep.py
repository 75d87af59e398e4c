"""Summary sweeps: back-to-back ``LeaseArrayEngine.sweep`` calls over one
stacked batch of scenarios, ``collect="summary"`` with the §4 check on.

Set-up makes the batch from the seed: scenario ``i`` has its own seed,
and the drop rates of the traffic mix are dealt out in a seeded order so
that each takes the same share of every batch. It stacks the batch once
and sweeps it once, which compiles the dispatch. Every call in the
window is compared with the reference's summaries.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import numpy as np

from bench import reference
from bench.drivers.replay import build_scenario, engine_kwargs, fleet_planes
from bench.kernel_bytes import delayed_window_bytes
from bench.stats import rate


class SweepSummary(NamedTuple):
    """The fields of the program's sweep result that are compared."""

    max_owner_count: np.ndarray
    owned_frac: np.ndarray
    final_owners: np.ndarray


class Cell:
    def __init__(self, cell: dict, seed: int) -> None:
        from repro.lease_array import LeaseArrayEngine, Scenario

        self.cfg = cfg = cell["config_data"]
        traffic = cell["traffic_data"]
        B = traffic["n_scenarios"]
        self.n_cells = traffic["cells_per_scenario"]
        self.n_ticks = traffic["n_ticks"]
        choices = traffic["drop_choices"]
        rng = np.random.default_rng(seed)
        drops = rng.permutation(np.resize(np.asarray(choices), B))
        seeds = rng.integers(0, 2**62, B)
        self.planes = [
            fleet_planes(cfg, traffic, int(s), self.n_cells, self.n_ticks,
                         float(d))
            for s, d in zip(seeds, drops)
        ]
        self.stacked = Scenario.stack(
            [build_scenario(cfg, p) for p in self.planes]
        )
        self.engine = LeaseArrayEngine(self.n_cells, **engine_kwargs(cfg))
        self.kernel_bytes_per_call = B * delayed_window_bytes(
            self.n_ticks, self.n_cells, cfg["n_acceptors"], cfg["n_proposers"],
            extends=True,
        )
        self.results = []
        self.attempted = self.failed = 0
        self._sweep()  # compiles (or loads) the dispatch
        self.results.clear()

    def _sweep(self) -> None:
        with jax.profiler.TraceAnnotation("bench.sweep"):
            try:
                res = self.engine.sweep(self.stacked, collect="summary",
                                        verify=True)
            except AssertionError:  # the sweep's own §4 check fired
                res = None
        self.results.append(res)

    def step(self) -> None:
        self._sweep()
        self.attempted += 1

    def end_to_end(self, window_s: float) -> dict:
        return {"cell_ticks_per_s": rate(
            self.attempted, len(self.planes) * self.n_cells * self.n_ticks,
            window_s,
        )}

    def free(self) -> None:
        self.stacked = self.engine = None

    def check(self, control: bool = False) -> list:
        """Compare every sweep of the window with the reference; with
        ``control`` the reference's control stands in the program's
        place."""
        cfg = self.cfg
        kw = dict(n_proposers=cfg["n_proposers"],
                  lease_ticks=cfg["lease_ticks"],
                  round_ticks=cfg["round_ticks"])
        refs = reference.replay_batch(self.planes, **kw)
        cell_ticks = self.n_cells * self.n_ticks
        want_frac = (refs["owned_cell_ticks"] / np.float32(cell_ticks)).astype(
            np.float32
        )
        if control:
            ctl = reference.replay_batch(self.planes, control=cfg["control"], **kw)
            self.results = [SweepSummary(
                ctl["max_owner_count"],
                (ctl["owned_cell_ticks"] / np.float32(cell_ticks)).astype(
                    np.float32),
                ctl["final_owners"],
            )]
        summary_miss = owner_miss = 0
        max_count = 0
        for res in self.results:
            if res is None:
                self.failed += 1
                summary_miss += len(self.planes)
                max_count = max(max_count, 2)
                continue
            miss = int(np.count_nonzero(
                res.max_owner_count != refs["max_owner_count"]
            )) + int(np.count_nonzero(res.owned_frac != want_frac))
            owners = int(np.count_nonzero(
                res.final_owners != refs["final_owners"]
            ))
            summary_miss += miss
            owner_miss += owners
            max_count = max(max_count, int(res.max_owner_count.max()))
            self.failed += bool(miss or owners or res.max_owner_count.max() > 1)
        limit = cfg["guarantees"]["owners_per_cell_tick_max"]
        return [
            {"name": "sweeps_compared", "value": len(self.results),
             "limit": 1, "ok": len(self.results) >= 1},
            {"name": "summary_mismatches", "value": summary_miss, "limit": 0,
             "ok": summary_miss == 0},
            {"name": "final_owner_mismatches", "value": owner_miss,
             "limit": 0, "ok": owner_miss == 0},
            {"name": "max_owner_count", "value": max_count, "limit": limit,
             "ok": max_count <= limit},
        ]
