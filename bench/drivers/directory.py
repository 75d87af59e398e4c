"""Shard directory: ``LeaseArrayDirectory.tick``, one call per unit of work.

The window runs episodes of the traffic mix's length. Each starts a fresh
directory with every worker targeting ``ceil(n_shards / target_divisor)``
shards and follows one seeded schedule: an acquire storm from an empty
plane, steady §6 renewals, and one failover probe, a worker drawn from the
seed that stalls at ``stall_tick`` and stays stalled. Set-up runs a
shorter episode through the first renewals and the probe's failover, so
every program the window needs is compiled.

The directory's engine is wrapped on the instance (no program file
changes): ``engine.step`` and ``engine.ticks_left`` are timed and spanned,
each tick's inputs (the planes the directory's policy issued) are kept,
and the engine's owner counts go into a running maximum on the device,
read once after the window. After the window every episode is replayed
through the plain reference from its kept inputs: the owner rows the
directory returned are compared with the reference's, and the planes its
policy issued with those the reference's restatement of the policy
issues over the reference's owners.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.stats import tail
from bench.traffic.generate import stall_schedule


class Cell:
    def __init__(self, cell: dict, seed: int) -> None:
        self.cfg = cfg = cell["config_data"]
        self.traffic = traffic = cell["traffic_data"]
        self.n_shards = cfg["n_shards"]
        self.schedule = dict(stall_schedule(
            seed, n_workers=cfg["n_workers"],
            stall_tick=traffic["stall_tick"],
        ))
        self.target = -(-self.n_shards // cfg["target_divisor"])
        self.max_count = jnp.zeros((), jnp.int32)
        self.episodes = []
        self.tick_s, self.engine_s = [], []
        self._start_episode()
        for _ in range(traffic["warm_ticks"]):
            self._tick()
        self.max_count.block_until_ready()
        self.episodes.clear()
        self.tick_s.clear()
        self.engine_s.clear()
        self.max_count = jnp.zeros((), jnp.int32)
        self.ep = None
        self.attempted = self.failed = 0

    # ------------------------------------------------------------ episodes
    def _start_episode(self) -> None:
        from repro.lease_array.directory import LeaseArrayDirectory

        cfg = self.cfg
        d = LeaseArrayDirectory(
            self.n_shards, n_acceptors=cfg["n_acceptors"],
            lease_ticks=cfg["lease_ticks"], max_workers=cfg["n_workers"],
            max_delay_ticks=cfg["max_delay_ticks"],
        )
        if d.engine.round_ticks != cfg["round_ticks"]:
            raise ValueError("the directory's round differs from the config's")
        for w in range(cfg["n_workers"]):
            d.add_worker(w, self.target)
        self.ep = ep = {"dir": d, "ticks": [], "owners": [], "stalls": []}
        eng = d.engine
        step, ticks_left = eng.step, eng.ticks_left

        def timed_step(tick):
            ep["ticks"].append(tick)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                out = step(tick)
            self._engine += time.perf_counter() - t0
            self.max_count = jnp.maximum(
                self.max_count, eng.last_owner_count.max()
            )
            return out

        def timed_ticks_left():
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.ticks_left"):
                out = ticks_left()
            self._engine += time.perf_counter() - t0
            return out

        eng.step, eng.ticks_left = timed_step, timed_ticks_left
        self.episodes.append(ep)

    def _tick(self) -> None:
        ep = self.ep
        t = len(ep["owners"])
        if t in self.schedule:
            w = self.schedule[t]
            ep["dir"].stall(w)
            ep["stalls"].append((t, w))
        self._engine = 0.0
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.dir_tick"):
            owners = ep["dir"].tick()
        self.tick_s.append(time.perf_counter() - t0)
        self.engine_s.append(self._engine)
        ep["owners"].append(owners)

    def step(self) -> None:
        if self.ep is None or len(self.ep["owners"]) == self.traffic["episode_ticks"]:
            self._start_episode()
        self._tick()
        self.attempted += 1

    # ------------------------------------------------------------- metrics
    def failover_ticks(self) -> np.ndarray:
        """Per shard orphaned by a stall: ticks from the stall until another
        worker owns it. Stalls with fewer than ``failover_horizon`` ticks
        of the window after them are left out; a shard not owned again by
        then counts ``failover_horizon + 1``."""
        horizon = self.traffic["failover_horizon"]
        out = []
        for ep in self.episodes:
            owners = ep["owners"]
            for s, w in ep["stalls"]:
                if s == 0 or s + horizon > len(owners):
                    continue
                pending = np.flatnonzero(owners[s - 1] == w)
                for tau in range(s, s + horizon):
                    row = owners[tau][pending]
                    done = (row >= 0) & (row != w)
                    out.extend([tau - s + 1] * int(done.sum()))
                    pending = pending[~done]
                out.extend([horizon + 1] * pending.size)
        return np.asarray(out, np.float64)

    def end_to_end(self, window_s: float) -> dict:
        # a window in which no stall orphaned a shard reads as no failover
        # at all: more than the horizon
        fails = self.failover_ticks()
        return {
            "dir_tick_p95_ms": 1e3 * tail(self.tick_s, 95),
            "failover_p95_ticks": (
                tail(fails, 95) if fails.size
                else float(self.traffic["failover_horizon"] + 1)
            ),
        }

    def host_split_ms(self) -> tuple:
        ticks = np.asarray(self.tick_s)
        engine = np.asarray(self.engine_s)
        return 1e3 * np.median(ticks - engine), 1e3 * np.median(engine)

    def free(self) -> None:
        self.device_max_count = int(self.max_count)
        self.max_count = None
        for ep in self.episodes:
            ep["dir"] = None

    def check(self, control: bool = False) -> list:
        """Replay every episode's issued planes through the reference and
        compare the owner rows the directory returned, and the issued
        attempts, releases and extends with those of the reference's
        policy; with ``control`` the reference's control stands in the
        program's place."""
        cfg = self.cfg
        kw = dict(n_proposers=cfg["n_workers"],
                  lease_ticks=cfg["lease_ticks"],
                  round_ticks=cfg["round_ticks"])
        owner_miss = plane_miss = 0
        ctl_max = 0
        for ep in self.episodes:
            ticks = ep["ticks"]
            planes = {
                k: np.stack([tk.planes[k] for tk in ticks])
                for k in ("attempts", "releases", "extends", "acc_up",
                          "delay", "drop")
            }
            ref_owners, _, ref_ends = reference.replay(
                planes, lease_ends=True, **kw
            )
            want = reference.directory_planes(
                ref_owners, ref_ends, n_workers=cfg["n_workers"],
                target=self.target, stalls=ep["stalls"],
                lease_ticks=cfg["lease_ticks"],
                max_delay_ticks=cfg["max_delay_ticks"],
            )
            owners = np.stack(ep["owners"])
            if control:
                owners, counts = reference.replay(
                    planes, control=cfg["control"], **kw
                )
                ctl_max = max(ctl_max, int(counts.max()))
            wrong = np.count_nonzero(owners != ref_owners, 1)
            issued = np.zeros(len(ticks), np.int64)
            for k, plane in want.items():
                issued += np.count_nonzero(planes[k] != plane, 1)
            owner_miss += int(wrong.sum())
            plane_miss += int(issued.sum())
            self.failed += int(np.count_nonzero(wrong + issued))
        limit = cfg["guarantees"]["owners_per_cell_tick_max"]
        max_count = ctl_max if control else self.device_max_count
        return [
            {"name": "ticks_compared", "value": self.attempted, "limit": 1,
             "ok": self.attempted >= 1},
            {"name": "owner_mismatches", "value": owner_miss, "limit": 0,
             "ok": owner_miss == 0},
            {"name": "policy_plane_mismatches", "value": plane_miss,
             "limit": 0, "ok": plane_miss == 0},
            {"name": "max_owner_count", "value": max_count,
             "limit": limit, "ok": max_count <= limit},
        ]
