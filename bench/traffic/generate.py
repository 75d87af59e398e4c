"""The benchmark's traffic generator: seeded planes of a master-lease fleet
at start-up and in steady state, and the directory's failover probe.

``startup_trace`` makes the planes of a fleet of replicated shards, each
with its own lease cell, that start together with no master:

- start-up election: every replica of every shard sees no master and
  tries to acquire the lease once. The replicas of one cell try in a
  seeded order, one round (``4 * max_delay_ticks + 1`` ticks) apart,
  from a seeded start tick in ``[0, round)``: the engine models one open
  round per cell, so no round may overwrite another. The first round
  normally wins; the later ones meet a live lease;
- §6 renewals: once the start-up rounds are over, every replica asks to
  renew every ``round(lease_ticks * renew)`` ticks, each on its own tick
  of the group. A replica that does not hold the lease renews nothing
  (an extend by a non-owner is a no-op), so whichever replica won keeps
  its lease without the generator deciding who won;
- the network: per-(tick, acceptor) leg delays uniform in
  ``[0, max_delay_ticks]`` and leg losses at ``p_drop``.

Nothing else happens: no releases, no acceptor outages, no contender
during a live lease after start-up. A replica tries again only when it
sees no master, and no source gives a rate of master or acceptor
failures for these deployments, so no such fault is drawn.

A traffic mix is a JSON file beside this module, named by the cell's
``traffic``; the drivers read it with :func:`load`.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

NONE = -1
HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    """The traffic mix ``<name>.json`` beside this module."""
    return json.loads((HERE / f"{name}.json").read_text())


def startup_trace(
    seed: int, *, n_ticks: int, n_cells: int, n_acceptors: int,
    n_proposers: int, lease_ticks: int, renew: float,
    max_delay_ticks: int, p_drop: float = 0.0,
) -> dict:
    """Planes of one scenario: ``attempts`` and ``extends`` [T, N];
    ``acc_up``, ``delay`` and ``drop`` [T, A] (int32; ``drop`` is None
    without loss); ``releases`` is None."""
    T, N, A, P = n_ticks, n_cells, n_acceptors, n_proposers
    gap = 4 * max_delay_ticks + 1
    interval = max(gap, int(round(lease_ticks * renew)))
    if interval < P:
        raise ValueError("a renewal group must fit in one renewal interval")
    rng = np.random.default_rng(seed)
    order = rng.permuted(np.tile(np.arange(P, dtype=np.int32), (N, 1)), axis=1)
    start = rng.integers(0, gap, N)
    cols = np.arange(N)
    attempts = np.full((T, N), NONE, np.int32)
    for k in range(P):
        t = start + k * gap
        ok = t < T
        attempts[t[ok], cols[ok]] = order[ok, k]
    extends = np.full((T, N), NONE, np.int32)
    for first in range(P * gap, T, interval):
        for k in range(P):
            t = start + first + k
            ok = t < T
            extends[t[ok], cols[ok]] = order[ok, k]
    delay = rng.integers(0, max_delay_ticks + 1, (T, A)).astype(np.int32)
    drop = None
    if p_drop > 0.0:
        drop = (rng.random((T, A)) < p_drop).astype(np.int32)
    return {
        "attempts": attempts, "releases": None, "extends": extends,
        "acc_up": np.ones((T, A), np.int32), "delay": delay, "drop": drop,
    }


def stall_schedule(seed: int, *, n_workers: int, stall_tick: int) -> list:
    """The directory's failover probe: one (tick, worker) pair, a worker
    drawn from the seed that stalls at ``stall_tick``. Every seed asks for
    the same failover of a different worker."""
    rng = np.random.default_rng(seed)
    return [(int(stall_tick), int(rng.integers(0, n_workers)))]
