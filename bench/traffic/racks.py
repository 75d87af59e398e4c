"""Rack restarts over placed replicas: the planes of a range-sharded store
whose shard masters hold leases, with its replicas placed on nodes by
chained declustering and whole racks restarting.

``rack_restart_trace`` starts from ``generate.startup_trace`` (the
start-up election and the §6 renewals of every shard) and adds:

- the placement: the cells form ``n_ranges`` ranges of contiguous cells,
  and replica ``k`` of range ``r`` is on node ``(r + k) mod n_nodes``
  (Spinnaker's chained declustering); node ``i`` is in rack
  ``i mod n_racks``, so the replicas of a cell are in distinct racks
  while ``n_racks >= A``;
- rack restarts: at each tick of ``restart_ticks`` one rack, drawn from
  the seed (distinct racks), crashes and restarts whole within the tick:
  each of its nodes' acceptor and proposer restarts (``[T, K]`` node
  rows);
- failover: every cell with a replica on the rack has its two other
  replicas try once each, in a seeded order, one round
  (``4 * max_delay_ticks + 1`` ticks) apart, the first
  ``failover_after`` ticks after the restart or the least later tick at
  which neither try lands on a renewal tick of the cell (so no try
  replaces one of the cell's extends). With ``failover_after`` shorter
  than M a try meets the restarted replica deaf, so it needs both other
  replicas; where the cell's master lived elsewhere it meets that
  master's live lease, and where the master was on the rack, whose
  lease has run out by then, the first try normally wins.
"""
from __future__ import annotations

import numpy as np

from bench.traffic.generate import NONE, startup_trace


def chained_placement(n_cells: int, n_ranges: int, n_nodes: int,
                      replicas: int) -> np.ndarray:
    """``[replicas, n_cells]`` node ids: cell ``n`` is in range
    ``n * n_ranges // n_cells``, whose replica ``k`` is on node
    ``(range + k) mod n_nodes``."""
    rng_of = (np.arange(n_cells, dtype=np.int64) * n_ranges) // n_cells
    return np.stack([
        (rng_of + k) % n_nodes for k in range(replicas)
    ]).astype(np.int32)


def rack_restart_trace(
    seed: int, *, n_ticks: int, n_cells: int, n_acceptors: int,
    n_proposers: int, lease_ticks: int, renew: float,
    max_delay_ticks: int, p_drop: float, n_nodes: int, n_racks: int,
    n_ranges: int, restart_ticks: list, failover_after: int,
) -> tuple[dict, np.ndarray, list]:
    """``(planes, placement, restarts)``: the scenario's planes with
    ``acc_restart``/``prop_restart`` as ``[T, K]`` node rows and the
    failover tries in ``attempts``; the ``[A, N]`` placement; and each
    restart as ``(tick, rack)``."""
    T, N, A = n_ticks, n_cells, n_acceptors
    planes = startup_trace(
        seed, n_ticks=T, n_cells=N, n_acceptors=A, n_proposers=n_proposers,
        lease_ticks=lease_ticks, renew=renew,
        max_delay_ticks=max_delay_ticks, p_drop=p_drop,
    )
    place = chained_placement(N, n_ranges, n_nodes, A)
    rng = np.random.default_rng([seed, 1])
    racks = rng.choice(n_racks, len(restart_ticks), replace=False)
    arst = np.zeros((T, n_nodes), np.int32)
    attempts, extends = planes["attempts"], planes["extends"]
    gap = 4 * max_delay_ticks + 1
    interval = max(gap, int(round(lease_ticks * renew)))
    cols = np.arange(N)
    restarts = []
    for t_r, rack in zip(restart_ticks, racks):
        t_r, rack = int(t_r), int(rack)
        restarts.append((t_r, rack))
        arst[t_r, np.arange(rack, n_nodes, n_racks)] = 1
        on_rack = (place % n_racks) == rack                       # [A, N]
        hit = on_rack.any(axis=0)
        cells = cols[hit]
        down = on_rack[:, hit].argmax(axis=0)       # the slot on the rack
        others = np.stack([(down + 1) % A, (down + 2) % A])
        flip = rng.random(cells.size) < 0.5
        others[:, flip] = others[::-1, flip]
        first = np.full(cells.size, -1, np.int64)
        for shift in range(interval):
            t1 = t_r + failover_after + shift
            if t1 + gap >= T:
                break
            clear = (extends[t1, cells] == NONE) & (
                extends[t1 + gap, cells] == NONE
            )
            first = np.where((first < 0) & clear, t1, first)
        ok = first >= 0
        attempts[first[ok], cells[ok]] = others[0, ok]
        attempts[first[ok] + gap, cells[ok]] = others[1, ok]
    planes["acc_restart"] = arst
    planes["prop_restart"] = arst.copy()
    return planes, place, restarts
