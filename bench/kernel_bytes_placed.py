"""Bytes of logical work of one call of the delayed window kernel with a
placement (``bench/kernel_bytes.py`` counts the kernel without one).

A placed call moves every stream the unplaced call moves, by the same
shapes, and reads once more what the placement adds: each cell's restart
table, ``[F, A, N]`` int32 with ``F = 2 * MAX_RESTARTS + 2`` fields per
replica slot (its node's first three acceptor and three proposer restart
ticks, its deaf-until and its restart count at the call's start), made
on the device from the ``[A, N]`` placement and the ``[T, K]`` node rows
before the kernel runs. The kernel reads neither of those two itself:
the table stands for them.
"""
from __future__ import annotations

from bench.kernel_bytes import INT32, delayed_window_bytes

#: restarts of one node a dispatch may hold: the program's
#: ``state.MAX_RESTARTS``, the most its ballots' 2-bit restart counter
#: carries
MAX_RESTARTS = 3
#: fields of the restart table per replica slot, as the program's
#: ``netplane.RT_FIELDS`` lays them out: MAX_RESTARTS acceptor restart
#: ticks, MAX_RESTARTS proposer restart ticks, the deaf-until and the
#: restart count at the call's start
TABLE_FIELDS = 2 * MAX_RESTARTS + 2


def placed_window_bytes(n_ticks: int, n_cells: int, n_acceptors: int,
                        n_proposers: int, extends: bool) -> int:
    table = TABLE_FIELDS * n_acceptors * n_cells
    return delayed_window_bytes(
        n_ticks, n_cells, n_acceptors, n_proposers, extends
    ) + INT32 * table
