"""Bytes of logical work of one call of the delayed window kernel.

This counts what the call has to move between HBM and the chip by its
shapes alone, not what an implementation allocates: every int32 input
stream once (the ``[T, N]`` attempts, releases and, when present,
extends; the ``[T, A]`` reachability and acceptor clocks, the ``[T, P]``
proposer clocks and the ``[T, P, A]`` link matrix), the ``[T, N]`` owners
and owner counts out once, and the packed state once in and once out.
The packed state is per cell ``2A + 2`` int32 of leases (promises and
accepted leases per acceptor, the owner and its lease) and ``6A + 6`` of
in-flight messages and the open round (five slot kinds and a grant
payload per acceptor; ballot, phase, timer, deadline and two vote sets).
"""
from __future__ import annotations

INT32 = 4


def delayed_window_bytes(n_ticks: int, n_cells: int, n_acceptors: int,
                         n_proposers: int, extends: bool) -> int:
    T, N, A, P = n_ticks, n_cells, n_acceptors, n_proposers
    cell_streams = (3 if extends else 2) * T * N
    tick_streams = T * (A + A + P + P * A)
    outputs = 2 * T * N
    state = (2 * A + 2 + 6 * A + 6) * N
    return INT32 * (cell_streams + tick_streams + outputs + 2 * state)


#: how the window kernel's events show in a TPU trace: its ``pallas_call``
#: has no name of its own, so the event is the Mosaic custom call (the
#: cells run no other Pallas kernel)
KERNEL_EVENT = r'custom_call_target="tpu_custom_call"'
