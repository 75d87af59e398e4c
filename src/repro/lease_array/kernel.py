"""Time-resident fused Pallas kernels for the lease plane: the WHOLE tick
loop lives inside the kernel, not just one tick.

Earlier revisions dispatched one `pallas_call` per tick, round-tripping
every state plane through HBM ``T`` times per scenario and paying a kernel
launch per tick (the dispatch-dominated `lease_array_kernel_step` bench
row). The window kernels here replay a full ``[T, ...]`` scenario with ONE
launch: the grid is ``(cell_blocks, windows)`` with the window axis minor,
so each cell block's packed state stays **resident in VMEM** across the
whole scenario (the state BlockSpecs ignore the window index — Pallas
revisits the same block, no HBM writeback until the block changes), while
the per-tick scenario planes stream in one ``window``-tick slab at a time
and a `jax.lax.fori_loop` walks the ticks inside.

The tick bodies are the SAME functions the jnp oracle scans
(`ref.sync_tick_math`, `netplane.delayed_tick_math`), so kernel and oracle
are bit-identical by construction — including across window boundaries: a
message sent in window ``w`` with a deliver-at in window ``w+1`` simply
stays in its packed in-flight slot (part of the resident state) until the
later window's tick loop finds it due. Per-leg link delays are resolved
block-locally (`netplane.legs_select`): the tiny ``[P, A]`` link matrix of
the current tick is selected row-by-row in a compile-time P loop, so no
gather indices (and no flattened ``[P*A, N]`` planes) ever touch HBM.

Drifting clocks (§4) stream the same way: the per-tick ``[P, 1]``/
``[A, 1]`` *absolute local-clock* columns (exclusive prefix sums of the
scenario's rate planes, computed once in ops.py) ride the broadcast plane
specs like ``acc_up``, so drifted node time needs NO extra carry — the
deadline fields already resident in VMEM are simply minted from and
compared against these columns (per-cell owner clocks via the
compile-time P-loop ``state.clock_select``, the proposer discount
``guard_q4`` a closure constant like ``lease_q4``).

Layout: the acceptor (A) and proposer-bitmask axes ride on sublanes, the
cell axis N on the 128-wide lane axis. All state is int32, all updates are
`jnp.where` selects — pure VPU work, no MXU. ``backend="pallas_tpu"``
compiles for real TPUs (mind the sublane padding notes in docs/perf.md);
``backend="pallas"`` runs the same kernel in interpret mode anywhere.

The scan scalars (t0, total ticks) live in SMEM; protocol constants
(majority, lease length, round horizon, P, window) are compile-time
closure constants, mirroring how kernels/flash_attention bakes its block
geometry.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .netplane import (
    RT_ACC,
    RT_FIELDS,
    RT_PROP,
    NetPlaneState,
    delayed_tick_math,
    legs_select,
    placed_restart_columns,
)
from .state import MAX_RESTARTS
from .ref import sync_tick_math
from .state import PACK_SHIFT, PackedLeaseState, clock_select

N_LEASE = len(PackedLeaseState._fields)
N_NET = len(NetPlaneState._fields)

#: index of own_id inside PackedLeaseState — the per-tick owner row
_OWN_ID = PackedLeaseState._fields.index("owner_id")
#: index of the packed owner lease — the quiescence check reads its expiry
_OWN_LEASE = PackedLeaseState._fields.index("owner_lease")

# BlockSpecs for the packed lease plane ([A, bn] x2 then [1, bn] x2)
_LEASE_ROWS = (None, None, 1, 1)  # None -> the plane keeps its A rows
# NetPlaneState: 6 [A, bn] slot planes then 6 [1, bn] round rows
_NET_ROWS = (None,) * 6 + (1,) * 6


def _state_specs(rows, n_acceptors: int, block_n: int):
    """One resident-block spec per state plane: index map ignores the
    window axis, so the block stays in VMEM across all windows."""
    return [
        pl.BlockSpec(
            ((n_acceptors if r is None else r), block_n), lambda i, w: (0, i)
        )
        for r in rows
    ]


def _cell_plane_spec(tw: int, rows: int, block_n: int):
    """One streamed [W, tw, rows, block_n] scenario-plane slab per window
    (the leading W axis is squeezed away inside the kernel)."""
    return pl.BlockSpec((None, tw, rows, block_n), lambda i, w: (w, 0, 0, i))


def _bcast_plane_spec(tw: int, rows: int, cols: int):
    """A cell-independent plane (acc_up columns, link matrices): every cell
    block streams the same [tw, rows, cols] slab."""
    return pl.BlockSpec((None, tw, rows, cols), lambda i, w: (w, 0, 0, 0))


class LaunchPlan(NamedTuple):
    """The complete launch geometry of one window kernel: grid, BlockSpecs
    and the *logical* (full-array) shape behind every spec, in call order.

    The ``pallas_call`` entry points below consume a plan verbatim, and the
    static launch checker (``repro.analysis.staticcheck.launch``) audits the
    same object — bounds, write-race partition of the cell axis, VMEM
    residency — so there is no second hand-maintained description of the
    launch to drift out of sync.

    ``in_shapes``/``out_shapes`` align 1:1 with ``in_specs``/``out_specs``.
    The leading scalar-vector input rides in SMEM; its spec has
    no block shape, which the checker treats as exempt from tiling rules.
    """

    grid: tuple[int, int]
    in_specs: tuple
    out_specs: tuple
    in_shapes: tuple[tuple[int, ...], ...]
    out_shapes: tuple[tuple[int, ...], ...]
    block_n: int
    tw: int
    n_windows: int


def _window_geometry(n_cells: int, n_ticks: int, block_n: int, window: int):
    block_n = min(block_n, n_cells)
    assert n_cells % block_n == 0, \
        "pad the cell axis to a block multiple (ops.py)"
    tw = max(1, min(window, n_ticks))
    n_windows = -(-n_ticks // tw)
    return block_n, tw, n_windows


def _table_spec(fields: int, rows: int, block_n: int):
    """A per-cell [fields, rows, N] table read whole by each cell block:
    resident across the window axis like the state planes."""
    return pl.BlockSpec((fields, rows, block_n), lambda i, w: (0, 0, i))


def _launch_plan(
    rows, n_acceptors: int, n_cells: int, n_proposers: int, n_ticks: int,
    block_n: int, window: int, bcast_rows: tuple[tuple[int, int], ...],
    n_cell_planes: int = 2, skip_row: bool = False,
    table: tuple[int, int] | None = None,
) -> LaunchPlan:
    """Shared plan builder: ``rows`` describes the resident state planes
    (None -> A rows), ``n_cell_planes`` how many [T, N] cell-plane streams
    follow them (attempts/releases, plus the §6 extends stream), and
    ``bcast_rows`` the trailing cell-independent streams as (rows, cols)
    pairs. ``table`` (fields, rows) appends a resident per-cell
    [fields, rows, N] input last (a placed replay's restart table).
    ``skip_row`` appends a resident [1, N] output after the owners and
    counts: each cell block's count of windows that took the quiescent
    path."""
    A, N, T = n_acceptors, n_cells, n_ticks
    block_n, tw, n_windows = _window_geometry(N, T, block_n, window)
    grid = (N // block_n, n_windows)
    state_specs = _state_specs(rows, A, block_n)
    state_shapes = tuple((A if r is None else r, N) for r in rows)
    cell_spec = _cell_plane_spec(tw, 1, block_n)
    cell_shape = (n_windows, tw, 1, N)
    in_specs = (
        (
            pl.BlockSpec(memory_space=pltpu.SMEM),  # [2] scan scalars, whole
            *state_specs, *(cell_spec,) * n_cell_planes,
        )
        + tuple(_bcast_plane_spec(tw, r, c) for r, c in bcast_rows)
    )
    in_shapes = (
        ((2,), *state_shapes, *(cell_shape,) * n_cell_planes)
        + tuple((n_windows, tw, r, c) for r, c in bcast_rows)
    )
    if table is not None:
        in_specs += (_table_spec(*table, block_n),)
        in_shapes += ((*table, N),)
    skip_specs = tuple(_state_specs((1,), A, block_n)) if skip_row else ()
    skip_shapes = ((1, N),) if skip_row else ()
    return LaunchPlan(
        grid=grid,
        in_specs=in_specs,
        out_specs=(*state_specs, cell_spec, cell_spec, *skip_specs),
        in_shapes=in_shapes,
        out_shapes=(*state_shapes, cell_shape, cell_shape, *skip_shapes),
        block_n=block_n,
        tw=tw,
        n_windows=n_windows,
    )


def sync_launch_plan(
    n_acceptors: int, n_cells: int, n_proposers: int, n_ticks: int,
    *, block_n: int = 512, window: int = 16,
) -> LaunchPlan:
    """Launch geometry of ``lease_window_sync_pallas``: lease state +
    attempt/release cell planes + acc_up/pclk/aclk broadcast columns."""
    A, P = n_acceptors, n_proposers
    return _launch_plan(
        _LEASE_ROWS, A, n_cells, P, n_ticks, block_n, window,
        bcast_rows=((A, 1), (P, 1), (A, 1)),
    )


def delayed_launch_plan(
    n_acceptors: int, n_cells: int, n_proposers: int, n_ticks: int,
    *, block_n: int = 512, window: int = 16, corrupt: bool = False,
    restart: bool = False, extend: bool = False, placed: bool = False,
) -> LaunchPlan:
    """Launch geometry of ``lease_window_delayed_pallas``: lease + netplane
    state, the same streams as sync, plus the fused [P, A] link matrices.
    ``extend`` inserts the §6 extends stream as a THIRD [T, N] cell plane
    right after releases (the owner-extension proposer ids). ``corrupt``
    appends the two adversarial [A, 1] corruption columns (stale-ballot /
    equivocation masks) to the streamed planes; ``restart`` appends the
    four crash/restart columns (acceptor restart + deaf-window masks
    [A, 1], proposer restart + running restart counters [P, 1]) — the
    honest launch is geometry-identical to the pre-falsifier kernel.
    ``placed`` (a placement's per-cell restarts) reads the restart inputs
    from a resident per-cell [RT_FIELDS, A, N] restart table instead of
    the four streamed columns. The outputs end with the resident [1, N]
    skip-count row."""
    A, P = n_acceptors, n_proposers
    bcast = ((A, 1), (P, 1), (A, 1), (P, A))
    if corrupt:
        bcast += ((A, 1), (A, 1))
    if restart and not placed:
        bcast += ((A, 1), (A, 1), (P, 1), (P, 1))
    return _launch_plan(
        _LEASE_ROWS + _NET_ROWS, A, n_cells, P, n_ticks, block_n, window,
        bcast_rows=bcast, n_cell_planes=3 if extend else 2, skip_row=True,
        table=(RT_FIELDS, A) if placed else None,
    )


def _init_resident(w, in_refs, out_refs):
    """At the first window, seed the resident state blocks from the inputs
    (afterwards the out blocks ARE the carried state)."""

    @pl.when(w == 0)
    def _():
        for o, i in zip(out_refs, in_refs):
            o[...] = i[...]


def _window_bounds(sc_ref, tw: int):
    w = pl.program_id(1)
    base = w * tw
    n_ticks = jnp.minimum(tw, sc_ref[1] - base)
    return sc_ref[0] + base, n_ticks


def _sync_window_kernel(
    sc_ref,  # [2] int32 (t0, T) in SMEM
    *refs,
    majority: int, lease_q4: int, guard_q4: int, n_proposers: int, tw: int,
):
    ins, outs = refs[: N_LEASE + 5], refs[N_LEASE + 5:]
    att_ref, rel_ref, up_ref, pclk_ref, aclk_ref = ins[N_LEASE:]
    st_refs = outs[:N_LEASE]
    own_ref, cnt_ref = outs[N_LEASE], outs[N_LEASE + 1]
    _init_resident(pl.program_id(1), ins[:N_LEASE], st_refs)
    t_base, n_ticks = _window_bounds(sc_ref, tw)

    def body(tau, lease):
        lease, count = sync_tick_math(
            lease, t_base + tau,
            att_ref[tau], rel_ref[tau], up_ref[tau],
            pclk_ref[tau], aclk_ref[tau],
            majority=majority, lease_q4=lease_q4, n_proposers=n_proposers,
            guard_q4=guard_q4,
        )
        own_ref[tau] = lease[_OWN_ID]
        cnt_ref[tau] = count
        return lease

    lease = jax.lax.fori_loop(
        0, n_ticks, body, tuple(r[...] for r in st_refs)
    )
    for r, v in zip(st_refs, lease):
        r[...] = v


def _quiescent(
    st_refs, att_ref, rel_ref, ext_ref, pclk_ref, aclk_ref,
    stale_ref, equiv_ref, rst_refs, tw: int, tab_ref=None, t_base=None,
    n_ticks=None,
):
    """True iff this (cell block, window) pair provably cannot change the
    resident state: no message in flight, no open round, no scheduled
    attempt/release/extend (all-sentinel slabs — the zero tail padding of a
    partial last window reads as proposer 0 and correctly disqualifies it),
    no scheduled fault, and every lease — the owner row on the owner's
    clock, each acceptor's on its own — stays live through the window's
    LAST local-clock reading. Ticks inside such a window are pure owner
    samples: phase 1 expires nothing, phases 2-4 see only empty slots and
    sentinel rows."""
    rnd_ballot = st_refs[N_LEASE + 6]
    quiet = (
        jnp.all(att_ref[...] < 0)
        & jnp.all(rel_ref[...] < 0)
        & jnp.all(rnd_ballot[...] == 0)
    )
    if ext_ref is not None:
        quiet &= jnp.all(ext_ref[...] < 0)
    # the five in-flight slot planes (presp_pay is inert while presp == 0)
    for i in (0, 1, 3, 4, 5):
        quiet &= jnp.all(st_refs[N_LEASE + i][...] == 0)
    if stale_ref is not None:
        quiet &= jnp.all(stale_ref[...] == 0) & jnp.all(equiv_ref[...] == 0)
    if rst_refs is not None:
        arst_ref, _, prst_ref, _ = rst_refs
        quiet &= jnp.all(arst_ref[...] == 0) & jnp.all(prst_ref[...] == 0)
    if tab_ref is not None:
        # a placed replay: no cell of the block restarts inside the window
        t_end = t_base + n_ticks
        for f in (*range(RT_ACC, RT_ACC + MAX_RESTARTS),
                  *range(RT_PROP, RT_PROP + MAX_RESTARTS)):
            ticks = tab_ref[f]
            quiet &= jnp.all((ticks < t_base) | (ticks >= t_end))
    # leases must outlive the window on their holder's LOCAL clock: clocks
    # only advance, so the slab's last reading is the window's worst case
    own_id = st_refs[_OWN_ID][...]
    ownp = st_refs[_OWN_LEASE][...]
    own_clk_end = clock_select(pclk_ref[tw - 1], own_id)
    quiet &= jnp.all(
        (ownp == 0) | (ownp >= ((own_clk_end + 1) << PACK_SHIFT))
    )
    acc_lease = st_refs[1][...]
    aclk_end = aclk_ref[tw - 1]
    quiet &= jnp.all(
        (acc_lease == 0) | (acc_lease >= ((aclk_end + 1) << PACK_SHIFT))
    )
    return quiet


def _delayed_window_kernel(
    sc_ref,
    *refs,
    majority: int, lease_q4: int, round_q4: int, guard_q4: int,
    n_proposers: int, tw: int, corrupt: bool = False, restart: bool = False,
    extend: bool = False, skip_stable: bool = True, placed: bool = False,
    deaf_q4: int = 0,
):
    n_state = N_LEASE + N_NET
    n_cell = 3 if extend else 2
    n_in = (
        n_state + n_cell + 4 + (2 if corrupt else 0)
        + (4 if restart and not placed else 0) + (1 if placed else 0)
    )
    ins, outs = refs[:n_in], refs[n_in:]
    att_ref, rel_ref = ins[n_state:n_state + 2]
    ext_ref = ins[n_state + 2] if extend else None
    up_ref, pclk_ref, aclk_ref, link_ref = \
        ins[n_state + n_cell:n_state + n_cell + 4]
    extra = n_state + n_cell + 4
    stale_ref = equiv_ref = None
    if corrupt:
        stale_ref, equiv_ref = ins[extra:extra + 2]
        extra += 2
    rst_refs = ins[extra:extra + 4] if restart and not placed else None
    tab_ref = ins[extra] if placed else None
    st_refs = outs[:n_state]
    own_ref, cnt_ref, skip_ref = outs[n_state:n_state + 3]
    _init_resident(pl.program_id(1), ins[:n_state], st_refs)
    t_base, n_ticks = _window_bounds(sc_ref, tw)

    # the block's count of windows that took the quiescent path, resident
    # across the window axis like the state rows
    @pl.when(pl.program_id(1) == 0)
    def _():
        skip_ref[...] = jnp.zeros_like(skip_ref)

    def body(tau, carry):
        lease, net = carry[:N_LEASE], carry[N_LEASE:]
        adv = (
            {"stale": stale_ref[tau], "equiv": equiv_ref[tau]}
            if corrupt else {}
        )
        if extend:
            adv["extend"] = ext_ref[tau]
        if placed:
            arst, deaf, prst, rc = placed_restart_columns(
                tab_ref, t_base + tau, deaf_q4=deaf_q4
            )
            adv.update(
                acc_restart=arst, acc_deaf=deaf, prop_restart=prst,
                prop_rc=rc,
            )
        elif restart:
            arst_ref, deaf_ref, prst_ref, rc_ref = rst_refs
            adv.update(
                acc_restart=arst_ref[tau], acc_deaf=deaf_ref[tau],
                prop_restart=prst_ref[tau], prop_rc=rc_ref[tau],
            )
        lease, net, count = delayed_tick_math(
            lease, net, t_base + tau,
            att_ref[tau], rel_ref[tau], up_ref[tau],
            pclk_ref[tau], aclk_ref[tau], link_ref[tau],
            majority=majority, lease_q4=lease_q4, round_q4=round_q4,
            n_proposers=n_proposers, guard_q4=guard_q4, legs=legs_select,
            **adv,
        )
        own_ref[tau] = lease[_OWN_ID]
        cnt_ref[tau] = count
        return (*lease, *net)

    def run_window():
        carry = jax.lax.fori_loop(
            0, n_ticks, body, tuple(r[...] for r in st_refs)
        )
        for r, v in zip(st_refs, carry):
            r[...] = v

    if not skip_stable:
        run_window()
        return

    skip = _quiescent(
        st_refs, att_ref, rel_ref, ext_ref, pclk_ref, aclk_ref,
        stale_ref, equiv_ref, rst_refs, tw, tab_ref, t_base, n_ticks,
    )

    @pl.when(skip)
    def _():
        # quiescent fast path: the window is pure owner sampling — the
        # resident state is untouched and every tick reads the same row
        own_row = st_refs[_OWN_ID][...]
        cnt_row = (st_refs[_OWN_LEASE][...] > 0).astype(jnp.int32)
        own_ref[...] = jnp.broadcast_to(own_row[None], own_ref.shape)
        cnt_ref[...] = jnp.broadcast_to(cnt_row[None], cnt_ref.shape)
        skip_ref[...] = skip_ref[...] + 1

    @pl.when(jnp.logical_not(skip))
    def _():
        run_window()


def _windowed(plane, n_windows: int, tw: int, rows: int, n: int):
    """[T, rows(, n)] plane -> [W, tw, rows, n] slabs (zero tail padding —
    the in-kernel dynamic trip count never reads the pad)."""
    t = plane.shape[0]
    plane = plane.reshape(t, rows, n)
    pad = n_windows * tw - t
    if pad:
        plane = jnp.pad(plane, ((0, pad), (0, 0), (0, 0)))
    return plane.reshape(n_windows, tw, rows, n)


def lease_window_sync_pallas(
    packed: PackedLeaseState,
    t0,          # scalar int32 first tick
    attempts,    # [T, N] int32
    releases,    # [T, N] int32
    acc_up,      # [T, A] bool/int32
    pclk,        # [T, P] int32 proposer local clocks per tick
    aclk,        # [T, A] int32 acceptor local clocks per tick
    *,
    majority: int,
    lease_q4: int,
    n_proposers: int,
    guard_q4: int = None,
    block_n: int = 512,
    window: int = 16,
    interpret: bool = True,  # False on real TPUs
) -> tuple[PackedLeaseState, jax.Array, jax.Array]:
    """Replay T synchronous ticks in ONE kernel launch; N must be a
    multiple of ``block_n`` (ops.py pads). Returns
    (packed_state', owners [T, N], counts [T, N])."""
    A, N = packed.promised.shape
    P = n_proposers
    T = attempts.shape[0]
    plan = sync_launch_plan(A, N, P, T, block_n=block_n, window=window)
    tw, n_windows = plan.tw, plan.n_windows

    kernel = functools.partial(
        _sync_window_kernel,
        majority=majority, lease_q4=lease_q4,
        guard_q4=lease_q4 if guard_q4 is None else guard_q4,
        n_proposers=P, tw=tw,
    )
    row_plane = lambda p: _windowed(
        jnp.asarray(p, jnp.int32), n_windows, tw, 1, N
    )
    col_plane = lambda p, rows: _windowed(
        jnp.asarray(p, jnp.int32), n_windows, tw, rows, 1
    )
    sds = jax.ShapeDtypeStruct
    outs = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=list(plan.in_specs),
        out_specs=list(plan.out_specs),
        out_shape=[sds(s, jnp.int32) for s in plan.out_shapes],
        interpret=interpret,
        name="lease_window_sync",
    )(
        jnp.stack([jnp.asarray(t0, jnp.int32), jnp.int32(T)]),
        *packed,
        row_plane(attempts), row_plane(releases),
        col_plane(jnp.asarray(acc_up).astype(jnp.int32), A),
        col_plane(pclk, P), col_plane(aclk, A),
    )
    new_packed = PackedLeaseState(*outs[:N_LEASE])
    owners = outs[N_LEASE].reshape(n_windows * tw, N)[:T]
    counts = outs[N_LEASE + 1].reshape(n_windows * tw, N)[:T]
    return new_packed, owners, counts


def lease_window_delayed_pallas(
    packed: PackedLeaseState,
    net: NetPlaneState,
    t0,          # scalar int32 first tick
    attempts,    # [T, N] int32
    releases,    # [T, N] int32
    acc_up,      # [T, A] bool/int32
    pclk,        # [T, P] int32 proposer local clocks per tick
    aclk,        # [T, A] int32 acceptor local clocks per tick
    link,        # [T, P, A] int32 fused link matrices (netplane.pack_link)
    *,
    majority: int,
    lease_q4: int,
    round_q4: int,
    n_proposers: int,
    guard_q4: int = None,
    block_n: int = 512,
    window: int = 16,
    interpret: bool = True,  # False on real TPUs
    extends=None,  # [T, N] §6 owner-extension proposer ids (None = honest)
    skip_stable: bool = True,  # compile the quiescence fast path
    stale=None,  # [T, A] adversarial stale-ballot mask (None = honest)
    equiv=None,  # [T, A] adversarial equivocation mask (None = honest)
    acc_restart=None,   # [T, A] acceptor crash+restart mask (None = honest)
    acc_deaf=None,      # [T, A] post-restart deaf-window mask
    prop_restart=None,  # [T, P] proposer crash+restart mask
    prop_rc=None,       # [T, P] running per-proposer restart counters
    restart_table=None,  # [RT_FIELDS, A, N] a placed replay's restarts
    deaf_q4: int = 0,   # its deaf window (0 = restart_guard=False)
) -> tuple[PackedLeaseState, NetPlaneState, jax.Array, jax.Array, jax.Array]:
    """Replay T delayed-model ticks in ONE kernel launch (state AND the
    in-flight netplane stay VMEM-resident across windows). Returns
    (packed_state', net', owners [T, N], counts [T, N], steps [2]), where
    ``steps`` holds the grid steps (cell block, window) that took the
    quiescent path, then every grid step of the launch. Passing
    ``extends`` streams the §6 owner-extension ids as a third [T, N]
    cell plane and compiles the extend gate. Passing either corruption
    mask streams both as extra [A, 1] broadcast columns and compiles the
    corrupted tick body; passing any restart input streams all four
    crash/restart columns likewise; the honest launch is unchanged.
    ``restart_table`` (with A == P) replaces the four columns by the
    per-cell restart table of a placed replay (``netplane.
    placed_restart_columns`` derives each tick's columns from it, with
    the deaf window ``deaf_q4``). ``skip_stable`` compiles the per-(block, window) quiescence check:
    windows whose cell block provably cannot change (no traffic, no
    events, no expiry in reach) collapse to owner-row broadcasts instead
    of running the tick loop — bit-identical results, a fraction of the
    VPU work on steady-state phases (``False`` is the A/B bench control)."""
    A, N = packed.promised.shape
    P = n_proposers
    T = attempts.shape[0]
    extend = extends is not None
    corrupt = stale is not None or equiv is not None
    placed = restart_table is not None
    restart = placed or any(
        x is not None for x in (acc_restart, acc_deaf, prop_restart, prop_rc)
    )
    if placed and (A != P or any(
        x is not None for x in (acc_restart, acc_deaf, prop_restart, prop_rc)
    )):
        raise ValueError(
            "a restart table takes the place of the four restart columns "
            "and needs A == P"
        )
    plan = delayed_launch_plan(
        A, N, P, T, block_n=block_n, window=window, corrupt=corrupt,
        restart=restart, extend=extend, placed=placed,
    )
    tw, n_windows = plan.tw, plan.n_windows

    kernel = functools.partial(
        _delayed_window_kernel,
        majority=majority, lease_q4=lease_q4, round_q4=round_q4,
        guard_q4=lease_q4 if guard_q4 is None else guard_q4,
        n_proposers=P, tw=tw, corrupt=corrupt, restart=restart,
        extend=extend, skip_stable=skip_stable, placed=placed,
        deaf_q4=deaf_q4,
    )
    row_plane = lambda p: _windowed(
        jnp.asarray(p, jnp.int32), n_windows, tw, 1, N
    )
    col_plane = lambda p, rows: _windowed(
        jnp.asarray(p, jnp.int32), n_windows, tw, rows, 1
    )
    sds = jax.ShapeDtypeStruct
    outs = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=list(plan.in_specs),
        out_specs=list(plan.out_specs),
        out_shape=[sds(s, jnp.int32) for s in plan.out_shapes],
        interpret=interpret,
        name="lease_window_delayed",
    )(
        jnp.stack([jnp.asarray(t0, jnp.int32), jnp.int32(T)]),
        *packed,
        *net,
        row_plane(attempts), row_plane(releases),
        *((row_plane(extends),) if extend else ()),
        col_plane(jnp.asarray(acc_up).astype(jnp.int32), A),
        col_plane(pclk, P), col_plane(aclk, A),
        _windowed(jnp.asarray(link, jnp.int32), n_windows, tw, P, A),
        *(
            (
                col_plane(jnp.zeros((T, A), jnp.int32) if stale is None
                          else stale, A),
                col_plane(jnp.zeros((T, A), jnp.int32) if equiv is None
                          else equiv, A),
            )
            if corrupt else ()
        ),
        *(
            (
                col_plane(jnp.zeros((T, A), jnp.int32) if acc_restart is None
                          else acc_restart, A),
                col_plane(jnp.zeros((T, A), jnp.int32) if acc_deaf is None
                          else acc_deaf, A),
                col_plane(jnp.zeros((T, P), jnp.int32) if prop_restart is None
                          else prop_restart, P),
                col_plane(jnp.zeros((T, P), jnp.int32) if prop_rc is None
                          else prop_rc, P),
            )
            if restart and not placed else ()
        ),
        *((jnp.asarray(restart_table, jnp.int32),) if placed else ()),
    )
    n_state = N_LEASE + N_NET
    new_packed = PackedLeaseState(*outs[:N_LEASE])
    new_net = NetPlaneState(*outs[N_LEASE:n_state])
    owners = outs[n_state].reshape(n_windows * tw, N)[:T]
    counts = outs[n_state + 1].reshape(n_windows * tw, N)[:T]
    # every lane of a block's row holds the block's count: read one each
    skip_row = outs[n_state + 2]
    skipped = jax.lax.slice(
        skip_row, (0, 0), skip_row.shape, (1, plan.block_n)
    ).sum()
    steps = jnp.stack([skipped, jnp.int32(plan.grid[0] * plan.grid[1])])
    return new_packed, new_net, owners, counts, steps


def delayed_kernel_args(
    n_acceptors: int, n_cells: int, n_proposers: int, n_ticks: int, *,
    extend: bool = False, restart: bool = False, placed: bool = False,
    sharding=None,
) -> tuple[tuple, dict]:
    """Abstract int32 arguments of :func:`lease_window_delayed_pallas` for
    an ahead-of-time compile without data: ``(args, streams)``, the
    positional state and planes and the keyword streams of the ``extends``
    and restart variants (``placed``: the restart table), as
    ``jax.jit(f).lower(args, streams)`` takes them. ``sharding`` places
    every argument (e.g. on a described chip)."""
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
    A, N, P, T = n_acceptors, n_cells, n_proposers, n_ticks
    args = (
        PackedLeaseState(sds(A, N), sds(A, N), sds(1, N), sds(1, N)),
        NetPlaneState(*([sds(A, N)] * 6 + [sds(1, N)] * 6)),
        sds(), sds(T, N), sds(T, N), sds(T, A), sds(T, P), sds(T, A),
        sds(T, P, A),
    )
    streams = {"extends": sds(T, N)} if extend else {}
    if placed:
        streams["restart_table"] = sds(RT_FIELDS, A, N)
    elif restart:
        streams.update(
            acc_restart=sds(T, A), acc_deaf=sds(T, A),
            prop_restart=sds(T, P), prop_rc=sds(T, P),
        )
    return args, streams
