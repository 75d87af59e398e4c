"""LeaseArrayEngine: a stateful driver over the vectorized lease plane.

Three modes:
  - ``step(...)``    — advance one tick (host-driven; the directory uses it)
  - ``run_trace``    — a whole [T]-tick ``Scenario`` in ONE dispatch (the
                       bulk/benchmark path): the fused window scan
                       (``ops.lease_window_scan``) runs the packed tick
                       math under ``lax.scan`` (jnp) or inside the
                       time-resident Pallas window kernel (pallas backends)
  - ``sweep``        — a stacked BATCH of scenarios in one dispatch
                       (``jax.vmap`` inside, ``shard_map`` across devices
                       when more than one is visible), each replayed from
                       the engine's current state with donated plane
                       buffers; per-scenario §4 verification built in.

Inputs are declarative **Scenario planes** (``scenario.py``): one pytree
carries every fault dimension — attempts, releases, acceptor reachability,
asymmetric per-(proposer, acceptor) link delay/drop matrices, and per-node
clock-rate planes — so new fault planes register into the schema instead
of growing new arguments. The legacy per-plane kwargs still work as thin
shims that build the pytree.

Clock drift (§4): the engine carries each node's accumulated local clock
(``prop_clk``/``acc_clk``, local quarter-ticks) across dispatches, so a
drifted trace split over many ``run_trace``/``step`` calls replays
bit-identically to one call. ``drift_eps`` is the ε the proposers' guard
discount assumes (``guard_q4 = ⌊lease_q4·(1-ε)/(1+ε)⌋``); rate planes
beyond that bound can — by design — trip the §4 owner-count alarm.

Two network models share the machinery: the synchronous zero-delay tick
(every round resolves in one tick) and the delayed in-flight message plane
(``netplane.py``). A scenario (or ``step`` call) carrying nonzero delay or
drop planes switches the engine onto the delayed model; it stays there
(messages may be in flight) with zero-delay defaults from then on.

The packed int32 layout bounds the clock: ballots must fit in
``state.PACK_MASK`` — ``run_trace``/``step``/``sweep`` raise once a trace
would cross ``state.max_pack_tick`` (≈ 4k ticks at P = 8; see
docs/perf.md).
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .netplane import NetPlaneState, init_netplane
from .ops import (
    _margin_scan_impl,
    _plane_tick,
    _window_scan_impl,
    lease_plane_tick,
    pad_restart_table,
    placed_restart_table,
    resolve_backend,
    strip_default_planes,
)
from .ref import owner_row
from .scenario import (
    CORRUPTION_PLANES,
    EXTEND_PLANES,
    PLANES,
    RESTART_PLANES,
    Scenario,
    TickInputs,
    check_bounds,
    check_placed,
    make_tick,
    plane_digest,
)
from .state import (
    DEFAULT_RATE,
    NO_PROPOSER,
    QUARTERS,
    check_pack_budget,
    guarded_lease_q4,
    init_state,
    lease_quarters,
    rate1_clock,
)

#: a host span on the profiler's clock; ``span.is_enabled()`` is True only
#: while a JAX profiler trace is on, and counters are computed only then
span = jax.profiler.TraceAnnotation

_DEPRECATED_STEP_KWARGS = (
    "per-plane LeaseArrayEngine.step arguments (attempt=, release=, "
    "acc_up=, delay=, drop=) are deprecated; build a TickInputs with "
    "make_tick(...) and pass it as the single argument"
)
_DEPRECATED_TRACE_PLANES = (
    "LeaseArrayEngine.run_trace with raw plane arrays is deprecated; "
    "pass a Scenario (Scenario.build(...) or Trace.scenario())"
)


@functools.lru_cache(maxsize=512)
def _static_pack_findings(
    t_end: int, n_proposers: int, n_acceptors: int, lease_q4: int,
    round_q4: int, guard_q4: Optional[int], max_delay: int, max_rate: int,
    clk_slack: int, max_restarts: int = 0, placed: bool = False,
) -> tuple[str, ...]:
    """Interval-analysis twin of ``state.check_pack_budget``: walk the
    traced delayed tick core (the conservative superset of the sync one)
    and bound EVERY int32 intermediate for replays up to ``t_end``. The
    hand check budgets only ballots and lease deadlines — this one also
    sees round horizons, clock sums and any future field the core grows.
    ``placed`` proves the core with per-cell restart columns (a
    placement's), ``max_restarts`` then the highest count of any node.
    Cached because the same protocol config is re-proved per dispatch."""
    from ..analysis.staticcheck.intervals import (
        TickConfig,
        analyze_tick_config,
    )

    cfg = TickConfig(
        t_end=t_end, n_proposers=n_proposers, n_acceptors=n_acceptors,
        lease_q4=lease_q4, round_q4=round_q4, guard_q4=guard_q4,
        max_delay=max_delay, max_rate=max_rate, clk_slack=clk_slack,
        max_restarts=max_restarts, placed=placed,
    )
    return tuple(str(f) for f in analyze_tick_config(cfg))


@functools.lru_cache(maxsize=None)
def _scenario_scanner(
    majority: int, lease_q4: int, round_q4: int, backend: str, sync: bool,
    guard_q4: int = None,
):
    """Jitted (state, net, t0, clk0, planes) -> (state, net, owners, counts).

    The pre-PR 4 per-tick scanner: ``lax.scan`` whose body is ONE
    ``lease_plane_tick`` — every plane crosses the scan boundary every
    tick. Kept as the dispatch-overhead baseline (benchmarks) and the
    cross-check that the fused window scan (``ops.lease_window_scan``,
    what ``run_trace`` uses) changes nothing but speed; both run the same
    packed tick math, so they agree bit-for-bit. The local-clock columns
    ``clk0 = (prop [P], acc [A])`` ride the scan carry here (the fused
    path precomputes them as prefix-sum planes instead) — bit-identical
    accumulation either way, since everything is int32.
    """
    if guard_q4 is None:
        guard_q4 = lease_q4

    def scan_fn(state, net, t0, clk0, planes):
        if clk0 is None:  # the rate-1 reading at t0, like ops' default
            clk0 = (
                rate1_clock(t0, state.n_proposers),
                rate1_clock(t0, state.n_acceptors),
            )

        def body(carry, xs):
            st, nt, t, pc, ac = carry
            st, nt, count = lease_plane_tick(
                st, nt, t, TickInputs(xs),
                majority=majority, lease_q4=lease_q4, round_q4=round_q4,
                guard_q4=guard_q4, clk0=(pc, ac),
                backend=backend, sync=sync,
            )
            # a rate plane missing from a hand-rolled dict means the
            # drift-free step, like ops._local_clock_planes' contract
            carry = (
                st, nt, t + 1,
                pc + xs.get("prop_rate", DEFAULT_RATE),
                ac + xs.get("acc_rate", DEFAULT_RATE),
            )
            return carry, (owner_row(st), count)

        (state, net, _, _, _), (owners, counts) = jax.lax.scan(
            body, (state, net, t0, clk0[0], clk0[1]), planes
        )
        return state, net, owners, counts

    jitted = jax.jit(scan_fn)

    def strip_and_scan(state, net, t0, clk0, planes):
        # all-default corruption/restart/extends planes are the honest
        # path: drop them host-side (same contract as
        # ops.lease_window_scan) so the sync step never sees them and the
        # honest trace stays fault-free
        for k in RESTART_PLANES:
            v = planes.get(k)
            if (
                v is not None and not isinstance(v, jax.core.Tracer)
                and np.asarray(v).any()
            ):
                raise ValueError(
                    "the per-tick scanner cannot accumulate restart "
                    "history across ticks; replay restart scenarios "
                    "through run_trace/lease_window_scan instead"
                )
        planes = {
            k: v for k, v in planes.items()
            if not (
                k in CORRUPTION_PLANES + RESTART_PLANES + EXTEND_PLANES
                and not isinstance(v, jax.core.Tracer)
                and (np.asarray(v) == PLANES[k].default).all()
            )
        }
        return jitted(state, net, t0, clk0, planes)

    return strip_and_scan


class SweepResult(NamedTuple):
    """Per-scenario results of one :meth:`LeaseArrayEngine.sweep` dispatch.

    ``max_owner_count`` is the §4 verdict: >1 anywhere means some tick of
    that scenario would have produced a second simultaneous believer.
    """

    max_owner_count: np.ndarray  # [B] max per-cell owner count over T x N
    owned_frac: np.ndarray       # [B] fraction of (tick, cell) slots owned
    final_owners: np.ndarray     # [B, N] owner row after the last tick
    owners: Optional[np.ndarray] = None  # [B, T, N] iff collect="owners"
    counts: Optional[np.ndarray] = None  # [B, T, N] iff collect="owners"
    #: [B] int32 per margin component iff collect="margins" (see
    #: ops._margin_scan_impl for the definitions; MARGIN_BIG = never close)
    margins: Optional[dict] = None


def _cell_sharding_specs(planes_keys):
    """shard_map PartitionSpecs for a (state, net, t0, clk0, rst0, planes,
    rtab) call: every state/output plane splits on its trailing cell axis;
    scenario planes split iff their registered dims carry the cell axis
    "N" (acc_up, the [T, P, A] link matrices and the clock-rate planes are
    replicated, as are the [P]/[A] clock offsets and restart history); a
    placed replay's [F, A, N] restart table splits on its cell axis."""
    from jax.sharding import PartitionSpec as P

    from .scenario import PLANES

    cells = P(None, "cells")
    plane_specs = {
        k: (P(None, "cells") if "N" in PLANES[k].dims else P())
        for k in planes_keys
    }
    # the clk0/rst0 slots take bare prefix specs: they cover both the
    # per-node tuples and the None fast path (no leaves) identically; the
    # grid-step counts are summed over the devices
    return (
        (cells, cells, P(), P(), P(), plane_specs, P(None, None, "cells")),
        (cells, cells, cells, cells, P()),
    )


@functools.lru_cache(maxsize=None)
def _trace_fn(
    majority: int, lease_q4: int, round_q4: int, guard_q4: int, backend: str,
    sync: bool, block_n: int, window: int, n_devices: int, planes_keys: tuple,
    restart_guard: bool = True, skip_stable: bool = True,
    max_lease_q4: Optional[int] = None,
):
    """The fused scenario replay, jitted; with >1 device the cell axis is
    shard_map-ed across a 1-D device mesh (cells are independent — the
    tick math never reduces across N), so a trace uses every device.
    Returns ``_window_scan_impl``'s five outputs, the grid-step counts
    summed over the devices. ``rtab`` is a placed replay's restart table
    (None otherwise)."""

    def run(state, net, t0, clk0, rst0, planes, rtab):
        *out, steps = _window_scan_impl(
            state, net, t0, clk0, rst0, planes,
            majority=majority, lease_q4=lease_q4, round_q4=round_q4,
            guard_q4=guard_q4, backend=backend, sync=sync, block_n=block_n,
            window=window, restart_guard=restart_guard,
            skip_stable=skip_stable, rtab=rtab, max_lease_q4=max_lease_q4,
        )
        if n_devices > 1:
            steps = jax.lax.psum(steps, "cells")
        return (*out, steps)

    if n_devices > 1:
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:n_devices]), ("cells",))
        in_specs, out_specs = _cell_sharding_specs(planes_keys)
        sharded = jax.shard_map(
            run, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

        def run(state, net, t0, clk0, rst0, planes, rtab):
            # an uneven split pads the cell axis with empty cells (the
            # same sentinels init_state/init_netplane and the plane
            # registry use), so every device gets an equal share
            n = state.n_cells
            state, net, planes = _pad_cell_axis(state, net, planes, n_devices)
            if rtab is not None:
                rtab = pad_restart_table(rtab, n_devices)
            *out, steps = sharded(state, net, t0, clk0, rst0, planes, rtab)
            if state.n_cells != n:
                out = jax.tree.map(lambda a: a[..., :n], out)
            return (*out, steps)

    return jax.jit(run)


def _pad_cell_axis(state, net, planes: dict, multiple: int):
    """Append empty cells until the cell axis divides by ``multiple``:
    fresh ``init_state``/``init_netplane`` columns and each cell plane's
    registered default (no attempt, release or extend)."""
    pad = (-state.n_cells) % multiple
    if pad == 0:
        return state, net, planes
    A, P = state.n_acceptors, state.n_proposers
    cat = lambda a, b: jnp.concatenate([a, b], axis=-1)
    state = jax.tree.map(cat, state, init_state(pad, A, P))
    net = jax.tree.map(cat, net, init_netplane(pad, A))
    planes = {
        k: (
            jnp.pad(
                v, [(0, 0)] * (v.ndim - 1) + [(0, pad)],
                constant_values=PLANES[k].default,
            )
            if "N" in PLANES[k].dims else v
        )
        for k, v in planes.items()
    }
    return state, net, planes


@functools.lru_cache(maxsize=None)
def _sweep_fn(
    majority: int, lease_q4: int, round_q4: int, guard_q4: int, backend: str,
    sync: bool, block_n: int, window: int, collect: str, n_devices: int,
    restart_guard: bool = True, skip_stable: bool = True,
    max_lease_q4: Optional[int] = None,
):
    """One-dispatch batched scenario replay: vmap over the stacked planes
    (state broadcast), reductions inside the jit so a summary sweep never
    materializes [B, T, N] outputs, shard_map over the device mesh when
    more than one device is visible. The planes dict arrives split in two
    so that in ``collect="owners"`` mode only the [B, T, N] attempts/
    releases leaves are donated — exactly the buffers XLA can reuse for
    the owners/counts cubes; a summary sweep's outputs are [B]-shaped, so
    nothing could reuse any plane and donating would only warn."""

    def one(state, net, t0, clk0, rst0, cell_planes, rest_planes):
        if collect == "margins":
            # the margin mode always runs the delayed jnp oracle scan —
            # the backends agree bit-for-bit, so margins are backend-free
            owners, counts, margins = _margin_scan_impl(
                state, net, t0, clk0, {**cell_planes, **rest_planes},
                majority=majority, lease_q4=lease_q4, round_q4=round_q4,
                guard_q4=guard_q4, rst0=rst0, restart_guard=restart_guard,
                max_lease_q4=max_lease_q4,
            )
        else:
            margins = None
            _, _, owners, counts, _ = _window_scan_impl(
                state, net, t0, clk0, rst0, {**cell_planes, **rest_planes},
                majority=majority, lease_q4=lease_q4, round_q4=round_q4,
                guard_q4=guard_q4, backend=backend, sync=sync,
                block_n=block_n, window=window, restart_guard=restart_guard,
                skip_stable=skip_stable, max_lease_q4=max_lease_q4,
            )
        out = {
            "max_owner_count": counts.max(),
            "owned_frac": (owners >= 0).mean(),
            "final_owners": owners[-1],
        }
        if collect == "owners":
            out["owners"] = owners
            out["counts"] = counts
        if collect == "margins":
            out["margins"] = margins
        return out

    batched = jax.vmap(one, in_axes=(None, None, None, None, None, 0, 0))
    if n_devices > 1:
        from jax.sharding import PartitionSpec as P

        batched = jax.shard_map(
            batched, mesh=_batch_mesh(n_devices),
            in_specs=(P(), P(), P(), P(), P(), P("b"), P("b")),
            out_specs=P("b"),
            check_vma=False,
        )
    donate = (5,) if collect == "owners" else ()
    return jax.jit(batched, donate_argnums=donate)


def _batch_mesh(n_devices: int):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n_devices]), ("b",))


def _shard_batch(plane, n_devices: int):
    """A stacked [B, ...] sweep plane split over ``n_devices`` on upload:
    padded on the host to a device multiple with copies of scenario 0
    (their results are sliced off), then each device receives only its
    share. The buffer is always fresh, so donating it is safe."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    v = np.asarray(plane)
    pad = (-v.shape[0]) % n_devices
    if pad:
        v = np.concatenate([v, np.repeat(v[:1], pad, axis=0)])
    return jax.device_put(v, NamedSharding(_batch_mesh(n_devices), P("b")))


@functools.lru_cache(maxsize=None)
def _bounds_fn(planes_keys: tuple):
    """Jitted planes -> int32 [K, 2]: the (min, max) of each of the
    ``planes_keys`` planes, in one dispatch. An empty plane reads (int32
    max, int32 min), which ``check_bounds`` passes, as the host checks
    pass an empty array."""
    lo, hi = np.iinfo(np.int32).min, np.iinfo(np.int32).max

    def bounds(planes):
        return jnp.stack([
            jnp.stack([jnp.min(v, initial=hi), jnp.max(v, initial=lo)])
            for v in (planes[k].astype(jnp.int32) for k in planes_keys)
        ])

    return jax.jit(bounds)


def _device_checked(planes: dict) -> tuple:
    """The bounded [T, N] planes (``PlaneSpec.bounded`` with the cell
    axis) whose bounds ``run_trace`` reads on the device after the upload
    instead of in host passes before it; the upload keeps their values
    whole (an int32 or narrower dtype; a wider plane is checked on the
    host)."""
    return tuple(
        k for k, v in planes.items()
        if k in PLANES and "N" in PLANES[k].dims and PLANES[k].bounded
        and np.can_cast(np.asarray(v).dtype, np.int32)
    )


@functools.lru_cache(maxsize=None)
def _placed_table_fn(deaf_q4: int):
    """``ops.placed_restart_table``, jitted: a placed replay's per-cell
    restart table from the node rows and the placement, on the device."""
    return jax.jit(functools.partial(placed_restart_table, deaf_q4=deaf_q4))


def _placed_counters(place, arst, prst, n_ticks: int, t0: int,
                     deaf_q4: int, deaf0) -> dict:
    """A placed replay's restarts, counted from its node rows ``[T, K]``
    (None = no restarts) and ``[A, N]`` placement: ``node_restarts``,
    the (tick, node) pairs at which a node's acceptor or proposer
    restarts; ``restarted_cells``, the acceptor restarts of the cells'
    replicas (a node's restarts times the replica slots placed on it);
    ``deaf_cell_ticks``, the (tick, cell, slot) triples at which a
    replica's acceptor is deaf (the history ``deaf0`` [K] included)."""
    K = len(deaf0)
    per_node = np.bincount(np.asarray(place).ravel(), minlength=K)
    none = np.zeros((n_ticks, K), bool)
    a = none if arst is None else np.asarray(arst) > 0
    p = none if prst is None else np.asarray(prst) > 0
    ticks = t0 + np.arange(n_ticks, dtype=np.int64)[:, None]
    minted = np.where(a, 4 * ticks + deaf_q4, 0)
    until = np.maximum(np.maximum.accumulate(minted, axis=0),
                       np.asarray(deaf0, np.int64)[None, :])
    deaf = (4 * ticks < until) if deaf_q4 else none
    return {
        "node_restarts": int((a | p).sum()),
        "restarted_cells": int(a.sum(axis=0) @ per_node),
        "deaf_cell_ticks": int(deaf.sum(axis=0) @ per_node),
    }


def _grid_counts(steps) -> dict:
    """A dispatch's window-kernel grid steps, read back from the device:
    ``windows`` (cell blocks × windows) and ``skipped`` (those that took
    the quiescent path); both 0 where no delayed window kernel ran."""
    skipped, windows = np.asarray(steps).tolist()
    return {"windows": windows, "skipped": skipped}


class LeaseArrayEngine:
    def __init__(
        self,
        n_cells: int,
        *,
        n_acceptors: int = 5,
        n_proposers: int = 8,
        lease_ticks: int = 3,
        round_ticks: int = 1,
        drift_eps: float = 0.0,
        backend: str | None = None,
        window: int = 16,
        restart_guard: bool = True,
        skip_stable: bool = True,
        max_lease_ticks: Optional[int] = None,
    ) -> None:
        if n_acceptors < 1 or n_proposers < 1:
            raise ValueError("need at least one acceptor and one proposer")
        if max_lease_ticks is None:
            max_lease_ticks = lease_ticks
        if max_lease_ticks < lease_ticks:
            raise ValueError(
                f"max_lease_ticks={max_lease_ticks} is shorter than the "
                f"lease ({lease_ticks} ticks): M bounds every lease (§2)"
            )
        self.n_cells = n_cells
        self.n_acceptors = n_acceptors
        self.n_proposers = n_proposers
        self.majority = n_acceptors // 2 + 1
        self.lease_ticks = lease_ticks
        self.lease_q4 = lease_quarters(lease_ticks)
        #: M, the longest lease any proposer may ask for (§2-3): a
        #: restarted acceptor stays deaf this long on its own clock
        self.max_lease_ticks = int(max_lease_ticks)
        self.max_lease_q4 = lease_quarters(self.max_lease_ticks)
        self.round_ticks = round_ticks
        self.round_q4 = QUARTERS * int(round_ticks)
        #: ε, the assumed clock-drift bound (§4): proposers discount their
        #: own lease timer to T·(1-ε)/(1+ε) so a slow believer never
        #: outlives a fast acceptor's timer. ε=0 = the exact rate-1 engine.
        self.drift_eps = float(drift_eps)
        self.guard_q4 = guarded_lease_q4(self.lease_q4, self.drift_eps)
        #: the platform's choice unless named (``ops.resolve_backend``)
        self.backend = resolve_backend(backend)
        self.window = int(window)
        with span("lease.init"):  # the fleet's planes, made on the device
            self.state = init_state(n_cells, n_acceptors, n_proposers)
            self.net: NetPlaneState = init_netplane(n_cells, n_acceptors)
            self.last_owner_count = jnp.zeros(n_cells, jnp.int32)
        self.t = 0
        # accumulated local clocks (local quarter-ticks at global tick t);
        # advanced by the scenario's prop_rate/acc_rate planes each tick
        self.prop_clk = np.zeros(n_proposers, np.int32)
        self.acc_clk = np.zeros(n_acceptors, np.int32)
        # flips True on the first delayed step; once messages may be in
        # flight, every later tick must run the delayed model too
        self._netplane_active = False
        #: §2 diskless deaf window honored? False is the chaos suite's
        #: negative control: restarted acceptors answer immediately with
        #: blank state, which provably breaks §4 under crash schedules
        self.restart_guard = bool(restart_guard)
        #: quiescence fast path in the Pallas window kernels: stable
        #: (block, window) pairs collapse to owner-row broadcasts.
        #: Bit-identical results either way; False is the A/B bench control
        self.skip_stable = bool(skip_stable)
        # restart history carried across dispatches (mirrors the clocks):
        # per-proposer restart counters and each acceptor's deaf-until
        # reading on ITS local clock. flips _restart_active once any
        # restart plane fires so the restart-mode ballot encoding (the
        # RESTART_SHIFT carve) never switches off mid-trace
        self._rc = np.zeros(n_proposers, np.int32)
        self._deaf_until = np.zeros(n_acceptors, np.int32)
        self._restart_active = False
        # a placed deployment's (run_trace with a placement, pinned by its
        # first restart): the [A, N] map, and per node its restart count
        # and deaf-until in global quarter-ticks
        self._placement = None
        self._node_rc: Optional[np.ndarray] = None
        self._node_du: Optional[np.ndarray] = None

    # -------------------------------------------------------- packing budget
    def _max_restarts(self, prop_restart=None, n_nodes=None) -> int:
        """The pack-budget ``max_restarts`` charge for a dispatch that may
        add ``prop_restart`` ([T, P], [B, T, P] or a single [P] row) to the
        carried counters — 0 while the engine has never seen a restart
        (the honest encoding), else at least 1 so the RESTART_SHIFT carve
        is always charged once restart mode is on. With ``n_nodes`` (a
        placement) ``prop_restart`` holds [T, K] node rows, added to the
        nodes' counts: the charge is the highest count of any node."""
        width = self.n_proposers
        rc_end = self._rc.astype(np.int64)
        if n_nodes is not None:
            width = n_nodes
            rc_end = (np.zeros(n_nodes, np.int64) if self._node_rc is None
                      else self._node_rc.astype(np.int64))
        seen = self._restart_active
        if prop_restart is not None:
            prst = np.asarray(prop_restart, np.int64)
            if prst.size:
                if prst.ndim >= 3:
                    # [B, T, P] stack: each scenario replays independently,
                    # so charge the worst per-scenario total, not the sum
                    add = (
                        prst.reshape(prst.shape[0], -1, width)
                        .sum(axis=1).max(axis=0)
                    )
                else:
                    add = prst.reshape(-1, width).sum(axis=0)
                rc_end = rc_end + add
                seen = seen or bool(prst.any())
        if not seen:
            return 0
        return max(1, int(rc_end.max(initial=0)))

    def _check_pack_budget(
        self, t_end: int, max_delay: int = 0, max_rate: int = QUARTERS,
        max_restarts: int = 0,
    ) -> None:
        max_rate = max(int(max_rate), QUARTERS)
        clk_max = int(max(self.prop_clk.max(), self.acc_clk.max(), 0))
        check_pack_budget(
            t_end, self.n_proposers, self.lease_q4, max_delay,
            max_rate=max_rate,
            clk_slack=max(0, clk_max - max_rate * self.t),
            max_restarts=max_restarts,
        )

    def _static_bound_check(
        self, t_end: int, max_delay: int = 0, max_rate: int = QUARTERS,
        max_restarts: int = 0, placed: bool = False,
    ) -> None:
        """Run the leaselint interval analysis host-side before a bulk
        dispatch. Complements ``_check_pack_budget``: the hand bound is
        skipped under tracing and blind to everything but ballots and
        lease deadlines, while this proves every traced-core intermediate
        stays in int32. Fails closed: a finding (an actual overflow proof)
        raises, and so does the analyzer itself crashing — a dispatch never
        runs with the proof silently off."""
        max_rate = max(int(max_rate), QUARTERS)
        clk_max = int(max(self.prop_clk.max(), self.acc_clk.max(), 0))
        try:
            findings = _static_pack_findings(
                int(t_end), self.n_proposers, self.n_acceptors,
                self.lease_q4, self.round_q4, self.guard_q4,
                int(max_delay), max_rate,
                max(0, clk_max - max_rate * self.t),
                int(max_restarts), bool(placed),
            )
        except Exception as e:
            raise RuntimeError(
                f"static pack-budget analysis failed, so the {t_end}-tick "
                f"replay is refused: {e!r}"
            ) from e
        if findings:
            raise ValueError(
                f"static analysis refused a {t_end}-tick replay — the "
                f"traced tick core can overflow where the runtime check "
                f"does not look:\n  " + "\n  ".join(findings)
            )

    def _clk0(self):
        """The engine's local-clock offsets for a dispatch — or None while
        every clock still equals the rate-1 reading ``4t`` (an engine that
        never saw a drifted plane), so the jitted scan derives the default
        clocks in-graph and the host-driven step path pays no per-tick
        clock uploads."""
        t4 = QUARTERS * self.t
        if (self.prop_clk == t4).all() and (self.acc_clk == t4).all():
            return None
        return jnp.asarray(self.prop_clk), jnp.asarray(self.acc_clk)

    def _rst0(self):
        """The engine's restart history for a dispatch — or None while no
        restart plane has ever fired, so honest replays trace the
        restart-free tick core (and the honest ballot encoding) with zero
        extra uploads. Once active, always a concrete (rc [P],
        deaf_until [A]) pair: mode must stay pinned even through quiet
        dispatches so ballot encodings never mix mid-trace."""
        if not self._restart_active:
            return None
        return jnp.asarray(self._rc), jnp.asarray(self._deaf_until)

    def _advance_restarts(self, acc_restart, prop_restart, acc_rate) -> None:
        """Fold a dispatched schedule's restart planes into the carried
        history. MUST run before ``_advance_clocks``: deaf-until deadlines
        are minted against each acceptor's local clock reading AT the
        restart tick (``self.acc_clk`` + the exclusive rate prefix), the
        same readings ``ops._restart_planes`` derives in-graph."""
        prst = np.asarray(prop_restart, np.int64).reshape(
            -1, self.n_proposers
        )
        self._rc = (self._rc + prst.sum(axis=0)).astype(np.int32)
        arst = np.asarray(acc_restart, np.int64).reshape(
            -1, self.n_acceptors
        )
        rate = np.asarray(acc_rate, np.int64).reshape(-1, self.n_acceptors)
        aclk = self.acc_clk.astype(np.int64) + np.concatenate(
            [np.zeros((1, self.n_acceptors), np.int64),
             np.cumsum(rate, axis=0)[:-1]]
        )
        minted = np.where(arst > 0, aclk + self.max_lease_q4, 0)
        self._deaf_until = np.maximum(
            self._deaf_until, minted.max(axis=0, initial=0)
        ).astype(np.int32)

    def _advance_node_restarts(self, arst, prst, t0: int) -> None:
        """Fold a placed dispatch's [T, K] node rows (None = none) into
        the nodes' carried restart counts and deaf-until readings."""
        for rows in (arst, prst):
            if rows is not None:
                ticks = t0 + np.arange(len(rows), dtype=np.int64)
                break
        else:
            return
        if prst is not None:
            self._node_rc = (
                self._node_rc + np.asarray(prst, np.int64).sum(axis=0)
            ).astype(np.int32)
        if arst is not None:
            minted = np.where(
                np.asarray(arst) > 0, 4 * ticks[:, None] + self.max_lease_q4,
                0,
            )
            self._node_du = np.maximum(
                self._node_du, minted.max(axis=0, initial=0)
            ).astype(np.int32)

    def _advance_clocks(self, prop_rate, acc_rate) -> None:
        """Accumulate the scenario's rate planes ([T, P]/[T, A] or one
        tick's [P]/[A] rows) into the engine's local clocks."""
        self.prop_clk = (
            self.prop_clk
            + np.asarray(prop_rate, np.int64).reshape(-1, self.n_proposers)
            .sum(axis=0)
        ).astype(np.int32)
        self.acc_clk = (
            self.acc_clk
            + np.asarray(acc_rate, np.int64).reshape(-1, self.n_acceptors)
            .sum(axis=0)
        ).astype(np.int32)

    # ------------------------------------------------------------ one tick
    def step(
        self, tick=None, release=None, acc_up=None, delay=None, drop=None,
        *, attempt=None,
    ) -> np.ndarray:
        """Advance one tick; returns the per-cell owner row (id or -1).

        Pass a :class:`TickInputs` (``make_tick(...)``) — or the legacy
        per-plane kwargs, which build one: ``delay``/``drop`` are ``[P, A]``
        link matrices (legacy ``[A]`` broadcasts over P) for legs sent this
        tick, in whole ticks; passing either kwarg — or a tick whose
        delay/drop planes are nonzero — switches the engine onto the
        delayed in-flight model permanently. (For backward compatibility
        the legacy planes are also accepted positionally — the first
        positional argument doubles as the bare attempt row.)

        Slot-isolation precondition (netplane.py): a new attempt on a cell
        overwrites that cell's in-flight request slots, so attempts on the
        SAME cell must be spaced more than ``4 * max_delay`` ticks apart
        while older messages may still be in flight; same for releases
        with ``max_delay`` (``random_trace`` enforces both; hand-driven
        schedules must too).
        """
        with span("lease.step") as step_span:
            with span("lease.validate"):
                if self._placement is not None:
                    raise ValueError(
                        "this engine replays a placed deployment; a "
                        "placement is replayed whole, by run_trace"
                    )
                if tick is not None and not isinstance(tick, TickInputs):
                    if attempt is not None:
                        raise TypeError(
                            "pass the attempt row positionally or as "
                            "attempt=, not both"
                        )
                    attempt, tick = tick, None  # legacy positional row
                elif tick is not None and any(
                    x is not None
                    for x in (attempt, release, acc_up, delay, drop)
                ):
                    raise TypeError(
                        "pass planes inside the TickInputs, not alongside it"
                    )
                if tick is None:
                    if any(
                        x is not None
                        for x in (attempt, release, acc_up, delay, drop)
                    ):
                        warnings.warn(
                            _DEPRECATED_STEP_KWARGS, DeprecationWarning,
                            stacklevel=2,
                        )
                    # validates ghost proposer ids, shapes, dtypes
                    tick = make_tick(
                        n_cells=self.n_cells, n_acceptors=self.n_acceptors,
                        n_proposers=self.n_proposers,
                        attempts=attempt, releases=release, acc_up=acc_up,
                        delay=delay, drop=drop,
                    )
                    if delay is not None or drop is not None:
                        # only once validation passed
                        self._netplane_active = True
                else:
                    tick.validate_for(
                        n_cells=self.n_cells, n_acceptors=self.n_acceptors,
                        n_proposers=self.n_proposers,
                    )
                    if (
                        np.asarray(tick.delay).any()
                        or np.asarray(tick.drop).any()
                        or tick.corrupted
                        or tick.restarted
                        or tick.extended
                    ):
                        self._netplane_active = True
                self._check_pack_budget(
                    self.t + 1,
                    int(np.asarray(tick.delay).max(initial=0)),
                    max(
                        int(np.asarray(tick.prop_rate).max(initial=0)),
                        int(np.asarray(tick.acc_rate).max(initial=0)),
                    ),
                    self._max_restarts(tick.prop_restart),
                )
                if tick.restarted:
                    # crashes imply in-flight state (restart mode is
                    # delayed-only) and pin the restart-mode ballot
                    # encoding from here on
                    self._netplane_active = True
                    self._restart_active = True
            with span("lease.dispatch"):
                (self.state, self.net, self.last_owner_count,
                 steps) = _plane_tick(
                    self.state, self.net, self.t, tick,
                    majority=self.majority, lease_q4=self.lease_q4,
                    round_q4=self.round_q4, guard_q4=self.guard_q4,
                    clk0=self._clk0(), rst0=self._rst0(),
                    restart_guard=self.restart_guard, backend=self.backend,
                    sync=not self._netplane_active, window=self.window,
                    skip_stable=self.skip_stable,
                    max_lease_q4=self.max_lease_q4,
                )
                self.t += 1
                if self._restart_active:
                    self._advance_restarts(
                        tick.acc_restart, tick.prop_restart, tick.acc_rate
                    )
                self._advance_clocks(tick.prop_rate, tick.acc_rate)
                owners = owner_row(self.state)
            with span("lease.wait"):
                owners.block_until_ready()
            with span("lease.download"):
                owners = np.asarray(owners)
            if span.is_enabled():
                step_span.set_metadata(**_grid_counts(steps))
            return owners

    # ---------------------------------------------------------- validation
    def _coerce_scenario(self, scenario, releases, acc_up, delay, drop):
        """The call's Scenario, checked on the host but for the bounds of
        its [T, N] planes: ``run_trace`` reads those on the device once
        they are uploaded (``_device_checked``), and checks a placement in
        its ``lease.placement`` span. The legacy raw-array form builds its
        Scenario, which checks every plane on the host."""
        if not isinstance(scenario, Scenario):
            scenario = Scenario.build(
                n_cells=self.n_cells, n_acceptors=self.n_acceptors,
                n_proposers=self.n_proposers,
                attempts=scenario, releases=releases, acc_up=acc_up,
                delay=delay, drop=drop,
            )
        else:
            scenario.validate_for(
                n_cells=self.n_cells, n_acceptors=self.n_acceptors,
                n_proposers=self.n_proposers,
                skip_bounds=_device_checked(scenario.planes) + ("placement",),
            )
        return scenario

    def _pick_model(self, netplane, delayed: bool) -> bool:
        """Returns sync=True/False; the engine itself is left untouched
        (``run_trace`` pins the netplane, ``_netplane_active = not sync``,
        once its checks have passed)."""
        if netplane is False and (delayed or self._netplane_active):
            raise ValueError(
                "netplane=False but the scenario carries nonzero delay/drop, "
                "corruption or restart planes (or messages are already in "
                "flight); the synchronous model cannot honor them"
            )
        wants_net = bool(netplane) or (netplane is None and delayed)
        return not (wants_net or self._netplane_active)

    # ------------------------------------------------------------ bulk path
    def run_trace(
        self, scenario=None, releases=None, acc_up=None, delay=None,
        drop=None, *, netplane=None, attempts=None,
    ):
        """Replay a [T]-tick :class:`Scenario` in one fused dispatch.

        The first argument is a ``Scenario`` (``Scenario.build(...)``); the
        legacy form — a [T, N] attempts array (positionally or as the
        ``attempts=`` keyword) plus per-plane kwargs, with ``delay``/
        ``drop`` as [T, A] or [T, P, A] schedules — builds one (and is
        validated identically, ghost proposer ids included).

        ``netplane`` picks the network model: None (default) auto-selects
        the delayed in-flight model iff the scenario carries nonzero
        delay/drop planes (or the engine is already on it); True forces it
        (zero-delay scenarios are bit-identical either way); False forces
        the synchronous step — the sync tick cannot honor fault planes, so
        a delayed scenario (or an engine already on the in-flight model)
        raises rather than silently dropping them.
        Returns (owners [T, N], owner_counts [T, N]) as numpy; the
        engine's state/tick advance past the trace.

        The checks run in two ``lease.validate`` spans: on the host before
        the upload (shapes, the planes without a cell axis, the pack
        budget), and on the device after it, where one reduction reads the
        bounds of the [T, N] proposer-id planes. A refused scenario raises
        before the dispatch and leaves the engine as it was.
        """
        tracing = span.is_enabled()
        with span("lease.run_trace") as run_span:
            with span("lease.validate"):
                if attempts is not None:
                    if scenario is not None:
                        raise TypeError(
                            "pass the attempts plane positionally or as "
                            "attempts=, not both"
                        )
                    scenario = attempts  # legacy keyword call sites
                if not isinstance(scenario, Scenario):
                    warnings.warn(
                        _DEPRECATED_TRACE_PLANES, DeprecationWarning,
                        stacklevel=2,
                    )
                scenario = self._coerce_scenario(
                    scenario, releases, acc_up, delay, drop
                )
                T = scenario.n_ticks
                # all-default corruption/restart/extends planes stay
                # host-side: the honest replay never compiles the faulted
                # tick variants (bit-identical jaxpr, zero extra uploads);
                # once restart mode is pinned, rst0 (not the planes) keeps
                # it on across quiet dispatches. A stripped plane is valid:
                # it holds nothing but its default. A placement with no
                # restart and no placed history goes with them
                kept = strip_default_planes(
                    scenario.planes,
                    restart_history=self._placement is not None,
                )
                restarted = any(k in kept for k in RESTART_PLANES)
                placed = "placement" in kept
                self._check_placed_engine(placed, kept.get("placement"))
                sync = self._pick_model(
                    netplane,
                    scenario.delayed or restarted or placed or any(
                        k in kept for k in CORRUPTION_PLANES + EXTEND_PLANES
                    ),
                )
                if T == 0:
                    self._netplane_active = not sync
                    empty = np.zeros((0, self.n_cells), np.int32)
                    return empty, empty.copy()
                dmax = int(np.asarray(scenario.delay).max(initial=0))
                rmax = max(
                    int(np.asarray(scenario.prop_rate).max(initial=0)),
                    int(np.asarray(scenario.acc_rate).max(initial=0)),
                )
                n_nodes = scenario.n_nodes if placed else None
                mr = self._max_restarts(scenario.prop_restart, n_nodes)
                self._check_pack_budget(self.t + T, dmax, rmax, mr)
                self._static_bound_check(self.t + T, dmax, rmax, mr, placed)
            rtab = node_rows = None
            if placed:
                with span("lease.placement"):
                    # the map's host checks, its upload and the node rows',
                    # and the per-cell restart table derived on the device
                    place = kept.pop("placement")
                    node_rows = tuple(kept.pop(k, None) for k in RESTART_PLANES)
                    check_placed(
                        scenario.planes, n_nodes, self.n_acceptors,
                        self.n_proposers, "Scenario",
                    )
                    hist = (
                        (jnp.zeros(n_nodes, jnp.int32),) * 2
                        if self._node_rc is None
                        else (jnp.asarray(self._node_rc),
                              jnp.asarray(self._node_du))
                    )
                    rtab = _placed_table_fn(self._deaf_q4)(
                        hist,
                        *(None if r is None else jnp.asarray(r)
                          for r in node_rows),
                        jnp.asarray(place), jnp.int32(self.t),
                    )
                    if tracing:  # the span ends with the table made
                        jax.block_until_ready(rtab)
            with span("lease.upload"):
                planes = {k: jnp.asarray(v) for k, v in kept.items()}
                if tracing:  # the span ends with the planes on the device
                    jax.block_until_ready(planes)
            with span("lease.validate") as check_span:
                # the [T, N] planes' bounds, read on the device where they
                # now are: a few int32s come back, not passes over them
                checked = _device_checked(kept)
                if checked:
                    bounds = np.asarray(
                        _bounds_fn(checked)({k: planes[k] for k in checked})
                    )
                    for k, (lo, hi) in zip(checked, bounds.tolist()):
                        check_bounds(
                            PLANES[k], lo, hi, self.n_proposers, "Scenario"
                        )
                if tracing:
                    check_span.set_metadata(
                        planes=len(checked),
                        bytes=sum(planes[k].nbytes for k in checked),
                    )
            with span("lease.dispatch"):
                # the checks passed: only now does the engine change
                self._netplane_active = not sync
                if restarted:
                    # pins the restart ballot encoding
                    self._restart_active = True
                t0 = self.t
                if placed and self._node_rc is None:
                    self._placement = place
                    self._node_rc = np.zeros(n_nodes, np.int32)
                    self._node_du = np.zeros(n_nodes, np.int32)
                deaf0 = self._node_du
                fn = _trace_fn(
                    self.majority, self.lease_q4, self.round_q4, self.guard_q4,
                    self.backend, sync, 512, self.window, len(jax.devices()),
                    tuple(planes),
                    self.restart_guard, self.skip_stable, self.max_lease_q4,
                )
                self.state, self.net, owners, counts, steps = fn(
                    self.state, self.net, jnp.int32(t0), self._clk0(),
                    None if placed else self._rst0(), planes, rtab,
                )
                self.t += int(T)
                if placed:
                    self._advance_node_restarts(*node_rows, t0)
                elif self._restart_active:
                    self._advance_restarts(
                        scenario.acc_restart, scenario.prop_restart,
                        scenario.acc_rate,
                    )
                self._advance_clocks(scenario.prop_rate, scenario.acc_rate)
                self.last_owner_count = counts[-1]
            with span("lease.wait"):
                jax.block_until_ready((owners, counts))
            with span("lease.download"):
                owners, counts = np.asarray(owners), np.asarray(counts)
            if tracing:
                run_span.set_metadata(**_grid_counts(steps))
                if placed:
                    run_span.set_metadata(**_placed_counters(
                        place, *node_rows, T, t0, self._deaf_q4, deaf0,
                    ))
            return owners, counts

    @property
    def _deaf_q4(self) -> int:
        """The post-restart deaf window, M in quarter-ticks of the
        restarted acceptor's clock (0 under ``restart_guard=False``)."""
        return self.max_lease_q4 if self.restart_guard else 0

    def _check_placed_engine(self, placed: bool, place) -> None:
        """Refuse a dispatch that would mix placed and per-slot restart
        histories: a placed engine (pinned by its first placed dispatch)
        takes only its own placement, and a placement needs an engine
        with no per-slot restart history whose clocks run at the rate-1
        step."""
        if self._placement is not None:
            if not placed:
                raise ValueError(
                    "this engine replays a placed deployment; pass the "
                    "scenario's placement with every run_trace"
                )
            if place is not self._placement and not np.array_equal(
                place, self._placement
            ):
                raise ValueError(
                    "this engine replays a placed deployment with another "
                    "placement; a placement cannot change mid-trace"
                )
        elif placed:
            if self._restart_active:
                raise ValueError(
                    "a placement needs an engine without per-slot restart "
                    "history; replay the placed deployment on a fresh engine"
                )
            if self._clk0() is not None:
                raise ValueError(
                    "a placement needs clocks at the rate-1 reading; this "
                    "engine replayed drifted clock planes"
                )

    # ----------------------------------------------------------- the sweep
    def sweep(
        self, scenarios, *, netplane=None, collect: str = "summary",
        verify: bool = True, backend: Optional[str] = None, tags=None,
    ) -> SweepResult:
        """Replay a BATCH of scenarios in ONE dispatch — "replay 10k fault
        scenarios" as a single call.

        ``scenarios`` is a list of same-geometry same-length
        :class:`Scenario`\\ s or an already-stacked ``Scenario.stack``
        pytree ([B, T, ...] planes). Every scenario starts from THIS
        engine's current state/tick; the engine itself is NOT advanced
        (a sweep is a fan-out query, not a state transition). The batch is
        ``jax.vmap``-ed inside one jit (in ``collect="owners"`` mode the
        stacked planes are donated — their buffers become the output cubes);
        with more than one JAX device visible it is additionally
        ``shard_map``-ed across a 1-D device mesh over the batch axis
        (the planes are padded to a device multiple of B on the host, and
        each device is sent only its share).

        ``collect="summary"`` (default) reduces inside the dispatch — only
        [B]-shaped verdicts and the [B, N] final owner rows come back, so
        10k-scenario sweeps never materialize [B, T, N] on the host;
        ``collect="owners"`` also returns the full owners/counts cubes;
        ``collect="margins"`` additionally folds the §4 boundary-proximity
        margins (``ops._margin_scan_impl``) into the dispatch — [B] int32
        scalars per component, the falsifier's fitness signal, still never
        materializing [B, T, N]. With ``verify=True`` a per-scenario §4
        violation (max owner count > 1) raises immediately; the message
        carries each offender's ``plane_digest`` (and its ``tags[i]``
        lineage string when the caller — e.g. ``falsify.search`` — passes
        per-scenario ``tags``), so a 10k-batch violation reproduces
        standalone.
        """
        if collect not in ("summary", "owners", "margins"):
            raise ValueError(f"unknown collect mode {collect!r}")
        if self._placement is not None:
            raise ValueError(
                "this engine replays a placed deployment; sweep it with "
                "run_trace on fresh engines"
            )
        if isinstance(scenarios, (list, tuple)):
            if not scenarios:
                raise ValueError("sweep needs at least one scenario")
            for sc in scenarios:
                sc.validate_for(
                    n_cells=self.n_cells, n_acceptors=self.n_acceptors,
                    n_proposers=self.n_proposers,
                )
            stacked = Scenario.stack(scenarios)
        else:
            stacked = scenarios
        if "placement" in stacked.planes:
            raise ValueError(
                "a sweep takes no placement: replay a placed deployment "
                "with run_trace"
            )
        # one host read per fault plane (the delay plane feeds both the
        # model choice and the pack-budget check; don't pull it twice)
        dmax = int(np.asarray(stacked.planes["delay"]).max(initial=0))
        delayed = dmax > 0 or bool(np.asarray(stacked.planes["drop"]).any())
        # all-DEFAULT_RATE rate planes are the in-graph default clock:
        # don't ship [B, T, P]/[B, T, A] constants into the dispatch
        # (ops._local_clock_planes derives the same readings bit-for-bit);
        # likewise all-zero corruption planes stay host-side so an honest
        # sweep never compiles (or pays for) the corrupt tick variant
        drop_keys = []
        rmax = QUARTERS
        for k in ("prop_rate", "acc_rate"):
            plane = np.asarray(stacked.planes[k])
            if plane.size == 0 or (plane == DEFAULT_RATE).all():
                drop_keys.append(k)
            else:
                rmax = max(rmax, int(plane.max()))
        corrupt = False
        for k in CORRUPTION_PLANES:
            plane = stacked.planes.get(k)
            if plane is None:
                continue
            if np.asarray(plane).any():
                corrupt = True
            else:
                drop_keys.append(k)
        # all-zero restart planes drop like corruption planes; when the
        # engine already carries restart history, rst0 (below) keeps
        # restart mode — and its ballot encoding — on regardless
        restarted = self._restart_active
        for k in RESTART_PLANES:
            plane = stacked.planes.get(k)
            if plane is None:
                continue
            if np.asarray(plane).any():
                restarted = True
            else:
                drop_keys.append(k)
        # all-sentinel extends planes drop the same way (their default is
        # NO_PROPOSER, not zero): an extend-free sweep never compiles the
        # §6 gate
        extended = False
        for k in EXTEND_PLANES:
            plane = stacked.planes.get(k)
            if plane is None:
                continue
            if (np.asarray(plane) != PLANES[k].default).any():
                extended = True
            else:
                drop_keys.append(k)
        # in collect="owners" mode the [B, T, N] attempts/releases planes
        # are DONATED to the dispatch (XLA reuses their buffers for the
        # output cubes); copy those leaves when they are already device
        # arrays so a caller can reuse its stacked Scenario
        donating = collect == "owners"
        n_dev = len(jax.devices())
        cell_planes, rest_planes = {}, {}
        for k, v in stacked.planes.items():
            if k in drop_keys:
                continue
            cell = k in ("attempts", "releases")
            if n_dev > 1:
                arr = _shard_batch(v, n_dev)
            else:
                arr = jnp.asarray(v)
                if donating and cell and arr is v:
                    arr = arr.copy()
            (cell_planes if cell else rest_planes)[k] = arr
        B, T = np.shape(stacked.planes["attempts"])[:2]
        if T == 0:
            raise ValueError("sweep scenarios must have at least one tick")
        # a sweep is read-only: pick the model without flipping the engine
        # (corruption, restart and extends planes only exist in the
        # delayed tick)
        sync = self._pick_model(
            netplane, delayed or corrupt or restarted or extended,
        )
        mr = self._max_restarts(stacked.planes.get("prop_restart"))
        self._check_pack_budget(self.t + T, dmax, rmax, mr)
        self._static_bound_check(self.t + T, dmax, rmax, mr)
        fn = _sweep_fn(
            self.majority, self.lease_q4, self.round_q4, self.guard_q4,
            self.backend if backend is None else resolve_backend(backend),
            sync, 512, self.window, collect, n_dev,
            self.restart_guard, self.skip_stable, self.max_lease_q4,
        )
        out = fn(
            self.state, self.net, jnp.int32(self.t), self._clk0(),
            self._rst0(), cell_planes, rest_planes,
        )
        host = lambda a: np.asarray(a)[:B]  # drop the padding scenarios
        result = SweepResult(
            max_owner_count=host(out["max_owner_count"]),
            owned_frac=host(out["owned_frac"]),
            final_owners=host(out["final_owners"]),
            owners=host(out["owners"]) if collect == "owners" else None,
            counts=host(out["counts"]) if collect == "owners" else None,
            margins=(
                {k: host(v) for k, v in out["margins"].items()}
                if collect == "margins" else None
            ),
        )
        if verify and (result.max_owner_count > 1).any():
            bad = np.flatnonzero(result.max_owner_count > 1)
            # name each offender by its content digest (+ the caller's
            # lineage tag): batch indices alone don't reproduce standalone
            ids = []
            for i in bad[:8]:
                sc_planes = {
                    k: np.asarray(v)[i] for k, v in stacked.planes.items()
                }
                label = f"#{i} digest={plane_digest(sc_planes)}"
                if tags is not None and i < len(tags):
                    label += f" tag={tags[i]}"
                ids.append(label)
            raise AssertionError(
                f"§4 at-most-one-owner violated in {bad.size} scenario(s) "
                f"of the sweep: " + "; ".join(ids)
            )
        return result

    # ------------------------------------------------------------- queries
    def owners(self) -> np.ndarray:
        return np.asarray(owner_row(self.state))

    def ticks_left(self) -> np.ndarray:
        """Per cell: whole LOCAL ticks of ownership remaining as the owner
        sees it (0 if unowned). Owner expiries live in the owning
        proposer's local time, so remaining time is measured against that
        proposer's accumulated clock (= ``4t`` when nothing drifts)."""
        with span("lease.ticks_left"):
            expiry = jnp.max(
                jnp.where(self.state.owner_mask > 0, self.state.owner_expiry, 0),
                axis=0,
            )
            owners = owner_row(self.state)
            with span("lease.wait"):
                jax.block_until_ready((expiry, owners))
            with span("lease.download"):
                expiry, owners = np.asarray(expiry), np.asarray(owners)
            clk = np.where(
                owners == NO_PROPOSER, 0,
                self.prop_clk[np.clip(owners, 0, self.n_proposers - 1)],
            )
            return np.maximum(expiry - clk, 0) // QUARTERS
