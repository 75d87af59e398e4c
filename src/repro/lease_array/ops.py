"""jit'd public entry points for the lease plane: backend dispatch
(pure-jnp fallback vs fused Pallas window kernel) plus cell-axis padding so
callers can use any N. Mirrors the kernels/flash_attention kernel/ops/ref
layout.

The bulk path is :func:`lease_window_scan`: a whole ``[T, …]`` scenario in
ONE dispatch. All backends run the identical packed tick math
(``ref.sync_tick_math`` / ``netplane.delayed_tick_math``), so they agree
bit-for-bit:

  - ``"jnp"``        — `lax.scan` over the packed planes (the XLA-lowered
                       path off the TPU; also the oracle every kernel is
                       tested against);
  - ``"pallas"``     — the time-resident window kernel, interpret mode
                       (CPU only; correctness CI);
  - ``"pallas_tpu"`` — the same kernel compiled for the TPU.

``backend=None`` (every entry point's default) lets the platform choose
(:func:`resolve_backend`): the compiled kernel on a TPU, the jnp scan
anywhere else.

One step: :func:`lease_plane_tick` advances every cell one tick of either
network model — the synchronous zero-delay tick (``sync=True``) or the
delayed in-flight message plane (see ``netplane.py``). Its per-tick inputs
are a :class:`~repro.lease_array.scenario.TickInputs` pytree, so
registering a new fault plane never changes this signature.

``lease_plane_step`` / ``lease_plane_step_delayed`` are deprecation shims
for the old one-positional-argument-per-fault-dimension API.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import lease_window_delayed_pallas, lease_window_sync_pallas
from .netplane import (
    NO_TICK,
    R_PROPOSING,
    RT_DEAF0,
    RT_FIELDS,
    RT_RC0,
    NetPlaneState,
    delayed_tick_math,
    pack_link,
    placed_restart_columns,
)
from .ref import link_matrix, sync_tick_math
from .scenario import (
    CORRUPTION_PLANES,
    EXTEND_PLANES,
    PLANES,
    RESTART_PLANES,
    TickInputs,
    make_tick,
)
from .state import (
    MAX_RESTARTS,
    NO_PROPOSER,
    PACK_MASK,
    PACK_SHIFT,
    QUARTERS,
    LeaseArrayState,
    PackedLeaseState,
    ballot_proposer,
    check_pack_budget,
    clock_select,
    pack_state,
    packed_q4,
    rate1_clock,
    unpack_state,
)

BACKENDS = ("jnp", "pallas", "pallas_tpu")


def resolve_backend(backend: str | None = None) -> str:
    """The lease-plane backend for this process's platform. ``None`` picks
    the compiled kernel (``"pallas_tpu"``) on a TPU and the jnp scan
    anywhere else. A named backend must suit the platform: the interpret-
    mode kernel on a TPU, or the compiled kernel off one, raises instead of
    running something other than what was asked for."""
    on_tpu = jax.default_backend() == "tpu"
    if backend is None:
        return "pallas_tpu" if on_tpu else "jnp"
    if backend not in BACKENDS:
        raise ValueError(f"unknown lease-plane backend {backend!r}")
    if backend == "pallas" and on_tpu:
        raise ValueError(
            "backend='pallas' is the interpret-mode kernel; on a TPU use "
            "'pallas_tpu' (or leave backend unset)"
        )
    if backend == "pallas_tpu" and not on_tpu:
        raise ValueError(
            f"backend='pallas_tpu' needs a TPU; JAX's default backend is "
            f"{jax.default_backend()!r}"
        )
    return backend


def _local_clock_planes(t0, T: int, clk0, planes: dict, n_proposers: int,
                        n_acceptors: int):
    """Absolute per-tick local-clock planes ``(pclk [T, P], aclk [T, A])``:
    ``clk0`` (each node's accumulated local quarter-ticks at ``t0``) plus
    the exclusive prefix sum of the scenario's rate planes. Clock readings
    are a pure function of the rate planes, so drifted node time needs no
    scan carry — the planes stream into the kernel like ``acc_up``.

    ``clk0=None`` is the no-history default (``4·t0`` on every node: the
    rate-1 reading, so legacy rate-free callers reproduce the old global
    time base bit-for-bit); a rate plane missing from a hand-rolled dict
    means the drift-free DEFAULT_RATE step."""
    t0 = jnp.asarray(t0, jnp.int32)

    def one(rate, rows: int, c0):
        if c0 is None:
            c0 = rate1_clock(t0, rows)
        c0 = jnp.asarray(c0, jnp.int32)
        if rate is None:
            steps = QUARTERS * jnp.arange(T, dtype=jnp.int32)
            return c0[None, :] + steps[:, None]
        rate = jnp.asarray(rate, jnp.int32)
        return c0[None, :] + jnp.cumsum(rate, axis=0) - rate

    pc0, ac0 = (None, None) if clk0 is None else clk0
    return (
        one(planes.get("prop_rate"), n_proposers, pc0),
        one(planes.get("acc_rate"), n_acceptors, ac0),
    )


def _restart_planes(rst0, arst, prst, aclk, max_lease_q4: int,
                    guard: bool):
    """Absolute per-tick crash/restart planes, precomputed like the clock
    planes so restart state needs NO scan carry:

      ``rc [T, P]``        INCLUSIVE running per-proposer restart count
                           (a proposer restarting at tick t attempts at t
                           with the bumped counter, like core/cell's
                           persisted-counter bump);
      ``deaf [T, A]``      1 while the acceptor is inside its post-restart
                           deaf window: its local clock has not yet
                           advanced a maximal lease span
                           (``max_lease_q4`` local quarter-ticks — M on
                           ITS clock domain)
                           past the latest restart (a running cummax of
                           restart-minted horizons vs ``aclk``);
      ``deaf_rem [T, A]``  local quarter-ticks of deaf window remaining
                           (0 = not deaf; the margins scan's boundary
                           distance).

    ``rst0`` is the (rc0 [P], deaf_until0 [A]) restart history at t0
    (None = fresh). ``guard=False`` (the §4 negative control) zeroes the
    deaf window: restarted acceptors come back blank but answer
    immediately — the unsafe diskless restart the paper's M-wait forbids.
    """
    rc0, du0 = (None, None) if rst0 is None else rst0
    rc = jnp.cumsum(jnp.asarray(prst, jnp.int32), axis=0)
    if rc0 is not None:
        rc = rc + jnp.asarray(rc0, jnp.int32)[None, :]
    minted = jnp.where(
        jnp.asarray(arst, jnp.int32) > 0, aclk + max_lease_q4, 0
    )
    du = jax.lax.cummax(minted, axis=0)
    if du0 is not None:
        du = jnp.maximum(du, jnp.asarray(du0, jnp.int32)[None, :])
    deaf_rem = jnp.maximum(du - aclk, 0)
    if not guard:
        deaf_rem = jnp.zeros_like(deaf_rem)
    return rc, (deaf_rem > 0).astype(jnp.int32), deaf_rem


def placed_restart_table(rst0, arst, prst, place, t0, *, deaf_q4: int):
    """The per-cell restart table ``[RT_FIELDS, A, N]`` of a placed
    replay (``netplane.placed_restart_columns`` reads it), derived on the
    device from the ``[T, K]`` node rows ``arst``/``prst`` (None = no
    restarts) and the ``[A, N]`` placement: each node's first
    MAX_RESTARTS acceptor and proposer restart ticks (absolute, from
    ``t0``; NO_TICK where fewer) and its history at ``t0``, ``rst0`` =
    (restart count [K], deaf-until [K] in global quarter-ticks; None =
    fresh), gathered once per replica slot of every cell. Nothing
    ``[T, N]``-sized is formed. ``deaf_q4 = 0`` (the unguarded control)
    drops the deaf history with the deaf window."""
    place = jnp.asarray(place, jnp.int32)
    rows = [r for r in (arst, prst) if r is not None]
    if rows:
        T, K = jnp.shape(rows[0])
    else:
        T, K = 0, jnp.shape(rst0[0])[0]
    ticks = jnp.asarray(t0, jnp.int32) + jnp.arange(T, dtype=jnp.int32)

    def firsts(node_rows):
        if node_rows is None:
            return [jnp.full((K,), NO_TICK, jnp.int32)] * MAX_RESTARTS
        hit = jnp.asarray(node_rows, jnp.int32) > 0
        rank = jnp.cumsum(hit.astype(jnp.int32), axis=0)
        return [
            jnp.min(
                jnp.where(hit & (rank == j + 1), ticks[:, None], NO_TICK),
                axis=0, initial=NO_TICK,
            )
            for j in range(MAX_RESTARTS)
        ]

    zero = jnp.zeros((K,), jnp.int32)
    rc0, du0 = (zero, zero) if rst0 is None else (
        jnp.asarray(rst0[0], jnp.int32), jnp.asarray(rst0[1], jnp.int32)
    )
    if deaf_q4 == 0:
        du0 = zero
    fields = firsts(arst) + firsts(prst)
    fields.insert(RT_DEAF0, du0)
    fields.insert(RT_RC0, rc0)
    # one gather per field from its [K] row: a gather of [F, K] columns
    # pads each gathered index to a full lane tile on the TPU (16x the
    # table's bytes in temporaries at a fleet's size)
    return jnp.stack([
        jnp.take(row, place, mode="clip") for row in fields
    ])                                                           # [F, A, N]


def pad_restart_table(tab, multiple: int):
    """Append empty cells to a restart table until its cell axis divides
    by ``multiple``: no restarts, no history."""
    pad = (-tab.shape[-1]) % multiple
    if pad == 0:
        return tab
    fill = jnp.full((RT_FIELDS, tab.shape[1], pad), NO_TICK, jnp.int32)
    fill = fill.at[RT_DEAF0].set(0).at[RT_RC0].set(0)
    return jnp.concatenate([tab, fill], axis=-1)


def _pad_cells(arrays, multiple: int, pad_values):
    """Pad the trailing cell axis of each array to a block multiple."""
    n = arrays[0].shape[-1]
    pad = (-n) % multiple
    if pad == 0:
        return arrays, n
    width = [(0, 0)] * (arrays[0].ndim - 1) + [(0, pad)]
    return [
        jnp.pad(a, width, constant_values=v)
        for a, v in zip(arrays, pad_values)
    ], n


def _pad_packed(packed: PackedLeaseState, multiple: int):
    # padded cells never attempt or own anything (owner_id's empty
    # sentinel is NO_PROPOSER; every other plane's is 0)
    arrays, n = _pad_cells(
        list(packed), multiple,
        tuple(
            NO_PROPOSER if f == "owner_id" else 0
            for f in PackedLeaseState._fields
        ),
    )
    return PackedLeaseState(*arrays), n


def _pad_net(net: NetPlaneState, multiple: int) -> NetPlaneState:
    pad = (-net.n_cells) % multiple
    if pad == 0:
        return net
    # zero padding = empty slots / no open round in the padded cells;
    # presp_pay's empty sentinel is NO_PROPOSER, matching init_netplane
    return NetPlaneState(*(
        jnp.pad(
            arr, ((0, 0), (0, pad)),
            constant_values=NO_PROPOSER if name == "presp_pay" else 0,
        )
        for name, arr in zip(NetPlaneState._fields, net)
    ))


def _window_scan_impl(
    state: LeaseArrayState,
    net,
    t0,
    clk0,
    rst0,
    planes: dict,
    *,
    majority: int,
    lease_q4: int,
    round_q4: int,
    guard_q4: int,
    backend: str,
    sync: bool,
    block_n: int,
    window: int,
    restart_guard: bool = True,
    skip_stable: bool = True,
    rtab=None,
    max_lease_q4: int | None = None,
):
    """Shared unjitted body of the fused scan (also vmapped by
    ``engine.sweep``). ``planes`` is the Scenario plane dict ([T, ...]
    arrays); ``clk0`` the (prop [P], acc [A]) local-clock offsets at
    ``t0`` (None = the rate-1 reading ``4·t0``); ``rst0`` the
    (restart-counter [P], deaf-until [A]) restart history at ``t0``
    (None = fresh). ``rtab`` is a placed replay's per-cell restart table
    (``placed_restart_table``; ``planes`` then holds no restart plane and
    ``rst0`` is unused: the table holds the history). Returns
    (state', net', owners [T, N], counts [T, N],
    steps [2]). ``max_lease_q4`` is M, the post-restart deaf window
    (None = ``lease_q4``). ``steps`` counts the window kernel's grid steps that took
    the quiescent path, then all of its grid steps (both 0 where no
    delayed window kernel ran: the jnp scan and the synchronous kernel)."""
    backend = resolve_backend(backend)
    P = state.n_proposers
    A, N = state.highest_promised.shape
    t0 = jnp.asarray(t0, jnp.int32)
    attempts = jnp.asarray(planes["attempts"], jnp.int32)
    releases = jnp.asarray(planes["releases"], jnp.int32)
    acc_up = jnp.asarray(planes["acc_up"], jnp.int32)
    T = attempts.shape[0]
    pclk, aclk = _local_clock_planes(t0, T, clk0, planes, P, A)
    packed = pack_state(state)
    # the adversarial corruption planes: absent from the dict means the
    # honest tick math traces with NO corruption ops (the callers omit
    # all-zero planes host-side, so honest replays stay byte-identical)
    stale = planes.get("acc_stale")
    equiv = planes.get("acc_equiv")
    corrupt = stale is not None or equiv is not None
    if corrupt:
        if sync:
            raise ValueError(
                "corruption planes (acc_stale/acc_equiv) need the delayed "
                "model; the synchronous tick cannot honor them"
            )
        za = jnp.zeros((T, A), jnp.int32)
        stale = za if stale is None else jnp.asarray(stale, jnp.int32)
        equiv = za if equiv is None else jnp.asarray(equiv, jnp.int32)
    # the §6 extends plane: same omit-means-honest contract (all-default
    # -1 planes are stripped by the callers, so honest replays never
    # compile the extend gate)
    ext = planes.get("extends")
    extend = ext is not None
    if extend:
        if sync:
            raise ValueError(
                "the extends plane (§6 owner extension) needs the delayed "
                "model; the synchronous tick cannot honor it"
            )
        ext = jnp.asarray(ext, jnp.int32)
    # the crash/restart planes: same omit-means-honest contract; a restart
    # history (rst0) keeps restart mode on across incremental steps even
    # when this dispatch's planes are quiet, so ballot encoding never
    # switches mid-trace
    arst = planes.get("acc_restart")
    prst = planes.get("prop_restart")
    placed = rtab is not None
    restart = (
        placed or arst is not None or prst is not None or rst0 is not None
    )
    if restart and sync:
        raise ValueError(
            "restart planes (acc_restart/prop_restart) need the "
            "delayed model; the synchronous tick cannot honor them"
        )
    if max_lease_q4 is None:
        max_lease_q4 = lease_q4
    deaf_q4 = max_lease_q4 if restart_guard else 0
    if placed:
        if arst is not None or prst is not None:
            raise ValueError(
                "a placed replay's restarts come in its restart table, "
                "not in restart planes"
            )
    elif restart:
        arst = (
            jnp.zeros((T, A), jnp.int32) if arst is None
            else jnp.asarray(arst, jnp.int32)
        )
        prst = (
            jnp.zeros((T, P), jnp.int32) if prst is None
            else jnp.asarray(prst, jnp.int32)
        )
        rc, deaf, _ = _restart_planes(
            rst0, arst, prst, aclk, max_lease_q4, restart_guard
        )
    if not sync:
        link = pack_link(planes["delay"], planes["drop"])  # [T, P, A]

    if backend == "jnp":
        if sync:
            def body(carry, xs):
                lease, t = carry
                a, r, u, pc, ac = xs
                lease, count = sync_tick_math(
                    lease, t, a[None, :], r[None, :], u[:, None],
                    pc[:, None], ac[:, None],
                    majority=majority, lease_q4=lease_q4, n_proposers=P,
                    guard_q4=guard_q4,
                )
                return (lease, t + 1), (lease[2], count)

            (lease, _), (owners, counts) = jax.lax.scan(
                body, (tuple(packed), t0),
                (attempts, releases, acc_up, pclk, aclk),
            )
            new_net = net
        else:
            def body(carry, xs):
                lease, netc, t = carry
                a, r, u, pc, ac, lk = xs[:6]
                i = 6
                adv = {}
                if extend:
                    adv["extend"] = xs[i][None, :]
                    i += 1
                if corrupt:
                    adv.update(stale=xs[i][:, None], equiv=xs[i + 1][:, None])
                    i += 2
                if placed:
                    arst_t, deaf_t, prst_t, rc_t = placed_restart_columns(
                        rtab, t, deaf_q4=deaf_q4
                    )
                    adv.update(
                        acc_restart=arst_t, acc_deaf=deaf_t,
                        prop_restart=prst_t, prop_rc=rc_t,
                    )
                elif restart:
                    adv.update(
                        acc_restart=xs[i][:, None],
                        acc_deaf=xs[i + 1][:, None],
                        prop_restart=xs[i + 2][:, None],
                        prop_rc=xs[i + 3][:, None],
                    )
                lease, netc, count = delayed_tick_math(
                    lease, netc, t, a[None, :], r[None, :], u[:, None],
                    pc[:, None], ac[:, None], lk,
                    majority=majority, lease_q4=lease_q4, round_q4=round_q4,
                    n_proposers=P, guard_q4=guard_q4, **adv,
                )
                return (lease, netc, t + 1), (lease[2], count)

            xs = (attempts, releases, acc_up, pclk, aclk, link)
            if extend:
                xs += (ext,)
            if corrupt:
                xs += (stale, equiv)
            if restart and not placed:
                xs += (arst, deaf, prst, rc)
            (lease, netc, _), (owners, counts) = jax.lax.scan(
                body, (tuple(packed), tuple(net), t0), xs
            )
            new_net = NetPlaneState(*netc)
        new_state = unpack_state(PackedLeaseState(*lease), P)
        return (new_state, new_net, owners.reshape(T, N),
                counts.reshape(T, N), jnp.zeros(2, jnp.int32))

    interpret = backend == "pallas"
    padded, n = _pad_packed(packed, block_n)
    cell_planes = [attempts, releases] + ([ext] if extend else [])
    cell_planes, _ = _pad_cells(
        cell_planes, block_n, (NO_PROPOSER,) * len(cell_planes)
    )
    attempts_p, releases_p = cell_planes[:2]
    ext_p = cell_planes[2] if extend else None
    if sync:
        padded, owners, counts = lease_window_sync_pallas(
            padded, t0, attempts_p, releases_p, acc_up, pclk, aclk,
            majority=majority, lease_q4=lease_q4, n_proposers=P,
            guard_q4=guard_q4, block_n=block_n, window=window,
            interpret=interpret,
        )
        new_net = net
        steps = jnp.zeros(2, jnp.int32)
    else:
        net_p = _pad_net(net, block_n)
        if placed:
            rst_kw = dict(
                restart_table=pad_restart_table(rtab, block_n),
                deaf_q4=deaf_q4,
            )
        elif restart:
            rst_kw = dict(acc_restart=arst, acc_deaf=deaf, prop_restart=prst,
                          prop_rc=rc)
        else:
            rst_kw = {}
        padded, net_p, owners, counts, steps = lease_window_delayed_pallas(
            padded, net_p, t0, attempts_p, releases_p, acc_up, pclk, aclk,
            link, extends=ext_p, stale=stale, equiv=equiv, **rst_kw,
            majority=majority, lease_q4=lease_q4, round_q4=round_q4,
            n_proposers=P, guard_q4=guard_q4, block_n=block_n,
            window=window, interpret=interpret, skip_stable=skip_stable,
        )
        new_net = NetPlaneState(*(a[:, :n] for a in net_p))
    new_state = unpack_state(
        PackedLeaseState(*(a[:, :n] for a in padded)), P
    )
    return new_state, new_net, owners[:, :n], counts[:, :n], steps


_window_scan_jit = functools.partial(
    jax.jit,
    static_argnames=(
        "majority", "lease_q4", "round_q4", "guard_q4", "backend", "sync",
        "block_n", "window", "restart_guard", "skip_stable", "max_lease_q4",
    ),
)(_window_scan_impl)


#: "never got close" sentinel for the min-tracked margin components
MARGIN_BIG = 1 << 28

#: the margin components, in the order the scan carry holds them
MARGIN_NAMES = ("votes_gap", "tie_q4", "ghost_q4", "deaf_q4", "open_rounds")


def _margin_scan_impl(
    state: LeaseArrayState,
    net,
    t0,
    clk0,
    planes: dict,
    *,
    majority: int,
    lease_q4: int,
    round_q4: int,
    guard_q4: int,
    rst0=None,
    restart_guard: bool = True,
    max_lease_q4: int | None = None,
):
    """The delayed jnp scan with §4 boundary-proximity margins folded into
    the carry — the body of ``engine.sweep(collect="margins")``. Margins
    are whole-scenario int32 scalars reduced in-dispatch (never [T, N],
    let alone [B, T, N]):

      ``votes_gap``   min votes still missing for a *foreign* round to
                      reach a majority while another proposer's belief is
                      live — the ticks-to-second-believer proxy (0 ⇔ the
                      violating vote is already in flight);
      ``tie_q4``      min |owner expiry − owner local clock| in quarter-
                      ticks over ticks whose release names the live owner
                      — the guarded-expiry tie species (the PR 5 bug was
                      exactly tie_q4 = 0);
      ``ghost_q4``    min local quarter-ticks by which a majority-accepted
                      claim missed its own guarded timer (§3 step 5: the
                      ghost-lease guard refused the win; 1 = refused by a
                      single quarter-tick);
      ``deaf_q4``     min local quarter-ticks of deaf window left when a
                      post-restart deaf acceptor refused a due request
                      that would have completed a *foreign* quorum (one
                      vote short while another belief is live) — the
                      restart species' boundary distance (1 = the M-wait
                      saved §4 by a single quarter-tick);
      ``open_rounds`` max cells with a round open at once (contention).

    Min components start at ``MARGIN_BIG`` ("never got close"). Always
    the jnp oracle path of the delayed model — the backends are
    bit-identical by construction, so margins are backend-independent,
    and zero-delay planes are the sync special case bit-for-bit. Returns
    (owners [T, N], counts [T, N], margins dict of scalars).
    """
    P = state.n_proposers
    A, N = state.highest_promised.shape
    t0 = jnp.asarray(t0, jnp.int32)
    attempts = jnp.asarray(planes["attempts"], jnp.int32)
    releases = jnp.asarray(planes["releases"], jnp.int32)
    acc_up = jnp.asarray(planes["acc_up"], jnp.int32)
    T = attempts.shape[0]
    pclk, aclk = _local_clock_planes(t0, T, clk0, planes, P, A)
    packed = pack_state(state)
    link = pack_link(planes["delay"], planes["drop"])
    stale = planes.get("acc_stale")
    equiv = planes.get("acc_equiv")
    corrupt = stale is not None or equiv is not None
    if corrupt:
        za = jnp.zeros((T, A), jnp.int32)
        stale = za if stale is None else jnp.asarray(stale, jnp.int32)
        equiv = za if equiv is None else jnp.asarray(equiv, jnp.int32)
    ext = planes.get("extends")
    extend = ext is not None
    if extend:
        ext = jnp.asarray(ext, jnp.int32)
    arst = planes.get("acc_restart")
    prst = planes.get("prop_restart")
    restart = arst is not None or prst is not None or rst0 is not None
    if restart:
        arst = (
            jnp.zeros((T, A), jnp.int32) if arst is None
            else jnp.asarray(arst, jnp.int32)
        )
        prst = (
            jnp.zeros((T, P), jnp.int32) if prst is None
            else jnp.asarray(prst, jnp.int32)
        )
        rc, deaf, deaf_rem = _restart_planes(
            rst0, arst, prst, aclk,
            lease_q4 if max_lease_q4 is None else max_lease_q4, restart_guard,
        )
    big = jnp.int32(MARGIN_BIG)

    def vote_count(bits):  # popcount over the A vote bits (compile-time A)
        n = bits & 1
        for a in range(1, A):
            n = n + ((bits >> a) & 1)
        return n

    def body(carry, xs):
        lease, netc, t, m = carry
        a, r, u, pc, ac, lk = xs[:6]
        i = 6
        adv = {}
        ext_row = None
        if extend:
            ext_row = xs[i][None, :]
            adv["extend"] = ext_row
            i += 1
        if corrupt:
            adv.update(stale=xs[i][:, None], equiv=xs[i + 1][:, None])
            i += 2
        if restart:
            adv.update(
                acc_restart=xs[i][:, None], acc_deaf=xs[i + 1][:, None],
                prop_restart=xs[i + 2][:, None], prop_rc=xs[i + 3][:, None],
            )
            deaf_rem_col = xs[i + 4][:, None]
        att_row, rel_row = a[None, :], r[None, :]
        pc_col = pc[:, None]
        # pre-tick: guarded-expiry tie distance at releases — and, in
        # extend mode, at extends — that name the live owner: its packed
        # expiry vs its local clock right now (an extend racing its own
        # guarded expiry is the §6 twin of the PR 5 release tie)
        own_id_pre, ownp_pre = lease[2], lease[3]
        own_clk = clock_select(pc_col, own_id_pre)
        names_owner = (
            (rel_row >= 0) & (own_id_pre == rel_row) & (ownp_pre > 0)
        )
        if extend:
            names_owner = names_owner | (
                (ext_row >= 0) & (own_id_pre == ext_row) & (ownp_pre > 0)
            )
        tie_clk_d = jnp.abs(packed_q4(ownp_pre) - own_clk)
        tie_q4 = jnp.min(jnp.where(names_owner, tie_clk_d, big))

        # pre-tick: deaf-window boundary distance — a due request at a deaf
        # acceptor, belonging to the open round, while that round is one
        # vote short of a foreign quorum: the refusal the M-wait exists
        # for. Margin = deaf quarter-ticks remaining on the acceptor's
        # clock when it refused.
        if restart:
            preq_pre, poreq_pre = netc[0], netc[3]
            rnd_ballot_pre = netc[6]
            live_min_pre = (QUARTERS * t + 1) << PACK_SHIFT
            req_due = lambda s: (s > 0) & (s < live_min_pre)
            round_req = (
                (req_due(preq_pre) & ((preq_pre & PACK_MASK) == rnd_ballot_pre))
                | (req_due(poreq_pre) & ((poreq_pre & PACK_MASK) == rnd_ballot_pre))
            )
            rnd_prop_pre = ballot_proposer(rnd_ballot_pre, P)
            foreign_pre = (
                (rnd_ballot_pre > 0) & (ownp_pre > 0)
                & (own_id_pre != rnd_prop_pre)
            )
            if extend:
                # extend mode: a deaf refusal of the owner's OWN extend
                # round (one vote short) is the §6 boundary — the extend
                # that almost completed before the M-wait swallowed it
                foreign_pre = foreign_pre | (
                    (rnd_ballot_pre > 0) & (ownp_pre > 0)
                    & (own_id_pre == rnd_prop_pre)
                )
            nv_pre = jnp.maximum(
                vote_count(netc[10]), vote_count(netc[11])
            )
            one_short = nv_pre == (majority - 1)
            saved = (
                (deaf_rem_col > 0) & round_req & foreign_pre & one_short
            )
            deaf_q4 = jnp.min(jnp.where(saved, deaf_rem_col, big))
        else:
            deaf_q4 = big

        lease, netc, count = delayed_tick_math(
            lease, netc, t, att_row, rel_row, u[:, None],
            pc_col, ac[:, None], lk,
            majority=majority, lease_q4=lease_q4, round_q4=round_q4,
            n_proposers=P, guard_q4=guard_q4, **adv,
        )

        # post-tick: contention gap + ghost-guard refusals still visible
        # in the round rows (a refused §3-step-5 claim leaves its round
        # R_PROPOSING with a majority of accept bits set)
        own_id, ownp = lease[2], lease[3]
        rnd_ballot, rnd_phase, rnd_expiry = netc[6], netc[7], netc[8]
        rnd_open_bits, rnd_acc_bits = netc[10], netc[11]
        rnd_prop = ballot_proposer(rnd_ballot, P)
        rnd_clk = clock_select(pc_col, rnd_prop)
        nvotes = jnp.maximum(
            vote_count(rnd_open_bits), vote_count(rnd_acc_bits)
        )
        contested = (rnd_ballot > 0) & (ownp > 0) & (own_id != rnd_prop)
        gap = jnp.maximum(majority - nvotes, 0)
        votes_gap = jnp.min(jnp.where(contested, gap, big))
        refused = (
            (rnd_ballot > 0) & (rnd_phase == R_PROPOSING)
            & (vote_count(rnd_acc_bits) >= majority)
        )
        ghost_clk_d = rnd_clk - rnd_expiry + 1
        ghost_q4 = jnp.min(jnp.where(refused, ghost_clk_d, big))
        open_rounds = jnp.sum((rnd_ballot > 0).astype(jnp.int32))
        m = (
            jnp.minimum(m[0], votes_gap),
            jnp.minimum(m[1], tie_q4),
            jnp.minimum(m[2], ghost_q4),
            jnp.minimum(m[3], deaf_q4),
            jnp.maximum(m[4], open_rounds),
        )
        return (lease, netc, t + 1, m), (lease[2], count)

    m0 = (big, big, big, big, jnp.int32(0))
    xs = (attempts, releases, acc_up, pclk, aclk, link)
    if extend:
        xs += (ext,)
    if corrupt:
        xs += (stale, equiv)
    if restart:
        xs += (arst, deaf, prst, rc, deaf_rem)
    (_, _, _, m), (owners, counts) = jax.lax.scan(
        body, (tuple(packed), tuple(net), t0, m0), xs
    )
    margins = dict(zip(MARGIN_NAMES, m))
    return owners.reshape(T, N), counts.reshape(T, N), margins


#: one-time flag: the traced-away skip below is a real coverage gap (the
#: guard silently not running), so the first occurrence per process warns
_WARNED_TRACED_SKIP = False


def _guard_pack_budget(
    t0, n_ticks, planes, *, n_proposers, lease_q4, sync, clk0=None,
    rst0=None,
):
    """Best-effort host-side overflow guard for the public entry points:
    a tick past ``state.max_pack_tick`` would silently corrupt the packed
    (deadline, ballot) fields, so refuse it here. Skipped when ``t0`` or
    any consulted plane is a tracer (a caller jitting over time owns the
    check, like ``engine.step`` does). Fast clocks shrink the budget: the
    rate planes' maximum step and any clock offsets already ahead of the
    rate-1 reading are both charged. Restart mode (any restart plane or a
    restart history) charges the ballot carve: the budget shrinks by
    RESTART_SHIFT bits plus the highest per-proposer restart count (per
    node, where a placement makes the restart planes node rows)."""
    delay = None if sync else planes.get("delay")
    consulted = (t0, delay, planes.get("prop_rate"), planes.get("acc_rate"),
                 planes.get("acc_restart"), planes.get("prop_restart"))
    if clk0 is not None:
        consulted += tuple(clk0)
    if rst0 is not None:
        consulted += tuple(rst0)
    if any(isinstance(x, jax.core.Tracer) for x in consulted):
        global _WARNED_TRACED_SKIP
        if not _WARNED_TRACED_SKIP:
            _WARNED_TRACED_SKIP = True
            warnings.warn(
                "check_pack_budget skipped: the tick count or a consulted "
                "plane is a tracer, so the host-side overflow guard cannot "
                "run. The jitting caller owns the check — verify the "
                "config statically first (engine.run_trace/sweep do, via "
                "repro.analysis.staticcheck), or a replay past "
                "state.max_pack_tick will silently corrupt the packed "
                "fields.",
                RuntimeWarning, stacklevel=3,
            )
        return
    t0 = int(np.asarray(t0))
    max_delay = 0 if delay is None else int(np.asarray(delay).max(initial=0))
    max_rate = max(
        (
            int(np.asarray(planes[k]).max(initial=0))
            for k in ("prop_rate", "acc_rate") if planes.get(k) is not None
        ),
        default=QUARTERS,
    )
    max_rate = max(max_rate, QUARTERS)
    clk_slack = 0
    if clk0 is not None:
        clk_max = max(int(np.asarray(c).max(initial=0)) for c in clk0)
        clk_slack = max(0, clk_max - max_rate * t0)
    arst = planes.get("acc_restart")
    prst = planes.get("prop_restart")
    max_restarts = 0
    if arst is not None or prst is not None or rst0 is not None:
        # per proposer, or per node where a placement makes the restart
        # planes node rows: the highest count any one of them reaches
        width = n_proposers
        if prst is not None:
            width = np.shape(prst)[-1]
        elif rst0 is not None:
            width = np.shape(rst0[0])[0]
        rc_end = np.zeros(width, np.int64)
        if prst is not None:
            rc_end += np.asarray(prst, np.int64).reshape(
                -1, width).sum(axis=0)
        if rst0 is not None:
            rc_end += np.asarray(rst0[0], np.int64)
        # acc-only restart schedules still switch the ballot encoding, so
        # charge at least one carve slot
        max_restarts = max(1, int(rc_end.max(initial=0)))
    check_pack_budget(
        t0 + n_ticks, n_proposers, lease_q4, max_delay,
        max_rate=max_rate, clk_slack=clk_slack, max_restarts=max_restarts,
    )


def _only_default(plane, default, rows: int = 8) -> bool:
    """True iff the [T, ...] ``plane`` holds nothing but ``default``. It is
    read ``rows`` ticks at a time, so a plane in use (a [T, N] extends
    plane of renewals) is told apart at its first block that is not,
    without a pass over all of it before the uploads start."""
    v = np.asarray(plane)
    return not any(
        (v[i:i + rows] != default).any() for i in range(0, len(v), rows)
    )


def strip_default_planes(planes: dict, restart_history: bool = False) -> dict:
    """Drop optional fault planes sitting entirely at their registered
    default. All-default corruption/restart/extends planes ARE the honest
    engine, so stripping them host-side keeps the honest replay from
    compiling the fault variants — staticcheck's ``check_honest_strip``
    pins the resulting dispatch-jaxpr byte-identity. A placement goes
    with its restart planes unless a ``restart_history`` keeps restart
    mode on: with no restart to place it changes nothing. Tracers are
    never stripped (their values are unknown at trace time)."""
    out = {
        k: v for k, v in planes.items()
        if not (
            k in CORRUPTION_PLANES + RESTART_PLANES + EXTEND_PLANES
            and not isinstance(v, jax.core.Tracer)
            and _only_default(v, PLANES[k].default)
        )
    }
    if "placement" in out and not restart_history and not any(
        k in out for k in RESTART_PLANES
    ):
        del out["placement"]
    return out


def lease_window_scan(
    state: LeaseArrayState,
    net,
    t0,
    planes: dict,
    *,
    majority: int,
    lease_q4: int,
    round_q4: int,
    guard_q4: int = None,
    clk0=None,
    rst0=None,
    restart_guard: bool = True,
    backend: str | None = None,
    sync: bool = False,
    block_n: int = 512,
    window: int = 16,
    skip_stable: bool = True,
    max_lease_q4: int | None = None,
) -> tuple[LeaseArrayState, NetPlaneState, jax.Array, jax.Array]:
    """Replay a whole [T]-tick scenario-plane dict in ONE dispatch.

    ``sync=True`` runs the zero-delay synchronous model (``net`` passes
    through untouched; the planes' delay/drop entries are ignored);
    ``sync=False`` runs the delayed in-flight model. ``window`` is the
    number of ticks each Pallas kernel window keeps VMEM-resident per
    streamed plane slab (jnp ignores it). ``guard_q4`` is the proposer's
    drift-guarded own timespan (`state.guarded_lease_q4`; default: the
    full ``lease_q4``, the ε=0 case) and ``clk0`` the (prop [P], acc [A])
    accumulated local-clock offsets at ``t0`` (default: the rate-1
    reading ``4·t0`` on every node). ``rst0`` is the (restart-counter [P],
    deaf-until [A]) restart history at ``t0`` (None = fresh; its presence
    keeps restart mode on even for quiet planes); a restarted acceptor
    stays deaf for ``max_lease_q4`` (M, the longest lease any proposer
    may ask for; default ``lease_q4``) on its own clock, and
    ``restart_guard=False`` disables that deaf window — the §4 negative
    control. A ``placement`` plane ([A, N] node ids) makes the restart planes
    ``[T, K]`` node rows, and ``rst0`` then the nodes' (restart count
    [K], deaf-until [K] in global quarter-ticks); the clocks must run at
    the rate-1 step (``scenario.check_placed``).
    ``skip_stable=False`` disables the Pallas quiescence fast path (the
    A/B bench control; results are bit-identical either way).
    Returns (new_state, new_net, owners [T, N], owner_counts [T, N]).
    """
    if guard_q4 is None:
        guard_q4 = lease_q4
    if max_lease_q4 is None:
        max_lease_q4 = lease_q4
    planes = strip_default_planes(planes, restart_history=rst0 is not None)
    _guard_pack_budget(
        t0, int(jnp.shape(planes["attempts"])[0]), planes,
        n_proposers=state.n_proposers, lease_q4=lease_q4, sync=sync,
        clk0=clk0, rst0=rst0,
    )
    rtab = None
    if "placement" in planes:
        planes = dict(planes)
        rtab = placed_restart_table(
            rst0, planes.pop("acc_restart", None),
            planes.pop("prop_restart", None), planes.pop("placement"), t0,
            deaf_q4=max_lease_q4 if restart_guard else 0,
        )
    return _window_scan_jit(
        state, net, t0, clk0, rst0, planes,
        majority=majority, lease_q4=lease_q4, round_q4=round_q4,
        guard_q4=guard_q4, backend=backend, sync=sync, block_n=block_n,
        window=window, restart_guard=restart_guard,
        skip_stable=skip_stable, rtab=rtab, max_lease_q4=max_lease_q4,
    )[:4]


def _plane_tick(
    state: LeaseArrayState,
    net: NetPlaneState,
    t,
    tick: TickInputs,
    *,
    majority: int,
    lease_q4: int,
    round_q4: int,
    guard_q4: int = None,
    clk0=None,
    rst0=None,
    restart_guard: bool = True,
    backend: str | None = None,
    block_n: int = 512,
    sync: bool = False,
    window: int = 16,
    skip_stable: bool = True,
    max_lease_q4: int | None = None,
) -> tuple[LeaseArrayState, NetPlaneState, jax.Array, jax.Array]:
    """:func:`lease_plane_tick`, and the window kernel's grid-step counts
    of the dispatch (``steps``, see ``_window_scan_impl``)."""
    if guard_q4 is None:
        guard_q4 = lease_q4

    def _default_plane(k, v):
        # an all-DEFAULT_RATE rate plane is the in-graph default clock,
        # and an all-zero corruption/restart plane is the honest engine:
        # omit either from the dispatch dict (one fewer host->device
        # upload per step; the scan derives identical behavior
        # bit-for-bit). A restart history (rst0) pins the restart planes
        # in, so ballot encoding never switches mid-trace.
        if isinstance(v, jax.core.Tracer):
            return False
        if k in ("prop_rate", "acc_rate"):
            return bool((np.asarray(v) == QUARTERS).all())
        if k in CORRUPTION_PLANES:
            return not np.asarray(v).any()
        if k in EXTEND_PLANES:
            return bool((np.asarray(v) == PLANES[k].default).all())
        if k in RESTART_PLANES and rst0 is None:
            return not np.asarray(v).any()
        return False

    planes = {
        k: jnp.asarray(v)[None, ...] for k, v in tick.planes.items()
        if not _default_plane(k, v)
    }
    _guard_pack_budget(
        t, 1, tick.planes,
        n_proposers=state.n_proposers, lease_q4=lease_q4, sync=sync,
        clk0=clk0, rst0=rst0,
    )
    new_state, new_net, _, counts, steps = _window_scan_jit(
        state, net, t, clk0, rst0, planes,
        majority=majority, lease_q4=lease_q4, round_q4=round_q4,
        guard_q4=guard_q4, backend=backend, sync=sync, block_n=block_n,
        window=window, restart_guard=restart_guard,
        skip_stable=skip_stable, max_lease_q4=max_lease_q4,
    )
    return new_state, new_net, counts[0], steps


def lease_plane_tick(
    state: LeaseArrayState, net: NetPlaneState, t, tick: TickInputs, **kw,
) -> tuple[LeaseArrayState, NetPlaneState, jax.Array]:
    """Advance all cells one tick.

    ``sync=True`` runs the zero-delay synchronous model (``net`` passes
    through untouched; the tick's delay/drop planes are ignored);
    ``sync=False`` runs the delayed in-flight model with the tick's
    ``[P, A]`` link matrices. ``guard_q4``/``clk0`` are the drift
    parameters (see :func:`lease_window_scan`); the tick's
    ``prop_rate``/``acc_rate`` planes advance the clocks *after* this
    tick's deadlines are evaluated, so a stateful caller carries
    ``clk0 + rate`` into the next tick (``engine.step`` does). backend:
    None (the platform's choice, :func:`resolve_backend`), "jnp"
    (reference), "pallas" (kernel, interpret mode — CPU only) or
    "pallas_tpu" (compiled kernel, TPU only). Returns
    (new_state, new_net, owner_count[N]) — owner_count is the per-cell
    number of proposers who believe they own it (>1 would be a §4
    violation).

    Keywords: ``majority``, ``lease_q4`` and ``round_q4`` (required),
    ``guard_q4``, ``clk0``, ``rst0``, ``restart_guard``, ``backend``,
    ``block_n``, ``sync``, ``window``, ``skip_stable`` and
    ``max_lease_q4``, as
    :func:`lease_window_scan` takes them.
    """
    return _plane_tick(state, net, t, tick, **kw)[:3]


# --------------------------------------------------------------------------
# deprecation shims: the pre-Scenario one-argument-per-fault-dimension API
# --------------------------------------------------------------------------
def _shim_tick(state: LeaseArrayState, attempt, release, acc_up, delay, drop):
    A, N = state.highest_promised.shape
    P = state.n_proposers
    if any(
        isinstance(x, jax.core.Tracer)
        for x in (attempt, release, acc_up, delay, drop)
    ):
        # the old step functions were jit-traceable; keep the shims so too —
        # coerce with jnp and skip the host-side validation make_tick does
        links = lambda m: (
            jnp.zeros((P, A), jnp.int32) if m is None else link_matrix(m, P, A)
        )
        return TickInputs({
            "attempts": (
                jnp.full((N,), NO_PROPOSER, jnp.int32) if attempt is None
                else jnp.asarray(attempt, jnp.int32)
            ),
            "releases": (
                jnp.full((N,), NO_PROPOSER, jnp.int32) if release is None
                else jnp.asarray(release, jnp.int32)
            ),
            "acc_up": (
                jnp.ones((A,), jnp.int32) if acc_up is None
                else jnp.asarray(acc_up).astype(jnp.int32)
            ),
            "delay": links(delay),
            "drop": links(drop),
        })
    return make_tick(
        n_cells=N, n_acceptors=A, n_proposers=P,
        attempts=attempt, releases=release, acc_up=acc_up,
        delay=delay, drop=drop,
    )


def lease_plane_step(
    state: LeaseArrayState,
    t,
    attempt,
    release,
    acc_up,
    *,
    majority: int,
    lease_q4: int,
    backend: str = "jnp",
    block_n: int = 512,
) -> tuple[LeaseArrayState, jax.Array]:
    """Deprecated: build a :class:`TickInputs` and call
    :func:`lease_plane_tick` with ``sync=True`` instead."""
    warnings.warn(
        "lease_plane_step is deprecated; use lease_plane_tick(state, net, "
        "t, tick, ..., sync=True) with a scenario.TickInputs",
        DeprecationWarning, stacklevel=2,
    )
    tick = _shim_tick(state, attempt, release, acc_up, None, None)
    new_state, _, count = lease_plane_tick(
        state, None, t, tick,
        majority=majority, lease_q4=lease_q4, round_q4=0,
        backend=backend, block_n=block_n, sync=True,
    )
    return new_state, count


def lease_plane_step_delayed(
    state: LeaseArrayState,
    net: NetPlaneState,
    t,
    attempt,
    release,
    acc_up,
    delay,     # [A] or [P, A] int32 delays (ticks) for legs sent this tick
    drop,      # [A] or [P, A] bool/int32 drop masks for legs sent this tick
    *,
    majority: int,
    lease_q4: int,
    round_q4: int,
    backend: str = "jnp",
    block_n: int = 512,
) -> tuple[LeaseArrayState, NetPlaneState, jax.Array]:
    """Deprecated: build a :class:`TickInputs` and call
    :func:`lease_plane_tick` instead."""
    warnings.warn(
        "lease_plane_step_delayed is deprecated; use lease_plane_tick with "
        "a scenario.TickInputs",
        DeprecationWarning, stacklevel=2,
    )
    tick = _shim_tick(state, attempt, release, acc_up, delay, drop)
    return lease_plane_tick(
        state, net, t, tick,
        majority=majority, lease_q4=lease_q4, round_q4=round_q4,
        backend=backend, block_n=block_n, sync=False,
    )
