"""In-flight message plane: a multi-tick delay/loss network model for the
vectorized lease engine.

PaxosLease's whole claim (§1) is safety under message loss, reordering and
in-transit delays. The synchronous tick (`ref.lease_step_ref`) resolves a
whole prepare/propose round in one zero-delay instant, so none of those
behaviors exist at array scale. This module adds them as *dense state*:

  - five in-flight planes, one per protocol phase plus §7 releases
    (``prepare / prepare-response / propose / propose-response / rel``),
    each a ``[A, N]`` slot array. A slot packs the message's ballot and its
    delivery quarter-tick into ONE int32 — ``deliver_q4 << PACK_SHIFT |
    ballot`` (0 = empty slot) — so "is this slot due at t?" is two compares
    on one plane (``0 < slot < (t4+1) << PACK_SHIFT``) and a delivery
    clears it with a single select. A slot holds at most one message per
    (acceptor, cell) — the ``random_trace`` spacing construction
    guarantees live messages never collide (see ``trace.py``);
  - a proposer *round* plane, all ``[1, N]`` rows: open ballot, phase
    (preparing/proposing), the quarter-tick the proposer's own lease timer
    will expire (started when a majority of opens is in hand — the §4
    ordering), a timeout-and-abandon deadline, and per-acceptor response
    *bitmasks* (bit ``a`` set = acceptor ``a``'s vote counted) so
    duplicated deliveries can never double-count a quorum (the event
    engine's ``set``-of-acceptors bookkeeping, vectorized into one int).

Per tick, messages *sent* at tick ``t`` on the link between proposer ``p``
and acceptor ``a`` — request or response, either direction — take
``delay[p, a]`` whole ticks and are lost iff ``drop[p, a]``: asymmetric
per-(proposer, acceptor) link matrices (a straggler replica, a lossy rack
uplink, a slow cross-zone pair), mirroring a deterministic per-message
delay policy pinned onto the event-driven ``sim.network.Network`` (see
``trace.replay_event_sim``). Both planes arrive fused into one tiny
``[P, A]`` *link matrix* — ``delay << 1 | drop`` (``pack_link``) — that is
indexed block-locally per leg by the proposer id the leg involves: the
jnp oracle gathers rows with ``take_along_axis`` (``legs_gather``), the
Pallas kernel selects them in a compile-time P-loop (``legs_select``) so
no gather indices ever materialize in HBM. Both produce identical int32
values; the flattened ``[P*A, N]`` per-cell broadcast of earlier
revisions is gone. Symmetric per-acceptor schedules are the P-broadcast
special case. Reachability (``acc_up``) is checked when a *request* is
delivered, exactly like the event transport checks ``set_down`` at
delivery time; responses generated at that same tick see the same mask,
like ``send`` checking its source.

§7 releases are routed through the same plane: a releasing proposer stops
believing it owns immediately (a local action), but the discard messages
to the acceptors ride the ``rel`` in-flight slots — delayed by their
link and droppable like any other leg. In the event sim they deliver at
``REL_EPS`` inside the drain window, before any phase message (see
``trace.py``).

With all-zero delay/drop planes every message is generated and consumed
inside one tick, the slots stay empty, and the step is bit-identical to
the synchronous `lease_step_ref` — the PR 1 model is the zero-delay
special case.

``delayed_tick_math`` is pure elementwise/sublane-reduction jnp on plain
arrays, so the SAME function is the jnp oracle's body (`ref.py`) and the
fused Pallas kernel's body (`kernel.py`): the two backends agree bit-for-
bit by construction.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .state import (
    MAX_RESTARTS,
    NO_PROPOSER,
    PACK_MASK,
    PACK_SHIFT,
    QUARTERS,
    RESTART_SHIFT,
    ballot_proposer,
    clock_select,
    pack_pair,
    packed_ballot,
    packed_q4,
)

# round phases
R_IDLE, R_PREPARING, R_PROPOSING = 0, 1, 2

MAX_VOTE_ACCEPTORS = PACK_SHIFT  # vote bitmasks must stay positive int32


def pack_slot(ballot, deliver_q4):
    """One in-flight message as one int32 (0 = empty slot)."""
    return pack_pair(deliver_q4, ballot)


def pack_link(delay, drop):
    """Fuse (delay ticks, drop mask) into the one-plane link matrix."""
    return (jnp.asarray(delay, jnp.int32) << 1) | (
        jnp.asarray(drop, jnp.int32) & 1
    )


class NetPlaneState(NamedTuple):
    """In-flight messages + open proposer rounds. All arrays int32.

    Slot planes are ``[A, N]`` packed ``deliver_q4 << PACK_SHIFT | ballot``
    ints (``pack_slot``; 0 = empty). ``presp_pay`` is the prepare
    response's payload: the acceptor's accepted proposer at grant time
    (NO_PROPOSER = empty/open). Round rows are ``[1, N]``; the vote sets
    ``rnd_open_bits``/``rnd_acc_bits`` are per-acceptor bitmasks.

    The unpacked views of earlier revisions remain as properties
    (``preq_b``/``preq_at``/…, ``rnd_open``/``rnd_acc`` as [A, N] 0/1
    masks) for tests and diagnostics.
    """

    preq: jax.Array          # [A, N] prepare requests in flight (packed)
    presp: jax.Array         # [A, N] prepare responses (grants only, packed)
    presp_pay: jax.Array     # [A, N] accepted proposer payload (-1 = open)
    poreq: jax.Array         # [A, N] propose requests in flight (packed)
    poresp: jax.Array        # [A, N] propose responses (accepts only, packed)
    rel: jax.Array           # [A, N] §7 release messages in flight (packed)
    rnd_ballot: jax.Array    # [1, N] open round's ballot (0 = no round)
    rnd_phase: jax.Array     # [1, N] R_IDLE / R_PREPARING / R_PROPOSING
    rnd_expiry: jax.Array    # [1, N] LOCAL quarter-tick (round owner's clock) its guarded timer expires
    rnd_deadline: jax.Array  # [1, N] LOCAL quarter-tick (round owner's clock) the round is abandoned
    rnd_open_bits: jax.Array  # [1, N] bitmask of acceptors whose open counted
    rnd_acc_bits: jax.Array   # [1, N] bitmask of acceptors whose accept counted

    @property
    def n_acceptors(self) -> int:
        return self.preq.shape[0]

    @property
    def n_cells(self) -> int:
        return self.preq.shape[1]

    # ------------------------------------------------- unpacked views
    def _bits_mask(self, bits: jax.Array) -> jax.Array:
        a_ids = jax.lax.broadcasted_iota(jnp.int32, self.preq.shape, 0)
        return (bits >> a_ids) & 1

    @property
    def rnd_open(self) -> jax.Array:
        """[A, N] 0/1: acceptors whose open response counted."""
        return self._bits_mask(self.rnd_open_bits)

    @property
    def rnd_acc(self) -> jax.Array:
        """[A, N] 0/1: acceptors whose accept counted."""
        return self._bits_mask(self.rnd_acc_bits)


def _slot_views(name: str):
    def ballot_view(self) -> jax.Array:
        return packed_ballot(getattr(self, name))

    def at_view(self) -> jax.Array:
        return packed_q4(getattr(self, name))

    return property(ballot_view), property(at_view)


for _slot in ("preq", "presp", "poreq", "poresp", "rel"):
    _b, _at = _slot_views(_slot)
    setattr(NetPlaneState, f"{_slot}_b", _b)
    setattr(NetPlaneState, f"{_slot}_at", _at)


def init_netplane(n_cells: int, n_acceptors: int) -> NetPlaneState:
    if n_acceptors > MAX_VOTE_ACCEPTORS:
        raise ValueError(
            f"netplane vote bitmasks support at most {MAX_VOTE_ACCEPTORS} "
            f"acceptors; got {n_acceptors}"
        )
    za = jnp.zeros((n_acceptors, n_cells), jnp.int32)
    zr = jnp.zeros((1, n_cells), jnp.int32)
    return NetPlaneState(
        preq=za,
        presp=za, presp_pay=jnp.full_like(za, NO_PROPOSER),
        poreq=za, poresp=za,
        rel=za,
        rnd_ballot=zr, rnd_phase=zr, rnd_expiry=zr, rnd_deadline=zr,
        rnd_open_bits=zr, rnd_acc_bits=zr,
    )


# ---------------------------------------------------------------------------
# per-leg link indexing: [P, A] link matrix -> ([A, bn] delay_q4, drop) rows
# for the proposer each column's leg involves. ``prop`` is an int32
# proposer-id array, either [1, bn] (one sender per cell: attempts, open
# rounds, releases) or [A, bn] (per-slot: the in-flight ballot's proposer
# on response legs). Ids outside [0, P) — the no-attempt sentinel — pick
# arbitrary rows; every such leg is gated off by its own send/due mask.
# ---------------------------------------------------------------------------
def legs_select(link, prop):
    """Compile-time P-loop of selects — no dynamic gather, block-local:
    the Pallas kernel's strategy (`link` is a VMEM-resident [P, A] block,
    its rows broadcast against the lane axis)."""
    P, A = link.shape
    v = jnp.zeros((A,) + prop.shape[1:], link.dtype)
    for p in range(P):
        v = jnp.where(prop == p, link[p][:, None], v)
    return QUARTERS * (v >> 1), (v & 1) > 0


def legs_gather(link, prop):
    """One `take_along_axis` row gather — the XLA-lowered strategy (the
    jnp oracle / fused fallback). Bit-identical to `legs_select`."""
    P, A = link.shape
    idx = jnp.clip(prop, 0, P - 1)
    if idx.shape[0] == 1:
        idx = jnp.broadcast_to(idx, (A,) + idx.shape[1:])
    v = jnp.take_along_axis(link.T, idx, axis=1)
    return QUARTERS * (v >> 1), (v & 1) > 0


# ---------------------------------------------------------------------------
# placed restarts: each cell's replicas on nodes of a cluster (scenario.py's
# ``placement``). A placed replay carries a per-cell restart table
# ``[RT_FIELDS, A, N]`` (``ops.placed_restart_table``, gathered once per
# dispatch from the node rows through the placement): per replica slot, the
# first MAX_RESTARTS ticks its node's acceptor and its node's proposer
# restart in the dispatch (NO_TICK where fewer), and the node's restart
# history at the dispatch's start. Each tick derives the four restart
# columns from it (``placed_restart_columns``) — per cell, where the shared
# planes give one column per slot.
# ---------------------------------------------------------------------------
NO_TICK = 1 << 24  # "no restart": past any tick the packed layout reaches
RT_ACC = 0                      # fields [0, R): acceptor restart ticks
RT_PROP = MAX_RESTARTS          # fields [R, 2R): proposer restart ticks
RT_DEAF0 = 2 * MAX_RESTARTS     # deaf-until at the start (global q4; 0 = none)
RT_RC0 = 2 * MAX_RESTARTS + 1   # restart counter at the start
RT_FIELDS = 2 * MAX_RESTARTS + 2


def placed_restart_columns(tab, t, *, deaf_q4: int):
    """The restart inputs of ``delayed_tick_math`` at tick ``t`` for a
    placed replay, from the per-cell restart table ``tab`` (an array or a
    kernel ref, indexed by field: ``tab[f]`` is ``[A, bn]``). Returns
    ``(acc_restart, acc_deaf, prop_restart, prop_rc)``, each ``[A, bn]``
    int32 (A == P: slot ``k`` is acceptor ``k`` and proposer ``k``).

    A node restarting at tick ``s`` blanks the slot's acceptor at ``s``
    and keeps it deaf while ``4 (t - s) < deaf_q4`` — the maximal lease
    span on the acceptor's own clock, which with a placement ticks at the
    rate-1 step, so the window is the shared planes' cummax rule in
    global quarter-ticks; ``deaf_q4 = 0`` is the ``restart_guard=False``
    control. The proposer's counter is its history plus its restarts so
    far, inclusive (a proposer restarting at ``t`` attempts at ``t`` with
    the bumped counter, as ``ops._restart_planes`` counts)."""
    t4 = QUARTERS * t
    arst = prst = None
    deaf = t4 < tab[RT_DEAF0]
    rc = tab[RT_RC0]
    for j in range(MAX_RESTARTS):
        acc, prop = tab[RT_ACC + j], tab[RT_PROP + j]
        arst = (acc == t) if arst is None else arst | (acc == t)
        prst = (prop == t) if prst is None else prst | (prop == t)
        deaf = deaf | ((acc <= t) & (QUARTERS * (t - acc) < deaf_q4))
        rc = rc + (prop <= t).astype(jnp.int32)
    i32 = lambda m: m.astype(jnp.int32)
    return i32(arst), i32(deaf), i32(prst), rc


def delayed_tick_math(
    lease: tuple,      # PackedLeaseState fields, [A, bn] / [1, bn] blocks
    net: tuple,        # NetPlaneState fields, [A, bn] / [1, bn] blocks
    t,                 # scalar int32 tick
    attempt,           # [1, bn] int32 proposer id attempting (-1 = none)
    release,           # [1, bn] int32 proposer id releasing (-1 = none)
    up,                # [A, 1|bn] int32 acceptor reachability this tick
    pclk,              # [P, 1|bn] int32 proposer local clocks (quarter-ticks)
    aclk,              # [A, 1|bn] int32 acceptor local clocks (quarter-ticks)
    link,              # [P, A] int32 fused link matrix (delay << 1 | drop)
    *,
    majority: int,
    lease_q4: int,     # lease timespan in quarter-ticks
    round_q4: int,     # timeout-and-abandon horizon in quarter-ticks
    n_proposers: int,
    guard_q4: int = None,  # proposer's guarded own timer (default: no drift)
    legs=legs_gather,  # per-leg link strategy (select inside Pallas)
    extend=None,       # [1, bn] int32 proposer id extending its own lease (§6)
    stale=None,        # [A, 1|bn] adversarial: honor below-promise ballots
    equiv=None,        # [A, 1|bn] adversarial: report a live lease as open
    acc_restart=None,  # [A, 1|bn] diskless acceptor crash+restart this tick
    acc_deaf=None,     # [A, 1|bn] acceptor inside its post-restart deaf window
    prop_restart=None,  # [P, 1|bn] proposer crash+restart this tick
    prop_rc=None,       # [P, 1|bn] accumulated per-proposer restart counters
) -> tuple[tuple, tuple, jnp.ndarray]:
    """One tick of the delayed model on the packed layout. Returns
    (lease', net', owner_count[1, bn]).

    Within-tick order mirrors the event scheduler's drain window exactly:
    expiries fired before the tick boundary, then releases/attempts issued
    at the boundary, then the round-abandon timer, then deliveries in
    causal phase order (a zero-delay message cascades through all four
    phases inside this same tick). ``owner_count`` is 0/1 from the single
    believed-owner row, plus 1 at any tick a win would overwrite a live
    *other* belief — the §4 alarm survives the packed owner plane.

    Two time bases coexist (§4: no clock synchrony): message deliver-ats
    are GLOBAL quarter-ticks (the network has no clock), while every
    node-side timer — acceptor lease expiry, the proposer's guarded own
    timer (``guard_q4``), the round-abandon horizon — is minted from and
    compared against that node's LOCAL clock (``pclk``/``aclk``,
    accumulated local quarter-ticks; per-cell owner/round rows read the
    relevant proposer's entry via `state.clock_select`). All-``4t`` clock
    planes reproduce the rate-1 engine bit-for-bit.

    ``extend`` is the §6 owner-extension plane: an owner re-proposes
    in-flight to renew before expiry. The id is gated on the proposer's
    OWN belief AFTER this tick's expiry/restart/release phases (so a
    same-tick §7 release wins and the extend is a no-op, exactly like
    ``Proposer._renew``'s ``st.want and st.owner`` guard), then merged
    into the attempt row — an extend is a full fresh §3 round whose
    prepare responses count the owner's live proposal as open (phase 4c
    below). A non-owner extend id is a no-op. An explicit attempt on the
    same cell takes precedence. ``None`` traces no extend ops at all
    (honest path byte-identical).

    ``stale``/``equiv`` are the adversarial corruption masks (the
    falsification engine's negative controls — Byzantine acceptors in the
    spirit of dca's byzantine variants): where ``stale`` is set the
    acceptor grants prepares and accepts proposes whose ballot is BELOW
    its promise (§3.2/§3.4 broken; its promise still only ratchets up),
    and where ``equiv`` is set its prepare response lies that it holds no
    accepted lease (the §3.3 open count poisons). Passing ``None`` (the
    default) traces no corruption ops at all, so the honest path's jaxpr
    is byte-identical to a build without these arguments.

    ``acc_restart``/``acc_deaf``/``prop_restart``/``prop_rc`` are the
    crash/restart inputs (paper §2's diskless failure model). An acceptor
    restart blanks its column — promises, accepted lease and its own
    not-yet-delivered responses — and ``acc_deaf`` (precomputed by the ops
    layer from the accumulated clock planes: deaf while the local clock is
    within a maximal lease span of the restart) makes it unreachable like
    ``acc_up = 0``. A proposer restart drops its owner belief, abandons its
    open round, and — via ``prop_rc``, the inclusive running restart count
    — mints subsequent ballots with the restart counter carved into the
    upper word (``state.RESTART_SHIFT``), so numeric ballot order equals
    the event engine's (run, restart, proposer) ``Ballot`` order. ``None``
    defaults trace no restart ops at all (honest path byte-identical); the
    four arrive together or not at all.
    """
    promised, acc_lease, own_id, ownp = lease
    (preq, presp, presp_pay, poreq, poresp, rel_s,
     rnd_ballot, rnd_phase, rnd_expiry, rnd_deadline,
     rnd_open_bits, rnd_acc_bits) = net

    P = n_proposers
    if guard_q4 is None:
        guard_q4 = lease_q4
    t4 = QUARTERS * t
    live_min = (t4 + 1) << PACK_SHIFT  # GLOBAL time base: slot due iff <
    a_ids = jax.lax.broadcasted_iota(jnp.int32, promised.shape, 0)
    a_bit = 1 << a_ids                                             # [A, bn]
    up = up > 0
    stale_b = None if stale is None else stale > 0
    equiv_b = None if equiv is None else equiv > 0

    def due(slot):
        return (slot > 0) & (slot < live_min)

    def votes(bits):  # popcount over the A vote bits (A is compile-time)
        n = bits & 1
        for a in range(1, promised.shape[0]):
            n = n + ((bits >> a) & 1)
        return n

    # -- 1. expiry (each node's own local clock) ---------------------------
    acc_lease = jnp.where(acc_lease >= ((aclk + 1) << PACK_SHIFT), acc_lease, 0)
    own_clk = clock_select(pclk, own_id)                           # [1, bn]
    own_live = ownp >= ((own_clk + 1) << PACK_SHIFT)
    ownp = jnp.where(own_live, ownp, 0)
    own_id = jnp.where(own_live, own_id, NO_PROPOSER)

    # -- 1.5 crash/restart injection (§2: the diskless failure model) ------
    if acc_restart is not None:
        rst_a = acc_restart > 0                                    # [A, bn]
        # a diskless acceptor comes back BLANK: its promises, accepted
        # lease and its own not-yet-delivered responses are gone; requests
        # in flight TO it live in the network and survive
        promised = jnp.where(rst_a, 0, promised)
        acc_lease = jnp.where(rst_a, 0, acc_lease)
        presp = jnp.where(rst_a, 0, presp)
        presp_pay = jnp.where(rst_a, NO_PROPOSER, presp_pay)
        poresp = jnp.where(rst_a, 0, poresp)
    if acc_deaf is not None:
        # ... and stays deaf for a maximal lease span ON ITS OWN CLOCK (the
        # window is precomputed from the accumulated clock planes); a deaf
        # acceptor is unreachable exactly like acc_up = 0
        up = up & ~(acc_deaf > 0)
    if prop_restart is not None:
        # a restarted proposer loses its volatile owner belief NOW (its
        # open round is abandoned in phase 3 below)
        own_rst = clock_select(prop_restart, own_id) > 0           # [1, bn]
        ownp = jnp.where(own_rst, 0, ownp)
        own_id = jnp.where(own_rst, NO_PROPOSER, own_id)

    # -- 2. release (§7, routed through the network) -----------------------
    # 2a. the local action: the releasing owner stops believing NOW (the
    #     §7 "switch to non-owner first" ordering) ...
    rel = release                                                   # [1, bn]
    has_rel = rel >= 0
    rel_owner = has_rel & (own_id == rel)
    rel_ballot = jnp.where(rel_owner, ownp & PACK_MASK, 0)
    ownp = jnp.where(rel_owner, 0, ownp)
    own_id = jnp.where(rel_owner, NO_PROPOSER, own_id)
    # 2b. ... then the discard messages ride the in-flight plane, delayed
    #     and droppable per (releasing proposer, acceptor) link
    dq4, lost = legs(link, rel)                                     # [A, bn]
    send_rel = (rel_ballot > 0) & ~lost
    rel_s = jnp.where(send_rel, pack_slot(rel_ballot, t4 + dq4), rel_s)
    # 2c. deliver due releases (a zero-delay one lands this same tick):
    #     discard iff still reachable and the accepted ballot matches
    rel_due = due(rel_s)
    discard = rel_due & up & ((acc_lease & PACK_MASK) == (rel_s & PACK_MASK))
    acc_lease = jnp.where(discard, 0, acc_lease)
    rel_s = jnp.where(rel_due, 0, rel_s)

    # -- 3. round lifecycle ------------------------------------------------
    # a release wipes the releasing proposer's open round (Proposer.release
    # sets st.round = None); a timed-out round is abandoned (the event
    # round timer fires before this tick's deliveries); a new attempt
    # overwrites whatever round was open (Proposer._start_round).
    rnd_prop = ballot_proposer(rnd_ballot, P)                       # [1, bn]
    rel_kills = (rnd_ballot > 0) & has_rel & (rnd_prop == rel)
    if prop_restart is not None:
        # a restarted round owner abandons its open round (a crash loses
        # the volatile _Round; stale responses can no longer match it)
        rel_kills = rel_kills | (
            (rnd_ballot > 0) & (clock_select(prop_restart, rnd_prop) > 0)
        )
    # the abandon timer is a LOCAL timer: it fires once the round OWNER's
    # clock has advanced round_q4 local quarters past the attempt
    rnd_clk = clock_select(pclk, rnd_prop)                          # [1, bn]
    timed_out = (rnd_ballot > 0) & (rnd_clk >= rnd_deadline)
    att = attempt                                                   # [1, bn]
    if extend is not None:
        # §6: an extend is a fresh round started by the live owner — gated
        # on the local belief AFTER expiry/restart/release above, so a
        # same-tick §7 release (or a crash, or a lapsed timer) turns the
        # extend into a no-op. Attempts take precedence on collisions.
        ext_ok = (att < 0) & (extend >= 0) & (own_id == extend) & (ownp > 0)
        att = jnp.where(ext_ok, extend, att)
    has_att = att >= 0
    att_clk = clock_select(pclk, att)                               # [1, bn]
    if prop_rc is None:
        new_ballot = jnp.where(has_att, (t + 1) * P + att, 0)
    else:
        # restart mode: carve the attempting proposer's restart counter
        # into the ballot's upper word (state.RESTART_SHIFT) — numeric
        # order equals core.ballot's (run, restart, proposer) order
        rc_att = clock_select(prop_rc, att)                         # [1, bn]
        upper = ((t + 1) << RESTART_SHIFT) | rc_att
        new_ballot = jnp.where(has_att, upper * P + att, 0)
    keep = (rnd_ballot > 0) & ~timed_out & ~rel_kills & ~has_att
    rnd_ballot = jnp.where(has_att, new_ballot, jnp.where(keep, rnd_ballot, 0))
    rnd_phase = jnp.where(
        has_att, R_PREPARING, jnp.where(keep, rnd_phase, R_IDLE)
    )
    rnd_expiry = jnp.where(keep, rnd_expiry, 0)
    rnd_deadline = jnp.where(
        has_att, att_clk + round_q4, jnp.where(keep, rnd_deadline, 0)
    )
    fresh = has_att | ~keep                                         # [1, bn]
    rnd_open_bits = jnp.where(fresh, 0, rnd_open_bits)
    rnd_acc_bits = jnp.where(fresh, 0, rnd_acc_bits)

    # -- 4a. broadcast prepare requests for new attempts -------------------
    dq4, lost = legs(link, att)
    send_preq = has_att & ~lost                                     # [A, bn]
    preq = jnp.where(send_preq, pack_slot(new_ballot, t4 + dq4), preq)

    # -- 4b. deliver prepare requests at acceptors (§3.2) ------------------
    preq_due = due(preq)
    preq_b = preq & PACK_MASK
    if stale_b is None:
        grant = preq_due & up & (preq_b >= promised)
        promised = jnp.where(grant, preq_b, promised)
    else:
        # stale-ballot injection: the corrupted acceptor grants below its
        # promise too (the promise itself still only ratchets upward)
        grant = preq_due & up & ((preq_b >= promised) | stale_b)
        promised = jnp.where(grant, jnp.maximum(promised, preq_b), promised)
    # the response leg belongs to the REQUESTER's link: each slot's ballot
    # names the proposer the grant travels back to
    dq4, lost = legs(link, ballot_proposer(preq_b, P))
    send_presp = grant & ~lost
    acc_b = acc_lease & PACK_MASK                                   # [A, bn]
    acc_prop = jnp.where(acc_b > 0, ballot_proposer(acc_b, P), NO_PROPOSER)
    if equiv_b is not None:
        # equivocation: the corrupted acceptor's grant payload claims it
        # holds no accepted lease, whatever acc_lease says
        acc_prop = jnp.where(equiv_b, NO_PROPOSER, acc_prop)
    presp = jnp.where(send_presp, pack_slot(preq_b, t4 + dq4), presp)
    presp_pay = jnp.where(send_presp, acc_prop, presp_pay)
    preq = jnp.where(preq_due, 0, preq)

    # -- 4c. deliver prepare responses at proposers (§3.3) -----------------
    presp_due = due(presp)
    rnd_prop = ballot_proposer(rnd_ballot, P)  # recompute: round changed above
    rnd_clk = clock_select(pclk, rnd_prop)     # the round owner's clock
    match_prep = (
        presp_due & ((presp & PACK_MASK) == rnd_ballot)
        & (rnd_phase == R_PREPARING)
    )
    # §6 extend: a response carrying our own proposal counts as open only
    # while we still believe we own (checked at ARRIVAL, like st.owner)
    rnd_prop_owns = (own_id == rnd_prop) & (ownp > 0)               # [1, bn]
    is_open = match_prep & (
        (presp_pay == NO_PROPOSER) | ((presp_pay == rnd_prop) & rnd_prop_owns)
    )
    # set-union via the vote bitmask: duplicate-proof
    rnd_open_bits = rnd_open_bits | jnp.sum(
        jnp.where(is_open, a_bit, 0), axis=0, keepdims=True
    )
    opens = votes(rnd_open_bits)                                    # [1, bn]
    to_propose = (
        (rnd_ballot > 0) & (rnd_phase == R_PREPARING) & (opens >= majority)
    )
    # majority open: start OUR timer first, then broadcast the proposal —
    # the ordering the §4 proof depends on. The timer is the proposer's
    # LOCAL guarded timespan (the T·(1-ε)/(1+ε) drift discount)
    rnd_phase = jnp.where(to_propose, R_PROPOSING, rnd_phase)
    rnd_expiry = jnp.where(to_propose, rnd_clk + guard_q4, rnd_expiry)
    dq4, lost = legs(link, rnd_prop)
    send_poreq = to_propose & ~lost                                 # [A, bn]
    poreq = jnp.where(send_poreq, pack_slot(rnd_ballot, t4 + dq4), poreq)
    presp = jnp.where(presp_due, 0, presp)
    presp_pay = jnp.where(presp_due, NO_PROPOSER, presp_pay)

    # -- 4d. deliver propose requests at acceptors (§3.4) ------------------
    poreq_due = due(poreq)
    poreq_b = poreq & PACK_MASK
    accept = poreq_due & up & (poreq_b >= promised)
    if stale_b is not None:
        accept = poreq_due & up & ((poreq_b >= promised) | stale_b)
    # each accepting acceptor restarts the full-length timer on ITS clock
    acc_lease = jnp.where(accept, pack_pair(aclk + lease_q4, poreq_b), acc_lease)
    dq4, lost = legs(link, ballot_proposer(poreq_b, P))
    send_poresp = accept & ~lost
    poresp = jnp.where(send_poresp, pack_slot(poreq_b, t4 + dq4), poresp)
    poreq = jnp.where(poreq_due, 0, poreq)

    # -- 4e. deliver propose responses at proposers (§3.5) -----------------
    poresp_due = due(poresp)
    match_prop = (
        poresp_due & ((poresp & PACK_MASK) == rnd_ballot)
        & (rnd_phase == R_PROPOSING)
    )
    rnd_acc_bits = rnd_acc_bits | jnp.sum(
        jnp.where(match_prop, a_bit, 0), axis=0, keepdims=True
    )
    accs = votes(rnd_acc_bits)
    # the timer started in 4c bounds the claim (§3 step 5): accepts landing
    # after our own (local, guarded) lease window elapsed must not make us
    # owner — compared on the round owner's clock
    win = (
        (rnd_ballot > 0) & (rnd_phase == R_PROPOSING)
        & (accs >= majority) & (rnd_expiry > rnd_clk)
    )
    # a win that would overwrite a live OTHER belief is the §4 alarm
    viol = win & (ownp > 0) & (own_id != rnd_prop)
    own_id = jnp.where(win, rnd_prop, own_id)
    ownp = jnp.where(win, pack_pair(rnd_expiry, rnd_ballot), ownp)  # 4c timer
    rnd_ballot = jnp.where(win, 0, rnd_ballot)
    rnd_phase = jnp.where(win, R_IDLE, rnd_phase)
    rnd_expiry = jnp.where(win, 0, rnd_expiry)
    rnd_deadline = jnp.where(win, 0, rnd_deadline)
    rnd_open_bits = jnp.where(win, 0, rnd_open_bits)
    rnd_acc_bits = jnp.where(win, 0, rnd_acc_bits)
    poresp = jnp.where(poresp_due, 0, poresp)

    lease_out = (promised, acc_lease, own_id, ownp)
    net_out = (preq, presp, presp_pay, poreq, poresp, rel_s,
               rnd_ballot, rnd_phase, rnd_expiry, rnd_deadline,
               rnd_open_bits, rnd_acc_bits)
    owner_count = (ownp > 0).astype(jnp.int32) + viol.astype(jnp.int32)
    return lease_out, net_out, owner_count
