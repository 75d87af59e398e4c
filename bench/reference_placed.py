"""Plain reference of a fleet of PaxosLease cells whose replicas are placed
on nodes that crash and restart (arXiv 1209.4187, §2-§3).

This is the yardstick of the placed cells. Like ``bench/reference.py``,
whose carry and link helpers it reuses, it imports nothing of the
program: it restates the semantics in plain ``jax.numpy`` under
``lax.scan``, one cell per lane, with every field kept apart.

Replica slot ``k`` of cell ``n`` — acceptor ``k`` and proposer ``k`` —
runs on node ``placement[k, n]``. A node restart at tick ``t``
(``acc_restart[t, node]``, ``prop_restart[t, node]``) hits the replica of
every cell placed on that node, after the tick's expiries and before
its releases:

- a diskless acceptor comes back blank: no promise, no accepted lease,
  and the replies it had not yet delivered are gone (requests in flight
  to it survive, in the network). It then stays deaf — unreachable —
  for M, the maximal lease span of the deployment (``max_lease_ticks``,
  plus the quarter-tick every timer here carries): §3, a blank acceptor
  must wait out any lease it may have granted, and no lease is longer
  than M;
- a proposer forgets that it owns, abandons its open round, and counts
  one more restart on its stable counter, which every later ballot of it
  carries: ``ballot = (((t + 1) << 2) | restarts) * P + p``, so ballots
  stay unique and ordered by (tick, restarts, proposer).

Every other step is ``bench/reference.py``'s tick. ``control`` names a
control, the reference with part of these semantics broken:

- ``"no_deaf"``: restarted acceptors answer at once, the unsafe diskless
  restart §3 forbids: a blank acceptor then makes up a quorum that the
  deaf window would have left a reply short;
- ``"shifted"``: the placement shifted by one node, so restarts hit the
  wrong replicas;
- ``"no_restarts"``: the restarts left out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import (
    IDLE,
    NONE,
    PREPARING,
    PROPOSING,
    _links,
    init_carry,
)

CONTROLS = ("no_deaf", "shifted", "no_restarts")
RESTART_SHIFT = 2  # the restart counter's bits below the ballot's tick


def _tick(carry, xs, *, place, P, majority, lease_q4, round_q4, deaf_q4,
          symmetric):
    t, attempt, release, extend, up, delay, drop, node_acc, node_prop = xs
    c = dict(carry)
    A = c["promised"].shape[0]
    now = 4 * t

    def leg(p):
        """(arrival quarter-tick, lost) of a leg sent now on the link of
        proposer ``p`` ([N] or [A, N]) with every acceptor: [A, N] each."""
        shape = (A, p.shape[-1])
        if symmetric:
            d = jnp.broadcast_to(delay[:, None], shape)
            lost = jnp.broadcast_to(drop[:, None] > 0, shape)
            return now + 4 * d, lost
        p = jnp.broadcast_to(p, shape)
        d = jnp.zeros(shape, jnp.int32)
        lost = jnp.zeros(shape, bool)
        for q in range(P):
            d = jnp.where(p == q, delay[q][:, None], d)
            lost = jnp.where(p == q, drop[q][:, None] > 0, lost)
        return now + 4 * d, lost

    def of_slot(v, p):
        """``v[p[n], n]`` for a per-slot [P, N] array, 0 where p < 0."""
        out = jnp.zeros(p.shape, v.dtype)
        for q in range(P):
            out = jnp.where(p == q, v[q], out)
        return out

    def due(s):
        return (c[s + "_b"] > 0) & (c[s + "_at"] <= now)

    def send(s, mask, ballot, at):
        c[s + "_b"] = jnp.where(mask, ballot, c[s + "_b"])
        c[s + "_at"] = jnp.where(mask, at, c[s + "_at"])

    def clear(s, mask):
        c[s + "_b"] = jnp.where(mask, 0, c[s + "_b"])
        c[s + "_at"] = jnp.where(mask, 0, c[s + "_at"])

    # 1. expiry
    acc_live = (c["acc_b"] > 0) & (c["acc_end"] > now)
    c["acc_b"] = jnp.where(acc_live, c["acc_b"], 0)
    c["acc_end"] = jnp.where(acc_live, c["acc_end"], 0)
    own_live = (c["own"] >= 0) & (c["own_end"] > now)
    c["own"] = jnp.where(own_live, c["own"], NONE)
    c["own_b"] = jnp.where(own_live, c["own_b"], 0)
    c["own_end"] = jnp.where(own_live, c["own_end"], 0)

    # 1.5 node restarts, through the placement
    acc_down = node_acc[place] > 0                            # [A, N]
    prop_down = node_prop[place] > 0                          # [P, N]
    for k in ("promised", "acc_b", "acc_end"):
        c[k] = jnp.where(acc_down, 0, c[k])
    clear("presp", acc_down)
    clear("poresp", acc_down)
    c["presp_pay"] = jnp.where(acc_down, NONE, c["presp_pay"])
    c["deaf_until"] = jnp.where(
        acc_down, jnp.maximum(c["deaf_until"], now + deaf_q4),
        c["deaf_until"],
    )
    up = (up > 0)[:, None] & ~(now < c["deaf_until"])        # [A, N]
    c["restarts"] = c["restarts"] + prop_down.astype(jnp.int32)
    lost_owner = (c["own"] >= 0) & of_slot(prop_down, c["own"])
    c["own"] = jnp.where(lost_owner, NONE, c["own"])
    c["own_b"] = jnp.where(lost_owner, 0, c["own_b"])
    c["own_end"] = jnp.where(lost_owner, 0, c["own_end"])
    dropped = (c["rnd_b"] > 0) & of_slot(prop_down, c["rnd_b"] % P)
    for k in ("rnd_b", "rnd_end", "rnd_deadline"):
        c[k] = jnp.where(dropped, 0, c[k])
    c["rnd_phase"] = jnp.where(dropped, IDLE, c["rnd_phase"])
    c["opens"] = c["opens"] & ~dropped[None, :]
    c["accepts"] = c["accepts"] & ~dropped[None, :]

    # 2. release: stop believing, then discard over the network
    releasing = (release >= 0) & (c["own"] == release)
    rel_ballot = jnp.where(releasing, c["own_b"], 0)
    c["own"] = jnp.where(releasing, NONE, c["own"])
    c["own_b"] = jnp.where(releasing, 0, c["own_b"])
    c["own_end"] = jnp.where(releasing, 0, c["own_end"])
    at, lost = leg(release)
    send("rel", (rel_ballot > 0)[None, :] & ~lost, rel_ballot[None, :], at)
    rel_due = due("rel")
    discard = rel_due & up & (c["acc_b"] == c["rel_b"])
    c["acc_b"] = jnp.where(discard, 0, c["acc_b"])
    c["acc_end"] = jnp.where(discard, 0, c["acc_end"])
    clear("rel", rel_due)

    # 3. rounds
    open_round = c["rnd_b"] > 0
    rnd_p = c["rnd_b"] % P
    ended = open_round & (
        ((release >= 0) & (rnd_p == release)) | (now >= c["rnd_deadline"])
    )
    extending = (attempt < 0) & (extend >= 0) & (c["own"] == extend)
    who = jnp.where(extending, extend, attempt)
    starts = who >= 0
    runs = ((t + 1) << RESTART_SHIFT) | of_slot(c["restarts"], who)
    ballot = runs * P + who
    kept = open_round & ~ended & ~starts
    c["rnd_b"] = jnp.where(starts, ballot, jnp.where(kept, c["rnd_b"], 0))
    c["rnd_phase"] = jnp.where(
        starts, PREPARING, jnp.where(kept, c["rnd_phase"], IDLE)
    )
    c["rnd_end"] = jnp.where(kept, c["rnd_end"], 0)
    c["rnd_deadline"] = jnp.where(
        starts, now + round_q4, jnp.where(kept, c["rnd_deadline"], 0)
    )
    c["opens"] = c["opens"] & kept[None, :]
    c["accepts"] = c["accepts"] & kept[None, :]

    # 4a. prepares out
    at, lost = leg(who)
    send("preq", starts[None, :] & ~lost, ballot[None, :], at)

    # 4b. prepares in: grant at or above the promise
    arrived = due("preq")
    b = c["preq_b"]
    grant = arrived & up & (b >= c["promised"])
    c["promised"] = jnp.where(grant, b, c["promised"])
    held = jnp.where(c["acc_b"] > 0, c["acc_b"] % P, NONE)
    at, lost = leg(b % P)
    reply = grant & ~lost
    send("presp", reply, b, at)
    c["presp_pay"] = jnp.where(reply, held, c["presp_pay"])
    clear("preq", arrived)

    # 4c. grants in: a majority of open replies starts the proposal
    arrived = due("presp")
    rnd_p = c["rnd_b"] % P
    preparing = (c["rnd_b"] > 0) & (c["rnd_phase"] == PREPARING)
    still_owns = c["own"] == rnd_p
    counted = (
        arrived & (c["presp_b"] == c["rnd_b"][None, :]) & preparing[None, :]
        & (
            (c["presp_pay"] == NONE)
            | ((c["presp_pay"] == rnd_p[None, :]) & still_owns[None, :])
        )
    )
    c["opens"] = c["opens"] | counted
    propose = preparing & (c["opens"].sum(0) >= majority)
    c["rnd_phase"] = jnp.where(propose, PROPOSING, c["rnd_phase"])
    c["rnd_end"] = jnp.where(propose, now + lease_q4, c["rnd_end"])
    at, lost = leg(rnd_p)
    send("poreq", propose[None, :] & ~lost, c["rnd_b"][None, :], at)
    clear("presp", arrived)
    c["presp_pay"] = jnp.where(arrived, NONE, c["presp_pay"])

    # 4d. proposals in: accept at or above the promise
    arrived = due("poreq")
    b = c["poreq_b"]
    accept = arrived & up & (b >= c["promised"])
    c["acc_b"] = jnp.where(accept, b, c["acc_b"])
    c["acc_end"] = jnp.where(accept, now + lease_q4, c["acc_end"])
    at, lost = leg(b % P)
    send("poresp", accept & ~lost, b, at)
    clear("poreq", arrived)

    # 4e. accepts in: a majority inside the proposer's timer wins
    arrived = due("poresp")
    proposing = (c["rnd_b"] > 0) & (c["rnd_phase"] == PROPOSING)
    c["accepts"] = c["accepts"] | (
        arrived & (c["poresp_b"] == c["rnd_b"][None, :]) & proposing[None, :]
    )
    win = proposing & (c["accepts"].sum(0) >= majority) & (c["rnd_end"] > now)
    rnd_p = c["rnd_b"] % P
    second = win & (c["own"] >= 0) & (c["own"] != rnd_p)
    c["own"] = jnp.where(win, rnd_p, c["own"])
    c["own_b"] = jnp.where(win, c["rnd_b"], c["own_b"])
    c["own_end"] = jnp.where(win, c["rnd_end"], c["own_end"])
    for k in ("rnd_b", "rnd_end", "rnd_deadline"):
        c[k] = jnp.where(win, 0, c[k])
    c["rnd_phase"] = jnp.where(win, IDLE, c["rnd_phase"])
    c["opens"] = c["opens"] & ~win[None, :]
    c["accepts"] = c["accepts"] & ~win[None, :]
    clear("poresp", arrived)

    count = (c["own"] >= 0).astype(jnp.int32) + second.astype(jnp.int32)
    return c, (c["own"], count)


@functools.partial(
    jax.jit,
    static_argnames=("P", "majority", "lease_q4", "round_q4", "deaf_q4",
                     "symmetric"),
)
def _scan(carry, place, attempts, releases, extends, acc_up, delay, drop,
          node_acc, node_prop, *, P, majority, lease_q4, round_q4, deaf_q4,
          symmetric):
    T = attempts.shape[0]
    ts = jnp.arange(T, dtype=jnp.int32)
    body = functools.partial(
        _tick, place=place, P=P, majority=majority, lease_q4=lease_q4,
        round_q4=round_q4, deaf_q4=deaf_q4, symmetric=symmetric,
    )
    return jax.lax.scan(
        body, carry,
        (ts, attempts, releases, extends, acc_up, delay, drop, node_acc,
         node_prop),
    )


def replay(planes: dict, placement, *, n_nodes: int, n_proposers: int,
           lease_ticks: int, max_lease_ticks: int, round_ticks: int,
           block: int = 1 << 18, control: str | None = None):
    """Owners and owner counts ([T, N] int32 numpy each) of a fleet that
    starts empty at tick 0 and follows ``planes`` — ``bench/reference.py``'s
    planes, plus ``acc_restart`` and ``prop_restart`` as ``[T, K]`` node
    rows — with replica slot ``k`` of cell ``n`` on node
    ``placement[k, n]`` ([A, N]), leases of ``lease_ticks`` and restarted
    acceptors deaf for ``max_lease_ticks`` (M). Runs in blocks of
    ``block`` cells."""
    if control not in (None, *CONTROLS):
        raise ValueError(f"unknown control {control!r}")
    attempts = np.asarray(planes["attempts"], np.int32)
    T, N = attempts.shape
    acc_up = np.asarray(planes["acc_up"]).astype(np.int32)
    A, P = acc_up.shape[1], n_proposers
    place = np.asarray(placement, np.int32)
    if control == "shifted":
        place = (place + 1) % n_nodes
    node = {}
    for k in ("acc_restart", "prop_restart"):
        v = planes.get(k)
        if v is None or control == "no_restarts":
            v = np.zeros((T, n_nodes), np.int32)
        node[k] = jnp.asarray(np.asarray(v, np.int32))
    kw = dict(
        P=P, majority=A // 2 + 1, lease_q4=4 * lease_ticks + 1,
        round_q4=4 * round_ticks,
        deaf_q4=0 if control == "no_deaf" else 4 * max_lease_ticks + 1,
    )
    delay, drop, kw["symmetric"] = _links([planes], T, P, A)
    delay, drop = jnp.asarray(delay[0]), jnp.asarray(drop[0])
    up = jnp.asarray(acc_up)
    owners = np.empty((T, N), np.int32)
    counts = np.empty((T, N), np.int32)
    for lo in range(0, N, block):
        hi = min(lo + block, N)
        cols = []
        for k in ("attempts", "releases", "extends"):
            v = planes.get(k)
            cols.append(
                np.full((T, hi - lo), NONE, np.int32) if v is None
                else np.ascontiguousarray(np.asarray(v)[:, lo:hi])
            )
        carry = init_carry(hi - lo, A)
        carry["deaf_until"] = jnp.zeros((A, hi - lo), jnp.int32)
        carry["restarts"] = jnp.zeros((P, hi - lo), jnp.int32)
        _, (own, cnt) = _scan(
            carry, jnp.asarray(place[:, lo:hi]), *map(jnp.asarray, cols),
            up, delay, drop, node["acc_restart"], node["prop_restart"], **kw,
        )
        owners[:, lo:hi] = np.asarray(own)
        counts[:, lo:hi] = np.asarray(cnt)
    return owners, counts


def restarted_cells(planes: dict, placement, n_nodes: int) -> int:
    """Acceptor restarts of the cells' replicas that ``planes`` schedule:
    each node's restarts times the replica slots placed on it."""
    per_node = np.bincount(np.asarray(placement).ravel(), minlength=n_nodes)
    arst = planes.get("acc_restart")
    if arst is None:
        return 0
    return int((np.asarray(arst) > 0).sum(axis=0) @ per_node)


def failed_over(owners, placement, restarts: list, n_racks: int) -> int:
    """Cells whose owner before a rack restart (``restarts``: (tick,
    rack) pairs) was the replica on a restarted node, and that have
    another owner after the last tick."""
    owners = np.asarray(owners)
    place = np.asarray(placement)
    cols = np.arange(owners.shape[1])
    last = owners[-1]
    total = 0
    for t_r, rack in restarts:
        before = owners[t_r - 1] if t_r else np.full_like(last, NONE)
        owned = before >= 0
        node = place[np.clip(before, 0, place.shape[0] - 1), cols]
        lost = owned & (node % n_racks == rack)
        total += int((lost & (last >= 0) & (last != before)).sum())
    return total
