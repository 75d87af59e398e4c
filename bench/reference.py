"""Plain reference of a fleet of PaxosLease cells (arXiv 1209.4187).

This is the yardstick the benchmark holds the lease plane to. It imports
nothing of the program: it restates the semantics the program documents
(one tick of §3's prepare/propose round over a lossy, delayed network,
§4's lease timers, §6 extensions and §7 releases) with every field kept
apart, in plain ``jax.numpy`` under ``lax.scan``, one cell per lane.

The tick, in the order the program's tick runs it (time is counted in
quarter-ticks, ``now = 4 t``; a lease granted at ``now`` ends at
``now + 4 L + 1``, strictly between two ticks):

1. expiry: an acceptor's accepted lease and the proposer's belief lapse
   once their end is at or before ``now``;
2. release (§7): the named proposer, if it owns the cell, stops believing
   at once and sends a discard to every acceptor over its links; a due
   discard clears an acceptor's lease if that acceptor is reachable and
   still holds the released ballot;
3. rounds: a release by the round's proposer, or its abandon deadline,
   ends the open round; an attempt (or an extension by the live owner,
   §6, where no attempt is named) opens a new one with the ballot
   ``(t + 1) P + p``;
4. deliveries in causal order, a zero-delay leg landing in the same tick:
   prepares (granted at or above the promise; the grant carries the
   acceptor's accepted proposer), grants (a majority of open replies, or
   replies naming the proposer itself while it still owns, starts the
   proposer's timer and sends proposals), proposals (accepted at or above
   the promise, starting the acceptor's timer) and accepts (a majority
   before the proposer's timer ends makes it the owner).

A leg sent at tick ``t`` between proposer ``p`` and acceptor ``a`` takes
``delay[t, p, a]`` ticks and is lost if ``drop[t, p, a]``; a request
reaching an unreachable acceptor is lost. Each (acceptor, cell) holds one
message of each kind in flight; a newer one replaces it.

The outputs are those of a replay: the owner of every cell after every
tick (-1 for none) and the number of owners (a win over another live
owner counts 2, the §4 alarm).

``control`` names a control, the reference with the guarantee of at most
one owner broken; a configuration names the one its traffic exercises,
and the comparison that decides ``correct`` must fail it:

- ``"lie_open"``: acceptors answer every prepare as if they held no lease
  (a broken §3.3), so a contender wins over a live lease;
- ``"late_timer"``: a proposer starts its lease timer when its accepts are
  in, not before it sends its proposals (a broken §4 ordering), so it
  believes past the end of the acceptors' leases.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NONE = -1
IDLE, PREPARING, PROPOSING = 0, 1, 2
SLOTS = ("preq", "presp", "poreq", "poresp", "rel")


def init_carry(n_cells: int, n_acceptors: int) -> dict:
    za = jnp.zeros((n_acceptors, n_cells), jnp.int32)
    zn = jnp.zeros((n_cells,), jnp.int32)
    fa = jnp.zeros((n_acceptors, n_cells), bool)
    carry = {
        "promised": za, "acc_b": za, "acc_end": za,
        "own": zn + NONE, "own_b": zn, "own_end": zn,
        "rnd_b": zn, "rnd_phase": zn, "rnd_end": zn, "rnd_deadline": zn,
        "opens": fa, "accepts": fa, "presp_pay": za + NONE,
    }
    for s in SLOTS:
        carry[s + "_b"] = za
        carry[s + "_at"] = za
    return carry


def _tick(carry, xs, *, P, majority, lease_q4, round_q4, control,
          symmetric):
    t, attempt, release, extend, up, delay, drop = xs
    c = dict(carry)
    A = c["promised"].shape[0]
    now = 4 * t
    up = (up > 0)[:, None]                                   # [A, 1]

    def leg(p):
        """(arrival quarter-tick, lost) of a leg sent now on the link of
        proposer ``p`` ([N] or [A, N]) with every acceptor: [A, N] each.
        Symmetric links ([A] per tick) need no proposer at all."""
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
    extending = (
        (attempt < 0) & (extend >= 0) & (c["own"] == extend)
    )
    who = jnp.where(extending, extend, attempt)
    starts = who >= 0
    ballot = (t + 1) * P + who
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
    if control == "lie_open":
        held = jnp.full_like(held, NONE)
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
    # the proposer's own timer starts now, before its proposals go out
    # (the control starts it only once it wins: below)
    late = control == "late_timer"
    timer = (1 << 30) if late else now + lease_q4
    c["rnd_end"] = jnp.where(propose, timer, c["rnd_end"])
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
    own_end = now + lease_q4 if late else c["rnd_end"]
    c["own_end"] = jnp.where(win, own_end, c["own_end"])
    for k in ("rnd_b", "rnd_end", "rnd_deadline"):
        c[k] = jnp.where(win, 0, c[k])
    c["rnd_phase"] = jnp.where(win, IDLE, c["rnd_phase"])
    c["opens"] = c["opens"] & ~win[None, :]
    c["accepts"] = c["accepts"] & ~win[None, :]
    clear("poresp", arrived)

    count = (c["own"] >= 0).astype(jnp.int32) + second.astype(jnp.int32)
    return c, (c["own"], count, c["own_end"])


@functools.partial(
    jax.jit,
    static_argnames=("P", "majority", "lease_q4", "round_q4", "control",
                     "symmetric"),
)
def _scan(carry, t0, attempts, releases, extends, acc_up, delay, drop, *,
          P, majority, lease_q4, round_q4, control, symmetric):
    T = attempts.shape[0]
    ts = t0 + jnp.arange(T, dtype=jnp.int32)
    body = functools.partial(
        _tick, P=P, majority=majority, lease_q4=lease_q4, round_q4=round_q4,
        control=control, symmetric=symmetric,
    )
    return jax.lax.scan(
        body, carry, (ts, attempts, releases, extends, acc_up, delay, drop)
    )


def _links(scenarios: list, T: int, P: int, A: int) -> tuple:
    """The delay and drop planes of each scenario, stacked, and whether
    every link is symmetric (the same for every proposer). Symmetric
    planes come as [B, T, A], others as [B, T, P, A] (int32)."""
    def full(plane):
        if plane is None:
            return np.zeros((T, P, A), np.int32)
        plane = np.asarray(plane).astype(np.int32)
        if plane.ndim == 2:
            plane = np.broadcast_to(plane[:, None, :], (T, P, A))
        return plane

    delay = np.stack([full(sc.get("delay")) for sc in scenarios])
    drop = np.stack([full(sc.get("drop")) for sc in scenarios])
    symmetric = bool(
        (delay == delay[:, :, :1]).all() and (drop == drop[:, :, :1]).all()
    )
    if symmetric:
        delay, drop = delay[:, :, 0], drop[:, :, 0]
    return delay, drop, symmetric


def replay(planes: dict, *, n_proposers: int, lease_ticks: int,
           round_ticks: int, block: int = 1 << 18, control: str | None = None,
           lease_ends: bool = False):
    """Owners and owner counts ([T, N] int32 numpy each) of a fleet that
    starts empty at tick 0 and follows ``planes``: ``attempts``,
    ``releases`` and (optional) ``extends`` [T, N]; ``acc_up`` [T, A];
    ``delay`` and ``drop`` [T, A] or [T, P, A] (optional). With
    ``lease_ends`` also the quarter-tick at which each owner's belief
    ends after each tick ([T, N], 0 where no one owns).

    Cells are independent, so the fleet runs in blocks of ``block`` cells
    to bound device memory."""
    attempts = np.asarray(planes["attempts"], np.int32)
    T, N = attempts.shape
    acc_up = np.asarray(planes["acc_up"]).astype(np.int32)
    A, P = acc_up.shape[1], n_proposers
    kw = dict(
        P=P, majority=A // 2 + 1, lease_q4=4 * lease_ticks + 1,
        round_q4=4 * round_ticks, control=control,
    )
    delay, drop, kw["symmetric"] = _links([planes], T, P, A)
    delay, drop = jnp.asarray(delay[0]), jnp.asarray(drop[0])
    up = jnp.asarray(acc_up)
    owners = np.empty((T, N), np.int32)
    counts = np.empty((T, N), np.int32)
    ends = np.empty((T, N), np.int32) if lease_ends else None
    empty = None
    for lo in range(0, N, block):
        hi = min(lo + block, N)
        cols = []
        for k in ("attempts", "releases", "extends"):
            v = planes.get(k)
            if v is None:
                if empty is None or empty.shape[1] != hi - lo:
                    empty = np.full((T, hi - lo), NONE, np.int32)
                cols.append(empty)
            else:
                cols.append(np.ascontiguousarray(np.asarray(v)[:, lo:hi]))
        _, (own, cnt, end) = _scan(
            init_carry(hi - lo, A), jnp.int32(0), *map(jnp.asarray, cols),
            up, delay, drop, **kw,
        )
        owners[:, lo:hi] = np.asarray(own)
        counts[:, lo:hi] = np.asarray(cnt)
        if lease_ends:
            ends[:, lo:hi] = np.asarray(end)
    return (owners, counts, ends) if lease_ends else (owners, counts)


@functools.partial(
    jax.jit,
    static_argnames=("P", "majority", "lease_q4", "round_q4", "control",
                     "symmetric"),
)
def _summaries(attempts, releases, extends, acc_up, delay, drop, **kw):
    """Per scenario of a [B, ...] batch: the largest owner count, the
    number of owned cell-ticks and the owners after the last tick."""

    def one(a, r, e, up, d, dr):
        _, (own, cnt, _) = _scan(
            init_carry(a.shape[1], up.shape[1]), jnp.int32(0), a, r, e, up,
            d, dr, **kw,
        )
        return cnt.max(), jnp.sum(own >= 0, dtype=jnp.int32), own[-1]

    return jax.vmap(one)(attempts, releases, extends, acc_up, delay, drop)


def replay_batch(scenarios: list, *, n_proposers: int, lease_ticks: int,
                 round_ticks: int, block: int = 64,
                 control: str | None = None) -> dict:
    """What a summary sweep reports of each scenario of ``scenarios`` (a
    list of plane dicts of one shape, as :func:`replay` takes them), run
    ``block`` scenarios at a time: ``max_owner_count`` and
    ``owned_cell_ticks`` [B], ``final_owners`` [B, N]."""
    T, N = np.shape(scenarios[0]["attempts"])
    A, P = np.shape(scenarios[0]["acc_up"])[1], n_proposers
    kw = dict(
        P=P, majority=A // 2 + 1, lease_q4=4 * lease_ticks + 1,
        round_q4=4 * round_ticks, control=control,
    )
    delay, drop, kw["symmetric"] = _links(scenarios, T, P, A)
    empty = np.full((T, N), NONE, np.int32)
    out = {"max_owner_count": [], "owned_cell_ticks": [], "final_owners": []}
    for lo in range(0, len(scenarios), block):
        part = scenarios[lo:lo + block]
        stack = lambda f: jnp.asarray(np.stack([f(sc) for sc in part]))
        cell = lambda k: stack(
            lambda sc: empty if sc.get(k) is None else sc[k]
        )
        res = _summaries(
            cell("attempts"), cell("releases"), cell("extends"),
            stack(lambda sc: np.asarray(sc["acc_up"], np.int32)),
            jnp.asarray(delay[lo:lo + block]),
            jnp.asarray(drop[lo:lo + block]), **kw,
        )
        for key, v in zip(out, res):
            out[key].append(np.asarray(v))
    return {k: np.concatenate(v) for k, v in out.items()}


def directory_planes(owners, lease_ends, *, n_workers: int, target: int,
                     stalls: list, lease_ticks: int, max_delay_ticks: int):
    """The attempts, releases and extends ([T, N] int32 each) that a shard
    directory's documented policy issues, tick by tick, over a fleet whose
    owners and owner lease ends after each tick are ``owners`` and
    ``lease_ends`` (as :func:`replay` gives them). Worker ``w`` proposes
    as ``w``, every worker wants ``target`` shards, and ``stalls`` lists
    (tick, worker) pairs: from its tick on, that worker is silent.

    Before each tick ``t``, with the owners after tick ``t - 1``:

    - a per-shard cooldown counts down by one;
    - a live worker that owns more than its target releases its
      highest-numbered shards down to the target;
    - a live owner whose lease has at most ``renew_margin`` ticks left,
      and whose shard is not cooling down, extends it (§6); the shard
      then cools down for a round trip, ``4 * max_delay_ticks + 1``;
    - unowned shards that are not cooling down go, lowest first, to the
      live workers short of their target, one shard to each in turn
      (workers by number, as long as each is short), and cool down.

    ``renew_margin`` is ``max(lease_ticks // 2, round trip, 1)``; a lease
    end ``e`` leaves ``max(e - 4 t, 0) // 4`` ticks before tick ``t``."""
    owners = np.asarray(owners)
    T, N = owners.shape
    rtt = 4 * max_delay_ticks + 1
    margin = max(lease_ticks // 2, rtt, 1)
    out = {k: np.full((T, N), NONE, np.int32)
           for k in ("attempts", "releases", "extends")}
    cooldown = np.zeros(N, np.int32)
    live = np.ones(n_workers, bool)
    stalled_at = dict(stalls)
    for t in range(T):
        if t in stalled_at:
            live[stalled_at[t]] = False
        own = owners[t - 1] if t else np.full(N, NONE, np.int32)
        left = (np.maximum(lease_ends[t - 1] - 4 * t, 0) // 4 if t
                else np.zeros(N, np.int32))
        left = np.where(own >= 0, left, 0)
        cooldown = np.maximum(cooldown - 1, 0)
        rel, ext, att = (out[k][t] for k in
                         ("releases", "extends", "attempts"))
        counts = np.bincount(own[own >= 0], minlength=n_workers)
        short = np.zeros(n_workers, np.int64)
        for w in np.flatnonzero(live):
            if counts[w] > target:
                mine = np.flatnonzero(own == w)
                rel[mine[target:]] = w
            short[w] = max(target - counts[w], 0)
        owner_live = np.zeros(N, bool)
        owner_live[own >= 0] = live[own[own >= 0]]
        renew = (owner_live & (left <= margin) & (cooldown == 0)
                 & (rel != own))
        ext[renew] = own[renew]
        cooldown[renew] = rtt
        # worker w's k-th shard comes in turn k: order by (k, w)
        ws = np.flatnonzero(short)
        who = np.repeat(ws, short[ws])
        turn = np.concatenate([np.arange(short[w]) for w in ws] or [[]])
        seq = who[np.lexsort((who, turn))]
        free = np.flatnonzero((own < 0) & (cooldown == 0))
        n = min(len(seq), len(free))
        att[free[:n]] = seq[:n]
        cooldown[free[:n]] = rtt
    return out
