"""§6 owner lease extension in the array plane (the ISSUE 10 tentpole).

The ``extends [T, N]`` registry plane schedules full in-flight renewal
rounds gated on the extender's own live belief. Contracts pinned here:
all-default extends is stripped host-side and leaves the honest engine
bit-identical (and the honest dispatch jaxpr byte-identical — the
staticcheck mirror); renewal-enabled chaos traces replay bit-exactly
against the event-sim referee on BOTH backends; the §6 edges (ghost
extend after guarded expiry, extend straddling a diskless acceptor
restart, extend racing a same-tick §7 release) agree with the referee;
the quiescence fast path (``skip_stable``) changes nothing bit-wise; and
an honest ≥1024-scenario extends sweep holds §4 in one dispatch.
"""
import numpy as np
import pytest

from repro.lease_array import LeaseArrayEngine, Scenario
from repro.lease_array.scenario import EXTEND_PLANES
from repro.lease_array.state import NO_PROPOSER
from repro.lease_array.trace import (
    Trace,
    random_trace,
    replay_array,
    replay_event_sim,
)
from test_lease_array_differential import assert_engines_agree

BACKENDS = ["jnp", "pallas"]
NA = NO_PROPOSER

#: the renewal-chaos mix every differential below draws from: sparse
#: attempts (dense attempts suppress the renew cadence — an extend too
#: close before a future attempt on the cell is dropped by the
#: generator), live §6 renewals, delay + drop + drift + outages
RENEW_CHAOS = dict(
    n_ticks=120, n_cells=6, n_acceptors=3, n_proposers=4, lease_ticks=6,
    p_attempt=0.12, p_release=0.04, renew=0.5, max_delay_ticks=1,
    p_drop=0.05, drift_eps=0.25,
    # the abandon deadline must outlive a full prepare+propose round over
    # the slowest links (4·delay + 1) or every extend round is abandoned
    # mid-flight — the renewal-collapse geometry the directory test pins
    round_ticks=5,
)


def _engine(trace: Trace, backend="jnp", **kw) -> LeaseArrayEngine:
    return LeaseArrayEngine(
        trace.n_cells, n_acceptors=trace.n_acceptors,
        n_proposers=trace.n_proposers, lease_ticks=trace.lease_ticks,
        round_ticks=trace.round_ticks, drift_eps=trace.drift_eps,
        backend=backend, **kw,
    )


# ------------------------------------------------------- all-default plane

def test_all_default_extends_bit_identical():
    """A scenario whose registry-filled extends plane is all-NO_PROPOSER
    is the pre-extend engine: same bits (the plane is stripped host-side,
    never uploaded, so honest replays don't compile the extend variant)."""
    tr = random_trace(7, n_ticks=60, n_cells=4, n_acceptors=3,
                      n_proposers=4, lease_ticks=3, max_delay_ticks=1,
                      p_drop=0.05, drift_eps=0.25)
    base_ow, base_cn = replay_array(tr)
    sc = tr.scenario()
    assert all(k in sc.planes for k in EXTEND_PLANES)  # registry-filled
    assert not sc.extended
    eng = _engine(tr)
    ow, cn = eng.run_trace(sc)
    assert np.array_equal(np.asarray(ow), np.asarray(base_ow))
    assert np.array_equal(np.asarray(cn), np.asarray(base_cn))


def test_honest_dispatch_jaxpr_untouched_by_default_extends():
    """The staticcheck mirror: stripping an all-default extends plane
    restores the honest ``_window_scan_impl`` jaxpr byte-for-byte."""
    from repro.analysis.staticcheck.purity import check_honest_strip

    assert check_honest_strip() == []


# ------------------------------------- renewal differentials vs the referee

def _longest_same_owner_run(owners: np.ndarray) -> np.ndarray:
    """Per-cell longest unbroken same-owner run, in ticks."""
    runs = np.zeros(owners.shape[1], np.int64)
    best = np.zeros(owners.shape[1], np.int64)
    prev = np.full(owners.shape[1], NA, np.int32)
    for row in owners:
        same = (row == prev) & (row >= 0)
        runs = np.where(same, runs + 1, (row >= 0).astype(np.int64))
        prev = row
        best = np.maximum(best, runs)
    return best


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_renew_differential_vs_referee(seed, backend):
    tr = random_trace(seed, **RENEW_CHAOS)
    assert tr.extended, "trace must actually schedule §6 renewals"
    owners = assert_engines_agree(tr, backend=backend)
    assert (owners >= 0).any()


@pytest.mark.parametrize("backend", BACKENDS)
def test_renewals_chain_past_the_lease_window(backend):
    """Drift-free renewal chaos: successful §6 extends must chain — an
    unbroken same-owner run longer than one un-renewed lease window could
    ever produce — and still replay bit-exactly against the referee.
    (With drift the guard-discounted window is shorter than the open-loop
    cadence, so chaining is a closed-loop property — the directory's.)"""
    tr = random_trace(9, **{**RENEW_CHAOS, "drift_eps": 0.0})
    assert tr.extended
    owners = assert_engines_agree(tr, backend=backend)
    assert (_longest_same_owner_run(owners)
            > RENEW_CHAOS["lease_ticks"] + 1).any(), \
        "no lease was ever extended past its own window"


@pytest.mark.slow
def test_thousand_tick_renew_chaos_differential():
    """1000 renewal-enabled ticks of delay + drop + drift + outages, both
    backends bit-exact against the referee — the tentpole's acceptance
    differential."""
    tr = random_trace(
        1234, **{**RENEW_CHAOS, "n_ticks": 1000, "n_cells": 8}
    )
    assert tr.extended
    jow = assert_engines_agree(tr, backend="jnp")
    pow_ = assert_engines_agree(tr, backend="pallas")
    assert np.array_equal(jow, pow_)
    # and drift-free at length: renewals chain through the whole replay
    calm = random_trace(
        1234, **{**RENEW_CHAOS, "n_ticks": 1000, "n_cells": 8,
                 "drift_eps": 0.0}
    )
    owners = assert_engines_agree(calm)
    assert (_longest_same_owner_run(owners)
            > RENEW_CHAOS["lease_ticks"] + 1).any()


# ------------------------------------------------------------ §6 edge cases

def _edge_trace(**kw) -> Trace:
    T, N, A, P = 16, 2, 3, 2
    base = dict(
        n_cells=N, n_acceptors=A, n_proposers=P, lease_ticks=2,
        attempts=np.full((T, N), NA, np.int32),
        releases=np.full((T, N), NA, np.int32),
        acc_up=np.ones((T, A), bool),
        extends=np.full((T, N), NA, np.int32),
        round_ticks=3,
    )
    base.update(kw)
    return Trace(
        base.pop("n_cells"), base.pop("n_acceptors"),
        base.pop("n_proposers"), **base,
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_extend_after_guarded_expiry_is_a_ghost_noop(backend):
    """§6 gates on the live belief: an extend scheduled after the owner's
    guarded window closed is a non-owner extend — a no-op in both engines
    (no resurrected lease), and a later fresh attempt still works."""
    tr = _edge_trace()
    tr.attempts[0, 0] = 0    # owner at t=0, expiry quarter 4·2+1 = 9
    tr.extends[6, 0] = 0     # lease lapsed at t=3; this is a ghost
    tr.attempts[10, 0] = 1   # the cell is genuinely free: cold acquire
    owners = assert_engines_agree(tr, backend=backend)
    assert (owners[:3, 0] == 0).all()
    assert (owners[3:10, 0] == NA).all(), "ghost extend resurrected a lease"
    assert (owners[10:13, 0] == 1).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_extend_in_time_rolls_the_lease(backend):
    """The positive control for the ghost test: the same schedule with the
    extend INSIDE the live window keeps the owner through a second span."""
    tr = _edge_trace()
    tr.attempts[0, 0] = 0
    tr.extends[2, 0] = 0     # still owned (expiry quarter 9 > 8)
    owners = assert_engines_agree(tr, backend=backend)
    assert (owners[:5, 0] == 0).all(), "in-window extend did not roll"
    assert (owners[6:, 0] == NA).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_extend_straddling_acceptor_restart_deaf_window(backend):
    """A diskless acceptor restart in the middle of an extend round: the
    restarted node is blank + deaf (§2/§3 M-wait), the round must win or
    lapse identically in both engines."""
    T, N, A, P = 24, 2, 3, 2
    tr = Trace(
        N, A, P, lease_ticks=6,
        attempts=np.full((T, N), NA, np.int32),
        releases=np.full((T, N), NA, np.int32),
        acc_up=np.ones((T, A), bool),
        delay=np.ones((T, A), np.int32),
        extends=np.full((T, N), NA, np.int32),
        acc_restarts=np.zeros((T, A), np.int32),
        round_ticks=5,
    )
    tr.attempts[0, 0] = 0     # 1-tick legs: owner at t=4, through t=8
    # t=5, not t=4: an extend issued the tick the win lands still sees the
    # stale pre-win belief (phase order) and is a no-op in both engines
    tr.extends[5, 0] = 0      # extend round runs t=5..9 (4·delay ticks)
    tr.acc_restarts[7, 0] = 1  # acceptor 0 blanks mid-round, goes deaf
    owners = assert_engines_agree(tr, backend=backend)
    # quorum of the two live acceptors carries the extend: the lease rolls
    # seamlessly into a second span (new expiry minted at propose tick 7)
    assert (owners[4:13, 0] == 0).all()
    assert (owners[14:, 0] == NA).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_extend_racing_same_tick_release(backend):
    """§7 release and a §6 extend on the same (tick, cell): the release
    lands first (it already cleared the belief), the extend is a no-op —
    same verdict in both engines, and the cell frees up."""
    tr = _edge_trace()
    tr.attempts[0, 0] = 0
    tr.releases[2, 0] = 0
    tr.extends[2, 0] = 0
    owners = assert_engines_agree(tr, backend=backend)
    assert (owners[:2, 0] == 0).all()
    assert (owners[2:, 0] == NA).all(), "extend outran the same-tick release"


# ------------------------------------------------- quiescence fast path

@pytest.mark.parametrize("backend", BACKENDS)
def test_skip_stable_is_bitwise_invisible(backend):
    """The quiescence compaction (skip near-zero VMEM work on stable
    (block, window) pairs) is a pure fast path: bit-identical owners and
    counts with it on and off, under live renewals."""
    tr = random_trace(5, **RENEW_CHAOS)
    sc = tr.scenario()
    on = _engine(tr, backend=backend, skip_stable=True)
    off = _engine(tr, backend=backend, skip_stable=False)
    ow1, cn1 = on.run_trace(sc)
    ow2, cn2 = off.run_trace(sc)
    assert np.array_equal(np.asarray(ow1), np.asarray(ow2))
    assert np.array_equal(np.asarray(cn1), np.asarray(cn2))


# --------------------------------------------------- honest extends sweep

def test_honest_extends_sweep_single_dispatch_holds_section4():
    """≥1024 random honest scenarios with live extends planes, one
    ``engine.sweep`` dispatch, zero §4 violations (verify=True raises on
    any owner-count overlap)."""
    from repro.lease_array.falsify.search import (
        FalsifyConfig,
        random_population,
    )

    cfg = FalsifyConfig(pop_size=1024, extends=True, corrupt=False)
    planes = random_population(np.random.default_rng(42), cfg)
    assert (planes["extends"] != NA).any()
    eng = cfg.engine()
    res = eng.sweep(Scenario(planes), collect="summary", verify=True)
    assert int(res.max_owner_count.max()) <= 1
    assert res.max_owner_count.shape == (1024,)


# -------------------------------------- the directory renewal-collapse fix

def _healthy_directory(max_delay_ticks: int, lease_ticks: int = 12,
                       **kw) -> "LeaseArrayDirectory":
    from repro.lease_array.directory import LeaseArrayDirectory

    d = LeaseArrayDirectory(
        128, n_acceptors=3, lease_ticks=lease_ticks, max_workers=4,
        max_delay_ticks=max_delay_ticks, **kw,
    )
    for i in range(4):
        d.add_worker(i, 32)
    return d


# an extend round takes 4·delay+1 ticks end to end, so the lease must be
# long enough to contain one: delay-4 legs need a lease past 17 ticks
@pytest.mark.parametrize("max_delay_ticks,lease_ticks",
                         [(0, 12), (2, 12), (4, 24)])
def test_directory_sustains_renewals_under_link_delay(max_delay_ticks,
                                                      lease_ticks):
    """The bugfix's acceptance shape: with the full-round renew margin,
    round-trip pacing and a round deadline sized to the links, the
    directory holds ≥ 95% of its shards through many lease generations at
    delay ≤ 4 (the seed collapsed to owned_frac 0.05 here)."""
    d = _healthy_directory(max_delay_ticks, lease_ticks)
    d.tick(8 * max_delay_ticks + 10)  # warmup: acquire everything
    assert d.coverage() == 1.0
    fracs = []
    for _ in range(6 * d.engine.lease_ticks):  # many renewal generations
        d.tick(1)
        fracs.append(d.coverage())
    assert min(fracs) >= 0.95, f"renewal collapse: min owned_frac {min(fracs)}"


def test_directory_delay_blind_margin_and_redrive_collapse():
    """Negative control: the seed's geometry — a delay-blind renew margin
    driven every tick (each re-issue overwrites the open extend round,
    netplane phase 3) — collapses coverage, proving the fix is what holds
    the line above."""
    d = _healthy_directory(4, 24)
    d.tick(50)
    assert d.coverage() == 1.0
    d._round_trip = 1       # per-tick re-drive: the old behavior
    d._cooldown[:] = 0
    d.tick(6 * d.engine.lease_ticks)
    assert d.coverage() <= 0.5, "per-tick re-drive should livelock renewals"


def test_directory_rejects_unservable_renewal_geometry():
    from repro.lease_array.directory import LeaseArrayDirectory

    with pytest.raises(ValueError, match="cannot be renewed"):
        LeaseArrayDirectory(8, n_acceptors=3, lease_ticks=2,
                            max_delay_ticks=2)
    # the half-trip fallacy: 12 ticks LOOKS renewable over delay-4 legs
    # (2·4+1 = 9 < 12) but a full extend round is 17 ticks — unservable
    with pytest.raises(ValueError, match="cannot be renewed"):
        LeaseArrayDirectory(8, n_acceptors=3, lease_ticks=12,
                            max_delay_ticks=4)
    with pytest.raises(ValueError, match="below the worst-case"):
        LeaseArrayDirectory(8, n_acceptors=3, lease_ticks=24,
                            max_delay_ticks=4, renew_margin=12)


# ------------------------------------------ the directory's §6 renewal step

class _SpanLog:
    """Stands in for the directory's span: every span reads as traced and
    keeps its metadata, in the order the spans close."""

    def __init__(self):
        self.closed = []

    def is_enabled(self):
        return True

    def __call__(self, name):
        log = self

        class _Span:
            meta = {}

            def __enter__(self):
                self.meta = {}
                return self

            def __exit__(self, *exc):
                log.closed.append((name, self.meta))

            def set_metadata(self, **kw):
                self.meta.update(kw)

        return _Span()


def _renewal_rule(d, owners, ticks_left, cooldown, release):
    """The §6 renewal step cell by cell, over the state a tick starts
    from: each owned shard inside the renew margin and off cooldown is a
    candidate; its owner extends it if that owner is a registered worker,
    neither stalled nor draining, and not shedding the shard this tick.
    Returns the extends plane, the cooldown after it, and the candidate
    count."""
    extend = np.full(d.n_shards, NA, np.int32)
    cooldown = cooldown.copy()
    by_slot = {w.slot: w for w in d.workers.values()}
    candidates = np.flatnonzero(
        (owners >= 0) & (ticks_left <= d.renew_margin) & (cooldown == 0)
    )
    for cell in candidates:
        w = by_slot.get(int(owners[cell]))
        if w is not None and not w.stalled and not w.draining:
            if release[cell] != w.slot:
                extend[cell] = w.slot
                cooldown[cell] = d._round_trip
    return extend, cooldown, len(candidates)


@pytest.mark.parametrize("max_delay_ticks,lease_ticks",
                         [(0, 6), (1, 8), (2, 12)])
def test_directory_renewals_match_the_per_cell_rule(monkeypatch,
                                                     max_delay_ticks,
                                                     lease_ticks):
    """Through an acquire storm, a stall, a target lowered a few shards a
    tick (so shed shards fall inside the renew margin) and a drain, every
    tick's extends plane, the cooldown after it and the renewal span's
    ``candidates`` are what the per-cell rule gives over the same owners,
    ticks left, cooldown and releases."""
    from repro.lease_array import directory as directory_mod
    from repro.lease_array.directory import LeaseArrayDirectory

    issued = []
    make_tick = directory_mod.make_tick

    def spy(**planes):
        issued.append(planes)
        return make_tick(**planes)

    spans = _SpanLog()
    monkeypatch.setattr(directory_mod, "make_tick", spy)
    monkeypatch.setattr(directory_mod, "span", spans)
    d = LeaseArrayDirectory(128, n_acceptors=3, lease_ticks=lease_ticks,
                            max_workers=5, max_delay_ticks=max_delay_ticks,
                            backend="jnp")
    for w in range(4):
        d.add_worker(w, 32)
    # proposer 4, the plane's slot with no worker, takes 8 shards before
    # the directory's first tick: no worker renews them, so they lapse
    attempts = np.full(128, NA, np.int32)
    attempts[:8] = 4
    links = ({"delay": np.full(3, max_delay_ticks, np.int32)}
             if max_delay_ticks else {})
    for k in range(d._round_trip):
        d._owners = d.engine.step(make_tick(
            n_cells=128, n_acceptors=3, n_proposers=5,
            attempts=attempts if k == 0 else None, **links,
        )).astype(np.int32)
    assert (d._owners[:8] == 4).all()
    lefts = []
    ticks_left = d.engine.ticks_left

    def kept_ticks_left():
        lefts.append(ticks_left())
        return lefts[-1]

    d.engine.ticks_left = kept_ticks_left

    events = {2 * lease_ticks: lambda: d.stall(1),
              4 * lease_ticks + 2: lambda: d.drain(2)}
    for k in range(lease_ticks):  # 32 -> 20 shards, a few a tick
        events[3 * lease_ticks + k] = (
            lambda k=k: d.set_target(0, 32 - 2 * (k + 1)))
    shed_due = stalled_due = unheld_due = extended = 0
    for t in range(5 * lease_ticks + 4):
        if t in events:
            events[t]()
        owners, cooldown = d._owners.copy(), d._cooldown.copy()
        stalled = {w.slot for w in d.workers.values() if w.stalled}
        d.tick(1)
        planes = issued[-1]
        release = planes["releases"]
        extend, cool, n_candidates = _renewal_rule(
            d, owners, lefts[-1], np.maximum(cooldown - 1, 0), release)
        assert np.array_equal(planes["extends"], extend), f"tick {t}"
        # the assign step then cools the cells it attempts
        cool[planes["attempts"] >= 0] = d._round_trip
        assert np.array_equal(d._cooldown, cool), f"tick {t}"
        renews = [m for n, m in spans.closed if n == "lease.dir.renew"]
        assert len(renews) == t + 1
        renew_meta = renews[-1]
        assert renew_meta == {"candidates": n_candidates,
                              "extends": int(np.count_nonzero(extend >= 0))}
        due = ((owners >= 0) & (lefts[-1] <= d.renew_margin)
               & (np.maximum(cooldown - 1, 0) == 0))
        shed_due += int(np.count_nonzero(due & (release == owners)))
        stalled_due += int(np.count_nonzero(
            due & np.isin(owners, list(stalled))))
        unheld_due += int(np.count_nonzero(due & (owners == 4)))
        extended += int(np.count_nonzero(extend >= 0))
    # the run reached every branch of the rule
    assert shed_due and stalled_due and unheld_due and extended
