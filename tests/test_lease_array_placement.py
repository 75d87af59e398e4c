"""Placed replicas: per-cell diskless restarts read through a shard-to-node
placement (``scenario.py``'s ``placement`` plane, ``[A, N]`` node ids).

With a placement the crash/restart planes are ``[T, K]`` node rows: a
restart of node ``j`` blanks (then deafens) the acceptor and restarts the
proposer of every cell's replica on ``j``. A placed ``run_trace`` must
agree bit for bit with the jnp scan, the interpret-mode kernel, and one
shared-plane ``run_trace`` per range of cells on the same nodes (today's
per-slot planes, fed that range's node rows); the event sim spot-checks
single cells. The identity placement is the shared planes themselves;
what a placement cannot express is refused; the run's span counters
count the planes."""
import numpy as np
import pytest

from repro.lease_array import LeaseArrayEngine, Scenario
from repro.lease_array import engine as engine_mod
from repro.lease_array.ops import lease_window_scan, strip_default_planes
from repro.lease_array.netplane import init_netplane
from repro.lease_array.scenario import make_tick
from repro.lease_array.state import MAX_RESTARTS, init_state
from repro.lease_array.trace import (
    Trace,
    random_trace,
    replay_event_sim,
)

A = P = 3
RANGES, PER_RANGE, K, T = 8, 16, 8, 96
N = RANGES * PER_RANGE
LEASE, ROUND, DELAY = 4, 3, 1
#: the deaf spans M the placed replays are checked under: the lease
#: itself (the engine's default) and a longer maximal lease
SPANS = [None, 3 * LEASE + 2]


def _placement(ranges=RANGES, per_range=PER_RANGE, n_nodes=K, shift=0):
    """Chained declustering: replica k of range r on node (r + k + shift)
    mod K, each range ``per_range`` contiguous cells."""
    r = np.repeat(np.arange(ranges), per_range)
    return np.stack([(r + k + shift) % n_nodes for k in range(A)]).astype(
        np.int32
    )


def _node_rows(seed, n_ticks=T, n_nodes=K):
    """0/1 ``[T, K]`` acceptor and proposer restart rows: each node
    restarts whole (both) at most twice, plus one acceptor-only and one
    proposer-only restart somewhere."""
    rng = np.random.default_rng(seed)
    arst = np.zeros((n_ticks, n_nodes), np.int32)
    prst = np.zeros((n_ticks, n_nodes), np.int32)
    for j in range(n_nodes):
        for t in rng.choice(np.arange(4, n_ticks - 4), rng.integers(0, 3),
                            replace=False):
            arst[t, j] = prst[t, j] = 1
    arst[rng.integers(0, n_ticks), rng.integers(0, n_nodes)] = 1
    j = rng.integers(0, n_nodes)
    if prst[:, j].sum() < MAX_RESTARTS:
        prst[rng.integers(0, n_ticks), j] = 1
    return arst, prst


def _traffic(seed, n_cells=N, n_ticks=T):
    tr = random_trace(
        seed, n_ticks=n_ticks, n_cells=n_cells, n_acceptors=A,
        n_proposers=P, lease_ticks=LEASE, max_delay_ticks=DELAY,
        p_drop=0.05, p_down_flip=0.0, renew=0.4, round_ticks=ROUND,
    )
    return tr, dict(attempts=tr.attempts, releases=tr.releases,
                    extends=tr.extends, delay=tr.delay, drop=tr.drop)


def _engine(n_cells=N, backend="jnp", **kw):
    return LeaseArrayEngine(
        n_cells, n_acceptors=A, n_proposers=P, lease_ticks=LEASE,
        round_ticks=ROUND, backend=backend, **kw,
    )


def _placed(seed, place=None, n_nodes=K):
    _, planes = _traffic(seed)
    arst, prst = _node_rows(seed)
    place = _placement() if place is None else place
    sc = Scenario.build(
        T, n_cells=N, n_acceptors=A, n_proposers=P, n_nodes=n_nodes,
        placement=place, acc_restart=arst, prop_restart=prst, **planes,
    )
    return sc, planes, arst, prst, place


@pytest.fixture(scope="module", params=SPANS, ids=["m=lease", "m=long"])
def placed_jnp(request):
    """A placed replay on the jnp scan, and the deaf span M it ran with."""
    m = request.param
    sc, planes, arst, prst, place = _placed(11)
    return ((sc, planes, arst, prst, place),
            _engine(max_lease_ticks=m).run_trace(sc), m)


def test_placed_jnp_equals_per_range_shared_planes(placed_jnp):
    """Each range's cells share their nodes, so the range replays on
    today's per-slot planes: slot k's rows are node (r + k)'s."""
    (_, planes, arst, prst, place), (owners, counts), m = placed_jnp
    want_o, want_c = [], []
    for r in range(RANGES):
        cols = slice(r * PER_RANGE, (r + 1) * PER_RANGE)
        nodes = place[:, cols.start]
        sc = Scenario.build(
            T, n_cells=PER_RANGE, n_acceptors=A, n_proposers=P,
            **{k: (v[:, cols] if v.ndim == 2 and v.shape[1] == N else v)
               for k, v in planes.items() if v is not None},
            acc_restart=arst[:, nodes], prop_restart=prst[:, nodes],
        )
        o, c = _engine(PER_RANGE, max_lease_ticks=m).run_trace(sc)
        want_o.append(o)
        want_c.append(c)
    np.testing.assert_array_equal(owners, np.concatenate(want_o, axis=1))
    np.testing.assert_array_equal(counts, np.concatenate(want_c, axis=1))
    assert counts.max() <= 1
    # the restarts matter: without them the replay differs
    bare = Scenario.build(T, n_cells=N, n_acceptors=A, n_proposers=P,
                          **{k: v for k, v in planes.items() if v is not None})
    assert (_engine().run_trace(bare)[0] != owners).any()


def test_a_longer_deaf_span_changes_the_placed_replay(placed_jnp):
    """M is honored: the same placed replay under the other span of
    ``SPANS`` differs."""
    (sc, *_), (owners, _), m = placed_jnp
    other = SPANS[1] if m is None else None
    assert (_engine(max_lease_ticks=other).run_trace(sc)[0] != owners).any()


def test_placed_kernel_interpret_equals_jnp(placed_jnp):
    (sc, *_), (owners, counts), m = placed_jnp
    o, c = _engine(backend="pallas", max_lease_ticks=m).run_trace(sc)
    np.testing.assert_array_equal(o, owners)
    np.testing.assert_array_equal(c, counts)


@pytest.mark.parametrize("cell", [0, 37, 100])
def test_placed_cells_match_the_event_sim(placed_jnp, cell):
    """One cell alone, its replicas' node rows as the per-slot restart
    schedules, through the event-driven referee."""
    (_, planes, arst, prst, place), (owners, _), m = placed_jnp
    tr, _ = _traffic(11)
    nodes = place[:, cell]
    one = Trace(
        n_cells=1, n_acceptors=A, n_proposers=P, lease_ticks=LEASE,
        attempts=tr.attempts[:, [cell]], releases=tr.releases[:, [cell]],
        acc_up=tr.acc_up, round_ticks=ROUND, delay=tr.delay, drop=tr.drop,
        extends=tr.extends[:, [cell]],
        acc_restarts=arst[:, nodes].copy(), prop_restarts=prst[:, nodes].copy(),
    )
    np.testing.assert_array_equal(
        replay_event_sim(one, max_lease_ticks=m)[:, 0], owners[:, cell]
    )


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_identity_placement_is_the_shared_planes(backend):
    """K = A with every cell on nodes 0..A-1: the node rows are the slot
    rows, bit for bit, in one call and across two."""
    _, planes = _traffic(5)
    arst, prst = _node_rows(5, n_nodes=A)
    ident = np.repeat(np.arange(A, dtype=np.int32)[:, None], N, axis=1)
    kw = dict(n_cells=N, n_acceptors=A, n_proposers=P,
              **{k: v for k, v in planes.items() if v is not None},
              acc_restart=arst, prop_restart=prst)
    shared = Scenario.build(T, **kw)
    placed = Scenario.build(T, n_nodes=A, placement=ident, **kw)
    want = _engine(backend=backend).run_trace(shared)
    got = _engine(backend=backend).run_trace(placed)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    # split in two: the node history carries the counters and deaf windows
    e_shared, e_placed = _engine(backend=backend), _engine(backend=backend)
    for part in (slice(0, 40), slice(40, T)):
        w = e_shared.run_trace(shared[part])
        g = e_placed.run_trace(placed[part])
        np.testing.assert_array_equal(g[0], w[0])
        np.testing.assert_array_equal(g[1], w[1])


def test_lease_window_scan_takes_a_placement(placed_jnp):
    """The ops entry point derives the restart table itself."""
    (sc, *_), (owners, counts), m = placed_jnp
    e = _engine(max_lease_ticks=m)
    _, _, o, c = lease_window_scan(
        init_state(N, A, P), init_netplane(N, A), 0, dict(sc.planes),
        majority=e.majority, lease_q4=e.lease_q4, round_q4=e.round_q4,
        backend="jnp", max_lease_q4=e.max_lease_q4,
    )
    np.testing.assert_array_equal(np.asarray(o), owners)
    np.testing.assert_array_equal(np.asarray(c), counts)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_unguarded_placed_restarts_trip_the_alarm(backend):
    """The §3 M-wait through a placement: proposer 0 holds every cell,
    nodes 1 and 2 restart at tick 2 and proposer 1 attacks at tick 3.
    Ranges 0 and 1 have two replicas on those nodes, a blank majority: with
    the deaf window off (``restart_guard=False``) they grant a second
    owner; ranges 2 and 3 keep a majority that remembers. Guarded, every
    cell holds one owner."""
    t_n, per, n_nodes = 10, 8, 6
    n = 4 * per
    att = np.full((t_n, n), -1, np.int32)
    att[0], att[3] = 0, 1
    rst = np.zeros((t_n, n_nodes), np.int32)
    rst[2, [1, 2]] = 1
    sc = Scenario.build(
        t_n, n_cells=n, n_acceptors=A, n_proposers=P, n_nodes=n_nodes,
        placement=_placement(4, per, n_nodes), attempts=att,
        acc_restart=rst,
    )
    _, counts = _engine(n, backend, restart_guard=False).run_trace(sc)
    assert (counts.max(axis=0) == np.repeat([2, 2, 1, 1], per)).all()
    assert _engine(n, backend).run_trace(sc)[1].max() <= 1


def _build(**over):
    _, planes = _traffic(3)
    arst, prst = _node_rows(3)
    kw = dict(n_cells=N, n_acceptors=A, n_proposers=P, n_nodes=K,
              placement=_placement(), acc_restart=arst, prop_restart=prst,
              **{k: v for k, v in planes.items() if v is not None})
    kw.update(over)
    return Scenario.build(T, **kw)


def _repeated():
    place = _placement()
    place[2, 5] = place[0, 5]
    return place


def _out_of_range():
    place = _placement()
    place[1, 9] = K
    return place


def _too_many():
    arst = np.zeros((T, K), np.int32)
    arst[[3, 9, 20, 30], 2] = 1
    return arst


@pytest.mark.parametrize("over, match", [
    (dict(placement=_repeated()), "same node"),
    (dict(placement=_out_of_range()), "out of range"),
    (dict(placement=-_placement() - 1), "out of range"),
    (dict(acc_up=np.zeros((T, A), np.int32)), "acc_up"),
    (dict(acc_rate=np.full((T, A), 5, np.int32)), "acc_rate"),
    (dict(prop_rate=np.full((T, P), 3, np.int32)), "prop_rate"),
    (dict(acc_restart=_too_many()), "at most"),
    (dict(prop_restart=2 * _node_rows(3)[1]), "above 1"),
    (dict(n_nodes=None), "n_nodes"),
    (dict(acc_restart=np.zeros((T, A), np.int32)), "shape"),
], ids=["repeated", "out-of-range", "negative", "acc_up", "acc_rate",
        "prop_rate", "too-many", "non-binary", "no-n_nodes", "slot-rows"])
def test_what_a_placement_refuses(over, match):
    with pytest.raises(ValueError, match=match):
        _build(**over)


def test_a_placement_needs_as_many_proposers_as_acceptors():
    place = _placement()
    with pytest.raises(ValueError, match="as many proposers"):
        Scenario.build(T, n_cells=N, n_acceptors=A, n_proposers=4,
                       n_nodes=K, placement=place)


def test_run_trace_refuses_a_bad_placement_it_is_handed():
    """run_trace checks a hand-rolled placed Scenario in its placement
    span and leaves the engine as it was."""
    sc = _build()
    sc.planes["placement"] = _repeated()
    e = _engine()
    with pytest.raises(ValueError, match="same node"):
        e.run_trace(sc)
    assert e.t == 0 and e._placement is None


def test_a_placed_engine_keeps_its_placement():
    sc = _build()
    e = _engine()
    e.run_trace(sc[0:48])
    with pytest.raises(ValueError, match="another placement"):
        shifted = Scenario(dict(sc[48:T].planes, placement=_placement(shift=1)))
        e.run_trace(shifted)
    bare = Scenario({k: v for k, v in sc[48:T].planes.items()
                     if k not in ("placement", "acc_restart", "prop_restart")})
    bare.planes.update(acc_restart=np.zeros((T - 48, A), np.int32),
                       prop_restart=np.zeros((T - 48, P), np.int32))
    with pytest.raises(ValueError, match="placed deployment"):
        e.run_trace(bare)
    with pytest.raises(ValueError, match="placed deployment"):
        e.step(make_tick(n_cells=N, n_acceptors=A, n_proposers=P))
    with pytest.raises(ValueError, match="placed deployment"):
        e.sweep([bare])


def test_placed_scenarios_have_no_single_tick():
    sc = _build()
    with pytest.raises(ValueError, match="single-tick"):
        sc[3]
    with pytest.raises(ValueError, match="no placement"):
        make_tick(n_cells=N, n_acceptors=A, n_proposers=P,
                  placement=_placement())
    assert sc[10:20].planes["placement"] is sc.planes["placement"]
    joined = sc[0:40].concat(sc[40:T])
    for k, v in sc.planes.items():
        np.testing.assert_array_equal(joined.planes[k], v)


def test_a_placement_without_restarts_changes_nothing():
    """With all-zero node rows the placement is stripped like them: the
    honest dispatch, and an engine that is not placed."""
    over = dict(acc_restart=np.zeros((T, K), np.int32),
                prop_restart=np.zeros((T, K), np.int32))
    sc = _build(**over)
    assert "placement" not in strip_default_planes(dict(sc.planes))
    bare = {k: v for k, v in sc.planes.items() if k != "placement"}
    bare.update(acc_restart=np.zeros((T, A), np.int32),
                prop_restart=np.zeros((T, P), np.int32))
    e = _engine()
    got = e.run_trace(sc)
    want = _engine().run_trace(Scenario(bare))
    np.testing.assert_array_equal(got[0], want[0])
    assert e._placement is None and not e._restart_active


def test_the_pack_guard_charges_the_busiest_node():
    e = _engine()
    prst = np.zeros((T, K), np.int32)
    prst[[3, 9, 20], 6] = 1
    prst[5, 1] = 1
    assert e._max_restarts(prst, n_nodes=K) == MAX_RESTARTS
    assert e._max_restarts(np.zeros((T, K), np.int32), n_nodes=K) == 0


def test_m_shorter_than_the_lease_is_refused():
    with pytest.raises(ValueError, match="shorter than the lease"):
        _engine(max_lease_ticks=LEASE - 1)


@pytest.mark.parametrize("m", SPANS, ids=["m=lease", "m=long"])
def test_placed_span_counters_count_the_planes(monkeypatch, m):
    """Under a trace, lease.run_trace carries node_restarts,
    restarted_cells and deaf_cell_ticks: numpy counts of the node rows
    through the placement, each acceptor restart deaf for M."""
    sc, _, arst, prst, place = _placed(23)
    stats = {}

    class Span:
        enabled = True

        def __init__(self, name):
            self.name = name

        def __enter__(self):
            stats.setdefault(self.name, [])
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kw):
            stats[self.name].append(kw)

        @staticmethod
        def is_enabled():
            return True

    monkeypatch.setattr(engine_mod, "span", Span)
    _engine(max_lease_ticks=m).run_trace(sc)
    assert "lease.placement" in stats
    got = {}
    for kw in stats["lease.run_trace"]:
        got.update(kw)
    lq4 = 4 * (LEASE if m is None else m) + 1
    cells_on = np.bincount(place.ravel(), minlength=K)
    deaf = np.zeros((T, K), bool)
    for t, j in zip(*np.nonzero(arst)):
        deaf[t:, j] |= 4 * (np.arange(t, T) - t) < lq4
    assert got["node_restarts"] == int(((arst > 0) | (prst > 0)).sum())
    assert got["restarted_cells"] == int(arst.sum(axis=0) @ cells_on)
    assert got["deaf_cell_ticks"] == int(deaf.sum(axis=0) @ cells_on)
    assert got["restarted_cells"] > 0 and got["deaf_cell_ticks"] > 0
