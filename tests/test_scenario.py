"""The Scenario-plane API: defaulting, validation, slicing, concatenation,
vmap batching, the ghost-proposer regression on run_trace, and the §4
at-most-one-owner property under random asymmetric [T, P, A] link
scenarios (see docs/scenario_api.md; the deprecation shims for the old
one-kwarg-per-fault API live in test_deprecations.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.lease_array import (
    NO_PROPOSER,
    LeaseArrayEngine,
    Scenario,
    TickInputs,
    init_netplane,
    init_state,
    lease_quarters,
    make_tick,
    random_trace,
)
from repro.lease_array.engine import _scenario_scanner
from repro.lease_array.scenario import PLANES, register_plane

A = np.array
NA = NO_PROPOSER
GEOM = dict(n_cells=4, n_acceptors=3, n_proposers=2)


# ------------------------------------------------------------ build/validate
def test_build_defaults_all_planes():
    sc = Scenario.build(5, **GEOM)
    assert sc.n_ticks == 5
    assert set(sc.planes) == set(PLANES)
    assert sc.attempts.shape == (5, 4) and (sc.attempts == NA).all()
    assert sc.releases.shape == (5, 4) and (sc.releases == NA).all()
    assert sc.acc_up.shape == (5, 3) and (sc.acc_up == 1).all()
    assert sc.delay.shape == (5, 2, 3) and not sc.delay.any()
    assert sc.drop.shape == (5, 2, 3) and not sc.drop.any()
    assert not sc.delayed
    assert (sc.n_cells, sc.n_acceptors, sc.n_proposers) == (4, 3, 2)


def test_build_infers_ticks_and_broadcasts_symmetric_links():
    att = np.full((7, 4), NA, np.int32)
    sym = np.arange(3, dtype=np.int32)[None, :].repeat(7, 0)  # [T, A]
    sc = Scenario.build(attempts=att, delay=sym, **GEOM)
    assert sc.n_ticks == 7
    assert sc.delay.shape == (7, 2, 3)
    # the [T, A] form is the P-broadcast special case
    assert (sc.delay == sym[:, None, :]).all()
    assert sc.delayed


def test_build_rejects_bad_shapes_unknown_planes_and_negative_delay():
    with pytest.raises(ValueError, match="plane 'acc_up' has shape"):
        Scenario.build(3, acc_up=np.ones((3, 5), np.int32), **GEOM)
    with pytest.raises(ValueError, match="unknown scenario plane.*typo"):
        Scenario.build(3, typo=np.zeros((3, 4)), **GEOM)
    with pytest.raises(ValueError, match="negative"):
        Scenario.build(3, delay=np.full((3, 3), -1, np.int32), **GEOM)
    with pytest.raises(ValueError, match="n_ticks is required"):
        Scenario.build(**GEOM)


def test_bool_planes_coerce_to_int32():
    sc = Scenario.build(2, drop=np.ones((2, 3), bool), **GEOM)
    assert sc.drop.dtype == np.int32 and sc.drop.all()
    tick = make_tick(drop=np.ones(3, bool), **GEOM)
    assert tick.drop.dtype == np.int32 and tick.drop.shape == (2, 3)


# -------------------------------------------------- ghost-id regression (bugfix)
def test_run_trace_rejects_ghost_proposer_ids():
    """Regression: run_trace used to skip the proposer-id validation that
    step does — out-of-range ids silently leased cells to ghost proposers.
    Both paths now refuse through scenario.check_bounds."""
    e = LeaseArrayEngine(4, n_acceptors=3, n_proposers=2)
    bad = Scenario.build(3, **GEOM)
    bad.planes["attempts"][1, 2] = 2  # == n_proposers: a ghost
    with pytest.raises(ValueError, match=r"proposer id 2 out of range.*2 proposers"):
        e.run_trace(bad)
    rel = Scenario.build(3, **GEOM)
    rel.planes["releases"][0, 1] = -7
    with pytest.raises(ValueError, match="out of range"):
        e.run_trace(rel)
    assert e.t == 0  # nothing advanced


def test_run_trace_validates_prebuilt_scenario_pytrees():
    e = LeaseArrayEngine(4, n_acceptors=3, n_proposers=2)
    sc = Scenario.build(3, **GEOM)
    sc.planes["attempts"][0, 0] = 5  # hand-mutated pytree skips build checks
    with pytest.raises(ValueError, match="proposer id 5 out of range"):
        e.run_trace(sc)
    wrong = Scenario.build(3, n_cells=8, n_acceptors=3, n_proposers=2)
    with pytest.raises(ValueError, match="engine geometry wants"):
        e.run_trace(wrong)
    neg = Scenario.build(3, **GEOM)
    neg.planes["delay"][1] = -2  # negative deliver-at: legs land in the past
    with pytest.raises(ValueError, match="negative"):
        e.run_trace(neg)


def test_step_validates_tick_geometry_against_engine():
    """A TickInputs built for the wrong geometry must not reach the step:
    e.g. a [1] acc_up column would silently broadcast one acceptor's
    reachability over the whole ensemble."""
    e = LeaseArrayEngine(4, n_acceptors=5, n_proposers=2)
    tick = make_tick(n_cells=4, n_acceptors=1, n_proposers=2)
    with pytest.raises(ValueError, match="acc_up.*engine geometry wants"):
        e.step(tick)
    with pytest.raises(ValueError, match="engine geometry wants"):
        e.step(make_tick(n_cells=8, n_acceptors=5, n_proposers=2))
    assert e.t == 0


# ------------------------------------------------------- slicing/concat/stack
def test_tick_slice_and_subscenario():
    att = np.full((4, 4), NA, np.int32)
    att[2, 1] = 1
    sc = Scenario.build(attempts=att, **GEOM)
    tick = sc[2]
    assert isinstance(tick, TickInputs)
    assert tick.attempts.tolist() == [NA, 1, NA, NA]
    assert tick.delay.shape == (2, 3)
    sub = sc[1:3]
    assert isinstance(sub, Scenario) and sub.n_ticks == 2
    assert sub.attempts[1, 1] == 1


def test_concat_joins_ticks_and_checks_geometry():
    a = Scenario.build(2, **GEOM)
    b = Scenario.build(3, **GEOM)
    assert a.concat(b).n_ticks == 5
    other = Scenario.build(2, n_cells=8, n_acceptors=3, n_proposers=2)
    with pytest.raises(ValueError, match="cannot concat"):
        a.concat(other)


# ------------------------------------------------------------- vmap batching
def test_vmap_stacked_scenarios():
    """A stacked batch of scenarios runs through ONE vmapped scanner and
    agrees bit-for-bit with running each scenario alone."""
    n_cells, n_acc, n_prop, lease = 6, 3, 3, 2
    traces = [
        random_trace(s, n_ticks=30, n_cells=n_cells, n_acceptors=n_acc,
                     n_proposers=n_prop, lease_ticks=lease, p_release=0.1,
                     max_delay_ticks=1, p_drop=0.1, asymmetric=True,
                     round_ticks=2)
        for s in (11, 12, 13)
    ]
    stacked = Scenario.stack([t.scenario() for t in traces])
    planes = {k: jnp.asarray(v) for k, v in stacked.planes.items()}
    scanner = _scenario_scanner(
        n_acc // 2 + 1, lease_quarters(lease), 8, "jnp", False
    )
    state = init_state(n_cells, n_acc, n_prop)
    net = init_netplane(n_cells, n_acc)
    _, _, owners, counts = jax.vmap(
        scanner, in_axes=(None, None, None, None, 0)
    )(state, net, jnp.int32(0), None, planes)
    assert owners.shape == (3, 30, n_cells)
    assert int(counts.max()) <= 1
    for b, tr in enumerate(traces):
        eng = LeaseArrayEngine(
            n_cells, n_acceptors=n_acc, n_proposers=n_prop,
            lease_ticks=lease, round_ticks=tr.round_ticks,
        )
        solo_owners, solo_counts = eng.run_trace(tr.scenario(), netplane=True)
        assert np.array_equal(np.asarray(owners)[b], solo_owners)
        assert np.array_equal(np.asarray(counts)[b], solo_counts)


# ------------------------------------- §4 invariant under asymmetric chaos
def _invariant_holds(seed: int, n_ticks: int = 60) -> None:
    """Unconstrained random asymmetric link scenario (no slot-isolation
    spacing: overwritten slots only LOSE messages, and PaxosLease is safe
    under arbitrary loss) — at most one believed owner per cell per tick."""
    rng = np.random.default_rng(seed)
    n_cells, n_acc, n_prop = 5, int(rng.integers(1, 6)), int(rng.integers(2, 5))
    sc = Scenario.build(
        n_ticks, n_cells=n_cells, n_acceptors=n_acc, n_proposers=n_prop,
        attempts=np.where(rng.random((n_ticks, n_cells)) < 0.7,
                          rng.integers(0, n_prop, (n_ticks, n_cells)), NA),
        releases=np.where(rng.random((n_ticks, n_cells)) < 0.15,
                          rng.integers(0, n_prop, (n_ticks, n_cells)), NA),
        acc_up=rng.random((n_ticks, n_acc)) > 0.1,
        delay=rng.integers(0, 4, (n_ticks, n_prop, n_acc)),
        drop=rng.random((n_ticks, n_prop, n_acc)) < 0.15,
    )
    eng = LeaseArrayEngine(
        n_cells, n_acceptors=n_acc, n_proposers=n_prop,
        lease_ticks=int(rng.integers(1, 7)), round_ticks=int(rng.integers(1, 5)),
    )
    _, counts = eng.run_trace(sc, netplane=True)
    assert counts.shape == (n_ticks, n_cells)
    assert int(counts.max()) <= 1, f"§4 violated under scenario seed {seed}"


@pytest.mark.parametrize("seed", range(8))
def test_at_most_one_owner_under_asymmetric_chaos(seed):
    _invariant_holds(seed)


def test_at_most_one_owner_hypothesis_property():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import strategies as st

    @hyp.settings(max_examples=25, deadline=None)
    @hyp.given(st.integers(min_value=0, max_value=2**32 - 1))
    def prop(seed):
        _invariant_holds(seed, n_ticks=40)

    prop()


# ------------------------------------------------- model-selection regressions
def test_run_trace_netplane_false_rejects_delayed_scenario():
    """Regression: netplane=False used to silently run a faulty scenario
    through the sync step, discarding its delay/drop planes."""
    e = LeaseArrayEngine(2, n_acceptors=3, n_proposers=2)
    sc = Scenario.build(
        4, n_cells=2, n_acceptors=3, n_proposers=2,
        attempts=np.where(np.eye(4, 2, dtype=bool), 0, NA),
        drop=np.ones((4, 3), np.int32),
    )
    with pytest.raises(ValueError, match="netplane=False"):
        e.run_trace(sc, netplane=False)
    assert e.t == 0
    owners, _ = e.run_trace(sc)  # auto-select honors the drop plane
    assert (owners == NA).all()


def test_failed_step_does_not_corrupt_network_model():
    """Regression: a step that fails validation must not flip the engine
    onto the delayed model."""
    e = LeaseArrayEngine(4, n_acceptors=3, n_proposers=2)
    # wrong acceptor count, nonzero delay: validate_for must fire before
    # the tick's delay plane can flip the engine onto the netplane
    bad = make_tick(n_cells=4, n_acceptors=7, n_proposers=2,
                    delay=np.ones(7, np.int32))
    with pytest.raises(ValueError, match="engine geometry wants"):
        e.step(bad)
    sc = Scenario.build(2, **GEOM)
    e.run_trace(sc, netplane=False)  # still a pure-sync engine
    assert e.t == 2


def test_scenario_and_tick_pickle_roundtrip():
    import pickle

    sc = Scenario.build(3, **GEOM)
    back = pickle.loads(pickle.dumps(sc))
    assert isinstance(back, Scenario) and back.n_ticks == 3
    assert all(np.array_equal(back.planes[k], sc.planes[k]) for k in PLANES)
    tick = pickle.loads(pickle.dumps(sc[1]))
    assert isinstance(tick, TickInputs) and tick.attempts.shape == (4,)


# ------------------------------------------------------------- registry
def test_register_plane_rides_through_build_and_slicing():
    spec = register_plane("tmp_test_plane", ("A",), 7, "test-only plane")
    try:
        assert PLANES["tmp_test_plane"] is spec
        sc = Scenario.build(3, **GEOM)
        assert sc.tmp_test_plane.shape == (3, 3)
        assert (sc.tmp_test_plane == 7).all()  # registered default
        assert sc[1].tmp_test_plane.shape == (3,)
        got = Scenario.build(
            3, tmp_test_plane=np.zeros((3, 3), np.int32), **GEOM
        )
        assert not got.tmp_test_plane.any()
    finally:
        del PLANES["tmp_test_plane"]


def test_unknown_plane_message_names_registry():
    with pytest.raises(ValueError, match="register_plane"):
        make_tick(bogus=np.zeros(3), **GEOM)
