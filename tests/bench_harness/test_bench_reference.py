"""The benchmark's plain reference (``bench/reference.py``) against the
program, and its control.

The reference shares no code with the program. On small seeded fleets it
must give the owners and owner counts of ``run_trace`` bit for bit, and
the summaries of ``sweep``. Its control, acceptors that report a live
lease as open (a broken §3.3), must fail the same comparison and the
guarantee of at most one owner per cell-tick.
"""
import numpy as np
import pytest

from bench import reference

PLANES = ("attempts", "releases", "extends", "acc_up", "delay", "drop")
GEOMETRIES = {
    "keyspace_master": dict(
        n_acceptors=3, n_proposers=3, lease_ticks=28, round_ticks=5,
        max_delay_ticks=1, p_drop=0.01, renew=0.4, p_attempt=0.1,
        p_release=0.02, p_down_flip=0.02),
    "chubby_directory": dict(
        n_acceptors=5, n_proposers=32, lease_ticks=24, round_ticks=9,
        max_delay_ticks=2, p_drop=0.05, renew=0.5, p_attempt=0.2,
        p_release=0.02, p_down_flip=0.02),
}


def _fleet(seed, geometry, n_cells=384, n_ticks=150):
    """Chaos traffic from the program's own generator: contenders during
    live leases, releases and acceptor outages reach every branch of the
    reference."""
    from repro.lease_array.trace import random_trace

    g = dict(geometry)
    round_ticks = g.pop("round_ticks")
    tr = random_trace(seed, n_ticks=n_ticks, n_cells=n_cells,
                      round_ticks=round_ticks, **g)
    planes = tr.scenario().planes
    return {k: planes[k] for k in PLANES}, round_ticks


def _program(planes, geometry, round_ticks):
    from repro.lease_array import LeaseArrayEngine, Scenario

    T, N = planes["attempts"].shape
    sc = Scenario.build(
        n_cells=N, n_acceptors=geometry["n_acceptors"],
        n_proposers=geometry["n_proposers"],
        **{k: v for k, v in planes.items() if v is not None},
    )
    eng = LeaseArrayEngine(
        N, n_acceptors=geometry["n_acceptors"],
        n_proposers=geometry["n_proposers"],
        lease_ticks=geometry["lease_ticks"], round_ticks=round_ticks,
        backend="jnp",
    )
    return eng.run_trace(sc)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
@pytest.mark.parametrize("config", sorted(GEOMETRIES))
def test_reference_equals_run_trace(config, seed):
    geometry = GEOMETRIES[config]
    planes, round_ticks = _fleet(seed, geometry)
    owners, counts = _program(planes, geometry, round_ticks)
    ref_owners, ref_counts = reference.replay(
        planes, n_proposers=geometry["n_proposers"],
        lease_ticks=geometry["lease_ticks"], round_ticks=round_ticks,
        block=100,
    )
    np.testing.assert_array_equal(owners, ref_owners)
    np.testing.assert_array_equal(counts, ref_counts)
    assert (owners >= 0).mean() > 0.05  # the fleet does get owned
    assert ref_counts.max() == 1


@pytest.mark.parametrize("seed", [4, 5, 6])
@pytest.mark.parametrize("config", sorted(GEOMETRIES))
def test_control_fails_the_comparison(config, seed):
    geometry = GEOMETRIES[config]
    planes, round_ticks = _fleet(seed, geometry)
    owners, counts = _program(planes, geometry, round_ticks)
    ctl_owners, ctl_counts = reference.replay(
        planes, n_proposers=geometry["n_proposers"],
        lease_ticks=geometry["lease_ticks"], round_ticks=round_ticks,
        control="lie_open",
    )
    assert np.count_nonzero(ctl_owners != owners) > 100
    assert ctl_counts.max() > 1


def test_batch_summaries_equal_one_by_one():
    geometry = GEOMETRIES["keyspace_master"]
    fleets = [_fleet(s, geometry, n_cells=128, n_ticks=40)[0] for s in range(5)]
    kw = dict(n_proposers=3, lease_ticks=28, round_ticks=5)
    batch = reference.replay_batch(fleets, block=2, **kw)
    for i, planes in enumerate(fleets):
        owners, counts = reference.replay(planes, **kw)
        assert batch["max_owner_count"][i] == counts.max()
        assert batch["owned_cell_ticks"][i] == np.count_nonzero(owners >= 0)
        np.testing.assert_array_equal(batch["final_owners"][i], owners[-1])


@pytest.mark.parametrize("seed", [8, 9])
def test_reference_equals_run_trace_on_asymmetric_links(seed):
    """Per-(proposer, acceptor) links take the reference's general leg
    path (the cells' symmetric links take the short one)."""
    from repro.lease_array.trace import random_trace

    tr = random_trace(
        seed, n_ticks=100, n_cells=256, n_acceptors=5, n_proposers=8,
        lease_ticks=6, round_ticks=9, max_delay_ticks=2, p_drop=0.1,
        renew=0.5, p_attempt=0.3, p_release=0.05, p_down_flip=0.05,
        asymmetric=True,
    )
    geometry = dict(n_acceptors=5, n_proposers=8, lease_ticks=6)
    planes = {k: v for k, v in tr.scenario().planes.items()}
    owners, counts = _program(
        {k: planes[k] for k in ("attempts", "releases", "extends", "acc_up",
                                "delay", "drop")},
        geometry, tr.round_ticks,
    )
    ref_owners, ref_counts = reference.replay(
        planes, n_proposers=8, lease_ticks=6, round_ticks=tr.round_ticks,
    )
    np.testing.assert_array_equal(owners, ref_owners)
    np.testing.assert_array_equal(counts, ref_counts)


@pytest.mark.parametrize("n_shards, n_workers, stalls", [
    (1024, 32, [(64, 5)]),
    (600, 8, [(30, 0), (70, 7)]),
    (500, 4, []),
])
def test_directory_policy_equals_the_directory(n_shards, n_workers, stalls):
    """The reference's restatement of the directory's policy issues, over
    the reference's owners, the planes ``LeaseArrayDirectory`` issues."""
    from repro.lease_array.directory import LeaseArrayDirectory

    d = LeaseArrayDirectory(
        n_shards, n_acceptors=5, lease_ticks=24, max_workers=n_workers,
        max_delay_ticks=2, backend="jnp",
    )
    target = -(-n_shards // (n_workers - 1))
    for w in range(n_workers):
        d.add_worker(w, target)
    ticks, step = [], d.engine.step
    d.engine.step = lambda tick: (ticks.append(tick), step(tick))[1]
    at = dict(stalls)
    for t in range(110):
        if t in at:
            d.stall(at[t])
        d.tick()
    planes = {k: np.stack([tk.planes[k] for tk in ticks]) for k in PLANES}
    owners, _, ends = reference.replay(
        planes, n_proposers=n_workers, lease_ticks=24, round_ticks=9,
        lease_ends=True,
    )
    want = reference.directory_planes(
        owners, ends, n_workers=n_workers, target=target, stalls=stalls,
        lease_ticks=24, max_delay_ticks=2,
    )
    for k, plane in want.items():
        np.testing.assert_array_equal(planes[k], plane, err_msg=k)
    assert (planes["extends"] >= 0).sum() > n_shards  # renewals were issued
