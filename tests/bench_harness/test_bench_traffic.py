"""The benchmark's traffic: the start-up election and renewals of a
master-lease fleet, and the directory's failover probe."""
import numpy as np
import pytest

from bench import reference
from bench.traffic.generate import startup_trace, stall_schedule

KEYSPACE = dict(n_acceptors=3, n_proposers=3, lease_ticks=28, renew=0.4,
                max_delay_ticks=1, p_drop=0.01)
WIDE = dict(n_acceptors=5, n_proposers=8, lease_ticks=24, renew=0.5,
            max_delay_ticks=2, p_drop=0.05)
CASES = [(0, KEYSPACE), (7, {**KEYSPACE, "p_drop": 0.0}),
         (2**31 + 99, KEYSPACE), (2**40 + 5, WIDE)]


def _trace(seed, geometry, n_ticks=120, n_cells=300):
    return startup_trace(seed, n_ticks=n_ticks, n_cells=n_cells, **geometry)


@pytest.mark.parametrize("seed, geometry", CASES)
def test_same_seed_same_planes(seed, geometry):
    a, b = _trace(seed, geometry), _trace(seed, geometry)
    for key in a:
        if a[key] is None:
            assert b[key] is None
        else:
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    c = _trace(seed + 1, geometry)
    assert not np.array_equal(a["attempts"], c["attempts"])


@pytest.mark.parametrize("seed, geometry", CASES)
def test_every_replica_tries_once_a_round_apart(seed, geometry):
    P = geometry["n_proposers"]
    gap = 4 * geometry["max_delay_ticks"] + 1
    att = _trace(seed, geometry)["attempts"]
    for cell in range(att.shape[1]):
        ticks = np.flatnonzero(att[:, cell] >= 0)
        assert sorted(att[ticks, cell]) == list(range(P))
        assert ticks[0] < gap
        np.testing.assert_array_equal(np.diff(ticks), gap)


@pytest.mark.parametrize("seed, geometry", CASES)
def test_every_replica_renews_after_the_start_up_rounds(seed, geometry):
    P = geometry["n_proposers"]
    gap = 4 * geometry["max_delay_ticks"] + 1
    interval = max(gap, round(geometry["lease_ticks"] * geometry["renew"]))
    planes = _trace(seed, geometry)
    att, ext = planes["attempts"], planes["extends"]
    assert not np.any((att >= 0) & (ext >= 0))
    for cell in range(0, att.shape[1], 7):
        first = np.flatnonzero(att[:, cell] >= 0)
        for k in range(P):
            who = att[first[k], cell]
            ticks = np.flatnonzero(ext[:, cell] == who)
            assert ticks[0] == first[0] + P * gap + k
            np.testing.assert_array_equal(np.diff(ticks), interval)
    assert planes["releases"] is None
    assert planes["acc_up"].all()


@pytest.mark.parametrize("seed, geometry", CASES[:3])
def test_the_fleet_stays_owned_once_elected(seed, geometry):
    planes = _trace(seed, geometry, n_ticks=160, n_cells=256)
    owners, counts = reference.replay(
        planes, n_proposers=geometry["n_proposers"],
        lease_ticks=geometry["lease_ticks"], round_ticks=5,
    )
    assert counts.max() == 1
    assert (owners[20:] >= 0).mean() > 0.99
    # the owner elected at start-up keeps the lease: its renewals land
    assert np.mean(owners[20:] == owners[20]) > 0.99


def test_stall_schedule_is_one_probe_of_a_seeded_worker():
    seen = set()
    for seed in (0, 1, 2, 2**40 + 3):
        sched = stall_schedule(seed, n_workers=32, stall_tick=64)
        assert sched == stall_schedule(seed, n_workers=32, stall_tick=64)
        [(tick, worker)] = sched
        assert tick == 64 and 0 <= worker < 32
        seen.add(worker)
    assert len(seen) > 1
