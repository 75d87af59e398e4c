"""engine.sweep: a stacked batch of fault scenarios in ONE dispatch,
per-scenario §4 verification built in, engine state untouched."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.lease_array import LeaseArrayEngine, Scenario, random_trace

GEOM = dict(n_cells=8, n_acceptors=3, n_proposers=4)


def _traces(n, n_ticks=12, delayed=False, seed0=100):
    return [
        random_trace(
            seed0 + s, n_ticks=n_ticks, lease_ticks=2,
            p_attempt=0.5, p_release=0.08, p_down_flip=0.05,
            max_delay_ticks=1 if delayed else 0,
            p_drop=0.1 if delayed else 0.0,
            round_ticks=2, **GEOM,
        )
        for s in range(n)
    ]


def _engine(**kw):
    return LeaseArrayEngine(lease_ticks=2, round_ticks=2, **GEOM, **kw)


@pytest.mark.parametrize("delayed", [False, True])
def test_sweep_matches_solo_replays(delayed):
    """collect="owners": every scenario in the batch equals its solo
    run_trace replay bit-for-bit."""
    traces = _traces(6, delayed=delayed)
    eng = _engine()
    res = eng.sweep(
        [t.scenario() for t in traces], collect="owners",
        netplane=delayed or None,
    )
    assert res.owners.shape == (6, 12, GEOM["n_cells"])
    assert (res.max_owner_count <= 1).all()
    for b, tr in enumerate(traces):
        solo = _engine()
        ow, cn = solo.run_trace(tr.scenario(), netplane=delayed or None)
        assert np.array_equal(res.owners[b], ow)
        assert np.array_equal(res.counts[b], cn)
        assert np.array_equal(res.final_owners[b], ow[-1])
        owned = float((ow >= 0).mean())
        assert res.owned_frac[b] == pytest.approx(owned, abs=1e-6)


def test_sweep_is_read_only():
    """A sweep never advances the engine: state, netplane, and tick are
    exactly what they were before the dispatch."""
    eng = _engine()
    warm = _traces(1, n_ticks=6)[0]
    eng.run_trace(warm.scenario())  # give the engine nontrivial state
    t_before = eng.t
    state_before = [np.asarray(a).copy() for a in eng.state]
    res = eng.sweep([t.scenario() for t in _traces(4, seed0=300)])
    assert eng.t == t_before
    for a, b in zip(eng.state, state_before):
        assert np.array_equal(np.asarray(a), b)
    # the sweep continued from the engine's CURRENT tick, not zero
    assert (res.max_owner_count <= 1).all()


def test_sweep_1024_scenarios_single_dispatch():
    """The acceptance-floor batch: >=1024 scenarios, one dispatch, summary
    reductions only (no [B, T, N] materialization), §4 verified per
    scenario."""
    traces = _traces(1024, n_ticks=8)
    stacked = Scenario.stack([t.scenario() for t in traces])
    eng = _engine()
    res = eng.sweep(stacked)
    assert res.max_owner_count.shape == (1024,)
    assert (res.max_owner_count <= 1).all()
    assert res.final_owners.shape == (1024, GEOM["n_cells"])
    assert res.owners is None and res.counts is None
    assert float(res.owned_frac.mean()) > 0.1, "sweeps actually lease"


@pytest.mark.slow
def test_sweep_10k_scenarios():
    """The 10k-fault-scenario workload from the ISSUE, end to end."""
    traces = _traces(10_000, n_ticks=8)
    stacked = Scenario.stack([t.scenario() for t in traces])
    res = _engine().sweep(stacked)
    assert res.max_owner_count.shape == (10_000,)
    assert (res.max_owner_count <= 1).all()


def test_sweep_rejects_bad_input():
    eng = _engine()
    with pytest.raises(ValueError, match="at least one scenario"):
        eng.sweep([])
    with pytest.raises(ValueError, match="collect"):
        eng.sweep([t.scenario() for t in _traces(2)], collect="everything")


def test_stack_rejects_mismatched_scenarios():
    a = _traces(1)[0].scenario()
    b = _traces(1, n_ticks=9)[0].scenario()
    with pytest.raises(ValueError, match="cannot stack"):
        Scenario.stack([a, b])
    with pytest.raises(ValueError, match="at least one"):
        Scenario.stack([])


@pytest.mark.slow
def test_sweep_shard_map_across_forced_devices(tmp_path):
    """With >1 JAX device the sweep shard_maps the batch axis; forcing two
    host devices in a subprocess must reproduce the single-device owners
    bit-for-bit."""
    out = tmp_path / "sweep_sharded.npy"
    code = f"""
import numpy as np, jax
assert jax.device_count() == 2, jax.devices()
from repro.lease_array import LeaseArrayEngine, Scenario, random_trace
traces = [
    random_trace(100 + s, n_ticks=12, n_cells=8, n_acceptors=3,
                 n_proposers=4, lease_ticks=2, p_attempt=0.5,
                 p_release=0.08, p_down_flip=0.05, round_ticks=2)
    for s in range(4)
]
eng = LeaseArrayEngine(8, n_acceptors=3, n_proposers=4, lease_ticks=2,
                       round_ticks=2)
res = eng.sweep([t.scenario() for t in traces], collect="owners")
np.save({str(out)!r}, res.owners)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (env.get("PYTHONPATH", ""), "src") if p
    )
    subprocess.run(
        [sys.executable, "-c", code], check=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    sharded = np.load(out)
    eng = _engine()
    res = eng.sweep(
        [t.scenario() for t in _traces(4)], collect="owners"
    )
    assert np.array_equal(sharded, res.owners)


def test_uneven_splits_pad_across_forced_devices(tmp_path):
    """A cell count (run_trace) or batch (sweep) that does not divide by
    the device count is padded to a device multiple, not dropped to one
    device: on four forced host devices both still shard, and both equal
    the single-device replays bit-for-bit."""
    out = tmp_path / "uneven.npz"
    code = f"""
import numpy as np, jax
assert jax.device_count() == 4, jax.devices()
from repro.lease_array import LeaseArrayEngine, random_trace
tr = random_trace(7, n_ticks=12, n_cells=10, n_acceptors=3, n_proposers=4,
                  lease_ticks=2, p_attempt=0.5, p_release=0.08,
                  p_down_flip=0.05, max_delay_ticks=1, p_drop=0.1,
                  round_ticks=5)
eng = LeaseArrayEngine(10, n_acceptors=3, n_proposers=4, lease_ticks=2,
                       round_ticks=5)
owners, counts = eng.run_trace(tr.scenario())
spans = {{len(a.sharding.device_set) for a in eng.state}}
traces = [
    random_trace(100 + s, n_ticks=12, n_cells=8, n_acceptors=3,
                 n_proposers=4, lease_ticks=2, p_attempt=0.5,
                 p_release=0.08, p_down_flip=0.05, round_ticks=2)
    for s in range(6)
]
sweeper = LeaseArrayEngine(8, n_acceptors=3, n_proposers=4, lease_ticks=2,
                           round_ticks=2)
res = sweeper.sweep([t.scenario() for t in traces], collect="owners")
np.savez({str(out)!r}, owners=owners, counts=counts, spans=sorted(spans),
         sweep=res.owners)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (env.get("PYTHONPATH", ""), "src") if p
    )
    subprocess.run(
        [sys.executable, "-c", code], check=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    got = np.load(out)
    assert list(got["spans"]) == [4]  # the padded state is split 4 ways
    tr = random_trace(7, n_ticks=12, n_cells=10, n_acceptors=3,
                      n_proposers=4, lease_ticks=2, p_attempt=0.5,
                      p_release=0.08, p_down_flip=0.05, max_delay_ticks=1,
                      p_drop=0.1, round_ticks=5)
    eng = LeaseArrayEngine(10, n_acceptors=3, n_proposers=4, lease_ticks=2,
                           round_ticks=5)
    owners, counts = eng.run_trace(tr.scenario())
    assert np.array_equal(got["owners"], owners)
    assert np.array_equal(got["counts"], counts)
    res = _engine().sweep(
        [t.scenario() for t in _traces(6)], collect="owners"
    )
    assert np.array_equal(got["sweep"], res.owners)
