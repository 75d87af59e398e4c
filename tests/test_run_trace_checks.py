"""``run_trace`` checks the bounds of its [T, N] proposer-id planes on the
device, after the upload, and refuses exactly what the host check
(``Scenario.validate_for``) refuses, with the same message, before the
kernel is dispatched and before the engine changes. An all-default
extends plane is still stripped on the host and never uploaded."""
import jax
import numpy as np
import pytest

from repro.lease_array import LeaseArrayEngine, Scenario
from repro.lease_array import engine as engine_mod
from repro.lease_array.state import NO_PROPOSER
from repro.lease_array.trace import random_trace

N_CELLS, N_TICKS, N_PROPOSERS = 8, 6, 3
GEOM = dict(n_cells=N_CELLS, n_acceptors=3, n_proposers=N_PROPOSERS)


def _scenario(extend: bool = True, **planes) -> Scenario:
    """Every cell attempted at tick 0; with ``extend``, its owner extends
    it at tick 3."""
    attempts = np.full((N_TICKS, N_CELLS), NO_PROPOSER, np.int32)
    attempts[0] = np.arange(N_CELLS) % N_PROPOSERS
    extends = np.full((N_TICKS, N_CELLS), NO_PROPOSER, np.int32)
    if extend:
        extends[3] = attempts[0]
    return Scenario.build(attempts=attempts, extends=extends, **planes,
                          **GEOM)


def _corrupt(plane: str, value: int, dtype=np.int32) -> Scenario:
    """A built scenario with one entry of ``plane`` mutated in place, at
    the last tick and cell, so no build-time check saw it."""
    sc = _scenario()
    sc.planes[plane] = sc.planes[plane].astype(dtype)
    sc.planes[plane][-1, -1] = value
    return sc


CASES = {
    "valid": lambda: _scenario(),
    **{f"ghost_{k}": (lambda k=k: _corrupt(k, N_PROPOSERS))
       for k in ("attempts", "releases", "extends")},
    **{f"below_sentinel_{k}": (lambda k=k: _corrupt(k, NO_PROPOSER - 1))
       for k in ("attempts", "releases", "extends")},
    # a wider plane than the upload keeps: 2**32 + 1 would read as id 1
    # on the device, so it is checked on the host
    "ghost_int64_attempts": lambda: _corrupt("attempts", 2**32 + 1, np.int64),
}


def _host_error(sc: Scenario):
    try:
        sc.validate_for(**GEOM)
    except ValueError as e:
        return str(e)
    return None


@pytest.mark.parametrize("case", sorted(CASES))
def test_run_trace_refuses_what_validate_for_refuses(case, monkeypatch):
    sc = CASES[case]()
    want = _host_error(sc)
    assert (want is None) == (case == "valid")
    dispatched = []
    trace_fn = engine_mod._trace_fn
    monkeypatch.setattr(engine_mod, "_trace_fn",
                        lambda *a: dispatched.append(a) or trace_fn(*a))
    eng = LeaseArrayEngine(N_CELLS, n_acceptors=3, n_proposers=N_PROPOSERS,
                           lease_ticks=4, backend="jnp")
    if want is None:
        owners, _ = eng.run_trace(sc)
        assert eng.t == N_TICKS and (owners[1] >= 0).all()
        assert len(dispatched) == 1
    else:
        with pytest.raises(ValueError) as err:
            eng.run_trace(sc)
        assert str(err.value) == want
        assert dispatched == []  # refused before the kernel was dispatched


def _engine_view(eng: LeaseArrayEngine) -> dict:
    return {
        "t": eng.t,
        "state": [np.asarray(x) for x in jax.tree.leaves(eng.state)],
        "net": [np.asarray(x) for x in jax.tree.leaves(eng.net)],
        "last_owner_count": np.asarray(eng.last_owner_count),
        "prop_clk": eng.prop_clk.copy(),
        "acc_clk": eng.acc_clk.copy(),
        "rc": eng._rc.copy(),
        "deaf_until": eng._deaf_until.copy(),
        "restart_active": eng._restart_active,
        "netplane_active": eng._netplane_active,
    }


def _assert_same(a: dict, b: dict) -> None:
    assert a.keys() == b.keys()
    for k in a:
        if isinstance(a[k], list):
            assert len(a[k]) == len(b[k])
            assert all(np.array_equal(x, y) for x, y in zip(a[k], b[k])), k
        else:
            assert np.array_equal(a[k], b[k]), k


@pytest.mark.parametrize("plane", ["attempts", "releases", "extends"])
def test_a_refused_trace_leaves_the_engine_unchanged(plane):
    """The refused scenario is delayed, drifted and restarts a proposer,
    so it would move the network model, the clocks and the restart mode
    if any of them changed before its device check."""
    eng = LeaseArrayEngine(N_CELLS, n_acceptors=3, n_proposers=N_PROPOSERS,
                           lease_ticks=4, backend="jnp")
    # a replayed state, not a fresh one, still on the synchronous model
    eng.run_trace(_scenario(extend=False))
    before = _engine_view(eng)
    assert not before["netplane_active"] and not before["restart_active"]
    prop_restart = np.zeros((N_TICKS, N_PROPOSERS), np.int32)
    prop_restart[2, 1] = 1
    sc = _scenario(delay=np.ones((N_TICKS, 3), np.int32),
                   prop_rate=np.full((N_TICKS, N_PROPOSERS), 5, np.int32),
                   prop_restart=prop_restart)
    sc.planes[plane][-1, 0] = N_PROPOSERS
    with pytest.raises(ValueError, match="proposer id 3 out of range"):
        eng.run_trace(sc)
    _assert_same(_engine_view(eng), before)
    sc.planes[plane][-1, 0] = NO_PROPOSER  # repaired, it replays
    eng.run_trace(sc)
    assert eng.t == 2 * N_TICKS
    assert eng._netplane_active and eng._restart_active


def test_all_default_extends_is_stripped_and_bit_identical(monkeypatch):
    tr = random_trace(11, n_ticks=40, n_cells=16, n_acceptors=3,
                      n_proposers=3, lease_ticks=4, max_delay_ticks=1,
                      p_drop=0.05)
    sc = tr.scenario()
    assert (sc.planes["extends"] == NO_PROPOSER).all()
    uploaded, checked = [], []
    trace_fn, bounds_fn = engine_mod._trace_fn, engine_mod._bounds_fn
    monkeypatch.setattr(engine_mod, "_trace_fn",
                        lambda *a: uploaded.append(a[9]) or trace_fn(*a))
    monkeypatch.setattr(engine_mod, "_bounds_fn",
                        lambda keys: checked.append(keys) or bounds_fn(keys))
    results = {}
    for backend in ("jnp", "pallas"):
        eng = LeaseArrayEngine(16, n_acceptors=3, n_proposers=3,
                               lease_ticks=4, backend=backend)
        results[backend] = eng.run_trace(sc)
    assert len(uploaded) == 2
    for keys in uploaded:
        assert "extends" not in keys and "attempts" in keys
    assert checked == [("attempts", "releases")] * 2
    for a, b in zip(results["jnp"], results["pallas"]):
        assert np.array_equal(a, b)
    assert (results["jnp"][0] >= 0).any()
