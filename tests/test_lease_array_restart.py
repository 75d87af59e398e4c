"""Array-plane crash/restart injection (the ISSUE 9 tentpole contracts).

The two registry planes — ``acc_restart [T, A]`` (diskless acceptor
restart: blank + deaf for M local quarter-ticks, §3) and ``prop_restart
[T, P]`` (proposer restart-counter bump carved into the packed ballot,
§2) — must: reproduce the pre-restart engine bit-for-bit when all-default
(stripped host-side, never uploaded); replay bit-exactly against the
event-sim referee under crash + drift + delay + drop on BOTH backends;
trip the §4 owner-count-2 alarm when the deaf window is disabled (the
M-wait negative control) while a guarded ≥1024-scenario sweep stays
violation-free in a single dispatch; and refuse schedules the packed
restart-counter carve cannot represent."""
import numpy as np
import pytest

from repro.lease_array import LeaseArrayEngine, Scenario
from repro.lease_array.scenario import RESTART_PLANES
from repro.lease_array.state import (
    MAX_RESTARTS,
    check_pack_budget,
    max_pack_tick,
)
from repro.lease_array.trace import (
    Trace,
    random_trace,
    replay_array,
    replay_event_sim,
    trace_from_scenario,
)

BACKENDS = ["jnp", "pallas"]

#: the chaos-family fault mix every differential below draws from
CHAOS = dict(
    n_ticks=80, n_cells=4, n_acceptors=3, n_proposers=4, lease_ticks=3,
    max_delay_ticks=2, p_drop=0.05, restarts=0.02,
)


def _engine(trace: Trace, backend="jnp", **kw) -> LeaseArrayEngine:
    return LeaseArrayEngine(
        trace.n_cells, n_acceptors=trace.n_acceptors,
        n_proposers=trace.n_proposers, lease_ticks=trace.lease_ticks,
        round_ticks=trace.round_ticks, drift_eps=trace.drift_eps,
        backend=backend, **kw,
    )


# ------------------------------------------------------- all-default planes

def test_all_default_restart_planes_bit_identical():
    """A scenario whose registry-filled restart planes are all zero is the
    pre-restart engine: same bits, and the engine never enters restart
    mode (the planes are stripped host-side, not uploaded — no restart
    ballot carve, no deaf/counter streams in the dispatch)."""
    tr = random_trace(3, max_delay_ticks=1, p_drop=0.05, drift_eps=0.25,
                      **{k: v for k, v in CHAOS.items()
                         if k not in ("max_delay_ticks", "p_drop", "restarts")})
    base_ow, base_cn = replay_array(tr)
    sc = tr.scenario()
    assert all(k in sc.planes for k in RESTART_PLANES)  # registry-filled
    assert not sc.restarted
    eng = _engine(tr)
    ow, cn = eng.run_trace(sc)
    assert np.array_equal(np.asarray(ow), np.asarray(base_ow))
    assert np.array_equal(np.asarray(cn), np.asarray(base_cn))
    assert eng._restart_active is False  # zero uploads: mode never latched

    stacked = Scenario(
        {k: np.asarray(v)[None] for k, v in sc.planes.items()}
    )
    res = eng.sweep(stacked, collect="owners")
    assert eng._restart_active is False
    assert np.array_equal(np.asarray(res.owners[0]), np.asarray(base_ow))


# ------------------------------------- differential replay vs the referee

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_crash_restart_differential_vs_referee(seed):
    """Randomized crash + drift + delay + drop traces: the event-driven
    referee and the array plane agree bit-for-bit on every believed-owner
    bit, and §4 holds throughout."""
    tr = random_trace(
        seed, drift_eps=0.25 if seed % 2 else 0.0,
        asymmetric=bool(seed % 2), **CHAOS,
    )
    assert tr.restarted  # the fault family is actually exercised
    ref = replay_event_sim(tr)
    ow, cn = replay_array(tr)
    assert np.array_equal(ref, np.asarray(ow))
    assert int(np.max(np.asarray(cn))) <= 1


@pytest.mark.parametrize("seed", [0, 3])  # 3: drifted, asymmetric links
def test_a_longer_m_matches_the_referee_by_trace_and_by_step(seed):
    """M longer than the lease (the engine's ``max_lease_ticks``): a
    restarted acceptor stays deaf for M on its own clock in the fused
    replay, tick by tick through ``step``, and in the referee alike; and
    M is honored, for the replay differs from one at the default span."""
    tr = random_trace(
        seed, drift_eps=0.25 if seed % 2 else 0.0,
        asymmetric=bool(seed % 2), **CHAOS,
    )
    m = tr.lease_ticks + 8
    ref = replay_event_sim(tr, max_lease_ticks=m)
    sc = tr.scenario()
    ow, cn = _engine(tr, max_lease_ticks=m).run_trace(sc)
    assert np.array_equal(ref, np.asarray(ow))
    assert int(np.max(np.asarray(cn))) <= 1
    stepped = _engine(tr, max_lease_ticks=m)
    rows = [stepped.step(sc[t]) for t in range(tr.n_ticks)]
    assert np.array_equal(np.stack(rows), np.asarray(ow))
    assert not np.array_equal(np.asarray(replay_array(tr)[0]), ref)


@pytest.mark.parametrize("backend", BACKENDS)
def test_restart_trace_backends_bit_identical(backend):
    """The restart-mode dispatch (blanking, deaf gating, counter-carved
    ballots) is backend-independent: jnp scan and the fused Pallas window
    kernel produce identical owners/counts."""
    tr = random_trace(5, drift_eps=0.25, asymmetric=True, **CHAOS)
    ref_ow, ref_cn = replay_array(tr, backend="jnp")
    ow, cn = replay_array(tr, backend=backend)
    assert np.array_equal(np.asarray(ow), np.asarray(ref_ow))
    assert np.array_equal(np.asarray(cn), np.asarray(ref_cn))


@pytest.mark.slow
def test_1000_tick_crash_drift_delay_drop_differential():
    """ISSUE 9 acceptance: a 1000-tick randomized trace combining
    restarts, drifting clocks, link delays and drops replays bit-exactly
    against the event-sim referee on both backends."""
    tr = random_trace(
        42, n_ticks=1000, max_delay_ticks=2, p_drop=0.05,
        drift_eps=0.25, asymmetric=True, restarts=0.02,
    )
    assert tr.restarted
    ref = replay_event_sim(tr)
    for backend in BACKENDS:
        ow, cn = replay_array(tr, backend=backend)
        assert np.array_equal(ref, np.asarray(ow)), backend
        assert int(np.max(np.asarray(cn))) <= 1, backend


# -------------------------------------------- the §4 deaf-window controls

def _m_wait_trace() -> Trace:
    """Proposer 0 acquires everywhere; every acceptor crash-restarts
    mid-lease at tick 2 (blank majority); proposer 1 attacks at tick 3
    while p0's guarded belief is still live — the §3 M-wait showdown."""
    T, N, A, P = 10, 4, 5, 4
    att = np.full((T, N), -1, np.int32)
    att[0, :] = 0
    att[3, :] = 1
    rst = np.zeros((T, A), np.int32)
    rst[2, :] = 1
    return Trace(
        N, A, P, 4, att, np.full((T, N), -1, np.int32),
        np.ones((T, A), bool), acc_restarts=rst,
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_unguarded_restart_trips_owner_alarm(backend):
    """The negative control: with the deaf window disabled
    (``restart_guard=False``) the blank-restarted majority grants the
    rival a second live lease — owner count 2, the exact violation
    ``tests/test_restart_m.py`` demonstrates on the event engine."""
    ow, cn = replay_array(_m_wait_trace(), backend=backend,
                          restart_guard=False)
    assert int(np.max(np.asarray(cn))) == 2


@pytest.mark.parametrize("backend", BACKENDS)
def test_guarded_restart_holds_and_matches_referee(backend):
    """The guarded twin: same schedule, deaf window on — §4 holds and the
    event-sim referee agrees on every owner bit."""
    tr = _m_wait_trace()
    ow, cn = replay_array(tr, backend=backend)
    assert int(np.max(np.asarray(cn))) <= 1
    assert np.array_equal(replay_event_sim(tr), np.asarray(ow))


def test_guarded_sweep_1024_restart_scenarios_single_dispatch():
    """ISSUE 9 acceptance: >= 1024 random restart scenarios sweep through
    ONE vmapped dispatch with the built-in §4 verification on, and none
    violates."""
    from repro.lease_array.falsify import FalsifyConfig, random_population

    cfg = FalsifyConfig(restarts=True, pop_size=1024, seed=3,
                        p_restart=0.08)
    planes = random_population(np.random.default_rng(3), cfg)
    assert planes["acc_restart"].any() and planes["prop_restart"].any()
    res = cfg.engine().sweep(Scenario(planes))  # verify=True: raises on §4
    assert res.max_owner_count.shape == (1024,)
    assert (np.asarray(res.max_owner_count) <= 1).all()


# ------------------------------------------- S2: scenario -> trace triage

def _restart_scenario(acc_val=1, prop_hits=1):
    T, N, A, P = 12, 2, 3, 4
    att = np.full((T, N), -1, np.int32)
    att[0, :] = 0
    arst = np.zeros((T, A), np.int32)
    arst[4, 1] = acc_val
    prst = np.zeros((T, P), np.int32)
    prst[2:2 + prop_hits, 0] = 1
    return Scenario.build(
        T, n_cells=N, n_acceptors=A, n_proposers=P,
        attempts=att, acc_restart=arst, prop_restart=prst,
    )


def test_trace_from_scenario_refuses_multi_restart_ticks():
    with pytest.raises(ValueError, match="binary restart"):
        trace_from_scenario(_restart_scenario(acc_val=2), lease_ticks=2)


def test_trace_from_scenario_refuses_carve_overflow():
    sc = _restart_scenario(prop_hits=MAX_RESTARTS + 1)
    with pytest.raises(ValueError, match="MAX_RESTARTS"):
        trace_from_scenario(sc, lease_ticks=2)


def test_trace_from_scenario_converts_restarts_faithfully():
    """A legal restart scenario converts with its schedules intact, and
    the converted trace replays referee == array (the triage path a
    shrunk restart survivor takes)."""
    sc = _restart_scenario()
    tr = trace_from_scenario(sc, lease_ticks=2, round_ticks=3)
    assert np.array_equal(tr.acc_restarts,
                          np.asarray(sc.planes["acc_restart"]))
    assert np.array_equal(tr.prop_restarts,
                          np.asarray(sc.planes["prop_restart"]))
    ref = replay_event_sim(tr)
    ow, cn = replay_array(tr)
    assert np.array_equal(ref, np.asarray(ow))
    assert int(np.max(np.asarray(cn))) <= 1


# --------------------------------------------- the packed-ballot carve

def test_restart_carve_shrinks_the_pack_budget():
    """The RESTART_SHIFT carve costs the run field its two low bits: the
    P=8 honest bound 4094 collapses to 1022, where the final ballot
    ((1023 << 2) | 3) * 8 + 7 fills PACK_MASK exactly."""
    assert max_pack_tick(8, 13, 0) == 4094
    for mr in (1, MAX_RESTARTS):
        assert max_pack_tick(8, 13, 0, max_restarts=mr) == 1022
    check_pack_budget(1022, 8, 13, max_restarts=MAX_RESTARTS)
    with pytest.raises(ValueError, match="budget"):
        check_pack_budget(1023, 8, 13, max_restarts=MAX_RESTARTS)
    with pytest.raises(ValueError, match="carve"):
        check_pack_budget(10, 8, 13, max_restarts=MAX_RESTARTS + 1)


def test_engine_refuses_restarts_beyond_the_carve():
    """A trace restarting one proposer more often than the carve holds
    must be refused up front (host-side), not silently mis-encoded."""
    T, N, A, P = 16, 2, 3, 4
    prst = np.zeros((T, P), np.int32)
    prst[: MAX_RESTARTS + 1, 1] = 1
    sc = Scenario.build(T, n_cells=N, n_acceptors=A, n_proposers=P,
                        prop_restart=prst)
    eng = LeaseArrayEngine(N, n_acceptors=A, n_proposers=P, lease_ticks=2)
    with pytest.raises(ValueError, match="carve"):
        eng.run_trace(sc)
