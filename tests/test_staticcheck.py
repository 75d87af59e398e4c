"""leaselint: the static-analysis pass that gates CI (`make check`).

Four checkers over the *real* traced jaxprs / launch plans / sources:

  intervals    — abstract interpretation proving the packed int32 tick math
                 cannot overflow, deriving max_pack_tick independently
  purity       — no floats / silent int64 / gathers on the Pallas path
  launch       — BlockSpec bounds, write-race freedom, coverage, VMEM budget
  conventions  — AST lints: shim quarantine, clock-domain deadline compares,
                 registry-generated plane table in the docs

Each checker is mutation-tested: a seeded mutant fixture must trip it and
a clean twin must pass, else the lint itself has lost its teeth.
"""
import json
import shutil
import warnings
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.analysis.staticcheck import (  # noqa: E402
    TickConfig,
    analyze_tick_config,
    check_conventions,
    check_tick_cores,
    check_window_kernels,
    check_window_launches,
    derived_max_pack_tick,
    run_all,
)
from repro.analysis.staticcheck.cli import main, write_plane_table  # noqa: E402
from repro.analysis.staticcheck.fixtures import (  # noqa: E402
    FIXTURES,
    run_mutation_tests,
)
from repro.lease_array import LeaseArrayEngine, Scenario  # noqa: E402
from repro.lease_array.state import check_pack_budget, max_pack_tick  # noqa: E402

NA = -1

# round_ticks chosen so round deadlines (rnd_clk + 4*round_ticks) sit just
# under int32 max at t=0 and cross it within ~100 ticks — invisible to
# check_pack_budget, which never consults round_q4.
HUGE_ROUND_TICKS = 536_870_900
HUGE_ROUND_Q4 = 4 * HUGE_ROUND_TICKS


# --------------------------------------------------------------- clean tree
def test_clean_tree_is_clean():
    findings = run_all(skip_mutation=True)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_purity_clean_on_real_cores():
    assert check_tick_cores() == []
    assert check_window_kernels(256, n_ticks=16, block_n=256, window=16) == []


def test_launch_clean_on_shipped_plans():
    assert check_window_launches() == []


def test_conventions_clean_on_real_sources():
    assert check_conventions() == []


# ----------------------------------------------- the interval analysis core
@pytest.mark.parametrize("n_proposers", [2, 3, 8, 16])
@pytest.mark.parametrize("max_rate", [4, 9])
def test_derived_bound_matches_hand_exactly(n_proposers, max_rate):
    """The acceptance bar: the abstract interpreter re-derives the hand
    max_pack_tick bound to the tick (±0) with no knowledge of the formula."""
    hand = max_pack_tick(n_proposers, 13, 0, max_rate, 0)
    derived = derived_max_pack_tick(n_proposers, 13, 0, max_rate, 0)
    assert derived == hand


def test_interval_analysis_rejects_what_runtime_check_misses():
    """round_q4 never enters check_pack_budget, so a huge round horizon
    sails through the hand check — the jaxpr-level analysis catches the
    add that overflows."""
    # the runtime hand check is blind to this config...
    check_pack_budget(100, 2, 13, 0)  # does not raise
    # ...the interval analysis is not
    cfg = TickConfig(t_end=100, n_proposers=2, n_acceptors=3,
                     lease_q4=13, round_q4=HUGE_ROUND_Q4)
    rules = {f.rule for f in analyze_tick_config(cfg)}
    assert "int32-overflow" in rules


def test_interval_analysis_accepts_genuinely_safe_short_horizon():
    """At t_end=3 the same round deadline still fits int32 — the analysis
    proves exactly where overflow becomes reachable, not a blanket ban."""
    cfg = TickConfig(t_end=3, n_proposers=2, n_acceptors=3,
                     lease_q4=13, round_q4=HUGE_ROUND_Q4)
    assert analyze_tick_config(cfg) == []


# ------------------------------------------------------- mutation fixtures
@pytest.mark.parametrize("checker", sorted(FIXTURES))
def test_seeded_mutant_is_caught(checker):
    mutant, want_rules, _ = FIXTURES[checker]
    rules = {f.rule for f in mutant()}
    assert rules & want_rules, (
        f"{checker} mutant produced {sorted(rules)}, "
        f"expected one of {sorted(want_rules)}"
    )


@pytest.mark.parametrize("checker", sorted(FIXTURES))
def test_clean_twin_passes(checker):
    _, _, clean = FIXTURES[checker]
    findings = clean()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_mutation_self_test_is_green():
    assert run_mutation_tests() == []


# ------------------------------------- engine wiring: the static gate (S1)
def test_engine_run_trace_refuses_overflowing_round_horizon():
    eng = LeaseArrayEngine(4, n_acceptors=3, n_proposers=2,
                           round_ticks=HUGE_ROUND_TICKS)
    sc = Scenario.build(100, n_cells=4, n_acceptors=3, n_proposers=2)
    with pytest.raises(ValueError, match="static analysis refused"):
        eng.run_trace(sc)


def test_engine_sweep_refuses_overflowing_round_horizon():
    eng = LeaseArrayEngine(4, n_acceptors=3, n_proposers=2,
                           round_ticks=HUGE_ROUND_TICKS)
    sc = Scenario.build(100, n_cells=4, n_acceptors=3, n_proposers=2)
    with pytest.raises(ValueError, match="static analysis refused"):
        eng.sweep([sc])


def test_engine_accepts_default_configs():
    eng = LeaseArrayEngine(8, n_acceptors=5, n_proposers=8)
    sc = Scenario.build(20, n_cells=8, n_acceptors=5, n_proposers=8,
                        attempts=np.zeros((20, 8), np.int32))
    owners, counts = eng.run_trace(sc)
    assert owners.shape == (20, 8)
    assert (np.asarray(owners)[-1] == 0).all()


def test_traced_pack_budget_skip_warns_once(monkeypatch):
    """When the tick count is a tracer the host-side guard cannot run;
    the skip must announce itself (once), pointing at the static check."""
    import repro.lease_array.ops as ops_mod
    from repro.lease_array.netplane import init_netplane
    from repro.lease_array.ops import lease_window_scan
    from repro.lease_array.state import init_state

    monkeypatch.setattr(ops_mod, "_WARNED_TRACED_SKIP", False)
    T, N, P, A = 4, 4, 2, 3
    st, net = init_state(N, A, P), init_netplane(N, A)
    planes = {
        "attempts": np.full((T, N), NA, np.int32),
        "releases": np.full((T, N), NA, np.int32),
        "acc_up": np.ones((T, A), np.int32),
        "delay": np.zeros((T, P, A), np.int32),
        "drop": np.zeros((T, P, A), np.int32),
    }

    def scan(round_q4):
        return jax.jit(lambda s, n, t: lease_window_scan(
            s, n, t, planes, majority=2, lease_q4=13, round_q4=round_q4,
            block_n=N, window=T))

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        scan(8)(st, net, jnp.int32(0))
        scan(9)(st, net, jnp.int32(0))  # second trace: no repeat
    skips = [x for x in w if issubclass(x.category, RuntimeWarning)
             and "check_pack_budget skipped" in str(x.message)]
    assert len(skips) == 1
    assert ops_mod._WARNED_TRACED_SKIP is True


def test_engine_static_check_failure_degrades_to_warning(monkeypatch):
    """If the analyzer itself crashes, the engine fails closed: run_trace
    and sweep refuse the dispatch (no warning-and-carry-on), on every
    call, and the engine does not advance."""
    import repro.lease_array.engine as engine_mod

    def boom(*a, **k):
        raise RuntimeError("analyzer exploded")

    monkeypatch.setattr(engine_mod, "_static_pack_findings", boom)
    eng = LeaseArrayEngine(4, n_acceptors=3, n_proposers=2)
    sc = Scenario.build(5, n_cells=4, n_acceptors=3, n_proposers=2)
    for _ in range(2):  # refused every time, not only the first
        with pytest.raises(RuntimeError, match="analyzer exploded"):
            eng.run_trace(sc)
    with pytest.raises(RuntimeError, match="static pack-budget analysis"):
        eng.sweep([sc, sc])
    assert eng.t == 0


# ------------------------------------------------------------ CLI & output
def test_cli_clean_run_writes_json_artifact(tmp_path, capsys):
    out = tmp_path / "findings.json"
    rc = main(["--json", str(out), "--skip-mutation"])
    assert rc == 0
    assert "leaselint: clean" in capsys.readouterr().out
    payload = json.loads(out.read_text())
    assert payload["findings"] == []
    assert payload["ok"] is True
    assert payload["n_findings"] == 0


def test_write_plane_table_is_idempotent(tmp_path):
    repo = Path(__file__).resolve().parents[1]
    doc = repo / "docs" / "scenario_api.md"
    (tmp_path / "docs").mkdir()
    shutil.copy(doc, tmp_path / "docs" / "scenario_api.md")
    write_plane_table(root=tmp_path)
    # the committed table already matches the registry — a rewrite is a no-op
    assert (tmp_path / "docs" / "scenario_api.md").read_text() == doc.read_text()
