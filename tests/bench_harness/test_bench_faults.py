"""The comparison that decides a run's ``correct`` catches a broken timed
path: each cell runs (without the look for a chip, at a tiny size) with
a fault of ``bench/faults.py`` planted underneath, and must come out not
correct: the tick faults (state unchanged, half the cells left out, an
owner altered) in every cell, the policy faults (renewals left out,
attempts for the wrong worker) in the directory, where only the
comparison with the reference's policy can see them.

A sound run of each cell must come out correct.
"""
import pytest

from bench.faults import POLICY_FAULTS, TICK_FAULTS, clear_programs, planted
from bench_small import SMALL

CELLS = sorted(SMALL)
SEED = 2**31 + 12345


def _run(name, **kw):
    from bench.run import run_cell

    return run_cell(name, SEED, 1.5, False, require_chip=False,
                    overrides=SMALL[name], **kw)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, jax_settings):
    clear_programs()
    result = _run(name)
    assert result["correct"], result["checks"]
    assert result["window"]["compiles"] == 0
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", TICK_FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_broken_tick_is_not_correct(name, fault, jax_settings):
    with planted(fault):
        result = _run(name)
    assert not result["correct"], result["checks"]
    assert result["failed"] >= 1


@pytest.mark.parametrize("fault", POLICY_FAULTS)
def test_broken_policy_is_not_correct(fault, jax_settings):
    with planted(fault):
        result = _run("directory.chubby_directory")
    assert not result["correct"], result["checks"]
    assert result["checks"]["policy_plane_mismatches"]["value"] > 0
    assert result["checks"]["owner_mismatches"]["value"] == 0
    assert result["failed"] >= 1


@pytest.mark.parametrize("name", CELLS)
def test_control_in_the_programs_place_is_not_correct(name, jax_settings):
    clear_programs()
    result = _run(name, control=True)
    assert not result["correct"], result["checks"]
    assert sum(c["value"] for k, c in result["checks"].items()
               if k.endswith("mismatches")) > 0
