"""Vectorized lease plane: protocol semantics at the array level, pallas
kernel vs jnp oracle, batched-width floor, vmap-ability, and the shard
directory fast path."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.shards import ShardLeaseManager, build_shard_manager
from repro.configs import CellConfig
from repro.core import build_cell
from repro.lease_array import (
    NO_PROPOSER,
    LeaseArrayEngine,
    init_state,
    lease_quarters,
    make_tick,
    random_trace,
    replay_array,
)
from repro.lease_array.directory import LeaseArrayDirectory
from repro.lease_array.ref import lease_step_ref
from repro.sim.network import NetConfig

A = np.array
NA = NO_PROPOSER


def eng(n_cells=8, **kw):
    kw.setdefault("n_acceptors", 5)
    kw.setdefault("n_proposers", 4)
    kw.setdefault("lease_ticks", 3)
    return LeaseArrayEngine(n_cells, **kw)


def tick(e, **planes):
    """One validated TickInputs sized for engine ``e`` (registry names)."""
    return make_tick(
        n_cells=e.n_cells, n_acceptors=e.n_acceptors,
        n_proposers=e.n_proposers, **planes,
    )


# ----------------------------------------------------------- protocol steps
def test_acquire_hold_expire():
    e = eng(n_cells=4)
    own = e.step(tick(e, attempts=A([0, 1, NA, NA])))
    assert own.tolist() == [0, 1, NA, NA]
    # held without renewal for lease_ticks ticks, then expires
    for _ in range(e.lease_ticks):
        own = e.step()
        assert own.tolist() == [0, 1, NA, NA]
    assert e.step().tolist() == [NA] * 4


def test_extend_resets_clock_and_contender_is_shut_out():
    e = eng(n_cells=1)
    assert e.step(tick(e, attempts=A([0])))[0] == 0
    # a contender's higher ballot gets promises but no open majority
    assert e.step(tick(e, attempts=A([1])))[0] == 0
    # the owner extends (§6): its own accepted proposal counts as open
    assert e.step(tick(e, attempts=A([0])))[0] == 0
    for _ in range(e.lease_ticks):
        assert e.step()[0] == 0  # clock restarted at the extend tick
    assert e.step()[0] == NA


def test_release_frees_cell_immediately():
    e = eng(n_cells=2)
    e.step(tick(e, attempts=A([0, 1])))
    assert e.step(tick(e, releases=A([0, NA]))).tolist() == [NA, 1]
    # released cell is acquirable by someone else the very next tick
    assert e.step(tick(e, attempts=A([2, NA]))).tolist() == [2, 1]


def test_release_by_non_owner_is_noop():
    e = eng(n_cells=1)
    e.step(tick(e, attempts=A([0])))
    assert e.step(tick(e, releases=A([3])))[0] == 0


def test_quorum_loss_blocks_acquisition():
    e = eng(n_cells=1, n_acceptors=5)
    down3 = A([0, 0, 0, 1, 1])  # 3 of 5 unreachable -> no majority
    assert e.step(tick(e, attempts=A([0]), acc_up=down3))[0] == NA
    assert e.step(tick(e, attempts=A([0])))[0] == 0  # healed -> wins


def test_promises_survive_lease_expiry():
    e = eng(n_cells=1)
    e.step(tick(e, attempts=A([3])))
    for _ in range(e.lease_ticks + 1):
        e.step()
    assert e.owners()[0] == NA
    # later-tick ballots are higher, so a fresh acquire still works
    assert e.step(tick(e, attempts=A([0])))[0] == 0
    promised = np.asarray(e.state.highest_promised)
    assert (promised > 0).all()  # never reset by expiry


# ------------------------------------------------------- engine queries
def test_ticks_left_owned_unowned_expiring():
    e = eng(n_cells=3, lease_ticks=3)
    e.step(tick(e, attempts=A([0, 1, NA])))
    # owned cells: won at t=0, expiry quarter 4*3+1=13; unowned cell: 0
    # at t=1: (13 - 4) // 4 = 2 whole ticks beyond the current one
    assert e.ticks_left().tolist() == [2, 2, 0]
    e.step()
    assert e.ticks_left().tolist() == [1, 1, 0]
    e.step()
    assert e.ticks_left().tolist() == [0, 0, 0]  # expiring: no whole tick
    assert e.owners().tolist() == [0, 1, NA]  # ...but still owned...
    e.step()
    assert e.owners().tolist() == [0, 1, NA]  # ...through the expiry tick
    assert e.ticks_left().tolist() == [0, 0, 0]
    e.step()  # gone the tick after
    assert e.owners().tolist() == [NA] * 3
    assert e.ticks_left().tolist() == [0, 0, 0]


def test_ticks_left_resets_on_extend():
    e = eng(n_cells=1, lease_ticks=4)
    e.step(tick(e, attempts=A([2])))
    for _ in range(3):
        e.step()
    assert e.ticks_left().tolist() == [0]
    e.step(tick(e, attempts=A([2])))  # §6 extend restarts the clock
    assert e.ticks_left().tolist() == [3]


def test_row_rejects_ghost_proposer():
    e = eng(n_cells=2, n_proposers=4)
    with pytest.raises(ValueError, match=r"proposer id 4 out of range.*4 proposers"):
        e.step(tick(e, attempts=A([4, NA])))
    with pytest.raises(ValueError, match="out of range"):
        e.step(tick(e, releases=A([NA, 99])))


def test_row_rejects_below_sentinel():
    e = eng(n_cells=2)
    with pytest.raises(ValueError, match="out of range"):
        e.step(tick(e, attempts=A([-2, 0])))
    # the sentinel itself and valid ids are fine
    assert e.step(tick(e, attempts=A([NA, 0]))).tolist() == [NA, 0]


# -------------------------------------------------- kernel vs oracle, width
@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_backend_follows_platform(monkeypatch, platform):
    """No backend named: the compiled kernel on a TPU, the jnp scan
    elsewhere. A kernel mode that does not suit the platform raises."""
    from repro.lease_array.ops import resolve_backend

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    kernel, wrong = (
        ("pallas_tpu", "pallas") if platform == "tpu"
        else ("pallas", "pallas_tpu")
    )
    assert resolve_backend() == ("jnp", "pallas_tpu")[platform == "tpu"]
    assert eng(backend=None).backend == resolve_backend()
    assert resolve_backend(kernel) == kernel
    assert resolve_backend("jnp") == "jnp"
    with pytest.raises(ValueError, match=wrong):
        resolve_backend(wrong)
    with pytest.raises(ValueError, match=wrong):
        eng(backend=wrong)
    with pytest.raises(ValueError, match="unknown"):
        resolve_backend("cuda")


@pytest.mark.parametrize("n_cells", [64, 100, 1000])
def test_pallas_matches_jnp_oracle(n_cells):
    tr = random_trace(
        11, n_ticks=30, n_cells=n_cells, n_acceptors=5, n_proposers=6,
        lease_ticks=2, p_release=0.1, p_down_flip=0.05,
    )
    jo, jc = replay_array(tr, backend="jnp")
    po, pc = replay_array(tr, backend="pallas")
    assert np.array_equal(jo, po)
    assert np.array_equal(jc, pc)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_single_batched_step_at_4096_cells(backend):
    e = eng(n_cells=4096, n_proposers=8, backend=backend)
    attempt = np.arange(4096, dtype=np.int32) % 8
    own = e.step(tick(e, attempts=attempt))
    assert (own == attempt).all()  # uncontended: everyone wins its cell
    assert np.asarray(e.last_owner_count).max() <= 1


def test_vmap_over_independent_planes():
    step = functools.partial(
        lease_step_ref, majority=3, lease_q4=lease_quarters(3)
    )
    n_planes, n_cells = 3, 16
    states = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[init_state(n_cells, 5, 4)] * n_planes
    )
    attempts = jnp.stack(
        [jnp.full(n_cells, p % 4, jnp.int32) for p in range(n_planes)]
    )
    none = jnp.full((n_planes, n_cells), NA, jnp.int32)
    up = jnp.ones((n_planes, 5), jnp.int32)
    batched = jax.vmap(step, in_axes=(0, None, 0, 0, 0))
    states, counts = batched(states, jnp.int32(0), attempts, none, up)
    assert counts.shape == (n_planes, n_cells)
    assert (counts == 1).all()
    # planes are independent: each plane's owner is its own attempt row
    assert (np.asarray(states.owner_mask).sum(axis=1) == 1).all(), "one owner bit per cell"


# ----------------------------------------------------------- the directory
def test_directory_coverage_failover_drain_retarget():
    d = LeaseArrayDirectory(512, n_acceptors=3, lease_ticks=4, max_workers=8)
    for i in range(4):
        d.add_worker(i, 128)
    d.tick(3)
    assert d.coverage() == 1.0
    assert all(d.owned_count(i) == 128 for i in range(4))

    d.stall(0)  # straggler: stops renewing, says nothing
    d.tick(d.engine.lease_ticks + 2)
    assert d.owned_count(0) == 0
    # elastic pickup: retarget the healthy workers to absorb the loss
    for i in range(1, 4):
        d.set_target(i, 512 // 3 + 1)
    d.tick(3)
    assert d.coverage() == 1.0

    d.drain(1)  # graceful §7 release -> redistributed, not expired
    for i in (2, 3):
        d.set_target(i, 256)
    d.tick(4)
    assert d.owned_count(1) == 0
    assert d.coverage() == 1.0

    m = d.owner_map()
    assert len(m) == 512 and set(m.values()) <= {2, 3}


def test_build_shard_manager_backend_dispatch():
    assert isinstance(build_shard_manager(4096, max_workers=4), LeaseArrayDirectory)
    cfg = CellConfig(n_acceptors=3, max_lease_time=30.0, lease_timespan=5.0)
    d = build_shard_manager(2048, cfg=cfg, max_workers=4)
    assert isinstance(d, LeaseArrayDirectory)
    assert d.engine.n_acceptors == 3  # inherited from the cell config
    cell = build_cell(cfg, seed=0, net=NetConfig(delay_min=0.001, delay_max=0.002))
    m = build_shard_manager(64, cell=cell)
    assert isinstance(m, ShardLeaseManager)
    with pytest.raises(ValueError):
        build_shard_manager(64, backend="event")  # event path needs a Cell


@pytest.mark.parametrize("tick", [None, 0, 7, 8, 19])
def test_only_default_finds_a_value_in_any_block(tick):
    """run_trace's default-plane test reads the plane block by block; a
    value off the default in any tick, the last block's included, is
    found."""
    from repro.lease_array.ops import _only_default

    plane = np.full((20, 64), NO_PROPOSER, np.int32)
    if tick is not None:
        plane[tick, 63] = 2
    assert _only_default(plane, NO_PROPOSER) == (tick is None)
