"""The lease plane's own host spans and counters (``lease.*``).

Under a JAX profiler trace the engine's entry points and the directory's
tick record the span tree their docstrings name, with their counters as
event stats: the directory's issued attempts, releases and extends, and
the window kernel's grid steps and quiescent skips. With no trace on,
no counter is computed and the skip count is never read back."""
import warnings

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData, TraceAnnotation

from repro.lease_array import LeaseArrayEngine, Scenario
from repro.lease_array import directory as directory_mod
from repro.lease_array import engine as engine_mod
from repro.lease_array.directory import LeaseArrayDirectory
from repro.lease_array.state import NO_PROPOSER

RUN_TRACE = ["lease.validate", "lease.upload", "lease.validate",
             "lease.dispatch", "lease.wait", "lease.download"]
STEP = ["lease.validate", "lease.dispatch", "lease.wait", "lease.download"]
DIR_TICK = ["lease.ticks_left", "lease.dir.shed", "lease.dir.renew",
            "lease.dir.assign", "lease.dir.make_tick", "lease.step"]


class Node:
    def __init__(self, name, start, end, stats):
        self.name, self.start, self.end = name, start, end
        self.stats, self.children = stats, []

    def kids(self):
        return [c.name for c in self.children]

    def named(self, name):
        return [c for c in self.children if c.name == name]


def lease_roots(trace_dir) -> list:
    """The ``lease.*`` host events of the trace under ``trace_dir``,
    nested by time on their thread's line; returns the outermost ones."""
    (path,) = trace_dir.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))  # its planes live as long
    roots = []
    for plane in data.planes:
        for line in plane.lines:
            with warnings.catch_warnings():
                # the stats' binding type warns that it has no __module__
                warnings.simplefilter("ignore", DeprecationWarning)
                nodes = sorted(
                    (Node(e.name, e.start_ns, e.start_ns + e.duration_ns,
                          dict(e.stats))
                     for e in line.events if e.name.startswith("lease.")),
                    key=lambda n: (n.start, -n.end),
                )
            stack = []
            for n in nodes:
                while stack and n.end > stack[-1].end:
                    stack.pop()
                (stack[-1].children if stack else roots).append(n)
                stack.append(n)
    return sorted(roots, key=lambda n: n.start)


def _scenario(n_cells, n_ticks, attempts=None):
    if attempts is None:
        attempts = np.full((n_ticks, n_cells), NO_PROPOSER, np.int32)
        attempts[0] = np.arange(n_cells) % 3
    return Scenario.build(n_cells=n_cells, n_acceptors=3, n_proposers=3,
                          attempts=attempts)


def _directory(n_shards=256, workers=4):
    d = LeaseArrayDirectory(n_shards, n_acceptors=3, lease_ticks=6,
                            max_workers=workers, max_delay_ticks=1,
                            backend="jnp")
    for w in range(workers):
        d.add_worker(w, n_shards // workers)
    return d


def test_engine_and_directory_record_the_span_tree(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        eng = LeaseArrayEngine(128, n_acceptors=3, n_proposers=3,
                               lease_ticks=4, backend="jnp")
        eng.run_trace(_scenario(128, 6))
        d = _directory()
        d.tick(3)
    roots = lease_roots(tmp_path)
    assert [r.name for r in roots] == (
        ["lease.init", "lease.run_trace", "lease.init"]
        + ["lease.dir.tick"] * 3
    )
    run = roots[1]
    assert run.kids() == RUN_TRACE
    assert set(run.stats) == {"windows", "skipped"}
    # the device check read the attempts and releases planes, 6 x 128 each
    assert run.children[2].stats == {"planes": 2, "bytes": 2 * 6 * 128 * 4}
    for i, tick in enumerate(roots[3:]):
        assert tick.kids() == DIR_TICK
        assert set(tick.stats) == {"attempts", "releases", "extends", "t"}
        assert tick.stats["t"] == i
        ticks_left, renew, step = (tick.children[0], tick.children[2],
                                   tick.children[5])
        assert ticks_left.kids() == ["lease.wait", "lease.download"]
        assert set(renew.stats) == {"candidates", "extends"}
        assert step.kids() == STEP
        assert set(step.stats) == {"windows", "skipped"}
    assert eng.t == 6


def test_directory_counters_match_the_issued_planes(tmp_path, monkeypatch):
    issued = []
    make_tick = directory_mod.make_tick

    def spy(**planes):
        issued.append({k: planes[k] for k in
                       ("attempts", "releases", "extends")})
        return make_tick(**planes)

    monkeypatch.setattr(directory_mod, "make_tick", spy)
    d = _directory()
    d.tick(4)
    d.drain(3)  # the next tick releases worker 3's shards,
    d.set_target(0, 128)  # and worker 0 attempts them once they are free
    with jax.profiler.trace(str(tmp_path)):
        d.tick(8)
    ticks = [r for r in lease_roots(tmp_path) if r.name == "lease.dir.tick"]
    assert [t.stats["t"] for t in ticks] == list(range(4, 12))
    for tick, planes in zip(ticks, issued[4:]):
        renew = tick.children[2]
        n_ext = int(np.count_nonzero(planes["extends"] != NO_PROPOSER))
        assert renew.stats["extends"] == tick.stats["extends"] == n_ext
        assert renew.stats["candidates"] >= n_ext
        for k in ("attempts", "releases"):
            assert tick.stats[k] == np.count_nonzero(planes[k] != NO_PROPOSER)
    totals = {k: sum(t.stats[k] for t in ticks)
              for k in ("attempts", "releases", "extends")}
    assert all(totals.values()), totals  # each counter saw work


# Two cell blocks of 512, four windows of 4 ticks: 8 grid steps. Block 0
# is attempted at tick 0, so its first window runs the tick loop; its
# round resolves there (zero-delay legs) under a 100-tick lease, so its
# last three windows are quiescent. Block 1 is never touched: all four
# of its windows are quiescent. 7 of 8 steps skip.
SKIP_CASES = {
    "pallas": ("pallas", True, 7, 8),
    "pallas_no_skip": ("pallas", False, 0, 8),
    "jnp": ("jnp", True, 0, 0),
}


@pytest.mark.parametrize("case", sorted(SKIP_CASES))
def test_skip_count_against_a_hand_count(tmp_path, case):
    backend, skip_stable, skipped, windows = SKIP_CASES[case]
    n_cells, n_ticks = 1024, 16
    attempts = np.full((n_ticks, n_cells), NO_PROPOSER, np.int32)
    attempts[0, :512] = 1
    eng = LeaseArrayEngine(
        n_cells, n_acceptors=3, n_proposers=3, lease_ticks=100,
        backend=backend, window=4, skip_stable=skip_stable,
    )
    with jax.profiler.trace(str(tmp_path)):
        owners, _ = eng.run_trace(_scenario(n_cells, n_ticks, attempts),
                                  netplane=True)
    (run,) = [r for r in lease_roots(tmp_path) if r.name == "lease.run_trace"]
    assert run.stats == {"windows": windows, "skipped": skipped}
    assert (owners[1:, :512] == 1).all() and (owners[:, 512:] < 0).all()


@pytest.mark.parametrize("extend", [False, True], ids=["stripped", "in_use"])
def test_device_check_counts_its_planes_and_bytes(tmp_path, monkeypatch,
                                                  extend):
    """The device ``lease.validate`` span counts the [T, N] planes whose
    bounds it read and their bytes, while a trace is on; without one the
    counters are never computed."""
    n_cells, n_ticks = 256, 8
    extends = np.full((n_ticks, n_cells), NO_PROPOSER, np.int32)
    if extend:
        extends[4] = np.arange(n_cells) % 3
    sc = Scenario.build(n_cells=n_cells, n_acceptors=3, n_proposers=3,
                        attempts=_scenario(n_cells, n_ticks).attempts,
                        extends=extends)
    n_planes = 3 if extend else 2
    with jax.profiler.trace(str(tmp_path)):
        LeaseArrayEngine(n_cells, n_acceptors=3, n_proposers=3,
                         lease_ticks=6, backend="jnp").run_trace(sc)
    (run,) = [r for r in lease_roots(tmp_path) if r.name == "lease.run_trace"]
    host, device = run.named("lease.validate")
    assert host.stats == {} and host.end <= run.children[1].start
    assert device.start >= run.children[1].end
    assert device.stats == {"planes": n_planes,
                            "bytes": n_planes * n_ticks * n_cells * 4}
    stats = []
    monkeypatch.setattr(TraceAnnotation, "set_metadata",
                        lambda self, **kw: stats.append(kw))
    LeaseArrayEngine(n_cells, n_acceptors=3, n_proposers=3, lease_ticks=6,
                     backend="jnp").run_trace(sc)
    assert stats == []


def _drive():
    eng = LeaseArrayEngine(1024, n_acceptors=3, n_proposers=3,
                           lease_ticks=6, backend="pallas")
    eng.run_trace(_scenario(1024, 8), netplane=True)
    eng.step(_scenario(1024, 1)[0])
    _directory().tick(2)


@pytest.mark.parametrize("tracing", [False, True], ids=["off", "on"])
def test_counters_only_while_a_trace_is_on(tmp_path, monkeypatch, tracing):
    reads, stats = [], []
    grid_counts = engine_mod._grid_counts
    monkeypatch.setattr(engine_mod, "_grid_counts",
                        lambda steps: reads.append(1) or grid_counts(steps))
    monkeypatch.setattr(TraceAnnotation, "set_metadata",
                        lambda self, **kw: stats.append(kw))
    if tracing:
        with jax.profiler.trace(str(tmp_path)):
            _drive()
        # run_trace, step and the directory's two steps read the count;
        # each directory tick also counts its renewals and its planes, and
        # run_trace its device check
        assert len(reads) == 4 and len(stats) == 9
    else:
        _drive()
        assert reads == [] and stats == []
