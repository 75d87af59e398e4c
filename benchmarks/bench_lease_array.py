"""§8 at scale: cells/sec of the vectorized lease plane vs the event-driven
simulator on identical randomized workloads.

The event engine pays Python per message (the per-message overhead that
dominates quorum-protocol throughput in practice); the array plane pays one
batched dispatch for *all* cells — and, since PR 4, for all TICKS too: the
``lease_fused_scan`` row drives the fused window scan (packed int32 layout,
cell axis shard_map-ed across every visible device), while
``lease_array_scan`` keeps timing the per-tick ``lax.scan`` driver it always
measured, so the fused speedup is visible inside one file. Reported as
cell-ticks/sec.

``python -m benchmarks.bench_lease_array`` runs every mode and writes the
machine-readable ``BENCH_lease_array.json`` (schema at the bottom) so the
perf trajectory is tracked across PRs; ``make bench-json`` wraps it. The
__main__ entry re-execs itself with one JAX host device per CPU core so the
sharded driver has something to shard over (a real accelerator platform is
unaffected). ``benchmarks/compare_bench.py`` diffs two of these files and
gates CI on regressions.
"""
from __future__ import annotations

import os
import subprocess
import sys

if __name__ == "__main__" and "_LEASE_BENCH_CHILD" not in os.environ:
    # re-exec BEFORE jax is imported: expose every CPU core as a device so
    # the sharded fused driver can split the cell axis across them
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        n = os.cpu_count() or 1
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
        )
    os.environ["_LEASE_BENCH_CHILD"] = "1"
    os.execv(
        sys.executable,
        [sys.executable, "-m", "benchmarks.bench_lease_array", *sys.argv[1:]],
    )

import json
import platform
from pathlib import Path

import numpy as np

from repro.lease_array import (
    LeaseArrayEngine,
    make_tick,
    random_trace,
    replay_array,
    replay_event_sim,
)
from repro.lease_array.ops import resolve_backend

from .common import WallTimer, fmt

BEST_OF = 3  # timed reps per row (after warm-up); best wall time wins


def _kernel_backend() -> str:
    """The window kernel's backend here: the platform's own choice where
    that is the kernel (compiled, on a TPU), interpret mode anywhere
    else."""
    b = resolve_backend()
    return "pallas" if b == "jnp" else b


def timed(fn, reps=BEST_OF):
    """Best-of-N wall time of ``fn`` (call it warm first): the bench gates
    CI on per-row deltas, so single-shot scheduler noise must not fail the
    25% regression threshold on a loaded 2-core runner."""
    best_dt, best_out = None, None
    for _ in range(reps):
        with WallTimer() as wt:
            out = fn()
        if best_dt is None or wt.dt < best_dt:
            best_dt, best_out = wt.dt, out
    return best_dt, best_out

EVENT_CELLS, EVENT_TICKS = 96, 30
ARRAY_CELLS, ARRAY_TICKS = 4096, 128
KERNEL_CELLS, KERNEL_TICKS = 1024, 32
DELAY_CELLS, DELAY_TICKS = 1024, 96
DELAY_DEPTHS = (0, 1, 2, 4)
SWEEP_SCENARIOS, SWEEP_CELLS, SWEEP_TICKS = 1024, 32, 16


def _trace(n_cells, n_ticks, seed=0):
    return random_trace(
        seed, n_ticks=n_ticks, n_cells=n_cells,
        n_acceptors=5, n_proposers=8, lease_ticks=4,
        p_attempt=0.4, p_release=0.05, p_down_flip=0.0,
    )


def _pertick_replay(trace, *, netplane=False):
    """The trace through the pre-fused per-tick lax.scan driver (ONE
    lease_plane_tick dispatch body per tick) — the dispatch-overhead
    baseline the fused rows are measured against."""
    import jax.numpy as jnp

    from repro.lease_array import init_netplane, init_state
    from repro.lease_array.engine import _scenario_scanner
    from repro.lease_array.state import (
        QUARTERS,
        guarded_lease_q4,
        lease_quarters,
    )

    lease_q4 = lease_quarters(trace.lease_ticks)
    scanner = _scenario_scanner(
        trace.n_acceptors // 2 + 1,
        lease_q4,
        QUARTERS * trace.round_ticks,
        "jnp",
        not netplane,
        guarded_lease_q4(lease_q4, trace.drift_eps),
    )
    planes = {
        k: jnp.asarray(v) for k, v in trace.scenario().planes.items()
    }
    state = init_state(trace.n_cells, trace.n_acceptors, trace.n_proposers)
    net = init_netplane(trace.n_cells, trace.n_acceptors)
    _, _, owners, counts = scanner(state, net, jnp.int32(0), None, planes)
    return np.asarray(owners), np.asarray(counts)


def run():
    rows = []

    ev = _trace(EVENT_CELLS, EVENT_TICKS)
    dt, _ = timed(lambda: replay_event_sim(ev, strict_monitor=True), reps=2)
    ev_rate = EVENT_CELLS * EVENT_TICKS / dt
    rows.append((
        "lease_event_sim",
        dt / (EVENT_CELLS * EVENT_TICKS) * 1e6,
        f"{EVENT_CELLS} cells x {EVENT_TICKS} ticks: {fmt(ev_rate)} cell-ticks/s",
    ))

    ar = _trace(ARRAY_CELLS, ARRAY_TICKS)
    _pertick_replay(_trace(ARRAY_CELLS, ARRAY_TICKS, seed=1))  # warm the jit
    dt, (owners, counts) = timed(lambda: _pertick_replay(ar))
    assert counts.max() <= 1, "at-most-one-owner violated in the array plane"
    ar_rate = ARRAY_CELLS * ARRAY_TICKS / dt
    rows.append((
        "lease_array_scan",
        dt / (ARRAY_CELLS * ARRAY_TICKS) * 1e6,
        f"{ARRAY_CELLS} cells x {ARRAY_TICKS} ticks, per-tick scan driver: "
        f"{fmt(ar_rate)} cell-ticks/s ({fmt(ar_rate / ev_rate)}x event sim), "
        f"owned={float((owners >= 0).mean()):.2f}",
    ))

    # the fused window scan (run_trace's default path): packed layout, one
    # dispatch for the whole trace, cell axis sharded across devices
    replay_array(_trace(ARRAY_CELLS, ARRAY_TICKS, seed=1))  # warm
    dt, (owners, counts) = timed(lambda: replay_array(ar))
    assert counts.max() <= 1
    fused_rate = ARRAY_CELLS * ARRAY_TICKS / dt
    rows.append((
        "lease_fused_scan",
        dt / (ARRAY_CELLS * ARRAY_TICKS) * 1e6,
        f"{ARRAY_CELLS} cells x {ARRAY_TICKS} ticks, fused+sharded scan: "
        f"{fmt(fused_rate)} cell-ticks/s "
        f"({fused_rate / ar_rate:.2f}x the per-tick scan driver)",
    ))

    # dispatch cost, kept visible: ONE host-driven tick (warm) is dominated
    # by launch overhead, which the fused scan pays once per trace instead
    # of once per tick
    eng = LeaseArrayEngine(ARRAY_CELLS, n_acceptors=5, n_proposers=8,
                           lease_ticks=4)
    attempt = np.arange(ARRAY_CELLS, dtype=np.int32) % eng.n_proposers
    tick = make_tick(n_cells=ARRAY_CELLS, n_acceptors=5, n_proposers=8,
                     attempts=attempt)
    eng.step(tick)  # warm
    dt, _ = timed(lambda: eng.step(tick))
    rows.append((
        "kernel_launch_overhead",
        dt / ARRAY_CELLS * 1e6,
        f"one dispatched tick over {ARRAY_CELLS} cells "
        f"({dt * 1e3:.2f} ms/dispatch — the per-tick driver pays this "
        f"every tick, the fused scan once per trace)",
    ))

    # the Pallas window kernel under the scan driver: compiled on a TPU,
    # interpret mode elsewhere (interpret-mode wall time is a python-loop
    # artifact, not a kernel speed claim)
    kernel = _kernel_backend()
    kt = _trace(KERNEL_CELLS, KERNEL_TICKS)
    replay_array(
        _trace(KERNEL_CELLS, KERNEL_TICKS, seed=1), backend=kernel
    )  # warm
    dt, (owners_k, counts_k) = timed(
        lambda: replay_array(kt, backend=kernel), reps=2
    )
    owners_j, _ = replay_array(kt, backend="jnp")
    assert np.array_equal(owners_k, owners_j), "kernel != jnp oracle"
    rows.append((
        "lease_kernel_scan",
        dt / (KERNEL_CELLS * KERNEL_TICKS) * 1e6,
        f"{KERNEL_CELLS} cells x {KERNEL_TICKS} ticks, fused window kernel "
        f"(backend={kernel!r}, bit-exact vs jnp oracle)",
    ))
    return rows


def _delayed_trace(max_delay: int, n_ticks: int, seed: int = 5, asymmetric=False):
    return random_trace(
        seed, n_ticks=n_ticks, n_cells=DELAY_CELLS,
        n_acceptors=5, n_proposers=8, lease_ticks=8,
        p_attempt=0.8, p_release=0.05, p_down_flip=0.0,
        max_delay_ticks=max_delay, p_drop=0.05 if max_delay else 0.0,
        asymmetric=asymmetric, round_ticks=max(3, max_delay + 1),
    )


def run_delayed(depths=DELAY_DEPTHS):
    """Delay-depth sweep of the in-flight message plane: cell-ticks/sec of
    the netplane scan at increasing per-leg delay bounds (depth 0 = the
    zero-delay special case run through the same delayed step), plus the
    resulting ownership density — lease dynamics vs latency regime, the
    Keyspace/cloud-report axis (arXiv 1209.3913, 1404.6719). The deepest
    sweep point re-runs with asymmetric [T, P, A] link matrices, both
    through the fused scan (the historic row name) and through the
    per-tick driver (the in-file baseline for the fused speedup)."""
    rows = []
    sweep = [(d, False) for d in depths] + [(max(depths), True)]
    for depth, asym in sweep:
        tr = _delayed_trace(depth, DELAY_TICKS, asymmetric=asym)
        # warm with the SAME trace length: the scan jit is shape-specialized,
        # so a short warm-up trace would leave the compile inside the timer
        replay_array(
            _delayed_trace(depth, DELAY_TICKS, seed=6, asymmetric=asym),
            netplane=True,
        )
        dt, (owners, counts) = timed(
            lambda: replay_array(tr, netplane=True)
        )
        assert counts.max() <= 1, "at-most-one-owner violated in the netplane"
        rate = DELAY_CELLS * DELAY_TICKS / dt
        name = f"lease_netplane_delay{depth}" + ("_asym" if asym else "")
        rows.append((
            name,
            dt / (DELAY_CELLS * DELAY_TICKS) * 1e6,
            f"{DELAY_CELLS} cells x {DELAY_TICKS} ticks, delay<={depth} "
            f"drop={0.05 if depth else 0.0}"
            f"{' [P, A] asymmetric links' if asym else ''}: "
            f"{fmt(rate)} cell-ticks/s, "
            f"owned={float((owners >= 0).mean()):.2f}",
        ))
        if asym:  # the per-tick baseline on the identical workload
            _pertick_replay(
                _delayed_trace(depth, DELAY_TICKS, seed=6, asymmetric=True),
                netplane=True,
            )  # warm
            dt, _ = timed(lambda: _pertick_replay(tr, netplane=True))
            base_rate = DELAY_CELLS * DELAY_TICKS / dt
            rows.append((
                f"{name}_pertick",
                dt / (DELAY_CELLS * DELAY_TICKS) * 1e6,
                f"same workload through the per-tick scan driver: "
                f"{fmt(base_rate)} cell-ticks/s "
                f"(the fused row is {rate / base_rate:.2f}x faster)",
            ))
    return rows


def run_drift(depth: int = 2):
    """The drifted-clock path: the same netplane scan with per-node
    clock-rate planes (ε = 0.25 → integer rate steps in {3, 4, 5}) and the
    T·(1-ε)/(1+ε) proposer discount threaded through every deadline —
    through BOTH drivers, so the committed baseline gates the drift
    plumbing (local-clock prefix sums + per-cell owner-clock selects) on
    the fused path (``lease_netplane_drift``) and the per-tick driver
    (``lease_drift_pertick``, the ``_pertick`` naming convention of the
    asym row)."""
    def drift_trace(seed):
        return random_trace(
            seed, n_ticks=DELAY_TICKS, n_cells=DELAY_CELLS,
            n_acceptors=5, n_proposers=8, lease_ticks=8,
            p_attempt=0.8, p_release=0.05, p_down_flip=0.0,
            max_delay_ticks=depth, p_drop=0.05, round_ticks=depth + 1,
            drift_eps=0.25,
        )

    tr = drift_trace(7)
    replay_array(drift_trace(8), netplane=True)  # same-shape warm-up compile
    dt, (owners, counts) = timed(lambda: replay_array(tr, netplane=True))
    assert counts.max() <= 1, "§4 violated under drift in the bench trace"
    rate = DELAY_CELLS * DELAY_TICKS / dt
    rows = [(
        "lease_netplane_drift",
        dt / (DELAY_CELLS * DELAY_TICKS) * 1e6,
        f"{DELAY_CELLS} cells x {DELAY_TICKS} ticks, drift eps=0.25 "
        f"(rates 3-5/4) + delay<={depth} drop=0.05, fused scan: "
        f"{fmt(rate)} cell-ticks/s, "
        f"owned={float((owners >= 0).mean()):.2f}",
    )]
    _pertick_replay(drift_trace(8), netplane=True)  # warm
    dt, (_, counts) = timed(lambda: _pertick_replay(tr, netplane=True))
    assert counts.max() <= 1
    base_rate = DELAY_CELLS * DELAY_TICKS / dt
    rows.append((
        "lease_drift_pertick",
        dt / (DELAY_CELLS * DELAY_TICKS) * 1e6,
        f"same drifted workload through the per-tick scan driver: "
        f"{fmt(base_rate)} cell-ticks/s "
        f"(the fused row is {rate / base_rate:.2f}x faster)",
    ))
    return rows


def run_restart(depth: int = 4):
    """The crash/restart planes' cost next to the delay rows they extend:
    all-acceptor ROLLING diskless restarts (two staggered waves — every
    acceptor blanks and goes deaf for M twice per trace, never a whole
    quorum at once) plus one proposer restart-counter bump each (inside
    the RESTART_SHIFT carve), over the deepest delay regime. Restart mode
    switches the whole dispatch to carved ballots + deaf/counter streams,
    so this row prices exactly what the all-default strip avoids."""
    def storm_trace(seed):
        tr = _delayed_trace(depth, DELAY_TICKS, seed=seed)
        T, A, P = DELAY_TICKS, tr.n_acceptors, tr.n_proposers
        rst = np.zeros((T, A), np.int32)
        for wave in (16, 56):
            for a in range(A):
                rst[wave + 4 * a, a] = 1
        prst = np.zeros((T, P), np.int32)
        for p in range(P):
            prst[8 + 6 * p, p] = 1
        tr.acc_restarts, tr.prop_restarts = rst, prst
        return tr

    tr = storm_trace(9)
    replay_array(storm_trace(10), netplane=True)  # same-shape warm-up
    dt, (owners, counts) = timed(lambda: replay_array(tr, netplane=True))
    assert counts.max() <= 1, "§4 violated under the restart storm"
    rate = DELAY_CELLS * DELAY_TICKS / dt
    return [(
        "lease_restart_storm",
        dt / (DELAY_CELLS * DELAY_TICKS) * 1e6,
        f"{DELAY_CELLS} cells x {DELAY_TICKS} ticks, delay<={depth} "
        f"drop=0.05 + rolling acceptor restarts (2 waves x "
        f"{tr.n_acceptors} acceptors) + 1 restart-counter bump/proposer: "
        f"{fmt(rate)} cell-ticks/s, "
        f"owned={float((owners >= 0).mean()):.2f}",
    )]


RENEW_CELLS, RENEW_TICKS = 1024, 384
RENEW_LEASE, RENEW_CADENCE, RENEW_DELAY = 96, 64, 4


def _renew_storm_trace():
    """The §6 steady state: every cell acquired at t=0 and then extended in
    synchronized waves every RENEW_CADENCE ticks forever. The cadence is
    window-aligned (64 = 4 x the engine's 16-tick windows) so the ticks
    between extend rounds are genuinely quiescent — the workload the
    kernel's stable-window fast path exists for. The cadence must sit
    inside [4·delay+1, lease): shorter overwrites the open extend round
    (netplane phase 3), longer lapses the lease mid-renewal."""
    from repro.lease_array.trace import Trace

    T, N = RENEW_TICKS, RENEW_CELLS
    att = np.full((T, N), -1, np.int32)
    ext = np.full((T, N), -1, np.int32)
    cells = np.arange(N, dtype=np.int32)
    att[0] = cells % 8
    for te in range(RENEW_CADENCE, T, RENEW_CADENCE):
        ext[te] = cells % 8
    return Trace(
        N, 5, 8, RENEW_LEASE,
        att, np.full((T, N), -1, np.int32), np.ones((T, 5), np.int32),
        delay=np.full((T, 5), RENEW_DELAY, np.int32),
        round_ticks=4 * RENEW_DELAY + 1, extends=ext,
    )


def run_renew():
    """The renewal-collapse fix, measured: owner extensions (§6, the
    extends plane) sustain ownership through many lease generations at
    delay ≤ 4 — the geometry that collapsed to owned_frac 0.05 before the
    extend plane existed — A/B'd with the quiescence fast path compiled
    out, plus a deposed-owner failover handoff driven through the shard
    directory at array scale."""
    tr = _renew_storm_trace()
    sc = tr.scenario()
    owners_ref, counts = replay_array(tr, netplane=True, backend="jnp")
    assert counts.max() <= 1, "§4 violated in the renewal storm"
    warm = 2 * RENEW_DELAY + 1  # first acquisition lands after one RTT
    owned = float((np.asarray(owners_ref)[warm:] >= 0).mean())
    assert owned >= 0.95, f"renewal collapse: owned_frac {owned}"

    rows, rates = [], {}
    for skip in (True, False):
        def replay(skip=skip):
            eng = LeaseArrayEngine(
                RENEW_CELLS, n_acceptors=5, n_proposers=8,
                lease_ticks=RENEW_LEASE, round_ticks=4 * RENEW_DELAY + 1,
                backend=_kernel_backend(), skip_stable=skip,
            )
            return eng.run_trace(sc, netplane=True)

        replay()  # warm the (skip_stable-keyed) jit cache
        dt, (owners, _) = timed(replay)
        assert np.array_equal(np.asarray(owners), np.asarray(owners_ref)), \
            "skip path must be bitwise invisible"
        rates[skip] = RENEW_CELLS * RENEW_TICKS / dt
        name = "lease_renewal_storm" + ("" if skip else "_noskip")
        what = (
            "quiescence skip on" if skip
            else f"skip compiled out (the skip row is "
            f"{rates[True] / rates[False]:.2f}x faster)"
        )
        rows.append((
            name,
            dt / (RENEW_CELLS * RENEW_TICKS) * 1e6,
            f"{RENEW_CELLS} cells x {RENEW_TICKS} ticks, extend waves every "
            f"{RENEW_CADENCE} ticks at delay<={RENEW_DELAY}, window kernel, "
            f"{what}: {fmt(rates[skip])} cell-ticks/s, "
            f"owned={owned:.2f} past the first acquisition",
        ))

    # deposed-owner handoff through the closed-loop shard directory: stall
    # one of 8 workers, retarget the rest, count ticks until its shards are
    # re-owned by peers (bench_failover.py's scenario at array scale)
    from repro.lease_array.directory import LeaseArrayDirectory

    state = {}

    def handoff():
        d = LeaseArrayDirectory(RENEW_CELLS, n_acceptors=5, lease_ticks=24,
                                max_workers=8, max_delay_ticks=2)
        for i in range(8):
            d.add_worker(i, RENEW_CELLS // 8)
        d.tick(40)
        assert d.coverage() == 1.0, "storm warmup failed to acquire"
        d.stall(0)
        for i in range(1, 8):
            d.set_target(i, RENEW_CELLS // 7 + 1)
        ticks = 0
        while (d.owned_count(0) > 0 or d.coverage() < 0.95) and ticks < 400:
            d.tick(1)
            ticks += 1
        assert d.owned_count(0) == 0 and d.coverage() >= 0.95
        state["ticks"] = ticks
        return ticks

    dt, _ = timed(handoff, reps=2)
    total = RENEW_CELLS * (40 + state["ticks"])
    rows.append((
        "lease_failover_handoff",
        dt / total * 1e6,
        f"{RENEW_CELLS} shards, 8 workers, delay<=2: a stalled owner's "
        f"{RENEW_CELLS // 8} shards lapse and are re-acquired by peers in "
        f"{state['ticks']} ticks ({fmt(total / dt)} cell-ticks/s through "
        f"the per-tick directory control loop)",
    ))
    return rows


def run_sweep():
    """The scenario-sweep driver: a stacked batch of fault scenarios in ONE
    dispatch (vmap inside, shard_map across devices), §4 verified."""
    from repro.lease_array import Scenario

    traces = [
        random_trace(
            s, n_ticks=SWEEP_TICKS, n_cells=SWEEP_CELLS,
            n_acceptors=3, n_proposers=4, lease_ticks=3,
            p_attempt=0.5, p_release=0.05, p_down_flip=0.05,
        )
        for s in range(SWEEP_SCENARIOS)
    ]
    stacked = Scenario.stack([t.scenario() for t in traces])
    eng = LeaseArrayEngine(SWEEP_CELLS, n_acceptors=3, n_proposers=4,
                           lease_ticks=3)
    eng.sweep(stacked)  # warm
    dt, res = timed(lambda: eng.sweep(stacked))
    assert int(res.max_owner_count.max()) <= 1
    total = SWEEP_SCENARIOS * SWEEP_CELLS * SWEEP_TICKS
    return [(
        "lease_sweep_batch",
        dt / total * 1e6,
        f"{SWEEP_SCENARIOS} scenarios x {SWEEP_CELLS} cells x "
        f"{SWEEP_TICKS} ticks in one dispatch: "
        f"{fmt(total / dt)} cell-ticks/s, "
        f"owned={float(res.owned_frac.mean()):.2f}",
    )]


def run_falsify():
    """Falsification-search throughput: one steady-state generation of the
    coverage-guided search — a margins-mode sweep over the whole
    population plus the host-side selection + mutation pass."""
    import numpy as np

    from repro.lease_array import Scenario
    from repro.lease_array.falsify import (
        FalsifyConfig, margin_score, mutate, random_population,
    )

    cfg = FalsifyConfig(pop_size=4096)
    eng = cfg.engine()
    rng = np.random.default_rng(0)
    space = cfg.mutation_space()
    planes = random_population(rng, cfg)

    def generation(planes):
        res = eng.sweep(
            Scenario(planes), collect="margins", verify=False,
        )
        scores = margin_score(res.margins)
        order = np.argsort(scores, kind="stable")
        elite = order[: cfg.pop_size // 4]
        parents = rng.choice(elite, size=cfg.pop_size - elite.size)
        children = {k: np.asarray(v)[parents] for k, v in planes.items()}
        children, _ = mutate(children, rng, space)
        return {
            k: np.concatenate([np.asarray(v)[elite], children[k]])
            for k, v in planes.items()
        }, res

    planes, _ = generation(planes)  # warm (compile) + first evolution
    dt, (planes, res) = timed(lambda: generation(planes))
    assert int(res.max_owner_count.max()) <= 1
    return [(
        "lease_falsify_throughput",
        dt / (cfg.pop_size * cfg.n_cells * cfg.n_ticks) * 1e6,
        f"{cfg.pop_size} scenarios/generation "
        f"({cfg.n_cells} cells x {cfg.n_ticks} ticks, margins+mutation): "
        f"{fmt(cfg.pop_size / dt)} scenarios/s",
    )]


JSON_PATH = Path(__file__).resolve().parent.parent / "BENCH_lease_array.json"


def _git_rev() -> str:
    cwd = Path(__file__).resolve().parent
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, cwd=cwd,
        ).stdout.strip() or "unknown"
        dirty = subprocess.run(
            ["git", "status", "--porcelain"],
            capture_output=True, text=True, timeout=10, cwd=cwd,
        ).stdout.strip()
        return f"{rev}+dirty" if dirty else rev
    except Exception:
        return "unknown"


def emit_json(path=JSON_PATH) -> dict:
    """Run every mode and write the machine-readable trajectory record:
    ``{"rows": [{"name", "us_per_cell_tick", "detail"}, ...], ...}`` —
    lower ``us_per_cell_tick`` is better; names are stable across PRs. The
    header stamps git rev, JAX backend, and device kind/count so the bench
    trajectory stays interpretable across machines and PRs."""
    import jax

    rows = (
        run() + run_delayed() + run_drift() + run_restart() + run_renew()
        + run_sweep() + run_falsify()
    )
    doc = {
        "benchmark": "lease_array",
        "git_rev": _git_rev(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "jax_backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
        "rows": [
            {"name": n, "us_per_cell_tick": round(us, 4), "detail": d}
            for n, us, d in rows
        ],
    }
    Path(path).write_text(json.dumps(doc, indent=2) + "\n")
    return doc


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = sys.argv[1] if len(sys.argv) > 1 else JSON_PATH
    doc = emit_json(out)
    for r in doc["rows"]:
        print(f'{r["name"]},{r["us_per_cell_tick"]:.2f},"{r["detail"]}"')
    print(f"wrote {out}")
