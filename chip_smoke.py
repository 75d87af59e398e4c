#!/usr/bin/env python3
"""Run the lease plane once on a TPU, through the entry points its users
call, at a deployment's size, and check what comes out.

    python chip_smoke.py             # one chip: fleet, referee, directory, sweep
    python chip_smoke.py --chips 4   # four chips: sharded fleet + sweep only

One chip:

  fleet      ``LeaseArrayEngine.run_trace`` of a seeded 1,048,576-cell x
             256-tick trace (A=5, P=8, delay <= 2, drop 0.05, open-loop
             renewals), bit-exact against the same engine on the jnp scan,
             with at most one owner in every cell-tick;
  referee    a fresh-seed 256-cell x 128-tick trace with delay, drop,
             drift, restarts and renewals, equal to the event-driven
             reference (``replay_event_sim``) in every cell-tick;
  directory  ``LeaseArrayDirectory`` over 262,144 shards and 32 workers:
             full coverage, then one worker stalls and its shards are
             owned again;
  sweep      ``LeaseArrayEngine.sweep`` of 1024 scenarios x 1024 cells x
             64 ticks with the §4 check on, 8 of them against the jnp scan.

``--chips 4`` runs the fleet replay and the sweep with the cell and batch
axes sharded over four chips and compares them with one-chip
``ops.lease_window_scan`` replays of the same planes.

Every phase runs the compiled window kernel: the platform picks it
(``ops.resolve_backend``), and the script refuses anything else. JAX's
first device must be a TPU, or the script exits non-zero before any phase.
Each phase prints one JSON line with its checks and its wall and compile
seconds; those are timings of one smoke run, not metrics. The last line of
standard output is ``{"ok": true, "device": {...}}``, printed only when
every phase passed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import numpy as np

#: the backend every phase must run
KERNEL = "pallas_tpu"

#: the lease-service geometry of the fleet, directory and sweep phases
A, P = 5, 8
LEASE_TICKS, MAX_DELAY = 24, 2
ROUND_TICKS = 4 * MAX_DELAY + 1  # a full prepare+propose round trip
FLEET = dict(
    n_acceptors=A, n_proposers=P, lease_ticks=LEASE_TICKS,
    round_ticks=ROUND_TICKS, max_delay_ticks=MAX_DELAY, p_drop=0.05,
    renew=0.4, p_attempt=0.02, p_release=0.002, p_down_flip=0.005,
)
#: every trace is made from this seed (the referee and sweep from the next)
SEED = 20260


def require_tpu(n_chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX's first device is {devices[0].platform!r}, "
            f"not a TPU; no phase was run"
        )
    if len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke: --chips {n_chips} needs {n_chips} TPU devices; "
            f"JAX sees {len(devices)}"
        )
    return devices


class CompileClock:
    """Seconds JAX's backend compiler runs, from JAX's own monitoring
    events (tracing and lowering are not counted)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def _engine(n_cells: int, **kw):
    from repro.lease_array import LeaseArrayEngine

    return LeaseArrayEngine(
        n_cells, n_acceptors=A, n_proposers=P, lease_ticks=LEASE_TICKS,
        round_ticks=ROUND_TICKS, **kw,
    )


def _assert_kernel(backend: str) -> None:
    if backend != KERNEL:
        raise AssertionError(f"ran backend {backend!r}, not {KERNEL!r}")


def kernel_memory(n_cells: int, n_ticks: int) -> dict:
    """``memory_analysis()`` of the delayed window kernel (with the §6
    extends stream the fleet uses) at block_n=512, window=16, and the
    VMEM the compiler scoped for it."""
    from repro.analysis.hlo import kernel_scoped_vmem
    from repro.lease_array.kernel import (
        delayed_kernel_args,
        lease_window_delayed_pallas,
    )

    def call(args, streams):
        return lease_window_delayed_pallas(
            *args, **streams, majority=A // 2 + 1,
            lease_q4=4 * LEASE_TICKS + 1, round_q4=4 * ROUND_TICKS,
            n_proposers=P, block_n=512, window=16, interpret=False,
        )

    compiled = jax.jit(call).lower(
        *delayed_kernel_args(A, n_cells, P, n_ticks, extend=True)
    ).compile()
    mem = compiled.memory_analysis()
    return {
        "shape": {"cells": n_cells, "ticks": n_ticks, "block_n": 512,
                  "window": 16},
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "generated_code_bytes": mem.generated_code_size_in_bytes,
        "scoped_vmem_bytes": kernel_scoped_vmem(compiled.as_text()),
    }


def fleet(n_cells: int, n_ticks: int, seed: int) -> dict:
    """run_trace on the kernel vs the same engine on the jnp scan."""
    from repro.lease_array.trace import random_trace

    t0 = time.perf_counter()
    sc = random_trace(
        seed, n_ticks=n_ticks, n_cells=n_cells, **FLEET
    ).scenario()
    gen_s = time.perf_counter() - t0
    eng = _engine(n_cells)
    _assert_kernel(eng.backend)
    t0 = time.perf_counter()
    owners, counts = eng.run_trace(sc)
    kernel_s = time.perf_counter() - t0
    oracle = _engine(n_cells, backend="jnp")
    t0 = time.perf_counter()
    ref_owners, ref_counts = oracle.run_trace(sc)
    oracle_s = time.perf_counter() - t0
    owner_mismatch = int(np.count_nonzero(owners != ref_owners))
    count_mismatch = int(np.count_nonzero(counts != ref_counts))
    max_count = int(counts.max())
    if owner_mismatch or count_mismatch or max_count > 1:
        raise AssertionError(
            f"fleet: {owner_mismatch} owner and {count_mismatch} count "
            f"mismatches against the jnp scan, max owner count {max_count}"
        )
    return {
        "cells": n_cells, "ticks": n_ticks, "backend": eng.backend,
        "owner_mismatches": owner_mismatch, "count_mismatches": count_mismatch,
        "max_owner_count": max_count,
        "owned_frac": float((owners >= 0).mean()),
        "extends_scheduled": int((np.asarray(sc.extends) >= 0).sum()),
        "smoke_seconds": {
            "trace_gen": gen_s, "kernel_run_trace": kernel_s,
            "jnp_run_trace": oracle_s,
        },
    }


def referee(n_cells: int, n_ticks: int, seed: int) -> dict:
    """The kernel vs the event-driven reference on a chaos trace."""
    from repro.lease_array.ops import resolve_backend
    from repro.lease_array.trace import (
        random_trace,
        replay_array,
        replay_event_sim,
    )

    _assert_kernel(resolve_backend())
    tr = random_trace(
        seed, n_ticks=n_ticks, n_cells=n_cells, n_acceptors=A,
        n_proposers=P, lease_ticks=12, p_attempt=0.12, p_release=0.04,
        p_down_flip=0.005, renew=0.5, max_delay_ticks=MAX_DELAY, p_drop=0.05,
        drift_eps=0.25, restarts=0.01, asymmetric=True,
        round_ticks=ROUND_TICKS,
    )
    if not (tr.delayed and tr.drifted and tr.restarted and tr.extended):
        raise AssertionError("referee trace lacks a fault dimension")
    owners, counts = replay_array(tr)
    ref = replay_event_sim(tr)
    mismatches = int(np.count_nonzero(owners != ref))
    if mismatches or counts.max() > 1:
        raise AssertionError(
            f"referee: {mismatches} cell-ticks differ from the event sim, "
            f"max owner count {int(counts.max())}"
        )
    return {
        "cells": n_cells, "ticks": n_ticks, "seed": seed,
        "mismatches": mismatches, "max_owner_count": int(counts.max()),
        "owned_frac": float((owners >= 0).mean()),
    }


def directory(n_shards: int, n_workers: int, max_ticks: int = 400) -> dict:
    """Warm up to full coverage, stall one worker, tick until its shards
    are owned by the others again."""
    from repro.lease_array.directory import LeaseArrayDirectory

    d = LeaseArrayDirectory(
        n_shards, n_acceptors=A, lease_ticks=LEASE_TICKS,
        max_workers=n_workers, max_delay_ticks=MAX_DELAY,
    )
    _assert_kernel(d.engine.backend)
    # targets leave room for the others to absorb one stalled worker
    target = -(-n_shards // (n_workers - 1))
    for w in range(n_workers):
        d.add_worker(w, target)
    max_count = 0

    def tick():
        nonlocal max_count
        owners = d.tick()
        max_count = max(max_count, int(d.engine.last_owner_count.max()))
        return owners

    t0 = time.perf_counter()
    warm = 0
    while d.coverage() < 1.0:
        if warm == max_ticks:
            raise AssertionError(f"directory: coverage {d.coverage()}")
        owners = tick()
        warm += 1
    warm_s = time.perf_counter() - t0
    slot = d.workers[0].slot
    orphans = np.flatnonzero(owners == slot)
    d.stall(0)
    t0 = time.perf_counter()
    failover = 0
    while True:
        owners = tick()
        failover += 1
        taken = owners[orphans]
        if (taken >= 0).all() and (taken != slot).all() \
                and d.coverage() >= 0.95:
            break
        if failover == max_ticks:
            raise AssertionError(
                f"directory: stalled shards not re-owned after {failover} "
                f"ticks (coverage {d.coverage()})"
            )
    if max_count > 1:
        raise AssertionError("directory: two owners of one shard")
    return {
        "shards": n_shards, "workers": n_workers,
        "backend": d.engine.backend, "warmup_ticks": warm,
        "stalled_shards": int(orphans.size), "failover_ticks": failover,
        "coverage": d.coverage(),
        "smoke_seconds": {"warmup": warm_s,
                          "failover": time.perf_counter() - t0},
    }


def _sweep_scenarios(n_scenarios: int, n_cells: int, n_ticks: int, seed: int):
    from repro.lease_array.trace import random_trace

    return [
        random_trace(
            seed + i, n_ticks=n_ticks, n_cells=n_cells, **FLEET
        ).scenario()
        for i in range(n_scenarios)
    ]


def sweep(n_scenarios: int, n_cells: int, n_ticks: int, seed: int) -> dict:
    """A summary sweep with §4 verification, 8 scenarios vs the jnp scan."""
    t0 = time.perf_counter()
    scenarios = _sweep_scenarios(n_scenarios, n_cells, n_ticks, seed)
    gen_s = time.perf_counter() - t0
    eng = _engine(n_cells)
    _assert_kernel(eng.backend)
    t0 = time.perf_counter()
    res = eng.sweep(scenarios, collect="summary", verify=True)
    sweep_s = time.perf_counter() - t0
    ref = eng.sweep(scenarios[:8], collect="summary", backend="jnp")
    for field in ("max_owner_count", "owned_frac", "final_owners"):
        got, want = getattr(res, field)[:8], getattr(ref, field)
        if not np.array_equal(got, want):
            raise AssertionError(f"sweep: {field} differs from the jnp scan")
    return {
        "scenarios": n_scenarios, "cells": n_cells, "ticks": n_ticks,
        "backend": eng.backend,
        "max_owner_count": int(res.max_owner_count.max()),
        "owned_frac_mean": float(res.owned_frac.mean()),
        "smoke_seconds": {"scenario_gen": gen_s, "sweep": sweep_s},
    }


def _one_device_replay(planes: dict, n_cells: int):
    """``ops.lease_window_scan`` of fresh state on JAX's first device."""
    from repro.lease_array.netplane import init_netplane
    from repro.lease_array.ops import lease_window_scan
    from repro.lease_array.state import init_state, lease_quarters

    dev = jax.devices()[0]
    put = lambda x: jax.device_put(x, dev)
    _, _, owners, counts = lease_window_scan(
        jax.tree.map(put, init_state(n_cells, A, P)),
        jax.tree.map(put, init_netplane(n_cells, A)), 0,
        {k: put(np.asarray(v)) for k, v in planes.items()},
        majority=A // 2 + 1, lease_q4=lease_quarters(LEASE_TICKS),
        round_q4=4 * ROUND_TICKS,
    )
    return np.asarray(owners), np.asarray(counts)


def _peak_bytes(devices) -> list[int]:
    """Each device's peak bytes in use so far (0 where not reported)."""
    return [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    ]


def sharded_fleet(n_cells: int, n_ticks: int, seed: int, n_chips: int) -> dict:
    """run_trace sharded over every chip vs a one-chip replay."""
    from repro.lease_array.trace import random_trace

    sc = random_trace(
        seed, n_ticks=n_ticks, n_cells=n_cells, **FLEET
    ).scenario()
    eng = _engine(n_cells)
    _assert_kernel(eng.backend)
    t0 = time.perf_counter()
    owners, counts = eng.run_trace(sc)
    run_s = time.perf_counter() - t0
    spans = {
        name: len(leaf.sharding.device_set)
        for name, leaf in zip(eng.state._fields, eng.state)
    }
    if min(spans.values()) != n_chips:
        raise AssertionError(f"fleet state spans {spans} devices")
    ref_owners, ref_counts = _one_device_replay(sc.planes, n_cells)
    mismatch = int(np.count_nonzero(owners != ref_owners)) + int(
        np.count_nonzero(counts != ref_counts)
    )
    if mismatch or counts.max() > 1:
        raise AssertionError(
            f"sharded fleet: {mismatch} mismatches vs one chip, max owner "
            f"count {int(counts.max())}"
        )
    return {
        "cells": n_cells, "ticks": n_ticks, "chips": n_chips,
        "state_devices": min(spans.values()), "mismatches": mismatch,
        "max_owner_count": int(counts.max()),
        "smoke_seconds": {"sharded_run_trace": run_s},
    }


def sharded_sweep(
    n_scenarios: int, n_cells: int, n_ticks: int, seed: int, n_chips: int,
) -> dict:
    """A sweep whose batch is split over every chip (the batch size is
    uneven on purpose, so the split pads), 8 scenarios vs one-chip
    replays. Must run before anything else touches the chips: each chip's
    peak memory shows it held its share of the batch."""
    scenarios = _sweep_scenarios(n_scenarios, n_cells, n_ticks, seed)
    # the attempts and releases planes alone are a lower bound on what
    # each chip must hold of its share of the batch
    share = (n_scenarios // n_chips) * sum(
        scenarios[0].planes[k].nbytes for k in ("attempts", "releases")
    )
    eng = _engine(n_cells)
    _assert_kernel(eng.backend)
    t0 = time.perf_counter()
    res = eng.sweep(scenarios, collect="owners", verify=True)
    sweep_s = time.perf_counter() - t0
    peaks = _peak_bytes(jax.devices()[:n_chips])
    if min(peaks) < share:
        raise AssertionError(
            f"sweep: a chip peaked below its batch share ({peaks} < {share})"
        )
    for i in range(8):
        ref_owners, ref_counts = _one_device_replay(
            scenarios[i].planes, n_cells
        )
        if not (
            np.array_equal(res.owners[i], ref_owners)
            and np.array_equal(res.counts[i], ref_counts)
        ):
            raise AssertionError(f"sharded sweep: scenario {i} differs")
    return {
        "scenarios": n_scenarios, "cells": n_cells, "ticks": n_ticks,
        "chips": n_chips, "peak_bytes_per_chip": peaks,
        "batch_bytes_per_chip": share,
        "max_owner_count": int(res.max_owner_count.max()),
        "smoke_seconds": {"sharded_sweep": sweep_s},
    }


def run_phases(phases, clock: CompileClock) -> bool:
    ok = True
    for name, fn in phases:
        c0, t0 = clock.seconds, time.perf_counter()
        try:
            info = fn()
        except Exception:
            ok = False
            traceback.print_exc()
            print(json.dumps({"phase": name, "passed": False}), flush=True)
            continue
        info["smoke_timing_not_a_metric"] = {
            "wall_s": time.perf_counter() - t0,
            "compile_s": clock.seconds - c0,
        }
        print(json.dumps({"phase": name, "passed": True, **info}), flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: only the fleet replay and sweep, sharded over four chips",
    )
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(json.dumps({"device": device, "compile_cache": cache}), flush=True)
    clock = CompileClock()
    s = SEED
    if args.chips == 1:
        phases = [
            ("kernel_memory", lambda: kernel_memory(1 << 20, 256)),
            ("fleet", lambda: fleet(1 << 20, 256, s)),
            ("referee", lambda: referee(256, 128, s + 1)),
            ("directory", lambda: directory(262_144, 32)),
            ("sweep", lambda: sweep(1024, 1024, 64, s + 2)),
        ]
    else:
        phases = [
            ("sharded_sweep",
             lambda: sharded_sweep(1022, 1024, 64, s + 2, args.chips)),
            ("sharded_fleet",
             lambda: sharded_fleet(1 << 20, 256, s, args.chips)),
        ]
    if not run_phases(phases, clock):
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
