"""Plant a fault under a cell's timed path and read what its check compares.

    python3 -m bench.faults --workload <cell> --seconds <s> --seed <n> --faults <f> [<f> ...]

Each fault breaks the program where it produces its output, and one run
of the cell as the benchmark makes it (with a short window) prints the
numbers its check compared; every one must come out not correct. The
benchmark's own runs never run this; ``tests/bench_harness`` runs it at
tiny sizes on the CPU.

Faults in the tick function that the window kernel and the scan run:

- ``unchanged``: every tick returns its state unchanged;
- ``half``: the second half of the cells (of each block, in the kernel)
  is left out of every tick;
- ``altered``: the first cell's owner (of each block) is altered where
  the tick produces it, on every tick from tick 9 on that the tick
  function runs (the kernel skips it where a block is quiescent).

Faults in the directory's policy, where it makes the planes of its tick
(the owners then agree with a replay of those planes, so only the
comparison with the reference's policy sees them):

- ``no_renewals``: the extends the policy issued are left out;
- ``wrong_worker``: every attempt names the next worker.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys

import numpy as np

from bench.run import ROOT, run_cell

TICK_FAULTS = ("unchanged", "half", "altered")
POLICY_FAULTS = ("no_renewals", "wrong_worker")


def broken_tick(fault: str, tick_math):
    import jax
    import jax.numpy as jnp

    def tick(lease, net, t, *args, **kw):
        new_lease, new_net, count = tick_math(lease, net, t, *args, **kw)
        own = new_lease[2]
        cell = jax.lax.broadcasted_iota(jnp.int32, (1, own.shape[-1]), 1)
        if fault == "unchanged":
            return lease, net, (lease[3] > 0).astype(jnp.int32)
        if fault == "half":
            keep = cell < own.shape[-1] // 2
            pick = lambda new, old: jnp.where(keep, new, old)  # noqa: E731
            return (
                tuple(map(pick, new_lease, lease)),
                tuple(map(pick, new_net, net)),
                jnp.where(keep, count, (lease[3] > 0).astype(jnp.int32)),
            )
        hit = (cell == 0) & (t >= 9)
        own = jnp.where(hit, jnp.where(own >= 0, -1, 0), own)
        return (*new_lease[:2], own, new_lease[3]), new_net, count

    return tick


def broken_policy(fault: str, make_tick):
    def tick(**planes):
        if fault == "no_renewals":
            planes["extends"] = np.full_like(planes["extends"], -1)
        else:
            a = planes["attempts"]
            planes["attempts"] = np.where(
                a >= 0, (a + 1) % planes["n_proposers"], a
            )
        return make_tick(**planes)

    return tick


def clear_programs() -> None:
    import jax

    from repro.lease_array import engine

    for fn in (engine._scenario_scanner, engine._trace_fn, engine._sweep_fn):
        fn.cache_clear()
    jax.clear_caches()


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` planted, for the length of the block."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.lease_array import directory, kernel, ops

    if fault in TICK_FAULTS:
        targets = [(ops, "delayed_tick_math", broken_tick),
                   (kernel, "delayed_tick_math", broken_tick)]
    elif fault in POLICY_FAULTS:
        targets = [(directory, "make_tick", broken_policy)]
    else:
        raise ValueError(f"no fault named {fault!r}")
    saved = [(mod, name, getattr(mod, name)) for mod, name, _ in targets]
    for mod, name, wrap in targets:
        setattr(mod, name, wrap(fault, getattr(mod, name)))
    clear_programs()
    try:
        yield
    finally:
        for mod, name, original in saved:
            setattr(mod, name, original)
        clear_programs()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--faults", nargs="+", required=True)
    args = ap.parse_args(argv)
    for fault in args.faults:
        with planted(fault):
            r = run_cell(args.workload, args.seed, args.seconds, False)
        print(json.dumps({
            "workload": args.workload, "seed": args.seed, "fault": fault,
            "correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"], "checks": r["checks"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
