"""Purity/dtype lint over traced jaxprs: the tick cores (and the window
kernels wrapping them) must stay pure int32 VPU work.

Three rules, each today enforced only by convention:

  - ``float-op``: the §4 safety argument is exact integer arithmetic; a
    float creeping into the tick core (a stray ``/``, a float literal)
    breaks bit-for-bit backend agreement and the interval proof alike.
  - ``int64-promotion``: a silent widen (Python int literal over 2^31,
    ``jnp.sum`` with a promoted accumulator) would make the packed layout
    *look* safe while the int32 kernels still wrap.
  - ``gather-in-pallas``: the Pallas backend path must resolve per-leg
    link rows with the compile-time P-loop (``netplane.legs_select`` /
    ``state.clock_select``), never a dynamic gather — gather indices
    materializing in HBM is exactly what the fused kernel exists to avoid
    (the ``legs_select`` vs ``legs_gather`` rule).

The walk recurses into every sub-jaxpr (pjit, scan/fori_loop bodies,
``pallas_call`` kernels), so tracing ``lease_window_*_pallas`` checks the
code that actually runs inside the kernel.
"""
from __future__ import annotations

import numpy as np

from .findings import Finding

#: primitives that materialize dynamic indices (the Pallas-path ban)
GATHER_PRIMS = frozenset({
    "gather", "scatter", "scatter-add", "dynamic_slice", "dynamic_gather",
    "dynamic_update_slice",
})

_WIDE_INTS = (np.int64, np.uint64)


def _walk(jaxpr, visit, path=""):
    for i, eqn in enumerate(jaxpr.eqns):
        where = f"{path}eqn {i} `{eqn.primitive.name}`"
        visit(eqn, where)
        for name, p in eqn.params.items():
            subs = p if isinstance(p, (list, tuple)) else (p,)
            for s in subs:
                inner = getattr(s, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    _walk(inner, visit, f"{where}/{name}/")
                elif hasattr(s, "eqns"):
                    _walk(s, visit, f"{where}/{name}/")


def check_jaxpr_purity(
    closed, *, pallas_path: bool = False, what: str = "tick core",
) -> list[Finding]:
    """Lint one (closed) jaxpr. ``pallas_path=True`` additionally bans
    gather-family primitives (the block-local select rule)."""
    findings: list[Finding] = []
    seen: set[tuple] = set()

    def visit(eqn, where):
        prim = eqn.primitive.name
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is None:
                continue
            if np.issubdtype(dt, np.floating) or np.issubdtype(dt, np.complexfloating):
                key = ("float", prim)
                if key not in seen:
                    seen.add(key)
                    findings.append(Finding(
                        "purity", "float-op", where,
                        f"{what} produces a {dt} value via `{prim}`; the "
                        f"packed tick math must be exact int32",
                    ))
            elif dt.type in _WIDE_INTS:
                key = ("int64", prim)
                if key not in seen:
                    seen.add(key)
                    findings.append(Finding(
                        "purity", "int64-promotion", where,
                        f"{what} silently promotes to {dt} via `{prim}`; "
                        f"the int32 kernels would wrap where this widened",
                    ))
        if pallas_path and prim in GATHER_PRIMS:
            key = ("gather", prim, where)
            if key not in seen:
                seen.add(key)
                findings.append(Finding(
                    "purity", "gather-in-pallas", where,
                    f"`{prim}` reaches the Pallas backend path of {what}; "
                    f"use the compile-time P-loop selects "
                    f"(netplane.legs_select / state.clock_select) instead",
                ))

    _walk(closed.jaxpr, visit)
    return findings


def check_tick_cores(
    n_proposers: int = 8,
    n_acceptors: int = 5,
    lease_q4: int = 13,
    round_q4: int = 4,
    guard_q4: int = 13,
) -> list[Finding]:
    """Lint the real tick cores on both leg strategies:

    - sync core and the delayed core with ``legs_select`` must pass the
      full Pallas-path rules (these are the bodies the window kernels run);
    - the delayed core with ``legs_gather`` is the XLA-only oracle, where
      gather is allowed by design — but floats/int64 still aren't.
    """
    from .intervals import trace_tick_core

    majority = n_acceptors // 2 + 1
    args = (n_proposers, n_acceptors, lease_q4, round_q4, guard_q4, majority)
    findings = check_jaxpr_purity(
        trace_tick_core(*args, sync=True),
        pallas_path=True, what="sync_tick_math",
    )
    findings += check_jaxpr_purity(
        trace_tick_core(*args, sync=False, legs="select"),
        pallas_path=True, what="delayed_tick_math[legs_select]",
    )
    findings += check_jaxpr_purity(
        trace_tick_core(*args, sync=False, legs="gather"),
        pallas_path=False, what="delayed_tick_math[legs_gather]",
    )
    # the corruption-plane variants (falsifier negative controls) run the
    # same backends, so they obey the same rules
    findings += check_jaxpr_purity(
        trace_tick_core(*args, sync=False, legs="select", corrupt=True),
        pallas_path=True, what="delayed_tick_math[legs_select,corrupt]",
    )
    findings += check_jaxpr_purity(
        trace_tick_core(*args, sync=False, legs="gather", corrupt=True),
        pallas_path=False, what="delayed_tick_math[legs_gather,corrupt]",
    )
    # the §6 extend variant runs the same backends: same rules
    findings += check_jaxpr_purity(
        trace_tick_core(*args, sync=False, legs="select", extend=True),
        pallas_path=True, what="delayed_tick_math[legs_select,extend]",
    )
    findings += check_jaxpr_purity(
        trace_tick_core(*args, sync=False, legs="gather", extend=True),
        pallas_path=False, what="delayed_tick_math[legs_gather,extend]",
    )
    return findings


def check_window_kernels(
    n_cells: int = 1024,
    n_acceptors: int = 5,
    n_proposers: int = 8,
    n_ticks: int = 32,
    *,
    block_n: int = 512,
    window: int = 16,
) -> list[Finding]:
    """Trace the whole ``lease_window_{sync,delayed}_pallas`` entry points
    (shapes only — nothing executes) and lint everything inside the
    ``pallas_call``, fori_loop bodies included, under the Pallas rules."""
    import jax
    import jax.numpy as jnp

    from ...lease_array.kernel import (
        lease_window_delayed_pallas,
        lease_window_sync_pallas,
    )
    from ...lease_array.netplane import NetPlaneState, init_netplane
    from ...lease_array.state import PackedLeaseState, init_state, pack_state

    A, P, N, T = n_acceptors, n_proposers, n_cells, n_ticks
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    packed = PackedLeaseState(
        *(sds(a.shape, i32) for a in pack_state(init_state(N, A, P)))
    )
    packed_a = PackedLeaseState(  # A == P, as a placement asks
        *(sds(a.shape, i32) for a in pack_state(init_state(N, A, A)))
    )
    net = NetPlaneState(*(sds(a.shape, i32) for a in init_netplane(N, A)))
    t0 = sds((), i32)
    planes = dict(
        attempts=sds((T, N), i32), releases=sds((T, N), i32),
        acc_up=sds((T, A), i32), pclk=sds((T, P), i32),
        aclk=sds((T, A), i32),
    )
    kw = dict(majority=A // 2 + 1, lease_q4=13, n_proposers=P,
              block_n=block_n, window=window, interpret=True)

    sync_jaxpr = jax.make_jaxpr(
        lambda p, t, a, r, u, pc, ac: lease_window_sync_pallas(
            p, t, a, r, u, pc, ac, **kw
        )
    )(packed, t0, *planes.values())
    delayed_jaxpr = jax.make_jaxpr(
        lambda p, n, t, a, r, u, pc, ac, lk: lease_window_delayed_pallas(
            p, n, t, a, r, u, pc, ac, lk, round_q4=4, **kw
        )
    )(packed, net, t0, *planes.values(), sds((T, P, A), i32))

    corrupt_jaxpr = jax.make_jaxpr(
        lambda p, n, t, a, r, u, pc, ac, lk, st, eq:
        lease_window_delayed_pallas(
            p, n, t, a, r, u, pc, ac, lk, round_q4=4, stale=st, equiv=eq,
            **kw
        )
    )(packed, net, t0, *planes.values(), sds((T, P, A), i32),
      sds((T, A), i32), sds((T, A), i32))

    extend_jaxpr = jax.make_jaxpr(
        lambda p, n, t, a, r, u, pc, ac, lk, ex:
        lease_window_delayed_pallas(
            p, n, t, a, r, u, pc, ac, lk, round_q4=4, extends=ex, **kw
        )
    )(packed, net, t0, *planes.values(), sds((T, P, A), i32),
      sds((T, N), i32))

    from ...lease_array.netplane import RT_FIELDS

    placed_kw = dict(kw, n_proposers=A)
    placed_jaxpr = jax.make_jaxpr(
        lambda p, n, t, a, r, u, pc, ac, lk, ex, tab:
        lease_window_delayed_pallas(
            p, n, t, a, r, u, pc, ac, lk, round_q4=4, extends=ex,
            restart_table=tab, deaf_q4=13, **placed_kw
        )
    )(packed_a, net, t0, planes["attempts"], planes["releases"],
      planes["acc_up"], sds((T, A), i32), planes["aclk"], sds((T, A, A), i32),
      sds((T, N), i32), sds((RT_FIELDS, A, N), i32))

    findings = check_jaxpr_purity(
        sync_jaxpr, pallas_path=True, what="lease_window_sync_pallas"
    )
    findings += check_jaxpr_purity(
        placed_jaxpr, pallas_path=True,
        what="lease_window_delayed_pallas[placed]",
    )
    findings += check_jaxpr_purity(
        delayed_jaxpr, pallas_path=True, what="lease_window_delayed_pallas"
    )
    findings += check_jaxpr_purity(
        corrupt_jaxpr, pallas_path=True,
        what="lease_window_delayed_pallas[corrupt]",
    )
    findings += check_jaxpr_purity(
        extend_jaxpr, pallas_path=True,
        what="lease_window_delayed_pallas[extend]",
    )
    return findings


def check_honest_strip(
    n_cells: int = 16,
    n_acceptors: int = 3,
    n_proposers: int = 4,
    n_ticks: int = 4,
) -> list[Finding]:
    """The all-default ``extends`` plane (and the corruption/restart
    planes with it) must leave the honest dispatch jaxpr BYTE-IDENTICAL
    to one that never mentioned the plane: ``ops.strip_default_planes``
    is the host-side gate ``lease_window_scan`` applies before its jit,
    so honest replays never compile (or cache-miss on) the fault
    variants. A placement whose node rows place no restart goes with
    them, to the same honest jaxpr. Traces the real impl both ways and
    diffs the jaxprs."""
    import jax
    import numpy as np

    from ...lease_array import ops
    from ...lease_array.netplane import init_netplane
    from ...lease_array.scenario import PLANES, Scenario
    from ...lease_array.state import init_state

    A, P, N, T = n_acceptors, n_proposers, n_cells, n_ticks
    honest = Scenario.build(
        T, n_cells=N, n_acceptors=A, n_proposers=P,
        delay=np.ones((T, A), np.int32),  # delayed model: extends' home
    )
    planes = dict(honest.planes)
    assert (np.asarray(planes["extends"]) == PLANES["extends"].default).all()
    without = ops.strip_default_planes(
        {k: v for k, v in planes.items() if k != "extends"}
    )
    stripped = ops.strip_default_planes(planes)

    state = init_state(N, A, P)
    net = init_netplane(N, A)
    kw = dict(majority=A // 2 + 1, lease_q4=13, round_q4=8, guard_q4=13,
              backend="jnp", sync=False, block_n=8, window=2,
              restart_guard=True, skip_stable=True)

    def jaxpr_of(pl):
        return str(jax.make_jaxpr(
            lambda s, n_, t, p: ops._window_scan_impl(
                s, n_, t, None, None, p, **kw
            )
        )(state, net, np.int32(0), pl))

    findings: list[Finding] = []
    if "extends" in stripped:
        findings.append(Finding(
            "purity", "honest-strip", "ops.strip_default_planes",
            "an all-default extends plane survived the host-side strip; "
            "every honest replay would compile the extend variant",
        ))
    elif jaxpr_of(stripped) != jaxpr_of(without):
        findings.append(Finding(
            "purity", "honest-strip", "ops._window_scan_impl",
            "the honest dispatch jaxpr with a stripped all-default "
            "extends plane differs from one traced without the plane — "
            "the strip no longer restores the honest computation",
        ))
    # a placement with all-zero node rows places no restart: it goes with
    # them, and the dispatch is the honest one (A == P for a placement)
    placed = Scenario.build(
        T, n_cells=N, n_acceptors=A, n_proposers=A, n_nodes=A + 2,
        placement=(np.arange(A)[:, None] + np.arange(N)[None, :]) % (A + 2),
        delay=np.ones((T, A), np.int32),
    )
    kept = ops.strip_default_planes(dict(placed.planes))
    if set(kept) & {"placement", "acc_restart", "prop_restart"}:
        findings.append(Finding(
            "purity", "honest-strip", "ops.strip_default_planes",
            "a placement without restarts survived the host-side strip; "
            "every such replay would compile the placed variant",
        ))
    else:
        state_a, net_a = init_state(N, A, A), init_netplane(N, A)
        honest_a = ops.strip_default_planes(dict(Scenario.build(
            T, n_cells=N, n_acceptors=A, n_proposers=A,
            delay=np.ones((T, A), np.int32),
        ).planes))
        trace = lambda pl: str(jax.make_jaxpr(
            lambda s, n_, t, p: ops._window_scan_impl(
                s, n_, t, None, None, p, **kw
            )
        )(state_a, net_a, np.int32(0), pl))
        if trace(kept) != trace(honest_a):
            findings.append(Finding(
                "purity", "honest-strip", "ops._window_scan_impl",
                "a stripped placement without restarts leaves a dispatch "
                "jaxpr that differs from the honest one",
            ))
    return findings
