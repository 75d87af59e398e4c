"""Static audit of the window-kernel launch geometry.

The Pallas grid iterates ``(cell_block, window)`` with the window axis
minor; correctness of the whole time-resident design rests on three
properties of the BlockSpecs that nothing at runtime checks:

  - **bounds**: every block an index map selects lies inside its logical
    array (Pallas silently clamps out-of-range blocks, which would alias
    the last block instead of failing);
  - **write-race**: output index maps must partition the cell axis —
    two grid instances may write the same output region only if they are
    the same cell block revisited across *windows* (that revisit is the
    point: the block stays VMEM-resident, serialized by the minor axis).
    Any same-region write from two different cell blocks is a data race;
  - **coverage**: the union of written regions must tile each output
    exactly, or part of the result is whatever XLA left in the buffer;
  - **VMEM residency**: one grid step's working set (every block of every
    spec) must fit the per-core VMEM budget, and must not undercut the
    analytic accounting in ``analysis/roofline.py`` — if the plan counts
    fewer resident bytes than the roofline model, a state plane fell out
    of the plan and the two descriptions have drifted.

The checker consumes the same :class:`repro.lease_array.kernel.LaunchPlan`
the ``pallas_call`` entry points run, so there is no second description of
the launch to keep in sync.

Block index maps return *block* indices (units of one block shape), so
regions are aligned tiles: two blocks of the same spec either coincide
exactly or are disjoint — partial overlap cannot happen, which keeps the
race check exact rather than approximate.
"""
from __future__ import annotations

import math

from .findings import Finding

#: conservative per-core VMEM floor (v4-class TensorCore); newer parts have
#: more, but a plan that fits here fits everywhere we run
VMEM_BUDGET_BYTES = 16 * 2**20

#: refuse to enumerate absurd grids instead of silently sampling
_MAX_GRID_POINTS = 1 << 16

_BYTES = 4  # everything in the lease plane is int32


def _block_shape(spec):
    """Concrete block shape with squeezed (None) dims as 1, or None for
    memory-space-only specs (the SMEM scan scalars)."""
    bs = getattr(spec, "block_shape", None)
    if bs is None:
        return None
    return tuple(1 if d is None else int(d) for d in bs)


def _grid_points(grid):
    pts = []
    for i in range(grid[0]):
        for w in range(grid[1]):
            pts.append((i, w))
    return pts


def check_launch_plan(
    plan,
    *,
    delayed: bool,
    n_acceptors: int = 5,
    n_proposers: int = 8,
    vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
    what: str = "window kernel",
) -> list[Finding]:
    """Audit one :class:`LaunchPlan`. Pure host-side arithmetic — nothing
    is traced or executed."""
    findings: list[Finding] = []
    grid = tuple(int(g) for g in plan.grid)
    if math.prod(grid) > _MAX_GRID_POINTS:
        return [Finding(
            "launch", "grid-too-large", what,
            f"grid {grid} has {math.prod(grid)} instances, beyond the "
            f"{_MAX_GRID_POINTS} the checker will enumerate; shrink the "
            f"audit geometry (the rules are geometry-independent)",
        )]
    pts = _grid_points(grid)
    vmem = 0

    def audit(kind, specs, shapes):
        nonlocal vmem
        for k, (spec, shape) in enumerate(zip(specs, shapes)):
            where = f"{what} {kind}[{k}]"
            bs = _block_shape(spec)
            if bs is None:  # SMEM scalar vector: no tiling to audit
                continue
            vmem += _BYTES * math.prod(bs)
            if len(bs) != len(shape):
                findings.append(Finding(
                    "launch", "rank-mismatch", where,
                    f"block shape {bs} has rank {len(bs)} but the array "
                    f"is {shape}",
                ))
                continue
            index_map = spec.index_map
            regions: dict[tuple, tuple] = {}  # region -> first grid point
            for pt in pts:
                try:
                    idx = tuple(int(x) for x in index_map(*pt))
                except Exception as e:  # arity/typing bug in the map
                    findings.append(Finding(
                        "launch", "index-map-error", where,
                        f"index map failed at grid point {pt}: {e!r}",
                    ))
                    regions = {}
                    break
                if len(idx) != len(bs):
                    findings.append(Finding(
                        "launch", "index-map-error", where,
                        f"index map returned {len(idx)} coords for a "
                        f"rank-{len(bs)} block at grid point {pt}",
                    ))
                    regions = {}
                    break
                for d, (b, n, j) in enumerate(zip(bs, shape, idx)):
                    if j < 0 or (j + 1) * b > n:
                        findings.append(Finding(
                            "launch", "block-out-of-bounds", where,
                            f"grid point {pt} selects block {idx}: axis "
                            f"{d} spans [{j * b}, {(j + 1) * b}) outside "
                            f"the array extent {n}",
                        ))
                if kind == "out":
                    prev = regions.get(idx)
                    if prev is None:
                        regions[idx] = pt
                    elif prev[0] != pt[0]:
                        findings.append(Finding(
                            "launch", "write-race", where,
                            f"grid points {prev} and {pt} (different cell "
                            f"blocks) both write block {idx}; output index "
                            f"maps must partition the cell axis — only "
                            f"window-axis revisits of the SAME cell block "
                            f"are race-free",
                        ))
            if kind == "out" and regions:
                covered = len(regions) * math.prod(bs)
                total = math.prod(shape)
                if covered < total:
                    findings.append(Finding(
                        "launch", "incomplete-coverage", where,
                        f"written blocks cover {covered} of {total} "
                        f"elements of {shape}; the rest is uninitialized "
                        f"output",
                    ))

    audit("in", plan.in_specs, plan.in_shapes)
    audit("out", plan.out_specs, plan.out_shapes)

    # -- VMEM residency -----------------------------------------------------
    if vmem > vmem_budget_bytes:
        findings.append(Finding(
            "launch", "vmem-budget", what,
            f"one grid step holds {vmem} bytes of blocks, over the "
            f"{vmem_budget_bytes}-byte VMEM budget; shrink block_n or "
            f"window",
        ))
    try:
        from ..roofline import lease_plane_roofline

        n_cells = plan.block_n * grid[0]
        analytic = lease_plane_roofline(
            n_cells, n_acceptors, n_proposers,
            delayed=delayed, window=plan.tw, block_n=plan.block_n,
        )["vmem_bytes_at_window"]
        if vmem < analytic:
            findings.append(Finding(
                "launch", "vmem-accounting", what,
                f"plan blocks sum to {vmem} bytes but the roofline model "
                f"expects at least {analytic} resident; a state plane has "
                f"fallen out of the launch plan",
            ))
    except Exception as e:  # roofline import/shape drift is itself a finding
        findings.append(Finding(
            "launch", "vmem-accounting", what,
            f"could not cross-check against analysis/roofline.py: {e!r}",
        ))
    return findings


def check_window_launches(
    n_cells: int = 4096,
    n_acceptors: int = 5,
    n_proposers: int = 8,
    n_ticks: int = 64,
    *,
    block_n: int = 512,
    window: int = 16,
) -> list[Finding]:
    """Audit both shipped window kernels at a representative geometry,
    the delayed one in its extend and placed launches too."""
    from ...lease_array.kernel import delayed_launch_plan, sync_launch_plan

    A, P = n_acceptors, n_proposers
    findings = check_launch_plan(
        sync_launch_plan(A, n_cells, P, n_ticks,
                         block_n=block_n, window=window),
        delayed=False, n_acceptors=A, n_proposers=P,
        what="lease_window_sync_pallas",
    )
    findings += check_launch_plan(
        delayed_launch_plan(A, n_cells, P, n_ticks,
                            block_n=block_n, window=window),
        delayed=True, n_acceptors=A, n_proposers=P,
        what="lease_window_delayed_pallas",
    )
    findings += check_launch_plan(
        delayed_launch_plan(A, n_cells, P, n_ticks,
                            block_n=block_n, window=window, extend=True),
        delayed=True, n_acceptors=A, n_proposers=P,
        what="lease_window_delayed_pallas[extend]",
    )
    # a placement's per-cell restart table (A == P: slot k is acceptor k
    # and proposer k), with the extends stream of the placed cells
    findings += check_launch_plan(
        delayed_launch_plan(A, n_cells, A, n_ticks, block_n=block_n,
                            window=window, extend=True, restart=True,
                            placed=True),
        delayed=True, n_acceptors=A, n_proposers=A,
        what="lease_window_delayed_pallas[placed]",
    )
    return findings
