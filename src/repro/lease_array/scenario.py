"""Scenario plane: one declarative pytree for every fault dimension.

The paper's failure model (§1) is open-ended — "messages may be delayed,
reordered, lost, and nodes may crash and restart" — so the engine API must
not grow one positional argument per failure dimension. A ``Scenario`` is
a *registry-driven* bundle of named planes, each a dense array with a
leading tick axis:

  attempts  [T, N]     proposer id attempting each cell (-1 = none)
  releases  [T, N]     proposer id releasing each cell (-1 = none)
  acc_up    [T, A]     acceptor reachability (1 = reachable)
  delay     [T, P, A]  per-(proposer, acceptor) link delay in whole ticks
  drop      [T, P, A]  per-(proposer, acceptor) link loss mask
  prop_rate [T, P]     proposer local-clock step (local quarter-ticks/tick)
  acc_rate  [T, A]     acceptor local-clock step (local quarter-ticks/tick)
  placement [A, N]     optional: node id of each replica slot of each cell

A ``placement`` (the one plane without a tick axis, and the one a
scenario may leave out) puts the replicas of each cell on nodes of a
cluster of ``K`` nodes: slot ``k`` of cell ``n`` — its acceptor ``k`` and
its proposer ``k`` (``A == P``) — lives on node ``placement[k, n]``, and
the crash/restart planes become node rows, ``[T, K]``: a restart of node
``j`` restarts the replica of every cell placed on ``j`` (docs/restarts.md).

``delay``/``drop`` are *asymmetric link matrices*: every message leg sent
at tick ``t`` on the link between proposer ``p`` and acceptor ``a`` —
request or response, either direction — takes ``delay[t, p, a]`` ticks
and is lost iff ``drop[t, p, a]``. The symmetric per-acceptor ``[T, A]``
schedules of earlier revisions are the P-broadcast special case and are
accepted everywhere a plane is (see each spec's ``alts``).

``prop_rate``/``acc_rate`` are the §4 clock-drift planes — the first
planes added through ``register_plane`` after the registry shipped (the
worked example in docs/scenario_api.md): each node's local clock advances
by its rate-plane entry in *local quarter-ticks per global tick*
(``state.DEFAULT_RATE`` = 4 = a drift-free rate-1.0 clock; 3 and 5 bound
ε = 0.25). Node-side deadlines — acceptor lease timers, the proposer's
guarded own timer, round-abandon horizons — are minted and compared in
each node's accumulated local time; message deliver-ats stay global (the
network has no clock). Rates are validated ≥ 1 (``min_value``): a rate-0
clock would freeze every timer it owns.

Adding a failure dimension (restart planes, clock-rate planes, …) is now
"register a plane": ``register_plane`` extends the schema, ``Scenario``
defaults/validates/slices it, and the scan machinery carries it without
any signature change (see docs/scenario_api.md).

Both ``Scenario`` and its per-tick slice ``TickInputs`` are registered
JAX pytrees: they flow through ``jax.jit``/``jax.lax.scan`` unchanged and
batch with ``jax.vmap`` over a ``Scenario.stack`` of stacked scenarios.
"""
from __future__ import annotations

from typing import Iterable, NamedTuple, Optional

import jax
import numpy as np

from .state import DEFAULT_RATE, MAX_RESTARTS, NO_PROPOSER

__all__ = [
    "PlaneSpec",
    "PLANES",
    "LAYOUT_PLANES",
    "CORRUPTION_PLANES",
    "RESTART_PLANES",
    "EXTEND_PLANES",
    "register_plane",
    "plane_table_md",
    "plane_digest",
    "Scenario",
    "TickInputs",
    "make_tick",
    "check_bounds",
    "check_placed",
    "PLACED_SHARED",
]


class PlaneSpec(NamedTuple):
    """Schema of one scenario plane (shapes are per tick, sans the T axis)."""

    name: str
    dims: tuple[str, ...]  # per-tick dims, of {"N", "A", "P", "K"}
    default: int           # fill value when the plane is omitted
    doc: str = ""
    #: alternate per-tick shapes accepted from callers; missing axes are
    #: broadcast (e.g. delay's ("A",): a symmetric [T, A] plane is expanded
    #: to [T, P, A] by repeating it for every proposer)
    alts: tuple[tuple[str, ...], ...] = ()
    #: validated as proposer-id rows (-1 sentinel .. n_proposers - 1)
    proposer_ids: bool = False
    #: entries below this raise at build/validate time (None = unchecked)
    min_value: Optional[int] = None
    #: the per-tick dims when the scenario carries a placement (the
    #: restart planes are then node rows)
    placed_dims: Optional[tuple[str, ...]] = None

    @property
    def bounded(self) -> bool:
        """True iff the plane's entries have bounds to check
        (``check_bounds``)."""
        return self.proposer_ids or self.min_value is not None


#: the plane registry — insertion order is the canonical plane order
PLANES: dict[str, PlaneSpec] = {}
#: the layout planes: no tick axis (``dims`` are the whole shape), and a
#: scenario carries one only where it is given — left out, it is its
#: default (the placement: slot k on node k)
LAYOUT_PLANES: dict[str, PlaneSpec] = {}


def register_plane(
    name: str,
    dims: Iterable[str],
    default: int,
    doc: str = "",
    *,
    alts: Iterable[Iterable[str]] = (),
    proposer_ids: bool = False,
    min_value: Optional[int] = None,
    placed_dims: Optional[Iterable[str]] = None,
    layout: bool = False,
) -> PlaneSpec:
    """Extend the scenario schema with a new named plane (``layout``: a
    plane without a tick axis that a scenario may leave out,
    ``LAYOUT_PLANES``)."""
    spec = PlaneSpec(
        name, tuple(dims), int(default), doc,
        tuple(tuple(a) for a in alts), proposer_ids,
        None if min_value is None else int(min_value),
        None if placed_dims is None else tuple(placed_dims),
    )
    (LAYOUT_PLANES if layout else PLANES)[name] = spec
    return spec


register_plane(
    "attempts", ("N",), NO_PROPOSER,
    "proposer id attempting each cell this tick (-1 = none)",
    proposer_ids=True,
)
register_plane(
    "releases", ("N",), NO_PROPOSER,
    "proposer id releasing each cell this tick (-1 = none)",
    proposer_ids=True,
)
register_plane(
    "acc_up", ("A",), 1,
    "acceptor reachability this tick (1 = reachable)",
)
register_plane(
    "delay", ("P", "A"), 0,
    "per-(proposer, acceptor) link delay (whole ticks) for legs sent this tick",
    alts=(("A",),),
    min_value=0,
)
register_plane(
    "drop", ("P", "A"), 0,
    "per-(proposer, acceptor) link loss mask for legs sent this tick",
    alts=(("A",),),
)
register_plane(
    "prop_rate", ("P",), DEFAULT_RATE,
    "proposer local-clock step this tick (local quarter-ticks; 4 = rate 1.0)",
    min_value=1,
)
register_plane(
    "acc_rate", ("A",), DEFAULT_RATE,
    "acceptor local-clock step this tick (local quarter-ticks; 4 = rate 1.0)",
    min_value=1,
)
register_plane(
    "acc_stale", ("A",), 0,
    "adversarial (falsifier negative control): acceptor honors "
    "below-promise ballots this tick",
    min_value=0,
)
register_plane(
    "acc_equiv", ("A",), 0,
    "adversarial (falsifier negative control): acceptor reports its live "
    "accepted lease as open this tick",
    min_value=0,
)
register_plane(
    "acc_restart", ("A",), 0,
    "diskless acceptor crash+restart this tick: state blanks, then deaf "
    "for a maximal lease span on its local clock (with a placement: "
    "`[K]`, per node)",
    min_value=0, placed_dims=("K",),
)
register_plane(
    "prop_restart", ("P",), 0,
    "proposer crash+restart this tick: abandons its round, drops its owner "
    "belief, bumps its ballot restart counter (with a placement: `[K]`, "
    "per node)",
    min_value=0, placed_dims=("K",),
)
register_plane(
    "extends", ("N",), NO_PROPOSER,
    "proposer id extending its own live lease on each cell this tick "
    "(§6 in-flight re-propose; -1 = none, non-owners are a no-op)",
    proposer_ids=True,
)

register_plane(
    "placement", ("A", "N"), 0,
    "no tick axis; node id in [0, K) of replica slot k of each cell, whose "
    "acceptor k and proposer k share the node (A = P, ids distinct in a "
    "cell); absent: slot k is node k",
    layout=True,
)

#: the adversarial corruption planes — Byzantine acceptor behaviors the
#: honest protocol must never exhibit; the falsification engine enables
#: them as negative controls proving the §4 alarm can fire at all
CORRUPTION_PLANES = ("acc_stale", "acc_equiv")

#: the crash/restart planes (paper §1 failure model): diskless acceptor
#: restarts + proposer restart counters. All-zero planes are stripped from
#: dispatch like the corruption planes, keeping the honest engine
#: bit-identical with zero extra uploads
RESTART_PLANES = ("acc_restart", "prop_restart")

#: the §6 owner-extension plane: an owner re-proposes in-flight to renew
#: its lease before expiry. All-default (-1 everywhere) is stripped from
#: dispatch host-side like the corruption/restart planes, so the honest
#: jaxpr stays byte-identical
EXTEND_PLANES = ("extends",)

#: the per-slot planes a placement cannot map to nodes yet: with a
#: placement they must sit at their defaults (the link planes, delay and
#: drop, stay per slot: the network model every deployment uses)
PLACED_SHARED = ("acc_up", "acc_rate", "prop_rate")


def plane_table_md(planes: Optional[dict[str, PlaneSpec]] = None) -> str:
    """Render the registry as the markdown plane table embedded in
    docs/scenario_api.md (between the ``plane-table`` markers): the
    per-tick planes, then the layout planes (default: absent).

    The registry is the single source of truth: the table in the docs is
    generated by this function, and the convention lint
    (``repro.analysis.staticcheck.conventions``) fails CI whenever the two
    drift — including when a plane is registered with an empty ``doc``.
    """
    specs = (
        [*PLANES.values(), *LAYOUT_PLANES.values()] if planes is None
        else planes.values()
    )
    rows = [
        "| plane | per-tick shape | default | meaning |",
        "|-------|----------------|---------|---------|",
    ]
    for spec in specs:
        shape = "`[" + ", ".join(spec.dims) + "]`"
        if spec.alts:
            shape += " (or " + " / ".join(
                "`[" + ", ".join(a) + "]`" for a in spec.alts
            ) + ")"
        default = (
            "absent" if spec.name in LAYOUT_PLANES else f"`{spec.default}`"
        )
        rows.append(
            f"| `{spec.name}` | {shape} | {default} | {spec.doc} |"
        )
    return "\n".join(rows) + "\n"


def plane_digest(planes: dict) -> str:
    """Content hash of one scenario's planes (12 hex chars): a stable,
    seed-independent identifier for "which exact scenario was this".
    ``engine.sweep`` prints it for §4-violating batch members so a
    10k-batch offender can be re-identified standalone, and the
    falsification engine stamps it into survivor lineage tags. Plane
    *names* participate, so two scenarios differing only in which plane
    holds a value hash differently."""
    import hashlib

    h = hashlib.sha256()
    for name in sorted(planes):
        arr = np.ascontiguousarray(np.asarray(planes[name], np.int32))
        h.update(name.encode())
        h.update(np.asarray(arr.shape, np.int64).tobytes())
        h.update(arr.tobytes())
    return h.hexdigest()[:12]


def check_bounds(
    spec: PlaneSpec, lo: int, hi: int, n_proposers: int, what: str
) -> None:
    """Refuse a ``spec`` plane whose entries span ``[lo, hi]``. A proposer
    id outside [-1, n_proposers) would lease cells to a proposer the plane
    has no row for — a ghost owner nobody believes in; an entry below the
    plane's ``min_value`` breaks its floor: delays must be >= 0 (legs
    cannot land in the past), clock rates >= 1 (a rate-0 clock freezes its
    timers). The one place these messages are written: the host checks
    (``Scenario.build``, ``make_tick``, ``validate_for``) and
    ``engine.run_trace``'s check of the bounds it reads on the device
    both raise here."""
    if spec.proposer_ids:
        if hi >= n_proposers:
            raise ValueError(
                f"proposer id {hi} out of range "
                f"(plane has {n_proposers} proposers)"
            )
        if lo < NO_PROPOSER:
            raise ValueError(
                f"proposer id {lo} out of range "
                f"({NO_PROPOSER} means no proposer)"
            )
    if spec.min_value is not None and lo < spec.min_value:
        kind = (
            "negative entries" if spec.min_value == 0
            else f"entries below {spec.min_value}"
        )
        raise ValueError(
            f"{what} plane {spec.name!r} has {kind} (min {lo}); "
            f"valid entries are >= {spec.min_value}"
        )


def _dim_sizes(
    n_cells: int, n_acceptors: int, n_proposers: int,
    n_nodes: Optional[int] = None,
) -> dict[str, int]:
    sizes = {"N": int(n_cells), "A": int(n_acceptors), "P": int(n_proposers)}
    if n_nodes is not None:
        sizes["K"] = int(n_nodes)
    return sizes


def _plane_dims(spec: PlaneSpec, placed: bool) -> tuple[str, ...]:
    """A plane's per-tick dims in a scenario with or without a placement."""
    return spec.placed_dims if placed and spec.placed_dims else spec.dims


def check_placement(place: np.ndarray, n_nodes: int, what: str) -> None:
    """Refuse a ``[A, N]`` placement with a node id outside ``[0, K)`` or
    two replica slots of one cell on the same node (a node failure would
    then take two replicas of the cell at once, and the slot's proposer
    and acceptor would no longer be one node's)."""
    if not place.size:
        return
    lo, hi = int(place.min()), int(place.max())
    if lo < 0 or hi >= n_nodes:
        raise ValueError(
            f"{what} placement has node id {lo if lo < 0 else hi} out of "
            f"range (the cluster has {n_nodes} nodes, ids 0..{n_nodes - 1})"
        )
    A = place.shape[0]
    for a in range(A):
        for b in range(a + 1, A):
            same = np.flatnonzero(place[a] == place[b])
            if same.size:
                n = int(same[0])
                raise ValueError(
                    f"{what} placement puts replica slots {a} and {b} of "
                    f"cell {n} on the same node {int(place[a, n])}; the "
                    f"slots of a cell must be on distinct nodes"
                )


def check_placed(
    planes: dict, n_nodes: int, n_acceptors: int, n_proposers: int,
    what: str, *, check_map: bool = True,
) -> None:
    """What a scenario with a placement must also satisfy: ``A == P``
    (slot ``k`` hosts acceptor ``k`` and proposer ``k``), the per-slot
    planes of ``PLACED_SHARED`` at their defaults (they are not mapped to
    nodes yet), 0/1 restart node rows with at most ``MAX_RESTARTS``
    restarts of any node, and (``check_map``) a sound map."""
    if n_acceptors != n_proposers:
        raise ValueError(
            f"{what} with a placement needs as many proposers as acceptors "
            f"(slot k hosts acceptor k and proposer k); got A={n_acceptors}, "
            f"P={n_proposers}"
        )
    for k in PLACED_SHARED:
        v = planes.get(k)
        if v is not None and (np.asarray(v) != PLANES[k].default).any():
            raise ValueError(
                f"{what} plane {k!r} departs from its default "
                f"{PLANES[k].default}; with a placement it would apply per "
                f"replica slot across every node, so it is refused until it "
                f"is mapped to nodes (only the restart planes are)"
            )
    for k in RESTART_PLANES:
        v = np.asarray(planes[k])
        if not v.size:
            continue
        if int(v.max()) > 1:
            raise ValueError(
                f"{what} plane {k!r} has an entry above 1; with a placement "
                f"the restart planes are 0/1 node rows"
            )
        most = int(v.reshape(-1, v.shape[-1]).sum(axis=0).max())
        if most > MAX_RESTARTS:
            raise ValueError(
                f"{what} plane {k!r} restarts a node {most} times; a "
                f"placed replay takes at most {MAX_RESTARTS} restarts of "
                f"each node (split the schedule across run_trace calls)"
            )
    if check_map:
        check_placement(np.asarray(planes["placement"]), n_nodes, what)


def _check_plane(
    spec: PlaneSpec, arr: np.ndarray, n_proposers: int, what: str
) -> None:
    """``check_bounds`` on a host array's own bounds (an empty or
    unbounded plane passes unread)."""
    if spec.bounded and arr.size:
        check_bounds(spec, int(arr.min()), int(arr.max()), n_proposers, what)


def _coerce_plane(
    spec: PlaneSpec,
    value,
    sizes: dict[str, int],
    lead: tuple[int, ...],
    what: str,
    placed: bool = False,
) -> np.ndarray:
    """Default / validate / broadcast one plane to ``lead + canonical``
    (a plane without a tick axis takes no ``lead``; with ``placed`` the
    restart planes are ``[T, K]`` node rows)."""
    dims = _plane_dims(spec, placed)
    if spec.name in LAYOUT_PLANES:
        lead = ()
    shape = lead + tuple(sizes[d] for d in dims)
    if value is None:
        return np.full(shape, spec.default, np.int32)
    arr = np.asarray(value)
    if arr.dtype == bool:
        arr = arr.astype(np.int32)
    arr = arr.astype(np.int32, copy=False)
    forms = (dims,) + (spec.alts if dims == spec.dims else ())
    for form in forms:
        want = lead + tuple(sizes[d] for d in form)
        if arr.shape == want:
            if form != dims:  # expand the alternate form, e.g. [T,A]
                missing = [d for d in dims if d not in form]
                for d in missing:
                    ax = len(lead) + dims.index(d)
                    arr = np.expand_dims(arr, ax)
                arr = np.broadcast_to(arr, shape).copy()
            _check_plane(spec, arr, sizes["P"], what)
            return arr
    accepted = " or ".join(
        str(lead + tuple(sizes[d] for d in form)) for form in forms
    )
    raise ValueError(
        f"{what} plane {spec.name!r} has shape {arr.shape}; expected "
        f"{accepted} (T, N, A, P, K = ticks, cells, acceptors, proposers, "
        f"nodes)"
    )


def _raise_unknown(bad):
    raise ValueError(
        f"unknown scenario plane(s) {sorted(bad)}; registered planes: "
        f"{sorted([*PLANES, *LAYOUT_PLANES])} (extend with register_plane)"
    )


class _PlaneBundle:
    """Shared dict-of-planes pytree behavior for Scenario / TickInputs."""

    __slots__ = ("planes",)
    _lead_ndim = 0  # leading axes before the per-tick dims

    def __init__(self, planes: dict) -> None:
        if bad := set(planes) - set(PLANES) - set(LAYOUT_PLANES):
            _raise_unknown(bad)
        self.planes = {
            k: planes[k] for k in (*PLANES, *LAYOUT_PLANES) if k in planes
        }

    def __getattr__(self, name: str):
        if name == "planes":  # unset slot (e.g. during unpickling probes)
            raise AttributeError(name)
        try:
            return self.planes[name]
        except KeyError:
            raise AttributeError(name) from None

    def _dim(self, plane: str, axis: int) -> int:
        return int(self.planes[plane].shape[self._lead_ndim + axis])

    @property
    def n_cells(self) -> int:
        return self._dim("attempts", 0)

    @property
    def n_acceptors(self) -> int:
        return self._dim("acc_up", 0)

    @property
    def n_proposers(self) -> int:
        return self._dim("delay", 0)

    @property
    def placed(self) -> bool:
        """True iff the bundle carries a placement (its restart planes
        are then node rows)."""
        return "placement" in self.planes

    @property
    def n_nodes(self) -> Optional[int]:
        """K, the cluster's node count, with a placement (else None)."""
        return self._dim("acc_restart", 0) if self.placed else None

    @property
    def delayed(self) -> bool:
        """True iff the delay or drop plane is nonzero anywhere (needs the
        in-flight netplane model). Host-side only — not traceable."""
        return bool(
            np.asarray(self.planes["delay"]).any()
            or np.asarray(self.planes["drop"]).any()
        )

    @property
    def drifted(self) -> bool:
        """True iff any clock-rate plane departs from the drift-free
        DEFAULT_RATE step. Host-side only — not traceable."""
        return bool(
            (np.asarray(self.planes["prop_rate"]) != DEFAULT_RATE).any()
            or (np.asarray(self.planes["acc_rate"]) != DEFAULT_RATE).any()
        )

    @property
    def corrupted(self) -> bool:
        """True iff an adversarial corruption plane is nonzero anywhere
        (needs the delayed model with the corruption inputs threaded).
        Host-side only — not traceable."""
        return bool(any(
            np.asarray(self.planes[k]).any() for k in CORRUPTION_PLANES
        ))

    @property
    def restarted(self) -> bool:
        """True iff a crash/restart plane is nonzero anywhere (needs the
        delayed model with the restart inputs threaded and switches ballots
        to the restart-counter carve). Host-side only — not traceable."""
        return bool(any(
            np.asarray(self.planes[k]).any() for k in RESTART_PLANES
        ))

    @property
    def extended(self) -> bool:
        """True iff the §6 extends plane schedules any owner extension
        (needs the delayed model with the extend input threaded).
        Host-side only — not traceable."""
        return bool(any(
            (np.asarray(self.planes[k]) != PLANES[k].default).any()
            for k in EXTEND_PLANES
        ))

    def validate_for(
        self, *, n_cells: int, n_acceptors: int, n_proposers: int,
        skip_bounds: Iterable[str] = (),
    ) -> None:
        """Check every plane against an engine's geometry (shape + ids +
        delay sign). ``build``/``make_tick`` output always passes;
        hand-rolled pytrees are checked here before they reach the step or
        the scanner. The planes named in ``skip_bounds`` have their shapes
        checked and their entries left unread: their caller checks those
        bounds itself (``engine.run_trace`` reads them on the device after
        the upload) through the same ``check_bounds``; ``"placement"``
        there leaves ``check_placed`` to the caller likewise."""
        placed = self.placed
        sizes = _dim_sizes(n_cells, n_acceptors, n_proposers, self.n_nodes)
        lead: tuple[int, ...] = ()
        if self._lead_ndim:
            lead = (int(self.planes["attempts"].shape[0]),)
        what = type(self).__name__
        for name, spec in (*PLANES.items(), *LAYOUT_PLANES.items()):
            if name not in self.planes:
                if name in LAYOUT_PLANES:
                    continue
                raise ValueError(f"{what} is missing plane {name!r}")
            arr = np.asarray(self.planes[name])
            want = (() if name in LAYOUT_PLANES else lead) + tuple(
                sizes[d] for d in _plane_dims(spec, placed)
            )
            if arr.shape != want:
                raise ValueError(
                    f"{what} plane {name!r} has shape {arr.shape}; "
                    f"engine geometry wants {want}"
                )
            if name not in skip_bounds:
                _check_plane(spec, arr, sizes["P"], what)
        if placed and "placement" not in skip_bounds:
            check_placed(
                self.planes, sizes["K"], n_acceptors, n_proposers, what
            )

    def __repr__(self) -> str:
        inner = ", ".join(
            f"{k}{tuple(v.shape)}" for k, v in self.planes.items()
        )
        return f"{type(self).__name__}({inner})"


def _register(cls):
    jax.tree_util.register_pytree_node(
        cls,
        lambda s: (tuple(s.planes.values()), tuple(s.planes.keys())),
        lambda names, leaves: cls(dict(zip(names, leaves))),
    )
    return cls


@_register
class TickInputs(_PlaneBundle):
    """One tick's worth of every scenario plane (no leading T axis)."""


def make_tick(
    *,
    n_cells: int,
    n_acceptors: int,
    n_proposers: int,
    **planes,
) -> TickInputs:
    """Build a validated single-tick input bundle (engine.step's currency).

    Omitted planes get their registered defaults; ``delay``/``drop`` accept
    the symmetric per-acceptor ``[A]`` form and broadcast it over P.
    """
    if planes.get("placement") is not None:
        raise ValueError(
            "a tick takes no placement: a placed deployment is replayed "
            "whole, by run_trace"
        )
    if bad := set(planes) - set(PLANES):
        _raise_unknown(bad)
    sizes = _dim_sizes(n_cells, n_acceptors, n_proposers)
    return TickInputs({
        name: _coerce_plane(spec, planes.get(name), sizes, (), "tick")
        for name, spec in PLANES.items()
    })


@_register
class Scenario(_PlaneBundle):
    """A [T]-tick fault scenario: every registered plane, leading T axis.

    Build with :meth:`Scenario.build` (defaulting + shape/dtype/id
    validation + broadcasting), slice with ``scenario[t]`` (→ TickInputs)
    or ``scenario[a:b]`` (→ sub-Scenario), join with :meth:`concat`, and
    batch with :meth:`stack` for ``jax.vmap``.
    """

    _lead_ndim = 1

    @classmethod
    def build(
        cls,
        n_ticks: Optional[int] = None,
        *,
        n_cells: int,
        n_acceptors: int,
        n_proposers: int,
        n_nodes: Optional[int] = None,
        **planes,
    ) -> "Scenario":
        """Default, validate and broadcast every registered plane.

        ``n_ticks`` may be omitted when at least one plane with a tick
        axis is given (it is inferred from the first one). Unknown plane
        names are rejected with the list of registered planes. A
        ``placement`` needs ``n_nodes`` (K); its restart planes are then
        ``[T, K]`` node rows (``check_placed``).
        """
        if bad := set(planes) - set(PLANES) - set(LAYOUT_PLANES):
            _raise_unknown(bad)
        placed = planes.get("placement") is not None
        if placed != (n_nodes is not None):
            raise ValueError(
                "a placement needs n_nodes (K, the cluster's node count), "
                "and n_nodes needs a placement"
            )
        if n_ticks is None:
            for k, v in planes.items():
                if v is not None and k in PLANES:
                    n_ticks = int(np.asarray(v).shape[0])
                    break
            else:
                raise ValueError(
                    "n_ticks is required when no plane is provided"
                )
        sizes = _dim_sizes(n_cells, n_acceptors, n_proposers, n_nodes)
        lead = (int(n_ticks),)
        out = {
            name: _coerce_plane(
                spec, planes.get(name), sizes, lead, "scenario", placed
            )
            for name, spec in (*PLANES.items(), *LAYOUT_PLANES.items())
            if name in PLANES or planes.get(name) is not None
        }
        if placed:
            check_placed(out, n_nodes, n_acceptors, n_proposers, "scenario")
        return cls(out)

    # ------------------------------------------------------------- queries
    @property
    def n_ticks(self) -> int:
        return int(self.planes["attempts"].shape[0])

    # -------------------------------------------------------- composition
    def __getitem__(self, key):
        if isinstance(key, slice):
            return Scenario({
                k: v if k in LAYOUT_PLANES else v[key]
                for k, v in self.planes.items()
            })
        if self.placed:
            raise ValueError(
                "a placed scenario has no single-tick form: its restart "
                "planes are node rows, replayed whole by run_trace"
            )
        return TickInputs({k: v[key] for k, v in self.planes.items()})

    def concat(self, *others: "Scenario") -> "Scenario":
        """Concatenate scenarios along the tick axis (same geometry; a
        placement, which has no tick axis, must be the same in all)."""
        for o in others:
            if set(o.planes) != set(self.planes):
                raise ValueError(
                    "cannot concat: the scenarios carry different planes "
                    f"({sorted(set(o.planes) ^ set(self.planes))})"
                )
            for name in self.planes:
                a, b = self.planes[name], o.planes[name]
                if name in LAYOUT_PLANES:
                    if not np.array_equal(np.asarray(a), np.asarray(b)):
                        raise ValueError(
                            f"cannot concat: plane {name!r} differs"
                        )
                elif a.shape[1:] != b.shape[1:]:
                    raise ValueError(
                        f"cannot concat: plane {name!r} per-tick shapes "
                        f"differ ({a.shape[1:]} vs {b.shape[1:]})"
                    )
        return Scenario({
            k: np.concatenate(
                [np.asarray(self.planes[k])]
                + [np.asarray(o.planes[k]) for o in others], axis=0,
            ) if k in PLANES else self.planes[k]
            for k in self.planes
        })

    @classmethod
    def stack(cls, scenarios: Iterable["Scenario"]):
        """Stack same-shape scenarios on a new leading batch axis — the
        ``jax.vmap`` batching form (``engine.sweep``'s currency). Returns a
        Scenario-shaped pytree whose leaves are [B, T, ...] (its per-tick
        properties no longer apply); feed it to a vmapped scanner with
        ``in_axes=0``."""
        scenarios = list(scenarios)
        if not scenarios:
            raise ValueError("Scenario.stack needs at least one scenario")
        first = scenarios[0]
        for i, sc in enumerate(scenarios[1:], 1):
            if set(sc.planes) != set(first.planes):
                raise ValueError(
                    f"cannot stack: scenario {i} carries different planes "
                    f"than scenario 0"
                )
            for name in first.planes:
                a = np.asarray(first.planes[name])
                b = np.asarray(sc.planes[name])
                if a.shape != b.shape:
                    raise ValueError(
                        f"cannot stack: scenario 0 plane {name!r} has shape "
                        f"{a.shape} but scenario {i} has {b.shape} "
                        f"(same tick count and geometry required)"
                    )
        return jax.tree.map(lambda *xs: np.stack(xs), *scenarios)
