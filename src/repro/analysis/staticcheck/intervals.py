"""Interval abstract interpretation over the lease tick-core jaxprs.

The packed int32 layout (``q4 << PACK_SHIFT | ballot``, ``state.py``) is a
bit budget: ballots must fit in PACK_SHIFT bits, deadlines in the rest,
and every intermediate of the tick math must stay inside int32. The only
runtime guard (``state.check_pack_budget``) is host-side and *skipped
under tracing* — this module closes that gap statically.

How: trace ``ref.sync_tick_math`` / ``netplane.delayed_tick_math`` to a
jaxpr once per protocol config (the cores are branch-free int32 math, so
the jaxpr IS the semantics for every backend — jnp scan and Pallas window
kernel alike), then walk the equations with an interval domain:

  - every input gets an interval from the scenario config: ``t`` in
    ``[0, t_end]``, local clocks in ``[0, max_rate*t_end + clk_slack]``,
    link words in ``[0, 2*max_delay + 1]``, attempt/release ids in
    ``[-1, P-1]``;
  - state planes (promised ballots, packed leases, in-flight slots, round
    rows) start at their init values and iterate to a fixpoint: the tick
    is re-interpreted with last round's output intervals joined in until
    nothing widens — the loop invariant of the scan, derived not assumed;
  - arithmetic is exact on unbounded Python ints, so ``add``/``mul``/
    ``shift_left`` results falling outside int32 are flagged
    (``int32-overflow``) — the check the traced graph can't do;
  - ``or`` carries *pack provenance*: a ``shift_left`` by a constant k
    tags its result, and ``(x << k) | low`` demands ``low`` fit in k bits
    — the ``pack-budget`` rule, which is exactly "ballot <= PACK_MASK"
    at every ``pack_pair``/``pack_slot`` site.

``derived_max_pack_tick`` inverts the checker: binary-search the largest
``t_end`` with no findings. For delay-free configs it reproduces
``state.max_pack_tick`` exactly (tests assert ±0); with link delays the
hand formula double-charges the clock budget and the derived bound is
strictly ≥ — the hand check stays safe, just conservative.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import NamedTuple, Optional

import numpy as np

from ...lease_array.state import (
    MAX_PACK_Q4,
    PACK_SHIFT,
    QUARTERS,
    lease_quarters,
)
from .findings import Finding

INT32_MIN = -(2**31)
INT32_MAX = 2**31 - 1

#: fixpoint passes before giving up and widening to full int32
_MAX_FIXPOINT_ITERS = 64


class IV(NamedTuple):
    """A closed integer interval [lo, hi] on unbounded Python ints."""

    lo: int
    hi: int

    def join(self, other: "IV") -> "IV":
        return IV(min(self.lo, other.lo), max(self.hi, other.hi))

    def __contains__(self, v: int) -> bool:
        return self.lo <= v <= self.hi


INT32 = IV(INT32_MIN, INT32_MAX)
BOOL = IV(0, 1)


class AbsVal(NamedTuple):
    """Interval + pack provenance: ``shift=k`` means the value is exactly
    some nonnegative field shifted left by the constant k (low k bits
    zero), so an ``or`` against it is field packing, not bit soup."""

    iv: IV
    shift: Optional[int] = None


def _clamp_i32(iv: IV) -> IV:
    return IV(max(iv.lo, INT32_MIN), min(iv.hi, INT32_MAX))


def _bitlen_cap(hi: int) -> int:
    """Smallest 2^m - 1 >= hi (hi >= 0): the or-result ceiling."""
    return (1 << int(hi).bit_length()) - 1


# ---------------------------------------------------------------------------
# the scenario config under analysis
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TickConfig:
    """Everything the interval analysis needs to bound a replay: the
    protocol constants baked into the traced core plus the scenario-wide
    extremes of the inputs (mirroring ``state.max_pack_tick``'s
    parameters, with ``clk_slack`` = how far ahead of ``max_rate * t`` the
    engine's accumulated clocks already run)."""

    t_end: int
    n_proposers: int = 8
    n_acceptors: int = 5
    lease_ticks: int = 3
    round_q4: int = QUARTERS
    guard_q4: Optional[int] = None  # None = lease_q4 (the eps=0 case)
    max_delay: int = 0
    max_rate: int = QUARTERS
    clk_slack: int = 0
    sync: bool = False
    lease_q4: Optional[int] = None  # overrides lease_ticks when given
    corrupt: bool = False  # thread the acc_stale/acc_equiv planes
    #: > 0 threads the crash/restart planes AND switches ballots onto the
    #: restart-carve encoding (state.RESTART_SHIFT): the highest per-
    #: proposer restart counter any tick can carry
    max_restarts: int = 0
    extend: bool = False  # thread the §6 extends plane
    #: the restart inputs come per cell ([A, bn] / [P, bn] columns, a
    #: placement's), not per slot; ``max_restarts`` is then the highest
    #: count of any node
    placed: bool = False

    @property
    def majority(self) -> int:
        return self.n_acceptors // 2 + 1

    @property
    def restart(self) -> bool:
        return self.max_restarts > 0

    @property
    def eff_lease_q4(self) -> int:
        if self.lease_q4 is not None:
            return int(self.lease_q4)
        return lease_quarters(self.lease_ticks)

    @property
    def eff_guard_q4(self) -> int:
        return self.eff_lease_q4 if self.guard_q4 is None else int(self.guard_q4)

    @property
    def eff_rate(self) -> int:
        return max(int(self.max_rate), QUARTERS)


# ---------------------------------------------------------------------------
# tracing the tick cores (once per protocol config; intervals re-run free)
# ---------------------------------------------------------------------------
#: invar layout of each traced core: (name, kind) per flat argument.
#: kind "state" participates in the fixpoint; the rest are config inputs.
_SYNC_ARGS = (
    ("promised", "state"), ("acc_lease", "state"),
    ("own_id", "state_id"), ("ownp", "state"),
    ("t", "t"), ("attempt", "pid"), ("release", "pid"),
    ("up", "bool"), ("pclk", "clk"), ("aclk", "clk"),
)
_NET_STATE = (
    ("preq", "state"), ("presp", "state"), ("presp_pay", "state_id"),
    ("poreq", "state"), ("poresp", "state"), ("rel_s", "state"),
    ("rnd_ballot", "state"), ("rnd_phase", "state"),
    ("rnd_expiry", "state"), ("rnd_deadline", "state"),
    ("rnd_open_bits", "state"), ("rnd_acc_bits", "state"),
)
_DELAYED_ARGS = _SYNC_ARGS[:4] + _NET_STATE + _SYNC_ARGS[4:] + (
    ("link", "link"),
)
#: the corruption-plane variant: two extra [A, 1] boolean planes
#: (falsifier negative controls — acc_stale / acc_equiv)
_CORRUPT_ARGS = _DELAYED_ARGS + (("stale", "bool"), ("equiv", "bool"))
#: the crash/restart variant: the per-tick restart/deaf indicator planes
#: plus the running restart-counter plane ([0, max_restarts], the "rc"
#: kind) that the restart-mode ballot mint ORs under RESTART_SHIFT
_RESTART_TAIL = (
    ("acc_restart", "bool"), ("acc_deaf", "bool"),
    ("prop_restart", "rc"), ("prop_rc", "rc"),
)
#: the §6 extend variant: one extra [1, bn] proposer-id plane (the owner
#: extending its own live lease) merged into the attempt stream
_EXTEND_TAIL = (("extend", "pid"),)


@functools.lru_cache(maxsize=None)
def trace_tick_core(
    n_proposers: int,
    n_acceptors: int,
    lease_q4: int,
    round_q4: int,
    guard_q4: int,
    majority: int,
    *,
    sync: bool = False,
    legs: str = "gather",
    block_n: int = 8,
    corrupt: bool = False,
    restart: bool = False,
    extend: bool = False,
    placed: bool = False,
):
    """``jax.make_jaxpr`` of one tick core with the protocol constants
    closed over, on tiny block shapes (intervals are shape-oblivious
    except for iota/reduction extents, which use the real A/P). Returns
    a ClosedJaxpr; cached — the expensive trace happens once per config,
    every ``t_end`` probe of the binary search re-walks it for free.
    ``placed`` traces the restart inputs as per-cell ``[A, bn]`` /
    ``[P, bn]`` columns (a placement's) instead of per-slot ones."""
    import jax
    import jax.numpy as jnp

    from ...lease_array import netplane as _netplane
    from ...lease_array.ref import sync_tick_math

    A, P, bn = n_acceptors, n_proposers, block_n
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    lease_shapes = [sds((A, bn), i32), sds((A, bn), i32),
                    sds((1, bn), i32), sds((1, bn), i32)]
    common = [sds((), i32), sds((1, bn), i32), sds((1, bn), i32),
              sds((A, 1), i32), sds((P, 1), i32), sds((A, 1), i32)]

    if sync:
        def fn(pr, al, oi, op, t, att, rel, up, pclk, aclk):
            lease, count = sync_tick_math(
                (pr, al, oi, op), t, att, rel, up, pclk, aclk,
                majority=majority, lease_q4=lease_q4,
                n_proposers=P, guard_q4=guard_q4,
            )
            return (*lease, count)

        return jax.make_jaxpr(fn)(*lease_shapes, *common)

    net_shapes = [sds((A, bn), i32)] * 6 + [sds((1, bn), i32)] * 6
    legs_fn = _netplane.legs_select if legs == "select" else _netplane.legs_gather

    def fn(*args):
        lease, net = args[:4], args[4:16]
        rest = list(args[16:])
        adv = {}
        if extend:
            adv["extend"] = rest.pop()
        if restart:
            arst, deaf, prst, prc = rest[-4:]
            rest = rest[:-4]
            adv.update(
                acc_restart=arst, acc_deaf=deaf,
                prop_restart=prst, prop_rc=prc,
            )
        if corrupt:
            stale, equiv = rest[-2:]
            rest = rest[:-2]
            adv.update(stale=stale, equiv=equiv)
        t, att, rel, up, pclk, aclk, link = rest
        lease, net, count = _netplane.delayed_tick_math(
            lease, net, t, att, rel, up, pclk, aclk, link,
            majority=majority, lease_q4=lease_q4, round_q4=round_q4,
            n_proposers=P, guard_q4=guard_q4, legs=legs_fn, **adv,
        )
        return (*lease, *net, count)

    extra = [sds((A, 1), i32)] * 2 if corrupt else []
    if restart:
        cols = bn if placed else 1
        extra = extra + [
            sds((A, cols), i32), sds((A, cols), i32),
            sds((P, cols), i32), sds((P, cols), i32),
        ]
    if extend:
        extra = extra + [sds((1, bn), i32)]
    return jax.make_jaxpr(fn)(
        *lease_shapes, *net_shapes, *common, sds((P, A), i32), *extra
    )


def _input_intervals(cfg: TickConfig) -> dict[str, AbsVal]:
    """Config inputs → intervals. Clocks are accumulated local quarter-
    ticks: at most ``max_rate`` per tick plus any pre-existing slack."""
    clk_hi = cfg.eff_rate * cfg.t_end + cfg.clk_slack
    return {
        "t": AbsVal(IV(0, cfg.t_end)),
        "pid": AbsVal(IV(-1, cfg.n_proposers - 1)),
        "bool": AbsVal(BOOL),
        "clk": AbsVal(IV(0, clk_hi)),
        "link": AbsVal(IV(0, 2 * cfg.max_delay + 1)),
        "rc": AbsVal(IV(0, cfg.max_restarts)),
    }


def _init_state(kind: str) -> AbsVal:
    # fresh engines: every packed plane is 0, id planes are NO_PROPOSER
    return AbsVal(IV(-1, -1)) if kind == "state_id" else AbsVal(IV(0, 0))


# ---------------------------------------------------------------------------
# the abstract interpreter
# ---------------------------------------------------------------------------
def _shift_amount(v: AbsVal) -> Optional[int]:
    """The shift count iff statically a single value."""
    return v.iv.lo if v.iv.lo == v.iv.hi else None


class _Interp:
    """One abstract walk of a (closed) jaxpr. Collects findings only when
    ``report`` is set — fixpoint warm-up passes stay silent so a single
    violation isn't reported once per iteration."""

    def __init__(self, report: Optional[list[Finding]] = None) -> None:
        self.report = report
        self._seen_unknown: set[str] = set()

    # -- findings ----------------------------------------------------------
    def _finding(self, rule: str, where: str, detail: str) -> None:
        if self.report is not None:
            self.report.append(Finding("intervals", rule, where, detail))

    def _check_i32(self, iv: IV, prim: str, where: str) -> IV:
        if iv.lo < INT32_MIN or iv.hi > INT32_MAX:
            self._finding(
                "int32-overflow", where,
                f"`{prim}` result can reach [{iv.lo}, {iv.hi}], outside "
                f"int32 [{INT32_MIN}, {INT32_MAX}] — the packed tick math "
                f"would silently wrap",
            )
            iv = _clamp_i32(iv)
        return iv

    # -- primitive rules ---------------------------------------------------
    def eval_jaxpr(self, jaxpr, consts, args: list[AbsVal]) -> list[AbsVal]:
        env: dict = {}

        def read(atom) -> AbsVal:
            from jax.extend.core import Literal

            if isinstance(atom, Literal):
                v = int(np.asarray(atom.val).min())
                hi = int(np.asarray(atom.val).max())
                return AbsVal(IV(v, hi))
            return env[atom]

        for var, const in zip(jaxpr.constvars, consts):
            arr = np.asarray(const)
            env[var] = AbsVal(IV(int(arr.min()), int(arr.max())))
        for var, val in zip(jaxpr.invars, args):
            env[var] = val

        for eqn in jaxpr.eqns:
            outs = self._eval_eqn(eqn, [read(v) for v in eqn.invars])
            for var, val in zip(eqn.outvars, outs):
                env[var] = val
        return [read(v) for v in jaxpr.outvars]

    def _eval_eqn(self, eqn, ins: list[AbsVal]) -> list[AbsVal]:
        prim = eqn.primitive.name
        where = f"eqn `{prim}`"
        out_aval = eqn.outvars[0].aval if eqn.outvars else None
        is_bool = out_aval is not None and out_aval.dtype == np.bool_

        # calls (pjit et al.): recurse into the sub-jaxpr
        sub = eqn.params.get("jaxpr")
        if sub is not None and hasattr(sub, "jaxpr"):
            outs = self.eval_jaxpr(sub.jaxpr, sub.consts, ins)
            return outs

        if prim in ("broadcast_in_dim", "reshape", "squeeze", "slice",
                    "transpose", "copy", "stop_gradient", "expand_dims"):
            return [ins[0]]  # shape-only: value set (and provenance) unchanged
        if prim == "gather":
            return [AbsVal(ins[0].iv)]
        if prim == "convert_element_type":
            iv = ins[0].iv
            if is_bool:
                iv = IV(max(0, min(iv.lo, 1)), max(0, min(iv.hi, 1)))
            return [AbsVal(iv)]
        if prim == "iota":
            dim = eqn.params["dimension"]
            n = eqn.params["shape"][dim]
            return [AbsVal(IV(0, max(0, n - 1)))]

        a = ins[0].iv if ins else None
        b = ins[1].iv if len(ins) > 1 else None

        if prim == "add":
            iv = self._check_i32(IV(a.lo + b.lo, a.hi + b.hi), prim, where)
            return [AbsVal(iv)]
        if prim == "sub":
            iv = self._check_i32(IV(a.lo - b.hi, a.hi - b.lo), prim, where)
            return [AbsVal(iv)]
        if prim == "mul":
            prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
            iv = self._check_i32(IV(min(prods), max(prods)), prim, where)
            return [AbsVal(iv)]
        if prim == "shift_left":
            s_lo = max(0, b.lo)
            s_hi = max(0, b.hi)
            cand = [a.lo << s_lo, a.lo << s_hi, a.hi << s_lo, a.hi << s_hi]
            raw = IV(min(cand), max(cand))
            if raw.hi > INT32_MAX and a.lo >= 0:
                # name the budget in pack terms when the shift is a pack
                k = _shift_amount(ins[1])
                if k == PACK_SHIFT:
                    self._finding(
                        "pack-budget", where,
                        f"packed deadline field can reach {a.hi} quarter-"
                        f"ticks but only [0, {MAX_PACK_Q4}] fits above "
                        f"PACK_SHIFT={PACK_SHIFT} in int32",
                    )
                    raw = _clamp_i32(raw)
                else:
                    raw = self._check_i32(raw, prim, where)
            else:
                raw = self._check_i32(raw, prim, where)
            shift = _shift_amount(ins[1]) if a.lo >= 0 else None
            return [AbsVal(raw, shift=shift)]
        if prim in ("shift_right_arithmetic", "shift_right_logical"):
            if prim == "shift_right_logical" and a.lo < 0:
                return [AbsVal(INT32)]  # not expected in the cores
            s_lo, s_hi = max(0, b.lo), max(0, b.hi)
            cand = [a.lo >> s_lo, a.lo >> s_hi, a.hi >> s_lo, a.hi >> s_hi]
            return [AbsVal(IV(min(cand), max(cand)))]
        if prim == "or":
            return [self._eval_or(ins[0], ins[1], is_bool, where)]
        if prim == "and":
            if is_bool:
                return [AbsVal(IV(min(a.lo, b.lo), min(a.hi, b.hi)))]
            if a.lo >= 0 or b.lo >= 0:
                hi = min(a.hi, b.hi) if (a.lo >= 0 and b.lo >= 0) else (
                    a.hi if a.lo >= 0 else b.hi
                )
                return [AbsVal(IV(0, max(0, hi)))]
            return [AbsVal(INT32)]
        if prim == "xor":
            if is_bool:
                return [AbsVal(BOOL)]
            if a.lo >= 0 and b.lo >= 0:
                return [AbsVal(IV(0, max(_bitlen_cap(a.hi), _bitlen_cap(b.hi))))]
            return [AbsVal(INT32)]
        if prim == "not":
            if is_bool:
                return [AbsVal(IV(1 - a.hi, 1 - a.lo))]
            return [AbsVal(IV(-a.hi - 1, -a.lo - 1))]
        if prim in ("eq", "ne", "lt", "le", "gt", "ge"):
            return [AbsVal(BOOL)]
        if prim == "max":
            return [AbsVal(IV(max(a.lo, b.lo), max(a.hi, b.hi)))]
        if prim == "min":
            return [AbsVal(IV(min(a.lo, b.lo), min(a.hi, b.hi)))]
        if prim == "clamp":
            lo_iv, x, hi_iv = ins[0].iv, ins[1].iv, ins[2].iv
            return [AbsVal(IV(max(x.lo, lo_iv.lo), min(x.hi, hi_iv.hi)))]
        if prim == "rem":
            if b.lo > 0:
                hi = b.hi - 1
                if a.lo >= 0:
                    return [AbsVal(IV(0, min(a.hi, hi)))]
                return [AbsVal(IV(-hi, hi))]  # lax.rem: sign of dividend
            return [AbsVal(INT32)]
        if prim == "sign":
            sgn = lambda v: (v > 0) - (v < 0)
            return [AbsVal(IV(sgn(a.lo), sgn(a.hi)))]
        if prim == "div":
            if b.lo > 0 or b.hi < 0:  # divisor can't be 0
                # lax.div truncates toward zero
                tdiv = lambda p, q: abs(p) // abs(q) * (1 if (p >= 0) == (q > 0) else -1)
                cand = [tdiv(p, q) for p in (a.lo, a.hi) for q in (b.lo, b.hi)]
                return [AbsVal(IV(min(cand), max(cand)))]
            return [AbsVal(INT32)]
        if prim == "select_n":
            iv = ins[1].iv
            for case in ins[2:]:
                iv = iv.join(case.iv)
            return [AbsVal(iv)]
        if prim == "reduce_sum":
            n = 1
            src = eqn.invars[0].aval.shape
            for ax in eqn.params["axes"]:
                n *= src[ax]
            iv = self._check_i32(IV(n * a.lo, n * a.hi), prim, where)
            return [AbsVal(iv)]
        if prim in ("reduce_max", "reduce_min", "reduce_or", "reduce_and"):
            return [AbsVal(a)]

        # unknown primitive: stay sound (full int32 / bool) and say so once
        if prim not in self._seen_unknown:
            self._seen_unknown.add(prim)
            self._finding(
                "unknown-primitive", where,
                f"no interval rule for `{prim}`; result widened to full "
                f"int32 — add a rule to staticcheck/intervals.py",
            )
        fallback = AbsVal(BOOL if is_bool else INT32)
        return [fallback for _ in eqn.outvars]

    def _eval_or(self, x: AbsVal, y: AbsVal, is_bool: bool, where: str) -> AbsVal:
        if is_bool:
            return AbsVal(IV(max(x.iv.lo, y.iv.lo), max(x.iv.hi, y.iv.hi)))
        # pack rule: (field << k) | low is exact addition iff low fits in k
        # bits; a low side that can't fit is a pack-budget violation (it
        # would bleed into the deadline field)
        for hi_side, lo_side in ((x, y), (y, x)):
            if hi_side.shift is None:
                continue
            k = hi_side.shift
            budget = (1 << k) - 1
            if 0 <= lo_side.iv.lo and lo_side.iv.hi <= budget:
                return AbsVal(IV(
                    hi_side.iv.lo + lo_side.iv.lo,
                    hi_side.iv.hi + lo_side.iv.hi,
                ))
            self._finding(
                "pack-budget", where,
                f"low field of a `<< {k} | ...` pack can reach "
                f"[{lo_side.iv.lo}, {lo_side.iv.hi}] but the packed layout "
                f"budgets [0, {budget}]"
                + (" (= PACK_MASK: a ballot past the 15-bit budget)"
                   if k == PACK_SHIFT else ""),
            )
            return AbsVal(_clamp_i32(IV(
                min(hi_side.iv.lo, lo_side.iv.lo),
                hi_side.iv.hi + max(0, lo_side.iv.hi),
            )))
        if x.iv.lo >= 0 and y.iv.lo >= 0:
            return AbsVal(IV(
                max(x.iv.lo, y.iv.lo),
                max(_bitlen_cap(x.iv.hi), _bitlen_cap(y.iv.hi)),
            ))
        return AbsVal(INT32)  # bitwise: can't leave int32


# ---------------------------------------------------------------------------
# the public checker
# ---------------------------------------------------------------------------
def _core_and_layout(cfg: TickConfig, legs: str):
    closed = trace_tick_core(
        cfg.n_proposers, cfg.n_acceptors, cfg.eff_lease_q4, cfg.round_q4,
        cfg.eff_guard_q4, cfg.majority, sync=cfg.sync, legs=legs,
        corrupt=cfg.corrupt, restart=cfg.restart, extend=cfg.extend,
        placed=cfg.placed,
    )
    if cfg.sync:
        layout = _SYNC_ARGS
    else:
        layout = _CORRUPT_ARGS if cfg.corrupt else _DELAYED_ARGS
        if cfg.restart:
            layout = layout + _RESTART_TAIL
        if cfg.extend:
            layout = layout + _EXTEND_TAIL
    return closed, layout


def analyze_tick_config(
    cfg: TickConfig, *, legs: str = "gather", core=None, layout=None,
) -> list[Finding]:
    """Prove (or refute) that replaying ticks ``[0, cfg.t_end]`` keeps
    every tick-core intermediate inside int32 and every pack inside its
    field budget. Returns the violations (empty = proven safe).

    ``core``/``layout`` override the traced core — the mutation fixtures
    use this to feed a seeded-bad variant through the same checker.
    """
    if core is None:
        core, layout = _core_and_layout(cfg, legs)
    jaxpr, consts = core.jaxpr, core.consts
    cfg_ivs = _input_intervals(cfg)
    n_state = sum(1 for _, kind in layout if kind.startswith("state"))
    state = [
        _init_state(kind) for _, kind in layout if kind.startswith("state")
    ]

    def args_for(state_vals):
        vals, si = [], 0
        for _, kind in layout:
            if kind.startswith("state"):
                vals.append(state_vals[si])
                si += 1
            else:
                vals.append(cfg_ivs[kind])
        return vals

    # fixpoint: join each pass's state outputs back into the state inputs
    silent = _Interp(report=None)
    for _ in range(_MAX_FIXPOINT_ITERS):
        outs = silent.eval_jaxpr(jaxpr, consts, args_for(state))
        new = [
            AbsVal(s.iv.join(o.iv))
            for s, o in zip(state, outs[:n_state])
        ]
        if all(n.iv == s.iv for n, s in zip(new, state)):
            break
        state = new
    else:  # pragma: no cover - the cores converge in a handful of passes
        state = [AbsVal(INT32)] * n_state

    # the reporting pass, on the converged invariant
    findings: list[Finding] = []
    _Interp(report=findings).eval_jaxpr(jaxpr, consts, args_for(state))
    return findings


def derived_max_pack_tick(
    n_proposers: int,
    lease_q4: int,
    max_delay_ticks: int = 0,
    max_rate: int = QUARTERS,
    clk_slack: int = 0,
    *,
    n_acceptors: int = 5,
    round_q4: int = QUARTERS,
    guard_q4: Optional[int] = None,
    sync: bool = False,
    max_restarts: int = 0,
) -> int:
    """``state.max_pack_tick`` as a *derived* result: the largest ``t_end``
    the interval analysis proves safe, by monotone binary search (larger
    horizons only widen intervals, so safety is downward-closed).

    Signature mirrors the hand formula so tests can diff them on a grid.
    """
    base = TickConfig(
        t_end=0, n_proposers=n_proposers, n_acceptors=n_acceptors,
        lease_q4=lease_q4, round_q4=round_q4, guard_q4=guard_q4,
        max_delay=max_delay_ticks, max_rate=max_rate, clk_slack=clk_slack,
        sync=sync, max_restarts=max_restarts,
    )
    core, layout = _core_and_layout(base, "gather")

    def safe(t_end: int) -> bool:
        return not analyze_tick_config(
            replace(base, t_end=t_end), core=core, layout=layout
        )

    if not safe(0):
        return -1  # the config can't even start (e.g. clk_slack too hot)
    lo, hi = 0, 1
    while safe(hi):
        lo, hi = hi, hi * 2
        if hi > INT32_MAX:
            return INT32_MAX  # pragma: no cover - ballots overflow far sooner
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if safe(mid) else (lo, mid)
    return lo
