"""Parse collective ops, and the VMEM of Mosaic kernels, out of compiled HLO
text.

``cost_analysis()`` does not expose collective bytes, so we regex the
post-SPMD module: every all-reduce / all-gather / reduce-scatter / all-to-all
/ collective-permute result shape is summed (result-shape bytes are a ring-
transfer proxy for bytes moved per device).

``lax.scan`` lowers to a while loop whose body HLO appears ONCE, so
collectives reachable from a while-body computation are scaled by the trip
count supplied by the caller (= n_layers for the layer scan). Reachability is
computed over the real call graph (``body=%comp``, ``calls=%comp``,
``condition=%comp`` edges) — collectives usually sit inside fusion
computations called from the body, not in the body computation itself.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?\s*->.*\{\s*$")
_OP_RE = re.compile(r"=\s*(\([^=]*?\)|\S+)\s+(" + "|".join(COLLECTIVES) + r")(-(start|done))?\(")
_EDGE_RE = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)=\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_BODY_RE = re.compile(r"\bbody=%?([\w.\-]+)")
_SCOPED_VMEM_RE = re.compile(
    r'"used_scoped_memory_configs":\[\{[^\]]*?"size":"(\d+)"'
)


def _bytes_of_type(tstr: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(tstr):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    per_op: dict = field(default_factory=lambda: defaultdict(int))  # op -> bytes
    per_op_count: dict = field(default_factory=lambda: defaultdict(int))
    total_bytes: int = 0

    def as_dict(self):
        return {
            "total_bytes": self.total_bytes,
            "by_op_bytes": dict(self.per_op),
            "by_op_count": dict(self.per_op_count),
        }


def _scan(hlo_text: str):
    """One pass: collectives per computation + call-graph edges + while bodies."""
    current = ""
    found = []  # (comp, op, bytes)
    edges: dict[str, set] = defaultdict(set)
    body_roots: set[str] = set()
    seen_comps: set[str] = set()
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m and "{" in line:
            current = m.group(1)
            seen_comps.add(current)
            continue
        for em in _EDGE_RE.finditer(line):
            for name in em.group(1).split(","):
                edges[current].add(name.strip().lstrip("%"))
        bm = _BODY_RE.search(line)
        if bm:
            body_roots.add(bm.group(1))
        om = _OP_RE.search(line)
        if om:
            tstr, op, _, startdone = om.group(1), om.group(2), om.group(3), om.group(4)
            if startdone == "done":
                continue
            found.append((current, op, _bytes_of_type(tstr)))
    return found, edges, body_roots


def _reachable(roots: set, edges: dict) -> set:
    out, stack = set(), list(roots)
    while stack:
        c = stack.pop()
        if c in out:
            continue
        out.add(c)
        stack.extend(edges.get(c, ()))
    return out

def parse_collectives(hlo_text: str, *, body_trip_counts: dict | None = None) -> CollectiveStats:
    """body_trip_counts: {"body": L} scales every collective reachable from a
    while-loop body by L (the layer-scan trip count). Collectives outside any
    loop (grad sync, logits) count once."""
    mult_default = 1
    trip = 1
    if body_trip_counts:
        trip = max(body_trip_counts.values())
    found, edges, body_roots = _scan(hlo_text)
    in_loop = _reachable(body_roots, edges)
    stats = CollectiveStats()
    for comp, op, nbytes in found:
        mult = trip if comp in in_loop else mult_default
        stats.per_op[op] += nbytes * mult
        stats.per_op_count[op] += mult
        stats.total_bytes += nbytes * mult
    return stats


def kernel_scoped_vmem(hlo_text: str) -> list[int]:
    """Bytes of VMEM the TPU compiler scoped for each Mosaic kernel call
    (``tpu_custom_call``) of a compiled module, in program order."""
    return [
        int(size)
        for line in hlo_text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
        for size in _SCOPED_VMEM_RE.findall(line)
    ]
