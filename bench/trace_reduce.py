"""From a JAX profiler trace to the numbers the per-layer metrics read.

A traced run records the window under ``jax.profiler`` and wraps it, and
each call into a layer, in host spans named ``bench.*``
(``jax.profiler.TraceAnnotation``). This reads the ``.xplane.pb`` file
with JAX's own ``ProfileData``:

- device operations: the events of each device plane's ``XLA Ops`` line;
- busy time: per device, the union of its operations' intervals inside
  the ``bench.window`` span, averaged over the devices that ran any;
  the idle share is one minus busy time over the window;
- idle gaps: the stretches of the first busy device's window in which no
  operation ran, each named by the innermost ``bench.*`` host span that
  covers its middle (``idle`` where none does);
- time by operation name, and the time of the operations whose name
  matches a pattern (a kernel's events).
"""
from __future__ import annotations

import re
from pathlib import Path

HOST_SPAN = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


def _union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end] intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Reduced:
    """The reduced trace. Times in seconds."""

    def __init__(self, ops: dict, spans: list) -> None:
        #: device name -> [(op name, start_ns, end_ns)]
        self.ops = {d: evs for d, evs in ops.items() if evs}
        #: [(span name, start_ns, end_ns)] of the bench.* host spans
        self.spans = spans
        windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
        if windows:
            self.lo, self.hi = windows[0]
        else:
            every = [(s, e) for evs in self.ops.values() for _, s, e in evs]
            every += [(s, e) for _, s, e in spans]
            self.lo = min((s for s, _ in every), default=0.0)
            self.hi = max((e for _, e in every), default=0.0)
        self.devices = sorted(self.ops)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy(self, device: str) -> list:
        return _union(
            [(s, e) for _, s, e in self.ops[device]], self.lo, self.hi
        )

    @property
    def busy_s(self) -> float:
        per = [sum(e - s for s, e in self.busy(d)) for d in self.devices]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: str | None = None) -> float:
        """Device time of the operations whose name matches ``pattern``
        (every operation when None), summed over devices."""
        rx = re.compile(pattern) if pattern else None
        return 1e-9 * sum(
            e - s for evs in self.ops.values() for n, s, e in evs
            if rx is None or rx.search(n)
        )

    def op_count(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(
            1 for evs in self.ops.values() for n, _, _ in evs if rx.search(n)
        )

    def by_name(self) -> list:
        """[(op name, seconds)], most time first."""
        tot = {}
        for evs in self.ops.values():
            for n, s, e in evs:
                tot[n] = tot.get(n, 0.0) + (e - s) * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])

    def span_at(self, t: float) -> str:
        """The innermost host span covering time ``t``, or ``idle``."""
        best = None
        for n, s, e in self.spans:
            if n != WINDOW_SPAN and s <= t <= e and (best is None or s > best[1]):
                best = (n, s)
        return best[0] if best else "idle"

    def idle_gaps(self) -> list:
        """[(host span, seconds)] of every idle stretch of the first busy
        device inside the window, longest first."""
        if not self.devices:
            return []
        gaps, cursor = [], self.lo
        for s, e in self.busy(self.devices[0]) + [[self.hi, self.hi]]:
            if s > cursor:
                gaps.append((self.span_at((s + cursor) / 2), (s - cursor) * 1e-9))
            cursor = max(cursor, e)
        return sorted(gaps, key=lambda g: -g[1])

    def breakdown(self, n: int = 10) -> dict:
        return {
            "device_ops": [[k, v] for k, v in self.by_name()[:n]],
            "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:n]],
        }


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def reduce_trace(path) -> Reduced:
    """Reduce the newest ``.xplane.pb`` under ``path`` (or that file)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        files = sorted(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    data = ProfileData.from_file(str(path))
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            ops[plane.name] = [ev for ln in lines for ev in _events(ln)]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [ev for ev in _events(ln) if ev[0].startswith(HOST_SPAN)]
    return Reduced(ops, spans)
