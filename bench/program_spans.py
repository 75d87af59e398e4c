"""The program's own host spans in a traced run, and their counters.

The lease plane names its host work with ``jax.profiler.TraceAnnotation``
spans called ``lease.*`` (the engine's entry points and their phases,
the directory's tick and its policy steps) and hangs its counters on
them as event stats. They land in the same ``.xplane.pb`` as the device's
operations, on the same clock. This reads them from the newest trace of
a cell, ``bench.run.TRACE_DIR / <cell>``:

- ``spans(cell)``: every ``lease.*`` event of the host planes, nested by
  time on its thread's line (each span's ``parent`` and ``children``);
  each trace is parsed once;
- ``self_s(span)``: the span's time less its children's;
- ``idle_by_span(trace, spans)``: the idle stretches of the first busy
  device (``trace_reduce.Reduced.busy``), each given whole to the
  innermost ``lease.*`` span covering its middle.

A program without the spans gives an empty list, and every metric that
reads one gives None.

    python3 -m bench.program_spans <cell>

prints the spans of the cell's newest trace, by name (count, seconds,
self seconds, counters summed), and its idle seconds by span.
"""
from __future__ import annotations

import bisect
import functools
import json
import sys
import warnings
from dataclasses import dataclass, field
from pathlib import Path

PREFIX = "lease."


@dataclass(eq=False)
class Span:
    name: str
    start: float  # ns
    end: float  # ns
    stats: dict
    parent: Span | None = None
    children: list = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9

    def named(self, name: str) -> list:
        """The span's children called ``name``."""
        return [c for c in self.children if c.name == name]


def self_s(span: Span) -> float:
    return span.seconds - sum(c.seconds for c in span.children)


def nest(spans: list) -> list:
    """Link the spans of one thread by time: each span's parent is the
    innermost span that holds it. Returns them in start order."""
    spans = sorted(spans, key=lambda s: (s.start, -s.end))
    open_ = []
    for s in spans:
        while open_ and not (open_[-1].start <= s.start
                             and s.end <= open_[-1].end):
            open_.pop()
        if open_:
            s.parent = open_[-1]
            open_[-1].children.append(s)
        open_.append(s)
    return spans


def newest_trace(cell: str) -> Path | None:
    from bench.run import TRACE_DIR

    files = sorted((TRACE_DIR / cell).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


@functools.lru_cache(maxsize=8)
def _read(path: str, mtime_ns: int) -> tuple:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)  # its planes live only as long
    out = []
    with warnings.catch_warnings():
        # the stats' binding type warns once that it has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                out += nest([
                    Span(ev.name, float(ev.start_ns),
                         float(ev.start_ns + ev.duration_ns), dict(ev.stats))
                    for ev in line.events if ev.name.startswith(PREFIX)
                ])
    return tuple(sorted(out, key=lambda s: s.start))


def read_file(path) -> list:
    """The ``lease.*`` spans of one ``.xplane.pb`` file, parsed once."""
    path = Path(path)
    return list(_read(str(path), path.stat().st_mtime_ns))


def spans(cell: str) -> list:
    """The ``lease.*`` spans of the cell's newest trace ([] if none)."""
    path = newest_trace(cell)
    return read_file(path) if path else []


def of_ctx(ctx: dict) -> list:
    """The spans of the traced run a metric reader is given."""
    return spans(ctx["cell"]["name"])


def named(spans: list, name: str) -> list:
    return [s for s in spans if s.name == name]


def mean_child_ms(spans: list, parent: str, child: str) -> float | None:
    """Mean over the ``parent`` spans of their ``child`` children's time,
    in ms (None without a ``parent`` span)."""
    parents = named(spans, parent)
    if not parents:
        return None
    total = sum(c.seconds for p in parents for c in p.named(child))
    return 1e3 * total / len(parents)


def skip_share(spans: list, name: str) -> float | None:
    """The window kernel's grid steps that took the quiescent path, in
    percent of all of its grid steps, summed over the ``name`` spans'
    counters (None where no window kernel ran)."""
    found = [s for s in named(spans, name) if "windows" in s.stats]
    windows = sum(s.stats["windows"] for s in found)
    if not windows:
        return None
    return 100.0 * sum(s.stats["skipped"] for s in found) / windows


def covering(spans: list, starts: list, t: float) -> Span | None:
    """The innermost of ``spans`` (in start order, ``starts`` their start
    times) that covers time ``t``: the latest begun at or before ``t``, or
    the nearest of its enclosing spans still open at ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    s = spans[i] if i >= 0 else None
    while s is not None and s.end < t:
        s = s.parent
    return s


def idle_by_span(trace, spans: list) -> dict:
    """Idle seconds of the first busy device of ``trace`` (a
    ``trace_reduce.Reduced``) inside its window, by the innermost
    ``lease.*`` span covering each idle stretch's middle; stretches no
    span covers go under None."""
    out = {}
    if not trace.devices:
        return out
    spans = sorted(spans, key=lambda s: s.start)
    starts = [s.start for s in spans]
    cursor = trace.lo
    for s, e in trace.busy(trace.devices[0]) + [[trace.hi, trace.hi]]:
        if s > cursor:
            inner = covering(spans, starts, (s + cursor) / 2)
            name = inner.name if inner else None
            out[name] = out.get(name, 0.0) + (s - cursor) * 1e-9
        cursor = max(cursor, e)
    return out


def summary(cell: str) -> dict:
    """Per span name: count, seconds, self seconds and summed counters;
    and the idle seconds by span, with the share no span covers."""
    from bench.run import TRACE_DIR
    from bench.trace_reduce import reduce_trace

    found = spans(cell)
    by_name = {}
    for s in found:
        row = by_name.setdefault(
            s.name, {"count": 0, "seconds": 0.0, "self_s": 0.0, "stats": {}}
        )
        row["count"] += 1
        row["seconds"] += s.seconds
        row["self_s"] += self_s(s)
        for k, v in s.stats.items():
            if isinstance(v, (int, float)):
                row["stats"][k] = row["stats"].get(k, 0) + v
    idle = idle_by_span(reduce_trace(TRACE_DIR / cell), found)
    total = sum(idle.values())
    return {
        "spans": by_name,
        "idle_by_span": {str(k): v for k, v in idle.items()},
        "idle_s": total,
        "idle_covered_share": (
            1 - idle.get(None, 0.0) / total if total else None
        ),
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[-1], file=sys.stderr)
        return 2
    print(json.dumps(summary(argv[0])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
