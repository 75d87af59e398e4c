"""engine.placement_ms: the mean over the traced window's ``run_trace``
calls of their ``lease.placement`` span, in ms: what a placement costs
outside the kernel (the map's host checks, its upload with the node
rows', and the per-cell restart table made on the device). Under a
trace the span waits for the table, so its device time lands here; an
untraced call overlaps that time with the upload. None where no call
carried a placement."""
from bench.program_spans import named, of_ctx


def read(ctx):
    calls = named(of_ctx(ctx), "lease.run_trace")
    placed = [c for c in calls if c.named("lease.placement")]
    if not placed:
        return None
    total = sum(s.seconds for c in placed for s in c.named("lease.placement"))
    return 1e3 * total / len(calls)
