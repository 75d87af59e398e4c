"""dir.engine_host_ms_p50: the median over the traced window's directory
ticks of the host time of the engine calls each makes, in ms: its
``lease.step`` and ``lease.ticks_left`` spans less their ``lease.wait``
children (the wait for the device)."""
import numpy as np

from bench.program_spans import named, of_ctx

CALLS = ("lease.step", "lease.ticks_left")


def read(ctx):
    per_tick = [
        sum(c.seconds - sum(w.seconds for w in c.named("lease.wait"))
            for name in CALLS for c in tick.named(name))
        for tick in named(of_ctx(ctx), "lease.dir.tick")
    ]
    if not per_tick:
        return None
    return 1e3 * float(np.median(per_tick))
