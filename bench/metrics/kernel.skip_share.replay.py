"""kernel.skip_share.replay: the delayed window kernel's grid steps (cell
block x window) that took the quiescent path, in percent of all of its
grid steps, from the ``skipped`` and ``windows`` counters of every
``lease.run_trace`` span (fleet replay cells)."""
from bench.program_spans import of_ctx, skip_share


def read(ctx):
    return skip_share(of_ctx(ctx), "lease.run_trace")
