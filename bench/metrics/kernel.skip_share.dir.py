"""kernel.skip_share.dir: the delayed window kernel's grid steps (cell
block x window) that took the quiescent path, in percent of all of its
grid steps, from the ``skipped`` and ``windows`` counters of every
``lease.step`` span (directory cells)."""
from bench.program_spans import of_ctx, skip_share


def read(ctx):
    return skip_share(of_ctx(ctx), "lease.step")
