"""engine.checks_ms: the mean over the traced window's ``run_trace``
calls of their ``lease.validate`` span, in ms: the scenario's validation,
the pack budget and its static proof, and the default-plane tests."""
from bench.program_spans import mean_child_ms, of_ctx


def read(ctx):
    return mean_child_ms(of_ctx(ctx), "lease.run_trace", "lease.validate")
