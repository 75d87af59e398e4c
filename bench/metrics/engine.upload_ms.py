"""engine.upload_ms: the mean over the traced window's ``run_trace``
calls of their ``lease.upload`` span, in ms: the scenario's planes put on
the device (the span ends when they are there)."""
from bench.program_spans import mean_child_ms, of_ctx


def read(ctx):
    return mean_child_ms(of_ctx(ctx), "lease.run_trace", "lease.upload")
