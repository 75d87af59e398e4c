"""engine.download_ms: the mean over the traced window's ``run_trace``
calls of their ``lease.download`` span, in ms: the owners and owner
counts brought to the host once the device has made them."""
from bench.program_spans import mean_child_ms, of_ctx


def read(ctx):
    return mean_child_ms(of_ctx(ctx), "lease.run_trace", "lease.download")
