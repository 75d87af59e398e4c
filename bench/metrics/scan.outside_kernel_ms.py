"""scan.outside_kernel_ms: device time per call of the operations outside
the window kernel's events (the scan driver's relayouts, pack and unpack,
padding and reductions), from the trace."""
from bench.kernel_bytes import KERNEL_EVENT


def read(ctx):
    trace, run = ctx["trace"], ctx["run"]
    if not trace.op_count(KERNEL_EVENT) or not run.attempted:
        return None
    outside = trace.op_seconds() - trace.op_seconds(KERNEL_EVENT)
    return 1e3 * outside / run.attempted
