"""dir.renew_ms_p95: the 95th percentile over the traced window's
directory ticks of their ``lease.dir.renew`` span, in ms: the policy's
loop over the shards inside the renew margin."""
from bench.program_spans import named, of_ctx
from bench.stats import tail


def read(ctx):
    renew = named(of_ctx(ctx), "lease.dir.renew")
    if not renew:
        return None
    return 1e3 * tail([s.seconds for s in renew], 95)
