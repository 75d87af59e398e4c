"""dir.engine_ms_p50: the median over the window's ticks of the time of
the engine calls a directory tick makes, ``engine.step`` and
``engine.ticks_left`` (host clock, each call ending with its result on
the host)."""


def read(ctx):
    run = ctx["run"]
    if not run.attempted:
        return None
    return run.host_split_ms()[1]
