"""dir.policy_ms_p50: the median over the window's ticks of a directory
tick's wall time less the time of the engine calls it made (host clock):
the directory's own host policy."""


def read(ctx):
    run = ctx["run"]
    if not run.attempted:
        return None
    return run.host_split_ms()[0]
