"""device.idle_share.dir: the share of the traced window, in percent,
in which no operation ran on the device (directory cells)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace.devices:
        return None
    return 100.0 * trace.idle_share
