"""device.idle_share.replay: the share of the traced window, in percent,
in which no operation ran on the device (fleet replay and sweep cells)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace.devices:
        return None
    return 100.0 * trace.idle_share
