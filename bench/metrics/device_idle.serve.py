"""Share of the traced serving window in which no operation ran on the
device (1 - union of device-busy intervals / window)."""


def read(ctx, trace):
    return 100.0 * trace.idle_share
