"""Device: share (%) of the traced window in which no op ran on the chip,
averaged over the chips the cell uses."""


def read(r):
    return 100.0 * (1.0 - r.busy_s() / r.window_s)
