"""Share of the traced window in which no operation ran on the device:
1 - (union of device-busy intervals) / (traced window)."""


def read(red, run):
    return 100.0 * red.idle_share if red.window_ns > 0 else None
