"""Share of the traced window in which no operation ran on the device, in %:
1 - (union of device-op intervals) / (traced window)."""


def read(tr):
    if not tr.devices:
        return None
    return (1 - tr.busy_s() / tr.window_s()) * 100
