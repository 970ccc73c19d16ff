"""Share of the traced window in which no operation ran on the device (%),
averaged over the cell's devices."""


def read(run):
    tr = run.trace
    if tr is None or not tr.device_names() or tr.window_s <= 0:
        return None
    return (1.0 - tr.busy_s() / tr.window_s) * 100.0
