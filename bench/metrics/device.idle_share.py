"""Share of the traced window in which no operation ran on the device
(1 - the union of device operation intervals over the window)."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"])
