"""Rows decoded per fused decode step over the window's epochs: the
sum of rows x steps of every decode item over the sum of each epoch's
step count (its largest k), from the server's per-epoch counters
(``host.epoch_log``).  Nothing where the server keeps no such
counters."""


def read(w):
    log = w.host.get("epoch_log")
    if not log:
        return None
    steps = sum(e["decode_steps"] for e in log)
    if not steps:
        return None
    return sum(e["decode_row_steps"] for e in log) / steps
