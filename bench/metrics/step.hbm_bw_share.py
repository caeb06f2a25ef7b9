"""HBM bytes the window's served steps must read (weights per task step
and per prefill chunk, KV prefix or SSM state; work.served_work) over
the window times the chip's HBM bandwidth."""


def read(w):
    if w.peaks is None:
        return None
    tot = w.work.totals(w.models, w.tasks, w.records, w.rows)
    return 100.0 * tot["bytes"] / (w.window_s * w.peaks["hbm_bytes_per_s"])
