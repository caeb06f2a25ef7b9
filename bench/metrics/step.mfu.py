"""Model FLOPs of every prefilled and decoded token of the window's
tasks (work.served_work) over the window times the chip's bf16 peak."""


def read(w):
    if w.peaks is None:
        return None
    tot = w.work.totals(w.models, w.tasks, w.records, w.rows)
    return 100.0 * tot["flops"] / (w.window_s * w.peaks["bf16_flops_per_s"])
