"""Device time of the fused decode-epoch programs per logical step (one
step decodes one token in every row of every task in the epoch).

A task's epoch runs min(epoch_len, its steps left, the steps to its next
128-slot KV window) steps, counted from its prompt length.  Where every
prompt and every decode budget is a multiple of ``epoch_len`` (and
``epoch_len`` divides 128), every epoch runs ``epoch_len`` steps and an
execution's time divides by it.  Otherwise the steps of a traced
execution are not known here, and the metric is left out."""
KV_WINDOW = 128


def read(w):
    if w.trace is None:
        return None
    p = w.trace["programs"]["decode_epoch"]
    k = w.epoch_len
    if not p["count"] or KV_WINDOW % k or any(
            t["prompt"] % k or t["decode"] % k for t in w.tasks):
        return None
    return 1e3 * p["seconds"] / (p["count"] * k)
