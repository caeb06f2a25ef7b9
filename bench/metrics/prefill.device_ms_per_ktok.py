"""Device time of the prefill-chunk programs per 1,000 prompt tokens
(rows times chunk length): the traced chunk programs' mean time over the
window's mean chunk.  (The trace can stop short of the window's end, so
the traced chunks are not all of the window's.)"""


def read(w):
    if w.trace is None:
        return None
    p = w.trace["programs"]["prefill_chunk"]
    chunks = sum(len(r["prefill_chunks"]) for r in w.records)
    if not p["count"] or not chunks:
        return None
    ktok_per_chunk = sum(w.rows * t["prompt"] for t in w.tasks) / chunks / 1e3
    return 1e3 * p["seconds"] / p["count"] / ktok_per_chunk
