"""50th percentile over the window's tasks of the time from a task's
due time (the server's step clock passing its arrival) to its first
token, as the server stamps it after blocking on that token."""
import numpy as np


def read(w):
    ttft = [r["ttft_s"] for r in w.records]
    if not ttft or any(t is None for t in ttft):
        return None
    return float(np.percentile(ttft, 50))
