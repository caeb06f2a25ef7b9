"""90th percentile over the window's tasks of the time from a task's
due time to the dispatch of its first prefill chunk: its wait behind
the arrivals admitted before it, its own admission and the admissions
after it in the same planning call (the server's per-task stamps,
``host.tasks``).  Nothing where the server keeps no such stamps."""
import numpy as np


def read(w):
    tasks = w.host.get("tasks")
    if not tasks:
        return None
    waits = [tasks[r["tid"]]["first_dispatch"] - tasks[r["tid"]]["due"]
             for r in w.records]
    return 1e3 * float(np.percentile(waits, 90))
