"""50th percentile over the window's tasks of the time from the
dispatch of a task's first prefill chunk to its first token on the
host: its chunked prefill, behind the device work queued before it (the
server's per-task stamps, ``host.tasks``).  Nothing where the server
keeps no such stamps."""
import numpy as np


def read(w):
    tasks = w.host.get("tasks")
    if not tasks:
        return None
    waits = [tasks[r["tid"]]["first_token"]
             - tasks[r["tid"]]["first_dispatch"] for r in w.records]
    return 1e3 * float(np.percentile(waits, 50))
