"""Host time the server spent admitting arrivals (weights, caches and
prompt made per task), per arrival in the window."""


def read(w):
    return 1e3 * w.host["admit_wall_s"] / len(w.tasks)
