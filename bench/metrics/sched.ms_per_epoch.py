"""Host time the server spent planning each epoch (grants, plans,
batching), admission excluded."""


def read(w):
    if not w.host["epochs"]:
        return None
    return 1e3 * w.host["sched_wall_s"] / w.host["epochs"]
