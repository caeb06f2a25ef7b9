"""Programs JAX built (compiled or read from the persistent cache)
while the window ran: the warm-up should have built every one."""


def read(w):
    return float(w.programs_built)
