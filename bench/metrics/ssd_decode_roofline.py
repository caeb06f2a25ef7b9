"""Share of its roofline that the in-place SSD decode kernel
(kernels/ssd_decode.py, ``ssd_decode`` in trace_names.json) reached in
the traced window: the least time of every traced call, each priced
from its label's shape (rows, heads and P of its readout ``y``) by the
family of the model with those heads and P (``ssd_decode_work``), over
the kernel's device seconds.  A call's least time is its bytes over the
chip's HBM bandwidth or its FLOPs over the bf16 peak, whichever is
larger: the bytes, by far.  Nothing where no traced call is priced."""
import re

import families

SHAPE = re.compile(r"^\S+ \(?f32\[(\d+),(\d+),(\d+)\]")


def price(models, rows, heads, p):
    """The call's work, where the models that have such a call agree."""
    works = []
    for entry in models.values():
        work = getattr(families.of(entry), "ssd_decode_work", None)
        got = work(entry, rows, heads, p) if work else None
        if got is not None and got not in works:
            works.append(got)
    return works[0] if len(works) == 1 else None


def read(w):
    if w.trace is None or w.peaks is None:
        return None
    calls = w.trace["kernels"].get("ssd_decode", {}).get("shapes", {})
    least, seconds = 0.0, 0.0
    for label, (sec, count) in calls.items():
        shape = SHAPE.match(label)
        work = shape and price(w.models, *map(int, shape.groups()))
        if not work:
            continue
        least += count * max(work["bytes"] / w.peaks["hbm_bytes_per_s"],
                             work["flops"] / w.peaks["bf16_flops_per_s"])
        seconds += sec
    if not seconds:
        return None
    return 100.0 * least / seconds
