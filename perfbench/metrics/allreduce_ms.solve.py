"""Device milliseconds per solve in all-reduce ops (the psum of the sampled
statistics, ``core/distributed.py``), averaged over the chips. A cell on one
chip has none, and reads nothing."""


def read(b, chip):
    tr, c = b.trace, b.counts
    if tr is None or not c.get("traced_solves"):
        return None
    secs = tr.op_seconds(r"^all-reduce")
    if secs <= 0:
        return None
    return 1e3 * secs / c["traced_solves"]
