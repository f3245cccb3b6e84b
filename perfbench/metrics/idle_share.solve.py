"""1 - busy / window over the traced solves; busy is the union of device op
intervals, averaged over the chips."""


def read(b, chip):
    if b.trace is None or not b.trace.ops:
        return None
    return 100.0 * b.trace.idle_share()
