"""Host time per engine round in which the device was idle: the part of the
driver's ``bench.step`` spans (one ``Engine.stream_step`` each) outside
device busy time, over the rounds (``EngineStats.syncs``) in the window."""


def read(b, chip):
    tr, c = b.trace, b.counts
    if tr is None or not tr.ops or not c.get("syncs"):
        return None
    return 1e3 * tr.host_seconds_outside_busy("bench.step") / c["syncs"]
