"""Share of the traced time in which the batcher held work (requests
queued or in slots) and no operation ran on the device, in percent."""


def read(run):
    t = run.trace
    if t is None or t.pending_s <= 0:
        return None
    return 100.0 * t.idle_share
