"""Median time from a request's due time to the host-clock start of the
``EmdServer`` launch that carried it, in ms: the generator's lateness,
the fill-or-deadline wait and the launches ahead of it. Nothing where the
launches do not account for every request."""


def read(rec):
    return rec.win.counters.get("queue_wait_ms")
