"""95th percentile of how late the load generator sent requests after
their due times, in ms: the event loop held by synchronous launches."""


def read(rec):
    return rec.win.counters.get("late_ms_p95")
