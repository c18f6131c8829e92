"""95th percentile over every request of the window of the time from its
due time to its answer in host memory, in ms: the queue's tail, where a
backlog behind slow launches shows first."""


def read(rec):
    return rec.win.e2e.get("latency_p95_ms")
