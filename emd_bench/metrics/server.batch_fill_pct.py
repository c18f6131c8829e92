"""Requests launched over the bucket slots launched, in %
(``ServerStats``: queries served over the sum of bucket x launches of
that bucket): how full the server's power-of-two buckets ran."""


def read(rec):
    return rec.win.counters.get("batch_fill_pct")
