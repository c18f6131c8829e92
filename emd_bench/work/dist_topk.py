"""Work of the Phase-1 kernel (``kernels/dist_topk``): distances from
every vocabulary word to each query's real bins, and each word's k
nearest.

Per search call of queries with L_q real bins: 2 v m L_q operations of
the distance product (padded query slots do not count), and the bytes
of reading the vocabulary once, each query's real bins once, and
writing each query's (v, k) costs and bin indices once."""
TRACE_NAME = "dist_topk_pallas"


def per_call(c: dict):
    e = c["engine"]
    if e.get("cascade") or e["method"] != "act":
        return None
    L = float(c["q_len"].sum())
    v, m, k = c["v"], c["m"], e["iters"] + 1
    flops = 2.0 * v * m * L
    nbytes = 4.0 * v * m + 4.0 * (m + 1) * L + 8.0 * v * k * len(c["q_len"])
    return flops, nbytes
