"""Work of the Phase-2/3 pour kernel (``kernels/act_phase2``) over the
whole corpus.

Per search call of nq queries against rows holding R real bins in all:
a multiply-add per rung (k = iters + 1 rungs) of every real bin of every
row, for every query; the bytes of reading each real bin's weight and
id once, each query's (v, k) cost ladder and (v, iters) capacities once,
and writing each query's n costs once. The ladders the kernel is handed
pre-gathered per padded slot do not count."""
TRACE_NAME = "act_phase2_pallas"


def per_call(c: dict):
    e = c["engine"]
    if e.get("cascade") or e["method"] != "act" or e["iters"] < 1:
        return None
    nq, it, v, n = len(c["q_len"]), e["iters"], c["v"], c["n"]
    flops = 2.0 * (it + 1) * nq * c["row_nnz"]
    nbytes = 8.0 * c["row_nnz"] + nq * (4.0 * v * (2 * it + 1) + 4.0 * n)
    return flops, nbytes
