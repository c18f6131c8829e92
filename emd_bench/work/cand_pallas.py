"""Work of the fused candidate kernel (``kernels/cand_pour``) in a
cascade: every stage after the first, and the rescorer, score only the
rows the stage before kept.

Per query and stage over ``rows`` candidates of mean real bins b, with
k rungs (1 for RWMD, iters + 1 for ACT): a multiply-add per rung of
every real bin; the bytes of reading each candidate's real bins (weight
and id) once, the query's (v, k) costs and (v, k - 1) capacities once,
and writing one cost per candidate. The one-hot products the kernel
gathers with do not count."""
TRACE_NAME = "cand_pallas"


def _rungs(method: str, iters: int) -> int:
    if method == "rwmd":
        return 1
    if method == "act":
        return iters + 1
    raise ValueError(f"no work count for candidate stage {method!r}")


def per_call(c: dict):
    from emd_bench.reference import budgets

    e = c["engine"]
    if not e.get("cascade"):
        return None
    nq, v, n = len(c["q_len"]), c["v"], c["n"]
    b = c["row_nnz"] / n
    keep = budgets(e["stages"], n, c["top_l"])
    scored = [(s[0], s[2] if len(s) > 2 else 1, rows)
              for s, rows in zip(e["stages"][1:], keep[:-1])]
    scored.append((e["rescorer"][0], e["rescorer"][1], keep[-1]))
    flops = nbytes = 0.0
    for method, iters, rows in scored:
        k = _rungs(method, iters)
        flops += nq * rows * b * 2.0 * k
        nbytes += nq * (rows * (8.0 * b + 4.0) + 4.0 * v * (2 * k - 1))
    return flops, nbytes
