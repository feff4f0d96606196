"""The search program's walk share of its bandwidth roofline: the rows the
walk must read (``work.walk_bytes`` over every lane's hops in the window),
at the chip's HBM bandwidth, over the search program's device time less
the filter_eval kernel's. The search program is the longest program run
in each ``bench.query_batch`` span, whatever kernels it holds."""
import work


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    runs = t.main_runs("bench.query_batch")
    seconds = t.run_seconds(runs) - t.op_seconds(t.is_filter_eval)
    if not runs or seconds <= 0 or "hbm_bytes_per_s" not in ctx["peak"]:
        return None
    hops = sum(int(h.sum()) for h in c["hops"])
    need = work.walk_bytes(hops, c["mean_degree"], c["d"])
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / seconds
