"""The filter_eval Pallas kernel's share of its bandwidth roofline: the
bytes one predicate sweep needs (``work.filter_eval_bytes``) for every
batch of the window, at the chip's HBM bandwidth, over the kernel's
device time."""
import work


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    seconds = t.op_seconds(t.is_filter_eval)
    if seconds <= 0 or "hbm_bytes_per_s" not in ctx["peak"]:
        return None
    need = c["batches"] * work.filter_eval_bytes(c["n"], c["fields"],
                                                 c["lanes"])
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / seconds
