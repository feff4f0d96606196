"""Device microseconds per vector pass of the predicate sweep: the own
device time of the operations in the ``filter_eval`` scope inside the
window, over the sum of ``clause_passes`` (the passes the sweep makes for
a batch by the kernel's cost model, from each batch's ``fns.unpack``
span) of the window's batches. None where the program has no scopes or
does not count the passes."""
import program_trace


def read(ctx):
    prog = program_trace.trace_of(__file__)
    ms = prog.per_unit_ms("filter_eval", "clause_passes") if prog else None
    return None if ms is None else 1e3 * ms
