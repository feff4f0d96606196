"""Device milliseconds per lockstep walk iteration: the own device time of
the operations in the ``walk_hop`` scope inside the window, over the sum of
``iters`` (the iterations the program ran, from each batch's
``fns.unpack`` span) of the window's batches. None where the program has
no scopes or no counter."""
import program_trace


def read(ctx):
    prog = program_trace.trace_of(__file__)
    return prog.per_unit_ms("walk_hop", "iters") if prog else None
