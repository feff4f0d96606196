"""Device milliseconds per restart round spent selecting anchors: the own
device time of the operations in the ``anchor_select`` scope inside the
window, over the sum of ``rounds`` (from each batch's ``fns.unpack`` span)
of the window's batches. None where the program has no scopes or no
counter."""
import program_trace


def read(ctx):
    prog = program_trace.trace_of(__file__)
    return prog.per_unit_ms("anchor_select", "rounds") if prog else None
