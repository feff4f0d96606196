"""Share of the lane slots the lockstep walk computed that belonged to a
lane still walking: the hops of the batch's queries over the slots its
walk iterations ran (each iteration's width, summed), both from each
batch's ``fns.unpack`` span and summed over the window's batches. Read on
a chip that ``peaks.json`` names, as the other shares of the chip's work
are. None where the program counts no slots (one whose walk runs every
iteration at the full batch width)."""
import program_trace


def read(ctx):
    prog = program_trace.trace_of(__file__)
    if prog is None or not ctx["peak"]:
        return None
    slots = prog.counter("slots")
    return prog.counter("hops") / slots if slots > 0 else None
