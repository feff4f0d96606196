"""Host milliseconds per batch spent unpacking its results: the time
inside the program's ``fns.unpack`` span of each batch of the window in
which no operation ran on the device, as a mean. None where the program
has no such span."""
import program_trace


def read(ctx):
    prog = program_trace.trace_of(__file__)
    return prog.host_ms(("fns.unpack",)) if prog else None
