"""Host milliseconds per batch spent forming and packing it: the time
inside the program's ``fns.form`` and ``fns.pack`` spans of each batch of
the window in which no operation ran on the device, as a mean. None where
the program has no such spans."""
import program_trace


def read(ctx):
    prog = program_trace.trace_of(__file__)
    return prog.host_ms(("fns.form", "fns.pack")) if prog else None
