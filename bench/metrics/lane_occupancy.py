"""Share of lane-hops that did work in the lockstep walk: the hops of the
real lanes over (padded lanes x the batch's largest hop count), summed
over the window's batches."""


def read(ctx):
    c = ctx["counters"]
    done = sum(int(h.sum()) for h in c["hops"])
    room = sum(c["lanes"] * int(h.max()) for h in c["hops"] if h.size)
    return done / room if room else None
