"""Host milliseconds per ``query_batch``: each batch span's length less the
time in it in which an operation ran on the device, as a mean over the
window. It needs no name of a program or a kernel."""


def read(ctx):
    t = ctx["trace"]
    spans = t.spans("bench.query_batch")
    if not spans:
        return None
    host = [(e - s) / 1e9 - t.busy_seconds(s, e) for s, e in spans]
    return 1e3 * sum(host) / len(host)
