"""99th percentile of the latency of every request due in the serving
window, ms, from its due time (a request never served counts as 1e9 ms).
It is the tail a user feels, but it is kept per layer: a host stall of
about a tenth of a second, which most windows on a v5e host hold and
some do not, sets it alone (PERF.md section 2)."""


def read(run):
    if run["kind"] != "serve":
        return None
    return run["latency_ms"]["p99"]
