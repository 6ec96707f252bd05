"""Share of the traced serving window in which no op ran on the device,
%: 1 - busy union / window."""


def read(run):
    t = run.get("trace")
    if run["kind"] != "serve" or not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
