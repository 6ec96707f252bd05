"""Share of the traced window in which no op ran on the device, %,
averaged over the chips: 1 - busy union / window."""


def read(run):
    t = run.get("trace")
    if run["kind"] != "train" or not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
