"""Real (non-pad) lanes per dispatch in the window, from the server's
``ServeStats`` counters."""


def read(run):
    if run["kind"] != "serve" or not run["dispatches"]:
        return None
    return run["real_lanes"] / run["dispatches"]
