"""Device ms per dispatch of the slot forward: the Pallas calls of the
serving program (``kernels/aip_step.py::serve_forward``)."""


def read(run):
    t = run.get("trace")
    if run["kind"] != "serve" or not t or t["kernel_s"] <= 0:
        return None
    return t["kernel_s"] / run["dispatches"] * 1e3
