"""Device ms per PPO iteration of the rollout kernel: the Pallas calls of
the train program (``kernels/aip_step.py::policy_rollout`` is the only
one), per chip."""


def read(run):
    t = run.get("trace")
    if run["kind"] != "train" or not t or t["kernel_s"] <= 0:
        return None
    return t["kernel_s"] / run["iterations"] * 1e3
