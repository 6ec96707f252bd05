"""The rollout kernel's share of its roofline, %: the least time its
operations and bytes allow on this chip (``bench/lib/flops.py``, from the
configuration's shapes), over its measured device time per iteration."""
from bench.lib import flops


def read(run):
    t = run.get("trace")
    if run["kind"] != "train" or not t or t["kernel_s"] <= 0:
        return None
    cfg, mix, peaks = run["cfg"], run["mix"], run["peaks"]
    lanes = cfg["n_agents"] * mix["n_envs"] // run["chips"]
    ops, nbytes = flops.rollout_kernel_cost(
        cfg, lanes, cfg["ppo"]["rollout_len"], cfg["n_agents"])
    least = max(ops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (t["kernel_s"] / run["iterations"])
