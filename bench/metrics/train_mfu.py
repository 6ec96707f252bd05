"""Model FLOP utilisation of training, %: model operations per sample
(``bench/lib/flops.py``: rollout policy + AIP forward, 4 epochs of policy
forward and backward) times the traced window's samples per second, over
chips times the chip's peak. Each sample counts once however many chips
repeat its work."""
from bench.lib import flops


def read(run):
    if run["kind"] != "train" or not run.get("trace"):
        return None
    per_sample = flops.model_flops_per_sample(run["cfg"])
    return (100.0 * per_sample * run["samples_per_s"]
            / (run["chips"] * run["peaks"]["flops_per_s"]))
