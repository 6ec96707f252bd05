"""Shape-based operation and byte counts against hand counts, and the
peak table."""
import pytest

from bench.lib import cells, device, flops


def _tiny(kind):
    return {"obs_dim": 3, "dset_dim": 2, "n_influence": 1, "n_actions": 2,
            "n_agents": 2, "ls_state_words": 3, "ls_noise_words": 1,
            "policy": {"kind": "mlp", "hidden": 4, "frame_stack": 2},
            "aip": {"kind": kind, "hidden": 5, "stack": 3},
            "ppo": {"epochs": 2}}


def test_policy_and_aip_flops_by_hand():
    cfg = _tiny("fnn")
    # policy 6 -> 4 -> 4 -> 3: 2 * (24 + 16 + 12)
    assert flops.policy_flops(cfg) == 104
    # FNN 6 -> 5 -> 5 -> 1: 2 * (30 + 25 + 5)
    assert flops.aip_flops(cfg) == 120
    # GRU (2 + 5) -> 15, 5 -> 1: 2 * (30 + 75 + 5)
    assert flops.aip_flops(_tiny("gru")) == 220
    # rollout fwd + AIP + 2 epochs x 3 forwards
    assert flops.model_flops_per_sample(cfg) == 104 + 120 + 6 * 104


def test_rollout_kernel_cost_by_hand():
    cfg = _tiny("fnn")
    ops, nbytes = flops.rollout_kernel_cost(cfg, lanes=4, T=2, agents=2)
    assert ops == 4 * 2 * (104 + 120)
    lane = 2 * (3 + 6 + 6)                   # LS, AIP stack, frames
    tick = (2 + 1 + 1 + 1 + 3) + (6 + 1 + 2 + 1 + 1)
    w = 2 * (6 * 5 + 5 + 25 + 5 + 5 + 1) + (24 + 4 + 16 + 4 + 12 + 3)
    assert nbytes == 4 * (4 * lane + 4 * 2 * tick + w)


def test_model_flops_of_the_configs():
    # about 0.62 and 1.47 MFLOP per sample
    assert flops.model_flops_per_sample(cells.config("traffic25_fnn")) \
        == 622080
    assert flops.model_flops_per_sample(cells.config("warehouse36_gru")) \
        == 1466368


def test_peaks():
    assert device.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        device.peaks("TPU v99")
