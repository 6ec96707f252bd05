"""A backbone or a policy is a module of its own, found by the kind a
configuration names, and every width a configuration states reaches the
program: the weights, the reference, the counts and the program built
from it."""
import copy
import hashlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import cells, domains, flops, serve, train, verdict, weights
from bench.reference import common

SEED = 2**31 + 77          # larger than 32 signed bits hold

# sha256 over each leaf's tree path and bytes, from weights.make(cfg, SEED)
# on the CPU at the commit before the backbones moved into modules of
# their own
PARENT_WEIGHTS = {
    "traffic25_fnn":
        "84d2246207c4e13552f6116aa1ddac98f3e64b65a4e33a50f8b51a1b78051cd9",
    "warehouse36_gru":
        "9d6e925691b2083fa68a5ec72cecf2361102deb30f65e4cc440b58d4d6c450cd",
}


def _digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _small(name, agents=2):
    cfg = copy.deepcopy(cells.config(name))
    cfg["n_agents"] = agents
    return cfg


@pytest.mark.parametrize("name", sorted(PARENT_WEIGHTS))
def test_weights_bitwise_as_before(name):
    assert _digest(weights.make(cells.config(name), SEED)) \
        == PARENT_WEIGHTS[name]


# sha256 as above over the reference's record of its three checked
# iterations (losses, rewards, values, Adam's first moment after one, the
# parameters after three) at ``_tiny_train``'s size, from the weights of
# SEED, on the CPU at the commit before the learner moved out of
# ``bench/reference/common.py`` into ``pooled.py``
PARENT_LEARNER = {
    ("traffic25_fnn.train_b256", "float32"):
        "dc49519ebc64984c81762df75895f242883594a4e57d617e4bbfe0b0b22f348f",
    ("traffic25_fnn.train_b256", "bfloat16"):
        "51c279ea99c41c05e125795b05b9d58ae3136b1ffee1f7c33775dd8b28cf763f",
    ("warehouse36_gru.train_b256", "float32"):
        "40b3ebef9741c04de69b3a757d58987365ae1d83845f69e8ded1b5238ae5196d",
    ("warehouse36_gru.train_b256", "bfloat16"):
        "5ebd909bfa0ccd4b6f6d9f241c0511098cdfa76cc83b44d9ebd02e97d34f251a",
}


@pytest.mark.parametrize("name,dt", sorted(PARENT_LEARNER))
def test_pooled_learner_bitwise_as_before(name, dt):
    """The reference and its bf16 control, learnt by the mlp kind's pooled
    learner through ``common.learn``, read the recorded digests to the
    bit."""
    cfg, mix = _tiny_train(name)
    w = weights.make(cfg, SEED)
    rec = train.reference_steps(cfg, mix, SEED, train.host(w["policy"]),
                                w["aip"], jnp.dtype(dt))
    got = {k: np.asarray(v, np.float64) if isinstance(v, list) else v
           for k, v in rec.items() if k != "p0"}
    assert _digest(got) == PARENT_LEARNER[(name, dt)]


def _aip_uses(cfg):
    B, A = 3, cfg["n_agents"]
    d = jnp.zeros((B, A, cfg["dset_dim"]))
    return {"weights": lambda: weights.make(cfg, SEED),
            "zero": lambda: common.aip_zero(cfg, B, A),
            "step": lambda: common.aip_step(cfg, None, None, d, jnp.float32),
            "flops": lambda: flops.aip_flops(cfg),
            "kernel_cost": lambda: flops.rollout_kernel_cost(cfg, 4, 2, A)}


def _policy_uses(cfg):
    x = jnp.zeros((3, cfg["obs_dim"] * cfg["policy"]["frame_stack"]))
    return {"weights": lambda: weights.make(cfg, SEED),
            "forward": lambda: common.policy(cfg, None, x, jnp.float32),
            "learn": lambda: common.learn(cfg, None, None, None, None, None,
                                          jnp.float32),
            "flops": lambda: flops.policy_flops(cfg),
            "kernel_cost": lambda: flops.rollout_kernel_cost(cfg, 4, 2, 2)}


USES = {"aip": _aip_uses, "policy": _policy_uses}


@pytest.mark.parametrize("use", ["weights", "reference", "flops",
                                 "kernel_cost"])
@pytest.mark.parametrize("block", sorted(USES))
def test_unknown_kind_raises(block, use):
    cfg = _small("warehouse36_gru")
    cfg[block]["kind"] = "lstm"
    calls = USES[block](cfg)
    ref = "step" if block == "aip" else "forward"
    with pytest.raises(ValueError, match=f"'lstm'.*lstm.py.*{block}"):
        calls[ref if use == "reference" else use]()


def _stand_in(block, cfg):
    """A module of a new kind, planted where the lookup finds it: each
    function answers with a value no real kind gives."""
    A = cfg["n_agents"]
    mod = types.ModuleType(f"bench.reference.{block}.stand_in")
    mod.init = lambda cfg_, key: {"w": jnp.full((A, 3), 7.0)}
    mod.zero = lambda cfg_, B, A_: jnp.full((B, A_, 5), 7.0)
    mod.step = lambda cfg_, w, s, d, dt: (s, jnp.full(d.shape[:2] + (1,),
                                                      7.0))
    mod.forward = lambda p, x, dt: (jnp.full(x.shape[:-1] + (2,), 7.0),
                                    jnp.zeros(x.shape[:-1]))
    mod.learn = lambda cfg_, pol, opt, batch, v_last, key, dt: (
        pol, opt, jnp.float32(7.0))
    mod.flops = lambda cfg_: 7_000_000
    mod.state_words = lambda cfg_: 7_000
    mod.weight_words = lambda cfg_: 7_000_000
    return mod


@pytest.mark.parametrize("block", sorted(USES))
def test_a_new_kind_is_a_new_module(block, monkeypatch):
    """A backbone or policy added as one new file is reached through the
    weights, the reference and the counts: no existing file names it."""
    cfg = _small("traffic25_fnn")
    cfg[block]["kind"] = "stand_in"
    mod = _stand_in(block, cfg)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    for use, call in USES[block](cfg).items():
        got = call()
        if use == "weights":
            assert float(got[block]["w"][0, 0]) == 7.0
        elif use in ("zero", "step", "forward"):
            out = got if use == "zero" else got[1 if use == "step" else 0]
            assert float(out.reshape(-1)[0]) == 7.0
        elif use == "learn":
            assert float(got[2]) == 7.0
        elif use == "flops":
            assert got == 7_000_000
        else:
            ops, nbytes = got
            assert ops > 4 * 2 * 7_000_000 and nbytes > 4 * 7_000_000


def _tiny_train(name, **aip):
    """The cell's configuration and mix at a test's size (4 agents, 8 envs
    each, 16 ticks), with ``aip`` widths changed from the configuration."""
    cell = cells.cell(name)
    cfg = copy.deepcopy(cells.config(cell["config"]))
    mix = copy.deepcopy(cells.mix(cell["traffic"]))
    cfg["n_agents"], mix["n_envs"] = 4, 8
    cfg["ppo"]["rollout_len"] = cfg["ppo"]["episode_len"] = 16
    cfg["aip"].update(aip)
    return cfg, mix


@pytest.mark.parametrize("name,aip,state", [
    ("traffic25_fnn.train_b256", {"hidden": 32, "stack": 4}, (8, 4, 4, 40)),
    ("warehouse36_gru.train_b256", {"hidden": 32}, (8, 4, 32)),
])
def test_configured_aip_widths_reach_the_program(name, aip, state):
    """Widths other than the program's defaults build a program whose AIP
    state has them, and the program stays correct against the reference."""
    cfg, mix = _tiny_train(name, **aip)
    prog = train.Program(cfg, mix, jax.devices())
    assert (prog.acfg.hidden, prog.acfg.stack) == (
        cfg["aip"]["hidden"], cfg["aip"]["stack"])
    params, ost, rs = prog.start(SEED)
    assert rs.env_state.aip_state.shape == state
    keys = train.iteration_keys(SEED, 0, train.CHECKED)
    _, got = train.checked_steps(prog, (params, ost, rs), keys)
    ref = train.reference_steps(cfg, mix, SEED, got["p0"], prog.aip,
                                jnp.float32)
    ok, checks = verdict.judge(train.readings(got, ref), cells.limits(name))
    assert ok, checks


def test_a_dropped_aip_width_is_caught_at_set_up(monkeypatch):
    """A program that builds its AIP at another width than the
    configuration states stops at set-up, naming the ``aip`` block."""
    from repro.core import influence
    real = influence.AIPConfig
    monkeypatch.setattr(influence, "AIPConfig",
                        lambda **kw: real(**{**kw, "hidden": 64}))
    cfg, mix = _tiny_train("warehouse36_gru.train_b256", hidden=32)
    prog = train.Program(cfg, mix, jax.devices())
    with pytest.raises(ValueError, match="aip block"):
        prog.start(SEED)


@pytest.mark.parametrize("domain,grid,agents", [("traffic", 7, 49),
                                                ("warehouse", 4, 16)])
def test_configured_grid_reaches_the_program(domain, grid, agents):
    name = "traffic25_fnn" if domain == "traffic" else "warehouse36_gru"
    cfg = _small(name, agents)
    cfg["grid"] = grid
    gs, _ = domains.module(cfg).build(cfg)
    assert gs.spec.n_agents == agents
    cfg["grid"] = grid - 1
    with pytest.raises(ValueError, match=f"{agents} > {grid - 1}x"):
        domains.module(cfg).build(cfg)


@pytest.mark.parametrize("key", ["obs_dim", "dset_dim", "n_influence",
                                 "n_actions", "policy.frame_stack"])
def test_a_stated_width_the_program_does_not_build_is_named(key):
    cfg, mix = _tiny_train("traffic25_fnn.train_b256")
    *block, last = key.split(".")
    (cfg[block[0]] if block else cfg)[last] += 1
    with pytest.raises(ValueError, match=key):
        train.Program(cfg, mix, jax.devices())


def test_one_agent_is_refused():
    """``rl_train`` builds a single-agent simulator for one agent, the
    harness's domains a multi-agent one: set-up stops, naming it."""
    cfg, mix = _tiny_train("traffic25_fnn.train_b256")
    cfg["n_agents"] = 1
    with pytest.raises(ValueError, match="n_agents 1"):
        train.Program(cfg, mix, jax.devices())


POLICY_CELLS = ["traffic25_fnn.train_b256", "traffic25_fnn.serve_r80"]


def _build(cell, cfg, mix):
    if cell.endswith("serve_r80"):
        return serve.Server(cfg, mix, SEED)
    return train.Program(cfg, mix, jax.devices())


@pytest.mark.parametrize("cell", POLICY_CELLS)
def test_a_policy_kind_the_program_does_not_build_stops_set_up(
        cell, monkeypatch):
    """A kind with a reference but no program side stops the training and
    the serving set-up, naming the kind and ``bench/lib/policies``."""
    cfg, mix = _tiny_train(cell)
    cfg["policy"]["kind"] = "stand_in"
    mod = _stand_in("policy", cfg)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    with pytest.raises(ValueError,
                       match="'stand_in'.*bench/lib/policies"):
        _build(cell, cfg, mix)


def _policy_sides(calls):
    """A policy kind that comes as two new modules, the program's and the
    reference's: the shared MLP under another name, each function noting
    its calls in ``calls``."""
    from bench.lib.policies import mlp as program_mlp
    from bench.reference import pooled
    from bench.reference.policy import mlp as reference_mlp

    def noted(name, fn):
        def call(*args):
            calls.append(name)
            return fn(*args)
        return call

    program = types.ModuleType("bench.lib.policies.stand_in")
    for name in ("train_config", "program_init", "server"):
        setattr(program, name, noted(name, getattr(program_mlp, name)))
    reference = types.ModuleType("bench.reference.policy.stand_in")
    for name in ("init", "forward", "flops", "weight_words"):
        setattr(reference, name, getattr(reference_mlp, name))
    reference.learn = noted("learn", pooled.learn)
    return program, reference


@pytest.mark.parametrize("cell", POLICY_CELLS)
def test_a_new_policy_kind_is_two_new_modules(cell, monkeypatch):
    """A policy kind whose two sides are new modules, and no existing file
    names it, is built by ``Program`` and ``Server``, learnt by its own
    ``learn`` in the reference, and comes out correct."""
    cfg, mix = _tiny_train(cell)
    cfg["policy"]["kind"] = "stand_in"
    calls = []
    for mod in _policy_sides(calls):
        monkeypatch.setitem(sys.modules, mod.__name__, mod)
    if cell.endswith("serve_r80"):
        mix["rate_rps"] = 2000.0
        srv = _build(cell, cfg, mix)
        tr = serve.trace_for(cfg, mix, SEED, 0.2)
        res = srv.replay(tr)
        idx = np.arange(len(tr["arrival"]))
        acts, logits = srv.served_outputs(res["where"], idx)
        ref = serve.reference_logits(cfg, srv.params, tr["frame"][idx],
                                     jnp.float32)
        checks = serve.readings(acts, logits, ref)
        assert calls == ["server"]
    else:
        prog = _build(cell, cfg, mix)
        keys = train.iteration_keys(SEED, 0, train.CHECKED)
        _, got = train.checked_steps(prog, prog.start(SEED), keys)
        ref = train.reference_steps(cfg, mix, SEED, got["p0"], prog.aip,
                                    jnp.float32)
        checks = train.readings(got, ref)
        assert calls == ["train_config", "program_init", "learn"]
    ok, judged = verdict.judge(checks, cells.limits(cell))
    assert ok, judged


@pytest.mark.parametrize("cell", POLICY_CELLS)
def test_a_policy_kind_whose_sides_differ_stops_set_up(cell, monkeypatch):
    """A program side whose parameters are not the reference's stops the
    training and the serving set-up, naming ``policy.kind``."""
    from bench.lib.policies import mlp
    cfg, mix = _tiny_train(cell)
    cfg["policy"]["kind"] = "stand_in"
    reference = _stand_in("policy", cfg)
    program = types.ModuleType("bench.lib.policies.stand_in")
    program.__dict__.update(train_config=mlp.train_config,
                            program_init=mlp.program_init,
                            server=mlp.server)
    for mod in (reference, program):
        monkeypatch.setitem(sys.modules, mod.__name__, mod)
    with pytest.raises(ValueError, match="policy.kind 'stand_in'"):
        _build(cell, cfg, mix)


@pytest.mark.parametrize("name", sorted(PARENT_WEIGHTS))
def test_policy_weight_words_count_every_parameter(name):
    """``rollout_kernel_cost``'s weights term reads every parameter the
    kind's ``init`` makes, however many networks it holds."""
    cfg = cells.config(name)
    tree = jax.eval_shape(lambda: weights.make(cfg, SEED)["policy"])
    assert flops.policy_weight_words(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(tree))


def test_unknown_domain_raises():
    cfg = _small("traffic25_fnn")
    cfg["domain"] = "harbour"
    with pytest.raises(ValueError, match="'harbour'"):
        domains.module(cfg)
