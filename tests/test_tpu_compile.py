"""The main path compiles for a TPU v5e — with no chip attached.

JAX's TPU compiler is installed with the CPU wheel, so every Pallas
kernel of the main path is compiled here for a described ``v5e:2x2``
topology at the widths ``rl_train`` uses (AIP hidden 64, 16 envs,
T=128, A in {1, 25, 36}, a 128-lane serving slot): the rollout kernels
through the engine's kernel route, the actor-in-the-loop kernel through
PPO's rollout, ``serve_forward``, ``serve_forward_multi`` and
``aip_step`` directly, and the 4-device ``train_iteration`` with its
kernel running on each chip's own lanes. The interpret-mode parity tests
pin the math; these pin what only the TPU compiler checks (tiling,
lowerable ops, casts).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the suite runs in several. The
persistent compilation cache is off around these compiles (an entry
compiled for a described chip cannot be read back without one)."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import engine, influence
from repro.launch.rl_train import build_domain
from repro.rl import ppo

B, T, HIDDEN, SLOT = 16, 128, 64, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_routes(monkeypatch):
    """The engine and ``kernels/ops.py`` pick the kernel route by asking
    ``jax.default_backend()``, which sees the CPU here: answer "tpu"."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


def _engine(domain, kind, A, mesh=None):
    gs, _, bls, frame_stack = build_domain(domain, 0, A)
    acfg = influence.AIPConfig(kind=kind, d_in=gs.spec.dset_dim,
                               n_out=gs.spec.n_influence, hidden=HIDDEN,
                               stack=8 if kind == "fnn" else 1)
    if A > 1:
        params = jax.vmap(lambda k: influence.init_aip(acfg, k))(
            jax.random.split(jax.random.PRNGKey(0), A))
    else:
        params = influence.init_aip(acfg, jax.random.PRNGKey(0))
    env = engine.make_unified_ials(bls, params, acfg, n_agents=A,
                                   use_horizon_kernel=True, mesh=mesh)
    pcfg = ppo.PPOConfig(obs_dim=gs.spec.obs_dim,
                         n_actions=gs.spec.n_actions,
                         frame_stack=frame_stack, n_envs=B, rollout_len=T,
                         episode_len=T, n_agents=A)
    return env, pcfg


_KEY = jax.ShapeDtypeStruct((2,), jnp.uint32)


def _kernel_op_names(hlo):
    """The ``op_name`` of every Pallas call in compiled HLO text."""
    return [m.group(1) for line in hlo.splitlines()
            if "tpu_custom_call" in line
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


def _kernel_names(hlo):
    """The kernels' fixed ``name``s: the scope just above each
    ``pallas_call`` in its op name."""
    return {n.split("/")[-2] for n in _kernel_op_names(hlo)}


@pytest.mark.parametrize("domain,kind,A", [
    ("traffic", "fnn", 1), ("traffic", "gru", 25),
    ("warehouse", "gru", 36), ("warehouse", "fnn", 1)])
def test_engine_rollout_kernel_compiles(domain, kind, A, one_chip,
                                        tpu_routes):
    """``aip_rollout_multi`` (GRU) / ``fnn_rollout`` (FNN) with the
    domain's LS tick traced in, as the engine's ``rollout`` builds it."""
    env, _ = _engine(domain, kind, A)
    state = jax.eval_shape(lambda k: env.reset(k, B), _KEY)
    acts = jax.ShapeDtypeStruct((T, B) + ((A,) if A > 1 else ()),
                                jnp.int32)
    keys = jax.eval_shape(lambda k: jax.random.split(k, T), _KEY)
    hlo = jax.jit(env.rollout).lower(
        *_on((state, acts, keys), one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert _kernel_names(hlo) == {"aip_rollout"}


@pytest.mark.parametrize("domain,kind,A", [
    ("traffic", "fnn", 1), ("traffic", "fnn", 25),
    ("warehouse", "gru", 1), ("warehouse", "gru", 36)])
def test_policy_rollout_kernel_compiles(domain, kind, A, one_chip,
                                        tpu_routes):
    """The actor-in-the-loop ``policy_rollout`` kernel, as PPO's rollout
    (the first half of ``train_iteration``) hands it the acting loop."""
    env, pcfg = _engine(domain, kind, A)
    assert env.policy_rollout is not None
    pol = jax.eval_shape(lambda k: ppo.init_policy(pcfg, k), _KEY)
    rs = jax.eval_shape(lambda k: ppo.init_rollout_state(env, pcfg, k),
                        _KEY)
    fn = jax.jit(lambda p, r, k: ppo.rollout(env, pcfg, p, r, k))
    hlo = fn.lower(*_on((pol, rs, _KEY), one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert _kernel_names(hlo) == {"policy_rollout"}
    assert all("/ppo.rollout/" in n for n in _kernel_op_names(hlo))


@pytest.mark.parametrize("domain", ["traffic", "warehouse"])
def test_serve_forward_compiles(domain, one_chip):
    from repro.kernels.aip_step import serve_forward
    gs, _, _, frame_stack = build_domain(domain)
    pcfg = ppo.PPOConfig(obs_dim=gs.spec.obs_dim,
                         n_actions=gs.spec.n_actions,
                         frame_stack=frame_stack)
    pol = jax.eval_shape(lambda k: ppo.init_policy(pcfg, k), _KEY)
    frames = jax.ShapeDtypeStruct((SLOT, pcfg.obs_dim * frame_stack),
                                  jnp.float32)
    mask = jax.ShapeDtypeStruct((SLOT,), jnp.bool_)
    fn = jax.jit(lambda f, m, w: serve_forward(
        f, m, ppo.flat_policy_weights(w), fast_gates=True,
        interpret=False))
    hlo = fn.lower(*_on((frames, mask, pol), one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert _kernel_names(hlo) == {"serve_forward"}


@pytest.mark.parametrize("domain", ["traffic", "warehouse"])
def test_serve_forward_multi_compiles(domain, one_chip):
    """The cross-policy serving dispatch over 3 stacked checkpoints."""
    from repro.kernels.aip_step import serve_forward_multi
    gs, _, _, frame_stack = build_domain(domain)
    pcfg = ppo.PPOConfig(obs_dim=gs.spec.obs_dim,
                         n_actions=gs.spec.n_actions,
                         frame_stack=frame_stack)
    pols = [jax.eval_shape(lambda k: ppo.init_policy(pcfg, k), _KEY)] * 3
    frames = jax.ShapeDtypeStruct((SLOT, pcfg.obs_dim * frame_stack),
                                  jnp.float32)
    mask = jax.ShapeDtypeStruct((SLOT,), jnp.bool_)
    pidx = jax.ShapeDtypeStruct((SLOT,), jnp.int32)
    fn = jax.jit(lambda f, m, p, ws: serve_forward_multi(
        f, m, p, ppo.stack_policy_weights(ws), fast_gates=True,
        interpret=False))
    hlo = fn.lower(*_on((frames, mask, pidx, pols),
                        one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert _kernel_names(hlo) == {"serve_forward_multi"}


@pytest.mark.parametrize("domain,A", [("traffic", 1), ("traffic", 25),
                                      ("warehouse", 36)])
def test_aip_step_compiles(domain, A, one_chip):
    from repro.kernels.aip_step import aip_step
    gs, _, _, _ = build_domain(domain)
    L, D, M, H = B * A, gs.spec.dset_dim, gs.spec.n_influence, HIDDEN
    S = jax.ShapeDtypeStruct
    args = (S((L, D), jnp.float32), S((L, H), jnp.float32),
            S((D, 3 * H), jnp.float32), S((H, 3 * H), jnp.float32),
            S((3 * H,), jnp.float32), S((H, M), jnp.float32),
            S((M,), jnp.float32), S((L, M), jnp.uint32))
    fn = jax.jit(lambda *a: aip_step(*a, interpret=False))
    hlo = fn.lower(*_on(args, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert _kernel_names(hlo) == {"aip_step"}


def _defs(hlo):
    """HLO instruction name -> its text line."""
    out = {}
    for line in hlo.splitlines():
        line = line.strip()
        if line.startswith("ROOT "):
            line = line[5:]
        if line.startswith("%") and " = " in line:
            out[line.split(" = ", 1)[0]] = line
    return out


def test_train_iteration_4_chips_runs_kernel_per_chip(topo, tpu_routes):
    """``rl_train``'s 4-device program (traffic A=25, 16 envs): the
    kernel is there, it sees each chip's 4 env lanes, and none of its
    operands is an all-gather — the lane state is never gathered around
    ``tpu_custom_call``."""
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_host_mesh
    A = 25
    mesh = make_host_mesh(devices=topo.devices)
    env, pcfg = _engine("traffic", "fnn", A, mesh=mesh)
    opt, iteration = ppo.make_train_iteration(env, pcfg, mesh=mesh)
    rep = NamedSharding(mesh, P())
    pol = jax.eval_shape(lambda k: ppo.init_policy(pcfg, k), _KEY)
    ost = jax.eval_shape(opt.init, pol)
    rs = jax.eval_shape(lambda k: ppo.init_rollout_state(env, pcfg, k),
                        _KEY)
    rs = jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                          sharding=NamedSharding(mesh, s)),
        rs, shd.ials_state_specs(rs, mesh, A))
    hlo = iteration.lower(*_on((pol, ost), rep), rs,
                          _on(_KEY, rep)).compile().as_text()
    defs = _defs(hlo)
    calls = [l for l in defs.values() if "tpu_custom_call" in l]
    assert len(calls) == 1
    call = calls[0]
    # per-chip lane state: (A, B/4, 4, L) traffic lanes, int32-encoded
    assert f"s32[{A},{B // 4},4,10]" in call.split(" custom-call(")[0]
    operands = call.split(" custom-call(", 1)[1].split(")")[0]
    for name in (o.strip().split("*/")[-1] for o in operands.split(",")):
        assert " all-gather(" not in defs.get(name, ""), name
