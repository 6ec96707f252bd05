"""Sharding rules + an end-to-end multi-device dry-run (subprocess: the
device-count override must not leak into other tests)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.configs.base import get_config, list_configs
from repro.models import lm

SRC = str(Path(__file__).resolve().parents[1] / "src")


class _FakeMesh:
    """Duck-typed mesh: only .axis_names and .shape are consulted."""
    axis_names = ("pod", "data", "model")
    shape = {"pod": 2, "data": 16, "model": 16}


@pytest.mark.slow
def test_param_specs_respect_divisibility_all_archs():
    from repro.distributed.sharding import param_specs
    mesh = _FakeMesh()
    for arch in list_configs():
        cfg = get_config(arch)
        shapes = lm.param_shapes(cfg)
        specs = param_specs(shapes, mesh)
        for (path, leaf), (_, spec) in zip(
                jax.tree_util.tree_leaves_with_path(shapes),
                jax.tree_util.tree_leaves_with_path(
                    specs, is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec))):
            assert len(spec) <= len(leaf.shape), (arch, path)
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                n = 1
                for a in axes:
                    n *= mesh.shape[a]
                assert dim % n == 0, (arch, path, dim, ax)


def test_opt_state_moments_widen_over_pod():
    from repro.distributed.sharding import opt_state_specs, param_specs
    from repro.optim.adamw import adamw
    mesh = _FakeMesh()
    cfg = get_config("qwen3-4b")
    shapes = lm.param_shapes(cfg)
    pspecs = param_specs(shapes, mesh)
    opt = adamw(1e-4)
    oshapes = jax.eval_shape(opt.init, shapes)
    ospecs = opt_state_specs(oshapes, mesh, pspecs)
    # at least one moment leaf picked up the "pod" axis (ZeRO-1)
    axes_used = set()
    for s in jax.tree_util.tree_leaves(
            ospecs.mu, is_leaf=lambda x: isinstance(
                x, jax.sharding.PartitionSpec)):
        for a in tuple(s):
            if isinstance(a, tuple):
                axes_used.update(a)
            elif a:
                axes_used.add(a)
    assert "pod" in axes_used


@pytest.mark.slow
def test_multi_device_dryrun_cell():
    """Real multi-device lower+compile for one cell on a small mesh, in a
    subprocess with a forced host device count."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax
        import repro.launch.mesh as mesh_mod
        mesh_mod.make_production_mesh = (
            lambda multi_pod=False: mesh_mod.make_mesh(
                (2, 2, 2), ("pod", "data", "model"))
            if multi_pod else mesh_mod.make_mesh((4, 2), ("data", "model")))
        from repro.launch.dryrun import run_cell
        r1 = run_cell("whisper-base", "train_4k", "pod1")
        r2 = run_cell("whisper-base", "decode_32k", "pod2")
        print(json.dumps({"pod1": r1["status"], "pod2": r2["status"],
                          "coll": r1["hlo"]["collective_bytes_total"] > 0}))
    """)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=1200,
                         env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["pod1"] == "ok" and res["pod2"] == "ok"
    assert res["coll"]  # the mesh actually communicates


# ---------------------------------------------------------------------------
# IALS partition rules (the unified engine / PPO rollout state)
# ---------------------------------------------------------------------------

class _HostMesh:
    """Duck-typed host mesh of n simulated devices, (data, model)."""
    axis_names = ("data", "model")

    def __init__(self, data, model=1):
        self.shape = {"data": data, "model": model}


_HOST_MESHES = [_HostMesh(1), _HostMesh(2), _HostMesh(4, 2),
                _HostMesh(8)]                    # 1 / 2 / 8 devices


def _engine_state_shapes(domain, backbone, A, B):
    import jax.numpy as jnp
    from repro.core import engine, influence
    from repro.envs.traffic import (TrafficConfig,
                                    make_batched_local_traffic_env)
    from repro.envs.warehouse import (WarehouseConfig,
                                      make_batched_local_warehouse_env)
    bls = (make_batched_local_traffic_env(TrafficConfig())
           if domain == "traffic"
           else make_batched_local_warehouse_env(WarehouseConfig()))
    acfg = influence.AIPConfig(
        kind=backbone, d_in=bls.spec.dset_dim, n_out=bls.spec.n_influence,
        hidden=64, stack=8 if backbone == "fnn" else 1)
    key_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if A > 1:
        aip = jax.eval_shape(
            lambda ks: jax.vmap(lambda k: influence.init_aip(acfg, k))(ks),
            jax.ShapeDtypeStruct((A, 2), jnp.uint32))
    else:
        aip = jax.eval_shape(lambda k: influence.init_aip(acfg, k), key_s)
    env = engine.make_unified_ials(bls, aip, acfg, n_agents=A)
    state = jax.eval_shape(lambda k: env.reset(k, B), key_s)
    return state, aip


def _assert_divides(leaf, spec, mesh, ctx):
    assert len(tuple(spec)) <= len(leaf.shape), ctx
    for dim, ax in zip(leaf.shape, tuple(spec)):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        assert dim % n == 0, (ctx, dim, ax)


@pytest.mark.parametrize("domain,backbone,A",
                         [("traffic", "fnn", 1), ("traffic", "gru", 25),
                          ("warehouse", "gru", 36),
                          ("warehouse", "fnn", 36)])
def test_ials_state_specs_divide_or_replicate(domain, backbone, A):
    """Every engine state leaf gets a PartitionSpec that divides its dims
    (or cleanly falls back to replication) on 1/2/8 simulated host
    devices, for A in {1, 25, 36}."""
    from repro.distributed import sharding as shd
    B = 16
    state, aip = _engine_state_shapes(domain, backbone, A, B)
    for mesh in _HOST_MESHES:
        specs = shd.ials_state_specs(state, mesh, A)
        for (path, leaf), (_, spec) in zip(
                jax.tree_util.tree_leaves_with_path(state),
                jax.tree_util.tree_leaves_with_path(
                    specs, is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec))):
            _assert_divides(leaf, spec, mesh,
                            (domain, backbone, A, mesh.shape, path))
        for (path, leaf), (_, spec) in zip(
                jax.tree_util.tree_leaves_with_path(aip),
                jax.tree_util.tree_leaves_with_path(
                    shd.ials_aip_param_specs(aip, mesh, A, batch=B),
                    is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec))):
            _assert_divides(leaf, spec, mesh,
                            (domain, backbone, A, mesh.shape, path))


def test_host_mesh_axes_are_auto():
    """Meshes from launch/mesh.py have Auto axes — the only kind
    ``with_sharding_constraint`` accepts — and take the first n devices;
    asking for more devices than exist is an error."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(n_devices=1)
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    assert mesh.devices.size == 1
    with pytest.raises(ValueError):
        make_host_mesh(n_devices=len(jax.devices()) + 1)


def test_ials_kernel_specs_match_state_rules():
    """The kernel route's shard_map specs take the same lane and agent
    axes as the state rules, for every operand kind."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shd
    mesh = _HostMesh(4, 2)
    s4 = shd.ials_kernel_specs(16, 4, mesh)
    assert s4 == {"state": P("data", "model"),
                  "stream": P(None, "data", "model"),
                  "lanes": P("data"), "agent": P("model"), "rep": P()}
    s25 = shd.ials_kernel_specs(16, 25, mesh)     # agents replicate
    assert s25["state"] == P(("data", "model"))
    assert s25["agent"] == P()
    s1 = shd.ials_kernel_specs(16, 1, mesh)
    assert s1["stream"] == P(None, ("data", "model"))
    assert s1["agent"] == P()


def test_ials_lanes_shard_and_agents_coshard():
    """On a mesh whose axes divide: env lanes take the data axes, the
    agent axis and the stacked AIP leading dim co-shard on "model"; when
    A does not divide "model", both fall back to replication."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shd
    mesh = _HostMesh(4, 2)
    lane, agent_ax = shd.ials_lane_axes(16, 4, mesh)
    assert lane == ("data",) and agent_ax == "model"
    state, aip = _engine_state_shapes("traffic", "gru", 4, 16)
    sspec = shd.ials_state_pspec(state.aip_state, mesh, 4)
    assert tuple(sspec)[:2] == ("data", "model")
    aip_specs = shd.ials_aip_param_specs(aip, mesh, 4, batch=16)
    assert tuple(aip_specs["gru"]["wx"])[0] == "model"   # co-sharded
    # A=25 does not divide model=2 -> agents replicate, lanes absorb model
    lane25, agent25 = shd.ials_lane_axes(16, 25, mesh)
    assert agent25 is None and lane25 == ("data", "model")
    state25, aip25 = _engine_state_shapes("traffic", "gru", 25, 16)
    assert tuple(shd.ials_state_pspec(state25.aip_state, mesh, 25)) \
        == (("data", "model"),)
    specs25 = shd.ials_aip_param_specs(aip25, mesh, 25, batch=16)
    assert all(s == P() for s in jax.tree_util.tree_leaves(
        specs25, is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec)))
    # trivial mesh: everything replicates
    for leaf in jax.tree_util.tree_leaves(
            shd.ials_state_specs(state, _HostMesh(1), 4),
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)):
        assert leaf == P()


def test_ials_policy_specs_replicated():
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shd
    from repro.rl import ppo
    cfg = ppo.PPOConfig(obs_dim=6, n_actions=3)
    params = jax.eval_shape(
        lambda k: ppo.init_policy(cfg, k),
        jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
    specs = shd.ials_replicated_specs(params)
    assert all(s == P() for s in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec)))


def test_ials_sharded_policy_rollout_bitwise_parity():
    """The acceptance bar: PPO's whole rollout (the engine's fused
    ``policy_rollout`` route) on a forced 8-host-device mesh is
    bitwise-equal to the single-device program, for both domains x both
    backbones. Lane sharding is pure data parallelism — no reduction
    order changes — so exact equality is required, not approximate."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.core import engine, influence
        from repro.envs.traffic import (TrafficConfig,
                                        make_batched_local_traffic_env)
        from repro.envs.warehouse import (WarehouseConfig,
                                          make_batched_local_warehouse_env)
        from repro.launch.mesh import make_host_mesh
        from repro.rl import ppo

        assert len(jax.devices()) == 8
        mesh = make_host_mesh(model=2)          # (4, 2) (data, model)
        A, B, T = 4, 8, 8
        for domain, backbone in [("traffic", "fnn"), ("traffic", "gru"),
                                 ("warehouse", "gru"),
                                 ("warehouse", "fnn")]:
            bls, fs = ((make_batched_local_traffic_env(TrafficConfig()), 1)
                       if domain == "traffic" else
                       (make_batched_local_warehouse_env(
                           WarehouseConfig()), 8))
            acfg = influence.AIPConfig(
                kind=backbone, d_in=bls.spec.dset_dim,
                n_out=bls.spec.n_influence, hidden=16,
                stack=8 if backbone == "fnn" else 1)
            key = jax.random.PRNGKey(0)
            ka, kp, ks, kr = jax.random.split(key, 4)
            aip = jax.vmap(lambda k: influence.init_aip(acfg, k))(
                jax.random.split(ka, A))
            kw = dict(n_agents=A, use_horizon_kernel=True)
            env1 = engine.make_unified_ials(bls, aip, acfg, **kw)
            env2 = engine.make_unified_ials(bls, aip, acfg, mesh=mesh,
                                            **kw)
            assert env1.policy_rollout is not None
            pcfg = ppo.PPOConfig(
                obs_dim=bls.spec.obs_dim, n_actions=bls.spec.n_actions,
                frame_stack=fs, n_envs=B, rollout_len=T, episode_len=T,
                n_agents=A)
            pol = ppo.init_policy(pcfg, kp)
            rs1 = ppo.init_rollout_state(env1, pcfg, ks)
            rs2 = ppo.init_rollout_state(env2, pcfg, ks, mesh=mesh)

            def run(env, rs):
                f = jax.jit(lambda p, r, k: ppo.rollout(env, pcfg, p,
                                                        r, k))
                return f(pol, rs, kr)

            o1, o2 = run(env1, rs1), run(env2, rs2)
            mism = [p for (p, a), (_, b) in zip(
                        jax.tree_util.tree_leaves_with_path(o1),
                        jax.tree_util.tree_leaves_with_path(o2))
                    if not np.array_equal(np.asarray(a), np.asarray(b))]
            assert not mism, (domain, backbone, mism)
            print(f"parity ok: {domain}/{backbone}")
        print("ALL_BITWISE_EQUAL")
    """)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=1200,
                         env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ALL_BITWISE_EQUAL" in out.stdout


def test_cache_specs_long_context_batch1():
    """batch-1 long-context decode shards the cache sequence dim on data."""
    from repro.distributed.sharding import cache_specs
    cfg = get_config("xlstm-1.3b")
    mesh = _FakeMesh()
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 1, 1024))
    specs = cache_specs(cache, mesh, 1)
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert len(leaves) > 0  # well-formed for a state-only (SSM) cache
