#!/usr/bin/env python3
"""Chip smoke test: the IALS trainer and the policy server on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the 4-chip mesh path only

The default run, in one process on one chip:
  (a) device — platform, device kind and device count as JAX reports them;
  (b) kernels — each main-path Pallas kernel (``aip_rollout_multi``,
      ``fnn_rollout``, ``policy_rollout``, ``serve_forward``) against its
      ``kernels/ref.py`` oracle on the same seeded inputs, at rl_train's
      widths (AIP hidden 64, 16 envs per agent, T=128, A = 25 / 36);
  (c) trainer — ``repro.launch.rl_train.main`` in-process for traffic
      A=25 (FNN AIP) and warehouse A=36 (GRU AIP), 3 PPO iterations with
      a GS evaluation on the last; the compiled ``train_iteration`` must
      hold the kernel (``tpu_custom_call``), not the oracle;
  (d) serving — ``repro.launch.policy_serve.main`` in-process: traffic,
      a 128-lane slot, about a second of open-loop requests; the compiled
      ``serve_forward`` dispatch must hold the kernel.

``--four-chips`` runs only rl_train's integrated ``train_iteration`` on
a 4-chip (data, model) mesh (traffic A=25, 16 envs) and the same seed on
one chip, and compares the parameters.

Every phase runs even when an earlier one failed; the script exits
non-zero if any did. The last line of standard output is the JSON result
``{"ok": true, "device": {...}}``, printed only when every phase passed.
On a host whose first JAX device is not a TPU the script exits non-zero
before any phase, and prints no result.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
B, T, HIDDEN, SLOT = 16, 128, 64, 128     # rl_train's default widths

# (b) tolerances. The kernels and the oracles compute the same math, but
# neither sets a matmul precision: on the TPU both contract f32 operands
# at the backend's default (reduced) precision, in different orders, so
# a logit can move in its third significant digit. One forward pass
# (serve_forward) must agree to SERVE_ATOL. A rollout feeds 128 ticks of
# Bernoulli draws back into the simulator, so a draw whose uniform lies
# within that error of its probability flips and that lane's trajectory
# departs; the rollouts are held to the share of discrete outputs (LS
# state, rewards, actions) that match, MATCH_MIN.
SERVE_ATOL = 2e-2
MATCH_MIN = 0.95
# (four chips) lane sharding keeps every rollout lane on one chip with
# the same math; only the learner's batch reductions and the gradient
# all-reduce change their summation order, and three Adam steps of
# lr 3e-4 move a parameter by at most ~1e-3, so the runs must agree to
# MESH_ATOL.
MESH_ATOL = 1e-3

FAILURES: list = []


def check(ok: bool, what: str) -> None:
    print(f"  {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        FAILURES.append(what)


def phase(name):
    """Run a phase; an exception fails it (and the script), not the
    phases after it."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*a, **kw):
            print(f"== {name}", flush=True)
            try:
                return fn(*a, **kw)
            except Exception as e:  # noqa: BLE001 — recorded, exit != 0
                import traceback
                traceback.print_exc()
                check(False, f"{name}: {type(e).__name__}: {e}")
        return run
    return wrap


class CompileClock:
    """Seconds spent compiling (cache retrieval included) and persistent
    cache hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.count = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# (b) kernels against their oracles
# ---------------------------------------------------------------------------

def _compare(name, kern, ref):
    """Largest |diff| over the float outputs, share of equal elements
    over the discrete ones -> (max_diff, match_share)."""
    import jax
    import numpy as np
    diff, same, total = 0.0, 0, 0
    for k, r in zip(jax.tree_util.tree_leaves(kern),
                    jax.tree_util.tree_leaves(ref)):
        k, r = np.asarray(k), np.asarray(r)
        assert k.shape == r.shape, (name, k.shape, r.shape)
        if np.issubdtype(k.dtype, np.floating):
            diff = max(diff, float(np.max(np.abs(k - r))))
        else:
            same += int(np.sum(k == r))
            total += k.size
    share = same / total if total else 1.0
    return diff, share


def _ls_inputs(bls, L, key):
    """A real local simulator's kernel operands: encoded state and noise
    leaves of L lanes, their reset states for T ticks, and the engine's
    tick / dset / obs closures."""
    import jax
    from repro.core.engine import kernel_fns
    from repro.envs.api import horizon_noise, kernel_codec
    k_s, k_n, k_r = jax.random.split(key, 3)
    leaves, ls_def = jax.tree_util.tree_flatten(bls.reset(k_s, L))
    noise = horizon_noise(bls.noise_fn, jax.random.split(k_n, T), L)
    nz_leaves, nz_def = jax.tree_util.tree_flatten(noise)
    resets = jax.vmap(lambda k: bls.reset(k, L))(jax.random.split(k_r, T))
    ls_dt = tuple(l.dtype for l in leaves)
    nz_dt = tuple(l.dtype for l in nz_leaves)
    ls_enc, _ = kernel_codec(ls_def, ls_dt)
    nz_enc, _ = kernel_codec(nz_def, nz_dt)
    tick, dset, obs = kernel_fns(bls, ls_def, ls_dt, nz_def, nz_dt)
    return (ls_enc(leaves), nz_enc(nz_leaves),
            ls_enc(jax.tree_util.tree_leaves(resets)), tick, dset, obs)


def _aip_weights(kind, A, d_in, M, key):
    import jax
    from repro.core import influence
    acfg = influence.AIPConfig(kind=kind, d_in=d_in, n_out=M, hidden=HIDDEN,
                               stack=8 if kind == "fnn" else 1)
    p = jax.vmap(lambda k: influence.init_aip(acfg, k))(
        jax.random.split(key, A))
    if kind == "gru":
        return acfg, (p["gru"]["wx"], p["gru"]["wh"], p["gru"]["b"],
                      p["head"]["w"], p["head"]["b"])
    return acfg, (p["l1"]["w"], p["l1"]["b"], p["l2"]["w"], p["l2"]["b"],
                  p["head"]["w"], p["head"]["b"])


@phase("(b) kernels against kernels/ref.py oracles")
def kernels_vs_oracles():
    import jax
    import jax.numpy as jnp
    from repro.kernels import aip_step as kern
    from repro.kernels import ref
    from repro.launch.rl_train import build_domain
    from repro.rl import ppo

    print(f"  tolerances: serve_forward max|diff| <= {SERVE_ATOL}; "
          f"rollouts: discrete match share >= {MATCH_MIN} (see the "
          f"module constants for why)")
    key = jax.random.PRNGKey(SEED)
    for domain, kind, A in (("traffic", "fnn", 25),
                            ("warehouse", "gru", 36)):
        gs, _, bls, stack = build_domain(domain, 0, A)
        L, M = A * B, gs.spec.n_influence
        key, k_ls, k_w, k_a, k_b, k_h, k_p, k_g = jax.random.split(key, 8)
        ls, nz, rls, tick, dset, obs = _ls_inputs(bls, L, k_ls)
        acfg, aw = _aip_weights(kind, A, gs.spec.dset_dim, M, k_w)
        acts = jax.random.randint(k_a, (T, L), 0, gs.spec.n_actions)
        bits = jax.random.bits(k_b, (T, L, M), jnp.uint32)
        s_dim = HIDDEN if kind == "gru" else acfg.stack * acfg.d_in
        s0 = jax.random.normal(k_h, (L, s_dim)) * 0.5
        kw = dict(n_agents=A, tick_fn=tick, dset_fn=dset)

        # the engine's rollout kernel for this backbone
        if kind == "gru":
            k_fn, r_fn, name = (kern.aip_rollout_multi,
                                ref.ials_rollout_multi_ref,
                                "aip_rollout_multi")
        else:
            k_fn, r_fn, name = kern.fnn_rollout, ref.fnn_rollout_ref, \
                "fnn_rollout"
        args = (ls, s0) + aw + (acts, bits, nz)
        out_k = k_fn(*args, interpret=False, **kw)
        out_r = jax.jit(functools.partial(r_fn, **kw))(*args)
        diff, share = _compare(name, out_k, out_r)
        print(f"  {name} {domain} A={A}: max|diff| {diff:.3e}, "
              f"discrete match {share:.6f}")
        check(share >= MATCH_MIN, f"{name} {domain} A={A} match share")

        # the actor-in-the-loop kernel (one whole PPO acting horizon)
        pcfg = ppo.PPOConfig(obs_dim=gs.spec.obs_dim,
                             n_actions=gs.spec.n_actions, frame_stack=stack)
        pol_w = ppo.flat_policy_weights(ppo.init_policy(pcfg, k_p))
        gumbel = jax.random.gumbel(k_g, (T, L, gs.spec.n_actions))
        done = (jnp.arange(T)[:, None] % 40 == 39).astype(jnp.int32) \
            * jnp.ones((1, L), jnp.int32)
        frames0 = jnp.zeros((L, stack * gs.spec.obs_dim), jnp.float32)
        pkw = dict(kind=kind, n_agents=A, fast_gates=True, tick_fn=tick,
                   dset_fn=dset, obs_fn=obs)
        pargs = (ls, s0, frames0, aw, pol_w, gumbel, bits, done, nz, rls)
        out_k = kern.policy_rollout(*pargs, interpret=False, **pkw)
        out_r = jax.jit(functools.partial(ref.policy_rollout_ref, **pkw))(
            *pargs)
        diff, share = _compare("policy_rollout", out_k, out_r)
        act_share = float(jnp.mean(out_k[4] == out_r[4]))
        print(f"  policy_rollout {domain} A={A}: max|diff| {diff:.3e}, "
              f"discrete match {share:.6f}, actions match {act_share:.6f}")
        check(share >= MATCH_MIN,
              f"policy_rollout {domain} A={A} match share")

    # the serving dispatch: one masked forward over a 128-lane slot
    gs, _, _, stack = build_domain("traffic")
    pcfg = ppo.PPOConfig(obs_dim=gs.spec.obs_dim,
                         n_actions=gs.spec.n_actions, frame_stack=stack)
    k_p, k_f = jax.random.split(key)
    pol_w = ppo.flat_policy_weights(ppo.init_policy(pcfg, k_p))
    frames = jax.random.bernoulli(
        k_f, 0.3, (SLOT, stack * gs.spec.obs_dim)).astype(jnp.float32)
    mask = jnp.arange(SLOT) < 100
    out_k = kern.serve_forward(frames, mask, pol_w, fast_gates=True,
                               interpret=False)
    out_r = jax.jit(functools.partial(ref.serve_forward_ref,
                                      fast_gates=True))(pol_w, frames, mask)
    diff, _ = _compare("serve_forward", out_k, out_r)
    act_share = float(jnp.mean(jnp.argmax(out_k[0], -1)
                               == jnp.argmax(out_r[0], -1)))
    print(f"  serve_forward traffic slot={SLOT}: max|diff| {diff:.3e}, "
          f"greedy actions match {act_share:.6f}")
    check(diff <= SERVE_ATOL, "serve_forward max|diff|")


# ---------------------------------------------------------------------------
# (c) the trainer through its entry point
# ---------------------------------------------------------------------------

def _capture_train_iteration(programs: list, seconds: list):
    """Have ``ppo.make_train_iteration`` compile its program ahead of the
    first call and keep the compiled text: the same program rl_train then
    runs, compiled once. Each call's device-synchronised seconds go to
    ``seconds`` (rl_train's own wallclock rows also hold its GS
    evaluations)."""
    import jax
    from repro.rl import ppo
    make = ppo.make_train_iteration

    def capturing(env, cfg, mesh=None):
        opt, jitted = make(env, cfg, mesh=mesh)
        compiled = []

        def iteration(*args):
            if not compiled:
                compiled.append(jitted.lower(*args).compile())
                programs.append(compiled[0].as_text())
            t0 = time.perf_counter()
            out = jax.block_until_ready(compiled[0](*args))
            seconds.append(time.perf_counter() - t0)
            return out
        return opt, iteration

    ppo.make_train_iteration = capturing
    return make


@phase("(c) trainer: repro.launch.rl_train.main")
def trainer(clock: CompileClock):
    import math
    import jax
    from repro.launch import rl_train
    from repro.rl import ppo

    cuts = {"--collect-episodes": "16", "--aip-epochs": "2"}
    print(f"  cut from rl_train's defaults: {cuts} (AIP dataset and fit "
          f"only; every width is the default)")
    dev = jax.devices()[0]
    for domain, A in (("traffic", 25), ("warehouse", 36)):
        argv = ["--domain", domain, "--simulator", "ials",
                "--n-agents", str(A), "--iterations", "3",
                "--eval-every", "1000", "--n-envs", str(B),
                "--rollout-len", str(T), "--episode-len", str(T),
                "--seed", str(SEED)]
        for k, v in cuts.items():
            argv += [k, v]
        programs, seconds = [], []
        make = _capture_train_iteration(programs, seconds)
        c0 = clock.seconds
        try:
            out = rl_train.main(argv)
        finally:
            ppo.make_train_iteration = make
        hist = out["history"]
        last = hist[-1]
        print(f"  {domain} A={A}: train_reward "
              f"{[r['train_reward'] for r in hist]}, gs_eval_reward "
              f"{last.get('gs_eval_reward')}, compile {clock.seconds - c0:.1f}"
              f" s, train_iteration seconds {seconds}, "
              f"peak_bytes_in_use "
              f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
        check(all(math.isfinite(r["train_reward"]) for r in hist),
              f"{domain} A={A} finite train_reward")
        check(math.isfinite(last.get("gs_eval_reward", float("nan"))),
              f"{domain} A={A} finite gs_eval_reward")
        check(len(programs) == 1 and "tpu_custom_call" in programs[0],
              f"{domain} A={A} compiled train_iteration holds the kernel")


# ---------------------------------------------------------------------------
# (d) serving through its entry point
# ---------------------------------------------------------------------------

@phase("(d) serving: repro.launch.policy_serve.main")
def serving():
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.launch import policy_serve
    from repro.launch.rl_train import build_domain
    from repro.rl import ppo

    out = policy_serve.main(["--domain", "traffic", "--slot", str(SLOT),
                             "--duration-s", "1", "--seed", str(SEED)])
    admitted = out["requests"] - out["rejected"]
    print(f"  requests {out['requests']}, served {out['served']}, rejected "
          f"{out['rejected']}, p50 {out['p50_ms']:.3f} ms, p99 "
          f"{out['p99_ms']:.3f} ms, qps {out['qps']:.1f}")
    check(out["served"] == admitted and out["rejected"] == 0,
          "every admitted request served, none dropped")

    # the dispatch the server ran, compiled for this backend
    gs, _, _, stack = build_domain("traffic")
    pcfg = ppo.PPOConfig(obs_dim=gs.spec.obs_dim,
                         n_actions=gs.spec.n_actions, frame_stack=stack)
    pol = ppo.init_policy(pcfg, jax.random.PRNGKey(SEED))
    fwd = jax.jit(lambda f, m, w: ops.serve_forward(
        f, m, ppo.flat_policy_weights(w), fast_gates=True))
    hlo = fwd.lower(jnp.zeros((SLOT, stack * gs.spec.obs_dim)),
                    jnp.ones((SLOT,), bool), pol).compile().as_text()
    check("tpu_custom_call" in hlo, "compiled serve_forward holds the kernel")


# ---------------------------------------------------------------------------
# --four-chips: the mesh path against one chip
# ---------------------------------------------------------------------------

@phase("four chips: rl_train's train_iteration on a (4, 1) mesh vs 1 chip")
def four_chips(clock: CompileClock, iterations: int = 3):
    import jax
    import numpy as np
    from repro.launch import rl_train
    from repro.launch.mesh import make_host_mesh
    from repro.rl import ppo

    A = 25
    mesh = make_host_mesh(n_devices=4)
    print(f"  mesh {dict(mesh.shape)}, traffic A={A}, {B} envs, T={T}, "
          f"{iterations} iterations; tolerance max|diff| <= {MESH_ATOL}")
    gs, _, bls, stack = rl_train.build_domain("traffic", 0, A)
    root = jax.random.PRNGKey(SEED)
    pcfg = ppo.PPOConfig(obs_dim=gs.spec.obs_dim,
                         n_actions=gs.spec.n_actions, frame_stack=stack,
                         n_envs=B, rollout_len=T, episode_len=T, n_agents=A)
    kw = dict(collect_episodes=16, ep_len=T, aip_epochs=2)
    sim_params = None
    finals = {}
    for name, m in (("4 chips", mesh), ("1 chip", None)):
        sb = rl_train.prepare_simulator("ials", gs, bls, "fnn", mesh=m, **kw)
        if sim_params is None:          # one AIP fit, shared by both runs
            sim_params, _ = sb.train(jax.random.fold_in(root, 0))
        env = sb.make_env(sim_params)
        params = ppo.init_policy(pcfg, jax.random.fold_in(root, 1))
        opt, iteration = ppo.make_train_iteration(env, pcfg, mesh=m)
        params, ost = ppo.replicate((params, opt.init(params)), m)
        rs = ppo.init_rollout_state(env, pcfg, jax.random.fold_in(root, 2),
                                    mesh=m)
        secs = []
        for it in range(iterations):
            k = jax.random.fold_in(jax.random.fold_in(root, 3), it)
            t0 = time.perf_counter()
            params, ost, rs, metrics = jax.block_until_ready(
                iteration(params, ost, rs, k))
            secs.append(time.perf_counter() - t0)
            if it == 0:
                n_compiled = clock.count
        finals[name] = jax.tree_util.tree_map(np.asarray, params)
        print(f"  {name}: params md5 {rl_train.params_md5(params)}, "
              f"mean_reward {float(metrics['mean_reward'])}, "
              f"train_iteration seconds {secs}")
        check(clock.count == n_compiled,
              f"{name}: iterations after the first compile nothing")
    diff = max(float(np.max(np.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(finals["4 chips"]),
        jax.tree_util.tree_leaves(finals["1 chip"])))
    print(f"  largest |param diff| 4 chips vs 1 chip: {diff:.3e}")
    check(diff <= MESH_ATOL, "4-chip params agree with 1 chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh train_iteration and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    d0 = devices[0]
    print(f"== (a) device: platform {d0.platform}, kind {d0.device_kind}, "
          f"count {len(devices)}", flush=True)
    if d0.platform != "tpu":
        print("no TPU: chip_smoke measures the chip and has no CPU mode",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"needs {need} chips, found {len(devices)}", file=sys.stderr)
        return 2
    print(f"  compilation cache: {enable_compile_cache()}")
    clock = CompileClock()

    if args.four_chips:
        four_chips(clock)
    else:
        kernels_vs_oracles()
        trainer(clock)
        serving()
    print(f"== compile {clock.seconds:.1f} s in all, persistent cache hits "
          f"{clock.hits}")
    if FAILURES:
        print(f"{len(FAILURES)} check(s) failed: {FAILURES}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
