"""The training cells: rl_train's integrated PPO loop on the IALS.

Set-up builds one object, ``ppo.make_train_iteration``'s jitted
``train_iteration`` over the unified engine as ``rl_train`` builds it for
its IALS simulator, at the configuration's widths, with the AIP and
policy weights made from the seed, and drives it through its
first three iterations. Those compile it, warm it, and are what the
reference follows. The same object and state then run the measured
window, threaded as ``rl_train._run_integrated`` threads them: donated
params, optimizer and rollout state, and the iteration's ``mean_reward``
read each iteration, a few seconds of iterations after it was
dispatched (``read_lag``).
"""
from __future__ import annotations

import collections
import math
import time

import numpy as np

from bench.lib import weights
from bench.lib.tracing import span

CHECKED = 3         # iterations the reference follows


class Program:
    """The system under test for one configuration and mix: built once,
    then started from any seed. Every width the configuration states
    reaches the program: the grid and agent count the simulators are
    built at, the policy's hidden width and frame stack, and the AIP's
    ``aip`` block less its weights' seed and head bias. The policy and
    its learner are ``policy.kind``'s (``bench/lib/policies/<kind>.py``).
    What the program cannot take is checked against it, naming the key:
    the frame stack ``rl_train`` trains the domain at, and the policy's
    parameters against those of the reference's ``policy.kind``."""

    def __init__(self, cfg: dict, mix: dict, devices):
        from repro.core import influence
        from repro.launch.mesh import make_host_mesh
        from repro.launch import rl_train
        from bench.lib import domains, policies
        pol = policies.module(cfg)
        self.cfg, self.mix = cfg, mix
        A, B, pc = cfg["n_agents"], mix["n_envs"], cfg["ppo"]
        if A < 2:
            raise ValueError(f"{cfg['name']}: n_agents {A}: rl_train builds "
                             "a single-agent simulator for one agent, "
                             "bench/lib/domains only multi-agent ones")
        self.mesh = (make_host_mesh(n_devices=len(devices), devices=devices)
                     if len(devices) > 1 else None)
        gs, self.bls = domains.module(cfg).build(cfg)
        spec = gs.spec
        got = {"n_agents": spec.n_agents, "obs_dim": spec.obs_dim,
               "dset_dim": spec.dset_dim, "n_influence": spec.n_influence,
               "n_actions": spec.n_actions,
               # rl_train trains each domain at one frame stack
               "policy.frame_stack": rl_train.build_domain(cfg["domain"])[3]}
        want = {**{k: cfg[k] for k in got if k in cfg},
                "policy.frame_stack": cfg["policy"]["frame_stack"]}
        bad = {k: (v, want[k]) for k, v in got.items() if v != want[k]}
        if bad:
            raise ValueError(f"{cfg['name']}: the program builds, and the "
                             f"configuration states, {bad}")
        self.pcfg = pol.train_config(cfg, spec, B)
        weights.check_policy(cfg, pol.program_init(self.pcfg))
        widths = {k: v for k, v in cfg["aip"].items()
                  if k not in ("weights_seed", "head_bias")}
        self.acfg = influence.AIPConfig(
            d_in=spec.dset_dim, n_out=spec.n_influence, **widths)
        self.samples_per_iteration = A * B * pc["rollout_len"]
        self.iteration = None

    def start(self, seed: int):
        """-> (params, opt_state, rollout_state) for ``seed``; builds the
        engine (as ``rl_train``'s IALS simulator builds it) and the jitted
        iteration on first use (the AIP weights are the configuration's,
        the same for every seed)."""
        from repro.core import engine
        from repro.rl import ppo
        w = weights.make(self.cfg, seed)
        first = self.iteration is None
        if first:
            self.aip = w["aip"]
            self.env = engine.make_unified_ials(
                self.bls, w["aip"], self.acfg,
                n_agents=self.cfg["n_agents"], mesh=self.mesh)
            self.opt, self.iteration = ppo.make_train_iteration(
                self.env, self.pcfg, mesh=self.mesh)
        params = w["policy"]
        ost = self.opt.init(params)
        rs = ppo.init_rollout_state(
            self.env, self.pcfg, weights.stream(seed, weights.K_ROLLOUT),
            mesh=self.mesh)
        if first:
            self._check_aip_state(rs.env_state.aip_state)
        params, ost = ppo.replicate((params, ost), self.mesh)
        return params, ost, rs

    def _check_aip_state(self, state):
        """The engine's AIP state against the one the configuration's
        ``aip`` block gives its backbone."""
        import jax
        from bench.reference import aip
        cfg = self.cfg
        want = jax.eval_shape(lambda: aip.module(cfg).zero(
            cfg, self.mix["n_envs"], cfg["n_agents"])).shape
        if state.shape != want:
            raise ValueError(f"{cfg['name']}: the program's AIP state is "
                             f"{state.shape}, the configuration's aip block "
                             f"{cfg['aip']} gives {want}")


def iteration_keys(seed: int, start: int, n: int) -> np.ndarray:
    """Keys of iterations ``start .. start + n - 1`` as host uint32 data,
    so the loop hands each call a fresh key without a device op."""
    import jax
    import jax.numpy as jnp
    base = weights.stream(seed, weights.K_TRAIN)
    ks = jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.arange(start, start + n, dtype=jnp.uint32))
    return np.asarray(ks)


def host(tree):
    import jax
    return jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), tree)


def checked_steps(prog: Program, state, keys):
    """Run the first ``CHECKED`` iterations -> (state, record of what the
    reference is compared on)."""
    params, ost, rs = state
    rec = {"p0": host(params), "loss": [], "reward": [], "value": [],
           "seconds": []}
    for i in range(CHECKED):
        t0 = time.perf_counter()
        params, ost, rs, m = prog.iteration(params, ost, rs, keys[i])
        rec["loss"].append(float(m["loss"]))
        rec["seconds"].append(time.perf_counter() - t0)
        rec["reward"].append(float(m["mean_reward"]))
        rec["value"].append(float(m["mean_value"]))
        if i == 0:
            rec["mu1"] = host(ost.mu)
    rec["p3"] = host(params)
    return (params, ost, rs), rec


def read_lag(mix: dict, iteration_s: float) -> int:
    """Iterations dispatched ahead of the one whose ``mean_reward`` is
    read: about ``mix["ahead_s"]`` seconds of work, so a host stall of a
    second leaves the chip fed."""
    return max(1, math.ceil(mix["ahead_s"] / iteration_s))


def window(prog: Program, state, keys, seconds: float, lag: int):
    """The measured loop -> (state, iterations, failed, elapsed s).

    Each iteration's ``mean_reward`` is read ``lag`` iterations after it
    was dispatched. When the time is up nothing more is dispatched, every
    iteration sent is waited for and read, and only then is the clock
    read: all the work sent counts, over all the time it took."""
    params, ost, rs = state
    sent = collections.deque()
    n = failed = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with span("bench.window"):
        while n < len(keys) and time.perf_counter() < deadline:
            with span("bench.dispatch"):
                params, ost, rs, m = prog.iteration(params, ost, rs,
                                                    keys[n])
            sent.append(m["mean_reward"])
            n += 1
            if len(sent) > lag:
                with span("bench.read"):
                    failed += not math.isfinite(float(sent.popleft()))
        with span("bench.read"):
            while sent:
                failed += not math.isfinite(float(sent.popleft()))
    elapsed = time.perf_counter() - t0
    return (params, ost, rs), n, failed, elapsed


def reference_steps(cfg: dict, mix: dict, seed: int, p0, aip, dt):
    """The plain reference from the same start -> its record."""
    import jax
    import jax.numpy as jnp
    from bench.reference import common
    it = common.make_iteration(cfg, dt)
    keys = iteration_keys(seed, 0, CHECKED)
    state = common.initial_state(
        cfg, weights.stream(seed, weights.K_ROLLOUT), mix["n_envs"])
    pol = jax.tree_util.tree_map(jnp.asarray, p0)
    opt = common.adam_init(pol)
    rec = {"p0": p0, "loss": [], "reward": [], "value": []}
    for i in range(CHECKED):
        pol, opt, state, m = it(aip, pol, opt, state, keys[i])
        rec["loss"].append(float(m["loss"]))
        rec["reward"].append(float(m["mean_reward"]))
        rec["value"].append(float(m["mean_value"]))
        if i == 0:
            rec["mu1"] = host(opt["mu"])
    rec["p3"] = host(pol)
    return rec


def _leaves(tree):
    import jax
    return [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(tree)]


def _rel(a, b):
    """The largest |a - b| / |b| over paired scalars."""
    return max(abs(x - y) / max(abs(y), 1e-30) for x, y in zip(a, b))


def _worst_diff(g, r, keep):
    """The worst kept leaf's norm of the difference, over the larger of
    that leaf's reference norm and the median leaf's."""
    n = [np.linalg.norm(x) for x in r]
    med = float(np.median(n))
    return float(max(np.linalg.norm(g[i] - r[i]) / max(n[i], med)
                     for i in keep))


def readings(got: dict, ref: dict) -> dict:
    """The numbers ``correct`` compares, each a relative gap of the
    program's record against the reference's:

    - ``loss_gap``, ``reward_gap``: the largest over the checked
      iterations of |program - reference| / |reference| for the mean PPO
      loss and the rollout's mean reward;
    - ``moment_gap``: after iteration 1, the worst leaf's gap between the
      norms of Adam's first moment (the gradients as the optimizer got
      them), over the larger of that leaf's reference norm and the median
      leaf's;
    - ``change_gap``: the same for the norm of each leaf's change over the
      checked iterations;
    - ``change_diff``: the worst leaf's norm of the difference between the
      program's change and the reference's, on the same scale. The means
      and norms above average a fault in a few env lanes, or a lower
      precision, out of hundreds of thousands of samples; the change
      itself keeps the direction each sample pushed the parameters in.

    Leaves whose reference first moment is under a thousandth of the
    median leaf's move by round-off alone and are left out of the three
    leaf readings."""
    mu_g = [np.linalg.norm(x) for x in _leaves(got["mu1"])]
    mu_r = [np.linalg.norm(x) for x in _leaves(ref["mu1"])]
    med = float(np.median(mu_r))
    keep = [i for i, m in enumerate(mu_r) if m >= 1e-3 * med]

    def worst(g, r):
        scale = float(np.median(r))
        return float(max(abs(g[i] - r[i]) / max(r[i], scale)
                         for i in keep))

    p0 = _leaves(ref["p0"])
    d_g = [a - b for a, b in zip(_leaves(got["p3"]), p0)]
    d_r = [a - b for a, b in zip(_leaves(ref["p3"]), p0)]
    ch_g = [np.linalg.norm(d) for d in d_g]
    ch_r = [np.linalg.norm(d) for d in d_r]
    return {"loss_gap": _rel(got["loss"], ref["loss"]),
            "reward_gap": _rel(got["reward"], ref["reward"]),
            "moment_gap": worst(mu_g, mu_r),
            "change_gap": worst(ch_g, ch_r),
            "change_diff": _worst_diff(d_g, d_r, keep)}


def diagnostics(got: dict, ref: dict) -> dict:
    """Readings beside ``readings`` that no limit holds, for
    ``bench/control.py``: the rollout's mean value, and the worst leaf's
    norm of the difference of Adam's first moment after iteration 1."""
    mu_g, mu_r = _leaves(got["mu1"]), _leaves(ref["mu1"])
    return {"value_gap": _rel(got["value"], ref["value"]),
            "moment_diff": _worst_diff(mu_g, mu_r, range(len(mu_r)))}
