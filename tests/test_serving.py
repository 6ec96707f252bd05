"""The serving tier (PR 8): ragged-batch parity + scheduler guarantees.

Pins the serving contract of docs/ARCHITECTURE.md §8 exactly as stated:

* **Packed-vs-dense bitwise parity.** Inside one jitted fixed-slot
  program, a real lane's (action, logits, v) are bitwise-identical to a
  dense all-copies dispatch of the same request at the same slot shape —
  whatever the pad lanes hold (zeros, 1e6, NaN) and wherever the lane
  sits. Pinned for both domains x both AIP backbones (backbone-specific
  engine rollouts supply the frames) on the production dispatch route
  AND the forced interpret-mode Pallas kernel. The reference is a
  same-slot-shape dispatch on purpose: XLA's GEMM reduction order is
  program-shape-dependent, so the *compiled fixed-slot program* — not
  "the math" — is the unit of bitwise reproducibility.
* **Pad lanes are no-ops.** Outputs at pad lanes are exactly zero (and
  action 0) regardless of pad content; pad content never perturbs real
  lanes (property-tested across fill patterns via hypothesis, or its
  deterministic hypcompat grid when hypothesis is absent).
* **Scheduler guarantees.** No silent drops, EDF across classes with
  FIFO within a class, and miss counters that equal a ground-truth
  recount of the completion log — on adversarial traces with tied
  arrivals and a zero-slack deadline class.
* **Serve-time restore.** ``ckpt.restore_subtree`` brings a policy out
  of a full rl_train checkpoint without reading the training payload —
  proven by deleting every non-policy member from ``arrays.npz`` and
  restoring anyway.
"""
import json
import zipfile

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # pure-pytest fallback (hypcompat)
    from hypcompat import given, settings, st

from repro.checkpoint import ckpt
from repro.core import engine, influence
from repro.envs.api import pad_lanes, pad_mask
from repro.envs.traffic import TrafficConfig, make_batched_local_traffic_env
from repro.envs.warehouse import (WarehouseConfig,
                                  make_batched_local_warehouse_env)
from repro.launch import policy_serve
from repro.rl import ppo
from repro.serving import (PolicyServer, Request, ServeStats,
                           SlotScheduler, TraceConfig, synthetic_trace)

S = 8                                    # the test slot shape
FRAME_STACK = {"traffic": 1, "warehouse": 8}    # as rl_train.build_domain
_JUNK = {"zero": 0.0, "big": 1e6, "nan": np.nan}
_cache = {}


def _bls(domain):
    if domain == "traffic":
        return make_batched_local_traffic_env(TrafficConfig())
    return make_batched_local_warehouse_env(WarehouseConfig())


def _frames(domain, kind):
    """(S, frame_dim) f32 observation frames from a short rollout of the
    unified IALS engine with the given AIP backbone — real serving
    inputs, and the backbone axis of the parity matrix."""
    key = ("frames", domain, kind)
    if key not in _cache:
        bls = _bls(domain)
        acfg = influence.AIPConfig(kind=kind, d_in=bls.spec.dset_dim,
                                   n_out=bls.spec.n_influence, hidden=8,
                                   stack=2)
        aip = influence.init_aip(acfg, jax.random.PRNGKey(0))
        env = engine.make_unified_ials(bls, aip, acfg, n_agents=1,
                                       use_horizon_kernel=False)
        state = env.reset(jax.random.PRNGKey(1), S)
        k = jax.random.PRNGKey(2)
        for _ in range(2):
            k, ka, ks = jax.random.split(k, 3)
            a = jax.random.randint(ka, (S,), 0, bls.spec.n_actions)
            state, _, _, _ = env.step(state, a, ks)
        obs = np.asarray(env.observe(state), np.float32)
        _cache[key] = np.tile(obs, (1, FRAME_STACK[domain]))
    return _cache[key]


def _server(domain, route):
    """One PolicyServer per (domain, route), shared across tests so each
    jitted slot program compiles once. All routes of a domain share the
    same params (same init key)."""
    key = ("server", domain, route)
    if key not in _cache:
        bls = _bls(domain)
        pcfg = ppo.PPOConfig(obs_dim=bls.spec.obs_dim,
                             n_actions=bls.spec.n_actions,
                             frame_stack=FRAME_STACK[domain], hidden=16)
        params = ppo.init_policy(pcfg, jax.random.PRNGKey(3))
        _cache[key] = PolicyServer(params, obs_dim=pcfg.obs_dim,
                                   n_actions=pcfg.n_actions,
                                   frame_stack=FRAME_STACK[domain],
                                   slot=S, route=route)
    return _cache[key]


def _packed(frames, n_valid, junk):
    out = frames.copy()
    out[n_valid:] = _JUNK[junk]
    return out


# ---------------------------------------------------------------- parity

@pytest.mark.parametrize("route", ["auto", "interpret"])
@pytest.mark.parametrize("kind", ["gru", "fnn"])
@pytest.mark.parametrize("domain", ["traffic", "warehouse"])
def test_packed_vs_dense_bitwise(domain, kind, route):
    """Every real lane of a NaN-padded packed slot == the same request
    dispatched dense (all-copies, same slot shape), bitwise, on both
    dispatch routes; pad-lane outputs are exactly zero."""
    frames = _frames(domain, kind)
    srv = _server(domain, route)
    for n_valid in (1, 3, S):
        a, lg, v = srv.forward_slot(_packed(frames, n_valid, "nan"),
                                    n_valid)
        for i in range(n_valid):
            da, dlg, dv = srv.forward_slot(np.tile(frames[i], (S, 1)), S)
            assert jnp.array_equal(lg[i], dlg[i]), (n_valid, i)
            assert jnp.array_equal(v[i], dv[i]), (n_valid, i)
            assert int(a[i]) == int(da[i]), (n_valid, i)
        assert not jnp.any(lg[n_valid:]) and not jnp.any(v[n_valid:])
        assert not jnp.any(a[n_valid:])


@settings(max_examples=20, deadline=None)
@given(n_valid=st.integers(1, S),
       junk=st.sampled_from(["zero", "big", "nan"]))
def test_pad_content_never_perturbs_real_lanes(n_valid, junk):
    """Property: real-lane outputs are a function of real-lane inputs
    only — any pad fill (including NaN, which would poison an unmasked
    reduction) leaves them bitwise-unchanged on both routes."""
    frames = _frames("traffic", "gru")
    for route in ("auto", "interpret"):
        srv = _server("traffic", route)
        base = srv.forward_slot(_packed(frames, n_valid, "zero"), n_valid)
        var = srv.forward_slot(_packed(frames, n_valid, junk), n_valid)
        for b, w in zip(base, var):
            assert jnp.array_equal(b[:n_valid], w[:n_valid]), (route, junk)
        assert not jnp.any(var[1][n_valid:])


def test_lane_permutation_equivariance():
    """Where a request sits in the slot does not change its outputs:
    permuting the packed lanes permutes the outputs, bitwise."""
    frames = _frames("traffic", "fnn")
    perm = np.random.default_rng(0).permutation(S)
    for route in ("auto", "interpret"):
        srv = _server("traffic", route)
        out = srv.forward_slot(frames, S)
        pout = srv.forward_slot(frames[perm], S)
        for o, p in zip(out, pout):
            assert jnp.array_equal(p, jnp.asarray(o)[perm]), route


@pytest.mark.parametrize("domain", ["traffic", "warehouse"])
def test_serve_forward_matches_training_policy(domain):
    """The fused serving forward == the training net
    (``ppo.policy_forward``) on logits/actions bitwise under jit; ``v``
    is the documented 1-ulp allclose leaf (the fused route computes both
    heads as one GEMM)."""
    frames = _frames(domain, "gru")
    aa, la, va = _server(domain, "auto").forward_slot(frames, S)
    ax, lx, vx = _server(domain, "xla").forward_slot(frames, S)
    assert jnp.array_equal(la, lx)
    assert jnp.array_equal(aa, ax)
    assert jnp.allclose(va, vx, atol=1e-6)


def test_pad_lanes_and_mask_contract():
    """The ragged-batch packing helpers: edge fill replicates lane 0,
    zero fill writes zeros, oversize batches and unknown fills raise,
    and ``pad_mask`` marks exactly the real prefix."""
    tree = {"x": jnp.arange(6.0).reshape(3, 2), "y": jnp.arange(3)}
    out = pad_lanes(tree, 5)
    assert out["x"].shape == (5, 2) and out["y"].shape == (5,)
    assert jnp.array_equal(out["x"][:3], tree["x"])
    assert jnp.array_equal(out["x"][3:],
                           jnp.broadcast_to(tree["x"][:1], (2, 2)))
    zout = pad_lanes(tree, 5, fill="zero")
    assert not jnp.any(zout["y"][3:])
    assert zout["y"].dtype == tree["y"].dtype
    with pytest.raises(ValueError):
        pad_lanes(tree, 2)
    with pytest.raises(ValueError):
        pad_lanes(tree, 5, fill="wrap")
    assert jnp.array_equal(pad_mask(3, 5),
                           jnp.array([1, 1, 1, 0, 0], bool))
    with pytest.raises(ValueError):
        PolicyServer({}, obs_dim=4, n_actions=2, route="mystery")


# ------------------------------------------------------------- scheduler

def _adversarial_trace(seed, n=60):
    """Tied arrivals (coarse rounding), a zero-slack deadline class
    (klass 0 misses by construction), interleaved classes."""
    rng = np.random.default_rng(seed)
    classes = (0.0, 0.004, 0.02)
    arrivals = np.sort(np.round(rng.uniform(0.0, 0.05, n), 3))
    frame = np.zeros(4, np.float32)
    return [Request(rid=rid, region=int(rng.integers(0, 5)),
                    klass=(k := int(rng.integers(0, len(classes)))),
                    arrival=float(t), deadline=float(t) + classes[k],
                    frame=frame)
            for rid, t in enumerate(arrivals)]


def _drive(trace, slot, service_s=0.003):
    """The server's replay loop with a virtual clock, scheduler only —
    returns (scheduler, batches in pop order)."""
    sched = SlotScheduler(slot)
    pops, now, i = [], 0.0, 0
    while i < len(trace) or sched.pending:
        while i < len(trace) and trace[i].arrival <= now:
            sched.admit(trace[i])
            i += 1
        if not sched.pending:
            now = trace[i].arrival
            continue
        batch = sched.next_batch()
        now += service_s
        sched.complete(batch, now)
        pops.append(batch)
    return sched, pops


@given(seed=st.integers(0, 3), slot=st.sampled_from([1, 3, 8]))
def test_scheduler_no_drops_and_exact_miss_accounting(seed, slot):
    """Every admitted request is served exactly once (even the ones that
    already missed — recorded, never shed), and the miss counters equal
    an independent recount of the completion log."""
    trace = _adversarial_trace(seed)
    sched, pops = _drive(trace, slot)
    served_rids = sorted(r.rid for b in pops for r in b)
    assert served_rids == list(range(len(trace)))     # exactly once each
    assert sched.served == sched.admitted == len(trace)
    assert sched.pending == 0
    misses, by_class = 0, {}
    for rid, klass, arrival, deadline, t_done in sched.completions:
        assert deadline == trace[rid].deadline
        if t_done > deadline:
            misses += 1
            by_class[klass] = by_class.get(klass, 0) + 1
    assert sched.deadline_misses == misses
    assert sched.misses_by_class == by_class
    assert misses > 0                    # klass 0 has zero slack


@given(seed=st.integers(0, 3), slot=st.sampled_from([1, 3, 8]))
def test_scheduler_edf_and_fifo_within_class(seed, slot):
    """Each popped batch is deadline-sorted (EDF), and per deadline
    class the global pop order is admission order (FIFO) — absolute
    deadlines make that a theorem, the heap tiebreak makes it bitwise."""
    trace = _adversarial_trace(seed)
    _, pops = _drive(trace, slot)
    for batch in pops:
        dls = [r.deadline for r in batch]
        assert dls == sorted(dls)
    flat = [r for b in pops for r in b]
    for klass in {r.klass for r in trace}:
        rids = [r.rid for r in flat if r.klass == klass]
        assert rids == sorted(rids), klass


def test_scheduler_rejects_degenerate_slot():
    with pytest.raises(ValueError):
        SlotScheduler(0)


# ------------------------------------------------- trace + virtual replay

def test_synthetic_trace_deterministic_sorted_and_bursty():
    cfg = TraceConfig(n_regions=12, mean_rps=600.0, horizon_s=0.3,
                      frame_dim=6, seed=4)
    a, b = synthetic_trace(cfg), synthetic_trace(cfg)
    assert len(a) == len(b) > 0
    sizes_by_region = {}
    for ra, rb in zip(a, b):
        assert (ra.rid, ra.region, ra.klass, ra.arrival,
                ra.deadline) == (rb.rid, rb.region, rb.klass, rb.arrival,
                                 rb.deadline)
        assert np.array_equal(ra.frame, rb.frame)        # pure fn of cfg
        assert ra.deadline == ra.arrival + cfg.classes_s[ra.klass]
        assert ra.frame.shape == (cfg.frame_dim,)
        sizes_by_region.setdefault((ra.region, ra.arrival), 0)
        sizes_by_region[(ra.region, ra.arrival)] += 1
    assert [r.rid for r in a] == list(range(len(a)))     # dense rids
    arrivals = [r.arrival for r in a]
    assert arrivals == sorted(arrivals)
    sizes = {}
    for (region, _), k in sizes_by_region.items():
        assert k in cfg.region_sizes                     # whole bursts
        sizes.setdefault(region, set()).add(k)
    assert all(len(s) == 1 for s in sizes.values())      # fixed per region
    assert len({r.region for r in a}) == cfg.n_regions   # staggered phases


def test_virtual_replay_report_is_exact_and_deterministic():
    """``mode="virtual"`` report numbers equal a ground-truth recount of
    the scheduler's completion log, and two replays are identical."""
    srv = _server("traffic", "auto")
    trace = synthetic_trace(TraceConfig(
        n_regions=8, mean_rps=400.0, horizon_s=0.2,
        frame_dim=srv.frame_dim, seed=5))
    sched = SlotScheduler(srv.slot)
    rep = srv.serve(trace, sched, mode="virtual", service_time_s=0.002)
    assert rep.requests == rep.served == len(trace) == sched.served
    assert rep.dispatches >= 1
    assert rep.mean_occupancy * rep.dispatches == pytest.approx(
        rep.served)                      # every request in some batch
    lat = np.array([t - a for (_, _, a, _, t) in sched.completions])
    assert rep.p50_s == float(np.percentile(lat, 50))
    assert rep.p99_s == float(np.percentile(lat, 99))
    misses = sum(t > d for (_, _, _, d, t) in sched.completions)
    assert rep.deadline_misses == misses == sched.deadline_misses
    last_done = max(t for (_, _, _, _, t) in sched.completions)
    assert np.isclose(rep.qps, rep.served / (last_done
                                             - trace[0].arrival))
    rep2 = srv.serve(trace, mode="virtual", service_time_s=0.002)
    assert rep2.latencies_s == rep.latencies_s
    assert rep2.summary() == rep.summary()
    with pytest.raises(ValueError):
        srv.serve(trace, mode="closed-loop")


def _dyadic_trace(srv, n=40):
    """Arrivals on a 1/1024 s grid in bursts, so requests queue and every
    clock sum of a virtual replay at a dyadic service time is exact."""
    rng = np.random.default_rng(7)
    arrivals = np.sort(rng.integers(0, 64, n)) / 1024
    frame = np.zeros(srv.frame_dim, np.float32)
    return [Request(rid=i, region=i % 5, klass=0, arrival=float(t),
                    deadline=float(t) + 1.0, frame=frame)
            for i, t in enumerate(arrivals)]


def test_virtual_replay_wait_counters_add_up():
    """Each request's latency is its queue wait (pop less arrival) plus
    its service (completion less pop): summed over the replay the
    counters and the report agree exactly. The loop notices an arrival
    no later than its dispatch is popped."""
    srv = _server("traffic", "auto")
    trace = _dyadic_trace(srv)
    svc = 2.0 ** -8
    rep = srv.serve(trace, SlotScheduler(srv.slot), mode="virtual",
                    service_time_s=svc)
    st_ = rep.stats
    assert rep.served == len(trace)
    assert st_.queue_wait_s + rep.served * svc == sum(rep.latencies_s)
    assert 0.0 < st_.admit_lag_s <= st_.queue_wait_s
    assert 0.0 < st_.admit_lag_max_s <= st_.queue_wait_max_s
    assert st_.queue_wait_max_s + svc == max(rep.latencies_s)
    s = rep.summary()
    assert (s["admit_lag_s"], s["admit_lag_max_s"], s["queue_wait_s"],
            s["queue_wait_max_s"]) == (
        st_.admit_lag_s, st_.admit_lag_max_s, st_.queue_wait_s,
        st_.queue_wait_max_s)
    fresh = ServeStats().summary()
    for key in ("admit_lag_s", "admit_lag_max_s", "queue_wait_s",
                "queue_wait_max_s", "padded_lane_frac", "rejected"):
        assert fresh[key] == 0


def test_serve_spans_nest_in_each_dispatch(tmp_path):
    """A profiler trace of a replay holds one ``serve.dispatch`` span per
    dispatch (its index and real lanes attached) with ``serve.pop``,
    ``serve.pack``, ``serve.forward`` and ``serve.complete`` inside it in
    that order, and ``serve.put``, ``serve.launch``, ``serve.wait``
    inside ``serve.forward``."""
    srv = _server("traffic", "auto")
    trace = _dyadic_trace(srv)
    with jax.profiler.trace(str(tmp_path)):
        rep = srv.serve(trace, SlotScheduler(srv.slot), mode="virtual",
                        service_time_s=2.0 ** -8)
    spans = []
    for path in tmp_path.rglob("*.xplane.pb"):
        for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith("serve.")]
    spans.sort(key=lambda s: (s[0], -s[1]))

    def inside(outer, names):
        return [s[2] for s in spans if s[2] in names
                and outer[0] <= s[0] and s[1] <= outer[1]]

    disp = [s for s in spans if s[2] == "serve.dispatch"]
    assert len(disp) == rep.dispatches > 1
    assert [d[3]["dispatch"] for d in disp] == list(range(rep.dispatches))
    assert sum(d[3]["lanes"] for d in disp) == rep.served
    parts = ("serve.pop", "serve.pack", "serve.forward", "serve.complete")
    for d in disp:
        assert inside(d, parts) == list(parts)
    fwd = [s for s in spans if s[2] == "serve.forward"]
    assert len(fwd) == rep.dispatches
    for f in fwd:
        assert inside(f, ("serve.put", "serve.launch", "serve.wait")) == [
            "serve.put", "serve.launch", "serve.wait"]
    assert [s for s in spans if s[2] == "serve.admit"]


# ------------------------------------------------------ restore + driver

def test_serve_restore_reads_only_policy_payload(tmp_path):
    """Serve-time policy restore never touches the training payload:
    delete every non-``['policy']`` member from ``arrays.npz`` — full
    ``restore`` breaks, ``restore_subtree`` still yields exact params,
    and a server built from them matches the original bitwise."""
    pcfg = ppo.PPOConfig(obs_dim=41, n_actions=2, frame_stack=1,
                         hidden=16)
    policy = ppo.init_policy(pcfg, jax.random.PRNGKey(7))
    tree = {"policy": policy,
            "opt": {"m": jnp.zeros((256, 256)), "v": jnp.ones((256, 256))},
            "rs": jnp.arange(32, dtype=jnp.uint32),
            "it": jnp.int32(11)}
    ckpt.save(tmp_path, 11, tree, metadata={"it": 11})

    d = tmp_path / "step_000000011"
    meta = msgpack.unpackb((d / "meta.msgpack").read_bytes())
    keep = {f"leaf_{i:05d}.npy" for i, p in enumerate(meta["paths"])
            if p.startswith("['policy']")}
    assert 0 < len(keep) < len(meta["paths"])
    src = d / "arrays.npz"
    with zipfile.ZipFile(src) as zin:
        members = {n: zin.read(n) for n in zin.namelist() if n in keep}
    with zipfile.ZipFile(src, "w") as zout:
        for n, raw in members.items():
            zout.writestr(n, raw)

    with pytest.raises(KeyError):        # training payload really gone
        ckpt.restore(tmp_path, jax.eval_shape(lambda: tree))
    got, step, user = ckpt.restore_subtree(
        tmp_path, jax.eval_shape(lambda: policy), "['policy']")
    assert step == 11 and user == {"it": 11}
    for a, b in zip(jax.tree_util.tree_leaves(policy),
                    jax.tree_util.tree_leaves(got)):
        assert a.dtype == b.dtype and bool((a == b).all())

    frames = _frames("traffic", "gru")
    kw = dict(obs_dim=41, n_actions=2, frame_stack=1, slot=S)
    out_a = PolicyServer(policy, **kw).forward_slot(frames, 5)
    out_b = PolicyServer(got, **kw).forward_slot(frames, 5)
    for x, y in zip(out_a, out_b):
        assert jnp.array_equal(x, y)


def test_policy_serve_driver_end_to_end(tmp_path):
    """The launch driver serves a small wall-clock trace to completion
    and writes the JSON report."""
    out = tmp_path / "serve.json"
    res = policy_serve.main([
        "--domain", "traffic", "--slot", "8", "--regions", "4",
        "--rps", "400", "--duration-s", "0.05", "--out", str(out)])
    assert res["served"] == res["requests"] > 0
    assert res["p99_ms"] >= res["p50_ms"] > 0
    on_disk = json.loads(out.read_text())
    assert on_disk == res                # json round-trips floats exactly
