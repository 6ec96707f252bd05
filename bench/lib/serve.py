"""The serving cells: ``PolicyServer.serve`` replaying open-loop traffic.

The server is built as ``launch/policy_serve.build_server_and_trace``
builds it (one compiled slot, the ``auto`` route: the Pallas slot
forward on the chip), with the policy weights made from the seed. The
benchmark hands ``serve`` its own scheduler, a ``SlotScheduler`` that
also notes which requests each dispatch popped and when it completed,
and wraps the server's ``forward_slot`` to keep each dispatch's outputs
(device arrays, read only after the window). Latency runs from a
request's due time (the window's start plus its arrival) to the host
clock at its slot's completion.
"""
from __future__ import annotations

import time

import numpy as np

from bench.lib import requests, weights
from bench.lib.tracing import span


def trace_for(cfg: dict, mix: dict, seed: int, horizon_s: float,
              rate: float | None = None) -> dict:
    frame_dim = cfg["obs_dim"] * cfg["policy"]["frame_stack"]
    pool = np.random.default_rng([seed, 1]).binomial(
        1, mix["frame_density"], (mix["frame_pool"], frame_dim)
    ).astype(np.float32)
    return requests.generate(
        n_regions=mix["regions"], region_sizes=mix["region_sizes"],
        rate=mix["rate_rps"] if rate is None else rate, horizon_s=horizon_s,
        classes_s=mix["classes_s"], class_mix=mix["class_mix"],
        frame_dim=frame_dim, seed=seed, frame_pool=pool,
        fixed_arrivals=mix.get("fixed_arrivals", False))


def program_requests(tr: dict) -> list:
    from repro.serving import Request
    cols = zip(tr["region"].tolist(), tr["klass"].tolist(),
               tr["arrival"].tolist(), tr["deadline"].tolist(),
               list(tr["frame"]), tr["size"].tolist())
    return [Request(rid=i, region=r, klass=k, arrival=a, deadline=d,
                    frame=f, size=z)
            for i, (r, k, a, d, f, z) in enumerate(cols)]


class Server:
    """The policy server under test, with the benchmark's recording."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from bench.lib import policies
        pol = policies.module(cfg)
        self.cfg, self.mix = cfg, mix
        self.params = weights.make(cfg, seed)["policy"]
        self.server = pol.server(cfg, mix, self.params)
        self.server.warmup()
        forward = self.server.forward_slot
        self.outs = []

        def recorded(frames, n_valid, pidx=None):
            with span("bench.forward"):
                out = forward(frames, n_valid, pidx)
            self.outs.append(out)
            return out

        self.server.forward_slot = recorded

    def replay(self, tr: dict, reqs: list | None = None) -> dict:
        """Serve ``tr`` (whose requests ``reqs`` may be built ahead) to the
        end -> per-request latency (s, from due time; inf if never
        served), the dispatch of each request and its lane there, and the
        server's ``ServeStats``."""
        from repro.serving import SlotScheduler

        class Recorder(SlotScheduler):
            def __init__(self, slot):
                super().__init__(slot)
                self.popped, self.done_at = [], []

            def next_dispatch(self):
                with span("bench.pop"):
                    shape, batch = super().next_dispatch()
                self.popped.append([r.rid for r in batch])
                return shape, batch

            def complete(self, batch, t_done):
                self.done_at.append(time.perf_counter())
                super().complete(batch, t_done)

        if reqs is None:
            reqs = program_requests(tr)
        sched = Recorder(self.server.slot)
        self.outs = []
        n = len(reqs)
        with span("bench.window"):
            t0 = time.perf_counter()
            report = self.server.serve(reqs, scheduler=sched)
        lat = np.full(n, np.inf)
        where = np.full((n, 2), -1, np.int64)
        for j, (rids, t) in enumerate(zip(sched.popped, sched.done_at)):
            rids = np.asarray(rids, np.int64)
            lat[rids] = t - (t0 + tr["arrival"][rids])
            where[rids, 0] = j
            where[rids, 1] = np.arange(len(rids))
        return {"latency_s": lat, "where": where, "stats": report.stats,
                "close": t0 + float(tr["arrival"][-1]) if n else t0}

    def served_outputs(self, where: np.ndarray, idx: np.ndarray):
        """-> (actions, logits) the server produced for requests ``idx``."""
        acts = np.empty(len(idx), np.int64)
        logits = np.empty((len(idx), self.cfg["n_actions"]), np.float32)
        cache = {}
        for k, i in enumerate(idx):
            j, lane = where[i]
            if j not in cache:
                a, lg, _ = self.outs[j]
                cache[j] = (np.asarray(a), np.asarray(lg))
            acts[k], logits[k] = cache[j][0][lane], cache[j][1][lane]
        return acts, logits


def readings(served_actions, served_logits, ref_logits) -> dict:
    """- ``logit_err``: the largest |served - reference| logit over the
      sample, over the sample's largest |reference logit|;
    - ``action_gap``: the widest gap by which a served action's reference
      logit lies below the reference's best, on the same scale."""
    scale = max(float(np.max(np.abs(ref_logits))), 1e-30)
    err = float(np.max(np.abs(served_logits - ref_logits))) / scale
    chosen = np.take_along_axis(ref_logits, served_actions[:, None], 1)[:, 0]
    gap = float(np.max(ref_logits.max(-1) - chosen)) / scale
    return {"logit_err": err, "action_gap": gap}


def reference_logits(cfg: dict, params, frames: np.ndarray, dt):
    import jax
    import jax.numpy as jnp
    from bench.reference import common
    fwd = jax.jit(lambda p, x: common.policy(cfg, p, x, dt)[0])
    return np.asarray(fwd(params, jnp.asarray(frames)), np.float32)
