"""PolicyServer: multi-slot, multi-policy continuous-batching inference.

One server = one or more trained policies + a small table of jitted slot
programs. Every dispatch runs one compiled masked slot forward on a
packed (shape, frame_dim) batch with a lane-validity mask — pad lanes
are zeroed inside the dispatch (the ragged-batch contract,
``envs/api.py``), and actions are the greedy ``argmax`` over the masked
logits, exactly the deployment policy ``rl/ppo.py::make_evaluator``
measures.

**Slot shapes.** ``slot`` is either one shape (the PR-8 fixed-slot
server: ONE compiled program, every dispatch padded to it) or an
ascending bucket set, e.g. ``(16, 64, 256)`` — one compiled program per
shape, all warmed before the serving clock starts (``warmup``), with
``scheduler.py::BucketedSlotScheduler`` right-sizing each dispatch into
the smallest admissible shape. Packing reuses one preallocated staging
buffer per shape (no per-dispatch allocation; pad lanes keep whatever
the previous dispatch left — garbage by contract, masked at the kernel
boundary).

**Policies.** ``params`` is either one policy tree (the single-tenant
``kernels/ops.py::serve_forward`` program) or a list of N trees —
cross-policy batching: the weights stack into one leading policy axis
(``rl/ppo.py::stack_policy_weights``) and every lane of a packed slot
selects its own checkpoint by index inside the one dispatch
(``kernels/ops.py::serve_forward_multi``), so one server process serves
a whole family of per-region checkpoints.

**Lifecycle + overload hardening (PR 10, the overload contract of
docs/ARCHITECTURE.md §8).** The server walks ``warming -> serving ->
draining -> drained``: ``warmup`` compiles every slot program before
the clock starts, ``serve`` flips to ``serving``, transitions to
``draining`` once the trace's arrivals are exhausted (only backlog
remains; ``drain`` is the standalone version), and lands on ``drained``
with a final stats snapshot. ``serve`` optionally takes an
``overload.py::AdmissionController`` (bounded queue +
deadline-feasibility rejection + brownout shedding — explicit counted
sheds instead of silent deadline misses), a
``distributed/fault_injection.py::FaultInjector`` (``SlowDispatch``,
``RequestFlood``, ``CorruptCheckpoint`` fire at deterministic
dispatch/reload seams), and ``reload_at`` hot-reload points.

**Hot policy reload.** ``reload(params)`` swaps the serving weights
in-place — same compiled programs, new weights (the forward takes the
weight pytree as a jit *argument*, so a same-shape swap never
recompiles) — but only after validation: (1) an ABI check (the
candidate's weight pytree must match the serving weights' structure,
shapes, and dtypes exactly), (2) a canary forward on a pinned probe
slot whose outputs must be finite, and (3) bitwise agreement of that
canary with the candidate's *own fresh server* at the same probe shape.
Any failure rolls back to the previous weights and counts
``reload_rejected`` — the server keeps serving bitwise-identical
outputs on the old weights. ``reload_from_checkpoint`` wires the same
gate to ``checkpoint/ckpt.py::restore_subtree``, so a torn or corrupt
checkpoint (COMMITTED missing, truncated payload, mangled metadata) is
rejected at restore and can never be swapped in.

Reproducibility contract (docs/ARCHITECTURE.md §8): the slot shape set
is static per server, and each forward always runs as the same jitted
program — XLA's GEMM reduction order is shape- and program-dependent, so
the *compiled slot program* is the unit of bitwise reproducibility.
Within one program, a real lane's (logits, v, action) are
bitwise-identical whatever the pad lanes hold and wherever in the slot
the lane sits — and a multi-policy lane is bitwise-identical to the
single-policy server of its own checkpoint at the same shape. Pinned by
``tests/test_serving.py`` on both the oracle and
forced-interpret-kernel routes.

Latency measurement (the driver + bench method): open-loop trace replay
on a wall clock. Request latency = (slot dispatch completion, blocked on
device outputs) - (trace arrival time); a request that waits in queue
pays its queueing delay in full, and arrivals never throttle to the
server's pace. ``mode="virtual"`` replaces the wall clock with a fixed
per-dispatch service time so scheduler tests — and every overload /
fault-injection decision — are deterministic.

Host spans (``jax.profiler.TraceAnnotation``, on the profiler's clock
with the device's ops; free but for well under a microsecond each when
no trace is active): ``serve.dispatch`` per dispatch (its index and real
lanes attached) holding ``serve.pop``, ``serve.pack``, ``serve.forward``
(itself ``serve.put``, ``serve.launch``, ``serve.wait``) and
``serve.complete``; ``serve.admit`` around each pass that admits due
arrivals; ``serve.idle`` around the open-loop sleep to the next arrival.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.envs.api import pad_mask
from repro.kernels import ops
from repro.rl.ppo import (flat_policy_weights, policy_forward,
                          stack_policy_weights)
from repro.serving.request import Request, flood_trace
from repro.serving.scheduler import BucketedSlotScheduler, SlotScheduler

#: occupancy-fraction bins per slot shape in ``ServeStats`` histograms
HIST_BINS = 8

#: server lifecycle states, in order
LIFECYCLE = ("warming", "serving", "draining", "drained")


def _span(name: str, **kwargs):
    """A host span ``name`` in the profiler's trace around a phase of the
    serving loop. With no trace active it costs well under a
    microsecond, and ``kwargs`` are formatted only while one is."""
    return jax.profiler.TraceAnnotation(name, **kwargs)


def _lag(arrivals, t: float) -> Tuple[float, float]:
    """-> (sum, max) over ``arrivals`` of ``t`` less the arrival."""
    return len(arrivals) * t - sum(arrivals), t - min(arrivals)


@dataclass
class ServeStats:
    """Padding-waste + overload observability, accumulated per replay.

    ``record(shape, n)`` logs one dispatch of ``n`` real lanes in a
    ``shape``-lane program; ``record_rejection(reason, klass)`` logs one
    counted admission shed. The exported counters (all in ``summary()``
    and surfaced by ``repro.launch.policy_serve`` + the serve bench
    JSON): dispatches and real/padded lane totals per slot shape, the
    aggregate ``padded_lane_frac`` (padded lanes / dispatched lanes —
    the pure-waste FLOP fraction the bucketed scheduler exists to
    shrink), a per-shape occupancy histogram (``HIST_BINS`` equal
    occupancy-fraction bins; a healthy bucket loads the last bin), and
    the overload counters: ``rejected`` total with
    ``rejected_by_reason`` (queue_full / brownout / infeasible) and
    ``shed_by_class`` breakdowns, plus the replay's hot-reload outcomes
    (``reloads`` accepted, ``reload_rejected`` rolled back) and the
    lifecycle state at snapshot time (``final_state``). The wait
    counters, on the replay loop's clock (s from its start): the sum and
    the largest, over requests, of ``admit_lag`` (the clock when the
    loop took the request from the trace, admitted or shed, less its
    arrival: how late the open loop noticed it) and of ``queue_wait``
    (the clock when its dispatch was popped less its arrival), recorded
    by ``record_admit`` / ``record_wait``. Every ratio is guarded for
    the zero-dispatch replay (empty or fully shed trace): ``summary()``
    on a fresh instance is all zeros/empties, never a division
    error."""
    dispatches_by_slot: Dict[int, int] = field(default_factory=dict)
    lanes_by_slot: Dict[int, int] = field(default_factory=dict)
    occupancy_hist_by_slot: Dict[int, List[int]] = field(
        default_factory=dict)
    rejected: int = 0
    rejected_by_reason: Dict[str, int] = field(default_factory=dict)
    shed_by_class: Dict[int, int] = field(default_factory=dict)
    reloads: int = 0
    reload_rejected: int = 0
    final_state: str = ""
    admit_lag_s: float = 0.0
    admit_lag_max_s: float = 0.0
    queue_wait_s: float = 0.0
    queue_wait_max_s: float = 0.0

    def record(self, shape: int, n: int) -> None:
        self.dispatches_by_slot[shape] = (
            self.dispatches_by_slot.get(shape, 0) + 1)
        self.lanes_by_slot[shape] = self.lanes_by_slot.get(shape, 0) + n
        hist = self.occupancy_hist_by_slot.setdefault(
            shape, [0] * HIST_BINS)
        hist[min(HIST_BINS - 1, max(0, (n - 1) * HIST_BINS // shape))] += 1

    def record_rejection(self, reason: str, klass: int) -> None:
        """One counted admission shed (the overload contract: explicit
        rejections replace silent deadline misses)."""
        self.rejected += 1
        self.rejected_by_reason[reason] = (
            self.rejected_by_reason.get(reason, 0) + 1)
        self.shed_by_class[klass] = self.shed_by_class.get(klass, 0) + 1

    def record_admit(self, now: float, arrivals: List[float]) -> None:
        """The loop took requests arriving at ``arrivals`` from the trace
        at ``now``."""
        total, most = _lag(arrivals, now)
        self.admit_lag_s += total
        self.admit_lag_max_s = max(self.admit_lag_max_s, most)

    def record_wait(self, t_pop: float, arrivals: List[float]) -> None:
        """One dispatch of requests arriving at ``arrivals`` was popped
        at ``t_pop``."""
        total, most = _lag(arrivals, t_pop)
        self.queue_wait_s += total
        self.queue_wait_max_s = max(self.queue_wait_max_s, most)

    @property
    def dispatches(self) -> int:
        return sum(self.dispatches_by_slot.values())

    @property
    def total_lanes(self) -> int:
        """Dispatched lanes, real + padded (occupancy denominator)."""
        return sum(s * k for s, k in self.dispatches_by_slot.items())

    @property
    def real_lanes(self) -> int:
        return sum(self.lanes_by_slot.values())

    @property
    def padded_lane_frac(self) -> float:
        total = self.total_lanes
        return (total - self.real_lanes) / total if total else 0.0

    def summary(self) -> Dict:
        return {
            "padded_lane_frac": self.padded_lane_frac,
            "dispatches_by_slot": {str(s): k for s, k in
                                   sorted(self.dispatches_by_slot.items())},
            "mean_occupancy_by_slot": {
                str(s): self.lanes_by_slot[s] / (s * k)
                for s, k in sorted(self.dispatches_by_slot.items())},
            "occupancy_hist_by_slot": {
                str(s): list(h) for s, h in
                sorted(self.occupancy_hist_by_slot.items())},
            "rejected": self.rejected,
            "rejected_by_reason": dict(sorted(
                self.rejected_by_reason.items())),
            "shed_by_class": {str(k): v for k, v in
                              sorted(self.shed_by_class.items())},
            "reloads": self.reloads,
            "reload_rejected": self.reload_rejected,
            "final_state": self.final_state,
            "admit_lag_s": self.admit_lag_s,
            "admit_lag_max_s": self.admit_lag_max_s,
            "queue_wait_s": self.queue_wait_s,
            "queue_wait_max_s": self.queue_wait_max_s,
        }


@dataclass
class ServeReport:
    """One trace replay's results. Latencies in seconds; ``qps`` is
    served requests / makespan (first arrival -> last completion);
    ``stats`` is the padding-waste + overload observability
    (``ServeStats`` — rejections, sheds, reload outcomes, lifecycle)."""
    requests: int
    served: int
    p50_s: float
    p99_s: float
    qps: float
    deadline_misses: int
    misses_by_class: Dict[int, int]
    max_queue_depth: int
    dispatches: int
    mean_occupancy: float        # mean real lanes per dispatched slot
    stats: ServeStats = field(default_factory=ServeStats)
    latencies_s: List[float] = field(repr=False, default_factory=list)

    def summary(self) -> Dict:
        """JSON-ready summary (drops the raw latency list)."""
        return {
            "requests": self.requests, "served": self.served,
            "p50_ms": self.p50_s * 1e3, "p99_ms": self.p99_s * 1e3,
            "qps": self.qps, "deadline_misses": self.deadline_misses,
            "misses_by_class": {str(k): v for k, v
                                in sorted(self.misses_by_class.items())},
            "max_queue_depth": self.max_queue_depth,
            "dispatches": self.dispatches,
            "mean_occupancy": self.mean_occupancy,
            **self.stats.summary(),
        }


class _ReloadRejected(Exception):
    """Internal: a reload validation gate failed (reason in args)."""


class PolicyServer:
    """Continuous-batching inference over a table of jitted slot programs.

    ``slot``: one shape (fixed-slot server) or an ascending bucket set
    (multi-slot server; dispatches right-size via
    ``BucketedSlotScheduler``). ``params``: one policy tree, or a list
    of N trees for cross-policy batching (lane -> checkpoint by the
    request's ``policy`` index).

    ``route`` selects the forward implementation (all three agree on
    logits/actions bitwise under jit; see the module docstring):
      - ``"auto"``: the production ``ops.serve_forward`` /
        ``ops.serve_forward_multi`` dispatch (compiled Pallas kernel on
        TPU, identical-math oracle elsewhere);
      - ``"interpret"``: force the Pallas kernel in interpret mode (the
        parity tests' route);
      - ``"xla"``: masked ``rl/ppo.py::policy_forward`` — the training
        net verbatim (its separate value-head GEMM makes ``v`` the
        documented 1-ulp leaf vs the fused routes).

    The forward takes its weight pytree as a jit *argument* (not a
    closure constant), which is what makes ``reload`` an atomic swap:
    same shapes -> same compiled programs, zero recompiles.
    """

    def __init__(self, params, *, obs_dim: int, n_actions: int,
                 frame_stack: int = 1,
                 slot: Union[int, Sequence[int]] = 64,
                 fast_gates: bool = True, route: str = "auto"):
        if route not in ("auto", "interpret", "xla"):
            raise ValueError(f"unknown route: {route!r}")
        shapes = (slot,) if isinstance(slot, int) else tuple(slot)
        shapes = tuple(sorted(set(int(s) for s in shapes)))
        if not shapes or shapes[0] < 1:
            raise ValueError(f"slot shapes must be >= 1, got {slot!r}")
        self.slots = shapes
        self.slot = shapes[-1]           # the largest compiled shape
        self.obs_dim = obs_dim
        self.frame_stack = frame_stack
        self.frame_dim = obs_dim * frame_stack
        self.n_actions = n_actions
        self.fast_gates = fast_gates
        self.route = route
        multi = isinstance(params, (list, tuple))
        self.n_policies = len(params) if multi else 1
        self._staging: Dict[int, np.ndarray] = {}
        self._pidx_staging: Dict[int, np.ndarray] = {}
        self._warmed: set = set()
        self.state = "warming"
        self.policy_version = 0
        self.reloads = 0
        self.reload_rejected = 0
        self.reload_log: List[Tuple[str, str]] = []
        # pinned probe slot for reload canaries: fixed frames at the
        # smallest compiled shape, every checkpoint exercised
        self._probe_frames = np.random.default_rng(0).standard_normal(
            (self.slots[0], self.frame_dim)).astype(np.float32)

        interpret = True if route == "interpret" else None
        if multi:
            if route == "xla":
                def fwd(frames, mask, pidx, weights):
                    m = mask != 0
                    logits = jnp.zeros(
                        (frames.shape[0], n_actions), jnp.float32)
                    v = jnp.zeros((frames.shape[0],), jnp.float32)
                    for n, p in enumerate(weights):
                        lg_n, v_n = policy_forward(p, frames,
                                                   fast_gates=fast_gates)
                        sel = pidx == n
                        logits = jnp.where(sel[:, None], lg_n, logits)
                        v = jnp.where(sel, v_n, v)
                    logits = jnp.where(m[:, None], logits, 0.0)
                    v = jnp.where(m, v, 0.0)
                    return jnp.argmax(logits, -1), logits, v

                def make_weights(ps):
                    return tuple(ps)
            else:
                def fwd(frames, mask, pidx, weights):
                    logits, v = ops.serve_forward_multi(
                        frames, mask, pidx, weights, fast_gates=fast_gates,
                        interpret=interpret)
                    return jnp.argmax(logits, -1), logits, v

                def make_weights(ps):
                    return stack_policy_weights(list(ps))
        else:
            if route == "xla":
                def fwd(frames, mask, pidx, weights):
                    del pidx             # single policy: one checkpoint
                    logits, v = policy_forward(weights, frames,
                                               fast_gates=fast_gates)
                    m = mask != 0
                    logits = jnp.where(m[:, None], logits, 0.0)
                    v = jnp.where(m, v, 0.0)
                    return jnp.argmax(logits, -1), logits, v

                def make_weights(ps):
                    return ps
            else:
                def fwd(frames, mask, pidx, weights):
                    del pidx             # single policy: one checkpoint
                    logits, v = ops.serve_forward(frames, mask, weights,
                                                  fast_gates=fast_gates,
                                                  interpret=interpret)
                    return jnp.argmax(logits, -1), logits, v

                def make_weights(ps):
                    return flat_policy_weights(ps)

        self._params = list(params) if multi else params
        self._make_weights = make_weights
        self._weights = make_weights(self._params)
        self._fwd = jax.jit(fwd)

    def forward_slot(self, frames, n_valid: int, pidx=None):
        """One dispatch on an already-padded (shape, frame_dim) batch
        with ``n_valid`` real lanes -> (actions (shape,), logits, v),
        blocked on device completion. The compiled program is selected
        by the batch's shape (one jitted specialization per slot shape).
        ``pidx`` (shape,) int32 routes each lane to its checkpoint on a
        multi-policy server (zeros — checkpoint 0 — when omitted).
        Pad-lane outputs are zeros (and action 0) by the kernel-boundary
        mask — garbage by contract. Host spans: ``serve.put`` (inputs to
        the device), ``serve.launch`` (the jitted call until it returns),
        ``serve.wait`` (until the outputs are ready)."""
        with _span("serve.put"):
            frames = jnp.asarray(frames)
            shape = frames.shape[0]
            if pidx is None:
                pidx = jnp.zeros((shape,), jnp.int32)
            mask = pad_mask(n_valid, shape)
            pidx = jnp.asarray(pidx, dtype=jnp.int32)
        with _span("serve.launch"):
            out = self._fwd(frames, mask, pidx, self._weights)
        self._warmed.add(shape)
        with _span("serve.wait"):
            return jax.block_until_ready(out)

    def warmup(self, shapes: Optional[Sequence[int]] = None) -> None:
        """Compile every slot program before the serving clock starts —
        a trace+compile must never land on a dispatch latency. Idempotent
        per shape; ``serve`` calls it with the scheduler's shape set."""
        for shape in shapes if shapes is not None else self.slots:
            if shape not in self._warmed:
                frames, pidx = self._pack([], shape)
                self.forward_slot(frames, 0, pidx)

    # ---------------------------------------------------- hot reload

    def _probe_pidx(self, shape: int) -> np.ndarray:
        return (np.arange(shape, dtype=np.int32) % self.n_policies)

    def reload(self, params) -> bool:
        """Validated atomic hot swap of the serving weights (the reload
        gate of the overload contract, ARCHITECTURE §8). Three gates, in
        order, all on the *candidate* — the serving weights are untouched
        until every gate passes:

        1. **ABI check**: the candidate's weight pytree (built by the
           same route-specific builder as the serving weights) must
           match structure, shapes, and dtypes exactly.
        2. **Canary forward** on the pinned probe slot (fixed frames at
           the smallest compiled shape, every checkpoint of a
           multi-policy server exercised): all outputs must be finite —
           a NaN/Inf-poisoned payload (torn write, bit rot) dies here.
        3. **Bitwise parity vs the candidate's own fresh server**: a new
           ``PolicyServer`` built from the candidate at the probe shape
           must produce bitwise-identical (action, logits, v) — the
           live program with swapped weights IS the program a fresh
           deployment of those weights would run.

        Success swaps weights + params atomically (same compiled
        programs — the weights are a jit argument), bumps
        ``policy_version`` and ``reloads``, and returns True. Any
        failure (including exceptions from malformed candidates) rolls
        back to the previous weights, counts ``reload_rejected``, logs
        the reason in ``reload_log``, and returns False — the server
        keeps serving bitwise-identical outputs on the old weights."""
        multi = isinstance(self._params, list)
        try:
            if multi != isinstance(params, (list, tuple)):
                raise _ReloadRejected(
                    "abi: single/multi policy kind mismatch")
            if multi and len(params) != self.n_policies:
                raise _ReloadRejected(
                    f"abi: {len(params)} policies for a "
                    f"{self.n_policies}-policy server")
            cand_params = list(params) if multi else params
            try:
                cand = self._make_weights(cand_params)
            except Exception as e:
                raise _ReloadRejected(f"abi: weight build failed: {e}")
            cur_leaves, cur_def = jax.tree_util.tree_flatten(self._weights)
            cand_leaves, cand_def = jax.tree_util.tree_flatten(cand)
            if cand_def != cur_def:
                raise _ReloadRejected("abi: weight tree structure differs")
            for old, new in zip(cur_leaves, cand_leaves):
                if (tuple(np.shape(old)) != tuple(np.shape(new))
                        or np.asarray(old).dtype != np.asarray(new).dtype):
                    raise _ReloadRejected(
                        f"abi: leaf {tuple(np.shape(old))}/"
                        f"{np.asarray(old).dtype} != "
                        f"{tuple(np.shape(new))}/{np.asarray(new).dtype}")

            probe = self.slots[0]
            pidx = self._probe_pidx(probe)
            out = jax.block_until_ready(self._fwd(
                jnp.asarray(self._probe_frames), pad_mask(probe, probe),
                jnp.asarray(pidx), cand))
            if not all(bool(jnp.isfinite(x).all()) for x in out[1:]):
                raise _ReloadRejected(
                    "canary: non-finite logits/values on the probe slot")
            fresh = PolicyServer(
                cand_params, obs_dim=self.obs_dim,
                n_actions=self.n_actions, frame_stack=self.frame_stack,
                slot=probe, fast_gates=self.fast_gates, route=self.route)
            ref = fresh.forward_slot(self._probe_frames, probe, pidx)
            if not all(bool(jnp.array_equal(a, b))
                       for a, b in zip(out, ref)):
                raise _ReloadRejected(
                    "canary: probe outputs differ from the candidate's "
                    "own fresh server (not bitwise)")
        except _ReloadRejected as e:
            reason = str(e)
        except Exception as e:           # malformed candidate trees etc.
            reason = f"abi: {type(e).__name__}: {e}"
        else:
            self._weights = cand
            self._params = cand_params
            self.policy_version += 1
            self.reloads += 1
            self.reload_log.append(("ok", f"v{self.policy_version}"))
            return True
        self.reload_rejected += 1
        self.reload_log.append(("rejected", reason))
        return False

    def reload_from_checkpoint(self, ckpt_dir, step: Optional[int] = None
                               ) -> bool:
        """Hot-reload the policy subtree of an ``rl_train`` checkpoint
        through the full reload gate. A torn or corrupt checkpoint
        (missing COMMITTED, truncated payload, mangled metadata — every
        layout ``distributed/fault_injection.py::torn_save`` builds)
        makes ``ckpt.restore_subtree`` raise, which is counted as a
        rejected reload — it can never be swapped in, and the server
        keeps serving on the old weights."""
        if self.n_policies != 1:
            raise ValueError(
                "reload_from_checkpoint serves single-policy servers; "
                "restore each checkpoint and call reload([..]) instead")
        try:
            params, _, _ = ckpt.restore_subtree(
                ckpt_dir, self._params, "['policy']", step=step)
        except Exception as e:
            self.reload_rejected += 1
            self.reload_log.append(
                ("rejected", f"restore: {type(e).__name__}: {e}"))
            return False
        return self.reload(params)

    # ------------------------------------------------------- packing

    def _pack(self, batch: List[Request], shape: int):
        """Pack ``batch`` into the preallocated ``shape``-lane staging
        buffers -> (frames (shape, frame_dim) f32, pidx (shape,) i32).
        One buffer pair per slot shape, allocated on first use and
        reused every dispatch — no per-dispatch allocation, and no
        re-pad of the tail: pad lanes keep whatever the previous
        dispatch left there, which the kernel-boundary mask makes
        garbage by contract (pinned by the pad-content property test).
        A slot-sized batch overwrites every lane, so it skips even
        that."""
        frames = self._staging.get(shape)
        if frames is None:
            frames = self._staging.setdefault(
                shape, np.zeros((shape, self.frame_dim), np.float32))
            self._pidx_staging[shape] = np.zeros((shape,), np.int32)
        pidx = self._pidx_staging[shape]
        if batch:
            frames[:len(batch)] = [req.frame for req in batch]
            pidx[:len(batch)] = [req.policy for req in batch]
        return frames, pidx

    def make_scheduler(self) -> SlotScheduler:
        """The server's matching scheduler: bucketed over ``slots`` when
        the server compiled several shapes, fixed-slot otherwise."""
        if len(self.slots) > 1:
            return BucketedSlotScheduler(self.slots)
        return SlotScheduler(self.slot)

    # -------------------------------------------------------- replay

    def _dispatch_once(self, sched, stats: ServeStats,
                       latencies: List[float], now: float, mode: str,
                       service_time_s: float, t_start: float,
                       extra_s: float) -> Tuple[float, float, int]:
        """Pop + pack + forward one batch, advance the clock (virtual:
        ``service_time_s + extra_s``; wallclock: real time plus a
        slept ``extra_s``), complete the batch -> (new now, measured
        dispatch seconds, slot shape). One ``serve.dispatch`` host span
        (its dispatch index and real lanes attached) holds
        ``serve.pop``, ``serve.pack``, ``serve.forward`` and
        ``serve.complete``."""
        with _span("serve.dispatch", dispatch=stats.dispatches) as span:
            t_disp = time.perf_counter()
            with _span("serve.pop"):
                shape, batch = sched.next_dispatch()
            span.set_metadata(lanes=len(batch))
            with _span("serve.pack"):
                frames, pidx = self._pack(batch, shape)
            with _span("serve.forward"):
                self.forward_slot(frames, len(batch), pidx)
            if mode == "wallclock":
                if extra_s > 0:
                    time.sleep(extra_s)
                t_pop = t_disp - t_start
                now = time.perf_counter() - t_start
                dt = time.perf_counter() - t_disp
            else:
                t_pop = now
                dt = service_time_s + extra_s
                now = now + dt
            with _span("serve.complete"):
                sched.complete(batch, now)
                stats.record(shape, len(batch))
                arrivals = [r.arrival for r in batch]
                stats.record_wait(t_pop, arrivals)
                latencies.extend(now - a for a in arrivals)
        return now, dt, shape

    def drain(self, sched, *, stats: Optional[ServeStats] = None,
              now: float = 0.0, service_time_s: float = 1e-3
              ) -> Tuple[ServeStats, float]:
        """Complete every in-flight batch on ``sched`` — no new
        admissions — on a virtual clock starting at ``now``, then land
        the lifecycle on ``drained`` and emit the final stats snapshot:
        -> (stats, completion time). ``serve`` does the same inline for
        the tail of a trace; this is the standalone path for shutting
        down a server whose scheduler still holds work."""
        self.state = "draining"
        stats = stats if stats is not None else ServeStats()
        latencies: List[float] = []
        while sched.pending:
            now, _, _ = self._dispatch_once(
                sched, stats, latencies, now, "virtual", service_time_s,
                0.0, 0.0)
        self.state = "drained"
        stats.final_state = self.state
        return stats, now

    def serve(self, trace: List[Request],
              scheduler: Optional[SlotScheduler] = None, *,
              mode: str = "wallclock",
              service_time_s: float = 1e-3,
              admission=None, faults=None,
              reload_at: Sequence[int] = (),
              reload_params=None) -> ServeReport:
        """Replay an arrival-sorted open-loop ``trace`` to completion.

        ``mode="wallclock"`` measures real dispatch latency (the bench /
        driver path; idles until the next arrival when the queue runs
        dry, so offered load stays open-loop). ``mode="virtual"``
        advances a deterministic clock by ``service_time_s`` per
        dispatch — no timers, same scheduler decisions every run (the
        property tests' path, and the overload/fault tests': every
        admission and fault decision replays exactly).

        ``admission`` (an ``overload.py::AdmissionController``) gates
        every would-be ``sched.admit`` — rejections are counted in the
        report's stats, never silently dropped. ``faults`` (a
        ``FaultInjector``) fires ``RequestFlood`` on the trace before
        replay, ``SlowDispatch`` at its dispatch index, and
        ``CorruptCheckpoint`` at the matching hot-reload attempt.
        ``reload_at`` lists dispatch indices at which the server
        attempts ``reload(reload_params)`` (defaults to its own current
        params — a self-refresh, the canary path chaos plans corrupt);
        attempts past the last dispatch fire during the final drain so
        a plan never silently expires.

        Lifecycle: ``serving`` while arrivals remain, ``draining`` once
        only backlog is left, ``drained`` at return (the stats snapshot
        records it)."""
        if mode not in ("wallclock", "virtual"):
            raise ValueError(f"unknown mode: {mode!r}")
        if faults is not None:
            for fl in faults.take_floods():
                trace = flood_trace(trace, fl.at_s, fl.duration_s,
                                    fl.multiplier)
        sched = scheduler if scheduler is not None else \
            self.make_scheduler()
        self.warmup(getattr(sched, "buckets", (sched.slot,)))
        self.state = "serving"
        stats = ServeStats()
        reloads0 = self.reloads
        rejected0 = self.reload_rejected
        pending_reloads = sorted(set(int(d) for d in reload_at))
        reload_attempt = 0

        def try_reloads(dispatch_idx: Optional[int]) -> None:
            nonlocal reload_attempt
            while pending_reloads and (
                    dispatch_idx is None
                    or pending_reloads[0] <= dispatch_idx):
                pending_reloads.pop(0)
                cand = (reload_params if reload_params is not None
                        else self._params)
                if faults is not None:
                    cand = faults.corrupt_params(reload_attempt, cand)
                self.reload(cand)
                reload_attempt += 1

        latencies: List[float] = []
        next_req = 0
        dispatch_idx = 0
        n = len(trace)
        t_start = time.perf_counter()
        now = 0.0
        last_done = 0.0

        while next_req < n or sched.pending:
            if mode == "wallclock":
                now = time.perf_counter() - t_start
            if next_req < n and trace[next_req].arrival <= now:
                with _span("serve.admit"):
                    arrivals = []
                    while next_req < n and trace[next_req].arrival <= now:
                        req = trace[next_req]
                        arrivals.append(req.arrival)
                        if admission is None:
                            sched.admit(req)
                        else:
                            admission.admit(req, now, sched, stats)
                        next_req = next_req + 1
                    stats.record_admit(now, arrivals)
            if next_req >= n and self.state == "serving":
                self.state = "draining"   # only backlog left
            if not sched.pending:
                if next_req >= n:
                    break                 # everything shed: nothing to run
                # open-loop idle: jump/sleep to the next arrival
                now = trace[next_req].arrival
                if mode == "wallclock":
                    wait = now - (time.perf_counter() - t_start)
                    if wait > 0:
                        with _span("serve.idle"):
                            time.sleep(wait)
                continue
            try_reloads(dispatch_idx)
            extra = (faults.dispatch_delay_s(dispatch_idx)
                     if faults is not None else 0.0)
            now, dt, shape = self._dispatch_once(
                sched, stats, latencies, now, mode, service_time_s,
                t_start, extra)
            if admission is not None:
                admission.observe_dispatch(shape, dt, sched)
            last_done = now
            dispatch_idx += 1
        try_reloads(None)                 # leftover plan: fire at drain
        self.state = "drained"
        stats.reloads = self.reloads - reloads0
        stats.reload_rejected = self.reload_rejected - rejected0
        stats.final_state = self.state

        makespan = max(last_done - (trace[0].arrival if trace else 0.0),
                       1e-9)
        lat = np.asarray(latencies) if latencies else np.zeros(1)
        return ServeReport(
            requests=n, served=sched.served,
            p50_s=float(np.percentile(lat, 50)),
            p99_s=float(np.percentile(lat, 99)),
            qps=sched.served / makespan,
            deadline_misses=sched.deadline_misses,
            misses_by_class=dict(sched.misses_by_class),
            max_queue_depth=sched.max_queue_depth,
            dispatches=stats.dispatches,
            mean_occupancy=(stats.real_lanes / stats.dispatches
                            if stats.dispatches else 0.0),
            stats=stats,
            latencies_s=latencies)
