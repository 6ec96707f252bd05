"""Open-loop serving traffic from a mix's parameters (a copy of the
program's ``serving/request.py::synthetic_trace``, so later changes to the
program cannot move the yardstick).

``n_regions`` agent regions of sizes drawn from ``region_sizes`` each
tick with a common period ``lanes / rate`` at their own random phase and
submit one request per lane per tick; each tick draws a deadline class.
Frames come from ``frame_pool`` when it is given (a request's forward
costs the same whatever its frame holds). Every draw comes from one
``numpy.random.Generator`` seeded with ``seed``, so a trace is a pure
function of its arguments.

With ``fixed_arrivals`` every seed gets the same work in another order:
the region sizes are ``region_sizes`` repeated to ``n_regions`` (equal
counts), each paired with a phase from one draw of a stream that does
not depend on the seed, so every seed has the same (arrival, size)
ticks; the seed permutes which region holds which pair, and draws the
classes and frames. Without it the trace is
``synthetic_trace``'s, draw for draw.
"""
from __future__ import annotations

import numpy as np

FIXED_PHASES = 20221017     # the seed-free stream of ``fixed_arrivals``


def generate(*, n_regions: int, region_sizes, rate: float,
             horizon_s: float, classes_s, class_mix, frame_dim: int,
             seed: int, frame_pool=None,
             fixed_arrivals: bool = False) -> dict:
    """-> arrival-sorted columns: "arrival" (s), "klass", "deadline"
    (absolute s), "region", "size", "frame" (n, frame_dim) f32."""
    rng = np.random.default_rng(seed)
    if fixed_arrivals:
        sizes = np.resize(np.asarray(region_sizes), n_regions)
        period = int(sizes.sum()) / rate
        phases = np.random.default_rng(FIXED_PHASES).uniform(
            0.0, period, size=n_regions)
        order = rng.permutation(n_regions)
        sizes, phases = sizes[order], phases[order]
    else:
        sizes = rng.choice(np.asarray(region_sizes), size=n_regions)
        period = int(sizes.sum()) / rate
        phases = rng.uniform(0.0, period, size=n_regions)
    mix = np.asarray(class_mix, dtype=np.float64)
    mix = mix / mix.sum()
    # Each region ticks at phase, phase + period, ... (summed one period at
    # a time, as the original loop does) while the tick is inside the
    # horizon; ticks are drawn region by region, then sorted by time.
    k = int(np.ceil(horizon_s / period)) + 2
    steps = np.full((n_regions, k), period)
    steps[:, 0] = phases
    ticks = np.cumsum(steps, axis=1)
    live = ticks < horizon_s
    t = ticks[live]
    region = np.broadcast_to(np.arange(n_regions)[:, None], ticks.shape)[live]
    klass = rng.choice(len(classes_s), size=len(t), p=mix)
    order = np.lexsort((region, t))
    lanes = sizes[region[order]]
    cols = {"arrival": np.repeat(t[order], lanes),
            "klass": np.repeat(klass[order], lanes),
            "region": np.repeat(region[order], lanes),
            "size": np.repeat(lanes, lanes).astype(np.int64)}
    n = len(cols["arrival"])
    if frame_pool is not None:
        cols["frame"] = np.asarray(frame_pool, np.float32)[
            rng.integers(0, len(frame_pool), size=n)]
    else:
        cols["frame"] = rng.standard_normal((n, frame_dim)).astype(
            np.float32)
    cols["deadline"] = cols["arrival"] + np.asarray(classes_s)[cols["klass"]]
    return cols
