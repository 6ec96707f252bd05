"""Profiler capture and the one reduction from a device trace to numbers.

Host spans come only from the benchmark's own files (``span``), around
its calls into the program. ``load`` reads an ``.xplane.pb`` into plain
event lists; ``reduce`` turns those into per-category device seconds (each op's own
time, nested ops once),
busy time (the union of op intervals), and idle gaps named by the
innermost benchmark span they fell in. ``reduce`` is pure Python over
``(name, start_ns, dur_ns)`` tuples, so it is tested on constructed
traces.
"""
from __future__ import annotations

import contextlib
import glob
import os

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")
WINDOW_SPAN = "bench.window"


def span(name: str):
    """A host span in the profiler's trace (``bench.<what>``)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace everything inside into ``log_dir``; yields a list that holds
    the ``.xplane.pb`` path once the block exits. Python function calls
    are not traced (the serving loop would drown in them); host spans
    and device ops are."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    out = []
    with jax.profiler.trace(log_dir, profiler_options=opts):
        yield out
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                           f"{log_dir}")
    out.append(max(found, key=os.path.getmtime))


def _is_op(name: str, opcodes) -> bool:
    """Whether the event is an op of one of ``opcodes``: its bare name
    starts with one, or its HLO text applies one (``= f32[..] op(``), not
    merely takes another such op's result as an operand (``%op.3``)."""
    return any(name.startswith(op) or f" {op}(" in name
               or f" {op}-start(" in name or f" {op}-done(" in name
               for op in opcodes)


def is_kernel(name: str) -> bool:
    """A Pallas call: a custom call."""
    return _is_op(name, ("custom-call",))


def is_collective(name: str) -> bool:
    return _is_op(name, COLLECTIVES)


def _self_seconds(intervals):
    """Each (start, end)'s own time: its length less that of the ops
    nested in it (a ``while`` op spans the ops of its body on the same
    trace line, so summing lengths would count them twice)."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    own = [e - s for s, e in intervals]
    stack = []
    for i in order:
        s, e = intervals[i]
        while stack and intervals[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= intervals[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [x * 1e-9 for x in own]


def load(path: str) -> dict:
    """-> {"devices": [[(name, start_ns, dur_ns), ...] per TPU],
    "spans": [(name, start_ns, dur_ns), ...] of ``bench.`` host spans}."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            evs = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs += [(e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events]
            devices.append((int(plane.name[len("/device:TPU:"):]), evs))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events
                          if e.name.startswith("bench.")]
    devices.sort()
    return {"devices": [evs for _, evs in devices], "spans": spans}


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost(spans, t0, t1):
    """Name of the shortest benchmark span covering [t0, t1] the most."""
    best, best_key = "no bench span", None
    for name, s, d in spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(t1, s + d) - max(t0, s)
        if cover <= 0:
            continue
        key = (cover, -d)
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def reduce(trace: dict, n_devices: int, top: int = 10) -> dict:
    """Device seconds per category, busy and idle, over the traced
    window (the ``bench.window`` span; the trace's extent without one).
    An op's seconds are its own, less those of the ops nested in it.

    -> {"window_s", "busy_s", "kernel_s", "collective_s", "other_s" —
    each averaged over the first ``n_devices`` chips — "device_ops"
    [[name, seconds]] (most time first, summed over chips),
    "idle_gaps" [[span, seconds]] (device 0's longest gaps, named by the
    benchmark span they fell in)}."""
    spans = trace["spans"]
    devs = trace["devices"][:n_devices]
    if not devs or not any(devs):
        return {}
    win = [(s, s + d) for n, s, d in spans if n == WINDOW_SPAN]
    if win:
        w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    else:
        w0 = min(s for evs in devs for _, s, _ in evs)
        w1 = max(s + d for evs in devs for _, s, d in evs)
    totals = {"busy_s": 0.0, "kernel_s": 0.0, "collective_s": 0.0,
              "other_s": 0.0}
    by_name = {}
    gaps = []
    for i, evs in enumerate(devs):
        names, clipped = [], []
        for name, s, d in evs:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 > s2:
                names.append(name)
                clipped.append((s2, e2))
        for name, sec in zip(names, _self_seconds(clipped)):
            cat = ("kernel_s" if is_kernel(name) else "collective_s"
                   if is_collective(name) else "other_s")
            totals[cat] += sec
            by_name[name] = by_name.get(name, 0.0) + sec
        busy = _union(clipped)
        totals["busy_s"] += sum(e - s for s, e in busy) * 1e-9
        if i == 0:
            edges = [w0] + [x for iv in busy for x in iv] + [w1]
            for g0, g1 in zip(edges[0::2], edges[1::2]):
                if g1 > g0:
                    gaps.append((g1 - g0, g0, g1))
    n = len(devs)
    out = {k: v / n for k, v in totals.items()}
    out["window_s"] = (w1 - w0) * 1e-9
    out["device_ops"] = [[k, v] for k, v in sorted(
        by_name.items(), key=lambda kv: -kv[1])[:top]]
    gaps.sort(reverse=True)
    out["idle_gaps"] = [[_innermost(spans, g0, g1), g * 1e-9]
                        for g, g0, g1 in gaps[:top]]
    return out
