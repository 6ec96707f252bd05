"""Phases of the train program and spans of the policy server, from the
``.xplane.pb`` that ``tracing.capture`` writes.

Device ops. ``rl/ppo.py`` wraps each phase of ``train_iteration`` in a
``jax.named_scope`` (``ppo.noise``, ``ppo.rollout``, ``ppo.gae``,
``ppo.shuffle``, ``ppo.update``), which reaches each compiled op's
``op_name``; a fusion's is its root's. An op's phase is the innermost
``ppo.*`` component of it. A v5e trace's op events carry no op name, so
it is looked up by the event's HLO op name in the compiled program's
HLO text (``compiled.as_text()``). An op that XLA made without one (a
bitcast fusion, a loop's counter) takes the phase of the ops inside it,
else that of the loop it sits in; what is left is ``unscoped_s``. Each op counts its own seconds once
(``tracing._self_seconds``), clipped to the ``bench.window`` span and
averaged over the chips.

Host spans. ``serving/server.py`` marks each dispatch of the serving loop
and its parts with ``serve.*`` spans. A span name's self time is its
spans' length less what their child spans of the same family, on the
same thread, cover.

The reductions are pure Python over plain tuples, so they are tested on
constructed traces (``tests/bench_harness/test_bench_scopes.py``).
"""
from __future__ import annotations

import re

from bench.lib import tracing

SCOPE_PREFIX = "ppo."
SPAN_PREFIX = "serve."

_COMP = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r"(?:calls|body|condition|to_apply|"
                     r"true_computation|false_computation)=%?([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")


def scope_of(op_name: str | None) -> str | None:
    """The innermost ``ppo.*`` component of an op name, or None."""
    if not op_name:
        return None
    for part in reversed(op_name.split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return None


def hlo_name(event_name: str) -> str:
    """An event's HLO op name: ``%fusion.4 = f32[8] fusion(...)`` and
    ``fusion.4`` both give ``fusion.4``."""
    return event_name.lstrip("%").split(" ", 1)[0]


def hlo_scopes(hlo_text: str) -> dict:
    """Compiled HLO text -> {op: phase or None}. An op whose own op name
    has no phase takes that of the first op named inside the computation
    it calls (a fusion's), else that of the op calling the computation
    it sits in (a loop body's ``while``)."""
    comp = None
    own, calls, where, callers, members = {}, {}, {}, {}, {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and comp is not None:
            op = m.group(1)
            name = _OP_NAME.search(line)
            own[op] = scope_of(name.group(1) if name else None)
            calls[op] = [c.strip().lstrip("%") for one, many in
                         _CALLED.findall(line)
                         for c in ([one] if one else many.split(","))]
            for c in calls[op]:
                callers.setdefault(c, op)
            where[op] = comp
            members.setdefault(comp, []).append(op)
            continue
        m = _COMP.match(line)
        if m:
            comp = m.group(1)

    inner_memo = {}

    def inner(c):
        """The first phase named inside computation ``c``."""
        if c not in inner_memo:
            inner_memo[c] = None
            inner_memo[c] = next(
                (s for op in members.get(c, ())
                 for s in [own[op]] + [inner(x) for x in calls[op]] if s),
                None)
        return inner_memo[c]

    def resolved(op):
        s = own[op] or next((s for s in map(inner, calls[op]) if s), None)
        if s is None and where[op] in callers:
            return resolved(callers[where[op]])
        return s

    return {op: resolved(op) for op in own}


def load(path: str) -> dict:
    """-> {"devices": [[(name, start_ns, dur_ns)] per TPU], as
    ``tracing.load`` gives them, "spans": [(line, name, start_ns,
    dur_ns)] of the ``serve.`` and ``bench.`` host spans, ``line``
    telling threads apart}."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        tail = plane.name[len("/device:TPU:"):]
        if plane.name.startswith("/device:TPU:") and tail.isdigit():
            devices.append((int(tail), [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events]))
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans += [(f"{plane.name}/{i}", e.name, float(e.start_ns),
                           float(e.duration_ns)) for e in line.events
                          if e.name.startswith((SPAN_PREFIX, "bench."))]
    devices.sort()
    return {"devices": [evs for _, evs in devices], "spans": spans}


def _window(spans):
    win = [(s, s + d) for _, n, s, d in spans if n == tracing.WINDOW_SPAN]
    if not win:
        return None
    return min(s for s, _ in win), max(e for _, e in win)


def device_scopes(trace: dict, n_devices: int, hlo: dict) -> dict:
    """Device seconds per phase -> {"scope_s": {phase: s}, "unscoped_s":
    s}, each averaged over the first ``n_devices`` chips, over the
    ``bench.window`` span (the trace's extent without one). ``hlo`` is
    ``hlo_scopes`` of the traced program."""
    devs = trace["devices"][:n_devices]
    if not devs or not any(devs):
        return {}
    win = _window(trace["spans"])
    if win is None:
        win = (min(s for evs in devs for _, s, _ in evs),
               max(s + d for evs in devs for _, s, d in evs))
    w0, w1 = win
    totals = {}
    for evs in devs:
        scopes, clipped = [], []
        for name, s, d in evs:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 > s2:
                scopes.append(hlo.get(hlo_name(name)))
                clipped.append((s2, e2))
        for scope, sec in zip(scopes, tracing._self_seconds(clipped)):
            totals[scope] = totals.get(scope, 0.0) + sec
    n = len(devs)
    unscoped = totals.pop(None, 0.0)
    return {"scope_s": {k: v / n for k, v in sorted(totals.items())},
            "unscoped_s": unscoped / n}


def span_times(trace: dict) -> dict:
    """Self seconds and count per ``serve.*`` span name inside the
    ``bench.window`` span -> {"span_self_s": {name: s}, "span_count":
    {name: n}}."""
    win = _window(trace["spans"])
    lines = {}
    for line, name, s, d in trace["spans"]:
        if not name.startswith(SPAN_PREFIX):
            continue
        if win is not None and (s + d <= win[0] or s >= win[1]):
            continue
        lines.setdefault(line, []).append((name, s, s + d))
    self_s, count = {}, {}
    for evs in lines.values():
        own = tracing._self_seconds([(s, e) for _, s, e in evs])
        for (name, _, _), sec in zip(evs, own):
            self_s[name] = self_s.get(name, 0.0) + sec
            count[name] = count.get(name, 0) + 1
    return {"span_self_s": dict(sorted(self_s.items())),
            "span_count": dict(sorted(count.items()))}


def reduce(trace: dict, n_devices: int, hlo_text: str | None) -> dict:
    """``device_scopes`` (given the traced program's HLO text) and
    ``span_times`` of one trace, in one dict."""
    out = span_times(trace)
    if hlo_text:
        out.update(device_scopes(trace, n_devices, hlo_scopes(hlo_text)))
    return out
