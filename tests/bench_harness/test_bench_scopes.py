"""Phase scopes and serving spans from constructed traces and HLO text."""
import pytest

from bench.lib import scopes, tracing

MS = 1_000_000


def _op(name, start_ms, dur_ms):
    return (name, start_ms * MS, dur_ms * MS)


def _span(name, start_ms, dur_ms, line="/host:CPU/0"):
    return (line, name, start_ms * MS, dur_ms * MS)


def _hlo(ops):
    """HLO text of one entry computation holding ``ops``, each on its own
    parameter: [(name, opcode, op_name or None)]."""
    lines = ["HloModule jit_f, is_scheduled=true", "",
             "ENTRY %main.1 (p: f32[8]) -> f32[8] {"]
    for i, (name, opcode, op_name) in enumerate(ops):
        meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
        lines += [f"  %p.{i} = f32[8]{{0}} parameter({i})",
                  f"  %{name} = f32[8]{{0}} {opcode}(%p.{i}){meta}"]
    return "\n".join(lines + ["}", ""])


def test_scope_of_takes_the_innermost_phase():
    assert scopes.scope_of("jit(train_iteration)/ppo.update/while/body/"
                           "ppo.shuffle/gather") == "ppo.shuffle"
    assert scopes.scope_of("jit(train_iteration)/ppo.gae/mul") == "ppo.gae"
    assert scopes.scope_of("jit(train_iteration)/while/body/add") is None
    assert scopes.scope_of(None) is None
    assert scopes.hlo_name("%fusion.4 = f32[8] fusion(f32[8] %x)") == \
        "fusion.4"
    assert scopes.hlo_name("fusion.4") == "fusion.4"


def test_op_goes_to_its_innermost_scope():
    hlo = scopes.hlo_scopes(_hlo([
        ("fusion.1", "fusion", "jit(f)/ppo.update/while/body/ppo.shuffle/g"),
        ("fusion.2", "fusion", "jit(f)/ppo.update/while/body/dot"),
        ("fusion.3", "fusion", "jit(f)/ppo.noise/threefry")]))
    dev = [_op("%fusion.1 = f32[8] fusion(f32[8] %p)", 0, 10),
           _op("fusion.2", 10, 5), _op("fusion.3", 20, 2)]
    r = scopes.device_scopes({"devices": [dev], "spans": []}, 1, hlo)
    assert r["scope_s"] == {"ppo.noise": pytest.approx(0.002),
                            "ppo.shuffle": pytest.approx(0.010),
                            "ppo.update": pytest.approx(0.005)}
    assert r["unscoped_s"] == 0.0


def test_nested_while_counts_once():
    """A ``while`` spans its body's ops on the same line: the body's ops
    keep their own time and the loop only what they leave."""
    hlo = scopes.hlo_scopes(_hlo([
        ("while.4", "while", "jit(f)/ppo.update/while"),
        ("fusion.1", "fusion", "jit(f)/ppo.update/while/body/ppo.shuffle/g"),
        ("fusion.2", "fusion", "jit(f)/ppo.update/while/body/dot")]))
    dev = [_op("%while.4 = (f32[2]) while(f32[2] %p)", 0, 50),
           _op("fusion.1", 5, 20), _op("fusion.2", 30, 15)]
    r = scopes.device_scopes({"devices": [dev], "spans": []}, 1, hlo)
    assert r["scope_s"]["ppo.shuffle"] == pytest.approx(0.020)
    assert r["scope_s"]["ppo.update"] == pytest.approx(0.030)
    assert sum(r["scope_s"].values()) == pytest.approx(0.050)


def test_ops_without_scope_are_unscoped_and_window_clips():
    hlo = scopes.hlo_scopes(_hlo([
        ("fusion.1", "fusion", "jit(f)/ppo.gae/mul"),
        ("copy.3", "copy", "jit(f)/while/body/add"),
        ("fusion.9", "fusion", "jit(f)/ppo.gae/mul")]))
    dev = [_op("fusion.1", 0, 10), _op("copy.3", 10, 4),
           _op("fusion.77", 20, 3),          # in no program text
           _op("fusion.9", 95, 20)]
    dev1 = [_op("fusion.1", 0, 30)]
    spans = [_span(tracing.WINDOW_SPAN, 0, 100)]
    r = scopes.device_scopes({"devices": [dev, dev1], "spans": spans}, 2,
                             hlo)
    # chip 0: gae 10 + 5 (clipped at 100), unscoped 4 + 3; chip 1: gae 30
    assert r["scope_s"] == {"ppo.gae": pytest.approx((0.015 + 0.030) / 2)}
    assert r["unscoped_s"] == pytest.approx(0.007 / 2)


HLO = """HloModule jit_train_iteration, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(train_iteration)/ppo.gae/mul"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.2 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(train_iteration)/ppo.gae/mul"}
}

%body.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %copy.7 = f32[8]{0} copy(%gte.1)
  %fusion.5 = f32[8]{0} fusion(%copy.7), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(train_iteration)/ppo.update/while/body/ppo.shuffle/gather"}
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(%gte.1, %fusion.5)
}

ENTRY %main.9 (p0: f32[8], p1: f32[8]) -> (f32[8], f32[8]) {
  %p0 = f32[8]{0} parameter(0), metadata={op_name="params"}
  %p1 = f32[8]{0} parameter(1)
  %fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1
  %copy.2 = f32[8]{0} copy(%p1), metadata={op_name="rs.frames"}
  %while.3 = (s32[], f32[8]{0}) while(%copy.2), body=%body.2, metadata={op_name="jit(train_iteration)/ppo.update/while"}
  %gte.4 = f32[8]{0} get-tuple-element(%while.3), index=1
  %copy.5 = f32[8]{0} copy(%gte.4)
  ROOT %tuple.6 = (f32[8]{0}, f32[8]{0}) tuple(%fusion.1, %copy.5)
}
"""


def test_hlo_scopes_follow_fusions_and_loops():
    sc = scopes.hlo_scopes(HLO)
    assert sc["fusion.5"] == "ppo.shuffle"      # its own op name
    assert sc["fusion.1"] == "ppo.gae"          # the fused op's
    assert sc["copy.7"] == "ppo.update"         # the loop it sits in
    assert sc["while.3"] == "ppo.update"
    assert sc["copy.2"] is None                 # a phase-less op outside
    assert sc["copy.5"] is None


def test_span_self_time_subtracts_child_spans():
    spans = [_span(tracing.WINDOW_SPAN, 0, 100),
             _span("serve.dispatch", 10, 40),
             _span("serve.pop", 11, 4),
             _span("serve.forward", 16, 30),
             _span("bench.forward", 16.5, 29),
             _span("serve.put", 17, 5),
             _span("serve.wait", 23, 20),
             _span("serve.complete", 47, 2),
             _span("serve.idle", 60, 30),
             _span("serve.idle", 200, 5),          # outside the window
             _span("serve.pop", 20, 2, line="/host:CPU/1")]
    r = scopes.span_times({"devices": [], "spans": spans})
    self_s = r["span_self_s"]
    assert self_s["serve.dispatch"] == pytest.approx(0.040 - 0.036)
    assert self_s["serve.forward"] == pytest.approx(0.030 - 0.025)
    assert self_s["serve.wait"] == pytest.approx(0.020)
    assert self_s["serve.pop"] == pytest.approx(0.004 + 0.002)
    assert self_s["serve.idle"] == pytest.approx(0.030)
    assert r["span_count"]["serve.idle"] == 1
    assert r["span_count"]["serve.pop"] == 2
    assert "bench.forward" not in self_s


def test_idle_gap_in_serve_wait_is_named_by_it():
    """With the server's spans beside the benchmark's, a device gap
    inside ``serve.wait``, itself inside ``bench.forward``, takes the
    innermost span's name."""
    dev = [("fusion.1", 0, 10 * MS), ("fusion.2", 40 * MS, 10 * MS)]
    spans = [(tracing.WINDOW_SPAN, 0, 50 * MS),
             ("serve.dispatch", 0, 50 * MS),
             ("serve.forward", 5 * MS, 45 * MS),
             ("bench.forward", 6 * MS, 44 * MS),
             ("serve.wait", 8 * MS, 37 * MS)]
    r = tracing.reduce({"devices": [dev], "spans": spans}, 1)
    assert r["idle_gaps"][0] == ["serve.wait", pytest.approx(0.030)]


def test_reduce_joins_device_and_span_parts():
    trace = {"devices": [[_op("fusion.1", 0, 10)]],
             "spans": [_span("serve.pop", 0, 1)]}
    r = scopes.reduce(trace, 1, _hlo([("fusion.1", "fusion",
                                       "jit(f)/ppo.gae/x")]))
    assert set(r) == {"scope_s", "unscoped_s", "span_self_s", "span_count"}
    assert r["scope_s"] == {"ppo.gae": pytest.approx(0.010)}
    assert scopes.reduce(trace, 1, None) == {
        "span_self_s": {"serve.pop": pytest.approx(0.001)},
        "span_count": {"serve.pop": 1}}
