"""Discovery by name, the no-chip exit, the copied traffic generator and
the verdict."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.lib import cells, requests, verdict


def test_every_cell_finds_its_files():
    bench = cells.benchmark()
    for w in bench["workloads"]:
        cfg, mix = cells.config(w["config"]), cells.mix(w["traffic"])
        assert cfg["name"] == w["config"]
        assert mix["kind"] in ("train", "serve")
        assert set(cells.limits(w["name"]))
        e2e = {m["name"] for m in cells.metrics_of(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cells.metrics_of(w["name"], "per_layer")
    for m in bench["per_layer"]:
        assert callable(cells.reader(m["name"]))
    for c in bench["configs"]:
        assert cells.config(c["name"])["source"] == c["source"]


def test_unknown_cell_raises():
    with pytest.raises(KeyError):
        cells.cell("no_such.cell")


def test_no_tpu_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(cells.BENCH_DIR / "run.py"), "--workload",
         "traffic25_fnn.train_b256", "--seed", "3", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=cells.ROOT, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_copied_generator_reproduces_synthetic_trace():
    from repro.serving import TraceConfig, synthetic_trace
    pool = np.random.default_rng(0).standard_normal((16, 41)).astype(
        np.float32)
    kw = dict(n_regions=32, region_sizes=(1, 2, 4, 8), horizon_s=0.2,
              classes_s=(0.005, 0.025, 0.1), class_mix=(0.25, 0.5, 0.25),
              frame_dim=41, seed=2**31 + 5)
    for fp in (None, pool):
        ref = synthetic_trace(TraceConfig(mean_rps=5000.0, **kw),
                              frame_pool=fp)
        got = requests.generate(rate=5000.0, frame_pool=fp, **kw)
        assert len(ref) == len(got["arrival"]) > 0
        np.testing.assert_array_equal(got["arrival"],
                                      [r.arrival for r in ref])
        np.testing.assert_array_equal(got["deadline"],
                                      [r.deadline for r in ref])
        np.testing.assert_array_equal(got["klass"], [r.klass for r in ref])
        np.testing.assert_array_equal(got["region"], [r.region for r in ref])
        np.testing.assert_array_equal(got["size"], [r.size for r in ref])
        np.testing.assert_array_equal(got["frame"],
                                      np.stack([r.frame for r in ref]))


def test_fixed_arrivals_give_every_seed_the_same_work():
    kw = dict(n_regions=32, region_sizes=(1, 2, 4, 8), rate=5000.0,
              horizon_s=0.2, classes_s=(0.005, 0.025, 0.1),
              class_mix=(0.25, 0.5, 0.25), frame_dim=4,
              fixed_arrivals=True)
    a = requests.generate(seed=2**31 + 5, **kw)
    b = requests.generate(seed=2**31 + 6, **kw)
    np.testing.assert_array_equal(a["arrival"], b["arrival"])
    np.testing.assert_array_equal(a["size"], b["size"])
    assert not np.array_equal(a["region"], b["region"])
    assert not np.array_equal(a["klass"], b["klass"])
    region_size = {int(r): int(s) for r, s in zip(a["region"], a["size"])}
    assert sorted(region_size.values()) == sorted([1, 2, 4, 8] * 8)


def test_verdict():
    ok, checks = verdict.judge({"a": 1.0, "b": 0.5}, {"a": 1.0, "b": 1.0})
    assert ok and checks["a"] == {"value": 1.0, "limit": 1.0}
    assert not verdict.judge({"a": 1.5}, {"a": 1.0})[0]
    assert not verdict.judge({"a": float("nan")}, {"a": 1.0})[0]
    assert not verdict.judge({"a": 0.0}, {"a": 1.0}, failed=1)[0]
    with pytest.raises(KeyError):
        verdict.judge({"a": 0.0}, {"b": 1.0})


def test_change_diff_sees_a_direction_that_norms_miss():
    """A change of the same norm in another direction reads 0 on
    ``change_gap`` and on the order of 1 on ``change_diff``; a stale
    state reads 1 on both."""
    from bench.lib import train
    p0 = {"a": np.zeros(4, np.float32), "b": np.zeros(3, np.float32)}
    mu = {"a": np.ones(4, np.float32), "b": np.ones(3, np.float32)}
    ref = {"p0": p0, "mu1": mu, "loss": [1.0], "reward": [0.5],
           "p3": {"a": np.array([1, 0, 0, 0], np.float32),
                  "b": np.array([0, 1, 0], np.float32)}}
    turned = dict(ref, p3={"a": np.array([0, 1, 0, 0], np.float32),
                           "b": np.array([0, 1, 0], np.float32)})
    r = train.readings(turned, ref)
    assert r["change_gap"] == 0.0 and r["loss_gap"] == 0.0
    assert r["change_diff"] == pytest.approx(np.sqrt(2.0))
    stale = dict(ref, p3=p0)
    r = train.readings(stale, ref)
    assert r["change_gap"] == pytest.approx(1.0)
    assert r["change_diff"] == pytest.approx(1.0)
