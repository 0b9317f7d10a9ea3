"""Trace reduction: a small trace recorded on a TPU v5e (a two-layer
model of width 64 served through the engine; ``data/tiny.xplane.pb``) and
hand-made planes."""
import pathlib
from types import SimpleNamespace as NS

import pytest

import chipbench_small  # noqa: F401  (puts the repo on sys.path)
from chipbench import xtrace

TINY = pathlib.Path(__file__).parent / "data" / "tiny.xplane.pb"


def test_recorded_trace_reduces_to_busy_idle_and_breakdown():
    from jax.profiler import ProfileData
    r = xtrace.reduce(ProfileData.from_file(str(TINY)).planes)
    assert r["window_s"] == pytest.approx(0.012456299)
    assert r["busy_s"] == pytest.approx(3.3473e-05)
    assert r["idle_share"] == pytest.approx(1 - 3.3473e-05 / 0.012456299)
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert r["device_ops"][0][0] == "%while.2 while"      # the scanned layer loop
    assert all(t > 0 for _, t in r["device_ops"] + r["idle_gaps"])
    assert sum(t for _, t in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-12
    assert all(n.startswith("engine.step") for n, _ in r["idle_gaps"][:4])


def _ev(name, s, e):
    return NS(name=name, start_ns=s, end_ns=e)


def _plane(name, line, events):
    return NS(name=name, lines=[NS(name=line, events=events)])


def test_union_window_clipping_and_two_devices():
    host = _plane("/host:CPU", "python3", [
        _ev("traced_window", 100, 1100), _ev("engine.step", 100, 600),
        _ev("engine.step", 600, 1100), _ev("$numpy asarray", 650, 700)])
    dev0 = _plane("/device:TPU:0", "XLA Ops", [
        _ev("%a = f32[] add(x, y)", 50, 300),        # clipped to 100..300
        _ev("%b = f32[] fusion(x), kind=kLoop", 200, 400),   # overlaps %a
        _ev("%c = f32[] copy(x)", 800, 900)])
    dev1 = _plane("/device:TPU:1", "XLA Ops", [_ev("%a = f32[] add(x, y)", 100, 1100)])
    other = _plane("/device:TPU:0", "XLA Modules", [_ev("jit_step", 0, 2000)])
    r = xtrace.reduce([host, dev0, dev1, other])
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx((400e-9 + 1000e-9) / 2)   # mean of the devices
    assert dict(r["device_ops"])["%a add"] == pytest.approx(1200e-9)
    # device 0 idles 400..800 (midpoint 600: the second step) and 900..1100
    gaps = dict(r["idle_gaps"])
    assert gaps["engine.step"] == pytest.approx(600e-9)


def test_merge_and_op_name():
    assert xtrace.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
    assert xtrace.op_name("%fusion.3 = (f32[2]{0}, bf16[4]{0}) fusion(%p), kind=kLoop") \
        == "%fusion.3 fusion"
    with pytest.raises(ValueError):
        xtrace.reduce([_plane("/host:CPU", "python3", [])])
