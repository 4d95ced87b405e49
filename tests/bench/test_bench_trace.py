"""The reduction from a profiler trace to busy time, per-launch device
time, idle gaps and top operations."""
from pathlib import Path

import pytest

from bench import trace_reduce


def _raw():
    # window 0..1000 ns; launches: prefill at 100, decode at 500
    host = [("bench.window", 0, 1000), ("bench.prefill", 100, 120),
            ("bench.decode", 500, 510)]
    mods = [("jit_convert", 90, 95), ("jit__lambda", 130, 400),
            ("jit_argmax", 405, 420), ("jit__lambda", 520, 700)]
    ops = [("convert", 90, 95), ("while.1", 130, 400), ("fusion.1", 130, 300),
           ("fusion.2", 300, 390), ("argmax", 405, 420), ("fusion.1", 520, 700)]
    return {"host": host,
            "devices": [{"name": "/device:TPU:0", "modules": mods,
                         "ops": ops}]}


def test_busy_window_and_launch_times():
    r = trace_reduce.reduce(_raw())
    assert r.window_s == pytest.approx(1e-6)
    assert r.busy_s == pytest.approx((5 + 270 + 15 + 180) * 1e-9)
    assert r.launch_kinds == ["prefill", "decode"]
    assert r.launch_device_s == pytest.approx([270e-9, 180e-9])
    # the prefill's whole step runs to the decode's start: the argmax
    # launched after it and the idle time count
    assert r.launch_step_s == pytest.approx([390e-9, 180e-9])


def test_idle_gaps_are_labelled_by_host_activity():
    r = trace_reduce.reduce(_raw())
    idle = dict(r.idle_by_host)
    # idle 0-90, 95-130 (inside the prefill span), 400-405 and 420-520
    # (after the prefill), 700-1000 (after the decode)
    assert idle["host before first launch"] == pytest.approx(90e-9)
    assert idle["host in bench.prefill"] == pytest.approx(35e-9)
    assert idle["host loop after bench.prefill"] == pytest.approx(105e-9)
    assert idle["host loop after bench.decode"] == pytest.approx(300e-9)
    assert sum(idle.values()) == pytest.approx(1e-6 - r.busy_s)


def test_top_ops_rank_self_time_by_program():
    r = trace_reduce.reduce(_raw())
    top = dict(r.top_ops)
    assert top["decode: fusion.1"] == pytest.approx(180e-9)
    assert top["prefill: fusion.1"] == pytest.approx(170e-9)
    assert top["prefill: while.1"] == pytest.approx(10e-9)     # less its body
    assert top["jit_argmax: argmax"] == pytest.approx(15e-9)
    assert top["jit_convert: convert"] == pytest.approx(5e-9)


def test_step_executions_that_do_not_match_the_launches_raise():
    raw = _raw()
    raw["devices"][0]["modules"] = raw["devices"][0]["modules"][:-1]
    with pytest.raises(ValueError, match="1 executions .* 2 launch spans"):
        trace_reduce.reduce(raw)
    with pytest.raises(ValueError):        # a step program under a new name
        trace_reduce.reduce(_raw(), step_stems=("jit_step",))


def test_a_trace_without_window_or_device_reduces_to_nothing():
    raw = _raw()
    assert trace_reduce.reduce({"host": raw["host"], "devices": []}) is None
    assert trace_reduce.reduce({"host": [], "devices": raw["devices"]}) is None


DATA = Path(__file__).resolve().parent / "data" / "smoke_serve.xplane.pb.gz"


def test_recorded_tpu_trace():
    """A trace recorded on one v5e: a smoke-size dense model served for
    0.3 s, one prefill then decode steps and refills."""
    r = trace_reduce.reduce(trace_reduce.load(str(DATA)))
    assert r.launch_kinds[0] == "prefill"
    assert r.launch_kinds.count("prefill") == 12
    assert len(r.launch_kinds) == 54
    assert all(t is not None and t > 0 for t in r.launch_device_s)
    assert 0 < r.busy_s < r.window_s == pytest.approx(0.309252983)
    assert sum(s for _, s in r.idle_by_host) <= r.window_s - r.busy_s + 1e-9
    assert any(name.startswith("decode: ") for name, _ in r.top_ops)
    assert trace_reduce.module_stem("<lambda>") == "jit__lambda"
