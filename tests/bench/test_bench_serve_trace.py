"""The reduction of the serve engine's own spans and the model's scopes
(``bench/serve_trace.py``), its protobuf reader (``bench/xspace.py``) and
the per-layer metrics that read it."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import serve_trace, trace_reduce, xspace
from bench.run import load_metric

DATA = Path(__file__).resolve().parent / "data"
OLD = DATA / "smoke_serve.xplane.pb.gz"          # an engine without spans
NEW = DATA / "smoke_serve_spans.xplane.pb.gz"


def _raw():
    # window 0..1000 ns: the first fill, a step, a step with a refill, a
    # last step whose next decode falls outside the trace
    host = [("bench.window", 0, 1000, {}),
            ("serve.admit", 2, 6, {"rows": 2}),
            ("serve.prefill", 6, 20, {"rows": 2, "batch": 2}),
            ("bench.prefill", 8, 18, {}),
            ("serve.step", 100, 200, {"step_num": 0}),
            ("serve.decode", 100, 120, {"rows": 2}),
            ("bench.decode", 105, 115, {}),
            ("serve.sync", 120, 150, {}), ("serve.emit", 150, 170, {}),
            ("serve.admit", 170, 175, {"rows": 0}),
            ("serve.step", 200, 300, {"step_num": 1}),
            ("serve.decode", 220, 230, {"rows": 2}),
            ("serve.sync", 230, 260, {}), ("serve.emit", 260, 270, {}),
            ("serve.admit", 270, 275, {"rows": 1}),
            ("serve.prefill", 275, 290, {"rows": 1, "batch": 2}),
            ("serve.splice", 290, 295, {}),
            ("serve.step", 300, 400, {"step_num": 2}),
            ("serve.decode", 310, 320, {"rows": 2}),
            ("serve.sync", 320, 350, {}), ("serve.emit", 350, 370, {})]
    mods = [("jit_prefill_step(1)", 20, 90), ("jit_decode_step(2)", 118, 148),
            ("jit_decode_step(2)", 225, 255), ("jit_prefill_step(1)", 280, 300),
            ("jit_decode_step(2)", 315, 345)]
    ops = [("while.1", 20, 90, "unscoped"), ("fusion.1", 25, 55, "attention"),
           ("fusion.2", 55, 85, "mlp"),
           ("fusion.3", 118, 128, "attention"), ("copy.1", 128, 148, "unscoped"),
           ("fusion.3", 225, 235, "attention"), ("copy.1", 235, 255, "unscoped"),
           ("fusion.4", 280, 290, "ssd"), ("fusion.5", 290, 300, "norm"),
           ("fusion.3", 315, 325, "attention"), ("copy.1", 325, 345, "unscoped")]
    return {"host": host,
            "devices": [{"name": "/device:TPU:0", "modules": mods,
                         "ops": ops}]}


def test_host_time_per_decode_step_skips_steps_with_a_refill():
    r = serve_trace.reduce(_raw())
    # the decode ending 148 is followed by one at 225: 77 ns idle; a
    # prefill runs between the next two; the last has no next decode
    assert r.decode_host_s == pytest.approx([77e-9])


def test_host_time_per_decode_step_is_read_on_the_device_clock():
    """The host's spans sit some tenths of a ms off the device's clock, by
    an amount that differs from trace to trace: here each execution shows
    0.3 ms before the span that launched it, so the second decode starts
    before the sync that precedes its launch has ended."""
    ms = 1_000_000
    host = [("bench.window", 0, 100 * ms, {}),
            ("serve.prefill", 0.4 * ms, 0.6 * ms, {"rows": 2, "batch": 2}),
            ("serve.decode", 1.3 * ms, 1.5 * ms, {"rows": 2}),
            ("serve.sync", 1.5 * ms, 11.8 * ms, {}),
            ("serve.decode", 11.8 * ms, 12.0 * ms, {"rows": 2}),
            ("serve.sync", 12.0 * ms, 21.8 * ms, {}),
            ("serve.prefill", 21.9 * ms, 22.1 * ms, {"rows": 1, "batch": 1}),
            ("serve.splice", 22.1 * ms, 22.3 * ms, {}),
            ("serve.decode", 22.3 * ms, 22.5 * ms, {"rows": 2}),
            ("serve.sync", 22.5 * ms, 35.8 * ms, {}),
            ("serve.decode", 35.9 * ms, 36.1 * ms, {"rows": 2}),
            ("serve.sync", 36.1 * ms, 45.8 * ms, {})]
    mods = [("jit_prefill_step(1)", 0.2 * ms, 0.9 * ms),
            ("jit_decode_step(2)", 1.1 * ms, 11.0 * ms),
            ("jit_argmax(3)", 11.0 * ms, 11.05 * ms),
            ("jit_decode_step(2)", 11.5 * ms, 21.0 * ms),
            ("jit_argmax(3)", 21.0 * ms, 21.05 * ms),
            ("jit_prefill_step(1)", 21.6 * ms, 25.0 * ms),
            ("jit_splice_rows(4)", 25.0 * ms, 25.2 * ms),
            ("jit_decode_step(2)", 25.2 * ms, 35.0 * ms),
            ("jit_argmax(3)", 35.0 * ms, 35.05 * ms),
            ("jit_decode_step(2)", 35.6 * ms, 45.0 * ms)]
    raw = {"host": host, "devices": [{"name": "/device:TPU:0",
                                      "modules": mods, "ops": []}]}
    r = serve_trace.reduce(raw)
    # from each argmax's end to the next decode's start; the refill's
    # step is left out
    assert r.decode_host_s == pytest.approx([0.45e-3, 0.55e-3])


def test_mixer_shares_are_scope_self_time_over_step_device_time():
    r = serve_trace.reduce(_raw())
    assert r.step_s == pytest.approx({"prefill": 90e-9, "decode": 90e-9})
    # prefill: attention 30 + ssd 10 of 70 + 20; the while's own 10 unscoped
    assert r.mixer_share("prefill") == pytest.approx(100 * 40 / 90)
    assert r.scope_s["prefill"] == pytest.approx(
        {"unscoped": 10e-9, "attention": 30e-9, "mlp": 30e-9, "ssd": 10e-9,
         "norm": 10e-9})
    assert r.mixer_share("decode") == pytest.approx(100 * 30 / 90)
    assert r.scope_s["decode"]["unscoped"] == pytest.approx(60e-9)


def test_idle_gaps_are_labelled_by_the_innermost_serve_or_bench_span():
    r = serve_trace.reduce(_raw())
    # gaps 0-20, 90-118, 148-225, 255-280, 300-315, 345-1000, each named
    # by the innermost span open at its middle
    assert dict(r.idle_by_span) == pytest.approx({
        "host in bench.prefill": 20e-9, "host in serve.decode": 28e-9,
        "host in serve.step": 77e-9 + 15e-9, "host in serve.emit": 25e-9,
        "host outside spans": 655e-9})


def test_a_trace_without_the_engine_spans_reduces_to_nothing():
    raw = _raw()
    raw["host"] = [e for e in raw["host"] if not e[0].startswith("serve.")]
    assert serve_trace.reduce(raw) is None
    assert serve_trace.reduce(serve_trace.load(str(OLD))) is None
    raw = _raw()
    assert serve_trace.reduce({"host": raw["host"], "devices": []}) is None


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(prefill_step)/while/body/closed_call/attention/dot_general:",
     "attention"),
    ("jit(decode_step)/while/body/ssd/norm/mul:", "norm"),   # innermost
    ("jit(decode_step)/lm_head/dot_general:", "lm_head"),
    ("jit(<lambda>)/jit(_take)/gather:", "unscoped"),
    ("p['embedding']:", "unscoped"), (None, "unscoped")])
def test_scope_of_a_name_stack(tf_op, scope):
    assert serve_trace.scope_of(tf_op) == scope


def test_metadata_reader_agrees_with_tensorflow_on_the_recorded_trace():
    pytest.importorskip("tensorflow")
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    import gzip

    space = xplane_pb2.XSpace()
    space.ParseFromString(gzip.open(OLD).read())
    ref = next(p for p in space.planes if p.name == "/device:TPU:0")
    names = {k: v.name for k, v in ref.stat_metadata.items()}
    mine = next(p for p in xspace.read(str(OLD), lambda n: n == ref.name))
    tf_ops = 0
    for mid, md in ref.event_metadata.items():
        stats = {names[s.metadata_id]: s.str_value for s in md.stats
                 if s.WhichOneof("value") == "str_value"}
        assert mine.meta[mid][0] == md.name
        assert mine.meta[mid][1].get("tf_op") == stats.get("tf_op")
        tf_ops += "tf_op" in stats
    assert tf_ops > 100
    line = next(l for l in ref.lines if l.name == "XLA Ops")
    got = next(l for l in mine.lines if l.name == "XLA Ops").events
    assert [e[0] for e in got] == [e.metadata_id for e in line.events]
    assert [e[1] for e in got] == pytest.approx(
        [line.timestamp_ns + e.offset_ps / 1e3 for e in line.events])


def test_recorded_trace_of_the_instrumented_engine(monkeypatch, tmp_path):
    """A smoke-size dense model served on one v5e for 0.3 s by the engine
    with its spans, named steps and scopes (``record_trace.py``)."""
    raw = serve_trace.load(str(NEW))
    r = serve_trace.reduce(raw)
    assert r is not None
    assert {e[0] for e in raw["host"]} >= {
        "serve.step", "serve.decode", "serve.sync", "serve.emit",
        "serve.admit", "serve.prefill", "serve.splice"}
    # the existing reduction matches launches with the renamed programs
    old = trace_reduce.reduce(trace_reduce.load(str(NEW)),
                              step_stems=set(serve_trace.STEPS.values()))
    assert r.window_s == pytest.approx(old.window_s)
    # ProfileData rounds each time to the nanosecond; this reader keeps ps
    assert sum(old.launch_device_s) == pytest.approx(
        sum(r.step_s.values()), abs=1e-9 * len(old.launch_device_s))
    assert 0 < r.mixer_share("prefill") < 100
    assert 0 < r.mixer_share("decode") < 100
    assert 0 < min(r.decode_host_s) and len(r.decode_host_s) > 10
    assert max(r.decode_host_s) < 1e-3          # none spans a step (5 ms)
    assert sum(s for label, s in r.idle_by_span
               if label.startswith("host in serve.")) > 0.5 * sum(
        s for _, s in r.idle_by_span)

    # the metric readers find the run's trace by its window
    import gzip
    monkeypatch.setattr(serve_trace, "TRACES", tmp_path)
    (tmp_path / "t.xplane.pb").write_bytes(gzip.open(NEW).read())
    run = SimpleNamespace(trace=old)
    values = {m: load_metric(m)(run) for m in
              ("decode_host_ms", "prefill_mixer_share", "decode_mixer_share")}
    assert values["decode_host_ms"] == pytest.approx(
        1e3 * sum(r.decode_host_s) / len(r.decode_host_s))
    assert values["prefill_mixer_share"] == pytest.approx(
        r.mixer_share("prefill"))
    assert values["decode_mixer_share"] == pytest.approx(
        r.mixer_share("decode"))
    run = SimpleNamespace(trace=SimpleNamespace(window_s=old.window_s + 1))
    assert load_metric("decode_host_ms")(run) is None      # not this run's
    assert load_metric("decode_host_ms")(SimpleNamespace(trace=None)) is None
