"""The serve engine's own instrumentation: host spans on the profiler's
clock, named step programs, layer-kind scopes in the step programs'
metadata, refill and compile counters, and per-request stamps."""
import logging
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.models.model import Model
from repro.serve import engine as engine_mod
from repro.serve.engine import Request, ServeEngine

B, PROMPT, CACHE = 2, 8, 32
# staggered budgets: slots free on different steps, so refills splice
BUDGETS = [3, 5, 2, 4, 3]


@pytest.fixture(scope="module", params=["phi4-mini-3.8b", "mamba2-130m"])
def parts(request):
    cfg = get_smoke_config(request.param)
    model = Model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(parts, prompt_len=PROMPT):
    model, params = parts
    return ServeEngine(model, params, batch_size=B, cache_len=CACHE,
                       prompt_len=prompt_len, plan_warmup=False)


def _requests(cfg, n=len(BUDGETS), length=PROMPT):
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, length,
                                               dtype=np.int32),
                    max_new_tokens=BUDGETS[i % len(BUDGETS)])
            for i in range(n)]


def _host_spans(trace_dir):
    """(name, start_ns, end_ns, args) of the ``serve.*`` host events."""
    from jax._src.profiler import ProfileData

    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("serve."))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def traced(parts, tmp_path_factory):
    """One traced run: the engine, its requests and its ``serve.*`` spans."""
    eng = _engine(parts)
    eng.run(_requests(parts[0].cfg, n=1))          # compile outside the trace
    reqs = _requests(parts[0].cfg)
    trace_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(trace_dir)):
        eng.run(reqs)
    return eng, reqs, _host_spans(trace_dir)


def _inside(spans, outer):
    _, a, b, _ = outer
    return [s for s in spans if a <= s[1] and s[2] <= b and s is not outer]


def test_each_decode_step_is_a_step_span_of_decode_sync_emit(traced):
    eng, _, spans = traced
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == eng.stats["decode_steps"] - 3   # the untraced run's
    for step in steps:
        names = [s[0] for s in _inside(spans, step)]
        assert names[:4] == ["serve.decode", "serve.sync", "serve.emit",
                             "serve.admit"]
        assert set(names) <= {"serve.decode", "serve.sync", "serve.emit",
                              "serve.admit", "serve.prefill", "serve.splice"}
    assert [int(s[3]["step_num"]) for s in steps] == list(
        range(3, eng.stats["decode_steps"]))
    live = [int(s[3]["rows"]) for s in spans if s[0] == "serve.decode"]
    assert max(live) == B and min(live) >= 1


def test_a_refill_admits_prefills_and_splices_with_its_rows(traced):
    eng, reqs, spans = traced
    by_name = defaultdict(list)
    for s in spans:
        by_name[s[0]].append(s)
    admits = [s for s in by_name["serve.admit"] if int(s[3]["rows"]) > 0]
    prefills = by_name["serve.prefill"]
    # the first fill and a refill of every slot take B fresh rows whole;
    # every other refill prefills its bucket and has a splice, in order
    partial = [p for p in prefills[1:] if int(p[3]["rows"]) < B]
    assert len(admits) == len(prefills)
    assert 0 < len(partial) == len(by_name["serve.splice"])
    assert sum(int(s[3]["rows"]) for s in admits) == len(reqs)
    for i, (admit, prefill) in enumerate(zip(admits, prefills)):
        assert admit[2] <= prefill[1]
        rows = int(prefill[3]["rows"])
        assert rows == int(admit[3]["rows"])
        assert int(prefill[3]["batch"]) == (
            B if i == 0 or rows == B else eng.bucket(rows))
    for prefill, splice in zip(partial, by_name["serve.splice"]):
        assert prefill[2] <= splice[1]


def test_steps_are_named_programs_with_layer_kind_scopes(parts):
    import re

    model, params = parts
    cfg = model.cfg
    eng = _engine(parts)
    assert eng.prefill_step.__name__ == "prefill_step"
    assert eng.decode_step.__name__ == "decode_step"
    batch = {"tokens": jax.numpy.zeros((B, PROMPT), jax.numpy.int32)}
    _, cache = jax.eval_shape(eng.prefill_step, params, batch)
    tok = jax.ShapeDtypeStruct((B, 1), jax.numpy.int32)
    want = {"ssd" if cfg.has_ssm else "attention", "norm", "embed", "lm_head"}
    if cfg.d_ff > 0:
        want.add("mlp")
    for name, lowered in (
            ("prefill_step", eng.prefill_step.lower(params, batch)),
            ("decode_step", eng.decode_step.lower(params, cache, tok))):
        hlo = lowered.as_text(dialect="hlo", debug_info=True)
        assert f"HloModule jit_{name}" in hlo
        ops = re.findall(r'op_name="([^"]*)"', hlo)
        scopes = {part for op in ops for part in op.split("/")}
        assert want <= scopes, (name, want - scopes)


def test_rows_admitted_counts_every_request_served(traced):
    eng, reqs, _ = traced
    assert all(r.done for r in reqs)
    assert eng.stats["rows_admitted"] == len(reqs) + 1   # and the untraced one


def test_compiles_while_serving_are_counted_and_named(parts, caplog):
    eng = _engine(parts)
    reqs = lambda: _requests(parts[0].cfg)          # noqa: E731
    eng.run(reqs())
    first = eng.stats["compiles"]
    assert first > 0                      # the steps compiled inside run()
    assert "jit(prefill_step)" in engine_mod._warned  # and were named
    eng.run(reqs())
    assert eng.stats["compiles"] == first           # same shapes: none
    eng.prompt_len = PROMPT + 4                     # a new prompt shape
    with caplog.at_level(logging.WARNING, logger="repro.serve.engine"):
        eng.run(_requests(parts[0].cfg, length=PROMPT + 4))
    recompiled = eng.stats["compiles"]
    assert recompiled > first
    # prefill_step was named when it first compiled while serving: once
    assert "jit(prefill_step) compiled while serving" not in caplog.text
    jax.jit(lambda x: x + 1)(np.ones(3))            # outside run(): no count
    assert eng.stats["compiles"] == recompiled


def test_request_stamps_are_ordered(traced):
    _, reqs, _ = traced
    for r in reqs:
        assert r.admitted_s <= r.first_token_s <= r.done_s


def test_launch_summary_reads_the_request_stamps(parts):
    from repro.launch import serve

    eng = _engine(parts)
    reqs = _requests(parts[0].cfg)
    out = serve.run(eng, reqs)
    assert "tok_per_s" not in out
    span = max(r.done_s for r in reqs) - min(r.admitted_s for r in reqs)
    assert out["serve_tok_s"] == pytest.approx(out["tokens"] / span, rel=1e-2)
    assert out["ttft_p50_ms"] == pytest.approx(1e3 * np.median(
        [r.first_token_s - r.admitted_s for r in reqs]), abs=0.01)
    assert out["compiles"] == eng.stats["compiles"] > 0
    assert out["wall_s"] >= span - 0.01
