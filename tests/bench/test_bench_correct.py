"""``correct`` at smoke size on the CPU: the reference agrees with what the
engine served through prefill, decode and refilled slots; the int8
control does not; and a run with its timed path broken comes out false."""
import json

import jax
import jax.numpy as jnp
import pytest

import bench_smoke
from bench import run
from bench.serving import Served

# mean gap readings of this flow at these sizes, seeds 11 to 22 (165 to
# 238 tokens each): the program reads at most 6.2e-4 (dense) and 6.5e-4
# (mamba2), the int8 control at least 1.24e-3 and 1.45e-3.
SMOKE_LIMITS = {"dense-smoke-wide": 9e-4, "mamba2-smoke-wide": 1e-3}


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    confs = [bench_smoke.wide(bench_smoke.DENSE),
             bench_smoke.wide(bench_smoke.MAMBA2)]
    limits = dict(bench_smoke.LIMITS, sample_requests=24)
    s = bench_smoke.write_spec(root, confs=confs, limits=limits)
    for name, limit in SMOKE_LIMITS.items():
        (root / "bench" / "limits" / f"{name}.smoke.json").write_text(
            json.dumps(dict(limits, mean_logit_gap=limit)))
    return root, s


def _cell(spec, name):
    root, s = spec
    return run.Cell(s, f"{name}.smoke", bench_dir=root / "bench",
                    require_tpu=False)


def _served(cell, seed, n=40):
    from bench import traffic
    reqs = traffic.generate(cell.mix, seed, cell.conf["vocab_size"])[:n]
    return [Served(rid=i, prompt=r["prompt"], max_new=r["max_new"])
            for i, r in enumerate(reqs)]


@pytest.mark.parametrize("name", sorted(SMOKE_LIMITS))
def test_reference_agrees_and_the_int8_control_does_not(spec, name):
    cell = _cell(spec, name)
    for seed in (11, 12, 13):
        params, eng, rec = cell.start(seed)
        served = _served(cell, seed)
        rec.serve(served)                 # to completion: no clock involved
        assert eng.stats["prefill_calls"] > 2     # slots were refilled
        rows = cell.checked_rows(rec, served, seed)
        program = cell.gaps(params, rows)
        control = cell.gaps(params, rows, control=True)
        assert len(program) > 100
        counters = {"nonfinite_tokens": eng.stats["nonfinite_tokens"]}
        # the cell's own comparison, with the cell's own limits file
        assert cell.judge(program, counters)[0], cell.judge(program, counters)
        assert not cell.judge(control, counters)[0], cell.judge(control, counters)


def _token_altered(eng):
    real, calls = eng.decode_step, [0]

    def decode(p, c, t):
        logits, cache = real(p, c, t)
        calls[0] += 1
        if calls[0] % 4 == 0:
            logits = logits.at[:, :, 5].add(1e4)
        return logits, cache
    eng.decode_step = decode


def _state_unchanged(eng):
    step = jax.jit(lambda p, c, t: (eng.model.decode(p, c, t)[0], c))
    eng.decode_step = step


def _half_batch_left_out(eng):
    real = eng.prefill_step

    def prefill(p, batch):
        toks = batch["tokens"]
        keep = jnp.arange(toks.shape[0])[:, None] < (toks.shape[0] + 1) // 2
        return real(p, dict(batch, tokens=jnp.where(keep, toks, 0)))
    eng.prefill_step = prefill


# the runs below stop on the clock, so a loaded machine finishes fewer
# requests and the mean of a small sample swings: their limit stands ten
# times above the program's readings; a broken path reads a hundred
# times more (a wrong token lies some logits below the best)
RUN_LIMIT = 0.01


@pytest.fixture(scope="module")
def run_spec(tmp_path_factory):
    """The same cells, with a limit for runs in place."""
    root = tmp_path_factory.mktemp("bench_runs")
    confs = [bench_smoke.wide(bench_smoke.DENSE),
             bench_smoke.wide(bench_smoke.MAMBA2)]
    limits = dict(bench_smoke.LIMITS, sample_requests=24,
                  mean_logit_gap=RUN_LIMIT)
    return root, bench_smoke.write_spec(root, confs=confs, limits=limits)


@pytest.fixture(scope="module")
def sound(run_spec):
    """One sound run of each cell."""
    root, s = run_spec
    return {name: run.execute(s, f"{name}.smoke", 5, 0.5, False,
                              require_tpu=False, bench_dir=root / "bench")
            for name in SMOKE_LIMITS}


@pytest.mark.parametrize("name", sorted(SMOKE_LIMITS))
def test_a_sound_run_is_correct(sound, name):
    assert sound[name]["correct"], sound[name]["checks"]


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch_left_out],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("name", sorted(SMOKE_LIMITS))
def test_a_broken_timed_path_is_not_correct(run_spec, sound, name, fault):
    root, s = run_spec
    broken = run.execute(s, f"{name}.smoke", 5, 0.5, False, require_tpu=False,
                         bench_dir=root / "bench", engine_hook=fault)
    assert not broken["correct"], broken["checks"]
    assert (broken["checks"]["mean_logit_gap"]["value"]
            > 3 * sound[name]["checks"]["mean_logit_gap"]["value"])
