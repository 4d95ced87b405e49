"""The benchmark harness finds cells, mixes and metrics by name, and
refuses to report without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import bench_smoke
from bench import reference, run, traffic, weights

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_every_cell_has_its_files(cell):
    config = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert (ROOT / config["file"]).is_file()
    mix = traffic.load_mix(cell["traffic"])
    assert mix.requests > mix.batch
    limits = json.loads(
        (ROOT / "bench" / "limits" / f"{cell['name']}.json").read_text())
    assert {"mean_logit_gap", "served_logit_gap", "sample_requests", "rows_per_call",
            "min_checked_tokens"} <= set(limits)
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert callable(run.load_metric(metric["name"]))
    assert NAME.match(metric["name"])


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_is_what_the_program_runs(config):
    """The file's program section is the registry's configuration, and
    its published keys agree with it, as its family maps them."""
    from repro.configs.registry import get_config
    from repro.models.config import ModelConfig

    conf = json.loads((ROOT / config["file"]).read_text())
    cfg = ModelConfig(**conf["program"])
    assert cfg == get_config(conf["name"])
    assert set(config["reduced"]) == set(conf["reduced"]) == set(conf["published"])
    want = reference.part(conf["reference"], "program_fields")(conf)
    assert {field: getattr(cfg, field) for field in want} == want


def test_a_new_traffic_file_is_found_by_name(tmp_path):
    mix = dict(bench_smoke.MIX, requests=50)
    (tmp_path / "new-mix.json").write_text(json.dumps(mix))
    got = traffic.load_mix("new-mix", tmp_path)
    assert (got.batch, got.prompt_len, got.requests) == (3, 8, 50)
    a = traffic.generate(got, 2 ** 33 + 1, 512)
    assert len(a) == 50 and all(r["prompt"].shape == (8,) for r in a)
    again = traffic.generate(got, 2 ** 33 + 1, 512)
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, again))


def test_a_mix_whose_outputs_overflow_the_cache_is_refused(tmp_path):
    mix = dict(bench_smoke.MIX, cache_len=10)
    (tmp_path / "tight.json").write_text(json.dumps(mix))
    with pytest.raises(ValueError):
        traffic.load_mix("tight", tmp_path)


def test_a_new_metric_file_is_found_by_name(tmp_path):
    (tmp_path / "tokens.per_request.py").write_text(
        "def read(run):\n"
        "    return sum(len(s.times) for s in run.served) / len(run.served)\n")
    read = run.load_metric("tokens.per_request", tmp_path)

    class Stub:
        served = [type("S", (), {"times": [1, 2]})(),
                  type("S", (), {"times": [3, 4, 5, 6]})()]
    assert read(Stub) == 3.0


def test_every_seed_serves_the_same_lengths_from_the_steady_state():
    mix = traffic.Mix(name="m", batch=4, prompt_len=8, cache_len=2048,
                      requests=2000,
                      output={"kind": "lognormal", "median": 128,
                              "sigma": 1.0, "min": 16, "max": 1024})
    a = traffic.generate(mix, 2 ** 33 + 1, 512)
    b = traffic.generate(mix, 7, 512)
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert [r["in_flight"] for r in a[:6]] == [True] * 4 + [False] * 2
    # the residual life of lengths that are all 10 is uniform on 1..10
    assert sorted(traffic.residual_lengths(np.full(50, 10), 10)) == list(
        range(1, 11))
    # a slot at a random step more likely holds a long request, and has
    # on average half of it left: E[R] = E[L^2] / (2 E[L]) + 1/2
    lens = traffic.output_lengths(mix)
    res = traffic.residual_lengths(lens, 4000)
    want = (lens.astype(float) ** 2).mean() / (2 * lens.mean()) + 0.5
    assert res.mean() == pytest.approx(want, rel=0.02)
    assert res.min() >= 1 and res.max() <= lens.max()


def test_a_fixed_length_mix_keeps_its_slots_in_step():
    mix = traffic.Mix(name="f", batch=4, prompt_len=8, cache_len=64,
                      requests=40, output={"kind": "fixed", "tokens": 16})
    reqs = traffic.generate(mix, 3, 512)
    assert {r["max_new"] for r in reqs} == {16}
    assert not any(r["in_flight"] for r in reqs)


def test_a_metric_that_lists_workloads_is_reported_there_alone():
    cell = run.Cell.__new__(run.Cell)
    cell.bench_dir = ROOT / "bench"
    cell.spec = {"end_to_end": [
        {"name": "tok_s", "unit": "tokens/s"},
        {"name": "setup_s", "unit": "s", "workloads": ["other"]}]}
    stub = type("R", (), {"served": [], "t_open": 0.0, "t_close": 2.0,
                          "setup_s": 1.0})
    cell.name = "this"
    assert set(cell.metrics(stub, False)) == {"tok_s"}
    cell.name = "other"
    assert set(cell.metrics(stub, False)) == {"tok_s", "setup_s"}


def test_large_seeds_make_keys_and_are_repeatable():
    k1 = weights.prng_key(2 ** 33 + 5)
    k2 = weights.prng_key(2 ** 33 + 5)
    k3 = weights.prng_key(5)
    import jax
    assert (jax.random.key_data(k1) == jax.random.key_data(k2)).all()
    assert not (jax.random.key_data(k1) == jax.random.key_data(k3)).all()
    with pytest.raises(ValueError):
        weights.prng_key(-1)


def _cli(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi4-mini.decode",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_without_a_tpu_fails_and_prints_no_result():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_output_lengths_keep_the_lognormal_shape():
    mix = traffic.Mix(name="m", batch=4, prompt_len=8, cache_len=2048,
                      requests=2001,
                      output={"kind": "lognormal", "median": 128,
                              "sigma": 1.0, "min": 16, "max": 1024})
    lens = traffic.output_lengths(mix)
    assert np.median(lens) == 128
    assert lens.min() == 16 and lens.max() == 1024
