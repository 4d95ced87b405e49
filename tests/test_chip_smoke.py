"""``chip_smoke.py`` refuses to run anywhere but a TPU, its checks fail
on the faults they exist for, and the persistent compilation cache lands
where ``launch/compile_cache.py`` says.  The refusal and cache cases run a
fresh interpreter: the platform and the cache directory are fixed when
jax starts."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import warnings
from pathlib import Path

import pytest

from faults import faulty_measure_fn

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, *, cwd, env_extra=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO / "src")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, where):
    """Under JAX_PLATFORMS=cpu, and in a directory that holds the script
    and nothing else of the repository, it exits non-zero and prints no
    result line."""
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    r = _run([str(script)], cwd=script.parent)
    assert r.returncode != 0, r.stdout
    assert '"ok": true' not in r.stdout
    if where == "checkout":
        assert "platform=cpu" in r.stdout


_CACHE_PROBE = (
    "import json, jax, jax.numpy as jnp\n"
    "from repro.launch.compile_cache import enable_compile_cache\n"
    "d = enable_compile_cache()\n"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
    "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
    "{compile}"
    "print(json.dumps({{'dir': d, "
    "'config': jax.config.jax_compilation_cache_dir}}))\n")


def test_compile_cache_uses_env_dir(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: that directory, and the compiled
    program lands in it."""
    cache = tmp_path / "cc"
    probe = _CACHE_PROBE.format(
        compile="jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    r = _run(["-c", probe], cwd=tmp_path,
             env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out == {"dir": str(cache), "config": str(cache)}
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_checkout(tmp_path):
    """No JAX_COMPILATION_CACHE_DIR: the fixed .jax_cache/ at the checkout
    root, which .gitignore lists."""
    r = _run(["-c", _CACHE_PROBE.format(compile="")], cwd=tmp_path,
             drop=("JAX_COMPILATION_CACHE_DIR",))
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["dir"] == out["config"] == str(REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


@pytest.mark.parametrize("fault", ["none", "zeros", "nan"])
def test_attention_error_holds_each_row_to_its_scale(smoke, fault):
    """Late causal rows are small; a kernel that zeroes them, or returns a
    NaN there, fails the check, and the oracle's own bf16 rounding
    passes."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref

    sq = skv = 2048
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 2, sq, 64)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 1, skv, 64)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 1, skv, 64)).astype(jnp.bfloat16)
    rows = [(0, 64), (sq - 64, 64)]
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        out = ref.attention_ref(q.astype(f32), k.astype(f32), v.astype(f32),
                                causal=True).astype(jnp.bfloat16)
        if fault == "zeros":
            out = out.at[:, :, sq // 2:].set(0)
        elif fault == "nan":
            out = out.at[0, 1, sq - 1, 3].set(jnp.nan)
        e, tol = smoke.attention_error(out, q, k, v, rows)
    assert (e <= tol) == (fault == "none"), (e, tol)


def _one_type_fails(inner, col_type):
    def measure(t, dv, p):
        if t == col_type:
            raise RuntimeError(f"{t} did not run")
        return inner(t, dv, p)
    return measure


@pytest.mark.parametrize("fault", ["none", "raise", "nan", "all-AllToAll"])
def test_calibration_check_fails_on_lost_points(smoke, tmp_path, fault):
    """The sweep drops a point that raises or times as NaN and fits the
    rest; the smoke check must still fail then, and when a whole
    collective type is missing from a fit that passes its gate."""
    from repro.calibrate.harness import _replace_mesh, synthetic_measure_fn
    from repro.core.hardware import tpu_v5e

    inner = synthetic_measure_fn(
        _replace_mesh(tpu_v5e().cluster_noc, (1, 4)))
    measure = {
        "none": inner,
        # call 0 is the first point's warmup; call 1 its first timing
        "raise": faulty_measure_fn(inner, fail_at=1, mode="raise"),
        "nan": faulty_measure_fn(inner, fail_at=1, mode="nan"),
        "all-AllToAll": _one_type_fails(inner, "AllToAll"),
    }[fault]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        ok = smoke.calibration_check(measure, 4, backend="synthetic",
                                     jax_version="test",
                                     store=str(tmp_path / "cal"))
    assert ok == (fault == "none")
