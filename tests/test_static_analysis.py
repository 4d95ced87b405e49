"""Tests for the static-analysis subsystem (``repro.analysis``): the
scan-aware jaxpr walker, the async-aware HLO parser, the kernel/sharded
contract checker (including a deliberately broken kernel that MUST be
flagged), and the repo-invariant AST lint."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ jaxpr walker


def test_nested_scan_trip_count_product():
    """Nested scans multiply their trip counts (outer x inner) — the
    regression the walker refactor pins."""
    from repro.analysis import structural_flops

    W = jax.ShapeDtypeStruct((3, 5, 16, 16), jnp.float32)
    X = jax.ShapeDtypeStruct((4, 16), jnp.float32)

    def f(x, ws):
        def outer(c, wrow):
            def inner(c2, w):
                return c2 @ w, None
            c, _ = jax.lax.scan(inner, c, wrow)
            return c, None
        out, _ = jax.lax.scan(outer, x, ws)
        return out

    assert structural_flops(f, X, W) == 3 * 5 * 2 * 4 * 16 * 16


def test_conv_general_dilated_flops():
    from repro.analysis import structural_flops

    def f(x, k):
        return jax.lax.conv_general_dilated(
            x, k, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    X = jax.ShapeDtypeStruct((2, 8, 8, 3), jnp.float32)
    K = jax.ShapeDtypeStruct((3, 3, 3, 7), jnp.float32)
    # 2 x output points x kernel spatial x in-channels-per-group
    assert structural_flops(f, X, K) == 2 * (2 * 8 * 8 * 7) * (3 * 3) * 3


def test_pallas_grid_multiplier():
    """The kernel body is counted once per grid cell: a blocked GEMM
    kernel must trace to exactly 2*M*N*K."""
    from repro.analysis import trace_counts
    from repro.kernels.gemm_softmax import gemm_softmax

    M, K, N = 256, 256, 128

    def f(a, b):
        return gemm_softmax(a, b, block_m=128, block_k=128)

    tc = trace_counts(f, jax.ShapeDtypeStruct((M, K), jnp.bfloat16),
                      jax.ShapeDtypeStruct((K, N), jnp.bfloat16))
    assert tc.flops == 2 * M * K * N
    assert tc.total_collective_dv() == 0.0


def test_cond_counts_max_branch():
    from repro.analysis import structural_flops

    def f(p, a, b):
        return jax.lax.cond(p, lambda: a @ b,
                            lambda: jnp.zeros((64, 16), jnp.float32))

    P = jax.ShapeDtypeStruct((), jnp.bool_)
    A = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    B = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    assert structural_flops(f, P, A, B) == 2 * 64 * 32 * 16


def test_collective_count_scan_multiplier():
    """A psum inside a scan inside a shard_map is counted scan-length
    times (and classified as an AllReduce)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.analysis import trace_counts

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

    def body(xs):
        def step(c, x):
            return c + jax.lax.psum(x, "x"), None
        out, _ = jax.lax.scan(step, jnp.zeros_like(xs[0]), xs)
        return out

    f = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)
    tc = trace_counts(f, jax.ShapeDtypeStruct((5, 8), jnp.float32))
    recs = list(tc.collectives.values())
    assert len(recs) == 1
    assert recs[0].col_type == "AllReduce"
    assert recs[0].count == 5.0


def test_launch_shims_reexport():
    """launch/jaxpr_analysis + launch/hlo_analysis stay importable and
    hand back the moved implementations, not copies."""
    import warnings

    import repro.analysis as an
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from repro.launch import hlo_analysis as shim_h
        from repro.launch import jaxpr_analysis as shim_j
    assert shim_j.structural_flops is an.structural_flops
    assert shim_j.trace_counts is an.trace_counts
    assert shim_h.parse_collectives is an.parse_collectives
    assert shim_h.shape_bytes is an.shape_bytes


def test_launch_shims_warn_deprecation():
    """Importing either compat shim emits DeprecationWarning (module-level,
    so re-importing an already-loaded shim needs a reload to re-fire)."""
    import importlib

    from repro.launch import hlo_analysis, jaxpr_analysis
    for shim in (jaxpr_analysis, hlo_analysis):
        with pytest.warns(DeprecationWarning, match="deprecated compat shim"):
            importlib.reload(shim)


# ------------------------------------------------------------- HLO parser

ASYNC_HLO = """
HloModule async_sample

ENTRY %main (p0: bf16[16,128]) -> bf16[64,128] {
  %ags = (bf16[16,128], bf16[64,128]) all-gather-start(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %agd = bf16[64,128] all-gather-done(%ags)
  %ars = f32[128] all-reduce-start(%q), replica_groups={{0,1}}, to_apply=%add
  %ard = f32[128] all-reduce-done(%ars)
  %rss = (f32[64,128], f32[16,128]) reduce-scatter-start(%r), replica_groups={{0,1,2,3}}, dimensions={0}
  %rsd = f32[16,128] reduce-scatter-done(%rss)
  %rag = bf16[32,64] ragged-all-to-all(%s, %t), replica_groups={{0,1,2,3}}
  ROOT %out = bf16[64,128] copy(%agd)
}
"""


def test_hlo_async_pairs_counted_once():
    """-start carries the volume, -done contributes nothing: each async
    collective is counted exactly once (no double- or zero-counting)."""
    from repro.analysis import parse_collectives
    d = parse_collectives(ASYNC_HLO).to_dict()
    assert d["all-gather"]["count"] == 1
    assert d["all-reduce"]["count"] == 1
    assert d["reduce-scatter"]["count"] == 1
    # all-gather-start result tuple: max element = the GATHERED result
    assert d["all-gather"]["raw_bytes"] == 64 * 128 * 2
    assert d["all-gather"]["wire_bytes"] == pytest.approx(
        64 * 128 * 2 * 3 / 4)
    # all-reduce-start: single-shape result, wire = 2(G-1)/G x bytes
    assert d["all-reduce"]["raw_bytes"] == 128 * 4
    assert d["all-reduce"]["wire_bytes"] == pytest.approx(128 * 4 * 1.0)
    # reduce-scatter-start: max tuple element is the INPUT; raw bytes is
    # input/G (the sync form's scattered output), wire = out x (G-1)
    assert d["reduce-scatter"]["raw_bytes"] == 64 * 128 * 4 // 4
    assert d["reduce-scatter"]["wire_bytes"] == pytest.approx(
        16 * 128 * 4 * 3)


def test_hlo_ragged_all_to_all_not_dropped():
    """ragged-all-to-all must precede all-to-all in the regex alternation
    or the op is silently dropped — pinned here."""
    from repro.analysis import parse_collectives
    d = parse_collectives(ASYNC_HLO).to_dict()
    assert d["ragged-all-to-all"]["count"] == 1
    assert d["ragged-all-to-all"]["raw_bytes"] == 32 * 64 * 2
    assert d["ragged-all-to-all"]["wire_bytes"] == pytest.approx(
        32 * 64 * 2 * 3 / 4)
    assert "all-to-all" not in d  # not mis-binned either


# -------------------------------------------------------------- contracts


def test_kernel_contracts_smoke_shapes():
    """One shape per family: plan-resolved blocks trace to exactly the
    compound op's GEMM FLOPs and zero collectives."""
    from repro.analysis.contracts import kernel_contract_checks
    shapes = {"gemm_epilogue_blocks": [(512, 4096, 128)],
              "attention_blocks": [(1024, 1024, 64)],
              "ssd_chunk_len": [(4096, 64, 128)]}
    checks = kernel_contract_checks(shapes)
    families = {c.detail["family"] for c in checks}
    assert families == {"gemm_softmax", "gemm_layernorm",
                        "flash_attention", "ssd"}
    bad = [c.describe() for c in checks if not c.ok]
    assert not bad, "\n".join(bad)


@pytest.mark.slow
def test_kernel_contracts_all_paper_shapes():
    from repro.analysis.contracts import kernel_contract_checks
    checks = kernel_contract_checks()
    assert len(checks) >= 2 * (2 * 3 + 4 + 1)  # 2 checks per (family, shape)
    bad = [c.describe() for c in checks if not c.ok]
    assert not bad, "\n".join(bad)


def test_broken_kernel_is_flagged():
    """A Pallas kernel that issues the dot twice per grid cell (double
    work) MUST fail its FLOP contract with an actionable report."""
    from jax.experimental import pallas as pl
    from repro.analysis import trace_counts
    from repro.analysis.contracts import kernel_contract_checks

    def _trace_broken(co, blocks):
        bm, bk = blocks
        M, K = co.dim_sizes["M"], co.dim_sizes["K"]
        N = co.dim_sizes["N"]

        def kernel(a_ref, b_ref, o_ref):
            a = a_ref[...].astype(jnp.float32)
            b = b_ref[...].astype(jnp.float32)
            # BROKEN: the dot is issued twice -> 2x the contracted FLOPs
            o_ref[...] = (jnp.dot(a, b) + jnp.dot(a, b)).astype(o_ref.dtype)

        def fn(a, b):
            return pl.pallas_call(
                kernel,
                grid=(M // bm, K // bk),
                in_specs=[pl.BlockSpec((bm, bk), lambda mi, ki: (mi, ki)),
                          pl.BlockSpec((bk, N), lambda mi, ki: (ki, 0))],
                out_specs=pl.BlockSpec((bm, N), lambda mi, ki: (mi, 0)),
                out_shape=jax.ShapeDtypeStruct((M, N), jnp.bfloat16),
                interpret=True,
            )(a, b)

        return trace_counts(fn, jax.ShapeDtypeStruct((M, K), jnp.bfloat16),
                            jax.ShapeDtypeStruct((K, N), jnp.bfloat16))

    checks = kernel_contract_checks(
        shapes={"gemm_epilogue_blocks": [(512, 4096, 128)]},
        tracers={"gemm_softmax": _trace_broken})
    bad = [c for c in checks if not c.ok
           and c.name.startswith("gemm_softmax")]
    assert bad, "broken kernel slipped through the contract check"
    fail = bad[0]
    assert fail.kind == "gemm_flops"
    # traced exactly double the prediction
    assert fail.traced == pytest.approx(2 * fail.predicted)
    # the report says which plan lied and by how much
    msg = fail.describe()
    assert "MISMATCH" in msg and "op_sig=" in msg and "predicted=" in msg
    # ...while the untouched sibling kernel still passes
    assert all(c.ok for c in checks if c.name.startswith("gemm_layernorm"))


def test_sharded_contracts_single_device_degrades():
    """On a 1-device mesh the schedule is empty and only the FLOP
    contract remains — and it holds."""
    from jax.sharding import Mesh
    from repro.analysis.contracts import sharded_contract_checks
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    checks = sharded_contract_checks(mesh=mesh)
    assert checks
    assert all(c.kind == "gemm_flops" for c in checks)
    bad = [c.describe() for c in checks if not c.ok]
    assert not bad, "\n".join(bad)


@pytest.mark.slow
def test_cli_smoke_multidevice():
    """`python -m repro.analysis --smoke` in a subprocess: the CLI forces
    8 virtual CPU devices, so the sharded arm runs a REAL 2x4 mesh
    contract check; both arms must pass and emit the JSON schema."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    try:
        r = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--smoke"],
            env=env, capture_output=True, text=True, timeout=900)
    except (OSError, PermissionError) as e:
        pytest.skip(f"sandbox cannot spawn the CLI subprocess: {e!r}")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    data = json.loads(r.stdout)
    assert data["schema"] == "repro/static-analysis/v2"
    assert data["ok"] and data["contracts"]["ok"] and data["lint"]["ok"]
    names = [c["name"] for c in data["contracts"]["checks"]]
    # the sharded arm ran on a multi-device mesh (2x4 from 8 devices)
    assert any("sharded_softmax_xent[dist" in n for n in names)
    assert any("@P4" in n for n in names)


@pytest.mark.slow
def test_sharded_contracts_equal_axis_sizes():
    """Regression: on a mesh where data and model axes have the SAME size
    (e.g. the 16x16 production mesh), the model-axis stat All-Reduces and
    the data-parallel scalar All-Reduces share a (type, participants)
    tracer bucket — the declared schedule must be aggregated by that key
    before comparison or both checks spuriously fail."""
    script = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
        "import jax\n"
        "from jax.sharding import Mesh\n"
        "import numpy as np\n"
        "from repro.analysis.contracts import sharded_contract_checks\n"
        "mesh = Mesh(np.array(jax.devices()).reshape(2, 2),\n"
        "            ('data', 'model'))\n"
        "checks = sharded_contract_checks(mesh)\n"
        "bad = [c.describe() for c in checks if not c.ok]\n"
        "assert not bad, '\\n'.join(bad)\n"
        "keys = [c.name for c in checks if 'AllReduce@P2' in c.name]\n"
        "assert keys, 'merged AllReduce@P2 bucket missing'\n"
        "print('EQUAL_AXIS_OK', len(checks))\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    try:
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=900)
    except (OSError, PermissionError) as e:
        pytest.skip(f"sandbox cannot spawn the subprocess: {e!r}")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "EQUAL_AXIS_OK" in r.stdout


def test_softmax_collective_schedule_declaration():
    """The declared schedule (what the planner costs AND what the
    contract checker audits against) — shape pinned."""
    from repro.parallel.collective_planner import softmax_collective_schedule
    d = softmax_collective_schedule("dist", 128, 4096, 8, dp_participants=2)
    assert ("AllReduce", 128 * 4.0, 8, 3) in d      # 3 stat ARs, f32 rows
    assert ("AllReduce", 4.0, 2, 2) in d            # 2 scalar data psums
    g = softmax_collective_schedule("gather", 128, 4096, 8)
    assert g == [("AllGather", 128 * 4096 * 4.0, 8, 1)]  # f32 gathered
    assert softmax_collective_schedule("dist", 128, 4096, 1) == []


# ------------------------------------------------------------------- lint


def test_lint_poly_math_rule():
    from repro.analysis.lint import lint_source
    src = "import math\ndef f(x):\n    return math.ceil(x)\n"
    assert any(f.rule == "poly-no-math" for f in lint_source(src, "core/cost.py"))
    # rule only applies on the polymorphic path
    assert lint_source(src, "models/layers.py") == []
    # allowlisted scalar-only helper in collectives.py
    src_ok = "import math\ndef _factor_table(x):\n    return math.ceil(x)\n"
    assert lint_source(src_ok, "core/collectives.py") == []


def test_lint_poly_array_branch_rule():
    from repro.analysis.lint import lint_source
    bad = "def f(dv):\n    if dv <= 0:\n        return 0\n    return dv\n"
    assert any(f.rule == "poly-array-branch"
               for f in lint_source(bad, "core/cost.py"))
    # the scalar-ok pragma silences an audited site
    ok = ("def f(dv):\n    if dv <= 0:  # scalar-ok: audited\n"
          "        return 0\n    return dv\n")
    assert lint_source(ok, "core/cost.py") == []
    # string compares / len() guards are recognized as scalar
    scalar = ("def f(mode, xs):\n    if mode == 'tree':\n        return 1\n"
              "    if len(xs) > 2:\n        return 2\n    return 0\n")
    assert lint_source(scalar, "core/cost.py") == []


def test_lint_builtin_max_rule():
    from repro.analysis.lint import lint_source
    bad = "def f(a, b):\n    return max(a, b)\n"
    assert any(f.rule == "poly-array-branch"
               for f in lint_source(bad, "core/numerics.py"))
    ok = "def f(a, b):\n    return max(a, b)  # scalar-ok: ints\n"
    assert lint_source(ok, "core/numerics.py") == []


def test_lint_kernel_no_host_rule():
    from repro.analysis.lint import lint_source
    src = ("import numpy as np\nimport jax.numpy as jnp\n"
           "def _foo_kernel(x_ref, o_ref):\n"
           "    s = np.sum(x_ref[...])\n"
           "    v = s.item()\n"
           "    o_ref[...] = jnp.asarray(v, jnp.float64)\n"
           "def host_helper(x):\n"
           "    return np.sum(x)\n")
    findings = lint_source(src, "kernels/foo.py")
    assert {f.rule for f in findings} == {"kernel-no-host"}
    msgs = "\n".join(f.message for f in findings)
    assert "np.sum" in msgs and ".item" in msgs and "float64" in msgs
    # only the kernel body is constrained, not host code
    assert all(f.line <= 6 for f in findings)
    # autotune (host-side planner) is exempt
    assert lint_source(src, "kernels/autotune.py") == []


def test_lint_core_sqlite_rule():
    from repro.analysis.lint import lint_source
    assert any(f.rule == "core-no-sqlite"
               for f in lint_source("import sqlite3\n", "core/foo.py"))
    assert any(f.rule == "core-no-sqlite"
               for f in lint_source("from sqlite3 import connect\n",
                                    "core/foo.py"))
    assert lint_source("import sqlite3\n", "core/planstore.py") == []
    assert lint_source("import sqlite3\n", "serve/api.py") == []


def test_lint_repo_clean():
    """The repo itself must pass its own lint — this is the same gate CI
    runs via `python -m repro.analysis --lint`."""
    from repro.analysis.lint import lint_repo
    findings = lint_repo()
    assert findings == [], "\n".join(f.describe() for f in findings)


def test_vmem_budget_catches_oversized_blocks(tmp_path):
    from repro.analysis.lint import vmem_findings
    kdir = tmp_path / "kernels"
    kdir.mkdir()
    # a gemm kernel declaring blocks 1024x larger than the autotuner's
    # candidates: must blow the double-buffered VMEM budget
    (kdir / "gemm_softmax.py").write_text(
        "from jax.experimental import pallas as pl\n"
        "def run(a, b, block_m, block_k, N):\n"
        "    return pl.pallas_call(\n"
        "        _k,\n"
        "        in_specs=[pl.BlockSpec((block_m * 1024, block_k),\n"
        "                               lambda i, j: (i, j))],\n"
        "        out_specs=pl.BlockSpec((block_m * 1024, N),\n"
        "                               lambda i, j: (i, 0)),\n"
        "    )(a, b)\n")
    findings = vmem_findings(tmp_path)
    assert findings and findings[0].rule == "vmem-budget"
    assert "exceeds" in findings[0].message


def test_vmem_budget_flags_extraction_rot(tmp_path):
    """A kernel file with no recognizable pallas_call is itself a finding
    — the static extraction must not silently rot."""
    from repro.analysis.lint import vmem_findings
    kdir = tmp_path / "kernels"
    kdir.mkdir()
    (kdir / "flash_attention.py").write_text("def f():\n    return 1\n")
    findings = vmem_findings(tmp_path)
    assert findings and findings[0].rule == "vmem-budget"
    assert "no pallas_call" in findings[0].message
