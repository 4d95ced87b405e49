"""Train-step collective contracts + jaxpr/HLO reconciliation (PR 8).

Three layers under test:

* the jaxpr walker's `while` trip-count detection and the
  `reduce_scatter` primitive mapping (`analysis/jaxpr.py`);
* the declared train schedule audited against the traced train step —
  including the injected-drift regression that proves a mis-declared
  psum is caught, in-process and through the CLI exit code
  (`analysis/contracts.py` + `parallel/collective_planner.py`);
* the jaxpr-vs-HLO reconciler and the checked-in golden fixture of a
  real compiled 2x2-mesh train step (`analysis/reconcile.py`).

Multi-device pieces run in subprocesses (XLA_FLAGS must be set before
jax initializes); everything else is pure and single-device.
"""
import gzip
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(REPO, "tests", "fixtures")


def _run_sub(script: str, timeout: int = 900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    try:
        return subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True,
                              timeout=timeout)
    except (OSError, PermissionError) as e:
        pytest.skip(f"sandbox cannot spawn the subprocess: {e!r}")


# ------------------------------------------------- walker: while + RS


def test_while_static_trip_count_multiplies():
    """A counted while (fori_loop lowers to one) multiplies the body's
    FLOPs by the statically derived trip count — no finding."""
    from repro.analysis import trace_counts

    def f(x):
        return jax.lax.fori_loop(
            0, 7, lambda i, c: c @ c, x)

    tc = trace_counts(f, jax.ShapeDtypeStruct((8, 8), jnp.float32))
    assert tc.flops == pytest.approx(7 * 2 * 8 * 8 * 8)
    assert tc.findings == []


def test_while_unbounded_is_a_finding_not_a_silent_lower_bound():
    """A data-dependent while cannot be statically counted: the body is
    counted ONCE and an explicit `while-unbounded` finding marks the
    totals as a lower bound."""
    from repro.analysis import trace_counts

    def f(x):
        def cond(c):
            return jnp.sum(c) < 100.0     # data-dependent bound

        return jax.lax.while_loop(cond, lambda c: c @ c, x)

    tc = trace_counts(f, jax.ShapeDtypeStruct((8, 8), jnp.float32))
    assert tc.flops == pytest.approx(2 * 8 * 8 * 8)   # body once
    assert len(tc.findings) == 1
    assert tc.findings[0]["kind"] == "while-unbounded"
    assert "lower bound" in tc.findings[0]["detail"]


def test_while_literal_bound_nonunit_step():
    """Trip count = ceil((bound - init) / step) for literal-stepped
    counters, not just fori_loop's +1."""
    from repro.analysis import trace_counts

    def f(x):
        def body(carry):
            i, c = carry
            return i + 2, c @ c

        _, out = jax.lax.while_loop(lambda carry: carry[0] < 9,
                                    body, (0, x))
        return out

    tc = trace_counts(f, jax.ShapeDtypeStruct((4, 4), jnp.float32))
    # i = 0,2,4,6,8 -> 5 iterations
    assert tc.flops == pytest.approx(5 * 2 * 4 * 4 * 4)
    assert tc.findings == []


def test_psum_scatter_binds_reduce_scatter_primitive():
    """jax.lax.psum_scatter binds a primitive named `reduce_scatter`
    (NOT `psum_scatter`); the walker's table must key on the bound
    name or every Reduce-Scatter is silently dropped.  Regression for
    the bug the gather-arm train contract exposed."""
    from repro.analysis.jaxpr import _PRIM_TO_TYPE
    assert _PRIM_TO_TYPE.get("reduce_scatter") == "ReduceScatter"

    script = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        "import jax, jax.numpy as jnp, numpy as np\n"
        "from jax.sharding import Mesh, PartitionSpec as P\n"
        "from repro.analysis import trace_counts\n"
        "mesh = Mesh(np.array(jax.devices()).reshape(4), ('model',))\n"
        "def body(x):\n"
        "    return jax.lax.psum_scatter(x, 'model', tiled=True)\n"
        "f = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P('model'),\n"
        "                  check_vma=False)\n"
        "tc = trace_counts(f, jax.ShapeDtypeStruct((8, 4), jnp.float32))\n"
        "rec = tc.collectives.get(('ReduceScatter', 4))\n"
        "assert rec is not None, tc.to_dict()\n"
        "assert rec.count == 1.0, rec\n"
        "assert rec.dv_bytes == 8 * 4 * 4.0, rec\n"
        "print('RS_TRACED_OK')\n")
    r = _run_sub(script)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "RS_TRACED_OK" in r.stdout


# ------------------------------------------------- declared schedule


class _FakeMesh:
    """Just enough Mesh surface for train_collective_schedule."""

    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


def test_train_schedule_declares_and_prices():
    """The declared schedule is serializable, covers both origins, and
    prices to a finite positive latency on the cluster NoC."""
    from repro.configs.registry import get_smoke_config
    from repro.parallel.collective_planner import (
        price_collective_schedule, train_collective_schedule)

    cfg = get_smoke_config("glm4-9b")
    mesh = _FakeMesh(data=2, model=4)
    sched = train_collective_schedule(cfg, mesh, 8, 16)
    assert sched
    origins = {d.origin for d in sched}
    assert origins == {"explicit", "gspmd"}
    labels = [d.label for d in sched]
    assert "xent/stats" in labels            # softmax schedule composed in
    assert any(lbl.startswith("grads/") for lbl in labels)
    for d in sched:
        rt = d.to_dict()
        assert set(rt) == {"label", "type", "dv_bytes", "participants",
                           "count", "origin"}
    t = price_collective_schedule(sched)
    assert 0.0 < t < float("inf")


def test_train_schedule_moe_has_no_all_to_all():
    """The MoE combine is declared as psums — a token all-to-all in the
    declaration would contradict models/moe.py's contract."""
    from repro.configs.registry import get_smoke_config
    from repro.parallel.collective_planner import train_collective_schedule

    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    sched = train_collective_schedule(cfg, _FakeMesh(data=2, model=4), 8, 16)
    assert all(d.col_type != "AllToAll" for d in sched)
    assert any(d.label == "moe/combine" for d in sched)
    assert any(d.label == "moe/router-grad" for d in sched)


def test_train_schedule_microbatch_scaling():
    """Microbatching splits activations into m smaller chunks: for
    activation-sized entries, count x m with DV / m (total wire
    invariant); for weight-gradient entries the psum repeats per
    microbatch on the SAME-sized tensor (count x m, DV unchanged —
    total wire grows), exactly what the traced jaxpr does."""
    from repro.configs.registry import get_smoke_config
    from repro.parallel.collective_planner import train_collective_schedule

    # pin the strategy: "auto" legitimately flips dist->gather when the
    # microbatch rows shrink, which would change the label set
    cfg = get_smoke_config("glm4-9b").with_(softmax_strategy="dist")
    mesh = _FakeMesh(data=2, model=4)
    s1 = {d.label: d for d in train_collective_schedule(
        cfg, mesh, 8, 16, microbatches=1) if d.origin == "explicit"}
    s2 = {d.label: d for d in train_collective_schedule(
        cfg, mesh, 8, 16, microbatches=2) if d.origin == "explicit"}
    assert set(s1) == set(s2)
    for label in ("xent/stats", "xent/hidden-cotangent"):  # activations
        assert s2[label].count == 2 * s1[label].count, label
        assert s2[label].dv_bytes == pytest.approx(
            s1[label].dv_bytes / 2), label
    w = "xent/unembed-grad"                                # weight grad
    assert s2[w].count == 2 * s1[w].count
    assert s2[w].dv_bytes == pytest.approx(s1[w].dv_bytes)


def test_train_contracts_pass_and_drift_is_caught():
    """The tentpole assertion, on a real 8-virtual-device mesh: the
    traced train step (dense + MoE) matches the declared schedule
    exactly, and a deliberately mis-declared psum (one count off) is
    flagged with the declared labels in the failure report."""
    script = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n"
        "from repro.analysis.contracts import train_contract_checks\n"
        "from repro.parallel.collective_planner import "
        "train_collective_schedule\n"
        "checks = train_contract_checks()\n"
        "assert checks, 'no train checks ran'\n"
        "bad = [c.describe() for c in checks if not c.ok]\n"
        "assert not bad, '\\n'.join(bad)\n"
        "names = [c.name for c in checks]\n"
        "assert any('moe-no-all-to-all' in n for n in names), names\n"
        "assert any('statically-bounded' in n for n in names), names\n"
        "assert any('qwen3-moe-30b-a3b' in n for n in names), names\n"
        "# inject drift: drop one xent/stats occurrence from the declaration\n"
        "def drifted(cfg, mesh, batch, seq, **kw):\n"
        "    out = []\n"
        "    for d in train_collective_schedule(cfg, mesh, batch, seq, **kw):\n"
        "        if d.label == 'xent/stats':\n"
        "            d = type(d)(d.label, d.col_type, d.dv_bytes,\n"
        "                        d.participants, d.count - 1, d.origin)\n"
        "        out.append(d)\n"
        "    return out\n"
        "checks = train_contract_checks(schedule_fn=drifted)\n"
        "fails = [c for c in checks if not c.ok]\n"
        "assert fails, 'mis-declared psum not caught'\n"
        "# the dropped count fails exactly; the bucket's wire may follow\n"
        "cnt = [c for c in fails if c.kind == 'collective_count']\n"
        "assert cnt, fails\n"
        "assert {c.kind for c in fails} <= "
        "{'collective_count', 'collective_wire_bytes'}, fails\n"
        "msg = cnt[0].describe()\n"
        "assert 'MISMATCH' in msg\n"
        "assert 'xent/stats' in cnt[0].detail['declared_labels']\n"
        "assert 'train_collective_schedule' in cnt[0].detail['note']\n"
        "print('TRAIN_CONTRACTS_OK', len(checks))\n")
    r = _run_sub(script)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "TRAIN_CONTRACTS_OK" in r.stdout


def test_cli_train_arm_exits_nonzero_on_drift():
    """`python -m repro.analysis --contracts=train` is the CI gate: it
    must exit 0 on the honest declaration and nonzero when the declared
    schedule drifts from the implementation."""
    script = (
        "import sys\n"
        "import repro.parallel.collective_planner as cp\n"
        "real = cp.train_collective_schedule\n"
        "def drifted(cfg, mesh, batch, seq, **kw):\n"
        "    out = []\n"
        "    for d in real(cfg, mesh, batch, seq, **kw):\n"
        "        if d.label == 'xent/stats':\n"
        "            d = type(d)(d.label, d.col_type, d.dv_bytes,\n"
        "                        d.participants, d.count - 1, d.origin)\n"
        "        out.append(d)\n"
        "    return out\n"
        "cp.train_collective_schedule = drifted\n"
        "from repro.analysis.__main__ import main\n"
        "rc = main(['--contracts=train', '--json', 'drift.json'])\n"
        "assert rc != 0, 'CLI returned 0 on a drifted schedule'\n"
        "import json\n"
        "rep = json.load(open('drift.json'))\n"
        "assert not rep['ok'] and not rep['contracts']['ok']\n"
        "assert rep['contracts']['arms'] == ['train']\n"
        "import os; os.unlink('drift.json')\n"
        "print('CLI_DRIFT_NONZERO_OK')\n")
    r = _run_sub(script)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "CLI_DRIFT_NONZERO_OK" in r.stdout
    # the human-readable mismatch report went to stderr
    assert "MISMATCH" in r.stderr


# --------------------------------------------------------- reconciler


def _stats(hlo: str):
    from repro.analysis import parse_collectives
    return parse_collectives(hlo)


AR_HLO = """
HloModule m
ENTRY %main (p0: f32[256]) -> f32[256] {
  %ar = f32[256] all-reduce(%p0), replica_groups={{0,1}}, to_apply=%add
  ROOT %out = f32[256] copy(%ar)
}
"""


def test_reconcile_match_uses_hlo_number():
    from repro.analysis import reconcile
    stats = _stats(AR_HLO)
    hlo_wire = stats.by_type["all-reduce"][2]
    rep = reconcile({"all-reduce": hlo_wire * 1.1}, stats, tol=0.25)
    assert rep.clean
    t = rep.per_type["all-reduce"]
    assert t.status == "match"
    assert t.reconciled_wire == pytest.approx(hlo_wire)
    assert rep.total_reconciled_wire == pytest.approx(hlo_wire)


def test_reconcile_mismatch_charges_larger_side():
    from repro.analysis import reconcile
    stats = _stats(AR_HLO)
    hlo_wire = stats.by_type["all-reduce"][2]
    rep = reconcile({"all-reduce": hlo_wire * 3.0}, stats, tol=0.25)
    assert not rep.clean
    t = rep.per_type["all-reduce"]
    assert t.status == "mismatch"
    assert t.reconciled_wire == pytest.approx(hlo_wire * 3.0)
    assert rep.findings[0]["kind"] == "reconcile-mismatch"
    assert "larger side" in rep.findings[0]["detail"]


def test_reconcile_hlo_only_and_expected_only():
    from repro.analysis import reconcile
    stats = _stats(AR_HLO)
    rep = reconcile({"all-gather": 512.0}, stats)
    assert {t.status for t in rep.per_type.values()} == \
        {"hlo-only", "expected-only"}
    kinds = {f["kind"] for f in rep.findings}
    assert kinds == {"reconcile-hlo-only", "reconcile-expected-only"}
    # never undercharge: both sides' volumes survive into the total
    assert rep.total_reconciled_wire == pytest.approx(
        512.0 + stats.by_type["all-reduce"][2])


def test_reconcile_zero_vs_zero_is_silent_match():
    """P=1 declarations produce 0 expected wire; an absent HLO op is 0
    too — that carries no signal and must not produce a finding."""
    from repro.analysis import reconcile
    from repro.analysis.hlo import CollectiveStats
    rep = reconcile({"collective-permute": 0.0}, CollectiveStats())
    assert rep.clean
    assert rep.per_type["collective-permute"].status == "match"


def test_reconcile_loop_trip_scales_while_body_collectives():
    from repro.analysis import reconcile
    hlo = """
HloModule m
%body (a: f32[64]) -> f32[64] {
  %ar = f32[64] all-reduce(%a), replica_groups={{0,1}}, to_apply=%add
  ROOT %r = f32[64] add(%ar, %ar)
}
ENTRY %main (p0: f32[64]) -> f32[64] {
  ROOT %w = f32[64] while(%p0), condition=%cond, body=%body
}
"""
    stats = _stats(hlo)
    per_trip = stats.by_type["all-reduce"][3]
    assert per_trip > 0.0
    rep = reconcile({"all-reduce": per_trip * 4}, stats, loop_trip=4)
    assert rep.clean
    assert rep.per_type["all-reduce"].hlo_wire == pytest.approx(per_trip * 4)


def test_reconcile_cell_adds_gspmd_schedule_to_trace():
    """Expected = jaxpr trace (explicit) + declared gspmd entries; the
    explicit entries must NOT be double-charged from the schedule."""
    from repro.analysis import reconcile_cell
    from repro.analysis.hlo import _wire_factor
    from repro.analysis.jaxpr import TraceCounts
    from repro.parallel.collective_planner import DeclaredCollective

    trace = TraceCounts()
    trace.add_collective("AllReduce", 2, 1.0, 1000.0, 1000.0)
    sched = [
        DeclaredCollective("grads/w", "AllReduce", 500.0, 2, 1,
                           origin="gspmd"),
        # explicit entries are already in the trace -> must be ignored
        DeclaredCollective("xent/stats", "AllReduce", 999.0, 2, 1,
                           origin="explicit"),
    ]
    from repro.analysis.hlo import CollectiveStats
    stats = CollectiveStats()
    stats.by_type["all-reduce"] = [1, 1500.0,
                                   _wire_factor("all-reduce", 2) * 1500.0,
                                   0.0]
    rep = reconcile_cell(trace, stats, schedule=sched)
    assert rep.clean, rep.findings
    t = rep.per_type["all-reduce"]
    assert t.expected_wire == pytest.approx(
        _wire_factor("all-reduce", 2) * 1500.0)
    assert t.status == "match"


# ----------------------------------------------------- golden fixture


def _load_fixture():
    with gzip.open(os.path.join(FIXDIR, "train_step_2x2.hlo.txt.gz"),
                   "rt") as fh:
        hlo = fh.read()
    with open(os.path.join(FIXDIR, "train_step_2x2.json")) as fh:
        side = json.load(fh)
    return hlo, side


def test_golden_fixture_reconciles():
    """The checked-in compiled HLO of a REAL 2x2-mesh glm4-9b train step
    must reconcile against its recorded jaxpr trace + declared schedule:
    the dominant all-reduce volume agrees within tolerance and nothing
    the declaration promises goes missing.  Pins the whole
    walker -> schedule -> HLO-parse -> reconciler chain without
    compiling anything in CI."""
    from repro.analysis import parse_collectives, reconcile_cell
    from repro.analysis.jaxpr import TraceCounts
    from repro.parallel.collective_planner import DeclaredCollective

    hlo, side = _load_fixture()
    stats = parse_collectives(hlo)
    assert stats.by_type.get("all-reduce", [0])[0] > 0, \
        "fixture HLO parse found no all-reduces"

    trace = TraceCounts(flops=side["jaxpr_trace"]["flops"])
    for c in side["jaxpr_trace"]["collectives"]:
        trace.add_collective(c["type"], c["participants"], c["count"],
                             c["dv_bytes"], c["shard_bytes"])
    sched = [DeclaredCollective(d["label"], d["type"], d["dv_bytes"],
                                d["participants"], d["count"], d["origin"])
             for d in side["schedule"]]

    rep = reconcile_cell(trace, stats, schedule=sched,
                         loop_trip=side["n_layers"])
    ar = rep.per_type["all-reduce"]
    assert ar.status == "match", rep.to_dict()
    assert ar.rel_err <= rep.tolerance
    # disagreements may only be GSPMD extras the declaration cannot see,
    # never a mismatch on something both sides claim to know
    kinds = {f["kind"] for f in rep.findings}
    assert "reconcile-mismatch" not in kinds, rep.describe_findings()
    assert "reconcile-expected-only" not in kinds, rep.describe_findings()
    assert rep.total_reconciled_wire >= rep.total_hlo_wire


def test_golden_fixture_trace_matches_declaration():
    """The sidecar's recorded jaxpr buckets equal the declared explicit
    schedule aggregated the same way — the train contract, replayed from
    the frozen artifact (catches schedule edits that forget the
    fixture)."""
    from repro.configs.registry import get_smoke_config
    from repro.parallel.collective_planner import train_collective_schedule

    _, side = _load_fixture()
    cfg = get_smoke_config(side["arch"])
    if side.get("softmax_strategy"):
        cfg = cfg.with_(softmax_strategy=side["softmax_strategy"])
    mesh = _FakeMesh(**side["mesh"])
    sched = train_collective_schedule(cfg, mesh, side["batch"], side["seq"])

    declared = {}
    for d in sched:
        if d.origin != "explicit" or d.participants <= 1:
            continue
        agg = declared.setdefault((d.col_type, d.participants),
                                  {"count": 0.0, "dv": 0.0})
        agg["count"] += d.count
        agg["dv"] += d.dv_bytes * d.count
    traced = {(c["type"], c["participants"]): c
              for c in side["jaxpr_trace"]["collectives"]
              if c["participants"] > 1}
    assert set(declared) == set(traced)
    for key, agg in declared.items():
        assert traced[key]["count"] == pytest.approx(agg["count"]), key
        assert traced[key]["dv_bytes"] == pytest.approx(agg["dv"]), key
