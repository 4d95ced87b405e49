"""One calibration pass: reuse-or-sweep, fit, gate, persist, summarize.

``calibrate_once`` is the single orchestration point behind both the
``python -m repro.calibrate`` CLI and the benchmark gates — it owns the
reuse semantics the acceptance criteria pin down:

* a persisted ``calibrated_noc.json`` whose provenance (backend, mesh,
  jax version) matches the requested run is **reused verbatim** — the
  summary reports ``reused: true`` and ``fits_solved: 0``, no sweep
  runs, and the file is not rewritten (so re-running is bit-identical);
* otherwise the sweep runs, the fit solves once (``fits_solved: 1``),
  and the result is persisted only when it is non-degenerate and finite
  (``save_calibration`` refuses NaN) — a degenerate fit warns and
  leaves any existing file alone;
* the **error gate** compares the fitted model's predictions against
  the very sweep it was fitted on: ``median |rel err| <= gate_median``.
  A calibration that cannot reproduce its own measurements is worse
  than the preset it would replace.
"""
from __future__ import annotations

import time
from collections import Counter
from typing import Callable, Dict, Optional

from repro.core.hardware import NoCParams

from .fitter import fit_noc_params
from .harness import SweepConfig, _warn_once, run_sweep
from .persist import (calibration_from_fit, calibration_path,
                      load_calibration, save_calibration)

__all__ = ["calibrate_once"]


def _params_json(p: NoCParams) -> Dict:
    return {"mesh": list(p.mesh), "channel_bandwidth": p.channel_bandwidth,
            "t_router": p.t_router, "t_enq": p.t_enq}


def _points_by_type(points) -> Dict[str, int]:
    return dict(sorted(Counter(p.col_type for p in points).items()))


def calibrate_once(
    measure_fn: Callable[[str, int, int], float],
    reference: NoCParams,
    participants,
    *,
    backend: str,
    jax_version: str,
    store: Optional[str] = None,
    force: bool = False,
    config: Optional[SweepConfig] = None,
    gate_median: float = 0.6,
    now: Callable[[], float] = time.time,
) -> Dict:
    """Run (or reuse) one calibration; return a flat summary dict.

    ``reference`` must carry the mesh the sweep actually runs over —
    hop distances are computed on it (``_replace_mesh`` in the harness
    re-meshes a preset NoC).  ``gate_median`` bounds the median
    |relative error| of the fitted model on its own sweep.
    """
    path = calibration_path(store)
    expect = {"backend": backend, "mesh": list(reference.mesh),
              "jax_version": jax_version}

    if not force:
        cached = load_calibration(path, expect=expect)
        if cached is not None:
            return {
                "reused": True,
                "fits_solved": 0,
                "path": str(path),
                "backend": backend,
                "n_points": len(cached.points),
                "points_by_type": _points_by_type(cached.points),
                "n_dropped": 0,
                "degenerate": bool(cached.provenance.get("degenerate",
                                                         False)),
                "max_rel_err": cached.max_rel_err,
                "median_rel_err": cached.median_rel_err,
                "gate_median": gate_median,
                "gate_ok": cached.median_rel_err <= gate_median,
                "persisted": True,
                "params": _params_json(cached.params),
            }

    sweep = run_sweep(measure_fn, participants, config=config)
    fit = fit_noc_params(sweep.points, reference)

    persisted_path = None
    if fit.degenerate:
        _warn_once(("calib-degenerate", backend),
                   f"calibration sweep on backend {backend!r} left "
                   f"{len(sweep.points)} usable point(s) "
                   f"(dropped: {dict(sweep.dropped)}) — fit is degenerate, "
                   f"keeping preset NoC params and persisting nothing")
    else:
        cal = calibration_from_fit(
            fit, backend=backend, jax_version=jax_version, now=now,
            extra={"dropped": dict(sweep.dropped),
                   "sweep": {"min_bytes": (config or SweepConfig()).min_bytes,
                             "max_bytes": (config or SweepConfig()).max_bytes,
                             "n_sizes": (config or SweepConfig()).n_sizes,
                             "iters": (config or SweepConfig()).iters}})
        persisted_path = save_calibration(cal, path)

    return {
        "reused": False,
        "fits_solved": 1,
        "path": str(persisted_path) if persisted_path else None,
        "backend": backend,
        "n_points": fit.n_points,
        "points_by_type": _points_by_type(sweep.points),
        "n_dropped": sweep.n_dropped,
        "dropped": dict(sweep.dropped),
        "degenerate": fit.degenerate,
        "max_rel_err": fit.max_rel_err,
        "median_rel_err": fit.median_rel_err,
        "gate_median": gate_median,
        "gate_ok": (not fit.degenerate
                    and fit.median_rel_err <= gate_median),
        "persisted": persisted_path is not None,
        "params": _params_json(fit.params),
    }
