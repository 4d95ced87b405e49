"""Readings that a cell's correctness limit is set from, on the chip, in one
process: for each seed, a short window at the cell's own load, then the
widest gap of the served tokens and, with ``--control``, of the control
(the reference with int8 products) at the same positions.

    python bench/readings.py --workload <name> --seeds 1,2,3 --seconds 10 --control 3

Prints one JSON line per seed (``--control N``: the control too, on the
first N seeds) and a summary line: the largest program readings (the
lower ends of the limits) and the smallest control readings (their
upper ends).  Both go through the cell's own comparison
(``Cell.judge`` with ``bench/limits/<workload>.json``): the run exits 1
where a program reading is not correct or a control reading is.  The
benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)
    bench_run._point_caches()
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(bench_run.CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
    cell = bench_run.Cell(spec, args.workload)
    prog, ctrl = [], []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        params, eng, rec = cell.start(seed)
        run = cell.window(rec, seed, args.seconds, False)
        rows = cell.checked_rows(rec, run.served, seed)
        counters = run.counters
        del rec, eng, run
        gc.collect()
        t1 = time.perf_counter()
        g = cell.gaps(params, rows)
        gap = float(g.max())
        t2 = time.perf_counter()
        line = {"seed": seed, "program": gap, "program_mean": float(g.mean()),
                "program_flips": int((g > 0).sum()),
                "program_correct": cell.judge(g, counters)[0],
                "tokens": int(sum(len(r["fed"]) for r in rows)),
                "rows": len(rows), "reference_s": t2 - t1,
                "serve_s": t1 - t0}
        prog.append((gap, line["program_mean"], line["program_correct"]))
        if i < args.control:
            cg = cell.gaps(params, rows, control=True)
            c = float(cg.max())
            line["control"] = c
            line["control_mean"] = float(cg.mean())
            line["control_flips"] = int((cg > 0).sum())
            line["control_correct"] = cell.judge(cg, counters)[0]
            line["control_s"] = time.perf_counter() - t2
            ctrl.append((c, line["control_mean"], line["control_correct"]))
        del params
        gc.collect()
        print(json.dumps(line), flush=True)
    sound = all(ok for _, _, ok in prog) and not any(ok for _, _, ok in ctrl)
    print(json.dumps({
        "workload": args.workload,
        "program_max": [max(g for g, _, _ in prog), max(m for _, m, _ in prog)],
        "control_min": ([min(g for g, _, _ in ctrl), min(m for _, m, _ in ctrl)]
                        if ctrl else None),
        "program_all_correct": all(ok for _, _, ok in prog),
        "control_any_correct": any(ok for _, _, ok in ctrl)}))
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
