"""Record the smoke-size trace that ``test_bench_serve_trace.py`` reads:
the smoke dense cell served through the benchmark's own path (warm-up,
then a 0.3 s window under the profiler) on one TPU.

    python tests/bench/record_trace.py

writes ``data/smoke_serve_spans.xplane.pb.gz`` and prints the run's
result line.
"""
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src"), str(HERE)]

import bench_smoke  # noqa: E402
from bench import run as bench_run  # noqa: E402

CELL = "dense-smoke.smoke"
OUT = HERE / "data" / "smoke_serve_spans.xplane.pb.gz"


def main() -> int:
    with tempfile.TemporaryDirectory() as d:
        spec = bench_smoke.write_spec(Path(d), confs=(bench_smoke.DENSE,))
        out = bench_run.execute(spec, CELL, seed=11, seconds=0.3, trace=True,
                                bench_dir=Path(d) / "bench")
    trace = sorted((bench_run.CACHE / "trace" / CELL).rglob("*.xplane.pb"))[-1]
    with open(trace, "rb") as src, gzip.open(OUT, "wb") as dst:
        shutil.copyfileobj(src, dst)
    print(json.dumps(out))
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
