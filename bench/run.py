"""Run one benchmark cell once on the chip this process finds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``) in ``BENCHMARK.json``; each
metric is read by ``bench/metrics/<metric>.py`` and each cell's
correctness limits are in ``bench/limits/<workload>.json``.  The run:

1. fails (exit 3, no result) unless JAX finds a TPU with the chips the
   cell asks for;
2. builds the engine through ``repro.launch.serve.build`` with weights
   drawn on the device from the seed, and requests from the mix;
3. warms up the cell's shapes on that same engine: one full prefill,
   decode steps, and one refill of a single slot into the live cache;
4. serves the mix for ``--seconds`` of wall clock (the queue never drains
   in the window: a run in which it does fails), under the profiler with
   ``--trace 1``;
5. frees the engine's state, compares a sample of what the window served
   with the float32 reference, and prints the result as the last line of
   standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE = ROOT / ".bench_cache"


@dataclass
class Run:
    """What a metric reader sees of one run."""
    conf: dict
    mix: object
    peak: dict
    setup_s: float
    t_open: float
    t_close: float
    served: list
    launches: list
    counters: Dict[str, float]
    trace: Optional[object] = None

    def with_device_times(self, kind: str, whole_step: bool = False):
        """(launch, seconds) of the traced launches of ``kind``: the device
        time of the step's program, or with ``whole_step`` the time from
        its start to the next step's start on the device."""
        if self.trace is None:
            return []
        times = (self.trace.launch_step_s if whole_step
                 else self.trace.launch_device_s)
        assert len(times) == len(self.launches), "trace and launches differ"
        return [(l, t) for l, t in zip(self.launches, times) if l.kind == kind]

    def device_times(self, kind: str) -> List[float]:
        return [t for _, t in self.with_device_times(kind)]


def load_metric(name: str, directory: Path = BENCH / "metrics"):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``."""
    path = directory / f"{name}.py"
    mod_name = "bench.metrics." + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name}: {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod.read


def _point_caches() -> None:
    """Compile cache, plan store and the TPU runtime's logs at fixed paths
    inside the checkout (before JAX starts)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ["REPRO_PLAN_CACHE"] = str(CACHE / "plans")
    os.environ["TPU_LOG_DIR"] = str(CACHE / "tpu_logs")
    (CACHE / "tpu_logs").mkdir(parents=True, exist_ok=True)


def _device_info(devices, used: int) -> dict:
    d = devices[0]
    peak = max((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dev in devices[:used])
    return {"platform": d.platform, "kind": d.device_kind, "count": used,
            "memory_peak_bytes": int(peak)}


class Cell:
    """One cell of the spec, loaded from its files: the configuration, the
    traffic mix, the correctness limits and the chip's peaks."""

    def __init__(self, spec: dict, workload: str, *, bench_dir: Path = BENCH,
                 require_tpu: bool = True):
        import jax

        from bench import traffic

        self.spec, self.name, self.bench_dir = spec, workload, bench_dir
        self.cell = next(w for w in spec["workloads"] if w["name"] == workload)
        self.devices = jax.devices()
        d = self.devices[0]
        if require_tpu and (d.platform != "tpu"
                            or len(self.devices) < self.cell["chips"]):
            raise SystemExit(
                f"bench: cell {workload} needs {self.cell['chips']} TPU "
                f"chip(s); JAX found {len(self.devices)} {d.platform} device(s)")
        config = next(c for c in spec["configs"]
                      if c["name"] == self.cell["config"])
        self.conf = json.loads((ROOT / config["file"]).read_text())
        self.mix = traffic.load_mix(self.cell["traffic"], bench_dir / "traffic")
        self.limits = json.loads(
            (bench_dir / "limits" / f"{workload}.json").read_text())
        self.peak = json.loads(
            (bench_dir / "peaks.json").read_text()).get(d.device_kind)
        if require_tpu and self.peak is None:
            raise SystemExit(f"bench: no peaks for device kind {d.device_kind!r}")

    def start(self, seed: int, engine_hook=None):
        """Weights from the seed, the engine through the user's entry point,
        and the warm-up of every shape the window uses: a full prefill,
        decode, then one slot refilled into the live cache (prefill,
        splice, decode).  Returns (params, engine, recorder)."""
        import numpy as np

        from bench import traffic, weights
        from bench.serving import Recorder, Served
        from repro.launch import serve
        from repro.models.config import ModelConfig
        from repro.models.model import Model

        cfg = ModelConfig(**self.conf["program"])
        params = weights.draw(Model(cfg).abstract_params(), seed)
        mix = self.mix
        eng, _ = serve.build(cfg, requests=0, batch=mix.batch,
                             prompt_len=mix.prompt_len,
                             cache_len=mix.cache_len, max_new=mix.max_new,
                             seed=seed, params=params)
        if engine_hook is not None:
            engine_hook(eng)
        rec = Recorder(eng, mix.prompt_len)
        rng = traffic.rng_for(seed, 3)
        rec.serve([Served(rid=-1 - i, max_new=2,
                          prompt=rng.integers(0, cfg.vocab_size,
                                              mix.prompt_len, dtype=np.int32))
                   for i in range(mix.batch + 1)])
        return params, eng, rec

    def window(self, rec, seed: int, seconds: float, trace: bool) -> Run:
        """Serve the mix for ``seconds``; with ``trace``, under the profiler."""
        import jax

        from bench import trace_reduce, traffic
        from bench.serving import Served

        mix, eng = self.mix, rec.eng
        served = [Served(rid=i, prompt=r["prompt"], max_new=r["max_new"],
                         in_flight=r["in_flight"])
                  for i, r in enumerate(traffic.generate(
                      mix, seed, self.conf["program"]["vocab_size"]))]
        trace_dir = CACHE / "trace" / self.name
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        rec.spans = trace
        stats0 = dict(eng.stats)
        # everything set-up made is long-lived: collect it once and keep it
        # out of later collections, so no full collection of set-up's heap
        # lands inside the window
        gc.collect()
        gc.freeze()
        t_open = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            closed = rec.serve(served, close=t_open + seconds)
        gc.unfreeze()
        rec.spans = False
        reduction = None
        if trace:
            jax.profiler.stop_trace()
            found = sorted(trace_dir.rglob("*.xplane.pb"))
            stems = {trace_reduce.module_stem(getattr(f, "__name__", ""))
                     for f in (rec.prefill_fn, rec.decode_fn)}
            reduction = found and trace_reduce.reduce(
                trace_reduce.load(str(found[-1])), step_stems=stems)
            if not reduction:
                raise SystemExit(f"bench: the profiler left no trace of the "
                                 f"window and a device under {trace_dir}")
        if not closed:
            raise SystemExit(f"bench: the queue of {mix.requests} requests "
                             f"drained inside the {seconds} s window")
        counters = {k: eng.stats.get(k, 0) - stats0.get(k, 0) for k in
                    ("prefill_calls", "nonfinite_tokens", "timeouts",
                     "rows_admitted", "rows_prefilled", "compiles")}
        return Run(conf=self.conf, mix=mix, peak=self.peak or {},
                   setup_s=t_open - T_START, t_open=t_open,
                   t_close=t_open + seconds, served=served,
                   launches=list(rec.launches), counters=counters,
                   trace=reduction)

    def checked_rows(self, rec, served: list, seed: int) -> List[dict]:
        """The sample of finished requests the reference replays: each
        prompt with every token the engine fed after it."""
        import numpy as np

        from bench import check

        picked = check.sample([s for s in served if s.done], seed,
                              self.limits["sample_requests"])
        first = rec.first_tokens(picked)
        return [{"prompt": s.prompt,
                 "fed": np.asarray([first[s.rid]] + [int(t) for t in s.tokens],
                                   np.int32)} for s in picked]

    def gaps(self, params, rows, *, control: bool = False):
        from bench import check

        return check.gaps(self.conf, params, rows, self.mix.prompt_len,
                          self.mix.max_new, control=control,
                          rows_per_call=self.limits["rows_per_call"])

    def judge(self, gaps, counters) -> tuple:
        """(correct, {number: [reading, limit]}).  Each gap must be at most
        its limit, no token may come from a non-finite logits row, and
        enough tokens must have been compared."""
        lim = self.limits
        checks = {
            "mean_logit_gap": [float(gaps.mean()) if len(gaps) else None,
                               lim["mean_logit_gap"]],
            "nonfinite_tokens": [counters["nonfinite_tokens"], 0],
            "checked_tokens": [int(len(gaps)), lim["min_checked_tokens"]],
        }
        if lim.get("served_logit_gap") is not None:
            checks["served_logit_gap"] = [
                float(gaps.max()) if len(gaps) else None,
                lim["served_logit_gap"]]
        correct = (len(gaps) >= lim["min_checked_tokens"]
                   and counters["nonfinite_tokens"] == 0
                   and all(v <= limit for k, (v, limit) in checks.items()
                           if k.endswith("_gap")))
        return bool(correct), checks

    def metrics(self, run: Run, trace: bool) -> dict:
        """The cell's end-to-end metrics, or with ``trace`` its per-layer
        ones: those that list no ``workloads``, or list this cell."""
        out = {}
        for m in self.spec["per_layer" if trace else "end_to_end"]:
            if self.name not in m.get("workloads", [self.name]):
                continue
            value = load_metric(m["name"], self.bench_dir / "metrics")(run)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out


def execute(spec: dict, workload: str, seed: int, seconds: float,
            trace: bool, *, require_tpu: bool = True,
            bench_dir: Path = BENCH, engine_hook=None) -> dict:
    """One run of ``workload``; returns the result line's object.
    ``engine_hook(eng)`` (tests only) may break the engine under test."""
    cell = Cell(spec, workload, bench_dir=bench_dir, require_tpu=require_tpu)
    params, eng, rec = cell.start(seed, engine_hook)
    run = cell.window(rec, seed, seconds, trace)
    device = _device_info(cell.devices, cell.cell["chips"])
    rows = cell.checked_rows(rec, run.served, seed)
    del rec, eng             # the engine's state goes; the weights are ours
    gc.collect()
    correct, checks = cell.judge(cell.gaps(params, rows), run.counters)
    attempted = sum(1 for s in run.served
                    if s.admitted is not None and s.admitted < run.t_close)
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": int(run.counters["timeouts"]),
           "metrics": cell.metrics(run, trace), "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.top_ops],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_by_host]}
    out["checks"] = {k: {"value": v, "limit": limit}
                     for k, (v, limit) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if not any(w["name"] == args.workload for w in spec["workloads"]):
        print(f"bench: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    _point_caches()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        out = execute(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
