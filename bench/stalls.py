"""Where the host stalls in a cell's window: several windows served by one
engine in one process, the main thread sampled from a second one.

    python bench/stalls.py --workload <name> --seed <n> --seconds 30 --windows 5 --after 1.0

A sampler thread wakes every ``--period`` seconds and records the main
thread's stack and how late it woke: a late wake means the main thread
held the interpreter (a garbage collection, a call that keeps the lock)
or the process got no CPU; an on-time wake that finds the main thread in
a call says the host waited there.  Prints one JSON line per window:
``tok_s`` and ``ttft_p95_ms`` as the benchmark reads them, the longest
gap between two consecutive step launches, the Python garbage
collections in the window (count, longest, total), and for each of the
three longest gaps over ``--after`` seconds the sampler's latest wake in
it and the stacks it found there most often.  ``--after`` has to exceed
the longest gap of a sound step, such as a prefill waited for in full.
The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import threading
import time
import traceback
from collections import Counter
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as bench_run  # noqa: E402


class Sampler(threading.Thread):
    """Samples the stack of thread ``target`` every ``period`` seconds."""

    def __init__(self, target: int, period: float):
        super().__init__(daemon=True)
        self.target, self.period = target, period
        self.samples = []               # (time, lateness, stack)
        self.done = threading.Event()

    def run(self) -> None:
        due = time.perf_counter() + self.period
        while not self.done.wait(max(0.0, due - time.perf_counter())):
            now = time.perf_counter()
            frames = traceback.extract_stack(
                sys._current_frames().get(self.target))
            ours = [f for f in frames if "/repro/" in f.filename
                    or "/bench/" in f.filename]
            stack = tuple(f"{f.filename.rsplit('/', 2)[-1]}:{f.lineno} {f.name}"
                          for f in ours[-3:] + frames[-2:])
            self.samples.append((now, now - due, stack))
            due = max(due + self.period, now)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", type=int, default=5)
    ap.add_argument("--after", type=float, default=1.0)
    ap.add_argument("--period", type=float, default=0.02)
    args = ap.parse_args(argv)
    bench_run._point_caches()
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(bench_run.CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
    cell = bench_run.Cell(spec, args.workload)
    _, eng, rec = cell.start(args.seed)

    launches = []

    def logged(fn, kind):
        def call(*a):
            launches.append((time.perf_counter(), kind))
            return fn(*a)
        return call
    eng.prefill_step = logged(eng.prefill_step, "prefill")
    eng.decode_step = logged(eng.decode_step, "decode")

    collections, started = [], {}

    def on_gc(phase, info):
        if phase == "start":
            started["t"] = time.perf_counter()
        else:
            collections.append((started.pop("t", time.perf_counter()),
                                time.perf_counter(), info["generation"]))
    gc.callbacks.append(on_gc)
    sampler = Sampler(threading.main_thread().ident, args.period)
    sampler.start()

    tok_s = bench_run.load_metric("tok_s")
    ttft = bench_run.load_metric("ttft_p95_ms")
    for k in range(args.windows):
        launches.clear()
        collections.clear()
        sampler.samples.clear()
        run = cell.window(rec, args.seed + k, args.seconds, False)
        inside = [(t, kind) for t, kind in launches
                  if run.t_open <= t < run.t_close]
        gaps = sorted(((b[0] - a[0], a[0], b[0], b[1])
                       for a, b in zip(inside, inside[1:])), reverse=True)
        pauses = [(b - a, g) for a, b, g in collections
                  if run.t_open <= a < run.t_close]
        stalls = []
        for gap, a, b, kind in gaps[:3]:
            if gap <= args.after:
                break
            seen = [(late, stack) for t, late, stack in sampler.samples
                    if a <= t < b]
            stalls.append({
                "gap_s": gap, "opened_s": a - run.t_open, "ends_at": kind,
                "latest_wake_s": max((late for late, _ in seen), default=None),
                "stacks": [[n, list(st)] for st, n in
                           Counter(st for _, st in seen).most_common(3)]})
        print(json.dumps({
            "window": k, "tok_s": tok_s(run), "ttft_p95_ms": ttft(run),
            "launches": len(inside),
            "longest_gap_s": gaps[0][0] if gaps else 0.0,
            "gaps_over_after": sum(1 for g in gaps if g[0] > args.after),
            "latest_wake_s": max((late for t, late, _ in sampler.samples
                                  if run.t_open <= t < run.t_close),
                                 default=None),
            "gc": {"count": len(pauses),
                   "longest_s": max((p for p, _ in pauses), default=0.0),
                   "total_s": sum(p for p, _ in pauses),
                   "full": sum(1 for _, g in pauses if g == 2)},
            "stalls": stalls}), flush=True)
    sampler.done.set()
    sampler.join()
    return 0


if __name__ == "__main__":
    sys.exit(main())
