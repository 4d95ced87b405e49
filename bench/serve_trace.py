"""From a profiler trace to what the serve engine's own spans and the
model's named scopes say (the engine's instrumentation: ``serve.*`` host
spans, step programs ``jit_prefill_step`` / ``jit_decode_step``, layer
kinds as ``jax.named_scope`` in each operation's ``tf_op``).

- ``decode_host_s``: for each two ``jit_decode_step`` executions with no
  ``jit_prefill_step`` between, the device's idle from the end of the
  first step's last program (the decode and its argmax and finite check)
  to the start of the second: what the host loop (tokens to the host,
  emit, the next launch) costs the device each step.
- ``step_s`` / ``scope_s``: device time of each step program's executions
  and, inside them, operations' self time by innermost layer-kind scope
  (``unscoped`` where the name stack holds none).
- ``idle_by_span``: idle gaps on the device labelled with the innermost
  ``serve.*`` or ``bench.*`` span open at their middle.

A program without that instrumentation reduces to nothing here.
``bench/trace_reduce.py`` keeps reducing the benchmark's own spans.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from . import trace_reduce, xspace

SCOPES = ("attention", "ssd", "mlp", "moe", "norm", "embed", "lm_head")
MIXERS = ("attention", "ssd")
STEPS = {"prefill": "jit_prefill_step", "decode": "jit_decode_step"}
WINDOW = trace_reduce.WINDOW
TRACES = Path(__file__).resolve().parents[1] / ".bench_cache" / "trace"


def scope_of(tf_op: Optional[str]) -> str:
    """The innermost layer-kind scope among a name stack's components
    (``jit(f)/while/body/attention/dot_general:`` -> ``attention``)."""
    if tf_op:
        for part in reversed(tf_op.rsplit(":", 1)[0].split("/")):
            if part in SCOPES:
                return part
    return "unscoped"


def _host(name: str) -> bool:
    return name.startswith(("serve.", "bench."))


def load(path: str) -> dict:
    """The host spans (name, start_ns, end_ns, args) and, per TPU, the
    program executions (name, start, end) and operations (short name,
    start, end, scope) of an xplane file (gzipped where it ends in .gz)."""
    planes = xspace.read(
        path,
        keep_plane=lambda p: (p.startswith("/host:")
                              or re.match(r"/device:TPU:\d+$", p) is not None),
        keep_line=lambda p, l: (p.startswith("/host:")
                                or l in ("XLA Modules", "XLA Ops")),
        event_stats=_host)
    host, devices = [], []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = plane.event_name(ev)
                    if _host(name):
                        host.append((name, ev[1], ev[2],
                                     plane.event_stats(ev)))
            continue
        dev = {"name": plane.name, "modules": [], "ops": []}
        for line in plane.lines:
            if line.name == "XLA Modules":
                dev["modules"] = [(plane.event_name(ev), ev[1], ev[2])
                                  for ev in line.events]
            else:
                ops = {mid: (trace_reduce._short(name),
                             scope_of(stats.get("tf_op")))
                       for mid, (name, stats) in plane.meta.items()}
                for ev in line.events:
                    name, scope = ops.get(ev[0], ("", "unscoped"))
                    dev["ops"].append((name, ev[1], ev[2], scope))
        devices.append(dev)
    return {"host": sorted(host, key=lambda e: e[1]), "devices": devices}


@dataclass
class ServeReduction:
    window_s: float
    decode_host_s: List[float]                  # per clean decode step
    step_s: Dict[str, float]                    # kind -> device seconds
    scope_s: Dict[str, Dict[str, float]]        # kind -> scope -> self s
    idle_by_span: List[Tuple[str, float]]       # label -> idle seconds

    def mixer_share(self, kind: str) -> Optional[float]:
        """Self time under ``attention`` or ``ssd`` over the device time of
        the ``kind`` step's executions, in %."""
        if not self.step_s.get(kind):
            return None
        mixers = sum(self.scope_s[kind].get(s, 0.0) for s in MIXERS)
        return 100.0 * mixers / self.step_s[kind]


def _idle_labels(gaps: List[Tuple[float, float]], spans) -> Dict[str, float]:
    """Idle seconds by the innermost span open at each gap's middle; spans
    of one thread nest, so a stack sweep finds it."""
    out: Dict[str, float] = {}
    spans = sorted(spans, key=lambda e: (e[1], -e[2]))
    stack: List[tuple] = []
    k = 0
    for a, b in sorted(gaps):
        t = (a + b) / 2
        while k < len(spans) and spans[k][1] <= t:
            while stack and stack[-1][2] <= spans[k][1]:
                stack.pop()
            stack.append(spans[k])
            k += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        label = f"host in {stack[-1][0]}" if stack else "host outside spans"
        out[label] = out.get(label, 0.0) + (b - a) / 1e9
    return out


def reduce(raw: dict) -> Optional[ServeReduction]:
    """None where the trace holds no window, no device or no ``serve.*``
    span (a program without the engine's instrumentation)."""
    wins = [e for e in raw["host"] if e[0] == WINDOW]
    if not wins or not raw["devices"]:
        return None
    _, lo, hi, _ = wins[-1]
    spans = [e for e in raw["host"] if e[0] != WINDOW and lo <= e[1] < hi]
    if not any(e[0].startswith("serve.") for e in spans):
        return None

    n_dev = len(raw["devices"])
    idle: Dict[str, float] = {}
    for dev in raw["devices"]:
        events = dev["ops"] or dev["modules"]
        busy = trace_reduce._union([(a, b) for _, a, b, *_ in events], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for label, s in _idle_labels(gaps, spans).items():
            idle[label] = idle.get(label, 0.0) + s / n_dev

    dev0 = raw["devices"][0]
    mods = sorted((m for m in dev0["modules"] if lo <= m[1] < hi),
                  key=lambda m: m[1])
    stems = [m[0].split("(", 1)[0] for m in mods]
    kind_of = {stem: kind for kind, stem in STEPS.items()}
    step_s = {kind: 0.0 for kind in STEPS}
    for m, stem in zip(mods, stems):
        if stem in kind_of:
            step_s[kind_of[stem]] += (m[2] - m[1]) / 1e9
    starts = [m[1] for m in mods]

    def owner(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0 or t >= mods[i][2]:
            return "outside programs"
        return kind_of.get(stems[i], "other")

    ops = [(scope, a, b) for _, a, b, scope in dev0["ops"] if lo <= a < hi]
    scope_s: Dict[str, Dict[str, float]] = {kind: {} for kind in STEPS}
    for key, ns in trace_reduce._self_times(ops, owner).items():
        kind, scope = key.split(": ", 1)
        if kind in scope_s:
            scope_s[kind][scope] = ns / 1e9

    # on the device's clock alone: the host's clock and the device's are
    # aligned only to a millisecond or so, which differs from trace to trace
    decode = [i for i, stem in enumerate(stems) if stem == STEPS["decode"]]
    host_s = []
    for i, j in zip(decode, decode[1:]):
        if STEPS["prefill"] in stems[i:j]:
            continue                        # a refill ran in between
        host_s.append((mods[j][1] - max(m[2] for m in mods[i:j])) / 1e9)
    return ServeReduction(
        window_s=(hi - lo) / 1e9, decode_host_s=host_s, step_s=step_s,
        scope_s=scope_s,
        idle_by_span=sorted(idle.items(), key=lambda kv: -kv[1]))


_memo: Dict[tuple, Optional[ServeReduction]] = {}


def for_run(run) -> Optional[ServeReduction]:
    """The reduction of the trace ``run`` was traced into.  A metric reader
    gets the run's reduced trace, not its file, so this takes the newest
    trace under the benchmark's trace directory and keeps it only where
    its window is the run's own."""
    if run.trace is None:
        return None
    found = sorted(TRACES.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        return None
    key = (str(found[-1]), found[-1].stat().st_mtime_ns)
    if key not in _memo:
        _memo.clear()
        _memo[key] = reduce(load(key[0]))
    r = _memo[key]
    if r is None or abs(r.window_s - run.trace.window_s) > 1e-6:
        return None
    return r
