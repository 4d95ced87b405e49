"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

On a TPU the device plane (``/device:TPU:<n>``) has a line ``XLA
Modules``, one event per program execution, named ``<module>(<hash>)``,
and a line ``XLA Ops``, one event per operation, nested (a ``while`` op
spans the operations of its body).  The host plane carries the
benchmark's spans, all on the device's clock.

- The window is the host span ``bench.window``.
- Busy time is the union of the intervals in which an operation ran on a
  device, clipped to the window and averaged over the devices.
- Each launch span (``bench.prefill``, ``bench.decode``) is matched with
  the execution of the program it launched: the engine's step programs
  are the modules named after its jitted step functions (``step_stems``),
  nothing else launches them, and a device runs programs in launch
  order, so the k-th step execution belongs to the k-th launch span.
  Where the counts differ, the trace cannot be read and :func:`reduce`
  raises.  A step's whole time runs from its execution's start to the
  next step execution's start (for the last, to its own end): its
  program, what the host launched after it (a refill's splice) and the
  idle time between.
- Idle gaps are labelled with what the host was doing at their middle:
  the innermost ``bench.*`` span then open or, outside the launch spans,
  the engine's host loop after the last launch's kind.
- Top operations are ranked by self time (an operation's time less that
  of the operations nested in it), each named by its program.
"""
from __future__ import annotations

import bisect
import gzip
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW = "bench.window"
LAUNCHES = ("bench.prefill", "bench.decode")


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    launch_kinds: List[str]                  # launch spans, in order
    launch_device_s: List[float]             # device time of each launch
    launch_step_s: List[float]               # its whole step's time
    idle_by_host: List[Tuple[str, float]]    # label -> idle seconds
    top_ops: List[Tuple[str, float]]         # op -> device self seconds


def module_stem(fn_name: str) -> str:
    """The module name XLA gives ``jax.jit`` of a function so named."""
    return "jit_" + re.sub(r"\W", "_", fn_name).rstrip("_")


def _short(op: str) -> str:
    """``%fusion.3 = bf16[16,8]{...} fusion(...)`` -> ``fusion.3 bf16[16,8]``."""
    lhs, _, rhs = op.partition(" = ")
    shape = rhs.split("{", 1)[0].split(" ", 1)[0] if rhs else ""
    return f"{lhs.lstrip('%')} {shape}".strip()


def load(path: str) -> dict:
    """The parts of an xplane file (gzipped where it ends in ``.gz``) that
    :func:`reduce` reads, as tuples (name, start_ns, end_ns)."""
    from jax._src.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [(e.name, e.start_ns,
                                       e.start_ns + e.duration_ns)
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    dev["ops"] = [(_short(e.name), e.start_ns,
                                   e.start_ns + e.duration_ns)
                                  for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"host": sorted(host, key=lambda e: e[1]), "devices": devices}


def _union(intervals: Iterable, lo: float, hi: float) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label(t: float, spans, launches) -> str:
    inner = None
    for name, a, b in spans:
        if a <= t < b and name != WINDOW and (inner is None or a >= inner[1]):
            inner = (name, a)
    if inner is not None:
        return f"host in {inner[0]}"
    prev = None
    for name, a, _ in launches:
        if a > t:
            break
        prev = name
    return f"host loop after {prev}" if prev else "host before first launch"


def _self_times(ops, owner) -> Dict[str, float]:
    """Self time by ``owner(start) + ': ' + name``; ops nest on one line."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []                  # [name, end, child_time, start]

    def close(ev):
        out[f"{owner(ev[3])}: {ev[0]}"] += ev[1] - ev[3] - ev[2]
    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] += b - a
        stack.append([name, b, 0.0, a])
    while stack:
        close(stack.pop())
    return out


def reduce(raw: dict, step_stems: Iterable[str] = ("jit__lambda",),
           top: int = 10) -> Optional[Reduction]:
    """Reduce a loaded trace; None where it holds no window or no device.
    Raises ValueError where the step executions and launches differ."""
    wins = [e for e in raw["host"] if e[0] == WINDOW]
    if not wins or not raw["devices"]:
        return None
    _, lo, hi = wins[-1]
    launches = [e for e in raw["host"] if e[0] in LAUNCHES and lo <= e[1] < hi]
    spans = [e for e in raw["host"] if lo <= e[1] < hi]
    stems = set(step_stems)

    busy_total, idle = 0.0, defaultdict(float)
    for dev in raw["devices"]:
        events = dev["ops"] or dev["modules"]
        busy = _union([(a, b) for _, a, b in events], lo, hi)
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle[_label((a + b) / 2, spans, launches)] += (b - a) / 1e9
    n_dev = len(raw["devices"])

    # step executions on the first device, matched in order with launches
    dev0 = raw["devices"][0]
    mods = sorted((m for m in dev0["modules"] if lo <= m[1] < hi),
                  key=lambda m: m[1])
    labels = [m[0].split("(", 1)[0] for m in mods]
    steps = [i for i, stem in enumerate(labels) if stem in stems]
    if len(steps) != len(launches):
        raise ValueError(
            f"{len(steps)} executions of the step programs {sorted(stems)} "
            f"against {len(launches)} launch spans in the window")
    device_s = [(mods[i][2] - mods[i][1]) / 1e9 for i in steps]
    ends = [mods[i][1] for i in steps[1:]] + [mods[i][2] for i in steps[-1:]]
    step_s = [(b - mods[i][1]) / 1e9 for i, b in zip(steps, ends)]
    for i, (span, _, _) in zip(steps, launches):
        labels[i] = span.split(".", 1)[1]

    starts = [m[1] for m in mods]

    def owner(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0 or t >= mods[i][2]:
            return "outside programs"
        return labels[i]

    ops = [o for o in dev0["ops"] if lo <= o[1] < hi]
    self_t = _self_times(ops, owner)
    top_ops = sorted(((k, v / 1e9) for k, v in self_t.items()),
                     key=lambda kv: -kv[1])[:top]
    idle_list = sorted(((k, v / n_dev) for k, v in idle.items()),
                       key=lambda kv: -kv[1])[:top]
    return Reduction(window_s=(hi - lo) / 1e9, busy_s=busy_total / n_dev / 1e9,
                     launch_kinds=[e[0].split(".", 1)[1] for e in launches],
                     launch_device_s=device_s, launch_step_s=step_s,
                     idle_by_host=idle_list,
                     top_ops=top_ops)
