"""Reduce a profiler trace to what the per-layer metrics read.

A TPU trace holds one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Ops`` has one event per operation run on the chip and whose line
``XLA Modules`` has one event per program run, named after the jitted
function (``jit_<name>(<id>)``). Host planes (``/host:...``) hold the
``jax.profiler.TraceAnnotation`` spans: the benchmark's own
(``bench.*``) and, with ``REPRO_TRACE_XLA=1``, the program's
``step.dispatch``. A CPU trace has no device plane; there the operations
are the host events that carry an ``hlo_module`` stat, which lets the
rehearsal on the CPU drive the same reduction.

All times are in nanoseconds on the trace's clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import re
import warnings
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def program_name(event_name: str) -> str:
    """``jit_prog(42)`` -> ``jit_prog``: the jitted function's name."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


@dataclasses.dataclass
class Device:
    """One chip's timeline: ``ops`` as (start, end, op, program) and
    ``programs`` as (start, end, program)."""
    ops: List[Tuple[float, float, str, str]]
    programs: List[Tuple[float, float, str]]

    def busy(self, lo: float, hi: float) -> float:
        """Nanoseconds in [lo, hi] in which some operation ran."""
        return _length(_clip(_union((s, e) for s, e, _, _ in self.ops),
                             lo, hi))

    def gaps(self, lo: float, hi: float) -> List[Interval]:
        """The idle intervals in [lo, hi]."""
        out, t = [], lo
        for s, e in _clip(_union((s, e) for s, e, _, _ in self.ops), lo, hi):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    def program_time(self, names: Sequence[str], lo: float,
                     hi: float) -> float:
        """Nanoseconds in [lo, hi] spent in the programs ``names``."""
        names = set(names)
        if self.programs:
            spans = ((s, e) for s, e, p in self.programs if p in names)
        else:
            spans = ((s, e) for s, e, _, p in self.ops if p in names)
        return _length(_clip(_union(spans), lo, hi))


@dataclasses.dataclass
class Trace:
    devices: Dict[int, Device]
    host: List[Tuple[float, float, str]]   # (start, end, annotation name)

    def annotation(self, name: str) -> Optional[Interval]:
        """The first host annotation called ``name``."""
        for s, e, n in self.host:
            if n == name:
                return s, e
        return None


def _union(spans: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(spans: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in spans if e > lo and s < hi]


def _length(spans: List[Interval]) -> float:
    return float(sum(e - s for s, e in spans))


def _stats(ev) -> dict:
    # jaxlib builds the stats type on first use and warns that it has no
    # __module__; where warnings are errors (pytest.ini) that aborts
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def from_profile(profile) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    devices: Dict[int, Device] = {}
    host: List[Tuple[float, float, str]] = []
    cpu_ops: Dict[int, list] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, progs = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.start_ns, ev.end_ns, ev.name) for ev in
                           line.events]
                elif line.name == MODULES_LINE:
                    progs = sorted((ev.start_ns, ev.end_ns,
                                    program_name(ev.name))
                                   for ev in line.events)
            devices[int(m.group(1))] = Device(
                [(s, e, n, _enclosing(progs, s)) for s, e, n in ops], progs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    st = _stats(ev)
                    if "hlo_module" in st:
                        cpu_ops.setdefault(int(st.get("device_ordinal", 0)),
                                           []).append(
                            (ev.start_ns, ev.end_ns, ev.name,
                             program_name(str(st["hlo_module"]))))
                    elif ev.duration_ns > 0:
                        host.append((ev.start_ns, ev.end_ns, ev.name))
    if not devices:
        devices = {d: Device(sorted(ops), []) for d, ops in cpu_ops.items()}
    host.sort()
    return Trace(devices, host)


def _enclosing(progs, t: float) -> str:
    """The program running at ``t`` (programs on one chip never overlap)."""
    i = bisect.bisect_right(progs, (t, float("inf"), "")) - 1
    if i >= 0 and progs[i][0] <= t < progs[i][1]:
        return progs[i][2]
    return ""


def load(path: str) -> Trace:
    """Reduce an ``.xplane.pb`` file (gzip-compressed or not)."""
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return from_profile(ProfileData.from_serialized_xspace(f.read()))


def top_ops(trace: Trace, devices: Sequence[int], lo: float, hi: float,
            n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` operations that took most device time in [lo, hi],
    summed over ``devices``, as (``program/op``, seconds); the rest of
    the busy time as ``other``. An operation that encloses others (a
    loop) counts only through them."""
    per: Dict[str, float] = {}
    for d in devices:
        for s, e, op, prog in _leaves(trace.devices[d].ops):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = f"{prog or '?'}/{op}"
                per[key] = per.get(key, 0.0) + (e - s) * 1e-9
    ranked = sorted(per.items(), key=lambda kv: -kv[1])
    if len(ranked) <= n:
        return ranked
    return ranked[:n - 1] + [("other", sum(v for _, v in ranked[n - 1:]))]


def _leaves(ops):
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[0] >= o[1]]


def named_gaps(trace: Trace, device: int, lo: float, hi: float,
               spans: Sequence[Tuple[float, float, str]],
               n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps of ``device`` in [lo, hi], each named
    after the innermost host span open at its midpoint (``none`` where
    no span is)."""
    out = []
    for s, e in trace.devices[device].gaps(lo, hi):
        mid = 0.5 * (s + e)
        inner = [sp for sp in spans if sp[0] <= mid <= sp[1]]
        name = min(inner, key=lambda sp: sp[1] - sp[0])[2] if inner \
            else "none"
        out.append((name, (e - s) * 1e-9))
    return sorted(out, key=lambda kv: -kv[1])[:n]
