"""From a ``jax.profiler`` trace to device busy time, kernel time and the
breakdown of one traced job.

The profiler writes an ``.xplane.pb`` file: one plane per device
(``/device:TPU:<i>``), whose ``XLA Ops`` line holds one event per operation
that ran, named by its HLO text (``%fusion.160 = u32[...] fusion(...)``),
with a loop's operations nested inside the loop's own event; and one host
plane (``/host:CPU``) with a line per thread.  The
harness wraps the traced job in a host annotation (:data:`JOB_SPAN`); its
bounds are the traced window.  Host spans taken on other clocks (JAX's
compile-stage time spans, the program's own ``repro.obs`` spans) are put
on the trace's clock through that annotation and the host clock readings
taken beside it.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from dataclasses import dataclass, field

JOB_SPAN = "bench:job"
OPS_LINE = "XLA Ops"


@dataclass
class Profile:
    """The parts of one trace the metrics read; times in ns."""
    ops: dict = field(default_factory=dict)   # device plane -> [(name, s, e)]
    host: list = field(default_factory=list)  # [(name, s, e)]

    def window(self) -> tuple:
        """Bounds of the traced job's annotation."""
        spans = [(s, e) for n, s, e in self.host if n == JOB_SPAN]
        if len(spans) != 1:
            raise ValueError(
                f"trace holds {len(spans)} {JOB_SPAN!r} spans, not one")
        return spans[0]


def find_xplane(profile_dir: str) -> str:
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"{len(paths)} .xplane.pb files under {profile_dir}, not one")
    return paths[0]


def load(path: str) -> Profile:
    """Read an ``.xplane.pb`` file, gzipped or not, or the one under a
    profile directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    prof = Profile()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    prof.ops[plane.name] = [
                        (ev.name, ev.start_ns, ev.end_ns)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                prof.host.extend((ev.name, ev.start_ns, ev.end_ns)
                                 for ev in line.events
                                 if ev.duration_ns > 0)
    return prof


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_s(prof: Profile, lo, hi) -> float:
    """Seconds in ``[lo, hi)`` in which some operation ran, averaged over
    the device planes."""
    if not prof.ops:
        return 0.0
    tot = 0.0
    for events in prof.ops.values():
        merged = union(_clip([(s, e) for _, s, e in events], lo, hi))
        tot += sum(e - s for s, e in merged)
    return tot / len(prof.ops) / 1e9


_HLO = re.compile(r"%([^ ]+) = ")


def op_name(event: str) -> str:
    """The HLO instruction name of a device event (``fusion.160``)."""
    m = _HLO.match(event)
    return m.group(1) if m else event


def is_kernel(event: str, kernel: str) -> bool:
    """Whether a device event is an instance of the Pallas kernel
    ``kernel``: a ``custom-call`` whose instruction the compiler named
    after the kernel (``vmap_kway_merge_.3`` under ``vmap``)."""
    return ("custom-call(" in event and re.fullmatch(
        rf"\w*{re.escape(kernel)}\w*(\.\d+)*", op_name(event)) is not None)


def kernel_s(prof: Profile, kernel: str, lo, hi) -> float:
    """Summed device seconds of the kernel's operations in ``[lo, hi)``,
    over all device planes."""
    return sum(e - s for events in prof.ops.values()
               for n, s, e in _clip_named(events, lo, hi)
               if is_kernel(n, kernel)) / 1e9


def _clip_named(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def self_times(events) -> list:
    """``(name, self ns)`` of each event: its span less the spans of the
    events directly nested in it (a loop's body operations)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [e - s for _, s, e in events]
    stack = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return [(events[i][0], self_ns[i]) for i in range(len(events))]


def top_ops(prof: Profile, lo, hi, n: int = 10, width: int = 120) -> list:
    """The ``n`` operations that took most device time in ``[lo, hi)``,
    by self time (a loop's own event does not count its body again), as
    ``[HLO text cut to width, seconds]``: summed over the instances of an
    instruction and averaged over the device planes."""
    tot, text = {}, {}
    for events in prof.ops.values():
        for name, ns in self_times(_clip_named(events, lo, hi)):
            key = op_name(name)
            tot[key] = tot.get(key, 0) + ns
            text.setdefault(key, name[:width])
    k = max(1, len(prof.ops))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[text[key], ns / k / 1e9] for key, ns in ranked]


def idle_gaps(prof: Profile, lo, hi, host_spans=(), n: int = 10) -> list:
    """Device idle time in ``[lo, hi)`` by what the host was doing.  Each
    gap between the device's busy intervals (on the first device plane)
    goes to the shortest host span that covers at least half of it, or
    else to the one that overlaps it most; the spans are the trace's host
    events and ``host_spans`` (``(name, s, e)`` on the trace's clock).
    Returns the ``n`` largest totals as ``[label, seconds]``; time that no
    span overlaps is ``"host: no span"``."""
    if not prof.ops:
        return []
    plane = sorted(prof.ops)[0]
    busy = union(_clip([(s, e) for _, s, e in prof.ops[plane]], lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    cands = sorted(((nm, s, e) for nm, s, e in
                    list(prof.host) + list(host_spans)
                    if nm != JOB_SPAN and e > s), key=lambda c: c[1])
    tot, active, nxt = {}, [], 0
    for gs, ge in gaps:           # in time order: sweep the spans once
        while nxt < len(cands) and cands[nxt][1] < ge:
            active.append(cands[nxt])
            nxt += 1
        active = [c for c in active if c[2] > gs]
        best, best_key = "host: no span", None
        for nm, s, e in active:
            ov = min(e, ge) - max(s, gs)
            covers = 2 * ov >= ge - gs
            key = (covers, -(e - s) if covers else ov)
            if best_key is None or key > best_key:
                best, best_key = nm, key
        tot[best] = tot.get(best, 0.0) + (ge - gs)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]
