"""Reduce a JAX profiler trace to device busy time, op time and idle gaps.

The profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into a :class:`Trace` of plain tuples, which is
what the rest of this module and the per-layer readers work on (a test can
build one by hand). Device planes are ``/device:<kind>:<n>``; their op
events are on the ``XLA Ops`` line, each named by its HLO instruction text
(``%fusion.3 = f32[...] fusion(...), ...``), which :func:`op_label` cuts
to the instruction's name and result type. Host spans are the events of the host
plane's threads, among them the benchmark's own ``TraceAnnotation`` spans
(``bench.window``, ``bench.job``, ``bench.check``...).

All times are nanoseconds on the profiler's clock. The window is the
``bench.window`` span: every reduction clips to it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

Span = tuple[str, float, float]  # host span: (name, start_ns, duration_ns)
Op = tuple[str, float, float, str]  # device op: label, start, duration, HLO text

WINDOW_SPAN = "bench.window"
CONTAINERS = ("while", "conditional", "call")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    """Device op events per device, and host spans, from one traced run."""

    devices: dict[str, list[Op]]
    host: list[Span]

    # ---------------------------------------------------------- window
    def window(self) -> tuple[float, float]:
        spans = [e for e in self.host if e[0] == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        _, start, dur = max(spans, key=lambda e: e[2])
        return start, start + dur

    def window_s(self) -> float:
        t0, t1 = self.window()
        return (t1 - t0) * 1e-9

    # ------------------------------------------------------- reductions
    def ops(self, device: str) -> list[Op]:
        """The device's op events, clipped to the window."""
        t0, t1 = self.window()
        out = []
        for name, start, dur, origin in self.devices[device]:
            a, b = max(start, t0), min(start + dur, t1)
            if b > a:
                out.append((name, a, b - a, origin))
        return out

    def busy_intervals(self, device: str) -> list[tuple[float, float]]:
        """Union of the device's op intervals inside the window, sorted."""
        merged: list[list[float]] = []
        for _, start, dur, _ in sorted(self.ops(device), key=lambda e: e[1]):
            end = start + dur
            if merged and start <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], end)
            else:
                merged.append([start, end])
        return [(a, b) for a, b in merged]

    def busy_s(self, device: str) -> float:
        return sum(b - a for a, b in self.busy_intervals(device)) * 1e-9

    def mean_busy_s(self) -> float:
        """Busy seconds averaged over the traced devices (0 with none)."""
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def op_seconds(self, device: str, needles: tuple[str, ...]) -> float:
        """Summed device time of the window's ops with one of ``needles`` in
        their HLO text."""
        return 1e-9 * sum(
            dur for _, _, dur, text in self.ops(device)
            if any(n in text for n in needles)
        )

    def max_op_seconds(self, needles: tuple[str, ...]) -> float | None:
        """:meth:`op_seconds` on the device that spends the most, or None
        where no device ran such an op."""
        secs = [self.op_seconds(d, needles) for d in self.devices]
        best = max(secs, default=0.0)
        return best if best > 0 else None

    def top_ops(self, limit: int = 10) -> list[list]:
        """``[[name, seconds]]``: the ops that took most device time,
        averaged over the devices. Control flow (``while``, ``conditional``)
        is left out: its event spans the ops it runs."""
        total: dict[str, float] = {}
        for d in self.devices:
            for name, _, dur, _ in self.ops(d):
                if name.startswith(CONTAINERS):
                    continue
                total[name] = total.get(name, 0.0) + dur
        n = max(len(self.devices), 1)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, ns * 1e-9 / n] for name, ns in top]

    def idle_gaps(self, limit: int = 10) -> list[list]:
        """``[[host span, seconds]]``: the longest stretches of the window in
        which the first device ran nothing, each named by the innermost host
        span around its midpoint (``idle`` where none is)."""
        if not self.devices:
            return []
        device = sorted(self.devices)[0]
        t0, t1 = self.window()
        gaps, cursor = [], t0
        for a, b in self.busy_intervals(device) + [(t1, t1)]:
            if a > cursor:
                gaps.append((cursor, a))
            cursor = max(cursor, b)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:limit]:
            mid = (a + b) / 2
            around = [
                e for e in self.host
                if e[1] <= mid <= e[1] + e[2] and e[0] != WINDOW_SPAN
            ]
            name = min(around, key=lambda e: e[2])[0] if around else "idle"
            out.append([name, (b - a) * 1e-9])
        return out


def read_device_idle(run) -> float | None:
    """Per-layer reader of ``device_idle.*``: the share of the window in
    which the chips ran no operation, in percent, averaged over the chips
    (one minus the union of each device's op intervals over the window).
    None where the trace holds no device."""
    trace = run.trace
    if not trace.devices:
        return None
    return 100.0 * (1.0 - trace.mean_busy_s() / trace.window_s())


def op_label(text: str) -> str:
    """``fusion.3 f32[8,128]`` from ``%fusion.3 = f32[8,128]{1,0} fusion(...)``."""
    m = re.match(r"%?(\S+) = (\S+?)(?:\{|\s|$)", text)
    return f"{m.group(1)} {m.group(2)}" if m else text


def hlo_ops_from(hlo_text: str, needle: str) -> set[str]:
    """Names of the instructions of a compiled HLO module whose source op
    (``metadata={op_name=...}``) contains ``needle``: how an op that XLA
    fused into a ``fusion.N`` is found in the trace again."""
    names = set()
    for m in re.finditer(r'%([\w.\-]+) = [^\n]*op_name="([^"]*)"', hlo_text):
        if needle in m.group(2):
            names.add(m.group(1))
    return names


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: dict[str, list[Op]] = {}
    host: list[Span] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices.setdefault(plane.name, []).extend(
                    (op_label(e.name), e.start_ns, e.duration_ns, e.name)
                    for e in line.events
                )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events
                )
    return Trace(devices=devices, host=host)
