"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, device time per XLA module and per
operation, and the longest idle gaps with what the host was doing.

Device planes are ``/device:TPU:<n>``; their ``XLA Modules`` line holds
one event per program execution (``jit_<name>(<id>)``) and their
``XLA Ops`` line one event per operation.  Everything is clipped to the
traced window and averaged over the devices.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
MARK = "bench.window"


def module_name(event_name: str) -> str:
    """``jit_train_jit(17)`` -> ``jit_train_jit``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%staged_gather.1 = f32[..] custom-call(..)`` -> ``staged_gather``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                   # mean over devices
    module_s: dict                  # module -> seconds, mean over devices
    module_calls: dict              # module -> executions on the first device
    op_s: dict                      # op -> seconds, mean over devices
    gaps: list                      # [(seconds, host span name)], longest first
    n_devices: int

    def seconds(self, modules) -> float:
        return sum(self.module_s.get(m, 0.0) for m in modules)

    def kernel_seconds(self, kernel: str) -> float:
        """Device seconds of the custom calls named ``kernel``: a Pallas
        kernel's operation is ``%<kernel>.<n> = ... custom-call(...)``."""
        return sum(s for op, s in self.op_s.items()
                   if op_name(op) == kernel and "custom-call(" in op)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10):
        """The ``n`` operations that took most device time, by name."""
        by = defaultdict(float)
        for op, s in self.op_s.items():
            by[op_name(op)] += s
        return sorted(by.items(), key=lambda kv: -kv[1])[:n]


def read_planes(path) -> list:
    from jax.profiler import ProfileData
    return list(ProfileData.from_file(str(path)).planes)


def reduce(planes, host_spans=(), host_offset_ns: float = 0.0,
           window_ns=None) -> Reduced:
    """``planes`` from :func:`read_planes`.  The window is the host
    event named ``bench.window`` unless ``window_ns`` (start, end) is
    given.  ``host_spans`` are (name, start_s, end_s[, rank]) on the host
    clock that ``host_offset_ns`` (profiler ns minus host ns) maps onto
    the trace; each idle gap is named by the span of lowest rank (0 when
    not given) that overlaps it, the one overlapping it most among
    those."""
    planes = list(planes)
    if window_ns is None:
        window_ns = _mark(planes)
    w0, w1 = window_ns
    devices = [p for p in planes if p.name.startswith("/device:TPU:")
               and p.name[len("/device:TPU:"):].isdigit()]
    if not devices:
        raise ValueError("trace has no /device:TPU:<n> plane")
    module_s, op_s = defaultdict(float), defaultdict(float)
    module_calls = defaultdict(int)
    busy, first_busy = 0.0, None
    for i, plane in enumerate(devices):
        lines = {ln.name: ln for ln in plane.lines}
        intervals = []
        for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines
                   else ()):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            name = module_name(ev.name)
            module_s[name] += (e - s) * 1e-9
            if i == 0:
                module_calls[name] += 1
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            op_s[ev.name] += (e - s) * 1e-9
            intervals.append((s, e))
        merged = _union(intervals)
        busy += sum(e - s for s, e in merged) * 1e-9
        if i == 0:
            first_busy = merged
    n = len(devices)
    gaps = _gaps(first_busy, w0, w1, host_spans, host_offset_ns)
    return Reduced(
        window_s=(w1 - w0) * 1e-9, busy_s=busy / n,
        module_s={k: v / n for k, v in module_s.items()},
        module_calls=dict(module_calls),
        op_s={k: v / n for k, v in op_s.items()},
        gaps=gaps, n_devices=n)


def _mark(planes):
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name == MARK:
                    return ev.start_ns, ev.end_ns
    raise ValueError(f"trace has no host event {MARK!r}")


def host_offset(planes, mark_host_s: float) -> float:
    """Profiler ns minus host-clock ns, from the ``bench.window`` event
    that began at ``mark_host_s`` on the host clock."""
    return _mark(list(planes))[0] - mark_host_s * 1e9


def _gaps(busy, w0, w1, host_spans, offset_ns, top: int = 10):
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    spans = [(sp[0], sp[1] * 1e9 + offset_ns, sp[2] * 1e9 + offset_ns,
              sp[3] if len(sp) > 3 else 0) for sp in host_spans]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, name = (-float("inf"), 0.0), "no span"
        for n, hs, he, rank in spans:
            ov = min(e, he) - max(s, hs)
            if ov > 0 and (-rank, ov) > best:
                best, name = (-rank, ov), n
        out.append((name, (e - s) * 1e-9))
    return out
