"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else. The measured
window is the host span ``bench.window`` that the client opens and
closes around it; every device interval is clipped to it.

Per device plane (``/device:TPU:<n>``), from its ops line:

  busy_s        union of the intervals in which an op ran
  op_s          device seconds by op name
  kernel_s      device seconds of the Pallas kernels, by kernel name:
                an op whose HLO instruction name contains a known kernel
                name (the stems of ``work/``); kernel_n their count
  module_s      device seconds of whole executables (the modules line),
                and ``modules`` their count
  collective_s  device seconds of the collective ops (all-gather,
                all-reduce, collective-permute, all-to-all,
                reduce-scatter, the start and done halves of their
                asynchronous forms, and fusions XLA names after them),
                by opcode or instruction name

Across devices: the means of these over the devices that ran anything,
the window's length, and the longest idle gaps of device 0, each named
after the client's host span (bench.submit, bench.poll, bench.wait,
bench.drain) that overlaps it most and, after " > ", the other host
event of at least ``HOST_EVENT_MIN_NS`` (on any thread) that overlaps
it most: what the client was waiting on.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
HOST_SPAN = "bench."
HOST_EVENT_MIN_NS = 1_000_000
WINDOW_SPAN = "bench.window"
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "all-to-all", "reduce-scatter")


def find_xplane(trace_dir) -> Path:
    paths = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def parse_op(name: str) -> tuple[str, str, str]:
    """(instruction name, first output shape, opcode) of an op's trace
    name, which is its whole HLO instruction."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name, "", ""
    depth, end = 0, len(rhs)
    for i, ch in enumerate(rhs):          # end of the (tuple) type
        depth += ch in "({["
        depth -= ch in ")}]"
        if ch == " " and depth == 0:
            end = i
            break
    opcode = rhs[end + 1:].split("(", 1)[0]
    shape = re.match(r"\(?([a-z0-9]+\[[\d,]*\])", rhs)
    return lhs, shape.group(1) if shape else "", opcode


def short_name(name: str) -> str:
    """``%name = <first output shape> opcode`` of an op's trace name."""
    lhs, shape, opcode = parse_op(name)
    if not opcode:
        return name[:80]
    return f"{lhs} = {shape} {opcode}"[:120]


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _kernel(text: str, kernels) -> str | None:
    for name in kernels:               # longest first
        if name in text:
            return name
    return None


def is_collective(lhs: str, opcode: str) -> bool:
    """An op (``parse_op``'s instruction name and opcode) is a
    collective: either starts with one (``all-gather-start``,
    ``%all-reduce.3``, ``%all-gather-fusion.1``, ...)."""
    return (opcode.startswith(COLLECTIVES)
            or lhs.lstrip("%").startswith(COLLECTIVES))


def _most_overlap(spans, s, e) -> str | None:
    best, name = 0, None
    for hs, he, hn in spans:
        ov = min(e, he) - max(s, hs)
        if ov > best:
            best, name = ov, hn
    return name


def reduce_profile(pd, kernels=(), gaps: int = 10) -> dict:
    """The reduction of a ``ProfileData``; ``kernels`` are the kernel
    names to look for."""
    kernels = sorted(kernels, key=len, reverse=True)
    host_spans, host_events = [], []
    window = None
    devices = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(2))] = plane
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(HOST_SPAN):
                    if ev.duration_ns >= HOST_EVENT_MIN_NS:
                        host_events.append((ev.start_ns,
                                            ev.start_ns + ev.duration_ns,
                                            ev.name))
                    continue
                if ev.name == WINDOW_SPAN:
                    if window is None:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                else:
                    host_spans.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns, ev.name))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} host span in the trace")
    lo, hi = window
    per_dev = {}
    for dev_id, plane in sorted(devices.items()):
        ivs, op_s = [], defaultdict(float)
        kernel_s, kernel_n = defaultdict(float), defaultdict(int)
        module_s, modules, collective_s = 0.0, 0, 0.0
        for line in plane.lines:
            if line.name in OPS_LINES:
                for ev in line.events:
                    s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 lo, hi)
                    if e <= s:
                        continue
                    ivs.append((s, e))
                    d = (e - s) / 1e9
                    op_s[short_name(ev.name)] += d
                    lhs, _, opcode = parse_op(ev.name)
                    if is_collective(lhs, opcode):
                        collective_s += d
                    k = _kernel(lhs, kernels)
                    if k is not None:
                        kernel_s[k] += d
                        kernel_n[k] += 1
            elif line.name in MODULE_LINES:
                for ev in line.events:
                    s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 lo, hi)
                    if e > s:
                        module_s += (e - s) / 1e9
                        modules += 1
        merged = _union(ivs)
        busy = sum(e - s for s, e in merged) / 1e9
        per_dev[dev_id] = {"busy_s": busy, "op_s": dict(op_s),
                           "kernel_s": dict(kernel_s),
                           "kernel_n": dict(kernel_n),
                           "module_s": module_s, "modules": modules,
                           "collective_s": collective_s, "merged": merged}
    active = [d for d in per_dev.values() if d["busy_s"] > 0]
    n = max(len(active), 1)
    ops = defaultdict(float)
    kern, kern_n = defaultdict(float), defaultdict(float)
    for d in active:
        for k, v in d["op_s"].items():
            ops[k] += v / n
        for k, v in d["kernel_s"].items():
            kern[k] += v / n
        for k, v in d["kernel_n"].items():
            kern_n[k] += v / n
    idle = []
    if per_dev:
        merged = per_dev[min(per_dev)]["merged"]
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                idle.append((s, e))
        idle.sort(key=lambda g: g[1] - g[0], reverse=True)
        idle = idle[:gaps]
    idle_gaps = []
    for s, e in idle:
        name = _most_overlap(host_spans, s, e) or "no client span"
        inner = _most_overlap(host_events, s, e)
        if inner:
            name = f"{name} > {inner[:80]}"
        idle_gaps.append([name, (e - s) / 1e9])
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": len(active),
        "busy_s": sum(d["busy_s"] for d in active) / n,
        "op_s": dict(ops),
        "kernel_s": dict(kern),
        "kernel_n": dict(kern_n),
        "module_s": sum(d["module_s"] for d in active) / n,
        "modules": sum(d["modules"] for d in active) / n,
        "collective_s": sum(d["collective_s"] for d in active) / n,
        "idle_gaps": idle_gaps,
    }


def reduce_trace(path, kernels=(), gaps: int = 10) -> dict:
    """``reduce_profile`` of an ``.xplane.pb`` file or of the newest
    one under a trace directory."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = find_xplane(path)
    return reduce_profile(ProfileData.from_file(str(path)), kernels, gaps)


def top_ops(red: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(red["op_s"].items(),
                                      key=lambda kv: kv[1],
                                      reverse=True)[:n]]
