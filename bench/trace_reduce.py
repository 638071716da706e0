"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

What it reads: the device planes (``/device:TPU:<n>``), their ``XLA Ops``
line (one event per operation run) and ``XLA Modules`` line (one event per
program run), and the host annotations the harness places around its own
calls (``jax.profiler.TraceAnnotation``: ``window``, ``submit``, ``step``,
``arrival_sleep``, ``bookkeeping``).  Host and device events share the
trace's clock.

What it gives, over the ``window`` annotation (the whole trace where there
is none), averaged over the device planes:

* ``busy_s``: the union of the intervals in which an operation ran;
* ``window_s``: the window's length;
* ``modules``: device seconds and runs per program (``jit_decode_fn``...),
  the program id suffix stripped;
* ``top_ops``: the operations that took most device time, as
  ``<program>/<instruction>`` (the HLO instruction's name, without its
  operands);
* ``idle_by_host``: idle device time, split by the host annotation that
  overlapped each idle gap most (``other`` where none did).

A trace without a TPU device plane is an error, not an empty reading.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

HOST_SPANS = ("submit", "step", "arrival_sleep", "bookkeeping")
_SUFFIX = re.compile(r"\(\d+\)$")


def _op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[..] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}: {paths}")
    return paths[0]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns), e


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], collections.defaultdict(list)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, d, _ in _events(line):
                    if name == "window" or name in HOST_SPANS:
                        host[name].append((s, s + d))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")

    per_device = [_device(plane) for plane in devices]
    if host["window"]:
        lo, hi = host["window"][0]
    else:
        lo = min(s for d in per_device for s, _ in d["ops"][:1])
        hi = max(e for d in per_device for _, e in d["ops"][-1:])
    spans = sorted((s, e, name) for name in HOST_SPANS for s, e in host[name])
    span_starts = [s for s, _, _ in spans]

    busy, idle = 0.0, collections.Counter()
    modules = collections.defaultdict(lambda: [0.0, 0])
    ops = collections.Counter()
    for d in per_device:
        merged = _clip(d["ops"], lo, hi)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                idle[_label(gs, ge, spans, span_starts)] += (ge - gs) / 1e9
        for name, (sec, runs) in d["modules"].items():
            modules[name][0] += sec
            modules[name][1] += runs
        ops.update(d["op_seconds"])
    n = len(per_device)
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9 / n,
        "modules": {k: {"seconds": v[0] / n, "runs": v[1] / n}
                    for k, v in modules.items()},
        "top_ops": [[k, v / n] for k, v in ops.most_common(top)],
        "idle_by_host": [[k, v / n] for k, v in idle.most_common(top)],
    }


def _device(plane) -> dict:
    """One device plane: merged op intervals, module and op seconds."""
    intervals, modules, op_seconds = [], {}, collections.Counter()
    mod_spans = []
    for line in plane.lines:
        if line.name == "XLA Modules":
            for name, s, d, _ in _events(line):
                key = _SUFFIX.sub("", name)
                sec, runs = modules.get(key, (0.0, 0))
                modules[key] = (sec + d / 1e9, runs + 1)
                mod_spans.append((s, s + d, key))
    mod_spans.sort()
    starts = [s for s, _, _ in mod_spans]
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for name, s, d, _ in _events(line):
            intervals.append((s, s + d))
            i = bisect.bisect_right(starts, s) - 1
            module = mod_spans[i][2] if i >= 0 and s < mod_spans[i][1] else "?"
            op_seconds[f"{module}/{_op_name(name)}"] += d / 1e9
    return {
        "ops": _union(intervals),
        "modules": modules,
        "op_seconds": op_seconds,
    }


def _label(gs: float, ge: float, spans, starts) -> str:
    """The host annotation that overlaps the gap [gs, ge] most; ``spans``
    are sorted and do not overlap (one thread places them in turn)."""
    best, label = 0.0, "other"
    i = max(bisect.bisect_right(starts, gs) - 1, 0)
    while i < len(spans) and spans[i][0] < ge:
        s, e, name = spans[i]
        overlap = min(e, ge) - max(s, gs)
        if overlap > best:
            best, label = overlap, name
        i += 1
    return label
