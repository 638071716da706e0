"""Engine spans and block scopes in a profiler trace.

``trace_reduce.reduce`` labels idle device time by the harness's own
annotations and names operations by HLO instruction.  This module reads
two more things from the same trace, over the same window:

* the engine's live ``serve.*`` spans.  An enabled ``repro.obs.Tracer``
  enters a ``jax.profiler.TraceAnnotation`` for each one, on the engine's
  thread, nested inside ``serve.step``;
* a scope map of the programs that ran, ``{module: {instruction: scope}}``.
  ``repro.obs.op_scopes`` builds it from the compiled text, because the
  trace's op events carry no metadata.

It gives:

* ``idle_by_span``: each idle gap labelled ``<harness span>/<engine
  span>``, e.g. ``step/serve.pages``.  The harness span is the one
  ``reduce`` picks.  The engine span is the one that is the innermost
  open for most of the part of the gap inside that harness span.  A gap
  that no engine span overlaps keeps the harness label alone.  Summed by
  the part before ``/``, this is ``idle_by_host`` exactly;
* ``scopes``: ``{module: {scope: seconds}}``, the device seconds in which
  the innermost running operation belongs to that scope, clipped to the
  window.  A parent operation (a layer loop's ``while``) keeps only the
  time that none of its children cover, so a module's scopes sum to its
  busy time in the window;
* ``engine_steps``: the ``serve.step`` spans that start in the window.

Both are averaged over the device planes, as ``reduce`` does.
"""

from __future__ import annotations

import bisect
import collections

from bench import trace_reduce

ENGINE_PREFIX = "serve."
OTHER = "other"


def reduce_spans(path: str, scopes: dict | None = None) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, harness, engine = [], collections.defaultdict(list), []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, d, _ in trace_reduce._events(line):
                    if name == "window" or name in trace_reduce.HOST_SPANS:
                        harness[name].append((s, s + d))
                    elif name.startswith(ENGINE_PREFIX):
                        engine.append((s, s + d, name))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")

    per_device = [trace_reduce._device(plane) for plane in devices]
    if harness["window"]:
        lo, hi = harness["window"][0]
    else:
        lo = min(s for d in per_device for s, _ in d["ops"][:1])
        hi = max(e for d in per_device for _, e in d["ops"][-1:])
    spans = sorted(
        (s, e, name) for name in trace_reduce.HOST_SPANS
        for s, e in harness[name]
    )
    span_starts = [s for s, _, _ in spans]
    roots = _tree(engine)

    idle = collections.Counter()
    by_scope = collections.defaultdict(collections.Counter)
    for plane, d in zip(devices, per_device):
        merged = trace_reduce._clip(d["ops"], lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                idle[_gap_label(gs, ge, spans, span_starts, roots)] += (
                    (ge - gs) / 1e9
                )
        for module, instr, s, e in _innermost(plane):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                scope = (scopes or {}).get(module, {}).get(instr, OTHER)
                by_scope[module][scope] += (e - s) / 1e9
    n = len(per_device)
    return {
        "idle_by_span": [[k, v / n] for k, v in idle.most_common()],
        "scopes": {
            module: {k: v / n for k, v in c.most_common()}
            for module, c in by_scope.items()
        },
        "engine_steps": sum(
            1 for s, _, name in engine
            if name == "serve.step" and lo <= s < hi
        ),
    }


def scoped_op_name(op: str, scopes: dict) -> str:
    """``<program>/<instruction>`` (``reduce``'s ``top_ops`` names) ->
    ``<program>/<scope>/<instruction>``."""
    module, instr = op.split("/", 1)
    return f"{module}/{scopes.get(module, {}).get(instr, OTHER)}/{instr}"


def _tree(spans):
    """Nest one thread's spans: [(start, end, name, children)], sorted,
    siblings disjoint."""
    roots, stack = [], []
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        node = (s, e, name, [])
        (stack[-1][3] if stack else roots).append(node)
        stack.append(node)
    return roots


def _most(gs, ge, spans, starts):
    """Index of the span that overlaps [gs, ge] most (the first of equals),
    or None: ``trace_reduce._label``'s rule over disjoint sorted spans."""
    best, found = 0.0, None
    i = max(bisect.bisect_right(starts, gs) - 1, 0)
    while i < len(spans) and spans[i][0] < ge:
        overlap = min(spans[i][1], ge) - max(spans[i][0], gs)
        if overlap > best:
            best, found = overlap, i
        i += 1
    return found


def _gap_label(gs, ge, spans, span_starts, roots) -> str:
    i = _most(gs, ge, spans, span_starts)
    if i is None:
        return OTHER
    s, e, label = spans[i]
    owned = []  # (seconds, name), outer spans first
    _own(max(gs, s), min(ge, e), roots, owned)  # the gap's part in span i
    if not owned:
        return label
    inner = max(owned, key=lambda x: x[0])[1]  # the first of equals
    return f"{label}/{inner}"


def _own(gs, ge, level, owned) -> float:
    """Append (seconds, name) for each span of ``level`` and below that
    overlaps [gs, ge]: the part in which it is the innermost span open.
    Returns the overlap of ``level`` itself."""
    total = 0.0
    i = max(bisect.bisect_right([x[0] for x in level], gs) - 1, 0)
    while i < len(level) and level[i][0] < ge:
        s, e, name, children = level[i]
        overlap = min(e, ge) - max(s, gs)
        if overlap > 0:
            total += overlap
            entry = len(owned)
            owned.append(None)
            inner = _own(max(s, gs), min(e, ge), children, owned)
            owned[entry] = (overlap - inner, name)
        i += 1
    return total


def _innermost(plane):
    """(module, instruction, start, end) pieces of the plane's ``XLA Ops``
    line in which that operation is the innermost one running."""
    mods = sorted(
        (s, s + d, trace_reduce._SUFFIX.sub("", name))
        for line in plane.lines if line.name == "XLA Modules"
        for name, s, d, _ in trace_reduce._events(line)
    )
    mod_starts = [s for s, _, _ in mods]
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        ops = sorted(
            ((s, s + d, trace_reduce._op_name(name))
             for name, s, d, _ in trace_reduce._events(line)),
            key=lambda o: (o[0], -o[1]),
        )
        stack, t = [], None  # open ops (end, module, instruction)
        for s, e, instr in ops:
            while stack and stack[-1][0] <= s:
                end, module, name = stack.pop()
                if end > t:
                    yield module, name, t, end
                    t = end
            if stack and s > t:
                yield stack[-1][1], stack[-1][2], t, s
            t = s
            i = bisect.bisect_right(mod_starts, s) - 1
            module = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            stack.append((e, module, instr))
        while stack:
            end, module, name = stack.pop()
            if end > t:
                yield module, name, t, end
                t = end
