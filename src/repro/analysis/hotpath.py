"""Hot-path lints for jitted serve programs.

``ServeEngine`` registers each jitted program (prefill / decode / insert /
extend) with a :class:`ProgramSet` at construction; the returned wrapper
records every *abstract signature* the program is called under (shape,
dtype, weak-type per leaf — cheap per call) and the set lints the programs
it has observed:

* ``host-sync``     — a loop program returns a non-carry output larger than
                      ``sync_bytes``: the driver loop will pull it to host
                      every step (the PR-4/5 contract is that decode's
                      per-step transfer is the sampled token ids only).
* ``callback``      — a callback primitive inside the traced program
                      re-enters Python from device code each call.
* ``retrace-risk``  — more distinct abstract signatures than the program
                      declares (``expected_signatures``): something in the
                      argument stream drifts and every drift is a retrace.
* ``weak-type``     — python-scalar / weak-typed operands in a loop
                      program's signature; dtype promotion differences
                      between call sites silently fork traces.
* ``const-capture`` — a large array baked into the trace as a constant
                      instead of passed as an operand (re-traced programs
                      re-bake it; donation can't reuse its buffer).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import time
from typing import Any, Callable, Sequence

import jax
import numpy as np

from repro.analysis import features as features_mod
from repro.analysis.diagnostics import Diagnostic

#: Host-transfer budget per loop-program call (non-carry outputs).  The
#: decode contract is "token ids only": (B,) int32 stays far below this.
DEFAULT_SYNC_BYTES = 32 * 1024

#: A constant this large baked into a trace is a capture bug, not a table.
DEFAULT_CONST_BYTES = 1 << 20


def _leaf_signature(leaf: Any) -> tuple:
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return (
            tuple(leaf.shape),
            str(leaf.dtype),
            bool(getattr(leaf, "weak_type", False)),
        )
    # python scalar: jit traces it weak-typed; value changes don't retrace
    # but promotion behaviour differs from a committed array operand
    return ("pyscalar", type(leaf).__name__)


def _leaf_struct(leaf: Any) -> Any:
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype)
    return np.asarray(leaf)


def _aval_bytes(tree: Any) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", None)
        itemsize = getattr(dtype, "itemsize", None) or np.dtype(dtype).itemsize
        total += int(math.prod(shape)) * int(itemsize)
    return total


@dataclasses.dataclass
class ProgramRecord:
    """One registered hot-path program and its observed call signatures."""

    name: str
    fn: Callable[..., Any]
    loop: bool = False  # called once per engine step (the decode loop)
    carry_outputs: tuple[int, ...] = ()  # top-level outputs that stay on device
    expected_signatures: int | None = None  # None = unbounded (e.g. prefill)
    signatures: dict[tuple, tuple] = dataclasses.field(default_factory=dict)
    calls: int = 0
    #: wall seconds spent in the first call of each distinct signature —
    #: trace+compile+dispatch, the retrace cost the timeline should show
    compile_seconds: float = 0.0

    @property
    def retraces(self) -> int:
        """Signatures beyond the first — each one recompiled the program."""
        return max(len(self.signatures) - 1, 0)

    def observe(self, args: tuple) -> bool:
        """Record one call; True when its abstract signature is new."""
        self.calls += 1
        leaves = jax.tree_util.tree_leaves(args)
        sig = tuple(_leaf_signature(leaf) for leaf in leaves)
        if sig not in self.signatures:
            # structs for on-demand abstract tracing; built only for new
            # signatures so the steady-state decode step pays one tuple()
            self.signatures[sig] = jax.tree_util.tree_map(
                _leaf_struct, args
            )
            return True
        return False


class ProgramSet:
    """Registry of one engine's hot-path programs, lintable on demand."""

    def __init__(
        self,
        sync_bytes: int = DEFAULT_SYNC_BYTES,
        const_bytes: int = DEFAULT_CONST_BYTES,
    ) -> None:
        self.records: dict[str, ProgramRecord] = {}
        self.sync_bytes = sync_bytes
        self.const_bytes = const_bytes
        #: optional ``repro.obs`` attachments (set by the engine): a
        #: Tracer whose live "serve.compile" span covers each first call
        #: of a new signature, and a MetricsRegistry that carries
        #: per-program retrace/compile-time counters.  Both default off —
        #: a bare ProgramSet stays analysis-only with zero obs coupling.
        self.tracer: Any = None
        self.metrics: Any = None

    def register(
        self,
        name: str,
        fn: Callable[..., Any],
        loop: bool = False,
        carry_outputs: Sequence[int] = (),
        expected_signatures: int | None = None,
    ) -> Callable[..., Any]:
        """Wrap ``fn`` so calls record their abstract signature (and the
        first-call wall time of each new signature — the compile cost).
        Returns the wrapper the caller should invoke instead of ``fn``."""
        rec = ProgramRecord(
            name=name,
            fn=fn,
            loop=loop,
            carry_outputs=tuple(carry_outputs),
            expected_signatures=expected_signatures,
        )
        self.records[name] = rec

        @functools.wraps(fn)
        def observed(*args: Any, **kwargs: Any) -> Any:
            new_sig = rec.observe(
                args if not kwargs else args + tuple(kwargs.values())
            )
            if not new_sig:
                return fn(*args, **kwargs)
            # first call under this signature: jit traces + compiles
            # synchronously inside the call, so its wall time is the
            # retrace cost (execution itself dispatches async)
            with self._compile_span(rec):
                t0 = time.perf_counter()
                out = fn(*args, **kwargs)
                dt = time.perf_counter() - t0
            rec.compile_seconds += dt
            self._on_compile(rec, dt)
            return out

        observed.record = rec  # type: ignore[attr-defined]
        return observed

    def _compile_span(self, rec: ProgramRecord) -> Any:
        """A live span around a first call: a compile inside a profiled
        window then shows as ``serve.compile``, not as unexplained time."""
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(
            "serve.compile", program=rec.name, signature=len(rec.signatures)
        )

    def _on_compile(self, rec: ProgramRecord, dt: float) -> None:
        if self.metrics is not None:
            self.metrics.counter(
                "serve_program_retraces_total",
                "distinct abstract signatures per program beyond the first",
                labelnames=("program",),
            ).labels(program=rec.name).inc(0 if len(rec.signatures) == 1
                                           else 1)
            self.metrics.counter(
                "serve_program_compile_seconds_total",
                "wall seconds spent in first-call-per-signature "
                "(trace + compile)",
                labelnames=("program",),
            ).labels(program=rec.name).inc(dt)

    def stats(self) -> dict[str, dict[str, Any]]:
        """Per-program compile/retrace counters for reports and the
        metrics endpoint."""
        return {
            name: {
                "calls": rec.calls,
                "signatures": len(rec.signatures),
                "retraces": rec.retraces,
                "compile_seconds": rec.compile_seconds,
            }
            for name, rec in self.records.items()
        }

    def observe(self, name: str, *args: Any) -> None:
        """Record a signature without wrapping (tests, ad-hoc programs)."""
        self.records[name].observe(args)

    def compiled_texts(
        self, names: Sequence[str] | None = None
    ) -> dict[str, list[str]]:
        """The compiled HLO text of each named program (default: every one
        called so far), one per signature it was called with, keyed by the
        text's ``HloModule`` name (``jit_decode_fn``: what a profiler
        trace calls its runs).  The signatures of one program share that
        name but not their instruction names: only a program with one
        signature maps a trace's instructions without ambiguity.
        Lowering and compiling again is served by the persistent
        compilation cache, whose key leaves metadata out: a cache written
        by code with other scopes hands back its text."""
        from repro.obs.scopes import module_name

        out: dict[str, list[str]] = {}
        for name, rec in self.records.items():
            if names is not None and name not in names:
                continue
            for structs in rec.signatures.values():
                text = rec.fn.lower(*structs).compile().as_text()
                out.setdefault(module_name(text), []).append(text)
        return out

    # -- lints ---------------------------------------------------------------

    def lint(self, names: Sequence[str] | None = None) -> list[Diagnostic]:
        diags: list[Diagnostic] = []
        for name, rec in self.records.items():
            if names is not None and name not in names:
                continue
            diags.extend(self._lint_record(rec))
        return diags

    def _lint_record(self, rec: ProgramRecord) -> list[Diagnostic]:
        diags: list[Diagnostic] = []
        if not rec.signatures:
            return diags  # never called — nothing observed to lint

        if (
            rec.expected_signatures is not None
            and len(rec.signatures) > rec.expected_signatures
        ):
            sigs = len(rec.signatures)
            diags.append(Diagnostic(
                pass_name="hotpath", code="retrace-risk", severity="warning",
                program=rec.name, subject=f"{sigs}-signatures",
                message=(
                    f"{sigs} distinct abstract signatures observed over "
                    f"{rec.calls} calls (declared {rec.expected_signatures})"
                    " — each drift recompiles the program"
                ),
            ))

        first_sig = next(iter(rec.signatures))
        structs = rec.signatures[first_sig]
        if rec.loop:
            for leaf_sig in first_sig:
                if leaf_sig and leaf_sig[0] == "pyscalar":
                    diags.append(Diagnostic(
                        pass_name="hotpath", code="weak-type",
                        severity="warning", program=rec.name,
                        subject=f"pyscalar-{leaf_sig[1]}",
                        message=(
                            f"python {leaf_sig[1]} operand in a loop "
                            "program; pass a committed array to pin dtype "
                            "promotion"
                        ),
                    ))
            diags.extend(self._lint_host_sync(rec, structs))
        diags.extend(self._lint_traced(rec, structs))
        return diags

    def _lint_host_sync(
        self, rec: ProgramRecord, structs: tuple
    ) -> list[Diagnostic]:
        try:
            out = jax.eval_shape(rec.fn, *structs)
        except Exception:  # noqa: BLE001 — unlintable under this signature
            return []
        parts = list(out) if isinstance(out, (tuple, list)) else [out]
        diags = []
        for i, part in enumerate(parts):
            if i in rec.carry_outputs:
                continue
            nbytes = _aval_bytes(part)
            if nbytes > self.sync_bytes:
                diags.append(Diagnostic(
                    pass_name="hotpath", code="host-sync", severity="warning",
                    program=rec.name, subject=f"output[{i}]",
                    message=(
                        f"non-carry output {i} is {nbytes} bytes "
                        f"(> {self.sync_bytes}); the driver loop pulls it "
                        "to host every step — fuse the reduction (e.g. "
                        "sampling) into the program"
                    ),
                ))
        return diags

    def _lint_traced(
        self, rec: ProgramRecord, structs: tuple
    ) -> list[Diagnostic]:
        try:
            feats = features_mod.trace_features(rec.fn, *structs)
        except Exception:  # noqa: BLE001 — unlintable under this signature
            return []
        diags = []
        for cb in feats.callbacks:
            diags.append(Diagnostic(
                pass_name="hotpath", code="callback", severity="warning",
                program=rec.name, subject=cb,
                message=(
                    f"'{cb}' primitive in the traced program re-enters "
                    "Python from device code on every call"
                ),
            ))
        if feats.largest_const_bytes > self.const_bytes:
            diags.append(Diagnostic(
                pass_name="hotpath", code="const-capture", severity="warning",
                program=rec.name,
                subject=f"const-{feats.largest_const_bytes}B",
                message=(
                    f"a {feats.largest_const_bytes}-byte array is baked "
                    "into the trace as a constant; pass it as an operand "
                    "so retraces don't re-bake it"
                ),
            ))
        return diags


def lint_traced_program(
    name: str,
    fn: Callable[..., Any],
    example_args: Sequence[Any],
    sync_bytes: int = DEFAULT_SYNC_BYTES,
    const_bytes: int = DEFAULT_CONST_BYTES,
    loop: bool = False,
    carry_outputs: Sequence[int] = (),
) -> list[Diagnostic]:
    """One-shot lint of a standalone program (zoo cells, CLI sweeps)."""
    ps = ProgramSet(sync_bytes=sync_bytes, const_bytes=const_bytes)
    ps.register(name, fn, loop=loop, carry_outputs=carry_outputs)
    ps.observe(name, *example_args)
    return ps.lint()
