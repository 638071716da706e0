"""FunctionBlock registry — the paper's technique as a first-class framework
feature.

Models in ``repro.models`` do not hard-code their compute implementations;
they invoke *named function blocks* (``call("rmsnorm", ...)``).  Every block
name has one or more registered implementations, tagged by execution target:

    "ref"     pure-jnp oracle (the naive/XLA-default path)
    "xla"     XLA-optimised jnp formulation
    "pallas"  Pallas TPU kernel (the cuFFT/IP-core shelf)

The offload engine's Step 3 selects a *binding* per block for the current
environment — by verification-environment measurement on a real machine, or
by dry-run cost analysis when only the compiler is available (the FPGA-style
pre-filter).  Bindings are scoped via a context manager so a training step
can be traced under a chosen offload pattern; this is how "offload pattern"
becomes a compile-time property of the jitted program.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable, Iterable, Iterator, Mapping


@dataclasses.dataclass(frozen=True)
class Impl:
    block: str
    target: str  # "ref" | "xla" | "pallas"
    fn: Callable[..., Any]
    note: str = ""


class FunctionBlockRegistry:
    def __init__(self) -> None:
        self._impls: dict[str, dict[str, Impl]] = {}
        self._local = threading.local()

    # -- registration --------------------------------------------------------
    def register(
        self, block: str, target: str, fn: Callable[..., Any], note: str = ""
    ) -> None:
        self._impls.setdefault(block, {})[target] = Impl(block, target, fn, note)

    def implementation(self, block: str, target: str) -> Impl:
        return self._impls[block][target]

    def blocks(self) -> list[str]:
        return sorted(self._impls)

    def targets(self, block: str) -> list[str]:
        return sorted(self._impls.get(block, {}))

    def shelf_fingerprint(self, blocks: Iterable[str] | None = None) -> str:
        """Hash of the *currently registered* implementations for the named
        blocks: (block, target, fn source) plus bound partial arguments.
        Registry state is import-order dependent (modules may re-register
        a block at import time), so persisted-plan fingerprints should use
        a registration-time snapshot instead — see
        ``repro.kernels.SHELF_FINGERPRINT`` / ``implementations_fingerprint``."""
        names = sorted(blocks) if blocks is not None else self.blocks()
        return implementations_fingerprint(
            (block, target, self._impls[block][target].fn)
            for block in names
            for target in self.targets(block)
        )

    # -- binding --------------------------------------------------------------
    @property
    def _bindings(self) -> dict[str, str]:
        b = getattr(self._local, "bindings", None)
        if b is None:
            b = {}
            self._local.bindings = b
        return b

    @contextlib.contextmanager
    def bind(self, mapping: Mapping[str, str]) -> Iterator[None]:
        """Scope a block->target binding (an offload pattern)."""
        saved = dict(self._bindings)
        self._bindings.update(mapping)
        try:
            yield
        finally:
            self._local.bindings = saved

    def resolve(self, block: str) -> Callable[..., Any]:
        impls = self._impls.get(block)
        if not impls:
            raise KeyError(f"unknown function block '{block}'")
        target = self._bindings.get(block)
        if target is None:
            # default preference: xla formulation, else ref
            for t in ("xla", "ref", "pallas"):
                if t in impls:
                    return impls[t].fn
            raise KeyError(f"block '{block}' has no usable implementation")
        return impls[target].fn

    def call(self, block: str, *args: Any, **kwargs: Any) -> Any:
        """Run the bound implementation under ``jax.named_scope(block)``:
        the block's device time keeps one name whichever target is bound
        (``repro.obs.op_scopes`` reads it back from the compiled text)."""
        import jax

        fn = self.resolve(block)
        with jax.named_scope(block):
            return fn(*args, **kwargs)

    def current_pattern(self) -> dict[str, str]:
        return dict(self._bindings)


def implementations_fingerprint(
    impls: "Iterable[tuple[str, str, Callable[..., Any]]]",
) -> str:
    """Hash (block, target, fn) triples by fn *source* (plus bound partial
    arguments), order-insensitively.  A kernel rewrite changes the hash,
    which invalidates stored plans measured against the old code
    (PlanStore fingerprint component)."""
    import functools
    import hashlib
    import inspect

    parts = []
    for block, target, fn in impls:
        bound = ""
        while isinstance(fn, functools.partial):
            bound += repr((fn.args, sorted((fn.keywords or {}).items())))
            fn = fn.func
        try:
            src = inspect.getsource(fn)
        except (OSError, TypeError):  # builtins / C extensions
            src = repr(fn)
        parts.append(f"{block}|{target}|{bound}|{src}")
    h = hashlib.sha256()
    for p in sorted(parts):
        h.update(p.encode())
    return h.hexdigest()[:16]


# Global registry used by the model zoo.
registry = FunctionBlockRegistry()


def call(block: str, *args: Any, **kwargs: Any) -> Any:
    return registry.call(block, *args, **kwargs)


def bind(mapping: Mapping[str, str]):
    return registry.bind(mapping)


def register(block: str, target: str, note: str = ""):
    """Decorator: ``@register("rmsnorm", "pallas")``."""

    def deco(fn: Callable[..., Any]) -> Callable[..., Any]:
        registry.register(block, target, fn, note)
        return fn

    return deco
