"""Opt-in ``jax.profiler`` capture windows.

The tracer (``repro.obs.trace``) answers *host-side* "why was this step
slow" questions; when the answer is inside a compiled program, the next
tool down is the XLA profiler.  :func:`profile_window` brackets a code
region with ``jax.profiler.start_trace``/``stop_trace`` so the captured
TensorBoard/Perfetto artifacts land in a log directory, and degrades to a
no-op (with one warning) when a capture cannot start, e.g. because one is
already running — profiling must never be the reason a serve loop cannot
run.

Typical uses::

    with obs.profile_window("/tmp/prof"):          # one planner round
        session.plan()

    engine.profile_steps(8, "/tmp/prof")           # N serve steps
"""

from __future__ import annotations

import contextlib
import warnings
from typing import Iterator

__all__ = ["profile_window"]


@contextlib.contextmanager
def profile_window(
    logdir: str, *, tracer=None, name: str = "profile"
) -> Iterator[bool]:
    """Capture a ``jax.profiler`` trace of the body into ``logdir``.

    Yields True when a capture is actually running, False on graceful
    degrade (the capture could not start, e.g. one is already active).
    When ``tracer`` (a :class:`repro.obs.Tracer`) is given, the window is
    also recorded as a host-side span so the two timelines line up.
    """
    from repro.obs.trace import get_tracer

    tracer = tracer if tracer is not None else get_tracer()
    started = False
    try:
        import jax.profiler

        jax.profiler.start_trace(logdir)
        started = True
    except Exception as e:  # noqa: BLE001 — degrade, don't abort serving
        warnings.warn(
            f"obs.profile_window: jax profiler capture unavailable "
            f"({type(e).__name__}: {e}); running unprofiled",
            stacklevel=3,
        )
    span = tracer.span(name, logdir=logdir, captured=started)
    try:
        with span:
            yield started
    finally:
        if started:
            import jax.profiler

            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                warnings.warn(
                    f"obs.profile_window: stop_trace failed "
                    f"({type(e).__name__}: {e})",
                    stacklevel=3,
                )
