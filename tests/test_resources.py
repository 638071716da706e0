"""repro.analysis.resources: memory-envelope verifier + capacity planner.

Covers the liveness estimator against XLA's own ``memory_analysis()`` on
CPU, envelope resolution, the OOM pre-filter driven through a real
OffloadSession search (pruned and unpruned must commit the same winner),
capacity-planner math cross-checked against ``PagePool`` accounting, the
``--preflight`` CLI rejecting an undersized device, and the shelf
coverage + baseline-portability satellites.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    Diagnostic,
    DeviceEnvelope,
    ResourceHint,
    STATIC_ENVELOPES,
    check_binding_space_resources,
    estimate_memory,
    lint_shelf_coverage,
    plan_serve_capacity,
    resolve_envelope,
)
from repro.analysis.devices import MiB
from repro.analysis.resources import jaxpr_peak_bytes
from repro.core.blocks import FunctionBlockRegistry
from repro.core.planner import BindingSpace, SingleThenCombine
from repro.offload.session import OffloadSession


# -- liveness estimator -------------------------------------------------------


def _chain(x, w):
    for _ in range(4):
        x = jnp.tanh(x @ w)
    return x.sum()


def test_estimator_brackets_xla_memory_analysis():
    """The liveness estimate must be an upper bound on what the program
    irreducibly holds (arguments + outputs) and within a small factor of
    XLA's own compiled accounting — fusion makes XLA leaner, never the
    other way around by more than the chain's live intermediates."""
    x = np.zeros((256, 256), np.float32)
    w = np.zeros((256, 256), np.float32)
    est = estimate_memory(_chain, x, w)

    compiled = jax.jit(_chain).lower(x, w).compile()
    ma = compiled.memory_analysis()
    xla_total = (
        ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
    )
    assert est.peak_live_bytes >= x.nbytes + w.nbytes
    assert est.peak_live_bytes <= 4 * xla_total


def test_estimator_counts_operands_consts_and_intermediates():
    w = jnp.ones((128, 128))  # captured -> const of the traced program

    def f(x):
        return (x @ w).sum()

    x = np.zeros((128, 128), np.float32)
    est = estimate_memory(f, x)
    assert est.operand_bytes == x.nbytes
    assert est.const_bytes == 128 * 128 * 4
    assert est.peak_intermediate_bytes >= 128 * 128 * 4  # the product
    assert est.peak_live_bytes >= est.operand_bytes + est.const_bytes


def test_donation_credit_reduces_peak():
    def f(cache, delta):
        return jax.tree.map(lambda c: c + delta, cache)

    cache = {"k": np.zeros((64, 64), np.float32)}
    est_plain = estimate_memory(f, cache, 1.0)
    est_donated = estimate_memory(f, cache, 1.0, donate_argnums=(0,))
    assert est_donated.donated_bytes == 64 * 64 * 4
    assert est_donated.peak_live_bytes < est_plain.peak_live_bytes


def test_peak_walk_recurses_into_scan_bodies():
    def f(x):
        def body(carry, _):
            y = jnp.tanh(carry @ carry)
            return y, y

        return jax.lax.scan(body, x, None, length=8)

    x = np.zeros((64, 64), np.float32)
    closed = jax.make_jaxpr(f)(x)
    peak = jaxpr_peak_bytes(closed.jaxpr)
    # stacked ys (8, 64, 64) live at the end, plus the body's working set
    assert peak >= 8 * 64 * 64 * 4 + 64 * 64 * 4


# -- device envelopes ---------------------------------------------------------


def test_envelope_resolution():
    tiny = resolve_envelope("tiny-32m")
    assert tiny.memory_bytes == 32 * MiB
    assert tiny is STATIC_ENVELOPES["tiny-32m"]
    custom = DeviceEnvelope("mine", "cpu", 123)
    assert resolve_envelope(custom) is custom
    with pytest.raises(KeyError, match="tiny-32m"):
        resolve_envelope("no-such-board")
    with pytest.raises(TypeError):
        resolve_envelope(3.14)
    probed = resolve_envelope("host")
    assert probed.source == "probed"
    assert probed.memory_bytes > 0
    assert tiny.headroom_bytes(48 * MiB) < 0 < tiny.headroom_bytes(MiB)


# -- OOM pre-filter through a real search -------------------------------------


def _toy_registry():
    reg = FunctionBlockRegistry()
    reg.register("norm", "ref", lambda x: x * 1.0)
    reg.register("norm", "xla", lambda x: x + 0.0)
    reg.register("norm", "pallas", lambda x: x - 0.0)
    return reg


def _toy_space(reg):
    return BindingSpace(
        lambda: (lambda x: reg.call("norm", x)), registry=reg, tag="toy"
    )


#: Synthetic small board plus a hint that makes only the pallas binding
#: blow past it (candidates share the baseline's shapes, so overheads are
#: what differentiates them).
SMALL_ENVELOPE = DeviceEnvelope("test-64m", "cpu", 64 * MiB)
OOM_HINTS = {("norm", "pallas"): ResourceHint(workspace_bytes=128 * MiB)}


class FakeExecutor:
    """Deterministic 'measurements' keyed on the candidate's binding; never
    calls the built fn (mirrors tests/test_analysis.py)."""

    name = "fake"

    def __init__(self, times):
        self.times = times
        self.measured: list = []

    def run(self, jobs, meter=None):
        from repro.core.verify import Measurement

        out = []
        for job in jobs:
            binding = job.space.binding_of(job.candidate)
            self.measured.append(binding)
            out.append(Measurement(
                seconds=self.times[binding.get("norm", "ref")],
                compile_seconds=0.0, repeats=1,
            ))
        return out


TIMES = {"ref": 0.02, "xla": 0.001, "pallas": 5.0}


def _searched_session(resources):
    session = OffloadSession(
        _toy_space(_toy_registry()),
        args=(jnp.ones((4, 4)),),
        strategy=SingleThenCombine(),
        executor=FakeExecutor(TIMES),
        repeats=1,
        resources=SMALL_ENVELOPE if resources else False,
        resource_hints=OOM_HINTS if resources else None,
    )
    session.analyze()
    session.discover()
    plan = session.plan()
    return session, plan


def test_oom_candidate_pruned_with_winner_parity():
    pruned_session, pruned_plan = _searched_session(resources=True)
    control_session, control_plan = _searched_session(resources=False)

    # the envelope pass found the OOM pallas binding and skipped it
    report = pruned_session._report
    assert report.pruned > 0
    assert any("memory" in r for r in report.pruned_reasons.values())
    fake = pruned_session.cache.executor
    assert all(b.get("norm") != "pallas" for b in fake.measured)

    # the control search measured (and rejected on merit) the 5 s pallas
    control_fake = control_session.cache.executor
    assert any(b.get("norm") == "pallas" for b in control_fake.measured)
    assert getattr(control_session._report, "pruned", 0) == 0

    # identical committed winner: pruning changed cost, not the outcome
    assert pruned_plan.mapping == control_plan.mapping == {"norm": "xla"}
    rep = pruned_session.resources_report
    assert rep is not None
    assert ("norm", "pallas") in rep.oom
    assert rep.verdicts[("norm", "xla")].fits
    assert control_session.resources_report is None


def test_resource_report_diagnostics_are_info_with_envelope_platform():
    rep = check_binding_space_resources(
        _toy_space(_toy_registry()),
        (jnp.ones((4, 4)),),
        envelope=SMALL_ENVELOPE,
        hints=OOM_HINTS,
        program="toy",
    )
    diags = rep.diagnostics()
    assert diags and all(d.severity == "info" for d in diags)
    assert all(d.platform == "test-64m" for d in diags)
    oom = [d for d in diags if d.code == "resource-oom"]
    assert [d.subject for d in oom] == ["norm->pallas"]
    assert rep.counts()["oom"] == 1


def test_vmem_tile_verdict():
    env = DeviceEnvelope("tpu-ish", "tpu", 1 << 34, vmem_bytes=16 * MiB)
    rep = check_binding_space_resources(
        _toy_space(_toy_registry()),
        (jnp.ones((4, 4)),),
        envelope=env,
        hints={("norm", "pallas"): ResourceHint(vmem_tile_bytes=32 * MiB)},
    )
    v = rep.verdicts[("norm", "pallas")]
    assert v.status == "vmem-oom"
    assert "VMEM" in rep.oom[("norm", "pallas")]


# -- capacity planner vs PagePool accounting ----------------------------------


def test_capacity_plan_matches_pagepool_math():
    from repro.configs import get_config
    from repro.serve.kv.pool import PagePool, pages_for

    cfg = get_config("llama3.2-1b").reduced()
    n_slots, max_len, page_size = 3, 64, 16
    plan = plan_serve_capacity(
        cfg, n_slots=n_slots, max_len=max_len, page_size=page_size,
        envelope="cpu-host-16g",
    )
    n_pages = n_slots * pages_for(max_len, page_size)  # engine default
    assert plan.n_pages == n_pages
    assert plan.pool_tokens == PagePool(n_pages, page_size).token_capacity
    assert plan.fits and plan.headroom_bytes > 0
    # the linear model reproduces the exact configured cache bytes
    assert plan.cache_bytes > 0
    assert plan.per_page_bytes > 0
    assert plan.max_slots >= n_slots
    assert plan.max_pages >= n_pages


def test_full_config_rejected_by_tiny_envelope():
    """The full (non-reduced) 1B config is ~GiB of params from metadata
    alone — it can never fit the synthetic 32 MiB board, and the verdict
    is a ratchetable warning."""
    from repro.configs import get_config

    cfg = get_config("llama3.2-1b")
    plan = plan_serve_capacity(
        cfg, n_slots=2, max_len=64, envelope="tiny-32m",
    )
    assert not plan.fits
    assert plan.headroom_bytes < 0
    (diag,) = plan.diagnostics(program="serve:llama3.2-1b:capacity")
    assert diag.code == "capacity-oom"
    assert diag.severity == "warning"
    assert diag.platform == "tiny-32m"


def test_engine_plan_capacity_cross_checks_live_pool():
    from repro.configs import get_config
    from repro.serve import ServeEngine

    cfg = get_config("llama3.2-1b").reduced()
    engine = ServeEngine(
        cfg, n_slots=2, max_len=32, page_size=8, seed=0, quiet=True
    )
    plan = engine.plan_capacity("cpu-host-16g")
    assert plan.pool_tokens == engine.kv.pool.token_capacity
    assert plan.fits
    assert engine.lint(envelope="cpu-host-16g") == [
        d for d in engine.lint(envelope="cpu-host-16g")
        if d.code == "capacity-fit"
    ]


# -- preflight CLI ------------------------------------------------------------


def test_preflight_cli_rejects_undersized_device(capsys):
    from repro.launch.serve import main

    rc = main([
        "--arch", "llama3.2-1b", "--envelope", "tiny-32m", "--preflight",
    ])
    assert rc == 2
    out = capsys.readouterr()
    assert "DOES NOT FIT" in out.out
    assert "preflight: FAIL" in out.err


def test_preflight_cli_accepts_fitting_config(capsys):
    from repro.launch.serve import main

    rc = main([
        "--arch", "llama3.2-1b", "--reduced", "--envelope", "cpu-host-16g",
        "--page-size", "16", "--max-len", "64", "--preflight",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "preflight: OK" in out
    assert "FITS" in out


# -- shelf coverage + baseline portability satellites -------------------------


def test_shelf_declares_resource_hints_for_every_impl():
    from repro import kernels

    assert set(kernels.BLOCK_RESOURCES) == set(kernels.SHELF_IMPL_PAIRS)
    assert set(kernels.BLOCK_LEGALITY) == set(kernels.SHELF_IMPL_PAIRS)
    assert lint_shelf_coverage() == []
    # pallas kernels carry a VMEM tile footprint for the fit pass
    assert kernels.BLOCK_RESOURCES[("matmul", "pallas")].vmem_tile_bytes > 0


def test_shelf_coverage_flags_undeclared_impl():
    diags = lint_shelf_coverage(
        impls=(("newkernel", "pallas"),), legality={}, hints={}
    )
    (d,) = diags
    assert d.code == "shelf-coverage"
    assert d.severity == "warning"
    assert "BLOCK_LEGALITY" in d.message and "BLOCK_RESOURCES" in d.message


def test_platform_normalized_out_of_fingerprint():
    """The same finding made on a CPU CI host and a TPU production host
    must ratchet as one baseline entry."""
    on_cpu = Diagnostic("legality", "illegal-binding", "warning", "p",
                        "x->pallas", "msg", platform="cpu")
    on_tpu = Diagnostic("legality", "illegal-binding", "warning", "p",
                        "x->pallas", "msg", platform="tpu")
    assert on_cpu.fingerprint == on_tpu.fingerprint
    assert "cpu" not in on_cpu.fingerprint
    rt = Diagnostic.from_dict(on_cpu.to_dict())
    assert rt == on_cpu
    # legacy payloads without the field still load
    legacy = {k: v for k, v in on_cpu.to_dict().items() if k != "platform"}
    assert Diagnostic.from_dict(legacy).platform == ""
