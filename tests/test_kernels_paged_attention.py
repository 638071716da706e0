"""Fused Pallas paged-attention block: parity, shelf metadata, planner
search, and serve-level token identity.

The parity tests run the fused kernel in interpret mode (the CPU-CI
path) and the XLA block walk against an independent float64 dense
oracle, across decode (S=1) and extend (S>1) chunks, GQA and MLA
layouts, ragged per-slot lengths, page boundaries, final partial pages,
null-page table entries and idle slots.  The integration tests
pin the acceptance criteria: both shelf targets carry legality/resource
metadata regardless of import order, the zoo decode search prunes the
TPU-only kernel statically on CPU while still committing a plan that
binds the block, the fused program's peak live bytes sit strictly below
the XLA walk's at serving-scale shapes, and a served greedy trace is
token-for-token identical under ``decode_impl="pallas"``.
"""

import dataclasses
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import paged_attention as paged
from repro.kernels.paged_attention import (
    block_tokens,
    paged_attention_pallas,
    paged_attention_xla,
    scatter_pages,
    walk_blocks,
    walk_plan,
)
from repro.serve import Request, ServeEngine

CFG = get_config("llama3.2-1b").reduced()
# token-identity comparisons across different decode programs: f32 keeps
# greedy argmax ties deterministic (same convention as test_serve_kv)
F32 = dataclasses.replace(CFG, compute_dtype="float32", remat="none")


# -- paged operand builder + dense float64 oracle ------------------------------


#: the stacked pools hold two layers; the kernels read layer 1
LAYERS, LAYER = 2, 1


def _pool(rng, n_total, ps, width):
    """Stacked token-major pools ``(LAYERS, n_total, ps, width)`` whose
    other layer and null page are poisoned: reading either shows."""
    pool = rng.standard_normal((LAYERS, n_total, ps, width))
    pool = pool.astype(np.float32)
    pool[:LAYER] = 1e6
    pool[:, n_total - 1] = 1e6  # masked rows must never contribute
    return pool


def _paged_case(rng, *, b, h, kh, s, dk, dv, ps, mp, lengths, dr=0):
    """Identity-table paged operands with per-slot logical lengths.

    ``lengths[i]`` is slot ``i``'s history length (== the first new-token
    position); table entries past the pages needed to hold
    ``lengths[i] + s`` tokens point at the null page.  The pools are
    stacked token-major ``(LAYERS, P_total, ps, KH * D)`` and read at
    ``LAYER``; the null page and the other layer are poisoned to catch
    any unmasked or misaddressed read.
    """
    n_pages = b * mp
    null = n_pages
    k_pool = _pool(rng, n_pages + 1, ps, kh * dk)
    v_pool = _pool(rng, n_pages + 1, ps, kh * dv)
    q = rng.standard_normal((b, h, s, dk)).astype(np.float32)
    pages = np.arange(n_pages, dtype=np.int32).reshape(b, mp)
    for i, ln in enumerate(lengths):
        used = -(-(ln + s) // ps)
        pages[i, used:] = null
    index = np.asarray(lengths, np.int32)
    case = {
        "q": jnp.asarray(q),
        "k_pool": jnp.asarray(k_pool),
        "v_pool": jnp.asarray(v_pool),
        "pages": jnp.asarray(pages),
        "index": jnp.asarray(index),
        "layer": jnp.asarray(LAYER, jnp.int32),
    }
    if dr:
        kr_pool = _pool(rng, n_pages + 1, ps, dr)
        case["q_rope"] = jnp.asarray(
            rng.standard_normal((b, h, s, dr)).astype(np.float32)
        )
        case["kr_pool"] = jnp.asarray(kr_pool)
        case["scale"] = 1.0 / float(np.sqrt(dk + dr))
    return case


def _oracle(case):
    """Dense float64 reference: gather every page, mask by position."""
    q = np.asarray(case["q"], np.float64)
    b, h, s, dk = q.shape
    k_pool = np.asarray(case["k_pool"], np.float64)
    v_pool = np.asarray(case["v_pool"], np.float64)
    pages = np.asarray(case["pages"])
    index = np.asarray(case["index"])
    layer = int(case["layer"])
    ps = k_pool.shape[2]
    kh = k_pool.shape[-1] // dk
    g = h // kh

    def view(pool):  # the layer's (P, ps, kh*d) -> (b, kh, mp*ps, d)
        v = pool[layer][pages].reshape(b, -1, kh, pool.shape[-1] // kh)
        return np.moveaxis(v, 2, 1)

    kv, vv = view(k_pool), view(v_pool)
    qg = q.reshape(b, kh, g, s, dk)
    sc = np.einsum("bkgqd,bktd->bkgqt", qg, kv)
    if "q_rope" in case:
        qr = np.asarray(case["q_rope"], np.float64)
        qr = qr.reshape(b, kh, g, s, -1)
        sc = (sc + np.einsum(
            "bkgqd,bktd->bkgqt", qr, view(np.asarray(case["kr_pool"],
                                                     np.float64))
        )) * case["scale"]
    else:
        sc = sc / np.sqrt(dk)
    pos = np.arange(kv.shape[2])
    qpos = index[:, None] + np.arange(s)
    mask = pos[None, None, None, None, :] <= qpos[:, None, None, :, None]
    sc = np.where(mask, sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    o = np.einsum("bkgqt,bktd->bkgqd", p, vv)
    return o.reshape(b, h, s, vv.shape[-1])


# lengths exercise: index 0 (empty history), a write landing exactly on a
# page boundary, a final partial page, and a fully ragged mix
GQA_CASES = [
    # (s, lengths) with ps=8, mp=4
    (1, (15, 8)),   # decode: last slot of page 2 / first slot of page 2
    (1, (0, 31)),   # decode: empty history / final table slot
    (4, (12, 0)),   # extend: mid-page / from scratch
    (4, (6, 20)),   # extend: chunk crosses a page boundary
]


@pytest.mark.parametrize("s,lengths", GQA_CASES)
def test_paged_parity_gqa(s, lengths, rng):
    case = _paged_case(
        rng, b=2, h=4, kh=2, s=s, dk=32, dv=32, ps=8, mp=4, lengths=lengths
    )
    want = _oracle(case)
    got_xla = paged_attention_xla(**case)
    got_pallas = paged_attention_pallas(**case, interpret=True)
    np.testing.assert_allclose(np.asarray(got_xla), want,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_pallas), want,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("s,lengths", [(1, (15, 8)), (4, (6, 20))])
def test_paged_parity_mla(s, lengths, rng):
    # MLA layout: shared latent K/V (kh=1), decoupled rope scores folded
    # in before the softmax, explicit 1/sqrt(dk+dr) scale
    case = _paged_case(
        rng, b=2, h=4, kh=1, s=s, dk=32, dv=32, ps=8, mp=4,
        lengths=lengths, dr=16,
    )
    want = _oracle(case)
    got_xla = paged_attention_xla(**case)
    got_pallas = paged_attention_pallas(**case, interpret=True)
    np.testing.assert_allclose(np.asarray(got_xla), want,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_pallas), want,
                               rtol=1e-4, atol=1e-4)


def test_paged_parity_uneven_final_page(rng):
    # mp*ps leaves the final page partially filled at max length
    case = _paged_case(
        rng, b=2, h=4, kh=2, s=1, dk=32, dv=32, ps=8, mp=3,
        lengths=(17, 23),
    )
    np.testing.assert_allclose(
        np.asarray(paged_attention_pallas(**case, interpret=True)),
        _oracle(case), rtol=1e-4, atol=1e-4,
    )


def test_paged_block_call_dispatches(rng):
    # the registered shelf entries resolve to the same numerics
    from repro.core import blocks

    case = _paged_case(
        rng, b=2, h=4, kh=2, s=1, dk=32, dv=32, ps=8, mp=2, lengths=(5, 9)
    )
    want = _oracle(case)
    for target in ("xla", "pallas"):
        with blocks.bind({"paged_attention": target}):
            got = blocks.call("paged_attention", *(
                case[k]
                for k in ("q", "k_pool", "v_pool", "pages", "index", "layer")
            ))
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-4, atol=1e-4)


# -- the XLA target's walk over live page blocks -------------------------------

#: a slot that holds no pages: a null-page row, its ``index`` left far past
#: ``max_len`` by the decode steps it sat out
IDLE = 10_000

# (lengths, s): one batch whose walk stops short of max_pages, one whose
# longest slot reaches into a last block that overhangs it
WALK_CASES = {
    "prefix": (37, IDLE, 50, IDLE, 0),
    "overhang": (9, IDLE, 125, 3),
}


def _walk_case(rng, monkeypatch, name, s, layout):
    """Operands for the walk at ``max_pages`` 16 with blocks of 3 pages (a
    block count that does not divide ``max_pages``), idle slots given
    null-page rows and a stale ``index``; returns (case, live rows)."""
    lengths = [0 if n == IDLE else min(n, 128 - s) for n in WALK_CASES[name]]
    kh, dr = (2, 0) if layout == "gqa" else (1, 16)
    case = _paged_case(
        rng, b=len(lengths), h=4, kh=kh, s=s, dk=32, dv=32, ps=8, mp=16,
        lengths=lengths, dr=dr,
    )
    monkeypatch.setattr(paged, "BLOCK_BYTES", 3 * 8 * kh * 32 * 4)
    live = [i for i, n in enumerate(WALK_CASES[name]) if n != IDLE]
    pages = np.array(case["pages"])
    index = np.array(case["index"])
    for i in set(range(len(lengths))) - set(live):
        pages[i] = case["k_pool"].shape[1] - 1
        index[i] = IDLE
    case["pages"], case["index"] = jnp.asarray(pages), jnp.asarray(index)
    return case, live


@pytest.mark.parametrize("layout", ["gqa", "mla"])
@pytest.mark.parametrize("s", [1, 3])
@pytest.mark.parametrize("name", sorted(WALK_CASES))
def test_block_walk_matches_oracle(name, s, layout, rng, monkeypatch):
    case, live = _walk_case(rng, monkeypatch, name, s, layout)
    assert block_tokens(8, case["k_pool"].shape[-1] * 4, 16) == 24
    got = np.asarray(jax.jit(paged_attention_xla)(**case))
    np.testing.assert_allclose(got[live], _oracle(case)[live],
                               rtol=1e-5, atol=1e-5)
    # an idle slot reads nothing: its null-page row holds no block
    idle = sorted(set(range(got.shape[0])) - set(live))
    np.testing.assert_array_equal(got[idle], 0.0)


@pytest.mark.parametrize("layout", ["gqa", "mla"])
@pytest.mark.parametrize("s", [1, 3])
@pytest.mark.parametrize("name", sorted(WALK_CASES))
def test_walk_trips_match_host_mirror(name, s, layout, rng, monkeypatch):
    """The trips the kernel's loop makes, computed on the device from its
    own operands, equal the host count the serve engine feeds
    ``serve_paged_walk_blocks_total`` with: ``walk_blocks`` over NumPy
    mirrors, the block sized from the pool's page as the engine sizes it."""
    case, live = _walk_case(rng, monkeypatch, name, s, layout)
    pool = case["k_pool"]
    trips = jax.jit(
        lambda pool, pages, index: walk_plan(pool, pages, index, s)[1].max()
    )(pool, case["pages"], case["index"])
    # the engine sizes the block from a layer's page: (page, row width)
    _, n_total, ps, width = pool.shape
    block = block_tokens(ps, width * pool.dtype.itemsize, 16)
    host = walk_blocks(
        np.asarray(case["index"]), np.asarray(case["pages"]), s,
        page_size=ps, block=block, null_page=n_total - 1,
    )
    assert int(trips) == int(host.max())
    longest = max(int(case["index"][i]) for i in live) + s
    assert int(host.max()) == -(-longest // block)
    assert host[[i for i in range(len(host)) if i not in live]].max() == 0


@pytest.mark.parametrize("layout", ["gqa", "mla"])
def test_block_walk_all_idle_batch_is_finite(layout, rng, monkeypatch):
    # every row a null-page row: the walk makes no trip, and the
    # zero-sum guard keeps 0/0 out of the result
    case, _ = _walk_case(rng, monkeypatch, "prefix", 1, layout)
    null = case["k_pool"].shape[1] - 1
    case["pages"] = jnp.full_like(case["pages"], null)
    case["index"] = jnp.full_like(case["index"], IDLE)
    got = np.asarray(jax.jit(paged_attention_xla)(**case))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, 0.0)


def test_engine_index_mirror_tracks_device(rng):
    """The serve engine's host mirror of the cache's per-row write
    positions (which feeds the walk counters) matches the device's after
    every step: admissions, finished slots whose rows keep advancing, and
    chunked prefills alike; the walk never counts more than a full one."""
    engine = ServeEngine(F32, n_slots=3, max_len=32, seed=0, page_size=4,
                         prefill_chunk=6)
    for n, g in ((5, 3), (13, 6), (3, 9), (9, 2)):
        engine.submit(Request(rng.integers(0, CFG.vocab_size, n).tolist(),
                              max_new_tokens=g))
    while engine.scheduler.has_work:
        engine.step()
        np.testing.assert_array_equal(
            engine._dev_index, np.asarray(engine.cache["index"])
        )
    walked = engine.registry.counter("serve_paged_walk_blocks_total")
    full = engine.registry.counter("serve_paged_walk_blocks_full_total")
    assert 0 < walked.value <= full.value



def test_scatter_chunk_matches_token_scatter(rng):
    """A 3-token chunk written at once equals three one-token writes, and
    lands token-major in its layer only: row ``(index + i) % 4`` of page
    ``pages[b, (index + i) // 4]``."""
    pool = jnp.zeros((2, 5, 4, 16), jnp.float32)  # (L, P, ps, KH*D)
    pages = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    index = jnp.asarray([3, 1], jnp.int32)  # chunk crosses a page boundary
    val = jnp.asarray(rng.standard_normal((2, 3, 16)), jnp.float32)
    got = scatter_pages(pool, val, pages, index, 1)
    want = pool
    for i in range(3):
        want = scatter_pages(want, val[:, i:i + 1], pages, index + i, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got = np.asarray(got)
    np.testing.assert_array_equal(got[0], 0.0)
    for b in range(2):
        for i in range(3):
            t = int(index[b]) + i
            np.testing.assert_array_equal(
                got[1, int(pages[b, t // 4]), t % 4], np.asarray(val[b, i])
            )


# -- shelf metadata: import-order independence + coverage ----------------------

_SNAPSHOT_SRC = """
import json
{imports}
from repro import kernels
from repro.core import blocks

print(json.dumps({{
    "fingerprint": kernels.SHELF_FINGERPRINT,
    "legality": sorted(",".join(k) for k in kernels.BLOCK_LEGALITY),
    "resources": sorted(",".join(k) for k in kernels.BLOCK_RESOURCES),
    "attention_xla_module": blocks.registry.implementation(
        "attention", "xla").fn.__module__,
    "paged_targets": sorted(blocks.registry.targets("paged_attention")),
}}))
"""


def _shelf_snapshot(imports):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]
    )
    out = subprocess.run(
        [sys.executable, "-c", _SNAPSHOT_SRC.format(imports=imports)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout)


def test_shelf_independent_of_import_order():
    """models.attention first vs kernels first must produce the same
    shelf: same fingerprint, same metadata keys, and attention/xla
    resolving to the kernels-owned implementation (the historical bug:
    whichever module imported second silently re-registered it)."""
    a = _shelf_snapshot("import repro.kernels\nimport repro.models.attention")
    b = _shelf_snapshot("import repro.models.attention\nimport repro.kernels")
    assert a == b
    assert a["attention_xla_module"] == "repro.kernels.attention_xla"
    assert "paged_attention,xla" in a["legality"]
    assert "paged_attention,pallas" in a["legality"]
    assert "paged_attention,xla" in a["resources"]
    assert "paged_attention,pallas" in a["resources"]
    assert a["paged_targets"] == ["pallas", "xla"]


def test_shelf_coverage_lint_passes():
    from repro.analysis.resources import lint_shelf_coverage

    assert lint_shelf_coverage() == []


def test_pallas_target_legality_is_tpu_only():
    from repro import kernels

    cons = kernels.BLOCK_LEGALITY[("paged_attention", "pallas")]
    assert cons.requires_platform == ("tpu",)
    # the XLA walk runs anywhere — it is the measured CPU baseline
    assert not kernels.BLOCK_LEGALITY[
        ("paged_attention", "xla")].requires_platform


# -- static resources: the fused kernel holds no gathered block ----------------


def test_fused_decode_peak_live_bytes_below_gather():
    """At serving-scale shapes the fused program's peak live bytes sit
    strictly below the XLA walk's: the walk gathers a block of pages per
    slot (at these widths one block spans every page), the fused kernel
    reads one page at a time in VMEM."""
    from repro.analysis.resources import estimate_memory
    from repro.core import blocks
    from repro.offload.zoo import _cell_target

    builder, args, _ = _cell_target(
        "llama3.2-1b", "decode", reduced=True, layers=2, batch=4,
        seq=256, seed=0,
    )
    peaks = {}
    for target in ("xla", "pallas"):
        with blocks.bind({"paged_attention": target}):
            peaks[target] = estimate_memory(builder(), *args).peak_live_bytes
    assert peaks["pallas"] < peaks["xla"], peaks


# -- planner: the decode cell searches the paged block -------------------------


def test_zoo_decode_plan_searches_paged_block(tmp_path):
    """The zoo decode cell exposes ``paged_attention`` as a search axis:
    on CPU the legality pass prunes every pallas candidate statically
    (the fused kernel is TPU-only), the measured winner binds the XLA
    implementation, and the committed plan records the block."""
    from repro.offload.zoo import plan_zoo

    results = plan_zoo(
        str(tmp_path), [("llama3.2-1b", "decode")],
        targets=("xla", "pallas"), reduced=True, layers=1, batch=2,
        seq=8, legality=True,
    )
    r = results[("llama3.2-1b", "decode")]
    assert r.mapping["paged_attention"] == "xla"
    assert r.report is not None and r.report.pruned > 0


# -- serve-level: --decode-impl forces the fused kernel ------------------------


def _run_trace(engine, prompts, gens, max_steps=800):
    ids = [
        engine.submit(Request(p, max_new_tokens=g))
        for p, g in zip(prompts, gens)
    ]
    engine.run_until_idle(max_steps=max_steps)
    return [engine.completions[i].tokens for i in ids]


def test_serve_decode_impl_token_identical(rng):
    """A greedy paged trace under ``decode_impl="pallas"`` (interpret
    mode on CPU) is token-for-token identical to the default binding —
    the acceptance bar for trusting the fused kernel in the hot loop."""
    prompts = [
        rng.integers(0, CFG.vocab_size, n).tolist() for n in (5, 9, 4)
    ]
    gens = (6, 4, 5)
    traces = {
        impl: _run_trace(
            ServeEngine(F32, n_slots=3, max_len=32, seed=0, page_size=4,
                        decode_impl=impl),
            prompts, gens,
        )
        for impl in ("auto", "pallas")
    }
    assert traces["pallas"] == traces["auto"]


def test_engine_decode_impl_validation():
    with pytest.raises(ValueError, match="decode_impl"):
        ServeEngine(F32, n_slots=2, max_len=32, seed=0, page_size=4,
                    decode_impl="cuda")
    with pytest.raises(ValueError, match="page"):
        ServeEngine(F32, n_slots=2, max_len=32, seed=0,
                    decode_impl="pallas")  # paged cache required
