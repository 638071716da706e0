"""Paged KV-cache tests: allocator accounting, engine parity, over-commit.

The allocator tests are property-style round-trips on the host-side
accounting (no jax involved); the engine tests pin the acceptance
criteria — paged serving is token-for-token identical to contiguous
serving under greedy sampling, admits request mixes the contiguous layout
cannot hold resident, reclaims pages on eviction mid-decode, and resumes
preempted requests token-identically.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.serve import PagePool, PageTable, PoolExhausted, Request, ServeEngine
from repro.serve.kv import pages_for

CFG = get_config("llama3.2-1b").reduced()
# parity tests compare token sequences across different programs: f32
# keeps argmax ties deterministic across program shapes
F32 = dataclasses.replace(CFG, compute_dtype="float32", remat="none")


#: the two paged layouts: GQA rows of KH·D, MLA's latent and rope rows
LAYOUTS = {
    "gqa": F32,
    "mla": dataclasses.replace(
        get_config("deepseek-v2-236b").reduced(), compute_dtype="float32",
        remat="none",
    ),
}


def _prompt(rng, n):
    return rng.integers(0, CFG.vocab_size, n).tolist()


# -- allocator accounting ------------------------------------------------------


def test_page_pool_alloc_free_roundtrip():
    pool = PagePool(n_pages=8, page_size=16)
    assert pool.free_pages == 8 and pool.used_pages == 0
    assert pool.null_page == 8
    a = pool.alloc(3)
    b = pool.alloc(2)
    assert sorted(a + b) == [0, 1, 2, 3, 4]  # ordered first allocation
    assert pool.free_pages == 3 and pool.used_pages == 5
    pool.free(a)
    # deterministic LIFO reuse: the pages just freed come back first,
    # last-freed first
    c = pool.alloc(3)
    assert c == a[::-1]
    pool.free(b + c)
    pool.check_leaks()
    assert pool.free_pages == 8 and pool.used_pages == 0
    assert pool.peak_used == 5


def test_page_pool_exhaustion_and_double_free():
    pool = PagePool(n_pages=4, page_size=8)
    held = pool.alloc(4)
    with pytest.raises(PoolExhausted):
        pool.alloc(1)
    assert pool.used_pages == 4  # failed alloc has no side effects
    pool.free(held[:1])
    with pytest.raises(ValueError, match="double free|not held"):
        pool.free(held[:1])  # double free
    with pytest.raises(ValueError, match="not held"):
        pool.free([pool.null_page])  # the null page is never allocatable
    pool.free(held[1:])
    pool.check_leaks()


def test_page_table_slot_lifecycle_and_stats():
    table = PageTable(n_slots=3, max_pages=4, pool=PagePool(12, 8))
    assert pages_for(17, 8) == 3
    table.alloc_slot(0, 17)  # 3 pages, 17 resident
    table.alloc_slot(2, 8)  # exactly one full page
    with pytest.raises(ValueError, match="already holds"):
        table.alloc_slot(0, 1)
    arr = table.array()
    assert arr.shape == (3, 4)
    assert list(arr[1]) == [table.pool.null_page] * 4  # empty slot -> null
    assert (arr[0, :3] != table.pool.null_page).all()
    assert arr[0, 3] == table.pool.null_page
    # append across a boundary: ensure() grows only when capacity runs out
    assert table.ensure(0, 24) == []  # 3 pages already hold 24
    grown = table.ensure(0, 25)
    assert len(grown) == 1 and table.capacity(0) == 32
    with pytest.raises(ValueError, match="max_pages"):
        table.ensure(0, 40)  # beyond the slot's table row
    # stats: slot0 holds 25/32, slot2 holds 8/8
    assert table.resident_tokens == 33
    assert table.partial_pages == 1  # only slot0's last page is partial
    assert 0 < table.stranded_pct < 100
    stats = table.stats()
    assert stats["used_pages"] == 5
    assert stats["utilization_pct"] == pytest.approx(5 / 12 * 100)
    # eviction returns everything; no leaked pages, table row nulls out
    table.free_slot(0)
    table.free_slot(2)
    table.pool.check_leaks()
    assert table.pool.used_pages == 0
    assert (table.array() == table.pool.null_page).all()


# -- engine parity (acceptance criteria) ---------------------------------------


def _run_trace(engine, prompts, gens, max_steps=800):
    ids = [
        engine.submit(Request(p, max_new_tokens=g))
        for p, g in zip(prompts, gens)
    ]
    engine.run_until_idle(max_steps=max_steps)
    return [engine.completions[i].tokens for i in ids]


def test_paged_matches_contiguous_greedy_staggered(rng):
    """The acceptance bar: a staggered 4-request greedy trace is
    token-for-token identical between the contiguous and paged engines —
    across page boundaries, slot reuse and mixed lengths — and the
    degenerate page_size=max_len case matches too."""
    prompts = [_prompt(rng, n) for n in (5, 9, 4, 7)]
    gens = (6, 3, 8, 2)
    expected = _run_trace(
        ServeEngine(F32, n_slots=2, max_len=64, seed=0), prompts, gens
    )
    paged = ServeEngine(F32, n_slots=2, max_len=64, seed=0, page_size=8)
    assert _run_trace(paged, prompts, gens) == expected
    assert paged.kv.pool.used_pages == 0  # everything reclaimed at idle
    paged.kv.pool.check_leaks()
    degenerate = ServeEngine(
        F32, n_slots=2, max_len=64, seed=0, page_size=64
    )
    assert _run_trace(degenerate, prompts, gens) == expected
    assert degenerate.kv.max_pages == 1  # one page per slot == contiguous


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_paged_decode_matches_contiguous_over_32_steps(layout, rng):
    """Decoding through the in-place page writes gives the contiguous
    cache's tokens over 32 steps, for GQA and MLA pools alike."""
    cfg = LAYOUTS[layout]
    prompts = [_prompt(rng, n) for n in (5, 11, 3)]
    gens = (32, 32, 20)
    expected = _run_trace(
        ServeEngine(cfg, n_slots=3, max_len=64, seed=0), prompts, gens
    )
    paged = ServeEngine(cfg, n_slots=3, max_len=64, seed=0, page_size=8)
    assert _run_trace(paged, prompts, gens) == expected


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_decode_step_writes_only_its_token_rows(layout, rng):
    """One engine decode step changes each layer's pool only at each
    active slot's (page, row) of that step — one of them the first row of
    a page the step opens — and an idle slot writes only the null page:
    every other row of every layer is bit-identical."""
    cfg = LAYOUTS[layout]
    ps = 4
    engine = ServeEngine(cfg, n_slots=3, max_len=32, seed=0, page_size=ps)
    for n in (5, 7):  # 7: the second decode step writes position 8
        engine.submit(Request(_prompt(rng, n), max_new_tokens=6))
    engine.step()  # both admitted, one decode step
    assert len(engine.scheduler.active) == 2  # slot 2 stays idle
    before = jax.tree.map(np.array, engine.cache)
    index = engine._dev_index.copy()
    engine.step()
    after = jax.tree.map(np.array, engine.cache)
    table, null = engine.kv.array(), engine.kv.pool.null_page
    want = {
        (int(table[slot, index[slot] // ps]), int(index[slot] % ps))
        for slot in engine.scheduler.active
    }
    assert (int(table[1, 2]), 0) in want  # the page this step opened
    for key, group in before.items():
        if key == "index":
            continue
        for leaf, old in group.items():
            new = after[key][leaf]
            assert new.shape == old.shape
            assert new.shape[1:3] == (null + 1, ps)  # (L, P_total, ps, W)
            changed = (old != new).any(axis=-1)  # (L, P_total, ps)
            changed[:, null] = False
            rows = {(int(p), int(r)) for _, p, r in np.argwhere(changed)}
            assert rows == want, (key, leaf)
            for p, r in want:  # ...in every layer of the group
                assert changed[:, p, r].all(), (key, leaf, p, r)


def test_paged_pool_rows_are_token_major():
    """A page is ``(page_size, W)`` token rows, a row holding every KV
    head side by side: GQA ``KH * D``, MLA's latent ``r`` and rope
    ``dr`` pools; the null page is the pool's last."""
    from repro.models import lm

    for cfg in LAYOUTS.values():
        cache = jax.eval_shape(
            lambda cfg=cfg: lm.init_cache(cfg, 2, 32, page_size=8, n_pages=5)
        )
        if cfg.mla is None:
            widths = {"k": cfg.n_kv_heads * cfg.d_head,
                      "v": cfg.n_kv_heads * cfg.d_head}
        else:
            widths = {"c": cfg.mla.kv_lora_rank,
                      "kr": cfg.mla.qk_rope_head_dim}
        for g in lm.groups_of(cfg):
            assert {k: a.shape for k, a in cache[g.key].items()} == {
                k: (g.count, 6, 8, w) for k, w in widths.items()
            }


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_insert_pages_writes_token_major_pages(layout, rng):
    """A prefilled batch-1 contiguous cache leaf lands in the slot's
    pages token-major: position ``t`` at row ``t % ps`` of page
    ``page_ids[t // ps]``, its per-token axes flattened; the null page
    absorbs the unallocated tail and no other page changes."""
    import jax.numpy as jnp

    from repro.models import lm
    from repro.models.attention import cache_seq_axes, insert_pages

    cfg, ps, max_len = LAYOUTS[layout], 4, 16
    pools = lm.init_cache(cfg, 2, max_len, page_size=ps, n_pages=6)
    b1 = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        lm.init_cache(cfg, 1, max_len),
    )
    page_ids = jnp.asarray([4, 1, 6, 6], jnp.int32)  # 2 pages, null tail
    for key, group in pools.items():
        if key == "index":
            continue
        for leaf, pool in group.items():
            axis = cache_seq_axes(cfg)[leaf]
            out = np.asarray(
                insert_pages(pool, b1[key][leaf], page_ids, axis)
            )
            src = np.moveaxis(np.asarray(b1[key][leaf])[:, 0], axis, 1)
            src = src.reshape(src.shape[0], max_len, -1)  # (L, S, W)
            for t in range(2 * ps):
                np.testing.assert_array_equal(
                    out[:, int(page_ids[t // ps]), t % ps], src[:, t]
                )
            untouched = [0, 2, 3, 5]
            np.testing.assert_array_equal(
                out[:, untouched], np.asarray(pool)[:, untouched]
            )


def test_paged_admits_mix_contiguous_capacity_defers(rng):
    """Capacity decoupling: with the same token memory (256), the paged
    engine keeps 8 short requests resident at once where the contiguous
    layout only fits 4 slots of max_len=64."""
    prompts = [_prompt(rng, 20) for _ in range(8)]
    gens = [8] * 8
    # contiguous: 256 tokens of memory = 4 slots -> concurrency capped at 4
    cont = ServeEngine(F32, n_slots=4, max_len=64, seed=0)
    _run_trace(cont, prompts, gens)
    assert cont.stats.max_active == 4
    # paged: same 256 tokens = 16 pages shared by 8 slots; each request
    # needs <= 28 tokens = 2 pages, so all 8 fit resident simultaneously
    paged = ServeEngine(
        F32, n_slots=8, max_len=64, seed=0, page_size=16, n_pages=16
    )
    _run_trace(paged, prompts, gens)
    assert paged.stats.max_active == 8
    assert paged.stats.preemptions == 0  # it genuinely fit, no thrashing
    assert paged.kv.pool.peak_used <= 16


def test_eviction_mid_decode_reclaims_pages(rng):
    """Finished requests return their pages while neighbours keep
    decoding: peak pool usage stays well under the sum of all requests'
    worst cases, and the pool drains to zero at idle."""
    engine = ServeEngine(F32, n_slots=2, max_len=64, seed=0, page_size=8)
    seen_used = []
    ids = [
        engine.submit(Request(_prompt(rng, p), max_new_tokens=g))
        for p, g in [(5, 12), (9, 2), (6, 9), (12, 3), (4, 6)]
    ]
    while engine.scheduler.has_work:
        engine.step()
        seen_used.append(engine.kv.pool.used_pages)
    assert len(engine.completions) == len(ids)
    # mid-flight the pool was in use, at idle everything was reclaimed
    assert max(seen_used) >= 2
    assert engine.kv.pool.used_pages == 0
    engine.kv.pool.check_leaks()
    # 5 requests churned through 2 slots: eviction freed pages mid-run,
    # otherwise the pool (16 pages) could not have served sum(worst cases)
    assert engine.stats.slot_reuses >= 3


def test_preemption_resumes_token_identically(rng):
    """An over-committed pool forces preemption mid-decode; the preempted
    request re-prefills (prompt + generated tokens) and continues with
    the exact token sequence of an unpressured run."""
    prompts = [_prompt(rng, 20) for _ in range(3)]
    gens = [12] * 3
    relaxed = ServeEngine(F32, n_slots=3, max_len=64, seed=0, page_size=8)
    expected = _run_trace(relaxed, prompts, gens)
    assert relaxed.stats.preemptions == 0
    # 6 pages = 48 tokens for 3 requests needing 32 each at the end
    tight = ServeEngine(
        F32, n_slots=3, max_len=64, seed=0, page_size=8, n_pages=6
    )
    got = _run_trace(tight, prompts, gens, max_steps=2000)
    assert tight.stats.preemptions > 0
    assert got == expected
    tight.kv.pool.check_leaks()
    assert tight.kv.pool.used_pages == 0


def test_submit_rejects_request_larger_than_pool(rng):
    engine = ServeEngine(
        CFG, n_slots=2, max_len=64, seed=0, page_size=8, n_pages=4
    )
    with pytest.raises(ValueError, match="never be resident"):
        engine.submit(Request(_prompt(rng, 30), max_new_tokens=10))
    # a request that fits the pool is accepted
    engine.submit(Request(_prompt(rng, 20), max_new_tokens=10))
    assert len(engine.run_until_idle(max_steps=100)) == 1


# -- chunked prefill -----------------------------------------------------------


def test_chunked_prefill_parity_and_interleaving(rng):
    """A long prompt split into chunks produces the identical greedy
    tokens, runs multiple prefill program calls, and — the TTFT point —
    an in-flight short request keeps decoding between the chunks."""
    long_prompt = _prompt(rng, 40)
    short_prompt = _prompt(rng, 4)
    base = ServeEngine(F32, n_slots=2, max_len=64, seed=0)
    expected = _run_trace(base, [long_prompt], [6])

    for kw in ({}, {"page_size": 8}):
        engine = ServeEngine(
            F32, n_slots=2, max_len=64, seed=0, prefill_chunk=8,
            max_tokens_per_step=10, **kw
        )
        short_id = engine.submit(Request(short_prompt, max_new_tokens=20))
        engine.step()  # short request admitted and decoding
        long_id = engine.submit(Request(long_prompt, max_new_tokens=6))
        decode_during_chunks = 0
        while long_id not in engine.completions:
            events = engine.step()
            if engine.scheduler.active and any(
                t.request_id == short_id
                for t in events
                if hasattr(t, "phase") and t.phase == "decode"
            ) and long_id not in engine.completions and (
                len(engine._prefilling) > 0
            ):
                decode_during_chunks += 1
        engine.run_until_idle(max_steps=500)
        assert engine.completions[long_id].tokens == expected[0]
        assert engine.stats.prefill_chunks >= 5  # 40 tokens / 8 per chunk
        # the short request decoded while the long prompt was mid-prefill
        assert decode_during_chunks > 0


def test_chunked_prefill_partial_tail_at_cache_end(rng):
    """A prompt whose padded final chunk would overrun max_len: the tail
    chunk must run at its exact width — a chunk-padded write at the cache
    end clamps backward and corrupts already-written prompt K/V."""
    prompt = _prompt(rng, 63)  # 63 = 6*10 + 3: partial tail at row 60/64
    base = ServeEngine(F32, n_slots=1, max_len=64, seed=0)
    expected = _run_trace(base, [prompt], [1])
    for kw in ({}, {"page_size": 8}):
        engine = ServeEngine(
            F32, n_slots=1, max_len=64, seed=0, prefill_chunk=10, **kw
        )
        assert _run_trace(engine, [prompt], [1]) == expected


def test_chunked_prefill_rejected_for_ssm():
    with pytest.raises(ValueError, match="SSM"):
        ServeEngine(get_config("mamba2-2.7b").reduced(), prefill_chunk=8)


# -- metrics -------------------------------------------------------------------


def test_metrics_report_pool_health(rng):
    paged = ServeEngine(CFG, n_slots=2, max_len=64, seed=0, page_size=8)
    paged.submit(Request(_prompt(rng, 9), max_new_tokens=4))
    paged.submit(Request(_prompt(rng, 5), max_new_tokens=6))
    paged.run_until_idle(max_steps=100)
    m = paged.metrics()
    assert m["mode"] == "paged"
    kv = m["kv"]
    assert kv["n_pages"] == 16 and kv["page_size"] == 8
    assert kv["peak_used_pages"] >= 2
    assert kv["used_pages"] == 0  # idle again
    assert 0 < m["mean_utilization_pct"] <= 100
    assert 0 <= m["mean_stranded_pct"] < 100
    assert 0 <= m["mean_fragmentation_pct"] <= 100

    cont = ServeEngine(CFG, n_slots=2, max_len=64, seed=0)
    cont.submit(Request(_prompt(rng, 9), max_new_tokens=4))
    cont.run_until_idle(max_steps=100)
    m = cont.metrics()
    assert m["mode"] == "contiguous"
    assert m["kv"]["token_capacity"] == 128
    # the contiguous layout strands most of the slot on short requests —
    # the number the page pool exists to reclaim
    assert m["mean_stranded_pct"] > 50


def test_abstract_cache_lowers_paged_decode_program():
    """The dry-run contract: the paged abstract cache (pool leaves + the
    pages operand) must lower the exact decode program the engine runs."""
    import jax

    from repro.configs.base import ShapeConfig
    from repro.launch import steps
    from repro.models import lm
    from repro.models import params as pm

    shape = ShapeConfig("decode_paged", 64, 4, "decode")
    cache = steps.abstract_cache(CFG, shape, page_size=16, n_pages=16)
    assert cache["pages"].shape == (4, 4)  # (n_slots, max_pages)
    params = pm.abstract_params(lm.build_metas(CFG))
    out = jax.eval_shape(
        steps.make_decode_step(CFG), params, cache,
        steps.input_specs(CFG, shape),
    )
    assert out[0].shape[0] == 4  # (B, V) logits
    assert out[1]["index"].shape == (4,)


# -- the scalar-index fallback is gone -----------------------------------------


def test_scalar_index_cache_rejected():
    import jax.numpy as jnp

    from repro.models import lm

    params = lm.init_params(CFG, 0)
    cache = lm.init_cache(CFG, 2, 16)
    cache["index"] = jnp.asarray(3, jnp.int32)  # legacy scalar position
    with pytest.raises(ValueError, match="per-slot"):
        lm.decode_step(
            params, jnp.zeros((2, 1), jnp.int32), CFG, cache
        )
