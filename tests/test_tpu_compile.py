"""Compile the main-path Pallas kernels for a described TPU v5e, at real widths.

No chip is needed: the TPU compiler is installed and compiles for a topology
that is described, not attached.  Each test lowers and compiles one kernel
(through its public ``ops`` wrapper where one exists) and checks that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).  What the
chip's compiler refuses (blocks that do not tile, too much VMEM) fails here
at no chip time.  Interpret-mode parity tests cannot see either.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every pytest worker imports
this file.
"""

from __future__ import annotations

import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.ssd import ssd_chunks_pallas

BF16 = jnp.bfloat16
F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read entries back from a persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; return the compiled text."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


# llama3.2-1b serving widths: 32 heads, 8 KV heads, head dim 64, page 16,
# 8 slots of 1024 tokens, 16 layers of stacked token-major pools
SLOTS, H, KH, D, PAGE, MAX_PAGES = 8, 32, 8, 64, 16, 64
N_POOL = SLOTS * MAX_PAGES + 1  # + the null page
LAYERS = 16


@pytest.mark.parametrize("s", [1, 16], ids=["decode", "extend"])
def test_paged_attention_gqa_compiles(one_chip, s):
    _compile(
        paged_attention_pallas, one_chip,
        ((SLOTS, H, s, D), BF16),
        ((LAYERS, N_POOL, PAGE, KH * D), BF16),
        ((LAYERS, N_POOL, PAGE, KH * D), BF16),
        ((SLOTS, MAX_PAGES), jnp.int32),
        ((SLOTS,), jnp.int32),
        ((), jnp.int32),
    )


def test_paged_attention_mla_decode_compiles(one_chip):
    # deepseek-v2 absorbed decode: 128 heads over one latent "KV head" of
    # rank 512, plus the 64-wide decoupled rope channel
    heads, rank, rope = 128, 512, 64

    def mla(q, c_pool, q_rope, kr_pool, pages, index, layer):
        return paged_attention_pallas(
            q, c_pool, c_pool, pages, index, layer, q_rope=q_rope,
            kr_pool=kr_pool, scale=1.0 / (128 + rope) ** 0.5,
        )

    _compile(
        mla, one_chip,
        ((SLOTS, heads, 1, rank), BF16),
        ((LAYERS, N_POOL, PAGE, rank), BF16),
        ((SLOTS, heads, 1, rope), BF16),
        ((LAYERS, N_POOL, PAGE, rope), BF16),
        ((SLOTS, MAX_PAGES), jnp.int32),
        ((SLOTS,), jnp.int32),
        ((), jnp.int32),
    )


def test_flash_attention_compiles(one_chip):
    fn = functools.partial(ops.flash_attention, backend="pallas")
    _compile(
        fn, one_chip,
        ((1, H, 2048, D), BF16), ((1, KH, 2048, D), BF16),
        ((1, KH, 2048, D), BF16),
    )


@pytest.mark.parametrize(
    "m,k,n,dtype",
    [(2048, 2048, 8192, BF16), (192, 192, 192, F32)],
    ids=["2048x8192-bf16", "192-f32"],
)
def test_matmul_compiles(one_chip, m, k, n, dtype):
    fn = functools.partial(ops.matmul, backend="pallas")
    _compile(fn, one_chip, ((m, k), dtype), ((k, n), dtype))


def test_rmsnorm_odd_rows_compiles(one_chip):
    fn = functools.partial(ops.rmsnorm, backend="pallas")
    _compile(fn, one_chip, ((17, 2048), BF16), ((2048,), BF16))


def test_ssd_mamba2_widths_compiles(one_chip):
    # mamba2-2.7b: d_inner 5120 = 80 heads x 64, state 128, chunk 128
    b, s, h, p, n = 1, 512, 80, 64, 128
    fn = functools.partial(ssd_chunks_pallas, chunk=128)
    _compile(
        fn, one_chip,
        ((b, s, h, p), F32), ((b, s, h), F32), ((h,), F32),
        ((b, s, n), F32), ((b, s, n), F32),
    )


@pytest.mark.parametrize("n", [192, 256])
def test_fft2d_compiles(one_chip, n):
    fn = functools.partial(ops.fft2d, backend="pallas")
    _compile(fn, one_chip, ((n, n), jnp.complex64))


@pytest.mark.parametrize("n", [192, 256])
def test_lu_compiles(one_chip, n):
    fn = functools.partial(ops.lu, backend="pallas")
    _compile(fn, one_chip, ((n, n), F32))


#: instructions that run no device operation of their own
FREE = re.compile(r" (constant|parameter|get-tuple-element|tuple|bitcast)\(")


def _decode_program_text(one_chip, cfg, slots, max_len, page):
    """The serving engine's paged decode program
    (``ServeEngine._build_decode``, the XLA page walk), compiled for the
    described chip."""
    import types

    from repro.models import lm
    from repro.models import params as pm
    from repro.serve.engine import ServeEngine

    n_pages = slots * (max_len // page) + 1

    def struct(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(struct, pm.abstract_params(lm.build_metas(cfg)))
    cache = jax.tree.map(struct, pm.abstract_params(lm.cache_metas_tree(
        cfg, slots, max_len, page_size=page, n_pages=n_pages,
    )))
    i32 = jnp.int32
    args = [
        params, jax.ShapeDtypeStruct((slots, 1), i32, sharding=one_chip),
        cache,
        jax.ShapeDtypeStruct((slots, max_len // page), i32, sharding=one_chip),
        *(jax.ShapeDtypeStruct((slots,), dt, sharding=one_chip)
          for dt in (i32, i32, F32, i32)),
    ]
    decode_fn = ServeEngine._build_decode(
        types.SimpleNamespace(cfg=cfg, paged=True)
    )
    return jax.jit(decode_fn, donate_argnums=(2,)).lower(*args).compile().as_text()


def test_paged_decode_page_walk_in_its_block_scope(one_chip):
    """On the chip's compiler too, the page-walk loops of the decode
    program (and every named instruction of their bodies) land in the
    ``paged_attention`` scope, the KV write in ``kv_write``."""
    from repro.configs import get_config
    from repro.core import blocks
    from repro.obs import module_name, op_scopes

    text = _decode_program_text(
        one_chip, get_config("llama3.2-1b").reduced(), SLOTS, 128, PAGE
    )
    assert module_name(text) == "jit_decode_fn"
    scopes = op_scopes(
        text, blocks.registry.blocks() + ["kv_write", "head", "mlp", "sample"]
    )
    bodies, lines = {}, {}
    current = None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            current = line.split()[1 if line.startswith("ENTRY") else 0]
            lines[current.lstrip("%")] = []
        elif current and line.startswith("  "):
            lines[current.lstrip("%")].append(line)
            m = re.search(r"%([\w.\-]+) = .* while\(.*body=%([\w.\-]+)", line)
            if m:
                bodies[m.group(1)] = m.group(2)
    walks = [w for w in bodies if scopes[w] == "paged_attention"]
    assert len(walks) >= 1  # one walk reads K and V together
    for w in walks:
        for line in lines[bodies[w]]:
            if "op_name=" in line and not FREE.search(line):
                name = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)", line).group(1)
                assert scopes[name] == "paged_attention", line
    assert {"kv_write", "head", "sample", "mlp"} <= set(scopes.values())


def _bench_module(name, path):
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        name, pathlib.Path(__file__).resolve().parents[1] / path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _cell(one_chip, name):
    """A benchmark configuration (``bench/configs/<name>.json``) at its
    engine size, for the described chip: (config, engine, abstract
    parameters, abstract page pools, a spec maker), the engine built
    with a one-page pool (its programs take the pools as arguments)."""
    import json
    import pathlib

    from repro.models import lm

    root = pathlib.Path(__file__).resolve().parents[1]
    config = json.loads((root / f"bench/configs/{name}.json").read_text())
    family = config["family"]
    adapter = _bench_module(f"adapter_{family}", f"bench/adapters/{family}.py")
    ref = _bench_module(f"reference_{family}", f"bench/reference/{family}.py")
    eng = config["engine"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    cfg = adapter.arch_config(config)
    params = adapter.program_params(
        {k: sds(s, config["dtypes"]["param"])
         for k, (s, _) in ref.layout(config).items()}, cfg)
    cache = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: lm.init_cache(
            cfg, eng["n_slots"], eng["max_len"], page_size=eng["page_size"],
            n_pages=eng["n_pages"])),
    )
    engine = adapter.engine(dict(config, engine=dict(eng, n_pages=1)), cfg,
                            params, 0)
    return config, engine, params, cache, sds


@pytest.fixture(scope="module")
def cell_decode(one_chip):
    """name -> (the cell's decode program over its whole page pool,
    compiled for the described chip, once; the abstract pools)."""
    compiled = {}

    def get(name):
        if name not in compiled:
            config, engine, params, cache, sds = _cell(one_chip, name)
            eng = config["engine"]
            slots = eng["n_slots"]
            i32 = functools.partial(sds, dtype="int32")
            compiled[name] = engine.programs.records["decode"].fn.lower(
                params, i32((slots, 1)), cache,
                i32((slots, eng["max_len"] // eng["page_size"])),
                i32((slots,)), i32((slots,)), sds((slots,), "float32"),
                i32((slots,)),
            ).compile(), cache
        return compiled[name]

    return get


def test_deepseek_v2_lite_cell_programs_fit_the_chip(one_chip, cell_decode):
    """The deepseek-v2-lite cell at its published widths, its held expert
    share and its engine size (``bench/configs/deepseek-v2-lite.json``):
    the decode step over the whole page pool fits the sizing rule's share
    of the chip's allocator limit and the longest prefill bucket beside
    that pool fits the limit, compiled for the described chip; the expert
    layers run the chip's grouped (ragged) kernel."""
    config, engine, params, cache, sds = _cell(one_chip, "deepseek-v2-lite")
    # the chip's allocator limit and the compiler's own reservation
    limit = _bench_module("sizing", "bench/sizing.py")
    decode, _ = cell_decode("deepseek-v2-lite")
    m = decode.memory_analysis()
    # the sizing rule keeps its margin of the limit free
    assert (limit.RESERVED + m.argument_size_in_bytes + m.temp_size_in_bytes
            <= (1 - limit.MARGIN) * limit.BYTES_LIMIT)
    assert "ragged" in decode.as_text()

    i32 = functools.partial(sds, dtype="int32")
    longest = config["engine"]["max_len"]  # the mix's longest context
    prefill = engine.programs.records["prefill"].fn.lower(
        params, i32((1, longest)), i32(()), i32(()), i32(()),
        sds((), "float32"), i32(()),
    ).compile()
    p = prefill.memory_analysis()
    pool = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert (limit.RESERVED + p.argument_size_in_bytes + p.temp_size_in_bytes
            + p.output_size_in_bytes + pool) <= limit.BYTES_LIMIT


#: each cell's decode program compiled for v5e while its layer loop still
#: sliced every layer's pool out of the stacked cache and wrote a new
#: stack: temporaries in bytes
SLICED_LOOP_TEMPS = {"deepseek-v2-lite": 5.00e9, "stablelm-1.6b": 5.10e9}

#: an instruction that moves a whole array: its name, element type, dims
#: and opcode
MOVE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
    r"(copy|dynamic-slice|dynamic-update-slice)\("
)


@pytest.mark.parametrize("name", sorted(SLICED_LOOP_TEMPS))
def test_decode_writes_the_page_pool_in_place(cell_decode, name):
    """The decode program's layer loop carries the stacked page pools and
    writes each new token's rows into them in place: no ``copy``,
    ``dynamic-slice`` or ``dynamic-update-slice``, fused or not, moves a
    whole layer's pool or a whole stack of them, and the temporaries fall
    by at least 90% of the pools' bytes.  Exempt: MLA's 64-wide rope
    pool, whose device layout keeps the page axis minor — at most two
    whole-pool relayouts of each, outside the loop (into the loop's
    layout and back), whose buffer the temporaries may hold."""
    decode, cache = cell_decode(name)
    sizes = {}  # element count -> leaf name, for a layer's pool and a stack
    for key, group in cache.items():
        if key == "index":
            continue
        for leaf, pool in group.items():
            sizes[pool.size] = sizes[pool.size // pool.shape[0]] = leaf
    entry = False
    moves = []  # (leaf, in the entry computation, name, opcode)
    for line in decode.as_text().splitlines():
        if line.endswith("{") and not line.startswith(" "):
            entry = line.startswith("ENTRY")
        m = MOVE.match(line)
        if m:
            n = math.prod(int(d) for d in m.group(3).split(",") if d)
            if m.group(2) == "bf16" and n in sizes:
                moves.append((sizes[n], entry, m.group(1), m.group(4)))
    assert [mv for mv in moves if mv[0] != "kr"] == []
    rope = [mv for mv in moves if mv[0] == "kr"]
    rope_pools = [g["kr"] for k, g in cache.items() if k != "index"
                  and "kr" in g]
    assert len(rope) <= 2 * len(rope_pools), rope
    assert all(in_entry and op == "copy" for _, in_entry, _, op in rope)
    pools = sum(a.size * a.dtype.itemsize for k, g in cache.items()
                if k != "index" for a in g.values())
    relayout = sum(a.size * a.dtype.itemsize for a in rope_pools)
    temps = decode.memory_analysis().temp_size_in_bytes
    assert temps - relayout < SLICED_LOOP_TEMPS[name] - 0.9 * pools, temps
