"""The work model against the program's parameter tree and by hand."""

import json

import pytest

from bench import work
from bench.harness import load_module
from bench_toy import REPO, TOY


def config(name):
    path = REPO / "bench/configs" / f"{name}.json"
    if not path.exists():
        path = TOY / "configs" / f"{name}.json"
    return json.loads(path.read_text())


@pytest.mark.parametrize("name", ["stablelm-1.6b", "granite-3-8b", "toy"])
def test_param_count_matches_program_tree(name):
    """Counts and shapes agree with the program's own ParamMeta tree, and
    the adapter hands the program exactly that tree."""
    import jax

    from repro.models import lm, params as pm

    cfg_json = config(name)
    adapter = load_module(REPO / "bench/adapters/dense.py", "a_dense")
    ref = load_module(REPO / "bench/reference/dense.py", "r_dense")
    cfg = adapter.arch_config(cfg_json)
    metas = lm.build_metas(cfg)
    assert work.param_count(cfg_json) == pm.count_params(metas)
    ours = adapter.program_params(
        {k: jax.ShapeDtypeStruct(s, "float32")
         for k, (s, _) in ref.layout(cfg_json).items()},
        cfg,
    )
    want = pm.abstract_params(metas)
    assert jax.tree.structure(ours) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype


TINY = {
    "hidden_size": 4, "num_hidden_layers": 2, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 2, "intermediate_size": 8,
    "vocab_size": 10, "tie_word_embeddings": False,
    "dtypes": {"compute": "bfloat16"},
}


def test_flops_and_bytes_by_hand():
    # per layer: q and o 4*2*2 = 16 each, k and v 4*1*2 = 8 each,
    # gate/up/down 4*8 = 32 each -> 16+16+8+8+96 = 144
    assert work.layer_matmul_params(TINY) == 144
    # decode of two slots at contexts 3 and 5: matmuls 2*(2*144 + 4*10)
    # per token; attention 4*layers*heads*head_dim = 32 per pair
    assert work.decode_flops(TINY, [3, 5]) == 2 * (288 + 40) * 2 + 32 * 8
    # prefill of 3 tokens: 2*288*3 + head 2*4*10 + 32 * (3*4/2) pairs
    assert work.prefill_flops(TINY, 3) == 2 * 288 * 3 + 80 + 32 * 6
    # bytes at bf16: weights 288 + head 40 + norms (2*2+1)*4 = 348 values,
    # two embedding rows of 4; kv 2 layers * 2 * 1 head * 2 dims * 2 B
    # = 16 B a position, 2 + 4 read and 2 written
    assert work.kv_bytes_per_token(TINY) == 16
    assert work.decode_bytes(TINY, [3, 5]) == 2 * (348 + 8) + 16 * 8
    # padded embedding rows count as held parameters
    assert work.param_count(TINY) == 256 * 4 * 2 + 2 * (144 + 8) + 4


def test_peaks_table():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")

