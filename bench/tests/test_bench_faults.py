"""``correct`` fails when the timed path is broken underneath the harness
(the control's failure is checked in ``test_bench_harness.py``).

The toy cell runs on the CPU without the harness's look for a chip.  Each
fault below is one the serving cells can have; the exchange between chips
is left out, since every cell runs on one chip.
"""

import jax
import pytest

from bench_toy import run_toy

SEED = 2**31 + 3


def keep_state(engine):
    """The decode step returns its cache unchanged: no token's keys and
    values are ever written."""
    build = engine._build_decode()

    def decode(params, tokens, cache, *rest):
        tok, _ = build(params, tokens, cache, *rest)
        return tok, cache

    engine._decode_fn = jax.jit(decode)


def half_batch(engine):
    """Half of the slot batch is left out: its tokens are copied from the
    other half."""
    build = engine._build_decode()

    def decode(params, tokens, cache, *rest):
        tok, new = build(params, tokens, cache, *rest)
        h = tok.shape[0] // 2
        return tok.at[h:].set(tok[: tok.shape[0] - h]), new

    engine._decode_fn = jax.jit(decode, donate_argnums=(2,))


def altered_token(engine):
    """Every decoded token is altered where the program produces it."""
    build = engine._build_decode()
    vocab = engine.cfg.vocab_size

    def decode(params, tokens, cache, *rest):
        tok, new = build(params, tokens, cache, *rest)
        return (tok + 1) % vocab, new

    engine._decode_fn = jax.jit(decode, donate_argnums=(2,))


@pytest.mark.parametrize("fault", [keep_state, half_batch, altered_token])
def test_fault_makes_correct_false(toy_root, fault):
    result = run_toy(toy_root, SEED, fault=fault)
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert result["correct"] is False
