"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and holding the
request with the most served tokens, is run through the plain reference:
each prompt followed by its served tokens, teacher-forced.  At every served
position the number read is the gap by which the served token's logit lies
below the reference's best logit there (0 where the program picked the
reference's arg-max).  The run's number is the widest gap over the sample.

The control (``control=True``) reads, at the same positions of the same
sequences, the gap of the token that the reference computed in the
configuration's control precision (``correct.control``) puts first.
"""

from __future__ import annotations

import numpy as np

from bench import weights

#: the sample holds at least this many served tokens where the window
#: finished enough requests, in at most ``MAX_REQUESTS`` requests
SERVED_TOKENS = 384
MAX_REQUESTS = 12


def sample(finished: list, seed: int) -> list:
    """``finished`` is [(request id, prompt, served tokens)]; returns the
    sample: the request with the most served tokens, then others in an
    order drawn from the seed until ``SERVED_TOKENS`` are held."""
    if not finished:
        return []
    items = sorted(finished, key=lambda f: f[0])
    longest = max(items, key=lambda f: (len(f[2]), -f[0]))
    rest = [f for f in items if f is not longest]
    order = weights.rng(seed, "sample").permutation(len(rest))
    out, served = [longest], len(longest[2])
    for i in order:
        if served >= SERVED_TOKENS or len(out) >= MAX_REQUESTS:
            break
        out.append(rest[i])
        served += len(rest[i][2])
    return out


def reference_length(context: int, block: int = 512) -> int:
    """The one padded length every sequence of a cell is run at."""
    return -(-context // block) * block


def gaps(ref, config: dict, seed: int, seqs: list, length: int,
         control: bool = False) -> dict:
    """Logit gaps of the program's served tokens over ``seqs`` [(rid,
    prompt, served)], all padded to ``length`` tokens: the widest
    (``max_logit_gap``) and the mean over every served position
    (``mean_logit_gap``); with ``control``, the same two numbers of the
    control's picks (``control_max_logit_gap``, ``control_mean_logit_gap``)."""
    import jax.numpy as jnp

    w = weights.make(ref.layout(config), seed, config["weights"], "float32")
    head = ref.head_matrix(w, config)
    static = ref.static(config)
    got_gaps, ctl_gaps = [], []
    for _, prompt, served in seqs:
        p, n = len(prompt), len(served)
        seq = np.zeros(length, np.int32)
        ctx = np.concatenate([np.asarray(prompt), np.asarray(served[:-1])])
        seq[: len(ctx)] = ctx
        tgt = np.zeros(length, np.int32)
        tgt[p - 1 : p - 1 + n] = served
        rows = slice(p - 1, p - 1 + n)
        h = ref.hidden(w, jnp.asarray(seq), static)
        best, got, _ = ref.logit_stats(h, head, jnp.asarray(tgt))
        best = np.asarray(best)[rows]
        got_gaps.append(best - np.asarray(got)[rows])
        if control:
            low = config["correct"]["control"]
            hq = ref.hidden(w, jnp.asarray(seq), static, control=low)
            _, _, pick = ref.logit_stats(hq, head, jnp.asarray(tgt), control=low)
            _, got_ctl, _ = ref.logit_stats(h, head, pick)
            ctl_gaps.append(best - np.asarray(got_ctl)[rows])
    out = {"tokens_compared": sum(len(g) for g in got_gaps)}
    for prefix, g in (("", got_gaps), ("control_", ctl_gaps)):
        if g:
            g = np.concatenate(g)
            out[prefix + "max_logit_gap"] = float(g.max())
            out[prefix + "mean_logit_gap"] = float(g.mean())
    return out
