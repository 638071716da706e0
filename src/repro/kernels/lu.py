"""Blocked right-looking LU with partial pivoting — the cuSOLVER-getrf
analogue for the matrix-calculation application.

Algorithm (block size nb, MXU-aligned 128):

    for each column block kb:
        1. panel factorisation  (rank-1 updates inside the panel, pivoting
           over the whole column) — latency-bound, stays in jnp;
        2. apply the panel's row swaps to the rest of the matrix;
        3. triangular solve U12 = L11^-1 A12     (small, jnp fori_loop);
        4. trailing update A22 -= L21 @ U12      (the FLOPs: >2/3 of n^3) —
           this is the MXU matmul, dispatched to the fused Pallas
           ``schur_update`` kernel on TPU.

This mirrors how cuSOLVER speeds up LU on GPUs: the algorithm is
restructured so nearly all work lands in the tuned matmul primitive — the
paper's point that *block-level replacement captures algorithm change*,
which loop-level offload cannot.

Pivot bookkeeping matches Numerical Recipes' ``indx`` convention (imax per
step, rows swapped in place) so the NR back-substitution consumes the result
unchanged; pad rows use an identity extension and can never be selected as
pivots for real columns.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

#: f32 contractions run exact: TPU's default f32 matmul is one bfloat16
#: pass, too coarse for the app-level verify (rtol 1e-3)
_EXACT = jax.lax.Precision.HIGHEST


def _panel_factor(panel: jax.Array, n_real_rows: int):
    """Unblocked LU of a (rows x nb) panel, pivoting over all rows.

    Returns (panel, piv, parity): piv[j] = row swapped with j at step j
    (panel-relative), NR semantics.
    """
    rows, nb = panel.shape
    ridx = jnp.arange(rows)

    def body(j, carry):
        panel, piv, parity = carry
        col = panel[:, j]
        # eligible pivots: at/below the diagonal, and never a pad row for a
        # real column (pad rows may only pivot for their own pad column).
        eligible = (ridx >= j) & ((ridx < n_real_rows) | (ridx == j))
        score = jnp.where(eligible, jnp.abs(col), -jnp.inf)
        imax = jnp.argmax(score)
        rj = panel[j]
        ri = panel[imax]
        panel = panel.at[j].set(ri).at[imax].set(rj)
        piv = piv.at[j].set(imax)
        parity = jnp.where(imax != j, -parity, parity)
        pivval = panel[j, j]
        pivval = jnp.where(pivval == 0.0, 1.0e-20, pivval)
        panel = panel.at[j, j].set(pivval)
        fac = jnp.where(ridx > j, panel[:, j] / pivval, 0.0)
        cidx = jnp.arange(nb)
        urow = jnp.where(cidx > j, panel[j], 0.0)
        panel = panel - jnp.outer(fac, urow)
        panel = panel.at[:, j].set(jnp.where(ridx > j, fac, panel[:, j]))
        return panel, piv, parity

    piv0 = jnp.zeros(nb, dtype=jnp.int32)
    return jax.lax.fori_loop(
        0, nb, body, (panel, piv0, jnp.asarray(1.0, panel.dtype))
    )


def _apply_swaps(mat: jax.Array, piv: jax.Array) -> jax.Array:
    """Apply the NR swap sequence piv (row j <-> piv[j]) to ``mat`` rows."""

    def body(j, m):
        i = piv[j]
        rj = m[j]
        ri = m[i]
        return m.at[j].set(ri).at[i].set(rj)

    return jax.lax.fori_loop(0, piv.shape[0], body, mat)


def _trsm_lower_unit(l11: jax.Array, b: jax.Array) -> jax.Array:
    """Solve L11 @ X = B with L11 unit lower triangular (nb x nb)."""
    nb = l11.shape[0]
    ridx = jnp.arange(nb)

    def body(r, x):
        lrow = jnp.where(ridx < r, l11[r], 0.0)  # (nb,)
        x_r = b[r] - jnp.dot(lrow, x, precision=_EXACT)
        return x.at[r].set(x_r)

    return jax.lax.fori_loop(0, nb, body, jnp.zeros_like(b))


def _schur_jnp(c: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    return c - jnp.dot(a, b, precision=_EXACT)


@functools.partial(jax.jit, static_argnames=("nb", "n_real", "use_pallas", "interpret"))
def lu_blocked(
    a: jax.Array,
    *,
    nb: int = 128,
    n_real: int | None = None,
    use_pallas: bool = False,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Blocked LU.  Returns (lu_packed, piv, parity).

    ``a`` must be square with n % nb == 0 (use ops.lu for auto-padding).
    ``n_real`` marks the boundary of identity padding.
    """
    n = a.shape[0]
    if a.shape[1] != n or n % nb:
        raise ValueError(f"need square n%nb==0 matrix, got {a.shape}, nb={nb}")
    n_real = n if n_real is None else n_real

    if use_pallas:
        from repro.kernels.matmul import schur_update_padded

        def schur(c, x, y):
            if min(c.shape + x.shape) == 0:
                return c
            return schur_update_padded(c, x, y, interpret=interpret)
    else:
        schur = _schur_jnp

    a = a.astype(jnp.float32)
    piv = jnp.zeros(n, dtype=jnp.int32)
    parity = jnp.asarray(1.0, jnp.float32)

    for kb in range(0, n, nb):
        rows = n - kb
        panel = jax.lax.dynamic_slice(a, (kb, kb), (rows, nb))
        panel, ppiv, pparity = _panel_factor(panel, max(n_real - kb, 0) or nb)
        parity = parity * pparity
        a = jax.lax.dynamic_update_slice(a, panel, (kb, kb))
        piv = jax.lax.dynamic_update_slice(piv, ppiv + kb, (kb,))
        # swap rows in the columns left of and right of the panel
        if kb > 0:
            left = jax.lax.dynamic_slice(a, (kb, 0), (rows, kb))
            left = _apply_swaps(left, ppiv)
            a = jax.lax.dynamic_update_slice(a, left, (kb, 0))
        rcols = n - kb - nb
        if rcols > 0:
            right = jax.lax.dynamic_slice(a, (kb, kb + nb), (rows, rcols))
            right = _apply_swaps(right, ppiv)
            l11 = panel[:nb]
            u12 = _trsm_lower_unit(l11, right[:nb])
            right = right.at[:nb].set(u12)
            if rows > nb:
                l21 = panel[nb:]
                a22 = schur(right[nb:], l21, u12)
                right = right.at[nb:].set(a22)
            a = jax.lax.dynamic_update_slice(a, right, (kb, kb + nb))

    return a, piv, parity
