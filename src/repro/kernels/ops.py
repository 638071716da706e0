"""Jit'd public wrappers for the kernel shelf, with environment dispatch.

Every wrapper picks its implementation from the deployment environment —
the environment-adaptive behaviour of the paper: the same call runs the
Pallas kernel on a TPU backend and the XLA-native formulation elsewhere.
``backend=`` overrides ("pallas" | "xla"); ``interpret=True`` runs the Pallas
kernel body in Python (how the kernels are validated on this CPU container).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.attention import flash_attention_pallas
from repro.kernels import paged_attention as _paged
from repro.kernels.fft import dft_matrix, fft2d_pallas
from repro.kernels.lu import lu_blocked
from repro.kernels.matmul import matmul_padded, schur_update_padded
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd import ssd_chunks_pallas


def _auto_backend(backend: str | None) -> str:
    if backend is not None:
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# -- matmul (cuBLAS analogue) --------------------------------------------------


def matmul(a, b, *, backend: str | None = None, interpret: bool = False):
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    if _auto_backend(backend) == "pallas":
        return matmul_padded(a, b, interpret=interpret)
    return _ref.matmul_ref(a, b)


def schur_update(c, a, b, *, backend: str | None = None, interpret: bool = False):
    if _auto_backend(backend) == "pallas":
        return schur_update_padded(c, a, b, interpret=interpret)
    return _ref.schur_update_ref(c, a, b)


# -- fft2d (cuFFT analogue) ----------------------------------------------------


@functools.partial(jax.jit, static_argnames=("backend", "variant", "interpret"))
def fft2d(
    x,
    *,
    backend: str | None = None,
    variant: str = "direct",
    interpret: bool = False,
):
    """2-D complex FFT.  pallas: matmul-DFT stages on the MXU; xla: native."""
    x = jnp.asarray(x)
    if x.dtype not in (jnp.complex64, jnp.complex128):
        x = x.astype(jnp.complex64)
    if _auto_backend(backend) == "pallas":
        if variant == "four-step":
            return _fft2d_four_step(x, interpret=interpret)
        return fft2d_pallas(x.astype(jnp.complex64), interpret=interpret)
    return jnp.fft.fft2(x).astype(jnp.complex64)


def _fft1d_four_step_axis1(x: jax.Array, interpret: bool = False) -> jax.Array:
    """Four-step FFT along the last axis via two matmul-DFT stages.

    n = n1*n2:  X (rows, n) -> reshape (rows, n1, n2)
      1) DFT_n2 along axis2 (matmul with F_{n2})
      2) twiddle  w^{j1*k2}
      3) DFT_n1 along axis1 (matmul with F_{n1})
      4) transpose (k2, j1) -> index k2*n1 + j1
    Cost 2n(n1+n2) vs direct 2n^2 — the beyond-paper §Perf variant.
    """
    rows, n = x.shape
    n1 = 1 << ((n.bit_length() - 1) // 2)
    n2 = n // n1
    fr2, fi2 = dft_matrix(n2)
    f2 = jnp.asarray(fr2) + 1j * jnp.asarray(fi2)
    fr1, fi1 = dft_matrix(n1)
    f1 = jnp.asarray(fr1) + 1j * jnp.asarray(fi1)
    # x[j1*n2 + j2] -> (j1, j2); DFT over j1 first, twiddle, DFT over j2.
    xr = x.reshape(rows, n1, n2)
    y = jnp.einsum("ab,rbc->rac", f1.astype(x.dtype), xr)  # axis1 -> k1
    k1 = jnp.arange(n1)[:, None]
    j2 = jnp.arange(n2)[None, :]
    tw = jnp.exp(-2j * jnp.pi * (k1 * j2) / n).astype(x.dtype)
    y = y * tw[None]
    z = jnp.einsum("rac,cd->rad", y, f2.astype(x.dtype))  # axis2 -> k2
    # output index k = k2*n1 + k1  (transpose the two factors)
    return jnp.transpose(z, (0, 2, 1)).reshape(rows, n)


def _fft2d_four_step(x: jax.Array, interpret: bool = False) -> jax.Array:
    y = _fft1d_four_step_axis1(x, interpret)
    y = _fft1d_four_step_axis1(y.T, interpret).T
    return y.astype(jnp.complex64)


# -- LU (cuSOLVER getrf analogue) ----------------------------------------------


def lu(a, *, nb: int | None = None, backend: str | None = None,
       interpret: bool = False):
    """Blocked LU with partial pivoting.  Returns (lu_packed, piv).

    Arbitrary n: pads to a multiple of nb with an identity extension (pad
    rows can never be chosen as pivots for real columns).  The default block
    size adapts to the problem: small matrices are panel-dominated and want
    small blocks; large ones want MXU-aligned 128 panels (verified 9x at
    n=160, see EXPERIMENTS §Paper-repro).
    """
    a = jnp.asarray(a, dtype=jnp.float32)
    n = a.shape[0]
    if nb is None:
        nb = 128 if n >= 512 else 32
    npad = ((n + nb - 1) // nb) * nb
    if npad != n:
        ap = jnp.eye(npad, dtype=jnp.float32)
        ap = ap.at[:n, :n].set(a)
        ap = ap.at[jnp.arange(n), jnp.arange(n)].set(a[jnp.arange(n), jnp.arange(n)])
    else:
        ap = a
    use_pallas = _auto_backend(backend) == "pallas"
    lu_p, piv, _parity = lu_blocked(
        ap, nb=nb, n_real=n, use_pallas=use_pallas, interpret=interpret
    )
    return lu_p[:n, :n], piv[:n]


def lu_nr_compat(a, *, backend: str | None = None, interpret: bool = False):
    """Numerical-Recipes-shaped interface: returns (lu, indx, d).

    This is the DB-registered replacement for ``ludcmp`` — C-1 glue that
    matches the host program's expected (lu, indx, d) signature.
    """
    lu_p, piv = lu(a, backend=backend, interpret=interpret)
    n = piv.shape[0]
    swaps = jnp.sum(jnp.where(piv != jnp.arange(n, dtype=piv.dtype), 1, 0))
    d = jnp.where(swaps % 2 == 0, 1.0, -1.0).astype(jnp.float32)
    return lu_p, piv.astype(jnp.int32), d


# -- attention ------------------------------------------------------------------


def flash_attention(
    q, k, v, *, causal: bool = True, backend: str | None = None,
    interpret: bool = False,
):
    if _auto_backend(backend) == "pallas" and q.shape[2] > 1:
        return flash_attention_pallas(q, k, v, causal=causal, interpret=interpret)
    return _ref.attention_ref(q, k, v, causal=causal)


def paged_attention(
    q, k_pool, v_pool, pages, index, layer, *, q_rope=None, kr_pool=None,
    scale: float | None = None, backend: str | None = None,
    interpret: bool | None = None,
):
    """Paged decode/extend attention through the page table, over layer
    ``layer`` of the stacked token-major pools.

    pallas: the fused page-walk kernel; xla: a rolled walk over the live
    blocks of pages with an online softmax.  When the pallas target is
    *forced* off-TPU (``backend="pallas"`` on this CPU container, e.g. a
    serve run with ``--decode-impl pallas``), ``interpret`` defaults on so
    the kernel body runs in Python — the parity path CPU CI proves
    token-identical.  On TPU the compiled Mosaic kernel runs as-is.
    """
    if _auto_backend(backend) == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return _paged.paged_attention_pallas(
            q, k_pool, v_pool, pages, index, layer, q_rope=q_rope,
            kr_pool=kr_pool, scale=scale, interpret=interpret,
        )
    return _paged.paged_attention_xla(
        q, k_pool, v_pool, pages, index, layer, q_rope=q_rope,
        kr_pool=kr_pool, scale=scale,
    )


# -- rmsnorm ---------------------------------------------------------------------


def rmsnorm(x, w, *, eps: float = 1e-6, backend: str | None = None,
            interpret: bool = False):
    if _auto_backend(backend) == "pallas":
        return rmsnorm_pallas(x, w, eps=eps, interpret=interpret)
    return _ref.rmsnorm_ref(x, w, eps=eps)


# -- Mamba-2 SSD scan -------------------------------------------------------------


def _ssd_chunks_jnp(x, dt, a, bmat, cmat, *, chunk: int):
    """XLA-native vectorised version of the per-chunk kernel terms."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    nc = s // chunk
    xf = x.astype(jnp.float32).reshape(b, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, h)
    af = a.astype(jnp.float32)
    bf = bmat.astype(jnp.float32).reshape(b, nc, chunk, n)
    cf = cmat.astype(jnp.float32).reshape(b, nc, chunk, n)

    a_seg = dtf * af[None, None, None, :]  # (B,NC,L,H)
    a_cum = jnp.cumsum(a_seg, axis=2)
    a_tot = a_cum[:, :, -1, :]  # (B,NC,H)

    diff = a_cum[:, :, :, None, :] - a_cum[:, :, None, :, :]  # (B,NC,L,L,H)
    ii = jnp.arange(chunk)[:, None]
    jj = jnp.arange(chunk)[None, :]
    lam = jnp.where((ii >= jj)[None, None, :, :, None], jnp.exp(diff), 0.0)
    g = jnp.einsum("bcin,bcjn->bcij", cf, bf)  # (B,NC,L,L)
    w = g[..., None] * lam * dtf[:, :, None, :, :]  # (B,NC,L,L,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", w, xf)

    sw = dtf * jnp.exp(a_tot[:, :, None, :] - a_cum)  # (B,NC,L,H)
    states = jnp.einsum("bcjn,bcjh,bcjhp->bchnp", bf, sw, xf)

    cumdecay = jnp.exp(a_cum).reshape(b, s, h)
    totals = jnp.exp(a_tot)
    return (
        y_intra.reshape(b, s, h, p),
        states,
        cumdecay,
        totals,
    )


def _ssd_combine(y_intra, states, cumdecay, totals, cmat, h0, chunk: int):
    b, nc, h, n, p = states.shape
    s = nc * chunk
    if h0 is None:
        h0 = jnp.zeros((b, h, n, p), jnp.float32)

    sts = jnp.moveaxis(states, 1, 0)  # (NC,B,H,N,P)
    tots = jnp.moveaxis(totals, 1, 0)  # (NC,B,H)

    def body(hprev, inp):
        st, tot = inp
        hnew = hprev * tot[..., None, None] + st
        return hnew, hprev

    hfin, henter = jax.lax.scan(body, h0.astype(jnp.float32), (sts, tots))
    c_chunks = cmat.astype(jnp.float32).reshape(b, nc, chunk, n)
    y_inter = jnp.einsum("bcln,cbhnp->bclhp", c_chunks, henter)
    y_inter = y_inter * cumdecay.reshape(b, nc, chunk, h)[..., None]
    y = y_intra + y_inter.reshape(b, s, h, p)
    return y, hfin


def ssd_scan(
    x, dt, a, bmat, cmat, *, chunk: int = 128, h0=None,
    backend: str | None = None, interpret: bool = False,
):
    """Chunked SSD selective scan.  Returns (y, final_state)."""
    be = _auto_backend(backend)
    s = x.shape[1]
    chunk = min(chunk, s)
    if s % chunk:
        # pad with dt=0 steps: decay exp(0)=1 and update dt*B*x=0, so the
        # final state is untouched; padded outputs are sliced away.
        pad = chunk - s % chunk
        padded = ssd_scan(
            jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(dt, ((0, 0), (0, pad), (0, 0))),
            a,
            jnp.pad(bmat, ((0, 0), (0, pad), (0, 0))),
            jnp.pad(cmat, ((0, 0), (0, pad), (0, 0))),
            chunk=chunk, h0=h0, backend=backend, interpret=interpret,
        )
        y, hfin = padded
        return y[:, :s], hfin
    if be == "pallas":
        y_i, states, cumdecay, totals = ssd_chunks_pallas(
            x, dt, a, bmat, cmat, chunk=chunk, interpret=interpret
        )
    elif be == "ref":
        return _ref.ssd_ref(x, dt, a, bmat, cmat, h0=h0)
    else:
        y_i, states, cumdecay, totals = _ssd_chunks_jnp(
            x, dt, a, bmat, cmat, chunk=chunk
        )
    return _ssd_combine(y_i, states, cumdecay, totals, cmat, h0, chunk)
