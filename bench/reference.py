"""Plain CPD-ALS over COO: the reference that decides ``correct``.

It imports nothing of the program. From the tensor's COO and the start's
key it draws the same initial factors (``jax.random.split`` of the key into
one key per mode, ``uniform [0, 1)`` of shape ``(I_d, R)``), then runs
Gauss-Seidel ALS sweeps in float32:

    M_d = X_(d) KRP(Y_w, w != d)                (MTTKRP over COO)
    V_d = hadamard_{w != d} Y_w^T Y_w + ridge I  (ridge = 1e-8 + 1e-6 tr/R)
    Y_d = M_d V_d^-1, columns normalized, norms -> lambda

and the fit ``1 - ||X - X_hat|| / ||X||`` after each sweep. The MTTKRP
accumulates chunks of ``CHUNK`` nonzeros with a segment sum, so its
partials stay small at any nnz. Matrix products run at ``precision``:
``"highest"`` for the reference. ``"high"`` is the control, the reference
as the nearest lower precision would compute it: three bf16 passes, where
each float32 operand of a product is carried as the sum of two bf16
values. The program forms the MTTKRP through one-hot matrix products, so
the control rounds the MTTKRP's operands (factor rows, the Khatri-Rao
product and the value) the same way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

CHUNK = 1 << 20
PRECISIONS = {"highest": lax.Precision.HIGHEST, "high": lax.Precision.HIGH}


def init_factors(key, dims, rank: int) -> list:
    keys = jax.random.split(key, len(dims))
    return [jax.random.uniform(k, (d, rank), jnp.float32)
            for k, d in zip(keys, dims)]


def _two_bf16(x):
    """``x`` as a sum of two bf16 values (what a three-pass product sees)."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi + (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def device_coo(indices: np.ndarray, values: np.ndarray):
    """COO on the device, padded with zero values at index 0 (they add
    nothing) to whole chunks of ``min(CHUNK, nnz)`` nonzeros."""
    pad = -indices.shape[0] % min(CHUNK, -(-indices.shape[0] // 1024) * 1024)
    return (jnp.asarray(np.pad(indices, ((0, pad), (0, 0)))),
            jnp.asarray(np.pad(values, (0, pad))))


@functools.partial(jax.jit, static_argnames=("mode", "dim", "precision"))
def mttkrp(idx, val, factors, *, mode: int, dim: int, precision: str):
    chunk = min(CHUNK, val.shape[0])
    if val.shape[0] % chunk:
        raise ValueError("pad the COO with device_coo")
    rnd = _two_bf16 if precision == "high" else (lambda x: x)

    def body(c, acc):
        ix = lax.dynamic_slice_in_dim(idx, c * chunk, chunk)
        v = lax.dynamic_slice_in_dim(val, c * chunk, chunk)
        krp = None
        for w, f in enumerate(factors):
            if w != mode:
                row = rnd(f[ix[:, w]])
                krp = row if krp is None else krp * row
        part = rnd(v)[:, None] * rnd(krp)
        return acc + jax.ops.segment_sum(part, ix[:, mode], num_segments=dim)

    zero = jnp.zeros((dim, factors[0].shape[1]), jnp.float32)
    return lax.fori_loop(0, val.shape[0] // chunk, body, zero)


@functools.partial(jax.jit, static_argnames=("mode", "precision"))
def als_update(m, factors, *, mode: int, precision: str):
    prec = PRECISIONS[precision]
    v = None
    for w, f in enumerate(factors):
        if w != mode:
            g = jnp.dot(f.T, f, precision=prec)
            v = g if v is None else v * g
    r = v.shape[0]
    v = v + (1e-8 + 1e-6 * jnp.trace(v) / r) * jnp.eye(r, dtype=v.dtype)
    with jax.default_matmul_precision(precision):
        y = jnp.linalg.solve(v.T, m.T).T
    lam = jnp.linalg.norm(y, axis=0)
    lam = jnp.where(lam < 1e-8, 1.0, lam)
    return y / lam, lam


@functools.partial(jax.jit, static_argnames=("precision",))
def _fit_terms(m_last, factors, lam, *, precision: str):
    prec = PRECISIONS[precision]
    inner = jnp.sum(m_last * (factors[-1] * lam[None, :]))
    g = None
    for f in factors:
        gf = jnp.dot(f.T, f, precision=prec)
        g = gf if g is None else g * gf
    return inner, jnp.dot(lam, jnp.dot(g, lam, precision=prec),
                          precision=prec)


def _fit(norm_x_sq, inner, est) -> float:
    resid = max(norm_x_sq - 2.0 * float(inner) + float(est), 0.0)
    return float(1.0 - np.sqrt(resid) / np.sqrt(norm_x_sq))


def last_update(idx, val, norm_x_sq: float, factors,
                lam) -> tuple[float, float]:
    """How far a final state ``(factors, lam)`` is from being what its own
    last ALS update gives, and the fit of that state.

    In a Gauss-Seidel sweep the last mode ``n = N-1`` is updated from the
    final factors of all the other modes, so ``Y_n diag(lam)`` solves
    ``Z V_n = M_n`` for ``V_n`` and ``M_n`` built from the state alone. The
    reference rebuilds both and reads the solve's backward error entry by
    entry: ``|Z V_n - M_n|`` over ``A_n + |Z| |V_n|``, where ``A_n`` is the
    MTTKRP of ``|X|`` over ``|Y_w|``, the most that rounding of the
    MTTKRP's terms can move ``M_n``. A backward error does not grow with
    the condition of ``V_n``. Returns ``(largest such ratio, fit)``."""
    factors = [jnp.asarray(f, jnp.float32) for f in factors]
    lam = jnp.asarray(lam, jnp.float32)
    n = len(factors)
    dim = int(factors[-1].shape[0])
    m = mttkrp(idx, val, tuple(factors), mode=n - 1, dim=dim,
               precision="highest")
    a = mttkrp(idx, jnp.abs(val), tuple(jnp.abs(f) for f in factors),
               mode=n - 1, dim=dim, precision="highest")
    ratio, inner, est = _last_update_terms(m, a, tuple(factors), lam)
    return float(ratio), _fit(norm_x_sq, inner, est)


@jax.jit
def _last_update_terms(m, a, factors, lam):
    hi = lax.Precision.HIGHEST
    v = None
    for f in factors[:-1]:
        g = jnp.dot(f.T, f, precision=hi)
        v = g if v is None else v * g
    r = v.shape[0]
    v = v + (1e-8 + 1e-6 * jnp.trace(v) / r) * jnp.eye(r, dtype=v.dtype)
    z = factors[-1] * lam[None, :]
    gap = jnp.abs(jnp.dot(z, v, precision=hi) - m)
    bound = a + jnp.dot(jnp.abs(z), jnp.abs(v), precision=hi)
    ratio = jnp.max(gap / jnp.maximum(bound, jnp.finfo(jnp.float32).tiny))
    inner, est = _fit_terms(m, factors, lam, precision="highest")
    return ratio, inner, est


def cp_als(idx, val, norm_x_sq: float, dims, rank: int, key, sweeps: int,
           precision: str = "highest"):
    """``sweeps`` ALS sweeps from ``key``'s initial factors over the
    device COO ``(idx, val)`` (see :func:`device_coo`). Returns
    ``(factors, lam, fits)`` with the factors as NumPy arrays."""
    factors = init_factors(key, dims, rank)
    lam = jnp.ones((rank,), jnp.float32)
    fits = []
    for _ in range(sweeps):
        for d in range(len(dims)):
            m = mttkrp(idx, val, tuple(factors), mode=d, dim=int(dims[d]),
                       precision=precision)
            factors[d], lam = als_update(m, tuple(factors), mode=d,
                                         precision=precision)
        inner, est = _fit_terms(m, tuple(factors), lam, precision=precision)
        fits.append(_fit(norm_x_sq, inner, est))
    return [np.asarray(f) for f in factors], np.asarray(lam), fits
