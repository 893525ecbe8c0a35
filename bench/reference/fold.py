"""Plain reference of a FedEx-LoRA round close (arXiv:2410.09432, Eq. 14).

For one adapted matrix and clients c with factors a_c (m×r), b_c (r×n) and
weights w_c (Σ w_c = 1): the global adapter is (ā, b̄) = (Σ w_c a_c,
Σ w_c b_c), and the base absorbs the exact residual

    W0 ← W0 + s · (Σ_c w_c a_c b_c − ā b̄),

computed in float32 and stored in W0's own dtype after every round. Straight
``jax.numpy``; every product is an ``einsum`` through :func:`dot`, in float32
at ``HIGHEST`` (``mode="f32"``), or, for the control, as three bfloat16
passes (``mode="3pass"``: what ``Precision.HIGH`` does on a TPU, written out
so it means the same on any backend). Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    """``x`` rounded to bfloat16's 8 significant bits, kept in float32.
    ``reduce_precision`` and not a round trip through ``astype``: a compiler
    that allows excess precision may drop a float32→bfloat16→float32 pair
    (the TPU's does)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def dot(spec: str, x, y, mode: str = "f32"):
    if mode == "f32":
        return jnp.einsum(spec, x, y, precision=HIGHEST)
    if mode != "3pass":
        raise ValueError(f"unknown mode {mode!r}")
    (xh, xl), (yh, yl) = _split(x), _split(y)
    return (jnp.einsum(spec, xh, yh, precision=HIGHEST)
            + jnp.einsum(spec, xh, yl, precision=HIGHEST)
            + jnp.einsum(spec, xl, yh, precision=HIGHEST))


@functools.partial(jax.jit, static_argnames=("mode",))
def means(a, b, w, mode="f32"):
    """(ā, b̄) over the leading client axis."""
    return dot("c,c...->...", w, a, mode), dot("c,c...->...", w, b, mode)


@functools.partial(jax.jit, static_argnames=("mode",))
def residual(a, b, w, mode="f32"):
    """Σ_c w_c a_c b_c − ā b̄ for a (C, …, m, r), b (C, …, r, n)."""
    wa = a * w.reshape((-1,) + (1,) * (a.ndim - 1))
    prod = dot("c...mr,c...rn->...mn", wa, b, mode)
    abar, bbar = means(a, b, w, mode)
    return prod - dot("...mr,...rn->...mn", abar, bbar, mode)


@jax.jit
def fold(w, res, scale):
    """One close: W0 + s·res in float32, stored in W0's dtype."""
    return (w.astype(jnp.float32) + scale * res).astype(w.dtype)


def replay(w0, r_even, r_odd, scale, rounds: int):
    """W0 after ``rounds`` closes; round k folds ``r_even`` when k is even,
    ``r_odd`` when odd. One program run per round, so W0 is stored in its
    own dtype between rounds as the program stores it (a fused loop would
    let the compiler keep the float32 sum across rounds)."""
    w = w0
    for k in range(rounds):
        w = fold(w, r_odd if k % 2 else r_even, scale)
    return w
