"""FedEx-LoRA residual fold-in Pallas kernels (the paper's Eq. 12+14, fused).

The flagship kernel computes  W0 + scale·( Σ_c w_c·(a_c @ b_c) − ā @ b̄ )
tile-by-tile, where ā = Σ_c w_c·a_c (and likewise b̄): for each MXU-aligned
(bm, bn) output tile, the stacked client factors stream through VMEM once and
the dense m×n residual is NEVER materialised in HBM (the naive host path
builds the full ΔW_res then adds — an extra 2·m·n f32 HBM round trip per
adapted matrix per round; at deepseek-v2 scale that is ~5 GB of avoidable
traffic per aggregation).

Two weighting modes:

* ``weights=None`` — the historical uniform mean. The kernel unrolls the
  client sum in slot order and divides by C at the end, mirroring
  ``core/aggregation.py``'s ``sum(...)/k`` op-for-op so the uniform path stays
  bitwise identical to the jnp ground truth.
* ``weights=(C,) f32`` — per-client weight vector delivered through scalar
  prefetch (SMEM, available before the tile loop starts). Zero-weight lanes
  act as a **participation mask**: stacks padded to a fixed ``C_max`` compile
  ONCE and serve every round — ragged quorums, partial participation and
  example-count weighting all reuse the same program, they only change the
  vector.

Three masked variants share the tiling and the scalar-prefetch weight vector,
covering the remaining round-close methods of the engine (core/engine.py).
Their padded public wrappers are ``kernels/ops.py::product_fold`` and
``perclient_fold`` (as ``fedex_fold`` wraps :func:`fedex_residual_apply`) —
the engine and every caller go through those:

* :func:`product_fold_apply` (→ ``ops.product_fold``) — W0 +
  scale·Σ_c s_c·(a_c @ b_c) with a SIGNED per-lane vector and no
  mean-product subtraction. s = w closes a ``reinit`` round (the full ideal
  update folds into W0, paper Table 5); a single lane with s = [1] folds a
  factored rank-r' truncated residual (the fedex_svd close) without the
  dense ΔW ever reaching HBM.
* :func:`perclient_fold_apply` (→ ``ops.perclient_fold``) — the
  ``keep_local`` close: every lane's own update
  W0_c + scale·(Σ_j w_j a_j b_j − a_c b_c)  in ONE pass. The ideal tile
  Σ_j w_j a_j b_j is accumulated once per output tile and the per-lane
  own-product is recomputed from the resident VMEM slabs (r is small, so the
  extra FLOPs are negligible vs re-streaming C dense residuals from HBM).
* :func:`hetero_fold_apply` (→ ``ops.hetero_fold``) — the ``hetero`` close:
  perclient_fold with ragged ranks. A SECOND scalar-prefetch vector carries
  each lane's true rank; padded rank columns are masked to exact zero inside
  the tile loop, and every lane's own-product comes from the SHARED
  rank-r_max truncation factors masked down to its own rank.

Every fold computes in f32 and writes the result in W0's dtype into W0's
own buffer (``input_output_aliases``): each grid step reads and writes the
same (bm, bn) tile, so the update is safe in place, and a donated bf16 base
never round-trips a full f32 copy through HBM. A caller that keeps its W0
gets a copy made by XLA.

Tile-indivisible shapes (whisper/qwen head dims, odd vocab slices) are padded
to the next (bm, bn) multiple with zeros and sliced back — zero rows/columns
of a/b contribute nothing to any product, so padding is exact.

The client sum over C is unrolled inside the kernels (C = cross-silo client
count, 3–32 — small); ā/b̄ tiles are recomputed per tile from the same VMEM
slabs, trading negligible FLOPs for zero extra memory traffic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.padding import pad_axis as _pad_axis


def _dot(x, y):
    """f32 product at full f32 precision: the TPU's default precision rounds
    f32 operands to bf16, which would put the close outside the f32 error
    bound it is checked against (``repro.core.bounds``)."""
    return jnp.dot(x, y, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _kernel(w0_ref, a_ref, b_ref, o_ref, *, scale: float, num_clients: int):
    """Uniform path: mean in slot order then /C (bitwise twin of sum(...)/k)."""
    a = a_ref[...].astype(jnp.float32)  # (C, bm, r)
    b = b_ref[...].astype(jnp.float32)  # (C, r, bn)
    mean_prod = _dot(a[0], b[0])
    abar, bbar = a[0], b[0]
    for c in range(1, num_clients):  # static unroll: C is small (cross-silo)
        mean_prod += _dot(a[c], b[c])
        abar = abar + a[c]
        bbar = bbar + b[c]
    mean_prod = mean_prod / num_clients
    abar = abar / num_clients
    bbar = bbar / num_clients
    residual = mean_prod - _dot(abar, bbar)
    o_ref[...] = (w0_ref[...].astype(jnp.float32)
                  + scale * residual).astype(o_ref.dtype)


def _kernel_weighted(w_ref, w0_ref, a_ref, b_ref, o_ref, *, scale: float,
                     num_clients: int):
    """Weighted/masked path: w_ref is the (C,) scalar-prefetch weight vector.

    Zero-weight lanes (masked / non-delivered slots) contribute exactly 0 to
    every sum, so a C_max-padded stack closes any ragged round.
    """
    a = a_ref[...].astype(jnp.float32)  # (C, bm, r)
    b = b_ref[...].astype(jnp.float32)  # (C, r, bn)
    mean_prod = jnp.zeros((a.shape[1], b.shape[2]), jnp.float32)
    abar = jnp.zeros_like(a[0])
    bbar = jnp.zeros_like(b[0])
    for c in range(num_clients):  # static unroll: C is small
        wc = w_ref[c]
        mean_prod += wc * _dot(a[c], b[c])
        abar += wc * a[c]
        bbar += wc * b[c]
    residual = mean_prod - _dot(abar, bbar)
    o_ref[...] = (w0_ref[...].astype(jnp.float32)
                  + scale * residual).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "interpret"))
def fedex_residual_apply(w0: jnp.ndarray, a_stack: jnp.ndarray,
                         b_stack: jnp.ndarray,
                         weights: jnp.ndarray | None = None, *,
                         scale: float = 1.0,
                         bm: int = 256, bn: int = 256,
                         interpret: bool = False) -> jnp.ndarray:
    """w0: (m, n), a_stack: (C, m, r), b_stack: (C, r, n) → (m, n) in
    W0's dtype, written into W0's buffer.

    ``weights`` — optional (C,) f32 normalized weight vector (zeros mask
    non-delivered lanes). ``None`` → uniform 1/C mean, bitwise identical to
    the unweighted jnp operators.
    """
    m, n = w0.shape
    c, _, r = a_stack.shape
    bm, bn = min(bm, m), min(bn, n)
    # pad to the next (bm, bn) multiple — zero rows/cols are exact no-ops for
    # every product in the residual; slice the tile-aligned result back.
    w0p = _pad_axis(_pad_axis(w0, bm, 0), bn, 1)
    ap = _pad_axis(a_stack, bm, 1)
    bp = _pad_axis(b_stack, bn, 2)
    mp, np_ = w0p.shape

    grid = (mp // bm, np_ // bn)
    if weights is None:
        return pl.pallas_call(
            functools.partial(_kernel, scale=scale, num_clients=c),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                pl.BlockSpec((c, bm, r), lambda i, j: (0, i, 0)),
                pl.BlockSpec((c, r, bn), lambda i, j: (0, 0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), w0p.dtype),
            input_output_aliases={0: 0},
            interpret=interpret,
            name="fedex_residual",
        )(w0p, ap, bp)[:m, :n]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
            pl.BlockSpec((c, bm, r), lambda i, j, *_: (0, i, 0)),
            pl.BlockSpec((c, r, bn), lambda i, j, *_: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel_weighted, scale=scale, num_clients=c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, np_), w0p.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
        name="fedex_residual_weighted",
    )(weights.astype(jnp.float32), w0p, ap, bp)[:m, :n]


# --------------------------------------------------------------------------
# signed product fold: W0 + scale·Σ_c s_c·(a_c @ b_c)  (no mean subtraction)
# --------------------------------------------------------------------------

def _kernel_product(s_ref, w0_ref, a_ref, b_ref, o_ref, *, scale: float,
                    num_clients: int):
    """s_ref is a SIGNED (C,) scalar-prefetch vector: s = w folds the ideal
    update (reinit close); s = w − e_i folds client i's keep_local residual;
    one lane with s = [1] folds a factored low-rank residual (svd close).
    Zero lanes vanish — the same participation-mask contract as the weighted
    residual kernel."""
    a = a_ref[...].astype(jnp.float32)  # (C, bm, r)
    b = b_ref[...].astype(jnp.float32)  # (C, r, bn)
    acc = jnp.zeros((a.shape[1], b.shape[2]), jnp.float32)
    for c in range(num_clients):  # static unroll: C is small
        acc += s_ref[c] * _dot(a[c], b[c])
    o_ref[...] = (w0_ref[...].astype(jnp.float32)
                  + scale * acc).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "interpret"))
def product_fold_apply(w0: jnp.ndarray, a_stack: jnp.ndarray,
                       b_stack: jnp.ndarray, signs: jnp.ndarray, *,
                       scale: float = 1.0, bm: int = 256, bn: int = 256,
                       interpret: bool = False) -> jnp.ndarray:
    """w0: (m, n), a_stack: (C, m, r), b_stack: (C, r, n), signs: (C,) f32
    (may be negative) → (m, n) in W0's dtype = W0 + scale·Σ_c s_c·a_c b_c."""
    m, n = w0.shape
    c, _, r = a_stack.shape
    bm, bn = min(bm, m), min(bn, n)
    w0p = _pad_axis(_pad_axis(w0, bm, 0), bn, 1)
    ap = _pad_axis(a_stack, bm, 1)
    bp = _pad_axis(b_stack, bn, 2)
    mp, np_ = w0p.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
            pl.BlockSpec((c, bm, r), lambda i, j, *_: (0, i, 0)),
            pl.BlockSpec((c, r, bn), lambda i, j, *_: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel_product, scale=scale, num_clients=c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, np_), w0p.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
        name="product_fold",
    )(signs.astype(jnp.float32), w0p, ap, bp)[:m, :n]


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "interpret"))
def product_accum_apply(acc: jnp.ndarray, a_stack: jnp.ndarray,
                        b_stack: jnp.ndarray, signs: jnp.ndarray, *,
                        scale: float = 1.0, bm: int = 256, bn: int = 256,
                        interpret: bool = False) -> jnp.ndarray:
    """acc: (m, n) f32, a_stack: (C, m, r), b_stack: (C, r, n), signs: (C,)
    f32 → (m, n) f32 = acc + scale·Σ_c s_c·a_c b_c.

    The read-modify-write twin of :func:`product_fold_apply` for chunked
    streaming closes (core/engine.py chunked ring mode): the running
    accumulator plays W0's role in the same ``_kernel_product`` body, and
    ``input_output_aliases`` hands the accumulator buffer to the output so
    folding chunk k updates it IN PLACE — no second dense m×n allocation per
    partial fold, which is the whole point of chunking.
    """
    m, n = acc.shape
    c, _, r = a_stack.shape
    bm, bn = min(bm, m), min(bn, n)
    accp = _pad_axis(_pad_axis(acc, bm, 0), bn, 1)
    ap = _pad_axis(a_stack, bm, 1)
    bp = _pad_axis(b_stack, bn, 2)
    mp, np_ = accp.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
            pl.BlockSpec((c, bm, r), lambda i, j, *_: (0, i, 0)),
            pl.BlockSpec((c, r, bn), lambda i, j, *_: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel_product, scale=scale, num_clients=c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        # operand 1 = the padded accumulator (0 is the scalar-prefetch sign
        # vector): alias it to the output for the in-place update
        input_output_aliases={1: 0},
        interpret=interpret,
        name="product_accum",
    )(signs.astype(jnp.float32), accp, ap, bp)[:m, :n]


# --------------------------------------------------------------------------
# per-client fold: the keep_local close, all lanes in one pass
# --------------------------------------------------------------------------

def _kernel_perclient(w_ref, w0_ref, a_ref, b_ref, o_ref, *, scale: float,
                      num_clients: int):
    """o[c] = w0[c] + scale·(Σ_j w_j a_j b_j − a_c b_c): the ideal tile is
    accumulated ONCE, then each lane's own product is recomputed from the
    same VMEM slabs — per-lane sign vectors (w − e_c) without C passes."""
    a = a_ref[...].astype(jnp.float32)  # (C, bm, r)
    b = b_ref[...].astype(jnp.float32)  # (C, r, bn)
    ideal = jnp.zeros((a.shape[1], b.shape[2]), jnp.float32)
    for c in range(num_clients):  # static unroll: C is small
        ideal += w_ref[c] * _dot(a[c], b[c])
    for c in range(num_clients):
        own = _dot(a[c], b[c])
        o_ref[c, :, :] = (w0_ref[c].astype(jnp.float32)
                          + scale * (ideal - own)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "interpret"))
def perclient_fold_apply(w0_stack: jnp.ndarray, a_stack: jnp.ndarray,
                         b_stack: jnp.ndarray, weights: jnp.ndarray, *,
                         scale: float = 1.0, bm: int = 256, bn: int = 256,
                         interpret: bool = False) -> jnp.ndarray:
    """w0_stack: (C, m, n), a_stack: (C, m, r), b_stack: (C, r, n),
    weights: (C,) f32 → (C, m, n) in W0's dtype with lane c = W0_c +
    scale·(ideal − a_c b_c). Masked (zero-weight) lanes still produce a
    lane (W0_c + scale·ideal when their factors are zero) — callers discard
    non-delivered lanes, exactly as the engine's C_max padding contract
    prescribes."""
    c, m, n = w0_stack.shape
    r = a_stack.shape[-1]
    bm, bn = min(bm, m), min(bn, n)
    w0p = _pad_axis(_pad_axis(w0_stack, bm, 1), bn, 2)
    ap = _pad_axis(a_stack, bm, 1)
    bp = _pad_axis(b_stack, bn, 2)
    mp, np_ = w0p.shape[1:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((c, bm, bn), lambda i, j, *_: (0, i, j)),
            pl.BlockSpec((c, bm, r), lambda i, j, *_: (0, i, 0)),
            pl.BlockSpec((c, r, bn), lambda i, j, *_: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((c, bm, bn), lambda i, j, *_: (0, i, j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel_perclient, scale=scale, num_clients=c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c, mp, np_), w0p.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
        name="perclient_fold",
    )(weights.astype(jnp.float32), w0p, ap, bp)[:, :m, :n]


# --------------------------------------------------------------------------
# hetero fold: rank-masked per-client fold, shared truncated own factors
# --------------------------------------------------------------------------

def _kernel_hetero(w_ref, rk_ref, w0_ref, a_ref, b_ref, oa_ref, ob_ref,
                   o_ref, *, scale: float, num_clients: int):
    """o[c] = w0[c] + scale·(Σ_j w_j·(a_j∘mask_j) b_j − (A'∘mask_c) B').

    TWO scalar-prefetch vectors ride in SMEM: the (C,) f32 weight vector and
    the (C,) int32 TRUE-rank vector (−1 = full rank). Rank columns of a_j
    past rank_j are zeroed before every product — one-sided masking
    suffices, since zeroing a's column k already kills the k-th rank-1 term
    of a@b — and each lane's own-product uses the SHARED rank-r_max
    truncated factors (A', B') masked down to its own rank: the
    leading-slice Eckart–Young truncation without per-lane shapes, so ONE
    compiled program serves every rank mix in the fleet.
    """
    a = a_ref[...].astype(jnp.float32)    # (C, bm, r)
    b = b_ref[...].astype(jnp.float32)    # (C, r, bn)
    oa = oa_ref[...].astype(jnp.float32)  # (bm, r)
    ob = ob_ref[...].astype(jnp.float32)  # (r, bn)
    r = a.shape[-1]
    # 2-D iota: TPU vector units have no 1-D iota (mosaic lowering rule)
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
    ideal = jnp.zeros((a.shape[1], b.shape[2]), jnp.float32)
    for c in range(num_clients):  # static unroll: C is small
        rk = jnp.where(rk_ref[c] < 0, r, rk_ref[c])
        mask = (iota < rk).astype(jnp.float32)  # (1, r): exact 0/1
        ideal += w_ref[c] * _dot(a[c] * mask, b[c])
    for c in range(num_clients):
        rk = jnp.where(rk_ref[c] < 0, r, rk_ref[c])
        mask = (iota < rk).astype(jnp.float32)
        own = _dot(oa * mask, ob)
        o_ref[c, :, :] = (w0_ref[c].astype(jnp.float32)
                          + scale * (ideal - own)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "interpret"))
def hetero_fold_apply(w0_stack: jnp.ndarray, a_stack: jnp.ndarray,
                      b_stack: jnp.ndarray, weights: jnp.ndarray,
                      ranks: jnp.ndarray, own_a: jnp.ndarray,
                      own_b: jnp.ndarray, *, scale: float = 1.0,
                      bm: int = 256, bn: int = 256,
                      interpret: bool = False) -> jnp.ndarray:
    """w0_stack: (C, m, n), a_stack: (C, m, r), b_stack: (C, r, n),
    weights: (C,) f32, ranks: (C,) int32 (−1 = full rank), own_a: (m, r),
    own_b: (r, n) → (C, m, n) in W0's dtype with lane c = W0_c +
    scale·(ideal − (A'∘mask_c) B'). Zero-weight AND zero-rank lanes both
    vanish from the ideal; callers discard non-delivered lanes (the C_max
    contract)."""
    c, m, n = w0_stack.shape
    r = a_stack.shape[-1]
    bm, bn = min(bm, m), min(bn, n)
    w0p = _pad_axis(_pad_axis(w0_stack, bm, 1), bn, 2)
    ap = _pad_axis(a_stack, bm, 1)
    bp = _pad_axis(b_stack, bn, 2)
    oap = _pad_axis(own_a, bm, 0)
    obp = _pad_axis(own_b, bn, 1)
    mp, np_ = w0p.shape[1:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((c, bm, bn), lambda i, j, *_: (0, i, j)),
            pl.BlockSpec((c, bm, r), lambda i, j, *_: (0, i, 0)),
            pl.BlockSpec((c, r, bn), lambda i, j, *_: (0, 0, j)),
            pl.BlockSpec((bm, r), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((r, bn), lambda i, j, *_: (0, j)),
        ],
        out_specs=pl.BlockSpec((c, bm, bn), lambda i, j, *_: (0, i, j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel_hetero, scale=scale, num_clients=c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c, mp, np_), w0p.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
        name="hetero_fold",
    )(weights.astype(jnp.float32), ranks.astype(jnp.int32), w0p, ap, bp,
      oap, obp)[:, :m, :n]
