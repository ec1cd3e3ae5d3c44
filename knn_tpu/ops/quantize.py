"""Per-row symmetric int8 quantization with a *certified* error bound —
the arithmetic behind the kernel's ``precision="int8"`` arm.

TPU MXUs execute int8 dot products at roughly double bf16 throughput
(the TPU-KNN paper's peak-FLOP/s mode, PAPERS.md), and an int8-resident
database also quarters the coarse pass's HBM traffic — which is exactly
what the streaming kernel's tile loop is bound by.  The certified
pipeline can exploit that only because a quantized coarse score comes
with a PROVABLE per-query bound ε on its distance error: the certify
threshold widens by ε, so a quantization-induced miss is *detected* and
lands in the existing fallback — recall@k = 1.0 holds by construction,
never by accuracy folklore.

Quantization scheme (``quantize_rows``): per row, ``scale = max|x|/127``
(1.0 for zero rows) and ``values = clip(round(x / scale), -127, 127)``
as int8.  The dequantized row is ``scale * values`` and the per-component
residual is bounded by ``scale / 2`` — but the bound below never uses
that worst case: it uses the ACTUAL residual norms, computed once at
quantization time, which is what lets exactly-representable data (bvecs
bytes, integer features) certify as tightly as the f32 kernel.

Error bound derivation (the certificate's ε).  The int8 kernel scores a
db row ``t`` against a query ``q`` (both optionally shifted by a common
``offset`` — squared L2 is translation invariant) as

    ŝ(t) = tn - 2 * sq * st * (qi · ti)          (qi·ti exact in int32)

where ``tn`` is the true f32 row norm and ``sq*qi = q̂``, ``st*ti = t̂``
are the dequantized vectors.  Writing ``q = q̂ + eq``, ``t = t̂ + et``:

    q·t - q̂·t̂ = q̂·et + eq·t̂ + eq·et

so by Cauchy-Schwarz, with per-db-row maxima hoisted at quantization
time (``db_bound_stats``),

    |s(t) - ŝ(t)| <= 2*( ||q̂||₂·E + ||eq||₂·T + ||eq||₂·E ) =: ε_quant
        T = max_j ||t̂_j||₂,   E = max_j ||et_j||₂.

Every factor is computable from the scales and payloads alone; nothing
is estimated.  On top rides an f32-evaluation slack for the rescale
pipeline (the int8→f32 conversion is EXACT per 128-wide dim chunk:
|qi·ti| <= 128*128*128 < 2^24), budgeted like the existing bf16x3 /
"highest" tolerance models:

    ε = ε_quant * (1 + 2^-10)  +  64 * eps_f32 * (||q||² + max||t||²)

``tests/test_quantize.py`` property-checks ε >= the observed error for
random draws across dims and dtypes; ``uint8`` data (SIFT-style bvecs)
takes :func:`from_uint8` — the byte payload itself, re-centered by the
L2-invariant -128 shift at unit scale, so ε_quant is exactly zero.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import numpy as np

#: headroom multiplier on the (rigorous) quantization term, covering the
#: f32 evaluation of the bound itself plus sub-ulp effects of computing
#: eq/q̂ norms in f32 on device
_BOUND_HEADROOM = 1.0 + 2.0 ** -10
#: budgeted f32-arithmetic slack factor for the int8 score pipeline
#: (rescale multiplies, chunk accumulation, tn reduction, the
#: certificate's own q_norm reduction) — same style as the 32-eps
#: "highest" and 2^-14 bf16x3 models in ops.pallas_knn.kernel_tolerance
_F32_SLACK = 64.0 * float(np.finfo(np.float32).eps)


class QuantizedRows(NamedTuple):
    """A per-row symmetrically quantized matrix.

    ``values`` int8 [N, D]; ``scales`` f32 [N]; ``offset`` is the common
    scalar subtracted from the f32 data before quantization (squared-L2
    distances are translation invariant, so a shifted coarse pass ranks
    identically — the mechanism that lets uint8 bvecs payloads ride at
    unit scale).  Dequantized (shifted-space) rows are
    ``scales[:, None] * values``; original-space rows add ``offset``.
    """

    values: np.ndarray
    scales: np.ndarray
    offset: float = 0.0


def quantize_rows_np(x: np.ndarray, offset: float = 0.0) -> QuantizedRows:
    """Host-side per-row symmetric quantization (numpy; the placement /
    test path).  ``offset`` is subtracted first."""
    xs = np.asarray(x, dtype=np.float32) - np.float32(offset)
    amax = np.abs(xs).max(axis=-1)
    scales = np.where(amax > 0, amax / np.float32(127.0), np.float32(1.0))
    scales = scales.astype(np.float32)
    q = np.clip(np.round(xs / scales[:, None]), -127, 127).astype(np.int8)
    return QuantizedRows(q, scales, float(offset))


def quantize_rows(x):
    """Traceable (jax.numpy) per-row symmetric quantization — the form
    the kernel prologue and the on-device bound share.  Returns
    ``(values int8, scales f32)``; the caller applies any offset before
    the call."""
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(x), axis=-1)
    scales = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scales[:, None]), -127, 127).astype(jnp.int8)
    return q, scales


def dequantize(qr: QuantizedRows) -> np.ndarray:
    """f32 reconstruction in ORIGINAL space (offset restored)."""
    return (qr.scales[:, None].astype(np.float32)
            * qr.values.astype(np.float32)
            + np.float32(qr.offset))


def from_uint8(x: np.ndarray) -> QuantizedRows:
    """uint8 rows (SIFT-style bvecs payloads) fed to the int8 path
    DIRECTLY: the byte values re-centered by the L2-invariant -128 shift
    land exactly in int8 at UNIT scale — no f32 round trip, residuals
    identically zero, so the certificate's quantization term vanishes
    and the int8 coarse pass is as tight as the f32 kernel on this
    data."""
    x = np.asarray(x)
    if x.dtype != np.uint8:
        raise ValueError(f"from_uint8 expects uint8 rows, got {x.dtype}")
    vals = (x.astype(np.int16) - 128).astype(np.int8)
    scales = np.ones(x.shape[0], dtype=np.float32)
    return QuantizedRows(vals, scales, 128.0)


def _f32_up(v: float) -> np.float32:
    """Round a float64 statistic UP to f32 so the device-side bound can
    never shrink through the cast."""
    f = np.float32(v)
    if float(f) < v:
        f = np.nextafter(f, np.float32(np.inf))
    return f


def db_bound_stats(
    qr: QuantizedRows, original: np.ndarray, *, chunk: int = 65536,
) -> dict:
    """The db-side maxima of the error bound, computed in float64 once
    at quantization/placement time from the ACTUAL residuals:

      ``t2hat_max``    max_j ||t̂_j||₂   (dequantized row norms),
      ``et2_max``      max_j ||t̂_j - t'_j||₂  (residual norms; exactly
                       0.0 for :func:`from_uint8` payloads),
      ``db_norm_max``  max_j ||t'_j||²  (shifted-space squared norms —
                       the f32-slack scale),

    where t' = original - offset.  Chunked so a 1M-row database never
    materializes a full f64 copy."""
    t2hat = 0.0
    et2 = 0.0
    nrm = 0.0
    n = qr.values.shape[0]
    for lo in range(0, n, chunk):
        hi = min(n, lo + chunk)
        t_sh = original[lo:hi].astype(np.float64) - qr.offset
        t_hat = (qr.scales[lo:hi, None].astype(np.float64)
                 * qr.values[lo:hi].astype(np.float64))
        t2hat = max(t2hat, float(np.sqrt((t_hat ** 2).sum(-1)).max()))
        et2 = max(et2, float(np.sqrt(((t_hat - t_sh) ** 2).sum(-1)).max()))
        nrm = max(nrm, float((t_sh ** 2).sum(-1).max()))
    return {
        "t2hat_max": float(t2hat),
        "et2_max": float(et2),
        "db_norm_max": float(nrm),
        "dim": int(qr.values.shape[1]),
    }


def bound_consts(stats: dict) -> np.ndarray:
    """[db_norm_max, t2hat_max, et2_max] as an f32 vector (each rounded
    UP), the replicated operand the sharded int8 program consumes — ONE
    packing home shared with :func:`score_error_bound_device`'s
    unpacking."""
    return np.array(
        [_f32_up(stats["db_norm_max"]), _f32_up(stats["t2hat_max"]),
         _f32_up(stats["et2_max"])],
        dtype=np.float32,
    )


def score_error_bound(
    q: np.ndarray, stats: dict, *, offset: float = 0.0,
) -> np.ndarray:
    """Host-side per-query ε [Q] (float64): sound upper bound on
    |f32 kernel score - int8 reconstructed score| for EVERY db row (see
    module docstring).  Mirrors :func:`score_error_bound_device`; the
    property test in tests/test_quantize.py pins ε >= observed."""
    qi, sq = quantize_rows_np(q, offset=offset)[:2]
    q_sh = np.asarray(q, dtype=np.float64) - offset
    q_hat = sq[:, None].astype(np.float64) * qi.astype(np.float64)
    eq2 = np.sqrt(((q_sh - q_hat) ** 2).sum(-1))
    qhat2 = np.sqrt((q_hat ** 2).sum(-1))
    q_norm = (q_sh ** 2).sum(-1)
    quant = 2.0 * (qhat2 * stats["et2_max"]
                   + eq2 * stats["t2hat_max"]
                   + eq2 * stats["et2_max"])
    return (quant * _BOUND_HEADROOM
            + _F32_SLACK * (q_norm + stats["db_norm_max"]))


def score_error_bound_device(q_shifted, consts):
    """Traceable twin of :func:`score_error_bound` for the sharded
    certificate program: ``q_shifted`` [Q, D] f32 (offset already
    subtracted), ``consts`` the :func:`bound_consts` vector.  Returns
    ``(q_norm [Q], eps [Q])`` — the shifted-space query norms the
    certificate compares in, and the per-query threshold widening.  The
    query re-quantization here traces the same ops as the kernel
    prologue's, so the residuals are the kernel's actual residuals."""
    import jax.numpy as jnp

    qi, sq = quantize_rows(q_shifted)
    q_hat = sq[:, None] * qi.astype(jnp.float32)
    eq = q_shifted - q_hat
    eq2 = jnp.sqrt(jnp.sum(eq * eq, axis=-1))
    qhat2 = jnp.sqrt(jnp.sum(q_hat * q_hat, axis=-1))
    q_norm = jnp.sum(q_shifted * q_shifted, axis=-1)
    db_norm_max, t2hat_max, et2_max = consts[0], consts[1], consts[2]
    quant = 2.0 * (qhat2 * et2_max + eq2 * t2hat_max + eq2 * et2_max)
    eps = quant * _BOUND_HEADROOM + _F32_SLACK * (q_norm + db_norm_max)
    return q_norm, eps
