"""Operations and bytes of the Tanimoto (MinMax) matvec, from its call shapes.

``T(x, z) @ v`` with ``x`` of shape (n, d), ``z`` (m, d) and ``v`` (m, s),
``T = Σ min / Σ max`` over counts. The work counted is the operation's,
whatever route the kernel takes (the fused kernel does Σmin as C·d-deep
count-plane products on the MXU, more operations than these), so a kernel
that recomputes or pads more does not earn a higher share:

* Σmin and Σmax: ``2 n m d`` (a min and an add per pair and dim; Σmax then
  comes from the norms);
* the norms: ``(n + m) d`` adds;
* the map: ``MAP_OPS`` per entry (Σmax = |x|₁ + |z|₁ − Σmin, the guard of two
  all-zero rows, the division);
* the contraction with ``v``: ``2 n m s``.

All operations are taken at the MXU's peak (the VPU's rate is not
published), so the share is a lower bound on nearness to the chip's limit.
Bytes are the least the operation must move: ``x``, ``z`` and ``v`` read once
at their stored dtype (float32) and the (n, s) result written once.
"""
from __future__ import annotations

from .work import least_time_s

#: Σmax from the norms (2), the zero guard (1), the division (1)
MAP_OPS = 4
#: the feature kernel's trace name
RF_KERNEL = r"tanimoto_rf_pallas"
#: the fused kernel's trace name (``repro.kernels.tanimoto``)
KERNEL = r"tanimoto_matvec_pallas"


def tanimoto_mv_work(n: int, m: int, d: int, s: int, itemsize: int = 4):
    """(operations, bytes) of one Tanimoto matvec T(x, z) @ v."""
    entries = n * m
    ops = 2 * entries * d + (n + m) * d + MAP_OPS * entries + 2 * entries * s
    nbytes = itemsize * (n * d + m * d + m * s + n * s)
    return ops, nbytes


def tanimoto_mv_calls(trace):
    """``(operations, bytes, seconds)`` of each fused Tanimoto matvec in the
    window: operands ``x`` (n, d), ``z`` (m, d), ``v`` (m, s)."""
    out = []
    for shapes, secs in trace.kernel_calls(KERNEL):
        (n, d), (m, _), (_, s) = shapes[:3]
        out.append((*tanimoto_mv_work(n, m, d, s), secs))
    return out


def tanimoto_mv_roofline(trace, peak: dict):
    """Least time of the window's Tanimoto matvecs over their device time, in
    %; ``None`` when none ran."""
    calls = tanimoto_mv_calls(trace)
    spent = sum(c[2] for c in calls)
    if not calls or spent <= 0:
        return None
    least = sum(least_time_s(ops, nbytes, peak)[0] for ops, nbytes, _ in calls)
    return 100.0 * least / spent

