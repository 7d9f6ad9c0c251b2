"""Operations and bytes of the measured operations, from their call shapes.

The work counted is the operation's, whatever kernel implements it, so a
kernel that recomputes or pads more does not earn a higher share.

Gram matvec ``K(x, z) @ v`` with ``x`` of shape (n, d), ``z`` (m, d) and
``v`` (m, s), the kernel built from squared distances:

* distances: ``2 n m d`` (the cross inner products) plus ``2 (n + m) d``
  (the squared norms);
* the covariance map: ``ELEMENTWISE[kind]`` operations per entry, counted
  from the map as the fused kernel writes it (assemble ``|x|^2 + |z|^2 -
  2 x.z``, clamp at zero, then the map itself);
* the contraction with ``v``: ``2 n m s``.

Bytes are the least the operation must move: ``x``, ``z`` and ``v`` read
once and the (n, s) result written once.

The chip's VPU and EUP rates (elementwise and transcendental work) are not
published, so the roofline bound takes all operations at the MXU's peak; on
the Matérn maps the elementwise count is a few per entry beside ``2 (d + s)``
contraction operations, and the bound understates the least time by at most
that ratio.
"""
from __future__ import annotations

import json
from pathlib import Path

#: operations per kernel entry after the cross inner product:
#: assembling d^2 (3) and the clamp (1), then the map:
#: se: -0.5 d^2, exp (2); matern12: sqrt(d^2 + eps) (2), -r, exp (2);
#: matern32: sqrt (2), sqrt(3) r (1), -s, exp (2), (1 + s) * e (2);
#: matern52: sqrt (2), s (1), s*s/3 (2), 1 + s + . (2), -s, exp (2), * (1)
ELEMENTWISE = {"se": 6, "matern12": 8, "matern32": 11, "matern52": 14}

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def gram_mv_work(kind: str, n: int, m: int, d: int, s: int, itemsize: int = 4):
    """(operations, bytes) of one Gram matvec K(x, z) @ v."""
    entries = n * m
    ops = 2 * entries * d + 2 * (n + m) * d + ELEMENTWISE[kind] * entries \
        + 2 * entries * s
    nbytes = itemsize * (n * d + m * d + m * s + n * s)
    return ops, nbytes


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The chip's published peaks; an unknown device kind is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time_s(ops: float, nbytes: float, peak: dict):
    """(seconds, bound) of the roofline: the larger of ops over peak FLOP/s
    and bytes over HBM bandwidth, and which of the two it was."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
