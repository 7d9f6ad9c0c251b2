"""Trace-time block-size autotuning for the Pallas tile kernels.

The fused Gram/RFF kernels take ``block_m``/``block_n`` tile sizes that trade
VMEM footprint against MXU utilisation and grid overhead. Hardcoding 256
everywhere (the pre-autotune default) is wrong at both ends: tiny problems pay
for padding up to a tile nobody fills, and wide-``d`` problems blow the VMEM
budget a smaller tile would respect. This module resolves ``block="auto"``
requests *at trace time* — shapes are static under ``jit``, so the lookup runs
in Python and returns a plain ``int``; re-tracing never happens because the
resolved block feeds the same static ``pallas_call`` arguments every time (see
tests/test_autotune.py).

Resolution order:

1. **Committed table** (``<checkout>/results/AUTOTUNE_gram.json``, resolved
   from this file's location so the working directory does not matter;
   overridable via the ``REPRO_AUTOTUNE_TABLE`` env var): keys are
   ``"<family>|n<bucket>|d<bucket>|<dtype>"`` over the shape grid swept by
   ``benchmarks/bench_gram_kernel.py`` (which emits the artifact — see
   docs/kernels.md for how to regenerate it). Shapes bucket to the
   nearest-lower grid point, so any (n, d) resolves to a swept neighbourhood.
2. **VMEM-budget heuristic** for unseen keys or a missing table (a table
   that exists but cannot be read raises instead): the largest
   candidate block whose estimated per-tile footprint fits ``VMEM_BUDGET_BYTES``
   and that does not out-pad the problem (never a 512 tile for 300 rows).

``check_matvecs.py`` gates table freshness: if the committed table's keys drift
from the grid this module expects (``expected_keys()``), CI fails until the
sweep is re-run.
"""
from __future__ import annotations

import functools
import json
import os
from pathlib import Path

#: Kernel families with distinct tile-footprint shapes.
FAMILIES = ("gram", "rff")

#: Training-set-size buckets (rows of the padded operand). Nearest-lower match.
N_GRID = (1024, 4096, 16384, 65536)

#: Input-dimension buckets. Nearest-lower match.
D_GRID = (2, 8, 32, 128)

#: Operand dtypes the table distinguishes (tile precision halves bf16 traffic).
DTYPES = ("float32", "bfloat16")

#: Blocks the sweep tries, largest first — the heuristic walks this list too.
CANDIDATE_BLOCKS = (512, 256, 128)

#: Per-kernel-invocation VMEM budget for the heuristic (half of a typical
#: 16 MB/core, leaving room for double buffering).
VMEM_BUDGET_BYTES = 8 * 2 ** 20

#: Assumed RHS width for footprint estimates (the solvers' pathwise multi-RHS
#: batch is num_samples + 1 ≈ 16; the estimate is deliberately round).
RHS_WIDTH_ESTIMATE = 16

#: Environment variable overriding the committed table path.
AUTOTUNE_ENV = "REPRO_AUTOTUNE_TABLE"

#: Committed-table location in the checkout (this file is
#: ``<checkout>/src/repro/kernels/``; the bench emits the table there).
DEFAULT_TABLE_PATH = str(
    Path(__file__).resolve().parents[3] / "results" / "AUTOTUNE_gram.json"
)


def _bucket(grid: tuple, v: int) -> int:
    lower = [g for g in grid if g <= v]
    return max(lower) if lower else grid[0]


def table_key(family: str, n: int, d: int, dtype: str = "float32") -> str:
    """Bucketed lookup key for a (family, n, d, dtype) shape."""
    if family not in FAMILIES:
        raise ValueError(f"unknown kernel family {family!r}; expected {FAMILIES}")
    if dtype not in DTYPES:
        raise ValueError(f"unknown tile dtype {dtype!r}; expected {DTYPES}")
    return f"{family}|n{_bucket(N_GRID, n)}|d{_bucket(D_GRID, d)}|{dtype}"


def expected_keys() -> set:
    """Every key the committed table must cover — the sweep's shape grid."""
    return {
        table_key(f, n, d, t)
        for f in FAMILIES for n in N_GRID for d in D_GRID for t in DTYPES
    }


def vmem_bytes(
    family: str, bm: int, bn: int, d: int,
    s: int = RHS_WIDTH_ESTIMATE, dtype: str = "float32",
) -> int:
    """Estimated VMEM footprint of one tile step (operands + tile + accumulator).

    Operand tiles land at the tile dtype; the pair/matvec accumulators and the
    in-flight (bm, bn) tile stay fp32 (the kernels accumulate in fp32 even when
    the MXU operands are bf16).
    """
    el = 2 if dtype == "bfloat16" else 4
    if family == "gram":
        # x (bm,d) + z (bn,d) + v (bn,s) operands; k tile (bm,bn) + acc (bm,s)
        return el * (bm * d + bn * d + bn * s) + 4 * (bm * bn + bm * s)
    # rff: x (bm,d) + ω (bn,d) + both w halves (2·bn·s); proj tile + acc
    return el * (bm * d + bn * d + 2 * bn * s) + 4 * (bm * bn + bm * s)


def heuristic_block(
    family: str, n: int, d: int, dtype: str = "float32",
    s: int = RHS_WIDTH_ESTIMATE,
) -> int:
    """Largest candidate block that fits the VMEM budget without out-padding n."""
    for b in CANDIDATE_BLOCKS:
        if b > max(CANDIDATE_BLOCKS[-1], n):
            continue  # padding a small problem up to b wastes every extra row
        if vmem_bytes(family, b, b, d, s=s, dtype=dtype) <= VMEM_BUDGET_BYTES:
            return b
    return CANDIDATE_BLOCKS[-1]


@functools.lru_cache(maxsize=8)
def _load_table(path: str) -> tuple:
    try:
        with open(path) as f:
            data = json.load(f)
    except FileNotFoundError:
        return ()
    except (OSError, ValueError) as e:
        raise ValueError(
            f"autotune table {path} exists but cannot be read ({e}); fix or "
            f"regenerate it (benchmarks/bench_gram_kernel.py) rather than let "
            f"block='auto' fall back to the heuristic in silence"
        ) from e
    table = data.get("table", data) if isinstance(data, dict) else {}
    return tuple(sorted((str(k), int(v)) for k, v in table.items()))


def load_table(path: str | None = None) -> dict:
    """The committed autotune table as {key: block}; {} if absent. A table
    that exists but cannot be read raises ``ValueError``.

    Cached per path — call ``load_table.cache_clear()`` (forwarded to the inner
    cache) after regenerating the artifact in-process.
    """
    path = path or os.environ.get(AUTOTUNE_ENV) or DEFAULT_TABLE_PATH
    return dict(_load_table(path))


load_table.cache_clear = _load_table.cache_clear  # type: ignore[attr-defined]


def resolve_block(
    family: str, n: int, d: int, *,
    precision: str = "fp32", table: dict | None = None,
    s: int = RHS_WIDTH_ESTIMATE,
) -> int:
    """Resolve ``block="auto"`` to a concrete static tile size.

    Pure trace-time Python on static shapes: committed-table lookup first,
    VMEM-budget heuristic fallback. Always returns a plain ``int``.
    """
    dtype = "bfloat16" if precision == "bf16" else "float32"
    if table is None:
        table = load_table()
    blk = table.get(table_key(family, n, d, dtype))
    # a key bucketed DOWN from a larger n can still advise a tile bigger than
    # this problem (n=192 buckets to n1024); never out-pad on table advice
    if blk is not None and int(blk) <= max(CANDIDATE_BLOCKS[-1], n):
        return int(blk)
    return heuristic_block(family, n, d, dtype=dtype, s=s)
