"""Static scaling analysis — prove the sharding story from compiled HLO.

On a single-core virtual device mesh, wall-clock scaling tables are
meaningless (every "device" shares one core), so claims like "the
federated reduction scales over ICI" must be proven STATICALLY: lower
the program at several mesh widths, read the compiled HLO, and assert

- per-device FLOPs fall ~1/d (the compute is actually partitioned);
- the bytes moved by cross-device collectives are O(model parameters)
  and INDEPENDENT of the node count / batch size (one all-reduce of
  the aggregate, not a gather of per-node replicas).

Used by tests/test_scaling_model.py and by ``__graft_entry__``'s
multichip dryrun, whose MULTICHIP report carries the verdict.
"""

from __future__ import annotations

import re
from typing import Any

from tpfl.management.profiling import cost_model

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

_SHAPE_RE = re.compile(r"([a-z]+\d*)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Bytes produced by each collective kind in optimized HLO text
    (result shapes of ``all-reduce``/``all-gather``/… ops; ``-start``
    variants counted once, ``-done`` skipped)."""
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        for kind in _COLLECTIVES:
            token = f" {kind}("
            start_token = f" {kind}-start("
            if token not in line and start_token not in line:
                continue
            lhs = line.split(f"{kind}-start(")[0].split(f"{kind}(")[0]
            # result may be a tuple: every shape before the op name
            total = sum(
                _shape_bytes(m.group(1), m.group(2))
                for m in _SHAPE_RE.finditer(lhs)
            )
            out[kind] = out.get(kind, 0) + total
            break
    return out


def analyze_compiled(compiled: Any) -> dict[str, Any]:
    """{"flops": per-device flops, "collectives": {kind: bytes},
    "collective_bytes": total}.

    FLOPs come from the shared :class:`~tpfl.management.profiling
    .CostModel` — the ONE ``cost_analysis()`` call path (the live
    ``tpfl_mfu`` gauge uses the same one, with the same
    scan-counted-once caveat), so static scaling analysis and live MFU
    can never disagree about what a program costs."""
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": cost_model.xla_flops(compiled) or 0.0,
        "collectives": coll,
        "collective_bytes": sum(coll.values()),
    }


def params_bytes(tree: Any) -> int:
    import jax

    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def check_scaling(
    records: list[dict],
    params_nbytes: int,
    flops_tol: float = 0.25,
    collective_factor: float = 4.0,
) -> list[str]:
    """Assert the scaling conditions over per-width analysis records
    ``[{"width": d, "flops": F_d, "collective_bytes": C_d}, ...]``.
    Returns a list of human-readable failures (empty = pass).

    - F_d · d within ``flops_tol`` of F_1 (per-device compute ∝ 1/d;
      the slack absorbs padding and the O(params) aggregation ops);
    - for d > 1: C_d ≤ collective_factor · params_nbytes (the
      reduction moves O(params), never O(params · nodes)), and C_d is
      width-independent within 2× (no hidden re-replication).
    """
    failures: list[str] = []
    base = next((r for r in records if r["width"] == 1), records[0])
    # Compare total WORK (per-device flops x width) so the check is
    # meaningful even when no width-1 record exists.
    base_work = base["flops"] * base["width"]
    for r in records:
        work = r["flops"] * r["width"]
        if not (
            base_work * (1 - flops_tol) <= work <= base_work * (1 + flops_tol)
        ):
            failures.append(
                f"width {r['width']}: per-device flops x width = {work:.0f} "
                f"not within {flops_tol:.0%} of base work "
                f"{base_work:.0f} — compute is not 1/d-partitioned"
            )
    multi = [r for r in records if r["width"] > 1]
    for r in multi:
        if r["collective_bytes"] > collective_factor * params_nbytes:
            failures.append(
                f"width {r['width']}: collective bytes "
                f"{r['collective_bytes']} exceed {collective_factor}x "
                f"params ({params_nbytes} B) — reduction is not O(params)"
            )
    if multi:
        cs = [r["collective_bytes"] for r in multi]
        if max(cs) > 2 * max(1, min(cs)):
            failures.append(
                f"collective bytes vary {min(cs)}..{max(cs)} across widths "
                f"— hidden width-dependent re-replication"
            )
    return failures
