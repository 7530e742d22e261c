"""Frozen planning/execution specs — the unified knob surface of the driver.

``plan_batches`` grew 15 keyword knobs and ``batched_summa3d`` 22 as the
paper's features landed (masked planning, the hash path, the retry
ladder, iterated-multiply cap pinning). This module collapses them
into three frozen dataclasses so every caller — MCL, APSP, the serving
engine, the autotuner — passes the SAME objects instead of hand-threading
floor kwargs:

  * ``PlanSpec``   — WHAT to plan: mask, local path, slack, reserved bytes.
    Pure policy; two calls with the same spec and operands
    produce the same ``BatchPlan``.
  * ``PlanFloors`` — capacity floors carried ACROSS plans: the four
    ``*_floor`` knobs plus ``caps_pow2``, with a monotonic ``merged()``
    (elementwise max, like ``RunReport.merged``) so iterated callers pin the
    fused step's static signature by folding each run's used capacities back
    in. JSON round-trips via ``to_meta``/``from_meta`` so a floors value
    survives a checkpoint (MCL / APSP resilient loops).
  * ``ExecSpec``   — HOW to run: pipelined schedule, lookahead depth, retry
    budget, graceful degradation.

``TunedConfig`` (``repro.tune``) is exactly one of each plus a grid shape,
which is what lets the autotuner emit a config the driver and the serve
admission path consume directly.

Backwards compat: the old keyword surface is still accepted for one release.
``resolve_specs`` maps legacy kwargs onto the spec objects (overriding any
field also set on a passed spec) and emits a single ``DeprecationWarning``
listing them.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Tuple

from .summa3d import BatchCaps, HashCaps


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """Planning policy for one multiply (see ``plan_batches``).

    ``local_path`` defaults to "auto" — the plan-driven ESC/hash dispatch.
    Bare ``plan_batches()`` calls (no spec) keep their historical "esc"
    default; a caller who passes a spec opts into the driver's semantics.
    """

    mask: Optional[object] = None  # C-layout DistSparse (§V-B masked plans)
    mask_complement: bool = False
    local_path: str = "auto"  # "auto" | "esc" | "hash" (| "dense": driver)
    slack: float = 1.3
    r_bytes: int = 12
    reserved_bytes: int = 0
    force_num_batches: Optional[int] = None
    # Structure-aware placement (core.placement). ``placement`` means "the
    # operands are ALREADY permuted by this Placement": the driver remaps
    # every consumer-facing column map back to original column space (use
    # ``placement.multiply_placed`` for the end-to-end permute/invert).
    # ``distribution`` swaps the planner's tile→batch fold (None resolves
    # to placement.BLOCK_CYCLIC — the only device-executable choice today).
    placement: Optional[object] = None  # core.placement.Placement
    distribution: Optional[object] = None  # core.placement.Distribution

    def replace(self, **kw) -> "PlanSpec":
        return dataclasses.replace(self, **kw)


def _emax(x, y, cls):
    """None-aware elementwise max of two caps dataclasses."""
    if x is None:
        return y
    if y is None:
        return x
    return cls(*(
        max(p, q)
        for p, q in zip(dataclasses.astuple(x), dataclasses.astuple(y))
    ))


@dataclasses.dataclass(frozen=True)
class PlanFloors:
    """Capacity floors carried across plans (iterated-multiply pinning).

    Every field is a FLOOR: the planner takes an elementwise max with its
    own derived value, so floors can only grow capacities, never shrink
    them — which is exactly what keeps the fused step's static signature
    stable (jit-cache hits) as nnz drifts across iterations.
    """

    caps: Optional[BatchCaps] = None
    sel_cap: int = 0
    num_batches: int = 0
    hash_caps: Optional[HashCaps] = None
    caps_pow2: bool = False

    def merged(self, other: "PlanFloors") -> "PlanFloors":
        """Monotonic fold (like ``RunReport.merged``): elementwise max, so
        ``a.merged(b)`` dominates both a and b."""
        return PlanFloors(
            caps=_emax(self.caps, other.caps, BatchCaps),
            sel_cap=max(self.sel_cap, other.sel_cap),
            num_batches=max(self.num_batches, other.num_batches),
            hash_caps=_emax(self.hash_caps, other.hash_caps, HashCaps),
            caps_pow2=self.caps_pow2 or other.caps_pow2,
        )

    def replace(self, **kw) -> "PlanFloors":
        return dataclasses.replace(self, **kw)

    def to_meta(self) -> dict:
        """JSON-safe encoding (checkpoint sidecars, serve snapshots)."""
        enc = lambda x: None if x is None else [
            int(v) for v in dataclasses.astuple(x)
        ]
        return {
            "caps": enc(self.caps),
            "sel_cap": int(self.sel_cap),
            "num_batches": int(self.num_batches),
            "hash_caps": enc(self.hash_caps),
            "caps_pow2": bool(self.caps_pow2),
        }

    @classmethod
    def from_meta(cls, d: Optional[dict]) -> "PlanFloors":
        if not d:
            return cls()
        dec = lambda v, c: None if v is None else c(*(int(x) for x in v))
        return cls(
            caps=dec(d.get("caps"), BatchCaps),
            sel_cap=int(d.get("sel_cap", 0)),
            num_batches=int(d.get("num_batches", 0)),
            hash_caps=dec(d.get("hash_caps"), HashCaps),
            caps_pow2=bool(d.get("caps_pow2", False)),
        )


@dataclasses.dataclass(frozen=True)
class ExecSpec:
    """Execution policy for the batched driver (schedule + robustness)."""

    pipelined: bool = True
    lookahead: int = 2
    max_retries: int = 4
    degrade: bool = True
    sorted_merge: bool = True

    def replace(self, **kw) -> "ExecSpec":
        return dataclasses.replace(self, **kw)


# legacy keyword -> spec field, one map per spec object
_PLAN_KEYS = {
    "mask": "mask",
    "mask_complement": "mask_complement",
    "local_path": "local_path",
    "slack": "slack",
    "r_bytes": "r_bytes",
    "reserved_bytes": "reserved_bytes",
    "force_num_batches": "force_num_batches",
}
_FLOOR_KEYS = {
    "caps_floor": "caps",
    "sel_cap_floor": "sel_cap",
    "num_batches_floor": "num_batches",
    "hash_caps_floor": "hash_caps",
    "caps_pow2": "caps_pow2",
}
_EXEC_KEYS = {
    "pipelined": "pipelined",
    "lookahead": "lookahead",
    "max_retries": "max_retries",
    "degrade": "degrade",
    "sorted_merge": "sorted_merge",
}


def resolve_specs(
    spec: Optional[PlanSpec],
    floors: Optional[PlanFloors],
    exec_spec: Optional[ExecSpec],
    legacy: dict,
    *,
    default_local_path: str = "auto",
    where: str = "batched_summa3d",
    allow_exec: bool = True,
) -> Tuple[PlanSpec, PlanFloors, ExecSpec]:
    """Normalize (spec, floors, exec_spec, **legacy) to the three specs.

    Legacy kwargs are accepted for one release: each is mapped onto its spec
    field (overriding the passed spec) under a single ``DeprecationWarning``.
    Unknown kwargs raise ``TypeError`` exactly like a real signature.
    """
    if spec is not None and not isinstance(spec, PlanSpec):
        raise TypeError(
            f"{where}: spec must be a PlanSpec, got {type(spec).__name__} "
            f"(old positional keyword arguments must be passed by name)"
        )
    if floors is not None and not isinstance(floors, PlanFloors):
        raise TypeError(
            f"{where}: floors must be a PlanFloors, got {type(floors).__name__}"
        )
    if spec is None:
        spec = PlanSpec(local_path=default_local_path)
    floors = floors if floors is not None else PlanFloors()
    ex = exec_spec if exec_spec is not None else ExecSpec()
    if legacy:
        known = set(_PLAN_KEYS) | set(_FLOOR_KEYS)
        if allow_exec:
            known |= set(_EXEC_KEYS)
        unknown = set(legacy) - known
        if unknown:
            raise TypeError(
                f"{where}() got unexpected keyword argument(s) "
                f"{sorted(unknown)}"
            )
        warnings.warn(
            f"{where}: keyword argument(s) {sorted(legacy)} are deprecated; "
            f"pass PlanSpec / PlanFloors / ExecSpec instead",
            DeprecationWarning,
            stacklevel=3,
        )
        spec = spec.replace(**{
            _PLAN_KEYS[k]: v for k, v in legacy.items() if k in _PLAN_KEYS
        })
        floors = floors.replace(**{
            _FLOOR_KEYS[k]: v for k, v in legacy.items() if k in _FLOOR_KEYS
        })
        if allow_exec:
            ex = ex.replace(**{
                _EXEC_KEYS[k]: v for k, v in legacy.items() if k in _EXEC_KEYS
            })
    return spec, floors, ex
