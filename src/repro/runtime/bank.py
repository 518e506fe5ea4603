"""Multi-point weight banks: every execution mode prepared in one pass.

An :class:`ExecutionPoint` names a whole-model precision policy (the paper's
"approximate" / "accurate" configuration-register settings, generalized to a
ladder). :func:`build_bank` runs ``prepare_params`` once per point through a
SHARED memo, so any layer whose per-layer (format, depth) agrees between two
points — criticality-pinned layers, scan-promoted layers — is materialized
exactly once and aliased into every tree. The serving loop then switches
execution points by handing a different (already-resident) tree to the same
jitted decode step: zero weight-side work per switch, the software analogue
of switching modes "without hardware modification".

Kernel-mode banks additionally share one *treedef* across points: the per-point
dot parameters (CORDIC depth, quantization formats) travel as a traced int32
params vector on each :class:`PreparedWeight` (``point`` child) rather than as
static pytree aux data, so a mode switch also costs zero retraces/recompiles of
the jitted burst/draft/verify programs — one compiled program serves every
point (compile-count asserted in ``tests/test_cordic_fused.py``). carmen/int8
points still carry static meta and re-specialize per point.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax

from repro.core.backends import PreparedWeight, prepare_params
from repro.core.fxp import FXP8, FXP16, FxPFormat
from repro.core.precision_policy import PrecisionPolicy, pin_critical

from .telemetry import calibration_id, estimate_point_cycles

__all__ = ["ExecutionPoint", "MultiPointBank", "build_bank", "default_points",
           "place_bank"]


@dataclasses.dataclass(frozen=True)
class ExecutionPoint:
    """One runtime-selectable mode: a name plus the policy it executes."""

    name: str
    policy: PrecisionPolicy


def default_points(
    fmt: FxPFormat = FXP8,
    *,
    base_policy: Optional[PrecisionPolicy] = None,
    hifi_fmt: Optional[FxPFormat] = FXP16,
) -> Tuple[ExecutionPoint, ...]:
    """The canonical mode ladder: {approx fmt, full fmt, full hifi_fmt}.

    When ``base_policy`` carries per-layer overrides (a §III sensitivity-scan
    assignment), it becomes the cheapest point — the scan already encodes
    which layers tolerate demotion. Otherwise the cheapest point is uniform
    approximate depth with the critical-layer floor pinned.

    The ``hifi_fmt`` point is meaningful for the carmen/kernel backends
    (wider signed-digit grid + activation format). For int8 the effective
    bits cap at 8 either way — pass ``hifi_fmt=None`` there, or the ladder
    gains a point that costs 1.75x cycles for identical arithmetic.
    """
    if base_policy is not None and base_policy.overrides:
        cheap = ExecutionPoint("mixed", pin_critical(base_policy))
    else:
        cheap = ExecutionPoint("approx", pin_critical(PrecisionPolicy.approximate(fmt)))
    points = [cheap, ExecutionPoint("accurate", PrecisionPolicy.accurate(fmt))]
    if hifi_fmt is not None and hifi_fmt != fmt:
        points.append(ExecutionPoint("hifi", PrecisionPolicy.accurate(hifi_fmt)))
    return tuple(points)


@dataclasses.dataclass
class MultiPointBank:
    """Prepared trees for every execution point, cheapest first.

    ``cycles_per_token`` is the estimated engine MAC cycles one decoded token
    costs at each point (iterative-PE model, see ``runtime.telemetry``);
    ``reference`` names the all-accurate baseline that savings are quoted
    against, and ``cycle_model`` names the calibration (or ``"analytic"``)
    those cycles were computed with. ``shared_leaves`` counts prepared leaves
    aliased between at least two points (the zero-copy pinning guarantee,
    test-asserted).
    """

    mode: str
    points: Tuple[ExecutionPoint, ...]
    trees: Dict[str, Any]
    cycles_per_token: Dict[str, float]
    reference: str
    shared_leaves: int = 0
    unique_leaves: int = 0
    cycle_model: str = "analytic"

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(p.name for p in self.points)

    def tree(self, name: str):
        return self.trees[name]

    def index(self, name: str) -> int:
        return self.names.index(name)

    def rel_cycles(self, name: str) -> float:
        """Cycle cost of ``name`` relative to the all-accurate reference."""
        return self.cycles_per_token[name] / self.cycles_per_token[self.reference]


def _leaf_ids(tree) -> set:
    return {
        id(l)
        for l in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, PreparedWeight))
        if isinstance(l, PreparedWeight)
    }


def place_bank(bank: MultiPointBank, mesh, specs=None) -> MultiPointBank:
    """Place every bank tree on ``mesh`` with the logical-axis shardings.

    Leaves are placed ONCE per tensor identity and re-aliased into every
    point's tree — pinned/agreeing layers stay single-copy on device, the
    same zero-copy guarantee ``build_bank``'s shared memo gives on the host.
    Mutates ``bank.trees`` in place (controllers and speculative decoders
    hold references to the bank), returns the bank. Idempotent: re-placing an
    already-placed bank is a no-op device_put.
    """
    from repro.sharding.partition import prepared_shardings, require_auto_axes

    require_auto_axes(mesh)
    if specs is None:
        raise ValueError("place_bank needs the model's param specs "
                         "(model.specs()) to derive shardings")
    is_pw = lambda x: isinstance(x, PreparedWeight)
    placed: Dict[int, Any] = {}
    for name in bank.names:
        tree = bank.trees[name]
        sh = prepared_shardings(tree, specs, mesh)

        def put(leaf, sharding):
            key = id(leaf)
            if key not in placed:
                placed[key] = jax.device_put(leaf, sharding)
            return placed[key]

        bank.trees[name] = jax.tree.map(put, tree, sh, is_leaf=is_pw)
    return bank


def build_bank(
    params,
    mode: str,
    points: Optional[Sequence[ExecutionPoint]] = None,
    *,
    specs=None,
    reference: Optional[str] = None,
    mesh=None,
    calibration: Optional[Dict] = None,
) -> MultiPointBank:
    """Materialize the multi-point weight bank (one prepare pass, shared memo).

    Points are re-ordered cheapest -> most expensive by estimated MAC cycles,
    so the controller's demote/promote directions are well-defined. The
    ``reference`` point (default: ``"accurate"`` when present, else the most
    expensive point) anchors relative-cycle and savings reporting.

    ``mesh`` places every prepared tree with the logical-axis shardings
    (:func:`place_bank`) — sharded serving hands the jitted decode step
    device-resident tensor-parallel trees, still zero weight-side work per
    switch.

    ``calibration`` (a ``repro.sim.calibrate`` export) refines the per-point
    cycle estimates, so the ModeController's budget and the PE-array
    simulator optimize the same cost; ``bank.cycle_model`` records which
    model produced the estimates.
    """
    if mode == "exact":
        raise ValueError(
            "adaptive banks need a depth-configurable backend "
            "(carmen | int8 | kernel); 'exact' has no precision knob"
        )
    points = tuple(points if points is not None else default_points())
    if len(points) < 2:
        raise ValueError("a multi-point bank needs at least two execution points")
    if len({p.name for p in points}) != len(points):
        raise ValueError("execution point names must be unique")

    cycles = {
        p.name: estimate_point_cycles(params, p.policy, specs=specs,
                                      calibration=calibration)
        for p in points
    }
    points = tuple(sorted(points, key=lambda p: cycles[p.name]))
    if reference is None:
        reference = "accurate" if "accurate" in cycles else points[-1].name
    if reference not in cycles:
        raise ValueError(f"reference point {reference!r} not in {sorted(cycles)}")

    memo: Dict = {}
    trees = {
        p.name: prepare_params(params, p.policy, mode, specs=specs, memo=memo)
        for p in points
    }

    id_sets = [_leaf_ids(t) for t in trees.values()]
    all_ids = set().union(*id_sets)
    shared = {i for i in all_ids if sum(i in s for s in id_sets) >= 2}
    bank = MultiPointBank(
        mode=mode,
        points=points,
        trees=trees,
        cycles_per_token=cycles,
        reference=reference,
        shared_leaves=len(shared),
        unique_leaves=len(all_ids),
        cycle_model=calibration_id(calibration),
    )
    if mesh is not None:
        place_bank(bank, mesh, specs)
    return bank
