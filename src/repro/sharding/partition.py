"""Logical-axis sharding rules -> NamedSharding, divisibility-aware.

The paper's N-PE vector engine scales by adding lanes; on the TPU cluster the
lane axis is the ``model`` mesh axis (TP/EP) and throughput scaling comes from
``(pod, data)`` (DP/FSDP). Rules map logical parameter axes to mesh axes; a
rule only applies when the dimension divides the mesh-axis extent — otherwise
the dimension falls back to replication (recorded by ``sharding_report`` so
the roofline pass can see what was dropped; e.g. 40-head attention on a
16-way model axis replicates heads and relies on FSDP for weight memory).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> ordered candidate mesh-axis groups (first that divides wins)
PARAM_RULES: Dict[str, Tuple[Tuple[str, ...], ...]] = {
    "vocab": (("model",),),
    "embed": (("pod", "data"), ("data",), ("pod",)),  # FSDP shard of the d_model dim
    "heads": (("model",),),
    "kv_heads": (("model",),),
    "head_dim": (),
    "mlp": (("model",),),
    # model-axis EP. (2D EP over data x model — fully-local expert weights —
    # was tried and REFUTED: GSPMD replicates the token batch to feed the
    # expert shards, 14x more collective bytes; see EXPERIMENTS.md §Perf B.)
    "experts": (("model",),),
    "layers": (),
    "q_lora": (),
    "kv_lora": (),
    "ssm_inner": (("model",),),
    "ssm_heads": (("model",),),
    "ssm_state": (),
    "conv": (),
    "groups": (),
    "frames": (),
    None: (),
}

# activation/batch rules used by input and cache shardings
BATCH_AXES = ("pod", "data")


def _resolve(axis_name: Optional[str], dim: int, mesh: Mesh, report: list) -> Optional[Tuple[str, ...]]:
    for group in PARAM_RULES.get(axis_name, ()):  # ordered preference
        group = tuple(a for a in group if a in mesh.axis_names)
        if not group:
            continue
        extent = int(np.prod([mesh.shape[a] for a in group]))
        if dim % extent == 0:
            return group
        report.append((axis_name, dim, group, extent))
    return None


def param_pspec(spec, mesh: Mesh, report: Optional[list] = None) -> P:
    """PartitionSpec for one ParamSpec. Only touches ``mesh.axis_names`` /
    ``mesh.shape``, so duck-typed stand-in meshes work (property tests)."""
    report = report if report is not None else []
    entries, used = [], set()
    for dim, ax in zip(spec.shape, spec.axes):
        group = _resolve(ax, dim, mesh, report)
        if group and not (set(group) & used):
            entries.append(group if len(group) > 1 else group[0])
            used.update(group)
        else:
            entries.append(None)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_shardings(specs, mesh: Mesh):
    """Pytree of NamedShardings matching a model's param specs."""
    from repro.models import params as P_  # local: avoids circular import

    report: list = []
    out = P_.tree_map_specs(lambda s: NamedSharding(mesh, param_pspec(s, mesh, report)), specs)
    return out, report


def sharding_report(specs, mesh: Mesh):
    """(logical_axis, dim, group, extent) tuples for every replication fallback."""
    _, report = param_shardings(specs, mesh)
    return report


def prepared_shardings(params, specs, mesh: Mesh, report: Optional[list] = None):
    """Shardings for a serving param tree (raw or ``prepare_params`` output).

    The tree's structure matches ``specs`` except that engine-routed matmul
    leaves may be :class:`PreparedWeight` containers (payload inherits the raw
    leaf's rule-derived sharding; the per-channel scale inherits the entries of
    the axes it shares with the payload — see ``PreparedWeight.placement``)
    and tied-embedding trees carry a synthesized transposed ``lm_head`` (its
    pspec comes from the embedding spec with shape/axes reversed). The result
    is usable both for ``jax.device_put`` placement and as jit in/out
    shardings.
    """
    from repro.core.backends import PreparedWeight  # local: avoids cycle
    from repro.models.params import ParamSpec

    report = report if report is not None else []
    param_sh, rep = param_shardings(specs, mesh)
    report.extend(rep)
    if (
        isinstance(params, dict)
        and "lm_head" in params
        and isinstance(param_sh, dict)
        and "lm_head" not in param_sh
    ):
        embed = specs["embed"]
        head_spec = ParamSpec(embed.shape[::-1], embed.axes[::-1])
        param_sh = dict(
            param_sh,
            lm_head=NamedSharding(mesh, param_pspec(head_spec, mesh, report)),
        )

    def one(sh, leaf):
        if isinstance(leaf, PreparedWeight):
            return leaf.placement(sh)
        return sh

    return jax.tree.map(one, param_sh, params)


def slot_pspec(shape, mesh: Mesh) -> P:
    """Per-slot serving-state leaves (and KV slot axes): dim 0 over the batch
    axes when the slot count divides their extent; replicated otherwise."""
    axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    extent = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if shape and axes and extent > 1 and shape[0] % extent == 0:
        return P(axes)
    return P()


def slot_shardings(state_tree, mesh: Mesh):
    """NamedShardings for the server's device-resident per-slot state."""
    return jax.tree.map(
        lambda l: NamedSharding(mesh, slot_pspec(l.shape, mesh)), state_tree
    )


@dataclasses.dataclass
class ServingShardings:
    """Every placement the serving hot path needs, derived from one mesh.

    ``params`` matches the (possibly prepared) serving tree, ``cache`` the
    multi-slot KV cache, ``state`` the per-slot decode state; ``report``
    collects every rule the divisibility fallback dropped (params + the
    synthesized lm_head).
    """

    mesh: Mesh
    params: object
    cache: object
    state: object
    report: list = dataclasses.field(default_factory=list)

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def slots(self, shape) -> NamedSharding:
        """Sharding for a ``(slots, ...)`` emit buffer."""
        return NamedSharding(self.mesh, slot_pspec(tuple(shape), self.mesh))

    def snapshot(self) -> Dict:
        """The JSON placement summary a serving-trace header embeds
        (:func:`serving_sharding_report`)."""
        return serving_sharding_report(self)


def serving_shardings(mesh: Mesh, *, params, cache, state, specs, cfg,
                      max_len: Optional[int] = None) -> ServingShardings:
    """Build the full serving placement bundle for ``BatchedServer(mesh=...)``."""
    require_auto_axes(mesh)
    report: list = []
    params_sh = prepared_shardings(params, specs, mesh, report=report)
    cache_sh = cache_shardings(cache, mesh, cfg, row_axis_len=max_len)
    state_sh = slot_shardings(state, mesh)
    return ServingShardings(mesh, params_sh, cache_sh, state_sh, report)


def serving_sharding_report(sh: ServingShardings) -> Dict:
    """JSON-able placement summary for a serving mesh.

    ``dropped`` records every rule the divisibility fallback rejected (the
    dims that silently replicate); ``params`` counts sharded vs replicated
    weight leaves; ``cache``/``state`` list the pspec of each leaf.
    """

    def _spec_entries(tree):
        out: Dict[str, str] = {}
        flat, _ = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding)
        )
        for path, leaf in flat:
            key = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
            out[key] = str(leaf.spec)
        return out

    param_leaves = [
        l
        for l in jax.tree.leaves(
            sh.params, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding)
        )
        if isinstance(l, jax.sharding.Sharding)
    ]
    n_sharded = sum(1 for l in param_leaves if tuple(l.spec))
    return {
        "mesh": {a: int(sh.mesh.shape[a]) for a in sh.mesh.axis_names},
        "devices": int(sh.mesh.devices.size),
        "dropped": [
            {"axis": a, "dim": int(d), "mesh_axes": list(g), "extent": int(e)}
            for a, d, g, e in sh.report
        ],
        "params": {
            "sharded": n_sharded,
            "replicated": len(param_leaves) - n_sharded,
        },
        "cache": _spec_entries(sh.cache),
        "state": _spec_entries(sh.state),
    }


def batch_pspec(mesh: Mesh, *, extra: Sequence[Optional[str]] = ()) -> P:
    axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    return P(axes, *extra)


def batch_sharding(mesh: Mesh, *, extra: Sequence[Optional[str]] = ()) -> NamedSharding:
    return NamedSharding(mesh, batch_pspec(mesh, extra=extra))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def require_auto_axes(mesh: Mesh) -> None:
    """Raise unless every axis of ``mesh`` is ``Auto``: placement here leans
    on GSPMD propagation and ``with_sharding_constraint``, which ``Explicit``
    axes (``jax.make_mesh``'s default) refuse."""
    if any(t != jax.sharding.AxisType.Auto for t in mesh.axis_types):
        raise ValueError(
            f"mesh axes {dict(zip(mesh.axis_names, mesh.axis_types))} must "
            "all be Auto: build the mesh with repro.launch.mesh.make_mesh"
        )


def mesh_axis_sizes() -> Dict[str, int]:
    """Axis name -> extent of the ambient mesh (``jax.set_mesh``), or {}."""
    am = jax.sharding.get_abstract_mesh()
    return {} if am.empty else dict(am.shape)


def current_mesh_axes() -> Tuple[str, ...]:
    """Axis names of the ambient mesh (``jax.set_mesh``), or ()."""
    return tuple(mesh_axis_sizes())


def constrain(x, *entries):
    """with_sharding_constraint against the ambient mesh; no-op without one.

    Entries: "batch" (-> all of pod/data present in the mesh), a mesh axis
    name, or None. Dims that don't divide their axis extent are left
    unconstrained. Model code calls this to pin activation layouts (GSPMD
    propagation otherwise drops the batch sharding after the vocab-sharded
    embedding gather — observed: a TP-only program doing 32x redundant work;
    see EXPERIMENTS.md §Dry-run).
    """
    sizes = mesh_axis_sizes()
    axes = tuple(sizes)
    if not axes:
        return x
    spec = []
    used: set = set()
    for i, e in enumerate(entries):
        if e == "batch":
            group = tuple(a for a in BATCH_AXES if a in axes and a not in used)
            extent = int(np.prod([sizes.get(a, 1) for a in group])) if group else 1
            if group and x.shape[i] % extent == 0:
                spec.append(group)
                used.update(group)
            else:
                spec.append(None)
        elif isinstance(e, tuple):
            group = tuple(a for a in e if a in axes and a not in used)
            extent = int(np.prod([sizes.get(a, 1) for a in group])) if group else 1
            if group and x.shape[i] % extent == 0:
                spec.append(group)
                used.update(group)
            else:
                spec.append(None)
        elif e in axes and e not in used and x.shape[i] % sizes.get(e, 1) == 0:
            spec.append(e)
            used.add(e)
        else:
            spec.append(None)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def use_2d_ep(num_experts: int) -> bool:
    """True when experts divide the full (data x model) extent — weights are
    then fully local (matches the 'experts' param rule preference)."""
    sizes = mesh_axis_sizes()
    extent = sizes.get("data", 1) * sizes.get("model", 1)
    return extent > 1 and num_experts % extent == 0


def cache_shardings(cache_tree, mesh: Mesh, cfg, *, row_axis_len: Optional[int] = None):
    """KV caches: batch over (pod, data); kv_heads/model-dim over model when divisible.

    Cache layouts (see models/*): attn (L, B, S, KV, hd) | mla latent
    (L, B, S, R) | ssm conv (L, B, W, C) / state (L, B, H, N, P).

    ``row_axis_len`` (the serving path passes ``max_len``) marks the sequence
    row axis: trailing dims of that extent are excluded from model-sharding
    and the EARLIEST remaining divisible dim wins — that is the heads/latent
    axis, the one the weight rules already shard, so decode never reshards
    rows. Without it (dry-run compatibility) the largest trailing dim wins.
    """
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    batch_extent = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    model_extent = mesh.shape.get("model", 1)

    def one(leaf):
        shape = leaf.shape
        if len(shape) <= 1:  # stacked index scalars
            return NamedSharding(mesh, P())
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.integer):
            # (L, B) write-index vectors: these are the decode scatter's
            # indices — GSPMD wants scatter indices replicated (sharding
            # them trips the partitioner's index-broadcast lowering inside
            # the burst scan), and at L*B int32 they are free to replicate
            return NamedSharding(mesh, P())
        entries: list = [None] * len(shape)
        if shape[1] % max(batch_extent, 1) == 0:
            entries[1] = batch_axes  # B dim (dim 0 is layers)
        best = None
        for i in range(2, len(shape)):
            if row_axis_len is not None and i == 2 and shape[i] == row_axis_len:
                # the S row axis — dim 2 of the (L, B, S, ...) row-cache
                # layouts: decode writes here, never shard it. (Position AND
                # extent are checked so a trailing dim that happens to equal
                # max_len is not silently excluded from model-sharding.)
                continue
            if shape[i] % model_extent == 0 and shape[i] >= model_extent:
                if best is None:
                    best = i
                elif row_axis_len is None and shape[i] > shape[best]:
                    best = i
        if best is not None:
            entries[best] = "model"
        while entries and entries[-1] is None:
            entries.pop()
        return NamedSharding(mesh, P(*entries))

    return jax.tree.map(one, cache_tree)
