"""Logical-axis sharding rules (DP / TP / EP / SP over the production mesh).

Params and activations are annotated with *logical* axis names; the rule
table maps them to mesh axes.  This indirection is what makes checkpoints
mesh-independent (elastic scaling) and lets the §Perf loop swap sharding
strategies by editing ONE table instead of every jit signature.

Divisibility fallback: if a tensor dim is not divisible by the mapped mesh
axes' total size, the dim silently degrades to replicated — e.g. 8 KV heads
on a 16-way model axis, or global_batch=1 (long_500k) on the data axis.
This mirrors MaxText's behaviour and keeps every (arch x shape) cell
lowerable with one rule table.

GA population sharding (:func:`population_rules` / :func:`population_mesh`):
the co-design engine's unit of parallelism is not the batch but the NSGA-II
*population* — ``core.trainer`` evaluates a whole generation as one
``vmap(train)`` program whose leading axis is one row per chromosome.  The
``"population"`` logical axis maps that row axis onto a flat 1-D ``data``
mesh over every visible device; ``population_rules`` simultaneously unbinds
``"batch"``/``"embed"`` (the LM-serving FSDP defaults) so nothing *inside*
a chromosome's training loop is partitioned.  The result is an
embarrassingly parallel layout: each device trains its population slice
end-to-end with zero collectives in the whole generation — the only
cross-device event is the host gathering the (P,) accuracy vector.  On one
device the divisibility fallback degrades the spec to fully replicated, so
CPU CI and a TPU pod run the identical code path.  Population padding to
bucket sizes (multiples of the device count) lives in the trainer, not
here: the rules stay shape-agnostic and the fallback guarantees a
non-dividing population still lowers (replicated) rather than erroring.
"""

from __future__ import annotations

import contextlib
import threading
import warnings

import numpy as np
import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axes (tuple = composed axes, None = replicated)
LOGICAL_RULES: dict[str, tuple[str, ...] | None] = {
    "batch": ("pod", "data"),     # DP; "pod" silently dropped on 1-pod meshes
    "seq": None,                  # sequence kept local (SP variant: ("model",))
    "embed": ("data",),           # FSDP: weight d_model dims sharded over DP
    "embed_out": None,
    "heads": ("model",),          # Megatron TP: attention heads
    "kv_heads": ("model",),       # falls back to replicated when H_kv < TP
    "head_dim": ("model",),       # cache fallback when H_kv < TP (hd divides)
    "ffn": ("model",),            # Megatron TP: MLP hidden
    "vocab": ("model",),          # embedding + logits sharded over vocab
    "experts": ("model",),        # MoE expert parallelism
    "expert_embed": ("data",),    # expert-weight d_model dim (FSDP default)
    "expert_ffn": None,           # intra-expert hidden stays local under EP
    "ssm_heads": ("model",),      # RWKV/Mamba channel TP
    "ssm_state": None,
    "conv_kernel": None,
    "population": ("data",),      # GA population sharding (beyond-paper)
    "island": ("island",),        # island-model sub-population groups
    "stage": ("stage",),          # pipeline parallelism (opt-in meshes)
    "seq_tp": ("model",),         # context-parallel fallback (heads % TP != 0)
}


def make_mesh(
    shape: tuple[int, ...], axis_names: tuple[str, ...], devices=None
) -> Mesh:
    """``jax.make_mesh`` with the one axis-type choice of this repo: Auto.

    Every mesh in ``src/`` is built here.  JAX's default is Explicit axes,
    under which a sharding is part of each array's type and an op whose
    output sharding JAX cannot infer raises instead of letting XLA pick
    one.  The QAT row program gathers a minibatch ``X_tr[idx]`` with
    population-sharded indices from a replicated table, which is such an
    op.  Auto axes leave that choice to XLA's sharding propagation.
    """
    return jax.make_mesh(
        shape, axis_names, axis_types=(AxisType.Auto,) * len(axis_names),
        devices=devices,
    )


def population_rules() -> dict[str, tuple[str, ...] | None]:
    """Rule overrides for GA population evaluation (beyond-paper).

    One NSGA-II generation is a single SPMD program: the population axis of
    every chromosome tensor maps onto the flat ``data`` device axis and each
    device trains its slice of the population; everything below the
    population axis (per-chromosome masks, hyper-params, model state inside
    the vmapped trainer) stays local.  Used by
    ``core.trainer.make_population_evaluator`` together with
    :func:`population_mesh`; ``logical_spec``'s divisibility fallback makes
    the same code degrade to fully-replicated on a single device.
    """
    return {"population": ("data",), "batch": None, "embed": None}


def population_mesh(
    n_devices: int | None = None, devices: list | None = None
) -> Mesh:
    """Flat 1-D ``data`` mesh over the available devices (population axis).

    Deliberately one-dimensional: a GA generation has no tensor/model
    parallelism to express (printed MLPs are tiny), so every device is a
    pure population worker.  The island-model layer factors this mesh into
    per-island device groups — see :func:`island_mesh` /
    :func:`island_rules`; multi-host ``(pod, data)`` extensions remain a
    ROADMAP follow-on and compose the same way (add a ``"pod"`` entry to
    the rules and the same trainer code lowers onto it).

    ``n_devices`` restricts the mesh to the first n visible devices;
    ``devices`` pins an explicit list (the elastic-recovery path hands the
    surviving subset here — ``jax.make_mesh`` requires the device list to
    match the shape product exactly, so a shrunken mesh must say which
    devices survive rather than letting JAX assume all of them).
    """
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return make_mesh((len(devices),), ("data",), devices=devices)


def island_rules() -> dict[str, tuple[str, ...] | None]:
    """Rule overrides for island-model GA evaluation (beyond-paper).

    Extends :func:`population_rules` with an ``"island"`` logical axis: a
    stacked cross-island chromosome tensor is (K, P, ...) — island groups
    map onto the ``island`` mesh axis, each island's population rows onto
    the ``data`` axis *within* its device group, and everything inside one
    chromosome's training loop stays local (same zero-collective layout as
    the single-population engine, replicated K ways).
    """
    return {**population_rules(), "island": ("island",)}


def island_mesh(
    num_islands: int, n_devices: int | None = None, devices: list | None = None
) -> Mesh:
    """2-D ``(island, data)`` mesh: device groups per island.

    The visible devices are factored into ``num_islands`` equal groups —
    ``(num_islands, n // num_islands)`` — so each island's population
    shards over its own group.  A device count that does not divide uses
    the LARGEST subset that factors — e.g. 8 devices, 3 islands gives a
    ``(3, 2)`` mesh over the first 6 devices — with a warning naming the
    dropped devices (silently collapsing to ``(1, n)`` would run the
    islands with no island-axis parallelism at all, which on a stacked
    driver means K-1 groups' worth of lost throughput, not a degraded
    layout).  Only with fewer devices than islands (the single-CPU CI
    case) does the mesh degrade to ``(1, n)``: the ``island`` axis is
    size 1, the K-island stack falls back to replicated via
    ``logical_spec``'s divisibility rule, and the stacked program still
    lowers — identical semantics, device-group parallelism or not.
    """
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    n = len(devices)
    if num_islands < 1:
        raise ValueError(f"num_islands must be >= 1, got {num_islands}")
    group = n // num_islands
    if group < 1:
        return make_mesh((1, n), ("island", "data"), devices=devices)
    used = group * num_islands
    if used != n:
        dropped = ", ".join(str(d) for d in devices[used:])
        warnings.warn(
            f"island_mesh: {n} devices do not factor into {num_islands} "
            f"islands; using the first {used} as a ({num_islands}, {group}) "
            f"mesh and dropping [{dropped}]",
            stacklevel=2,
        )
    return make_mesh(
        (num_islands, group), ("island", "data"), devices=devices[:used]
    )


def _axes_in_mesh(mesh: Mesh, axes: tuple[str, ...] | None) -> tuple[str, ...]:
    if axes is None:
        return ()
    return tuple(a for a in axes if a in mesh.axis_names)


def logical_spec(
    shape: tuple[int, ...],
    logical_axes: tuple[str | None, ...],
    mesh: Mesh,
    rules: dict | None = None,
) -> P:
    """Build a PartitionSpec for ``shape`` with divisibility fallback."""
    rules = {**LOGICAL_RULES, **(rules or {})}
    assert len(shape) == len(logical_axes), (shape, logical_axes)
    spec: list = []
    used: set[str] = set()
    for dim, name in zip(shape, logical_axes):
        entry: tuple[str, ...] | None = rules.get(name) if name else None
        axes = _axes_in_mesh(mesh, entry)
        axes = tuple(a for a in axes if a not in used)
        total = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if axes and dim % total == 0:
            spec.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        else:
            # fall back: try the largest prefix of axes that divides
            placed = False
            for k in range(len(axes) - 1, 0, -1):
                sub = axes[:k]
                t = int(np.prod([mesh.shape[a] for a in sub]))
                if dim % t == 0:
                    spec.append(sub if len(sub) > 1 else sub[0])
                    used.update(sub)
                    placed = True
                    break
            if not placed:
                spec.append(None)
    return P(*spec)


def logical_sharding(
    shape: tuple[int, ...],
    logical_axes: tuple[str | None, ...],
    mesh: Mesh,
    rules: dict | None = None,
) -> NamedSharding:
    return NamedSharding(mesh, logical_spec(shape, logical_axes, mesh, rules))


def shard_tree(tree_shapes, tree_logical, mesh: Mesh, rules: dict | None = None):
    """Map matching pytrees of shapes and logical-axis tuples to shardings."""
    return jax.tree.map(
        lambda shp, ax: logical_sharding(tuple(shp), tuple(ax), mesh, rules),
        tree_shapes,
        tree_logical,
        is_leaf=lambda x: isinstance(x, (tuple, list))
        and all(isinstance(e, (int, str, type(None))) for e in x),
    )


def constrain(x, logical_axes: tuple[str | None, ...], mesh: Mesh, rules=None):
    """with_sharding_constraint by logical axes (used inside model code)."""
    return jax.lax.with_sharding_constraint(
        x, logical_sharding(x.shape, logical_axes, mesh, rules)
    )


# ---------------------------------------------------------------------------
# activation-constraint context: model code calls ``act_constrain`` which is
# a no-op outside a mesh context (CPU smoke tests) and a
# with_sharding_constraint during sharded lowering.  Without these hints
# XLA's propagation happily reshards activations feature-wise to follow the
# FSDP param sharding and replicates the batch — 16x redundant compute
# (measured; see EXPERIMENTS.md §Perf iteration 0).
# ---------------------------------------------------------------------------

_TLS = threading.local()


@contextlib.contextmanager
def activation_mesh(mesh: Mesh, rules: dict | None = None):
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = (mesh, rules)
    try:
        yield
    finally:
        _TLS.ctx = prev


def act_constrain(x, logical_axes: tuple[str | None, ...]):
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    return constrain(x, logical_axes, mesh, rules)


def moe_stationary() -> bool:
    """True when the active rules shard expert_ffn (weights-stationary MoE):
    expert weights never move; the (much smaller) token batch is gathered
    into the expert compute and the down-proj partial-sums all-reduce.
    Activated by rules={'expert_ffn': ('data',), 'expert_embed': None}."""
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None:
        return False
    rules = {**LOGICAL_RULES, **(ctx[1] or {})}
    return rules.get("expert_ffn") is not None


def _needs_seq_tp(n_heads: int) -> bool:
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None:
        return False
    tp = dict(ctx[0].shape).get("model", 1)
    return n_heads % tp != 0


def lm_act_axes(n_heads: int) -> tuple[str | None, ...]:
    """(B, S, d) activation axes.  Archs whose head count divides TP keep
    the sequence local (Megatron TP); the rest run context-parallel: every
    activation stays sharded (batch x seq) across the whole layer and only
    K/V are gathered for attention — tokens/device = global/(DP*TP)."""
    return ("batch", "seq_tp", None) if _needs_seq_tp(n_heads) else ("batch", None, None)


def attn_q_axes(n_heads: int) -> tuple[str | None, ...]:
    """(B, S, H, d) q-activation axes: head-TP when H divides the model
    axis, else context-parallel over the query sequence.  Without this,
    archs whose head count doesn't divide TP (arctic: 56 heads on 16-way
    model) leave q replicated and XLA partitions the scores contraction
    over head_dim — an all-reduce of every (Sq, Sk) score block
    (EXPERIMENTS.md §Perf iteration A2)."""
    ctx = getattr(_TLS, "ctx", None)
    if ctx is not None:
        mesh = ctx[0]
        tp = dict(mesh.shape).get("model", 1)
        if n_heads % tp != 0:
            return ("batch", "seq_tp", None, None)
    return ("batch", None, "heads", None)
