"""Population-parallel QAT inner loop for the ADC-aware GA.

The paper evaluates chromosomes by running a full quantization-aware
training of the bespoke MLP per chromosome (serially, on an EPYC).  Here a
whole NSGA-II population is evaluated as ONE jitted+vmapped JAX program:

* heterogeneous *batch sizes* are realised by drawing a fixed-size
  ``max_batch`` sample every step and weighting the loss with a
  ``i < batch_size`` mask (identical semantics, uniform shapes);
* heterogeneous *epoch budgets* are realised by scanning a fixed
  ``max_steps`` and freezing parameter updates once a chromosome's own
  step budget is exhausted (``jnp.where`` on the update);
* *weight/activation precisions* and *learning rate* enter the quantizers
  and optimiser as traced scalars.

This is a beyond-paper systems contribution: the GA generation cost drops
from ``P × train`` to one SPMD program whose population axis is sharded
across every available device via ``parallel.sharding.population_rules``
(single-device falls back to a trivial 1-way mesh — same code path).

Population batches are padded up to a small set of bucket sizes (multiples
of the device count) so the memoized NSGA-II engine — which submits a
*varying* number of unseen genomes per generation — re-uses a handful of
compiled programs instead of recompiling per population size.

The programs are shared across evaluators.  The data split and the
trainer's base key ``PRNGKey(cfg.seed)`` are arguments of the row program,
not constants of it, so one jitted program serves every evaluator of the
same table shapes, MLP, ``EvalConfig`` (its seed aside), mesh and island
count; a module-level cache of bounded size hands it out.  Each evaluator
places its split on its mesh once, replicated, at its first call, and
passes it with the rows on every call.  A search that builds fresh
evaluators therefore traces and lowers nothing a previous search (or a
warm-up) already did: JAX traces a shared program once per bucket and per
matmul precision, which its own trace cache keys on.

:func:`make_island_evaluator` is the island-model variant: the K islands'
per-generation unseen batches are padded to one common bucket, stacked
into ``(K, B, …)`` tensors and evaluated as ONE ``vmap(vmap(train_one))``
program whose island axis maps onto the device groups of
``parallel.sharding.island_mesh`` — K islands train concurrently instead
of leaving K-1 device groups idle per island step.  Both evaluators vmap
the same ``_make_train_one`` row program, so a chromosome's result is
bit-identical whichever path evaluates it.

Async dispatch contract (the evaluator half of the NSGA-II begin/commit
phase split — see ``core.nsga2``'s module docstring for the GA half):
``evaluate(...)`` pads, shards and *launches* its jitted program, then
returns the resulting ``jax.Array`` without forcing it — JAX dispatches
asynchronously on every backend, so the caller decides when to pay the
synchronisation.  The synchronous engine converts immediately;
``evaluate.dispatch(...)`` instead returns a zero-arg ``resolve()`` that
performs the ``jax.block_until_ready`` + host transfer, which is what
lets the dispatched island schedule (``core.nsga2.IslandNSGA2._evaluate_wave``)
plan the next island's pool while this batch trains on device.  Nothing else differs between the two entry points: same
padding, same sharding, same compiled program, same values.
"""

from __future__ import annotations

import collections
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import chromosome, qat, spans
from repro.parallel import sharding as shd

__all__ = ["EvalConfig", "make_population_evaluator", "make_island_evaluator",
           "clear_programs"]


@dataclasses.dataclass(frozen=True)
class EvalConfig:
    max_batch: int = 128
    max_steps: int = 600          # scan length ceiling for every chromosome
    step_scale: float = 1.0       # global shrink factor for CI/smoke runs
    momentum: float = 0.9
    seed: int = 0
    pad_granule: int = 4          # population bucket size (>= device count)
    # route the pruned-ADC quantizer + first-layer matmul through the fused
    # Pallas kernel (kernels.fused_qat) — same values/STE gradient as the
    # pure-JAX pair, no HBM round-trip of the dequantized input tile
    use_fused_kernel: bool = False
    # generalized-genome gene groups (core.chromosome.AXES).  Beyond the
    # default "adc", each enabled axis appends one stacked array to every
    # evaluator row: "act" -> (n_hidden,) int32 activation selectors,
    # "wprec" -> (n_layers,) float32 per-layer weight widths (0.0=ternary).
    # The default traces the literal pre-axes program — bit-for-bit.
    genome_axes: tuple[str, ...] = ("adc",)


def _split_args(X_tr, y_tr, X_te, y_te, seed: int) -> tuple:
    """The split a row program takes after its rows: the training and test
    tables, typed as the program reads them, and ``PRNGKey(seed)``, the
    trainer's base key that each row folds its own seed into."""
    return (jnp.asarray(X_tr, jnp.float32), jnp.asarray(y_tr, jnp.int32),
            jnp.asarray(X_te, jnp.float32), jnp.asarray(y_te, jnp.int32),
            jax.random.PRNGKey(seed))


def _make_train_one(mlp_cfg: qat.MLPConfig, cfg: EvalConfig):
    """The per-chromosome QAT training program shared by both evaluators.

    Returns ``train_one(mask, wb, ab, bs, ep, lr, seed, *extra, split) ->
    test_acc``, ``split`` being ``(X_tr, y_tr, X_te, y_te, base_key)`` of
    :func:`_split_args` — a pure function of the chromosome row and the
    split (the training seed arrives as an input, derived upstream from the
    genome bytes, and is folded into ``base_key``), which is what makes its
    result independent of which batch, bucket, or island stack the row is
    evaluated in: the population and island evaluators vmap the SAME row
    program over the rows, the split unmapped, so their per-row outputs
    agree bit-for-bit.

    ``extra`` carries the generalized-genome rows for the axes enabled in
    ``cfg.genome_axes``, in canonical axis order: the "act" selector vector,
    then the "wprec" per-layer width vector.  With the default
    ``("adc",)`` no extras exist and the traced program is exactly the
    pre-axes one.
    """
    axes = chromosome.normalize_axes(cfg.genome_axes)
    has_act = "act" in axes
    has_wprec = "wprec" in axes
    n_extra = int(has_act) + int(has_wprec)

    def train_one(mask, wb, ab, bs, ep, lr, seed, *rest):
        *extra, (X_tr, y_tr, X_te, y_te, base_key) = rest
        if len(extra) != n_extra:
            raise TypeError(
                f"genome axes {axes} expect {n_extra} extra row arrays, "
                f"got {len(extra)}"
            )
        it = iter(extra)
        act_sel = next(it) if has_act else None
        layer_wb = next(it) if has_wprec else None
        n_train = X_tr.shape[0]
        key = jax.random.fold_in(base_key, seed)
        params = qat.init_mlp(key, mlp_cfg)
        velocity = jax.tree.map(jnp.zeros_like, params)

        steps_per_epoch = jnp.ceil(n_train / bs.astype(jnp.float32))
        budget = jnp.minimum(
            jnp.maximum(ep.astype(jnp.float32) * steps_per_epoch * cfg.step_scale, 1.0),
            float(cfg.max_steps),
        )

        def loss_fn(p, xb, yb, w):
            logits = qat.mlp_forward(
                p, xb, mlp_cfg, mask, wb, ab, use_fused=cfg.use_fused_kernel,
                act_sel=act_sel, layer_weight_bits=layer_wb,
            )
            with spans.scope("loss"):
                logp = jax.nn.log_softmax(logits, axis=-1)
                ce = -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
                return jnp.sum(w * ce) / jnp.maximum(jnp.sum(w), 1.0)

        def step(carry, t):
            p, v = carry
            k = jax.random.fold_in(key, t)
            idx = jax.random.randint(k, (cfg.max_batch,), 0, n_train)
            with spans.scope("gather"):
                xb, yb = X_tr[idx], y_tr[idx]
            w = (jnp.arange(cfg.max_batch) < bs).astype(jnp.float32)
            grads = jax.grad(loss_fn)(p, xb, yb, w)
            frac = jnp.minimum(t.astype(jnp.float32) / budget, 1.0)
            lr_t = lr * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
            active = (t.astype(jnp.float32) < budget).astype(jnp.float32)
            with spans.scope("sgd"):
                v = jax.tree.map(lambda vi, g: cfg.momentum * vi - lr_t * g, v, grads)
                p = jax.tree.map(lambda pi, vi: pi + active * vi, p, v)
            return (p, v), None

        (params, _), _ = jax.lax.scan(step, (params, velocity), jnp.arange(cfg.max_steps))
        with spans.scope("test"):
            logits = qat.mlp_forward(
                params, X_te, mlp_cfg, mask, wb, ab, use_fused=cfg.use_fused_kernel,
                act_sel=act_sel, layer_weight_bits=layer_wb,
            )
            return qat.accuracy(logits, y_te)

    return train_one


# The evaluators' jitted programs, one per row-program factory, table
# shapes and dtypes, MLP, EvalConfig (its seed aside, an argument), mesh
# and island count; the least recently used goes past _PROGRAMS_MAX.  The
# factory is part of the key so that a replaced ``_make_train_one`` builds
# programs of its own.
_PROGRAMS_MAX = 32
_programs: collections.OrderedDict = collections.OrderedDict()
_programs_lock = threading.Lock()


def _build_program(mlp_cfg: qat.MLPConfig, cfg: EvalConfig, stacked: bool):
    """``jit(vmap(train_one))``, or ``vmap(vmap(...))`` for ``(K, B)``
    island stacks: the rows mapped, the split (the last argument) not."""
    train_one = _make_train_one(mlp_cfg, cfg)

    def in_axes(n_args):
        return (0,) * (n_args - 1) + (None,)

    if stacked:
        def _evaluate_stacked(*args):
            spans.count("trainer.program_builds", 1)  # the body runs once per trace
            axes = in_axes(len(args))
            return jax.vmap(jax.vmap(train_one, in_axes=axes), in_axes=axes)(*args)

        return jax.jit(_evaluate_stacked)

    def _evaluate_padded(*args):
        spans.count("trainer.program_builds", 1)  # the body runs once per trace
        return jax.vmap(train_one, in_axes=in_axes(len(args)))(*args)

    return jax.jit(_evaluate_padded)


def _shared_program(split, mlp_cfg, cfg: EvalConfig, mesh, num_islands: int | None = None):
    """The one program of every evaluator with this key, built on a miss;
    ``num_islands`` None for the population program."""
    key = (_make_train_one, tuple((a.shape, a.dtype) for a in split), mlp_cfg,
           dataclasses.replace(cfg, seed=0), mesh, num_islands)
    with _programs_lock:
        program = _programs.get(key)
        if program is not None:
            _programs.move_to_end(key)
            spans.count("trainer.program_reuses", 1)
            return program
        program = _programs[key] = _build_program(mlp_cfg, cfg, num_islands is not None)
        while len(_programs) > _PROGRAMS_MAX:
            _programs.popitem(last=False)
        return program


def clear_programs() -> None:
    """Forget every shared program: the next evaluator builds and traces
    its own.  For tests that change code a program is traced from."""
    with _programs_lock:
        _programs.clear()


class _BoundProgram:
    """The shared program of an evaluator's key with the evaluator's split
    bound in.  Called with rows placed on ``mesh``, it runs the program on
    them and the split, which it places on the mesh, replicated, at the
    first call; ``lower(*rows)`` lowers it for rows placed as ``rows`` are
    (arrays or ``jax.ShapeDtypeStruct``\\ s), the split replicated over
    their devices."""

    def __init__(self, split, mlp_cfg, cfg: EvalConfig, mesh, num_islands: int | None = None):
        self.program = _shared_program(split, mlp_cfg, cfg, mesh, num_islands)
        self.split, self.mesh = split, mesh
        self._placed = None

    def __call__(self, *rows):
        if self._placed is None:
            # at the first call, not at build: an evaluator on a mesh of
            # devices this process cannot address (a topology described to
            # compile for) still lowers its program
            replicated = NamedSharding(self.mesh, PartitionSpec())
            self._placed = tuple(jax.device_put(a, replicated) for a in self.split)
        return self.program(*rows, self._placed)

    def lower(self, *rows):
        sharding = getattr(rows[0], "sharding", None)
        if isinstance(sharding, NamedSharding):
            sharding = NamedSharding(sharding.mesh, PartitionSpec())
        return self.program.lower(*rows, tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding) for a in self.split))


def _useful_row_steps(bs, ep, n_train: int, cfg: EvalConfig) -> int:
    """Row-steps ``train_one`` trains for rows with batch sizes ``bs`` and
    epochs ``ep``: the steps ``t < budget`` (its budget, in float32 as it
    computes it) times the samples a step weights."""
    bs = np.asarray(bs, np.float32)
    ep = np.asarray(ep, np.float32)
    budget = np.minimum(
        np.maximum(ep * np.ceil(np.float32(n_train) / bs) * np.float32(cfg.step_scale), 1.0),
        np.float32(cfg.max_steps),
    )
    return int((np.ceil(budget).astype(np.int64) * np.minimum(bs, cfg.max_batch)).sum())


def _count_rows(batches, computed: int, n_train: int, cfg: EvalConfig) -> None:
    """Counters of one evaluator call: the real rows of its ``batches``
    (each a ``(bs, ep)`` pair of row arrays) and the ``computed`` rows its
    program scans, padding included."""
    n = sum(len(bs) for bs, _ in batches)
    spans.count("trainer.rows", n)
    spans.count("trainer.padded_rows", computed - n)
    if spans.is_recording():
        useful = sum(_useful_row_steps(bs, ep, n_train, cfg) for bs, ep in batches)
        spans.count("trainer.useful_row_steps", useful)
        spans.count("trainer.scanned_row_steps", computed * cfg.max_steps * cfg.max_batch)


def make_population_evaluator(
    X_tr: np.ndarray,
    y_tr: np.ndarray,
    X_te: np.ndarray,
    y_te: np.ndarray,
    mlp_cfg: qat.MLPConfig,
    cfg: EvalConfig = EvalConfig(),
    *,
    mesh: "jax.sharding.Mesh | None" = None,
    n_devices: int | None = None,
):
    """Returns ``evaluate(masks, wb, ab, bs, ep, lr, seeds, *extra) ->
    test_acc (P,)`` where ``extra`` holds one stacked array per enabled
    genome axis beyond "adc" (``cfg.genome_axes``, canonical order).

    All per-chromosome arrays are leading-axis stacked; the function is one
    jitted program: ``vmap(train_qat)`` over the population, with the
    population axis sharded over ``mesh`` (default: a flat ``data`` mesh
    over every visible device, ``parallel.sharding.population_mesh``).  On
    one device the sharding degrades to replicated and the program is the
    plain vmapped trainer.  Inputs are padded to the next population bucket
    (multiple of ``max(device_count, cfg.pad_granule)``) so varying
    population sizes share compiled programs; padded rows are sliced off
    the result.

    ``n_devices`` restricts the mesh to the first n visible devices — the
    elastic-recovery path rebuilds the evaluator on the surviving subset
    via the returned function's ``.rebuild(n_devices)`` hook, which
    re-lowers the same row program onto a fresh mesh with everything else
    unchanged.
    """
    n_train = np.shape(X_tr)[0]

    pop_mesh = shd.population_mesh(n_devices) if mesh is None else mesh
    rules = shd.population_rules()
    # bucket granule must be a multiple of the device count or the padded
    # population axis won't divide the mesh and logical_spec falls back to
    # full replication (every device training the whole population)
    n_dev = max(int(np.prod(list(pop_mesh.shape.values()))), 1)
    granule = -(-max(cfg.pad_granule, 1) // n_dev) * n_dev
    program = _BoundProgram(_split_args(X_tr, y_tr, X_te, y_te, cfg.seed), mlp_cfg, cfg,
                            pop_mesh)

    def _shard(arr):
        """Commit one population-stacked array to its sharded layout."""
        axes = ("population",) + (None,) * (arr.ndim - 1)
        return jax.device_put(
            arr, shd.logical_sharding(arr.shape, axes, pop_mesh, rules)
        )

    def evaluate(*args):
        P = np.shape(args[0])[0]
        bucket = -(-P // granule) * granule
        with spans.span("trainer.call", rows=P, bucket=bucket):
            _count_rows([(args[3], args[4])], bucket, n_train, cfg)
            with spans.span("trainer.input"):
                if bucket != P:
                    # edge-replicate: padded rows are valid chromosomes, just unused
                    args = [np.asarray(a) for a in args]
                    args = [np.concatenate([a, np.repeat(a[-1:], bucket - P, 0)])
                            for a in args]
                # device arrays, even ones a caller placed on its own mesh with
                # Explicit axes, move device-to-device onto this evaluator's
                # Auto mesh: no host round-trip, and one program for any caller
                placed = [_shard(a) for a in args]
            with spans.span("trainer.dispatch"):
                acc = program(*placed)
            return acc if bucket == P else acc[:P]

    def dispatch(*args):
        """Launch the batch's program now; block in the returned resolve.

        ``evaluate`` above never forces its result (both return paths are
        un-synchronised ``jax.Array``\\ s), so dispatching is just calling
        it — the device starts immediately — and deferring the host
        transfer into ``resolve()``, where ``jax.block_until_ready``
        makes the synchronisation point explicit.  The dispatched island
        schedule launches every island's batch this way and resolves at
        commit time (``core.nsga2.IslandNSGA2._evaluate_wave``).
        """
        acc = evaluate(*args)

        def resolve():
            return np.asarray(jax.block_until_ready(acc))

        return resolve

    def rebuild(n_devices: int | None = None):
        """Fresh evaluator, same data/config, re-meshed on ``n_devices``."""
        return make_population_evaluator(
            X_tr, y_tr, X_te, y_te, mlp_cfg, cfg, n_devices=n_devices
        )

    evaluate.dispatch = dispatch
    evaluate.mesh = pop_mesh
    evaluate.shard_fn = _shard        # introspection hooks: the placement
    evaluate.program = program        # and the program to lower
    evaluate.rebuild = rebuild
    return evaluate


def make_island_evaluator(
    X_tr: np.ndarray,
    y_tr: np.ndarray,
    X_te: np.ndarray,
    y_te: np.ndarray,
    mlp_cfg: qat.MLPConfig,
    cfg: EvalConfig = EvalConfig(),
    num_islands: int = 1,
    *,
    mesh: "jax.sharding.Mesh | None" = None,
    n_devices: int | None = None,
):
    """Cross-island SPMD evaluator for the stacked island-model driver.

    Returns ``evaluate(batches) -> [(B_i,) test_acc, ...]`` where
    ``batches`` is one ``(masks, wb, ab, bs, ep, lr, seeds, *extra)``
    tuple per island (``num_islands`` of them, zero-row batches allowed —
    empty islands this generation; ``extra`` per ``cfg.genome_axes`` as in
    the population evaluator).  The variable-size per-island batches are
    padded to ONE common bucket ``B`` (the largest island rounded up to a
    granule that divides each island's device group) and stacked into
    ``(K, B, …)`` tensors, so every generation is a single jitted
    ``vmap(vmap(train_one))`` program: the island axis lays island groups
    onto the ``island`` mesh axis of ``parallel.sharding.island_mesh`` and
    each island's rows onto the ``data`` axis *within* its group
    (``island_rules``) — zero collectives, same as the flat population
    layout, replicated K ways.  Padding rows are edge-replicated valid
    chromosomes (a filler row from the first non-empty island when an
    island ships nothing) and are sliced off the result.  On a host whose
    devices cannot host K groups the mesh degrades to ``(1, n)`` and the
    program still lowers — the island axis just stops being a parallel
    dimension.  Per-row results are bit-identical to
    :func:`make_population_evaluator` (same ``train_one`` row program).
    """
    if num_islands < 1:
        raise ValueError(f"num_islands must be >= 1, got {num_islands}")
    n_train = np.shape(X_tr)[0]

    isl_mesh = shd.island_mesh(num_islands, n_devices) if mesh is None else mesh
    rules = shd.island_rules()
    # the population axis shards within one island's device group, so the
    # bucket granule must divide the group size, not the whole device count
    group = max(int(dict(isl_mesh.shape).get("data", 1)), 1)
    granule = -(-max(cfg.pad_granule, 1) // group) * group
    program = _BoundProgram(_split_args(X_tr, y_tr, X_te, y_te, cfg.seed), mlp_cfg, cfg,
                            isl_mesh, num_islands)

    def _shard(arr):
        """Commit one (K, B, ...) island-stacked array to its layout."""
        axes = ("island", "population") + (None,) * (arr.ndim - 2)
        return jax.device_put(
            arr, shd.logical_sharding(arr.shape, axes, isl_mesh, rules)
        )

    def _launch(batches):
        """Pad, stack, shard and *launch* one wave; no synchronisation.

        Returns ``(accs, sizes)`` where ``accs`` is the un-forced ``(K,
        B)`` device array (``None`` when every batch is empty) — the
        shared padding/stacking half of both entry points below.
        """
        if len(batches) != num_islands:
            raise ValueError(
                f"expected {num_islands} island batches, got {len(batches)}"
            )
        sizes = [int(np.shape(b[0])[0]) for b in batches]
        if not any(sizes):
            return None, sizes
        bucket = -(-max(sizes) // granule) * granule
        with spans.span("trainer.call", rows=sum(sizes), bucket=bucket):
            _count_rows([(b[3], b[4]) for b in batches], num_islands * bucket, n_train, cfg)
            with spans.span("trainer.input"):
                stacked = _stack(batches, sizes, bucket)
            with spans.span("trainer.dispatch"):
                return program(*stacked), sizes

    def _stack(batches, sizes, bucket):
        """Pad every island's rows to ``bucket``, stack and place them."""
        # filler for zero-row islands: any valid chromosome, results unused
        filler = next(
            [np.asarray(a)[:1] for a in b]
            for b, n in zip(batches, sizes) if n
        )
        stacked = []
        for j in range(len(filler)):
            rows = []
            for b, n in zip(batches, sizes):
                if n == 0:
                    a = np.repeat(filler[j], bucket, axis=0)
                else:
                    a = np.asarray(b[j])
                    if n < bucket:
                        a = np.concatenate(
                            [a, np.repeat(a[-1:], bucket - n, axis=0)]
                        )
                rows.append(a)
            stacked.append(_shard(np.stack(rows)))
        return stacked

    def _split(accs, sizes):
        """Slice the padded (K, B) result back into per-island rows."""
        if accs is None:
            return [np.zeros((0,), np.float32) for _ in sizes]
        accs = np.asarray(accs)
        return [accs[i, :n] for i, n in enumerate(sizes)]

    def evaluate(batches):
        accs, sizes = _launch(batches)
        return _split(accs, sizes)

    def dispatch(batches):
        """Launch one stacked wave now; block in the returned resolve.

        The island-stacked twin of the population evaluator's
        ``.dispatch``: the jitted cross-island program is dispatched
        asynchronously by ``_launch`` and the host returns immediately;
        ``resolve()`` pays the ``jax.block_until_ready`` + transfer and
        slices the per-island rows.  The evaluation service's wave
        scheduler uses this to overlap result distribution and the next
        wave's planning with in-flight device work.
        """
        accs, sizes = _launch(batches)

        def resolve():
            if accs is not None:
                jax.block_until_ready(accs)
            return _split(accs, sizes)

        return resolve

    def rebuild(n_devices: int | None = None):
        """Fresh stacked evaluator re-meshed on the first ``n_devices``."""
        return make_island_evaluator(
            X_tr, y_tr, X_te, y_te, mlp_cfg, cfg, num_islands,
            n_devices=n_devices,
        )

    evaluate.mesh = isl_mesh          # introspection hooks for tests and
    evaluate.granule = granule        # benchmarks: the device-group layout
    evaluate.shard_fn = _shard        # the stacked tensors are placed with
    evaluate.program = program
    evaluate.dispatch = dispatch
    evaluate.rebuild = rebuild
    return evaluate
