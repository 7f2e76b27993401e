"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

Opt-in feature for depth-dominated models (the assigned production mesh is
DP x TP, which fits every assigned arch at bf16; PP becomes necessary when
per-device HBM shrinks or layers grow — the rule table makes the swap a
config change).  Implementation: ``shard_map`` over ``stage``; each stage
holds its layer slice; microbatches flow stage-to-stage via
``lax.ppermute`` on a ``n_micro + n_stages - 1`` tick schedule (GPipe fill
+ drain).  The tick loop is a ``lax.scan`` so the HLO stays compact and
XLA can overlap the permute with the next tick's compute (send/recv and
MXU work target different units).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(
    stage_fn,
    stage_params,
    x: jnp.ndarray,
    *,
    mesh: Mesh,
    n_micro: int,
    axis: str = "stage",
):
    """Run ``x`` through ``n_stages`` pipeline stages.

    Args:
      stage_fn: ``(params_slice, activations) -> activations`` for ONE stage.
      stage_params: pytree whose leaves have a leading ``n_stages`` axis.
      x: (batch, ...) global input; batch must divide by ``n_micro``.
      mesh: mesh containing ``axis`` of size n_stages.
      n_micro: number of microbatches.
    Returns: (batch, ...) output of the final stage (replicated).
    """
    n_stages = mesh.shape[axis]
    batch = x.shape[0]
    assert batch % n_micro == 0, (batch, n_micro)
    mb = batch // n_micro
    xs = x.reshape((n_micro, mb) + x.shape[1:])

    def per_stage(params, xs_local):
        params = jax.tree.map(lambda p: p[0], params)  # drop stage axis
        sid = jax.lax.axis_index(axis)
        is_first = sid == 0
        is_last = sid == n_stages - 1
        ticks = n_micro + n_stages - 1

        state = jnp.zeros_like(xs_local[0])
        outputs = jnp.zeros_like(xs_local)

        def tick(carry, t):
            state, outputs = carry
            mb_idx = t - sid
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            inp = jnp.where(is_first, xs_local[jnp.clip(t, 0, n_micro - 1)], state)
            out = stage_fn(params, inp)
            out = jnp.where(active, out, jnp.zeros_like(out))
            write_idx = jnp.clip(mb_idx, 0, n_micro - 1)
            outputs = jnp.where(
                is_last & active,
                outputs.at[write_idx].set(out),
                outputs,
            )
            nxt = jax.lax.ppermute(
                out, axis, [(i, i + 1) for i in range(n_stages - 1)]
            )
            return (nxt, outputs), None

        (_, outputs), _ = jax.lax.scan(tick, (state, outputs), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast via psum
        outputs = jnp.where(is_last, outputs, jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, axis)

    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn(stage_params, xs)
    return out.reshape((batch,) + out.shape[2:])
