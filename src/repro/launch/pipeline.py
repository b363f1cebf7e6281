"""GPipe-style pipeline parallelism over shard_map + collective_permute.

Optional parallelism mode for very deep models (adds a "pipe" mesh axis).
Stages hold contiguous layer groups; microbatches stream through with
ppermute handoffs; bubbles = (S-1)/(S-1+M) as usual. Off by default on the
2-axis production mesh (the assigned models fit TP x DP comfortably); the
test exercises a 4-stage pipeline on fake devices via subprocess.

The implementation is deliberately minimal-but-real: it runs the SAME layer
body the LM uses, and the schedule is the classic fill-drain loop expressed
with lax.fori_loop + ppermute so it lowers to static HLO.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def pipeline_apply(layer_fn, stage_params, x_microbatches, mesh,
                   axis: str = "pipe"):
    """Run ``layer_fn(stage_params, x)`` across pipeline stages.

    stage_params: pytree stacked over stages on axis ``pipe``;
    x_microbatches: (M, mb, ...) microbatched inputs, resident on stage 0.
    Returns outputs (M, mb, ...) resident on the last stage (replicated out).
    """
    n_stages = dict(mesh.shape)[axis]
    m = x_microbatches.shape[0]
    total_ticks = m + n_stages - 1

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis), P(None)), out_specs=P(None),
             check_vma=False)
    def run(params_stage, xs):
        stage = jax.lax.axis_index(axis)
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_stage)
        buf = jnp.zeros_like(xs[0])          # current activation
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            mb_idx = jnp.clip(t, 0, m - 1)
            incoming = jnp.where((stage == 0) & (t < m), 1.0, 0.0)
            buf = buf * (1 - incoming) + xs[mb_idx] * incoming
            # all stages compute
            buf = layer_fn(params_local, buf)
            # last stage emits microbatch (t - n_stages + 1)
            out_idx = jnp.clip(t - n_stages + 1, 0, m - 1)
            emit = (stage == n_stages - 1) & (t >= n_stages - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(emit, buf, outs[out_idx]), out_idx, 0)
            # hand off downstream (ring; stage S-1 -> 0 wraps harmlessly)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(buf, axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, total_ticks, tick, (buf, outs))
        # replicate result (last stage holds it)
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    return run(stage_params, x_microbatches)
