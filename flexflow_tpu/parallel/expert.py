"""Expert parallelism: MoE dispatch/combine over an 'expert' mesh axis.

The reference achieves expert parallelism by making each expert a separate
Linear op the search places on a different GPU
(examples/cpp/mixture_of_experts/moe.cc:65-83 rebalances that placement at
runtime). Under SPMD/jit that per-op placement doesn't exist; the TPU-native
design stacks expert weights on a leading E dim sharded over an 'expert'
mesh axis and exchanges tokens with explicit collectives inside shard_map:

  dispatch:  local partial-group einsum, then reduce-scatter over the
             expert axis (the all_to_all+sum that moves every token to its
             expert's shard) and psum over remaining batch shards.
  experts:   batched einsum over the *local* expert block [E/ep, C, D].
  combine:   all_gather expert outputs over the expert axis, then the local
             gate-weighted combine einsum.

Numerics are exactly the dense path's: the dispatch/combine tensors are
built from the replicated gate/assign (tiny [B,K] ints), so capacity
positions are global — no per-shard cumsum divergence.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _mesh_axes(mesh: Mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def expert_parallel_ffn(x, dispatch, combine, w_h, b_h, w_o, b_o,
                        mesh: Mesh, expert_axis: str = "expert",
                        data_axes: Sequence[str] = ("data",),
                        activation=jax.nn.relu):
    """Run the MoE FFN with experts sharded over ``expert_axis``.

    x:        [B, D]   tokens; B is sharded over data_axes AND the expert
                       axis jointly (the expert axis doubles as a batch
                       axis on the token side, so the reduce-scatter sums
                       true partials, GShard-style)
    dispatch: [B, K, E, C] one-hot routing (same sharding as x on B)
    combine:  [B, K, E, C] gate-weighted routing
    w_h/b_h:  [E, D, H] / [E, H]   stacked expert weights, E sharded over
    w_o/b_o:  [E, H, D] / [E, D]   the expert axis
    returns:  [B, D] combined expert outputs, B sharded like x.
    """
    axes = _mesh_axes(mesh)
    ep = axes.get(expert_axis, 1)
    e_total = w_h.shape[0]
    data_axes = tuple(a for a in data_axes if axes.get(a, 1) > 1)
    tok_shards = ep
    for a in data_axes:
        tok_shards *= axes[a]
    if (ep <= 1 or e_total % ep != 0 or x.shape[0] % tok_shards != 0):
        if ep > 1:
            import warnings

            warnings.warn(
                f"expert_parallel_ffn: cannot shard {e_total} experts / "
                f"{x.shape[0]} tokens over expert axis of {ep} (tokens must "
                f"divide {tok_shards}); falling back to the replicated dense "
                f"path", stacklevel=2)
        return dense_moe_ffn(x, dispatch, combine, w_h, b_h, w_o, b_o,
                             activation=activation)

    def local(x_l, disp_l, comb_l, w_h_l, b_h_l, w_o_l, b_o_l):
        # partial groups over local tokens, all experts: [E, C, D]
        part = jnp.einsum("bd,bkec->ecd", x_l.astype(jnp.float32),
                          disp_l.astype(jnp.float32))
        # move each expert's rows home: sum over expert-axis peers while
        # scattering the E dim (reduce-scatter == all_to_all + local sum)
        grouped = jax.lax.psum_scatter(part, expert_axis,
                                       scatter_dimension=0, tiled=True)
        for a in data_axes:  # finish the token sum over batch shards
            grouped = jax.lax.psum(grouped, a)
        # local expert block FFN: [E/ep, C, D] -> [E/ep, C, D]
        h = jnp.einsum("ecd,edh->ech", grouped, w_h_l.astype(jnp.float32))
        h = activation(h + b_h_l[:, None, :])
        o = jnp.einsum("ech,ehd->ecd", h, w_o_l.astype(jnp.float32))
        o = o + b_o_l[:, None, :]
        # bring every expert's output to every token shard
        full = jax.lax.all_gather(o, expert_axis, axis=0, tiled=True)
        y = jnp.einsum("bkec,ecd->bd", comb_l.astype(jnp.float32), full)
        return y.astype(x_l.dtype)

    tok_axes = (*data_axes, expert_axis)
    tok2 = P(tok_axes, None)
    tok4 = P(tok_axes, None, None, None)
    wspec3 = P(expert_axis, None, None)
    wspec2 = P(expert_axis, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(tok2, tok4, tok4, wspec3, wspec2, wspec3, wspec2),
        out_specs=tok2, check_vma=False,
    )(x, dispatch, combine, w_h, b_h, w_o, b_o)


def dense_moe_ffn(x, dispatch, combine, w_h, b_h, w_o, b_o,
                  activation=jax.nn.relu):
    """Single-device / replicated reference path (identical numerics)."""
    grouped = jnp.einsum("bd,bkec->ecd", x.astype(jnp.float32),
                         dispatch.astype(jnp.float32))
    h = jnp.einsum("ecd,edh->ech", grouped, w_h.astype(jnp.float32))
    h = activation(h + b_h[:, None, :])
    o = jnp.einsum("ech,ehd->ecd", h, w_o.astype(jnp.float32))
    o = o + b_o[:, None, :]
    y = jnp.einsum("bkec,ecd->bd", combine.astype(jnp.float32), o)
    return y.astype(x.dtype)
