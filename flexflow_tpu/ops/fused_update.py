"""Fused one-dispatch optimizer update — the ``_k:fused`` kernel choice.

The reference update path is a *triad*: under WUS the gradient
reduce-scatter's epilogue, the per-leaf update kernels (read p/g/m/v,
write p/m/v), and the compute-param all-gather's prologue lower as
separate dispatch regions, each re-reading the parameter shard it needs
(three param round trips + three launches — the dispatch-bound tail the
ROADMAP names as the BERT proxy's remaining gap). The searched
``_k:fused`` twin collapses a chosen op's update into ONE region:

* **Pallas path** (TPU, or CPU under ``FLEXFLOW_TPU_PALLAS=interpret``):
  a single elementwise kernel reads p/g/m/v once from HBM and writes
  p'/m'/v' once — one launch, the minimal (2 + 2·state-copies) HBM
  round trips the native ``update_triad_time`` prices. On a mesh of
  several devices it runs per shard under ``shard_map`` over the leaf's
  own (WUS) sharding.
* **XLA fallback** (Pallas unavailable or shape not lane-aligned):
  ``lax.optimization_barrier`` fences the leaf's inputs so XLA forms
  one fused loop over the update instead of interleaving it with
  neighboring regions.

Both paths evaluate EXACTLY the reference optimizers' expression,
operand order included, so the fused update is **bit-compatible** with
the triad (asserted by tests/test_kernel_search.py) — the choice moves
dispatches, never values. Unknown optimizer classes fall back to the
whole-tree reference ``update`` (no fused ops), so a custom optimizer
degrades safely rather than silently drifting.
"""

from __future__ import annotations

import functools
from typing import Dict, Set, Tuple

import jax
import jax.numpy as jnp


# Row block of the Pallas update kernel's grid ([rows, 128] view of the
# flattened leaf). 512 rows x 128 lanes x 4 B x 7 resident arrays stays
# well inside one core's VMEM.
_BLK_ROWS = 512


def _adam_math(p, g, m, v, alpha_t, *, beta1, beta2, eps, wd):
    """The reference AdamOptimizer.update step — EXACT expression/order
    (flexflow_tpu/optimizers.py); any edit must change both."""
    sdt = m.dtype
    g = g.astype(p.dtype) + wd * p
    m_new = beta1 * m.astype(p.dtype) + (1 - beta1) * g
    v_new = beta2 * v.astype(p.dtype) + (1 - beta2) * g * g
    p_new = p - alpha_t * m_new / (jnp.sqrt(v_new) + eps)
    return p_new, m_new.astype(sdt), v_new.astype(sdt)


def _adam_kernel(alpha_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref,
                 vo_ref, *, beta1, beta2, eps, wd):
    p = p_ref[...]
    pn, mn, vn = _adam_math(p, g_ref[...], m_ref[...], v_ref[...],
                            alpha_ref[0, 0], beta1=beta1, beta2=beta2,
                            eps=eps, wd=wd)
    po_ref[...] = pn
    mo_ref[...] = mn.astype(mo_ref.dtype)
    vo_ref[...] = vn.astype(vo_ref.dtype)


def _pallas_rows(size: int):
    """(rows, block_rows) of the [rows, 128] kernel view, or None when
    the leaf is not lane-aligned / row-blockable — XLA fallback then."""
    if size <= 0 or size % 128:
        return None
    rows = size // 128
    if rows <= _BLK_ROWS:
        return rows, rows
    if rows % _BLK_ROWS == 0:
        return rows, _BLK_ROWS
    return None


def _adam_leaf_local(alpha2, p, g, m, v, *, mode, beta1, beta2, eps, wd):
    """The fused update of one device's block of a leaf."""
    geom = _pallas_rows(int(p.size)) if mode != "off" else None
    if geom is None:
        # XLA-fused fallback: the barrier fences the four inputs into
        # one region boundary; identity on values
        p, g, m, v = jax.lax.optimization_barrier((p, g, m, v))
        return _adam_math(p, g, m, v, alpha2[0, 0], beta1=beta1,
                          beta2=beta2, eps=eps, wd=wd)
    from jax.experimental import pallas as pl

    from flexflow_tpu.ops.pallas_kernels import KERNEL_NAME_PREFIX

    rows, blk = geom
    shp = p.shape
    view = lambda x: x.reshape(rows, 128)
    kern = functools.partial(_adam_kernel, beta1=beta1, beta2=beta2,
                             eps=eps, wd=wd)
    row_spec = pl.BlockSpec((blk, 128), lambda i: (i, 0))
    pn, mn, vn = pl.pallas_call(
        kern,
        name=KERNEL_NAME_PREFIX + "fused_adam",
        out_shape=(jax.ShapeDtypeStruct((rows, 128), p.dtype),
                   jax.ShapeDtypeStruct((rows, 128), m.dtype),
                   jax.ShapeDtypeStruct((rows, 128), v.dtype)),
        grid=(rows // blk,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  row_spec, row_spec, row_spec, row_spec],
        out_specs=(row_spec, row_spec, row_spec),
        interpret=mode == "interpret",
    )(alpha2, view(p), view(g), view(m), view(v))
    return pn.reshape(shp), mn.reshape(shp), vn.reshape(shp)


def fused_adam_leaf(p, g, m, v, alpha_t, *, beta1, beta2, eps, wd,
                    mesh=None, spec=None):
    """One leaf's fused Adam update -> (p', m', v').

    ``mesh`` / ``spec``: the sharding the leaf's master copy and moments
    live on (the WUS spec where WUS shards it). On a mesh of more than
    one device the kernel runs per shard under ``shard_map``: to the
    SPMD partitioner a bare ``pallas_call`` is a custom call it cannot
    split ("Mosaic kernels cannot be automatically partitioned"). The
    update is elementwise, so each device updates the block it owns and
    no collective is needed; a leaf the spec leaves replicated is
    updated redundantly on every device, as the triad does."""
    from flexflow_tpu.ops.pallas_kernels import pallas_mode

    mode = pallas_mode()
    local = functools.partial(_adam_leaf_local, mode=mode, beta1=beta1,
                              beta2=beta2, eps=eps, wd=wd)
    alpha2 = jnp.asarray(alpha_t, jnp.float32).reshape(1, 1)
    if mode == "off" or mesh is None or mesh.size == 1:
        return local(alpha2, p, g, m, v)
    from jax.sharding import PartitionSpec as P

    spec = spec if spec is not None else P()
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(), spec, spec, spec, spec),
                         out_specs=(spec, spec, spec),
                         check_vma=False)(alpha2, p, g, m, v)


def _sgd_math(opt, p, g, v):
    """The reference SGDOptimizer.update step (momentum form)."""
    g = g + opt.weight_decay * p
    v_new = opt.momentum * v + g
    upd = g + opt.momentum * v_new if opt.nesterov else v_new
    return p - opt.lr * upd, v_new


def fused_optimizer_update(opt, grads, state, params, fused_ops: Set[str],
                           mesh=None, spec_of=None) -> Tuple[Dict, Dict]:
    """``optimizer.update`` with the ``fused_ops`` subtrees routed
    through the fused one-dispatch region; value-identical to the
    reference update (same math, same order) by construction.
    ``spec_of(op name, param name, shape)`` names each leaf's master
    sharding on ``mesh`` (see ``fused_adam_leaf``)."""
    from flexflow_tpu.optimizers import AdamOptimizer, SGDOptimizer

    rest_names = [k for k in params if k not in fused_ops]
    rest_p = {k: params[k] for k in rest_names}
    rest_g = {k: grads[k] for k in rest_names}

    if isinstance(opt, AdamOptimizer):
        t = state["t"] + 1
        bc = jnp.sqrt(1.0 - opt.beta2 ** t.astype(jnp.float32)) / (
            1.0 - opt.beta1 ** t.astype(jnp.float32)
        )
        alpha_t = opt.alpha * bc
        new_p: Dict = {}
        new_m: Dict = {}
        new_v: Dict = {}
        if rest_names:
            # complement subtree through the REFERENCE update (no math
            # duplication to drift); its t advance equals ours
            rp, rs = opt.update(rest_g, dict(
                m={k: state["m"][k] for k in rest_names},
                v={k: state["v"][k] for k in rest_names},
                t=state["t"]), rest_p)
            new_p.update(rp)
            new_m.update(rs["m"])
            new_v.update(rs["v"])
        for op_name in fused_ops:
            if op_name not in params:
                continue
            sp: Dict = {}
            sm: Dict = {}
            sv: Dict = {}
            for pn, p in params[op_name].items():
                sp[pn], sm[pn], sv[pn] = fused_adam_leaf(
                    p, grads[op_name][pn], state["m"][op_name][pn],
                    state["v"][op_name][pn], alpha_t, beta1=opt.beta1,
                    beta2=opt.beta2, eps=opt.epsilon,
                    wd=opt.weight_decay, mesh=mesh,
                    spec=(spec_of(op_name, pn, tuple(p.shape))
                          if spec_of is not None else None))
            new_p[op_name] = sp
            new_m[op_name] = sm
            new_v[op_name] = sv
        return new_p, {"m": new_m, "v": new_v, "t": t}

    if isinstance(opt, SGDOptimizer):
        if opt.momentum == 0.0:
            new_p = {}
            if rest_names:
                rp, _ = opt.update(rest_g, state, rest_p)
                new_p.update(rp)
            for op_name in fused_ops:
                if op_name not in params:
                    continue
                sub = {}
                for pn, p in params[op_name].items():
                    g = grads[op_name][pn]
                    p, g = jax.lax.optimization_barrier((p, g))
                    sub[pn] = p - opt.lr * (g + opt.weight_decay * p)
                new_p[op_name] = sub
            return new_p, state
        new_p = {}
        new_v = {}
        if rest_names:
            rp, rs = opt.update(rest_g, dict(
                v={k: state["v"][k] for k in rest_names}), rest_p)
            new_p.update(rp)
            new_v.update(rs["v"])
        for op_name in fused_ops:
            if op_name not in params:
                continue
            sp = {}
            sv = {}
            for pn, p in params[op_name].items():
                g = grads[op_name][pn]
                v = state["v"][op_name][pn]
                p, g, v = jax.lax.optimization_barrier((p, g, v))
                sp[pn], sv[pn] = _sgd_math(opt, p, g, v)
            new_p[op_name] = sp
            new_v[op_name] = sv
        return new_p, {"v": new_v}

    # unknown optimizer class: the fused region has no reference math to
    # mirror — degrade to the whole-tree reference update
    return opt.update(grads, state, params)
