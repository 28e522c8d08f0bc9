"""The searched choice, and the plan the executors run.

The search names its pick per op in the grammar
``base[_wus][_ovl][_k:impl][_r]`` (composed in native/ffs_strategy.hpp;
a base carries ``_ring``/``_sp``, ``_ep`` and ``head`` inside it). The
name is the serialized form: strategy files, checkpoint manifests and
the search trace store it. ``Choice`` is the only code in the Python
tree that knows the grammar, and ``plan_execution`` the only code that
turns a strategy's choices, the ``FFConfig`` switches and the
``FFS_NO_*`` variables into what an executor runs (``ExecPlan``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import re
from typing import Dict, FrozenSet, Mapping, Optional

from flexflow_tpu.ffconst import CompMode, OperatorType

_GRAMMAR = re.compile(
    r"(?P<base>.*?)(?P<wus>_wus)?(?P<ovl>_ovl)?(?:_k:(?P<kernel>.+?))?"
    r"(?P<remat>_r)?")
# mesh axes the batch (and so the gradient sync) is sharded over
DATA_AXES = ("slice", "data", "replica")


@dataclasses.dataclass(frozen=True)
class Choice:
    """One op's searched choice, by its parts."""

    base: str = ""
    wus: bool = False  # weight-update sharding of this op's parameters
    ovl: bool = False  # its gradient sync priced as hidden under backward
    kernel: Optional[str] = None  # flash | einsum | fused | conv_bn_fused
    remat: bool = False  # forward under jax.checkpoint

    @staticmethod
    @functools.lru_cache(maxsize=1024)
    def parse(name: Optional[str]) -> "Choice":
        m = _GRAMMAR.fullmatch(name or "")
        return Choice(m["base"], bool(m["wus"]), bool(m["ovl"]),
                      m["kernel"], bool(m["remat"]))

    def __str__(self) -> str:
        return (self.base + "_wus" * self.wus + "_ovl" * self.ovl
                + (f"_k:{self.kernel}" if self.kernel else "")
                + "_r" * self.remat)

    @property
    def ring(self) -> bool:
        """Attention over the mesh's 'seq' axis (ring attention)."""
        return "_ring" in self.base

    @property
    def head(self) -> bool:
        """Attention heads sharded over the 'model' axis."""
        return "head" in self.base

    @property
    def expert(self) -> bool:
        """Experts sharded over the 'expert' axis."""
        return "_ep" in self.base


@dataclasses.dataclass(frozen=True)
class ExecPlan:
    """What an executor runs of the searched choice dimensions. The
    default engages none of them, which is what a forward-only serving
    bucket runs."""

    wus: bool = False
    # ops WUS shards; None = every eligible op (forced or heuristic)
    wus_ops: Optional[FrozenSet[str]] = None
    overlap: bool = False  # bucketed async gradient sync (needs wus)
    bucket_bytes: int = 4 << 20
    # {op name: impl}; None = no kernel dimension, ops keep their
    # availability-based defaults
    kernel_choices: Optional[Mapping[str, str]] = None
    remat_ops: Optional[FrozenSet[str]] = None  # flat meshes, per op
    body_remat: bool = False  # pipe meshes, per block

    def executed_choice(self, node, searched: Choice) -> Choice:
        """``searched`` with its suffixes set to what runs: the choice
        the simulator must price to replay the executed step."""
        name = node.op.name
        wus = bool(self.wus and node.op.params_elems()
                   and (self.wus_ops is None or name in self.wus_ops))
        kept = (self.kernel_choices or {}).get(name) == searched.kernel
        return dataclasses.replace(
            searched, wus=wus, ovl=wus and self.overlap,
            kernel=searched.kernel if kept else None,
            remat=name in (self.remat_ops or ()))


def _flash_was_enumerable(op, comp_mode) -> bool:
    """Whether a ``_k:flash`` twin could exist for this op: the op's own
    route names nothing that blocks the kernels whatever the platform
    (the native flash gate, ffs_strategy.hpp kernel_gate, mirrors the
    same rule). Where the gate excluded flash (dropout, tile
    divisibility, cross-attention) the search never priced it, and the
    availability-based pick must survive: eval and serve forwards may
    run flash legally."""
    try:
        return op.route({}, comp_mode == CompMode.TRAINING).blocked is None
    except Exception:
        return False


def plan_execution(nodes, strategy, mesh_axes: Dict[str, int], search_info,
                   cfg, comp_mode) -> ExecPlan:
    """The plan for ``nodes`` under ``strategy`` on a mesh of
    ``mesh_axes``. Every switch that gates a choice dimension is read
    here: ``weight_update_sharding``, ``overlap_bucket_mb``,
    ``kernel_search`` / ``FFS_NO_KERNEL_SEARCH``, ``remat_search`` /
    ``FFS_NO_REMAT``. 'auto' follows the search where one ran
    (``search_info`` is its dict) and a heuristic otherwise.

    Also settles ``kernel_impl`` on the attention ops of ``nodes``: with
    the kernel dimension on, the searched impl (an op whose choice kept
    the default is pinned to "einsum" where flash was enumerable, so the
    availability-based pick cannot run a kernel the search priced and
    rejected); with it off, cleared, as the off switch promises."""
    searched = isinstance(search_info, dict)
    info = search_info if searched else {}
    by_guid = {g: st.parsed for g, st in (strategy or {}).items()}

    def choice_of(node) -> Choice:
        return by_guid.get(node.op.guid, Choice())

    pipe = mesh_axes.get("pipe", 1) > 1
    data_deg = math.prod(mesh_axes.get(a, 1) for a in DATA_AXES)

    # weight-update sharding: a priced choice dimension where the search
    # ran; heuristic strategies engage it at data degree >= 4, where the
    # optimizer-state HBM win dominates. Training only.
    wus_mode = cfg.weight_update_sharding
    if wus_mode not in ("auto", "on", "off"):
        raise ValueError(f"weight_update_sharding expects auto|on|off, "
                         f"got {wus_mode!r}")
    searched_wus = searched and any(c.wus for c in by_guid.values())
    if comp_mode == CompMode.INFERENCE or wus_mode == "off":
        wus = False
    elif wus_mode == "on":
        wus = True
    else:
        wus = searched_wus if searched else data_deg >= 4
    wus = wus and data_deg > 1
    # under 'auto' the ops the search left on plain all-reduce keep it;
    # forced 'on' and heuristic strategies shard every eligible op
    wus_ops = None
    if wus and wus_mode == "auto" and searched_wus:
        wus_ops = frozenset(n.op.name for n in nodes if choice_of(n).wus)

    # comms-compute overlap: 'auto' follows the search ('_ovl' twins,
    # with the bucket size its sweep committed to) or, on heuristic
    # strategies, WUS at 4 MB; N forces N-MB buckets; '0'/'off' disables
    ovl_raw = str(cfg.overlap_bucket_mb).lower()
    bucket_mb = 4.0
    if ovl_raw in ("0", "off"):
        overlap = False
    elif ovl_raw == "auto":
        overlap = any(c.ovl for c in by_guid.values()) if searched else wus
        bucket_mb = float((info.get("overlap") or {}).get("bucket_mb")
                          or 4.0)
    else:
        bucket_mb = float(int(ovl_raw))
        overlap = bucket_mb > 0

    # kernel implementations: on when a search ran (or the strategy
    # carries '_k:' choices), unless switched off. Pipe meshes never
    # enumerated the dimension, so nothing was priced against the
    # availability-based pick there.
    kernel_on = ((searched or any(c.kernel for c in by_guid.values()))
                 and str(cfg.kernel_search).lower() != "off"
                 and not os.environ.get("FFS_NO_KERNEL_SEARCH")
                 and not pipe)
    kernel_choices = None
    if kernel_on:
        kernel_choices = {}
        for n in nodes:
            c = choice_of(n)
            if c.kernel is not None:
                kernel_choices[n.op.name] = c.kernel
            elif n.op.op_type == OperatorType.MULTIHEAD_ATTENTION:
                kernel_choices[n.op.name] = "ring" if c.ring else "einsum"
    for n in nodes:
        if kernel_on and hasattr(n.op, "seq_parallel"):
            impl = kernel_choices.get(n.op.name)
            if impl == "flash" or (impl == "einsum" and
                                   _flash_was_enumerable(n.op, comp_mode)):
                n.op.kernel_impl = impl
            n.op._kernel_fallback = None  # fresh compile, fresh record
        elif not kernel_on:
            # also what apply_strategy pinned from an imported '_k:'
            # strategy, and the stale fallback record FFL209 reads
            for attr in ("kernel_impl", "_kernel_fallback"):
                if getattr(n.op, attr, None) is not None:
                    setattr(n.op, attr, None)

    # rematerialization: per-op '_r' twins on flat meshes, the searched
    # pipeline's block-level 'remat' bit on pipe meshes
    remat_on = (str(cfg.remat_search).lower() != "off"
                and not os.environ.get("FFS_NO_REMAT"))
    remat_ops = None
    if remat_on and not pipe:
        remat_ops = frozenset(
            n.op.name for n in nodes if choice_of(n).remat) or None
    pinfo = info.get("pipeline") or {}
    body_remat = bool(remat_on and pipe and pinfo.get("blocks") is not None
                      and pinfo.get("remat"))

    return ExecPlan(
        wus=wus, wus_ops=wus_ops, overlap=bool(overlap and wus),
        # MB (1e6), the native bucket sweep's wire-byte unit
        # (ffs_strategy.hpp kOvlBucketMB)
        bucket_bytes=max(1, int(bucket_mb * 1e6)),
        kernel_choices=kernel_choices, remat_ops=remat_ops,
        body_remat=body_remat)
