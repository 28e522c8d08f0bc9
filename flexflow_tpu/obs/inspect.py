"""Compiled-step inspector: XLA cost/memory analysis + collective census.

The single owner of HLO-text parsing for collectives (the priced-vs-
emitted validator in ``flexflow_tpu/search/validate.py`` builds its
byte totals on this census). ``inspect_model_step`` lowers + compiles
the model's jitted train step on the live mesh and reports what the
program ACTUALLY is: FLOPs and HBM bytes accessed (XLA cost analysis),
per-device argument/temp/peak bytes (XLA memory analysis), and the
per-step collective census — all-reduce / all-gather / reduce-scatter /
all-to-all / collective-permute counts and payload byte volumes.
"""

from __future__ import annotations

import math
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

from flexflow_tpu.obs.step_scopes import COMPUTATION

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

# HLO collective opcodes the census recognizes (async -start/-done pairs
# count once via the -start op)
COLLECTIVE_KINDS = ("all-reduce", "reduce-scatter", "all-gather",
                    "all-to-all", "collective-permute")

# The payload threshold below which the search's simulator does not
# price a collective (scalar loss/metric reductions). The validator
# (search/validate.py) filters its census with this; the observability
# summary deliberately does NOT — it reports every collective the step
# runs — and records the threshold it used as ``collectives_min_bytes``.
PRICED_MIN_BYTES = float(1 << 12)

_COLLECTIVE_RE = re.compile(
    r"\b(" + "|".join(COLLECTIVE_KINDS) + r")(-start|-done)?(\.\d+)?\(")


def shape_bytes(shape_str: str) -> float:
    """Total bytes of an HLO shape string like ``f32[128,256]`` or a
    variadic tuple ``(f32[8,4], f32[8,4])``."""
    total = 0.0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_census(hlo_text: str, min_bytes: float = 0.0
                      ) -> Dict[str, Dict[str, float]]:
    """HLO opcode -> {count, bytes} over the optimized (SPMD) module.

    Byte volume is each op's OUTPUT shape — per-partition bytes in an
    SPMD module, i.e. what one device moves per step. The default
    ``min_bytes=0`` keeps every collective, scalar loss/metric
    reductions included; pass ``PRICED_MIN_BYTES`` to drop the ones the
    search's simulator deliberately does not price (as the validator
    does). HLO lines read ``%name = SHAPE opcode(operands)``; splitting
    at the first `` = `` keeps LHS names like ``%all-reduce.58`` from
    matching.
    """
    out: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        if " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        m = _COLLECTIVE_RE.search(rhs)
        if not m or m.group(2) == "-done":
            continue
        b = shape_bytes(rhs[:m.start()])
        if b < min_bytes:
            continue
        kind = m.group(1)
        e = out.setdefault(kind, dict(count=0, bytes=0.0))
        e["count"] += 1
        e["bytes"] += b
    return out


def pallas_kernel_count(hlo_text: str) -> int:
    """Pallas (Mosaic) kernels in an optimized TPU module: its custom-call
    instructions that target ``tpu_custom_call``. The bare name also turns
    up in op metadata, so the whole attribute is matched."""
    return hlo_text.count('custom_call_target="tpu_custom_call"')


def census_totals(census: Dict[str, Dict[str, float]]) -> Dict[str, float]:
    return dict(
        count=sum(e["count"] for e in census.values()),
        bytes=sum(e["bytes"] for e in census.values()),
    )


_RG_RE = re.compile(
    # explicit groups {{0,1},{2,3}} or the iota form [G,S]<=[dims]T(perm)
    r"replica_groups=(\{\{[\d, {}]*\}\}|\[[\d,]+\]<=\[[\d,]+\]"
    r"(?:T\([\d,]+\))?)")
_RG_IOTA_RE = re.compile(
    r"^\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?$")


def parse_replica_groups(attr: str):
    """Device-id groups of one collective's ``replica_groups`` HLO
    attribute. Handles the explicit form ``{{0,1},{2,3}}`` and the iota
    form ``[G,S]<=[dims]`` / ``[G,S]<=[dims]T(perm)`` (reshape
    iota(prod(dims)) to dims, transpose by perm, reshape to G x S).
    None when the attribute doesn't parse."""
    import numpy as np
    if attr.startswith("{{"):
        return [[int(x) for x in grp.split(",") if x.strip()]
                for grp in re.findall(r"\{([\d, ]*)\}", attr[1:-1])]
    m = _RG_IOTA_RE.match(attr)
    if not m:
        return None
    g, s = int(m.group(1)), int(m.group(2))
    dims = [int(x) for x in m.group(3).split(",")]
    arr = np.arange(int(np.prod(dims))).reshape(dims)
    if m.group(4):
        arr = arr.transpose([int(x) for x in m.group(4).split(",")])
    return arr.reshape(g, s).tolist()


def collective_census_by_fabric(hlo_text: str, chips_per_slice: int,
                                min_bytes: float = 0.0
                                ) -> Dict[str, Dict[str, float]]:
    """The census split by fabric tier: ``{"ici": {count, bytes},
    "dcn": {count, bytes}}`` over the optimized SPMD module.

    A collective rides DCN when any of its replica groups contains
    devices from more than one slice (device id // chips_per_slice, the
    slice-major order ``model.compile`` lays the ('slice', ...) mesh
    out in). A collective with no / unparseable replica_groups engages
    every participant — on a multi-slice mesh that spans, so it counts
    as DCN (conservative: the methodology BENCH_NOTES documents).

    Byte attribution is DECOMPOSED (ISSUE 20 r16): XLA lowers a
    spanning all-reduce hierarchically — intra-slice reduce-scatter,
    inter-slice exchange on the 1/d-sized shard each chip then holds
    (d = the group's largest single-slice membership), intra-slice
    all-gather — so only ``bytes/d`` of the payload crosses DCN; the
    remaining ``bytes*(1-1/d)`` moves on ICI and is charged there. A
    group with one chip per slice (d = 1) has no intra-slice stage and
    charges its full payload to DCN. Counts keep the old whole-fabric
    attribution: a spanning collective counts once, under "dcn"."""
    out = {"ici": dict(count=0, bytes=0.0), "dcn": dict(count=0, bytes=0.0)}
    cps = max(1, int(chips_per_slice))
    for line in hlo_text.splitlines():
        line = line.strip()
        if " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        m = _COLLECTIVE_RE.search(rhs)
        if not m or m.group(2) == "-done":
            continue
        b = shape_bytes(rhs[:m.start()])
        if b < min_bytes:
            continue
        rg = _RG_RE.search(rhs)
        groups = parse_replica_groups(rg.group(1)) if rg else None
        intra = 0  # largest single-slice membership over spanning groups
        if groups:
            spans = False
            for g in groups:
                if not g or len({d // cps for d in g}) <= 1:
                    continue
                spans = True
                per_slice: Dict[int, int] = {}
                for d in g:
                    per_slice[d // cps] = per_slice.get(d // cps, 0) + 1
                intra = max(intra, max(per_slice.values()))
        else:
            spans = True  # flat/implicit group: all participants
            intra = cps
        if spans:
            out["dcn"]["count"] += 1
            dcn_b = b / max(1, intra)
            out["dcn"]["bytes"] += dcn_b
            out["ici"]["bytes"] += b - dcn_b  # intra-slice stages
        else:
            out["ici"]["count"] += 1
            out["ici"]["bytes"] += b
    return out


_FUSION_RE = re.compile(r"=\s+\S+\s+fusion(\.\d+)?\(")
_CUSTOM_CALL_RE = re.compile(r"=\s+\S+\s+custom-call(\.\d+)?\(")


def fusion_census(hlo_text: str,
                  census: Optional[Dict[str, Dict[str, float]]] = None
                  ) -> Dict[str, int]:
    """Dispatch-count proxy over the optimized module: how many kernel
    launches the step is (fusion regions + custom calls + collectives).
    The coordinate the kernel-search dimension moves (ISSUE 15: a fused
    optimizer update collapses three regions into one), tracked by the
    bench's downward ``dispatch_count`` ratchet the way
    ``collective_bytes`` tracks the census. ``census``: a
    collective_census already computed for the same text (avoids a
    second full-module scan)."""
    fusions = len(_FUSION_RE.findall(hlo_text))
    custom = len(_CUSTOM_CALL_RE.findall(hlo_text))
    if census is None:
        census = collective_census(hlo_text)
    colls = sum(e["count"] for e in census.values())
    return dict(fusions=fusions, custom_calls=custom,
                collectives=int(colls),
                dispatches=fusions + custom + int(colls))


def inspect_compiled(compiled) -> Dict[str, Any]:
    """Cost + memory analysis + collective census of one jax ``Compiled``.

    Robust to backend gaps: any analysis a backend does not implement
    reports as None rather than raising (the CPU backend implements all
    three as of jax 0.4.x).
    """
    out: Dict[str, Any] = dict(flops=None, bytes_accessed=None,
                               transcendentals=None)
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            out["flops"] = float(ca.get("flops", 0.0)) or None
            out["bytes_accessed"] = (
                float(ca.get("bytes accessed", 0.0)) or None)
            t = ca.get("transcendentals")
            out["transcendentals"] = float(t) if t else None
    except Exception:
        pass
    mem: Optional[Dict[str, float]] = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            arg = float(getattr(ma, "argument_size_in_bytes", 0))
            tmp = float(getattr(ma, "temp_size_in_bytes", 0))
            mem = dict(
                argument_bytes=arg,
                output_bytes=float(getattr(ma, "output_size_in_bytes", 0)),
                temp_bytes=tmp,
                generated_code_bytes=float(
                    getattr(ma, "generated_code_size_in_bytes", 0)),
                # the per-device peak an HBM budget must cover: live
                # arguments (params/opt state/batch) + XLA temp — same
                # definition as search/validate.compiled_footprint_bytes
                peak_bytes=arg + tmp,
            )
    except Exception:
        pass
    out["memory"] = mem
    census: Dict[str, Dict[str, float]] = {}
    fusions: Optional[Dict[str, int]] = None
    try:
        text = compiled.as_text()
        census = collective_census(text)
        fusions = fusion_census(text, census=census)
    except Exception:
        pass
    out["collectives"] = census
    out["collectives_total"] = census_totals(census)
    out["collectives_min_bytes"] = 0.0
    out["fusions"] = fusions
    return out


def export_step_summary(ff, tracer) -> Dict[str, Any]:
    """Inspect the compiled train step and write the ``.summary.json``
    artifact next to the tracer's other files (the one emission path
    shared by ``FFModel._finalize_trace`` and ``bench.py``). Returns
    the summary dict."""
    import os

    from flexflow_tpu.obs.artifacts import write_artifact

    summary = inspect_model_step(ff)
    path = os.path.join(tracer.trace_dir, tracer.file_stem + ".summary.json")
    write_artifact(path, summary, host_id=tracer.host_id,
                   kind="step_summary",
                   header_extra=dict(run_name=tracer.run_name,
                                     run_seq=tracer.run_seq))
    return summary


_SCATTER = re.compile(r"= \(?\w+\[([\d,]*)\][^\n]*? scatter\("
                      r"(?:[^\n]*?op_name=\"([^\"]*)\")?")


def scatters_in(hlo_text: str, scope: str = "") -> List[Tuple[str, int]]:
    """(`op_name`, elements of the result) of every `scatter` instruction
    of an HLO text whose `op_name` holds `scope` (say "jit(moe_layer)").
    On the TPU v5e a scatter-add of rows runs row by row (ops/moe.py), so
    a layer that means to move rows by gathers checks its compiled step
    with this. The chip's compiler may cut an `op_name` down to the
    primitive's, or drop it (then ""); the size tells a table of tile ids
    from an activation."""
    return [(name, math.prod(int(n) for n in dims.split(",") if n))
            for dims, name in _SCATTER.findall(hlo_text) if scope in name]


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%?[\w.\-]+) = (.*?) ([\w\-]+)\(")


def arrays_between_fusions(hlo_text: str, dtype: str,
                           elements: int) -> List[str]:
    """Names of the instructions of an optimized HLO text whose result is
    a ``dtype`` (say "f32") array of ``elements`` elements and which are
    NOT inside a fusion's body: arrays the program writes to memory
    (parameters left out). A loss that means to keep the float32 copy of
    its [B, S, V] logits out of memory checks its compiled step with
    this (PR 40)."""
    array = re.compile(r"\b" + re.escape(dtype) + r"\[([\d,]+)\]")
    out, fused = [], False
    for line in hlo_text.splitlines():
        head = COMPUTATION.match(line)
        if head:
            fused = "fused_computation" in head.group(1)
            continue
        # name = result type(s) opcode(operands): a fusion of several
        # results has a tuple of types
        m = None if fused else _INSTRUCTION.match(line)
        if m and m.group(3) != "parameter" and any(
                math.prod(int(n) for n in dims.split(",")) == elements
                for dims in array.findall(m.group(2))):
            out.append(m.group(1))
    return out


def search_predictions(ff) -> Dict[str, Any]:
    """What the search returned for the strategy it chose, seconds a
    step and bytes a chip (``search_info``); None each where no search
    ran. The allocator's ``device_peak_bytes`` of a session's header
    and the device's busy time a step are what they are held against."""
    info = ff.search_info if isinstance(ff.search_info, dict) else {}
    return dict(search_predicted_step_s=info.get("predicted_time"),
                search_predicted_memory_bytes=info.get("predicted_memory"))


def model_context(ff) -> Dict[str, Any]:
    """Graph/mesh context the raw XLA numbers need to be interpreted —
    the ONE definition shared by the trace header (FFModel._make_tracer)
    and the step summary, so the two artifacts can never desync."""
    return dict(
        num_ops=len(ff.executor.nodes),
        mesh_axes=dict(zip(ff.mesh.axis_names, ff.mesh.devices.shape)),
        batch_size=(ff.input_tensors[0].shape[0]
                    if ff.input_tensors else None),
        # host seconds of FFModel.compile by phase, and of every
        # set_parameter call since (model.py records both, always)
        compile_phases=getattr(ff, "compile_phases", None),
        set_parameter_s=getattr(ff, "set_parameter_s", None),
        **search_predictions(ff),
        # what the trace of the ops' forwards and of the loss recorded on
        # the host (`GraphExecutor.traced_gauges`: zeros until a step or
        # a forward has been traced), each under its key's last dotted
        # part
        **{k.split(".")[-1].replace("/", "_"): v
           for k, v in ff.executor.traced_gauges().items()},
        # positions that carried a target in the last epoch of a weighted
        # loss (None before one, or under another loss)
        loss_target_positions=(getattr(ff, "op_counters", None) or {}).get(
            "loss/target_positions"),
        # the two unweighted cross-entropy sums of a model with a
        # multi-token-prediction module, its last epoch's
        **{"loss_" + part + "_nll": (getattr(ff, "op_counters", None)
                                     or {}).get(f"loss/{part}_nll")
           for part in (getattr(ff, "loss_parts", None) or ())},
    )


def inspect_model_step(ff) -> Dict[str, Any]:
    """Inspect the compiled TRAIN step of a compiled FFModel: lowers the
    jitted step on the live mesh with representative inputs and runs
    ``inspect_compiled`` on it (a fresh lower+compile — AOT inspection
    cannot reuse the executor's cached executable)."""
    from flexflow_tpu.search.validate import compiled_train_step

    compiled = compiled_train_step(ff)
    out = inspect_compiled(compiled)
    out.update(model_context(ff))
    # multi-slice fabric attribution: on a ('slice', ...) mesh, split the
    # census by fabric tier — the cross-slice (DCN) byte volume is the
    # coordinate bench.py records as dcn_bytes
    try:
        axis_names = tuple(getattr(ff.mesh, "axis_names", ()) or ())
        if "slice" in axis_names:
            axes = dict(zip(axis_names, ff.mesh.devices.shape))
            cps = int(ff.mesh.devices.size) // int(axes["slice"])
            out["collectives_by_fabric"] = collective_census_by_fabric(
                compiled.as_text(), cps)
    except Exception:
        pass
    return out
