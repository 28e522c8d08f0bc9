"""The join table of a compiled train step: HLO instruction -> the part
of the step it came from and its direction.

A device trace names an event by its HLO instruction (`fusion.12`); what
the instruction computes for stands in its `op_name`, which the profile
does not carry. The program names every part of its train step by
nested calls (`ops.base.scoped`: `loss`, `optimizer_update`, `head`,
`op_<kind>`, and the scopes of the ops that name themselves), and
`jvp(...)` / `transpose(jvp(...))` around them tell forward from
backward. This module holds the one rule that reads an `op_name` back
(`part_of`, `direction_of`, `classify`), parses a compiled step's HLO
text with it (`table_of`) and, for an open trace session, lowers the
step that the session's `fit` calls dispatched and writes the table
beside the session's other artifacts (`StepScopes`).
"""

from __future__ import annotations

import base64
import collections
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

from flexflow_tpu.obs.artifacts import write_artifact

SUFFIX = ".step_scopes.json"

# program scope -> part; beside them `attention_<kind>` -> "attention",
# `op_<kind>` -> itself and `ut<t>` -> itself
SCOPE_PARTS = {"optimizer_update": "optimizer_update", "loss": "loss",
               "head": "head", "moe_layer": "experts", "ssm_mixer": "ssm",
               # the Mamba-1 mixer, and the gated memory unit that reads
               # its scan's output (the builder's scope around the
               # unit's products and multiplies)
               "mamba_mixer": "mamba", "gated_memory": "gated_memory",
               # the gated delta-rule mixer (its chunked rule lies in the
               # nested call `delta_rule` inside it)
               "delta_mixer": "delta_mixer",
               # the two halves of a hyper-connection around a sublayer
               # (`hc_read`, `hc_maps`, `hc_write` lie inside it)
               "hyper_connection": "hyper_connection",
               # a multi-token-prediction module's own ops, whatever their
               # kind: the scope lies around theirs (`FFModel.scope`)
               "mtp": "mtp",
               # the indexer of a learned-sparse-attention op: its
               # projections, scores and selection, and its loss with the
               # gradient (two nested calls beside the op's own
               # `attention_sparse`, which is `attention` like its kind)
               "sparse_indexer": "sparse_indexer",
               # what a looped model's passes share: the concatenation of
               # the passes, the head's and the exit gate's products
               "exit": "exit"}
# a looped model's pass `ut<t>`, every op of it: itself, so that the
# table tells pass from pass (the same kinds and shapes run in each)
PASS_SCOPE = re.compile(r"ut\d+$")
ATTENTION_SCOPE = "attention_"
OP_SCOPE = "op_"
# a Pallas kernel called at the top level (the non-causal attention op's,
# whose events must keep the name `tpu_custom_call*`; a fused update
# outside any scope) is known by its own `name=`: (name, part, direction)
KERNELS = (("flash_bwd", "attention", "backward"),
           ("flash_fwd", "attention", "forward"),
           ("fused_adam", "optimizer_update", "optimizer"))
DIRECTIONS = ("forward", "backward", "optimizer", "none")

_JIT = re.compile(r"jit\(([\w.\-]+)\)")
_NAME = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# a computation's header, `name (parameters) -> results {`: a tuple of six
# results or more holds `/*index=5*/` (the ENTRY's among them), so nothing
# here may stop at an `=`
COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{\s*$")
_PAYLOAD = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_REFERENCE = re.compile(r"(\w+=)?%([\w.\-]+)")
# how far a part is looked for beyond an instruction that has none
NEIGHBOUR_DEPTH = 4


def scope_part(scope: str) -> Optional[str]:
    """The part a program scope's name stands for, or None."""
    if scope in SCOPE_PARTS:
        return SCOPE_PARTS[scope]
    if PASS_SCOPE.match(scope):
        return scope
    if scope.startswith(ATTENTION_SCOPE):
        return "attention"
    if scope.startswith(OP_SCOPE):
        return scope
    return None


def part_of(op_name: str) -> Optional[str]:
    """The part of the step an `op_name` lies in: that of the outermost
    `jit(<scope>)` that is one of the program's scopes (never a bare
    substring: `loss` also occurs in `jit(cross_entropy_loss)`)."""
    for scope in _JIT.findall(op_name):
        part = scope_part(scope)
        if part is not None:
            return part
    return None


def direction_of(op_name: str, part: Optional[str] = None) -> str:
    """`optimizer` inside the update; `backward` under a `transpose(`
    (or the recomputation of a checkpointed op, which runs in the
    backward pass); `forward` under a `jvp(` alone; else `none`."""
    if part == "optimizer_update":
        return "optimizer"
    if "transpose(" in op_name or "rematted_computation" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return "none"


def kernel_names(line: str) -> str:
    """Where a custom call's line can hold its kernel's `name=`: the
    line itself (`op_name`; on the chip a top-level instruction keeps
    only `pallas_call` of it) and the Mosaic payload, whose MLIR
    bytecode stands base64-coded under `"body"` and holds the name as
    the kernel function's symbol."""
    payloads = []
    for blob in _PAYLOAD.findall(line):
        try:
            payloads.append(base64.b64decode(blob).decode("latin-1"))
        except ValueError:
            pass
    return line + "".join(payloads)


def classify(op_name: str, line: str = "") -> Tuple[Optional[str], str]:
    """(part, direction) of one instruction. `line` is its line of the
    HLO text: a top-level kernel call, whose `op_name` holds no program
    scope, is placed by the kernel's own name (under `shard_map` the
    line of the `shard_map.N` instruction)."""
    op_name = op_name.split(";")[0]
    part = part_of(op_name)
    if part is None and "custom_call_target=" in line:
        where = kernel_names(line)
        for kernel, kernel_part, direction in KERNELS:
            if kernel in where:
                return kernel_part, direction
    return part, direction_of(op_name, part)


def table_of(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """instruction name -> `op_name`, `part` (None where no scope of
    the program holds it), `direction`, for every instruction of a
    compiled module's text, fused bodies included; a fusion also gets
    `parts`: part -> how many instructions of its body lie in it, and
    `directions` likewise (the fusion's event carries only its root's
    `op_name`: the update fused into a weight gradient's product reads
    `backward`). An instruction without a part (the compiler's own
    copies, prefetches and layout changes carry no `op_name`) gets
    `feeds`: the part most of those who read its result lie in, looked
    for through up to NEIGHBOUR_DEPTH part-less readers, or, where none
    of them has one, `fed_by`: the same among its operands."""
    table: Dict[str, Dict[str, Any]] = {}
    bodies: Dict[str, List[str]] = collections.defaultdict(list)
    calls: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    users: Dict[str, List[str]] = collections.defaultdict(list)
    computation = None
    for line in hlo_text.splitlines():
        m = _NAME.match(line)
        if m is None:
            c = COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(1)
        found = _OP_NAME.search(line)
        op_name = found.group(1) if found else ""
        part, direction = classify(op_name, line)
        table[name] = dict(op_name=op_name, part=part, direction=direction)
        bodies[computation].append(name)
        # `%x` is an operand, `calls=%f` and the like a computation
        operands[name] = [ref for attr, ref in _REFERENCE.findall(
            line[m.end():].split(", metadata=")[0]) if not attr]
        for ref in operands[name]:
            users[ref].append(name)
        if " fusion(" in line:
            called = _CALLS.search(line)
            if called:
                calls[name] = called.group(1)
    for name, body in calls.items():
        rows = [table[i] for i in bodies.get(body, ())
                if table[i]["part"] is not None]
        table[name]["parts"] = dict(collections.Counter(
            r["part"] for r in rows))
        table[name]["directions"] = dict(collections.Counter(
            r["direction"] for r in rows))

    def near(name, edges):
        """The part most of the nearest instructions along `edges` that
        have one lie in, breadth first."""
        seen, front = {name}, [name]
        for _ in range(NEIGHBOUR_DEPTH):
            reached = []
            for f in front:
                for n in edges.get(f, ()):
                    if n in table and n not in seen:
                        seen.add(n)
                        reached.append(n)
            parts = collections.Counter(
                table[n]["part"] for n in reached
                if table[n]["part"] is not None)
            if parts:
                return parts.most_common(1)[0][0]
            front = reached
        return None

    for name, row in table.items():
        if row["part"] is None:
            for key, edges in (("feeds", users), ("fed_by", operands)):
                part = near(name, edges)
                if part is not None:
                    row[key] = part
                    break
    return table


def abstract_args(args):
    """The call's avals and shardings, shapes only: what `lower` needs
    to make the program the call ran. An uncommitted array (the step's
    rng) keeps no sharding, as the call itself placed it."""
    import jax

    def abstract(a):
        sharding = a.sharding if getattr(a, "committed", True) else None
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding,
                                    weak_type=getattr(a, "weak_type", False))

    return jax.tree.map(abstract, args)


class StepScopes:
    """What an open session with `device=True` keeps of the train steps
    its `fit` calls dispatch, and the tables written from it."""

    def __init__(self):
        # executor id -> (model, jitted step, abstract arguments), in order
        self._steps: Dict[int, Tuple[Any, Any, Any]] = {}

    def wants(self, executor) -> bool:
        return id(executor) not in self._steps

    def keep(self, ff, step, args) -> None:
        """Once a session and executor, before the call (it donates)."""
        if hasattr(step, "lower"):
            self._steps.setdefault(id(ff.executor),
                                   (ff, step, abstract_args(args)))

    def write(self, trace_dir: str, file_stem: str,
              host_id: Optional[int] = None) -> Dict[str, Any]:
        """Lower and compile each kept step with its arguments' shapes,
        write `<stem>.step_scopes.json` (a second executor's step
        `<stem>.step_scopes.1.json`, ...) and return the header fields:
        the first table's path, what the lowering cost, how many
        instructions the table holds. Beside `instructions` the file
        holds `prices`: what the native simulator says of the strategy
        that ran (`priced_step`), and what that replay cost on the host
        (`step_prices_s`). The profiler has stopped."""
        meta: Dict[str, Any] = {}
        for k, (ff, step, args) in enumerate(self._steps.values()):
            t0 = time.perf_counter()
            try:
                text = step.lower(*args).compile().as_text()
                table = table_of(text)
            except Exception as e:   # no table: the readers return nothing
                meta.setdefault("step_scopes_error", repr(e))
                continue
            seconds = time.perf_counter() - t0
            payload, header = dict(instructions=table), dict(
                step_scopes_s=seconds, step_scopes_instructions=len(table))
            prices, priced = priced_step(ff)
            header.update(priced)
            if prices is not None:
                payload["prices"] = prices
            path = os.path.join(trace_dir, file_stem + (
                SUFFIX if k == 0 else f".step_scopes.{k}.json"))
            write_artifact(path, payload, host_id=host_id,
                           kind="step_scopes", indent=None,
                           header_extra=header)
            if "step_scopes" not in meta:
                meta.update(step_scopes=os.path.basename(path), **header)
        self._steps.clear()
        return meta


def priced_step(ff) -> Tuple[Optional[Dict[str, Any]], Dict[str, Any]]:
    """(the `prices` object of `ff`'s executed strategy, the header
    fields that say what it cost): ONE replay through the native
    simulator (`search.validate.simulate_strategy`), read by
    `obs.simtrace.step_prices`. A model that no search compiled has no
    prices to hold against the chip: (None, {}). A replay that fails
    leaves `step_prices_error` and no prices, and every reader then
    returns nothing, as for a missing table."""
    if not isinstance(ff.search_info, dict):
        return None, {}
    from flexflow_tpu.obs.simtrace import step_prices
    from flexflow_tpu.search.validate import simulate_strategy

    t0 = time.perf_counter()
    try:
        prices = step_prices(ff, simulate_strategy(ff))
    except Exception as e:
        return None, dict(step_prices_error=repr(e))
    return prices, dict(step_prices_s=time.perf_counter() - t0)
