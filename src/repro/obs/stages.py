"""Round stages on the device: which stage of Algorithm 1 each op of a
compiled lattice program belongs to.

The round pipeline wraps each stage in ``jax.named_scope(<stage>)``
(``core.pofl.round_algorithm``, ``sim.engine.SimEngine.scan_rounds``), so
every HLO instruction the stage lowers to carries the stage in the
``op_name`` path of its metadata (``jit(...)/vmap(local_update)/
transpose(jvp())/dot_general``). The engine registers each compiled lattice
module here at compile time (:func:`register_module`), keyed by its HLO
module name: the name a profiler trace's ``XLA Modules`` line gives its
executions (``jit__fused_lattice_cell(<fingerprint>)``). Registration keeps
a callable that renders the module's optimized HLO text, not the text: the
op → stage map is built only when first asked for (:func:`stage_map`), so
a run that never reads it never pays for rendering the module.

The map gives each instruction the first component of its ``op_name`` that
names a stage (transform wrappers such as ``vmap(...)`` are looked
through). An instruction with no ``op_name`` — a copy or a parameter that
XLA added — takes the stage of its first operand, and ``unscoped`` when
that has none either; a computation's parameters count as operands of the
instruction that runs it. A trace shows only the instructions that run as
ops of their own (not the insides of a fusion), but the map holds all. Registrations live for the whole process: clearing
the engine cache does not drop them, so a trace can be read after the
engines that ran it are gone.

No jax import: safe to import from anywhere.
"""
from __future__ import annotations

import re
import threading
from typing import Callable

# the stages of one round, in program order (``eval`` runs on eval rounds)
STAGES = (
    "channel",
    "local_update",
    "statistics",
    "scheduling",
    "aggregation",
    "apply_update",
    "round_record",
    "eval",
)
UNSCOPED = "unscoped"

_MODULES: dict[str, Callable[[], str]] = {}
_MAPS: dict[str, dict[str, str]] = {}
_LOCK = threading.Lock()

# ``%name = <shape> opcode(<operands>), ..., metadata={op_name="..." ...}``;
# shapes carry no ``%``, so the first ``%ident`` after ``=`` is operand 0
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_OPERAND = re.compile(r"%([^\s,()]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# a transform around a name-stack component: ``vmap(local_update)``
_WRAPPER = re.compile(r"^[\w.\-]*\((.*)\)$")
# ``%fused_computation.3 (param_0: f32[8]) -> f32[8] {`` / ``ENTRY %main ...{``
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")
# the computations an instruction runs: fusion bodies, reducers, loops,
# branches
_CALLED = re.compile(
    r"\b(?:calls|to_apply|select|scatter|body|condition|true_computation"
    r"|false_computation)=(%[^\s,}]+)|\bbranch_computations=\{([^}]*)\}"
)


def register_module(name: str, hlo_text: Callable[[], str]) -> None:
    """Register compiled module ``name``; ``hlo_text()`` renders its
    optimized HLO. A later registration under the same name replaces it."""
    with _LOCK:
        _MODULES[name] = hlo_text
        _MAPS.pop(name, None)


def registered_modules() -> tuple[str, ...]:
    """The names of every registered module."""
    with _LOCK:
        return tuple(_MODULES)


def stage_map(name: str) -> dict[str, str] | None:
    """Instruction name → stage for module ``name`` (None when the module
    was never registered). Built on first use, then kept."""
    with _LOCK:
        built = _MAPS.get(name)
        render = _MODULES.get(name)
    if built is not None or render is None:
        return built
    built = build_stage_map(render())
    with _LOCK:
        if _MODULES.get(name) is render:
            _MAPS[name] = built
    return built


def stage_of(op_name: str) -> str | None:
    """The first component of an ``op_name`` path that names a stage."""
    for part in op_name.split("/"):
        while True:
            if part in STAGES:
                return part
            m = _WRAPPER.match(part)
            if m is None:
                break
            part = m.group(1)
    return None


def build_stage_map(hlo_text: str) -> dict[str, str]:
    """Instruction name → stage over every computation of an HLO module's
    text (instruction names are unique in a module). A computation's
    parameters count as operands of the instruction that runs it, so a
    loop's carry or a fusion's inputs take that instruction's stage."""
    computations: dict[str, list[tuple[str, str]]] = {}
    body = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            body = computations.setdefault(head.group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if m is not None and body is not None:
            body.append(m.groups())
    # HLO text lists a computation before the computations that run it, so
    # in reverse order every caller comes before its callees
    out: dict[str, str] = {}
    caller_stage: dict[str, str] = {}
    for comp in reversed(list(computations)):
        for name, rest in computations[comp]:
            meta = _OP_NAME.search(rest)
            op_name = meta.group(1) if meta else ""
            stage = stage_of(op_name) if op_name else None
            if not op_name:
                operand = _OPERAND.search(rest)
                stage = (
                    out.get(operand.group(1)) if operand is not None
                    else caller_stage.get(comp)
                )
            stage = out[name] = stage or UNSCOPED
            for groups in _CALLED.findall(rest):
                for callee in _OPERAND.findall(" ".join(groups)):
                    caller_stage.setdefault(callee, stage)
    return out


def clear_modules() -> None:
    """Drop every registration (test hygiene)."""
    with _LOCK:
        _MODULES.clear()
        _MAPS.clear()
