"""Text-level parsers over lowered (StableHLO) and compiled (optimized HLO)
program artifacts — the ONE place the repo reads compiler output.

Two tiers of text, two sets of facts:

- ``lowered.as_text()`` (StableHLO) is what JAX *asked for*: collective ops
  still carry the wire dtype the program was traced with (XLA:CPU later
  promotes bf16 host collectives back to f32 during optimization, so dtype-
  at-collective-boundary checks MUST read this tier), and donated parameters
  carry ``tf.aliasing_output`` attributes.
- ``compiled.as_text()`` (optimized HLO) is what XLA *delivered*: the
  ``input_output_alias`` map records which donations were actually honored,
  ``allow_spmd_sharding_propagation_to_output`` records per-output whether
  the caller pinned the placement or left it to the compiler (the PR 8
  silent-recompile class), and ``constant(...)`` instructions record what got
  baked into the executable.

Consumers: :mod:`sheeprl_tpu.analysis.audit` (the graft-audit gate) and
``benchmarks/collective_analysis.py`` (the scaling-roofline bench) — both
walk HLO through these helpers so the gate and the bench can never drift.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Set, Tuple

__all__ = [
    "DTYPE_BYTES",
    "shape_bytes",
    "account_collectives",
    "stablehlo_collectives",
    "parse_input_output_aliases",
    "parse_output_pinning",
    "large_constants",
    "find_dtype",
    "op_scopes",
    "while_carried_shapes",
    "while_body_shapes",
    "whole_array_relayouts",
]

#: HLO short dtype -> bytes per element (unknown dtypes default to 4 at the
#: call sites that need a number; the parsers below keep them symbolic)
DTYPE_BYTES: Dict[str, int] = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_TUPLE_ELEM_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def shape_bytes(dtype: str, dims: str) -> int:
    """Bytes of one HLO shape, e.g. ``("f32", "16,128") -> 8192``."""
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


_HLO_COLLECTIVE_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(?:-start)?\("
)


def account_collectives(hlo_text: str) -> dict:
    """Per-collective-op byte totals from optimized HLO text.

    Accounts the RESULT signature of every collective instruction (the bytes
    that ride the interconnect per step, up to the ring factor the roofline
    applies). Caveat inherited by every caller: on XLA:CPU, bf16 collectives
    are promoted back to f32 during optimization — read the StableHLO tier
    (:func:`stablehlo_collectives`) when the wire dtype is the question.
    """
    out: dict = {}
    for line in hlo_text.splitlines():
        m = _HLO_COLLECTIVE_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        rhs_sig = line.split("=", 1)[1] if "=" in line else line
        # the result signature precedes the op name: f32[...] or a tuple
        sig = rhs_sig[: m.start() - len(line.split("=", 1)[0]) - 1] if "=" in line else rhs_sig
        elems = _TUPLE_ELEM_RE.findall(sig)
        nbytes = sum(shape_bytes(t, d) for t, d in elems if t in DTYPE_BYTES)
        if nbytes == 0:
            continue
        slot = out.setdefault(op, {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += nbytes
    return out


_SHLO_COLLECTIVE_RE = re.compile(
    r"stablehlo\.(all_reduce|all_gather|reduce_scatter|collective_permute|all_to_all)"
)
_SHLO_GROUPS_RE = re.compile(r"replica_groups\s*=\s*dense<[^>]*>\s*:\s*tensor<(\d+)x(\d+)xi64>")
_TENSOR_RE = re.compile(r"tensor<([0-9x]*?)x?(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|i64|i32|i16|i8|i1)>")
_SHLO_DTYPE_ALIASES = {"i64": "s64", "i32": "s32", "i16": "s16", "i8": "s8", "i1": "pred"}


def _tensor_bytes(sig: str) -> List[Tuple[str, int]]:
    """``(dtype, bytes)`` for every tensor type in a StableHLO signature."""
    out: List[Tuple[str, int]] = []
    for dims, dt in _TENSOR_RE.findall(sig):
        dt = _SHLO_DTYPE_ALIASES.get(dt, dt)
        n = 1
        for d in dims.split("x"):
            if d:
                n *= int(d)
        out.append((dt, n * DTYPE_BYTES.get(dt, 4)))
    return out


def stablehlo_collectives(stablehlo_text: str) -> List[Dict[str, object]]:
    """Collective ops from the LOWERED (StableHLO) text, with the dtype the
    program was traced with — the ground truth for wire-dtype policy checks.

    Returns one record per op: ``{"op", "dtype", "bytes", "group_size"}``
    where ``bytes`` accounts the result tensors and ``group_size`` is the
    replica-group width (== the size of the mesh axis the op rides for the
    1-axis meshes this repo builds today; multi-axis meshes disambiguate by
    matching group width against axis sizes).
    """
    lines = stablehlo_text.splitlines()
    records: List[Dict[str, object]] = []
    for i, line in enumerate(lines):
        m = _SHLO_COLLECTIVE_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        gm = _SHLO_GROUPS_RE.search(line)
        group_size = int(gm.group(2)) if gm else 0
        # The type signature `... : (tensor<...>) -> tensor<...>` sits on the
        # op line for region-free ops (all_gather) or on the region-closing
        # `}) : (...) -> ...` line for ops with a reduction body.
        sig_line: Optional[str] = None
        for j in range(i, min(i + 64, len(lines))):
            if ") -> " in lines[j]:
                sig_line = lines[j]
                break
        if sig_line is None:
            continue
        result_sig = sig_line.split(") -> ", 1)[1]
        tensors = _tensor_bytes(result_sig)
        nbytes = sum(b for _, b in tensors)
        dtypes = sorted({t for t, _ in tensors})
        records.append(
            {"op": op, "dtype": ",".join(dtypes) or "unknown", "bytes": nbytes, "group_size": group_size}
        )
    return records


_ALIAS_ENTRY_RE = re.compile(r"\{([0-9,\s]*)\}:\s*\((\d+)")


def parse_input_output_aliases(compiled_hlo_text: str) -> List[Tuple[Tuple[int, ...], int]]:
    """``[(output_tuple_index, parameter_number), ...]`` from the optimized
    HLO module header's ``input_output_alias`` map — the donations XLA
    actually honored. Empty list when nothing aliased."""
    # the alias map nests one level of braces per entry; grab the header
    # region between 'input_output_alias={' and the matching close brace
    start = compiled_hlo_text.find("input_output_alias={")
    if start < 0:
        return []
    depth = 0
    end = start
    for k in range(start + len("input_output_alias="), len(compiled_hlo_text)):
        ch = compiled_hlo_text[k]
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                end = k
                break
    block = compiled_hlo_text[start:end]
    out: List[Tuple[Tuple[int, ...], int]] = []
    for m in _ALIAS_ENTRY_RE.finditer(block):
        idx = tuple(int(x) for x in m.group(1).replace(" ", "").split(",") if x != "")
        out.append((idx, int(m.group(2))))
    return out


_PIN_RE = re.compile(r"allow_spmd_sharding_propagation_to_output=\{([a-z,]*)\}")


def parse_output_pinning(compiled_hlo_text: str) -> Optional[List[bool]]:
    """Per-flat-output ``True`` = the caller PINNED the placement
    (``out_shardings``), ``False`` = the compiler chose it (the PR 8
    silent-recompile class: an equivalent-but-differently-keyed placement on
    a fed-back output recompiles the whole program on call 2).

    Returns None when the module header carries no propagation flags (single
    unpartitioned executables). A single flag broadcasts over all outputs.
    """
    m = _PIN_RE.search(compiled_hlo_text)
    if not m:
        return None
    flags = [tok == "false" for tok in m.group(1).split(",") if tok]
    return flags or None


_CONST_RE = re.compile(r"=\s*([a-z0-9]+)\[([0-9,]*)\]\S*\s+constant\(")


def large_constants(compiled_hlo_text: str, min_bytes: int) -> List[Dict[str, object]]:
    """Constants baked into the optimized executable at or above
    ``min_bytes`` — weights folded into a program break hot swap (graft-serve)
    and bloat every copy of the executable."""
    out: List[Dict[str, object]] = []
    for line in compiled_hlo_text.splitlines():
        m = _CONST_RE.search(line)
        if not m:
            continue
        dtype, dims = m.group(1), m.group(2)
        if dtype not in DTYPE_BYTES:
            continue
        nbytes = shape_bytes(dtype, dims)
        if nbytes >= min_bytes:
            out.append({"dtype": dtype, "shape": dims or "scalar", "bytes": nbytes})
    out.sort(key=lambda r: -int(r["bytes"]))  # type: ignore[arg-type]
    return out


def find_dtype(stablehlo_text: str, dtype: str) -> int:
    """Occurrences of ``dtype`` (HLO/StableHLO short name, e.g. ``f64``) in
    tensor types of the lowered text — 0 means the program never touches it."""
    return len(re.findall(rf"tensor<(?:[0-9x]+x)?{re.escape(dtype)}>", stablehlo_text))


_INSTRUCTION_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z_][\w.\-]*)\s*=\s")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_NAME_STACK_SPLIT_RE = re.compile(r"[/()\[\]]")


def op_scopes(
    compiled_hlo_text: str, regions: Tuple[str, ...], kernel_prefix: str = "kernel."
) -> Dict[str, Dict[str, object]]:
    """Which ``jax.named_scope`` each instruction of an optimized executable
    ran under: ``{instruction: {"scope", "outer", "backward"}}``.

    A device trace names an event by its instruction and carries no
    ``op_name``; the executable's text carries both, so this table is the join
    from a trace's instruction names to the program's own region names. Read
    from each instruction's ``metadata={op_name="..."}`` alone (the first of a
    ``;``-joined list) — nothing is guessed from an instruction's kind or
    shape. ``outer`` is the outermost of ``regions`` on the name stack
    (regions are disjoint by it), ``scope`` the innermost known name (a
    region, or ``<kernel_prefix><name>``), both ``None`` where the path
    holds neither or the instruction has no ``op_name``; ``backward`` says the
    path went through ``transpose(...)``, which is how JAX marks the
    backward pass of ``jvp(<scope>)``.
    """
    known = set(regions)
    out: Dict[str, Dict[str, object]] = {}

    def entry(op_name: Optional[str]) -> Dict[str, object]:
        outer: Optional[str] = None
        scope: Optional[str] = None
        backward = False
        if op_name is not None:
            op_name = op_name.split(";", 1)[0]
            for token in _NAME_STACK_SPLIT_RE.split(op_name):
                if token in known:
                    outer = outer or token
                    scope = token
                elif token.startswith(kernel_prefix) and len(token) > len(kernel_prefix):
                    scope = token
            backward = "transpose(" in op_name
        return {"scope": scope, "outer": outer, "backward": backward}

    waiting: Optional[str] = None  # an instruction whose text runs on over the next lines, its metadata not seen yet
    for line in compiled_hlo_text.splitlines():
        m = _INSTRUCTION_RE.match(line)
        meta = _OP_NAME_RE.search(line)
        if not m:
            # a continuation line (a Pallas call's `kernel_metadata` is printed over several): the
            # instruction's own `metadata={op_name=...}` follows it there
            if waiting is not None and meta:
                out[waiting] = entry(meta.group(1))
                waiting = None
            continue
        out[m.group(1)] = entry(meta.group(1) if meta else None)
        waiting = None if meta else m.group(1)
    return out


_WHILE_RE = re.compile(r"=\s*(\(.*\))\s+while\(")


def _shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(t, tuple(int(d) for d in dims.split(",") if d)) for t, dims in _TUPLE_ELEM_RE.findall(text)]


def while_carried_shapes(compiled_hlo_text: str) -> List[List[Tuple[str, Tuple[int, ...]]]]:
    """For every ``while`` of an optimized executable, the ``(dtype, dims)`` of
    each array its loop carries (the instruction's result tuple, in order).
    A loop-invariant array rides the carry too, so a weight a scan applies
    appears once; a second array of its shape is an accumulator (the
    transposed scan's gradient of a closed-over weight)."""
    loops: List[List[Tuple[str, Tuple[int, ...]]]] = []
    for line in compiled_hlo_text.splitlines():
        m = _WHILE_RE.search(line)
        if m:
            loops.append(_shapes(m.group(1)))
    return loops


_COMPUTATION_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_WHILE_BODY_RE = re.compile(r"\swhile\(.*\bbody=%?([\w.\-]+)")


def while_body_shapes(compiled_hlo_text: str) -> List[Set[Tuple[str, Tuple[int, ...]]]]:
    """For every ``while`` of an optimized executable, the ``(dtype, dims)`` of
    every array its body names: the results and operands of the body's own
    instructions, its parameter tuple included (what a fusion works on inside
    shows as that fusion's operands and result). What a scan's step computes
    from, at the shapes it computes at."""
    lines: Dict[str, List[str]] = {}
    computation = None
    for line in compiled_hlo_text.splitlines():
        head = _COMPUTATION_RE.match(line)
        if head:
            computation = head.group(2)
        elif computation is not None:
            lines.setdefault(computation, []).append(line)
    return [set(_shapes("\n".join(lines.get(body, [])))) for body in _WHILE_BODY_RE.findall(compiled_hlo_text)]


_RELAYOUT_RE = re.compile(r"=\s*([a-z][a-z0-9]*)\[([0-9,]*)\](\{[^}]*\})?\s+(copy|reshape|transpose)\(")


def whole_array_relayouts(compiled_hlo_text: str, leading_dim: int) -> List[Dict[str, object]]:
    """Every ``copy``, ``reshape`` or ``transpose`` of an optimized executable
    whose result's leading dimension is ``leading_dim`` — with the replay
    ring's capacity, the instructions that rewrite a whole ring key (a layout
    change XLA answers a reshape or a custom call's operand layout with).
    ``[{"name", "op", "dtype", "dims", "layout", "bytes", "computation"}]`` in
    program order; ``bytes`` is the logical size, tile padding not counted."""
    found: List[Dict[str, object]] = []
    computation = None
    for line in compiled_hlo_text.splitlines():
        head = _COMPUTATION_RE.match(line)
        if head:
            computation = head.group(2)
            continue
        m = _RELAYOUT_RE.search(line)
        name = _INSTRUCTION_RE.match(line)
        if not (m and name):
            continue
        dtype, dims, layout, op = m.groups()
        if dims.split(",", 1)[0] != str(leading_dim):
            continue
        found.append(
            {
                "name": name.group(1), "op": op, "dtype": dtype, "dims": tuple(int(d) for d in dims.split(",")),
                "layout": layout or "", "bytes": shape_bytes(dtype, dims), "computation": computation,
            }
        )
    return found
