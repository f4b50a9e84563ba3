"""Whether a compiled program moves a weight before it reads it, read
from the executable's HLO text.

The rule the serving decode step is held to (docs/inference.md): a weight
is read from HBM once a call, in the layout it is stored in.  XLA breaks
it silently — layout assignment hands an operation an operand in another
layout than the parameter's and pays with a copy of the whole array in
every call (PERF.md, PR 30: a q projection cut into heads at once re-laid
its layer's ``wq``, the embedding gather the whole tied table).  Nothing engages per
request, so the instrument is a reading of the executable:
``relayout_bytes`` sums what the instructions that only move bytes write.
It compiles nothing itself; tests/kernels/test_tpu_compile.py and
tests_tpu/ hold the engine's decode step to 0.

The second rule (same document): work that only some requests need lies
under a ``cond`` on what the step is handed.  XLA turns a small
``conditional`` into selects that run both sides; ``ops_by_conditional``
says on which side of the executable's conditionals an opcode ended up.
"""

from __future__ import annotations

import re

from .collectives import (_BRANCHES, _CALLEE, _OPCODE, _computations,
                          _nbytes, _shapes, _trip_count)

_MOVES = {"copy", "transpose", "dynamic-slice"}
_RELABELS = {"parameter", "bitcast", "reshape", "get-tuple-element", "tuple"}


def _moves_only(lines, big: int) -> bool:
    """Whether a fused computation moves an array and computes nothing on
    it: of its instructions that produce at least ``big`` bytes, one is a
    copy, transpose or dynamic-slice and the rest relabel.  (Arithmetic
    on indices, a dynamic-slice's clamped start, is small.)"""
    ops = set()
    for line in lines:
        _, _, rhs = line.partition(" = ")
        m = _OPCODE.search(rhs)
        if m and any(_nbytes(s) >= big for s in _shapes(rhs[:m.start()])):
            ops.add(m.group(1))
    return bool(ops & _MOVES) and ops <= _MOVES | _RELABELS


def relayout_bytes(hlo_text: str, min_bytes: int = 8 << 20) -> dict:
    """``{shape: bytes written a call}`` by the instructions of a compiled
    program that only move an array of at least ``min_bytes``: ``copy``,
    ``transpose``, and ``dynamic-slice`` standing alone or as all a fusion
    does (a slice taken inside a matmul's fusion is read where it lies
    and is not counted; nor are the asynchronous ``copy-start`` /
    ``slice-start`` prefetches, which are the one read).  An instruction
    inside a ``while`` counts once a trip, so a layer scan's copy of one
    layer's weight reads as the whole stack.  ``shape`` is the HLO type
    without its layout, ``bf16[1,4544,4544]``.  The threshold keeps
    activations and a decode step's KV rows out: at serving widths only
    weights are that large."""
    comps, entry = _computations(hlo_text)
    out: dict = {}

    def walk(name: str, times: int) -> None:
        for line in comps.get(name, ()):
            _, _, rhs = line.partition(" = ")
            m = _OPCODE.search(rhs)
            if not m:
                continue
            result, op = rhs[:m.start()], m.group(1)
            if op == "while":
                body = re.search(r"\bbody=%?([\w.\-]+)", line).group(1)
                walk(body, times * (_trip_count(line, comps) or 1))
                continue
            callees = _CALLEE.findall(line)
            if op == "fusion":
                if not _moves_only(comps.get(callees[0], ()), min_bytes):
                    continue
            else:
                for callee in callees:
                    walk(callee, times)
                if op not in _MOVES:
                    continue
            for shape in _shapes(result):
                if _nbytes(shape) >= min_bytes:
                    key = f"{shape[0]}[{','.join(map(str, shape[1]))}]"
                    out[key] = out.get(key, 0) + times * _nbytes(shape)

    walk(entry, 1)
    return out


def ops_by_conditional(hlo_text: str, opcode: str):
    """``(inside, outside)``: the names of a compiled program's
    ``opcode`` instructions that run only when a ``conditional`` takes
    their branch, and of those that run in every call.  An instruction
    of a fusion, a loop body or a call counts where its caller lies."""
    comps, entry = _computations(hlo_text)
    inside, outside = [], []

    def walk(name: str, conditional: bool) -> None:
        for line in comps.get(name, ()):
            lhs, _, rhs = line.partition(" = ")
            m = _OPCODE.search(rhs)
            if not m:
                continue
            if m.group(1) == opcode:
                (inside if conditional else outside).append(
                    lhs.split()[-1].lstrip("%"))
            for callee in _CALLEE.findall(line):
                walk(callee, conditional or m.group(1) == "conditional")
            for group in _BRANCHES.findall(line):
                for callee in group.split(","):
                    walk(callee.strip().lstrip("%"), True)

    walk(entry, False)
    return inside, outside


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def ops_under_scopes(hlo_text: str, scopes, opcodes) -> list:
    """``[(opcode, result type, scope path)]`` of a compiled program's
    instructions with one of ``opcodes`` (None: any) whose ``op_name``
    holds one of ``scopes`` as a whole step of its path
    (``jax.named_scope``), in any computation: what a block was said to
    do without (a scatter, a row gather, a ragged product) and still does
    (docs/serving.md, "How a dropless layer routes").  A fused
    computation's instructions are listed beside the fusion itself."""
    comps, _ = _computations(hlo_text)
    found = []
    for lines in comps.values():
        for line in lines:
            _, _, rhs = line.partition(" = ")
            op, name = _OPCODE.search(rhs), _OP_NAME.search(line)
            if (op and name and (opcodes is None or op.group(1) in opcodes)
                    and set(scopes) & set(name.group(1).split("/"))):
                found.append((op.group(1), rhs[:op.start()].strip(),
                              name.group(1)))
    return found
