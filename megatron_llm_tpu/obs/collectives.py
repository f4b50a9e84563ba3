"""What a compiled train step does with its gradients across the ranks
that split the batch, read from the executable's HLO text.

Where the gradient's sum over those ranks is taken is fixed when the step
compiles (training/step.py:BatchAxisSum), so the counter that says which
order engaged is a reading of the compiled program, not of a run:
``grad_collectives`` counts the reducing collectives (all-reduce,
reduce-scatter) whose replica groups span a batch axis of the mesh and
that carry a parameter-shaped operand — inside the microbatch loop, times
the trip counts of the loops they sit in, and outside it.  The driver logs
the reading once at set-up and puts it on its first ``log_window`` event;
tests/training/test_grad_collectives.py holds the step to it.
"""

from __future__ import annotations

import re
from typing import Iterable

import numpy as np

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_BOUND = re.compile(r"\bs32\[\][^ ]* constant\((\d+)\)")
_SHAPE = re.compile(r"\b([a-z]+\d+[a-z0-9]*|pred)\[([\d,]*)\]")
_CALLEE = re.compile(
    r"\b(?:body|calls|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_REDUCING = {"all-reduce": "all-reduce", "all-reduce-start": "all-reduce",
             "reduce-scatter": "reduce-scatter"}
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8}


def _computations(hlo_text: str):
    """name -> instruction lines, and the entry computation's name."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m and " -> " in line:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
        elif line.startswith("}"):
            cur = None
        else:
            comps[cur].append(line)
    return comps, entry


def _shapes(type_text: str) -> list:
    """[(dtype, dims)] of an HLO type, a tuple's elements in order."""
    return [(d, tuple(int(x) for x in dims.split(",") if x))
            for d, dims in _SHAPE.findall(type_text)]


def _nbytes(shape) -> int:
    """Bytes of one ``(dtype, dims)`` of ``_shapes``."""
    dtype, dims = shape
    return (1 if dtype.startswith("f8") else _ITEMSIZE.get(dtype, 4)) * int(
        np.prod(dims, dtype=np.int64))


def _trip_count(while_line: str, comps: dict):
    """A ``while``'s trip count: XLA:CPU writes it on the instruction;
    XLA:TPU does not, and there it is the one s32 constant that the
    condition compares the counter with (a ``lax.scan`` counts up from 0
    by 1)."""
    m = _TRIPS.search(while_line)
    if m:
        return int(m.group(1))
    cond = re.search(r"\bcondition=%?([\w.\-]+)", while_line).group(1)
    lines = comps.get(cond, ())
    bounds = [b for line in lines for b in _BOUND.findall(line)]
    if len(bounds) == 1 and any("direction=LT" in line for line in lines):
        return int(bounds[0])
    return None


def _replica_groups(line: str, n_devices: int) -> np.ndarray:
    """[groups, members] partition ids of a collective's replica groups,
    from the explicit ``{{0,2},{1,3}}`` or the iota ``[2,2]<=[2,2]T(1,0)``
    form; no groups at all means one group of every device."""
    m = re.search(r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
                  line)
    if m:
        shape = [int(x) for x in m.group(1).split(",")]
        dims = [int(x) for x in m.group(2).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(3):
            ids = ids.transpose([int(x) for x in m.group(3).split(",")])
        return ids.reshape(shape)
    m = re.search(r"replica_groups=\{((?:\{[\d,]*\},?)*)\}", line)
    groups = [[int(x) for x in g.split(",") if x]
              for g in re.findall(r"\{([\d,]*)\}", m.group(1))] if m else []
    if not groups:
        return np.arange(n_devices)[None]
    return np.asarray(groups)


def _spans(groups: np.ndarray, mesh_shape: dict, axes: Iterable[str]) -> bool:
    """Whether a replica group holds devices that differ along one of
    ``axes``.  A partition id is a device's place in the mesh's flattened
    device array, which is the order jit assigns them in."""
    names = list(mesh_shape)
    coords = np.stack(np.unravel_index(
        groups, [mesh_shape[a] for a in names]), axis=-1)
    return any(np.any(coords[..., names.index(a)]
                      != coords[..., :1, names.index(a)]) for a in axes)


def param_shard_shapes(params, shardings) -> set:
    """The shapes a parameter's gradient takes on one device: each leaf's
    shard, and for the layer-stacked leaves one layer's slice of it (the
    layer scan's backward makes those one layer at a time)."""
    import jax

    shapes = set()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for (path, p), s in zip(flat, jax.tree.leaves(shardings)):
        shard = tuple(s.shard_shape(p.shape))
        shapes.add(shard)
        if "layers" in jax.tree_util.keystr(path) and len(shard) > 1:
            shapes.update({shard[1:], (1,) + shard[1:]})
    return shapes


def grad_collectives(hlo_text: str, mesh_shape: dict, batch_axes,
                     param_shapes: set, microbatches: int) -> dict:
    """``{"in_loop", "after_loop", "leaves", "kind", "bytes"}`` of a
    compiled train step.

    ``in_loop``: executions a step of reducing collectives over
    ``batch_axes`` with a parameter-shaped operand inside the microbatch
    loop — the outermost ``while`` of ``microbatches`` trips that carries
    an f32 array of a parameter's shape, the gradient sums — counted
    through the loops nested in it; ``after_loop``: the same outside it;
    ``leaves``, ``bytes``: the parameter-shaped operands those outside
    reduce and their bytes on one device; ``kind``: their opcodes.
    ``mesh_shape`` maps the mesh's axis names, in order, to their sizes.
    Raises ``ValueError`` where ``microbatches`` > 1 and no such loop is
    found: a reading that cannot tell inside from after says nothing.
    """
    comps, entry = _computations(hlo_text)
    n_devices = int(np.prod(list(mesh_shape.values())))
    out = {"in_loop": 0, "after_loop": 0, "kind": "none", "leaves": 0,
           "bytes": 0}
    kinds = set()
    loops_found = []

    def walk(name: str, times: int, in_loop: bool, in_while: bool) -> None:
        parsed = []                # (line, result type, opcode, operands)
        for line in comps.get(name, ()):
            lhs, _, rhs = line.partition(" = ")
            m = _OPCODE.search(rhs)
            if m:
                parsed.append((line, lhs.split()[-1], rhs[:m.start()],
                               m.group(1), rhs[m.end():].split(")")[0]))
        types = {lhs: result for _, lhs, result, _, _ in parsed}
        for line, _, result, op, operands in parsed:
            if op == "while":
                trips = _trip_count(line, comps)
                is_mb = (not in_while and microbatches > 1
                         and trips == microbatches
                         and any(d == "f32" and dims in param_shapes
                                 for d, dims in _shapes(result)))
                loops_found.append(is_mb)
                body = re.search(r"\bbody=%?([\w.\-]+)", line).group(1)
                walk(body, times * (trips or 1), in_loop or is_mb, True)
                continue
            callees = _CALLEE.findall(line)
            for group in _BRANCHES.findall(line):
                callees += [c.strip().lstrip("%") for c in group.split(",")]
            for callee in callees:
                walk(callee, times, in_loop, in_while)
            kind = _REDUCING.get(op)
            if kind is None or not _spans(
                    _replica_groups(line, n_devices), mesh_shape, batch_axes):
                continue
            shaped = [s for o in re.findall(r"%[\w.\-]+", operands)
                      for s in _shapes(types.get(o, ""))
                      if s[1] in param_shapes]
            if not shaped:
                continue
            if in_loop:
                out["in_loop"] += times
            else:
                out["after_loop"] += times
                out["leaves"] += times * len(shaped)
                out["bytes"] += times * sum(map(_nbytes, shaped))
                kinds.add(kind)

    walk(entry, 1, False, False)
    if microbatches > 1 and not any(loops_found):
        raise ValueError(
            f"no loop of {microbatches} trips carrying gradient sums among "
            f"{len(loops_found)} while instructions")
    out["kind"] = "+".join(sorted(kinds)) or "none"
    return out
