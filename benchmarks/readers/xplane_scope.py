"""Reads device time by the program's scope names (``jax.named_scope`` at
the model's block boundaries, ``name=`` on its Pallas kernels).

The scope of a device operation is not in its event's name (that is the
HLO text, ``%fusion.417 = ...``) but in the statistics of the event's
*metadata* — ``tf_op`` holds the HLO ``op_name``, e.g.
``jit(step)/transpose(jvp(lm_head))/dot_general`` — which
``jax.profiler.ProfileData`` does not expose and ``trace_reduce.Event``
drops.  So this reader decodes the ``.xplane.pb`` itself: the few
messages of ``xplane.proto`` it needs, straight from the wire format
(the generated ``xplane_pb2`` ships only inside TensorFlow, which is not
brought into the process that holds the chip).

``params``: ``scopes`` — names, an operation counts once if its scope
path holds any of them as a whole word; ``stat`` — ``share`` (own time
of the matching leaf operations over the device's busy time in the
traced window, %) or ``ms_per_run`` (their own time a run of the
executables whose name holds ``module``, ms; a run counts where it
ends).  Once a run the reader prints the traced window's device time by
scope — each leaf operation under the innermost of the program's names
(``obs/profile.py:DEVICE_SCOPES``) in its path — and, where under 90 % of
it lies under one of them, the largest operations outside.  Where the
trace carries no scope at all: None.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Iterator, List, Tuple

from benchmarks import trace_reduce
from benchmarks.common import say


def program_scopes() -> Tuple[str, ...]:
    """Every scope and kernel name the program gives its device work: it
    keeps the list, this reader none."""
    try:
        from megatron_llm_tpu.obs.profile import DEVICE_SCOPES
    except ImportError:        # a program from before it named anything
        return ()
    return tuple(DEVICE_SCOPES)

# the metadata statistic that holds an operation's scope path (read by
# hand from a v5e trace, PR 24: "jit(step)/.../attention/flash_fwd/pallas_call:")
SCOPE_STAT = "tf_op"


# --- the wire format --------------------------------------------------------

def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf: memoryview) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: varints as
    int, 64/32-bit as raw bytes, length-delimited as a memoryview."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
            yield num, wt, val
        elif wt == 2:
            size, i = _varint(buf, i)
            yield num, wt, buf[i:i + size]
            i += size
        elif wt == 1:
            yield num, wt, bytes(buf[i:i + 8])
            i += 8
        elif wt == 5:
            yield num, wt, bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wt} in an xplane")


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names: Dict[int, str]):
    """XStat → (name, string value or None).  A ``ref_value`` points at
    a stat metadata entry whose name is the string; text is decoded only
    for the statistic that is read (a ``source_stack`` is long)."""
    name = value = None
    for num, _wt, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v)
        elif num in (5, 6):
            value = v
        elif num == 7:
            value = stat_names.get(v)
    if name != SCOPE_STAT or value is None:
        return name, None
    return name, value if isinstance(value, str) else _text(value)


def device_ops(path: str) -> Dict[int, List[Tuple[str, float, float, str]]]:
    """``{device ordinal: [(HLO text, start_ns, end_ns, scope path)]}`` of
    the ``XLA Ops`` lines of the ``/device:TPU:<n>`` planes."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[int, List[Tuple[str, float, float, str]]] = {}
    for num, _wt, plane in _fields(space):
        if num != 1:
            continue
        name, lines, event_meta, stat_meta = "", [], [], {}
        for pnum, _w, v in _fields(plane):
            if pnum == 2:
                name = _text(v)
            elif pnum == 3:
                lines.append(v)
            elif pnum == 4:
                event_meta.append(v)
            elif pnum == 5:
                for mnum, _w2, mv in _fields(v):      # map entry: value
                    if mnum == 2:
                        sid, sname = 0, ""
                        for snum, _w3, sv in _fields(mv):
                            if snum == 1:
                                sid = sv
                            elif snum == 2:
                                sname = _text(sv)
                        stat_meta[sid] = sname
        m = trace_reduce.DEVICE_PLANE.match(name)
        if not m:
            continue
        meta: Dict[int, Tuple[str, str]] = {}          # id -> (name, scope)
        for entry in event_meta:
            for mnum, _w, mv in _fields(entry):
                if mnum != 2:
                    continue
                mid, mname, scope = 0, "", ""
                for enum, _w2, ev in _fields(mv):
                    if enum == 1:
                        mid = ev
                    elif enum == 2:
                        mname = _text(ev)
                    elif enum == 5:
                        sname, sval = _stat(ev, stat_meta)
                        if sname == SCOPE_STAT and sval:
                            scope = sval
                meta[mid] = (mname, scope)
        ops = out.setdefault(int(m.group(1)), [])
        for line in lines:
            lname, t_line, events = "", 0, []
            for lnum, _w, lv in _fields(line):
                if lnum == 2:
                    lname = _text(lv)
                elif lnum == 3:
                    t_line = lv
                elif lnum == 4:
                    events.append(lv)
            if lname != "XLA Ops":
                continue
            for ev in events:
                mid = off_ps = dur_ps = 0
                for enum, _w, evv in _fields(ev):
                    if enum == 1:
                        mid = evv
                    elif enum == 2:
                        off_ps = evv
                    elif enum == 3:
                        dur_ps = evv
                mname, scope = meta.get(mid, ("", ""))
                start = t_line + off_ps / 1e3
                ops.append((mname, start, start + dur_ps / 1e3, scope))
        ops.sort(key=lambda o: (o[1], -o[2]))
    return out


# --- the reduction ----------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _holds_one(scope: str, name: str) -> bool:
    return re.search(rf"(?<![A-Za-z0-9_]){re.escape(name)}(?![A-Za-z0-9_])",
                     scope) is not None


def _holds(scope: str, names) -> bool:
    """A trace has a few hundred distinct scope paths under its hundreds
    of thousands of operations: each (path, name) is searched once."""
    return any(_holds_one(scope, n) for n in names)


@functools.lru_cache(maxsize=None)
def _key(scope: str, known: Tuple[str, ...]) -> str:
    """The innermost of ``known`` in the path, else what the path ends in
    (an argument's name for the copies XLA inserts) or ``-``, bracketed."""
    at = {scope.rfind(k): k for k in known if _holds_one(scope, k)}
    if at:
        return at[max(at)]
    return "(" + (scope.rstrip(":").rsplit("/", 1)[-1] or "-") + ")"


def scoped_own_times(ops, window) -> List[Tuple[str, str, float]]:
    """``(HLO text, scope path, own ns)`` of the leaf operations that
    start in ``window``, on one device."""
    events = [trace_reduce.Event(name, s, e, scope)
              for name, s, e, scope in ops]
    return [(e.name, e.line, own)
            for e, own, leaf in trace_reduce.self_times(events)
            if leaf and own > 0 and window[0] <= e.start < window[1]]


def by_scope(leaves, known) -> List[Tuple[str, float]]:
    """``(scope, own ns)``, largest first: each leaf operation under its
    ``_key``."""
    total: Dict[str, float] = {}
    for _n, scope, own in leaves:
        key = _key(scope, known)
        total[key] = total.get(key, 0.0) + own
    return sorted(total.items(), key=lambda kv: -kv[1])


def leaves_of(evidence: dict, known) -> List[Tuple[str, str, float]]:
    """``scoped_own_times`` of the lowest device over the evidence's
    traced window, decoded once a run; prints the table by scope, and
    what lies outside every scope where that is over a tenth."""
    if "scope_ops" in evidence:
        return evidence["scope_ops"]
    path = trace_reduce.find_xplane(evidence["ctx"].trace_dir)
    per_device = device_ops(path)
    leaves = scoped_own_times(per_device[min(per_device)],
                              evidence["trace_window"]) \
        if per_device else []
    evidence["scope_ops"] = leaves
    total = sum(own for _n, _s, own in leaves)
    if not total or not any(s for _n, s, _own in leaves):
        return leaves
    say("device time by scope, % of the leaf operations' "
        f"{total / 1e6:.2f} ms: " + ", ".join(
            f"{k} {100 * v / total:.2f}"
            for k, v in by_scope(leaves, known)[:20]))
    under = sum(own for _n, s, own in leaves if _holds(s, known))
    if under < 0.9 * total:
        rest: Dict[str, float] = {}
        for n, s, own in leaves:
            if not _holds(s, known):
                key = f"{trace_reduce.hlo_name(n)} [{s[-80:]}]"
                rest[key] = rest.get(key, 0.0) + own
        say(f"scopes cover {100 * under / total:.1f} % of the leaf "
            f"operations' time; the largest outside any: "
            + "; ".join(f"{k} {v / 1e6:.2f} ms" for k, v in sorted(
                rest.items(), key=lambda kv: -kv[1])[:8]))
    return leaves


def read(evidence: dict, params: dict):
    trace, window = evidence.get("trace"), evidence.get("trace_window")
    if trace is None or not trace.ops or window is None:
        return None
    # sorted by the program's own names; one from before it kept the
    # list is sorted by the names this metric asks for
    leaves = leaves_of(evidence,
                       program_scopes() or tuple(params["scopes"]))
    if not any(s for _n, s, _own in leaves):
        return None                      # this trace carries no scope
    own_s = sum(own for _n, s, own in leaves
                if _holds(s, params["scopes"])) / 1e9
    if params["stat"] == "share":
        busy = trace_reduce.busy_seconds(trace, window)
        return 100.0 * own_s / busy[min(busy)]
    if params["stat"] == "ms_per_run":
        # runs that END in the window, on the device the leaves are of: a
        # mesh's whole-period window closes at the latest device's last
        # start, a few microseconds after the others' (so counting starts
        # finds a run more than the window holds)
        runs = sum(1 for e in trace.modules.get(min(trace.modules), ())
                   if params["module"] in e.name
                   and window[0] < e.end <= window[1])
        return 1e3 * own_s / runs if runs else None
    raise ValueError(f"xplane_scope reader: unknown stat {params['stat']!r}")
