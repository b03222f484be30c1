"""Reading a compiled program's collectives against the mesh it runs on.

Not on any step's path: a tool for tests and for the tables in PERF.md.
GSPMD decides which collectives a program has, so the only place to see
them is the partitioned HLO (`jitted.lower(...).compile().as_text()`).
There a collective names its devices by their position in the program's
device assignment, which for a program placed by `NamedSharding`s of one
mesh is the position in `mesh.devices.flat`: a group whose members differ
in their `dp` coordinate alone is a collective over `dp`.
"""

from __future__ import annotations

import math
import re
from typing import List, Tuple

import numpy as np

OPS = ("all-gather", "all-reduce", "all-to-all", "reduce-scatter",
       "collective-permute", "collective-broadcast", "ragged-all-to-all")

# `%name = <result> <op>(`, the result one array or a tuple of them; an
# asynchronous pair prints as <op>-start / <op>-done and the start has the groups
_LINE = re.compile(
    r"=\s*(?P<result>\(.*?\)|\S+)\s+"
    r"(?P<op>" + "|".join(OPS) + r")(?P<start>-start)?\(")
_ARRAY = re.compile(r"\b([a-z]+[0-9]+[a-z0-9]*|pred)\[([0-9,]*)\]")
_LISTED = re.compile(r"(?:replica_groups|source_target_pairs)=\{([0-9,{} ]*)\}")
_IOTA = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_CHANNEL = re.compile(r"channel_id=(\d+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# computations that are a fusion's body: a collective printed inside one is
# a piece of an asynchronous collective, never an op the program waits at
_FUSIONS = ("fused_computation", "async_collective_fusion")


def _ints(text: str) -> List[int]:
    return [int(t) for t in text.split(",") if t.strip()]


def _groups(line: str, n_devices: int) -> List[List[int]]:
    """The device groups a collective's line names, as lists of positions
    (of a permute: each source with its target)."""
    m = _IOTA.search(line)
    if m:       # [groups,size]<=[dims]T(perm): an iota, reshaped and permuted
        ids = np.arange(n_devices).reshape(_ints(m.group(3)))
        if m.group(4):
            ids = ids.transpose(_ints(m.group(4)))
        return ids.reshape(int(m.group(1)), int(m.group(2))).tolist()
    m = _LISTED.search(line)
    if m and m.group(1).strip():
        return [_ints(g) for g in re.findall(r"\{([0-9, ]*)\}", m.group(1))]
    return [list(range(n_devices))]     # no groups written: every device


def _axes(groups: List[List[int]], mesh) -> Tuple[str, ...]:
    """The mesh axes along which the members of a group differ."""
    shape = tuple(mesh.devices.shape)
    varies = np.zeros(len(shape), bool)
    for group in groups:
        coords = np.array(np.unravel_index(group, shape))
        varies |= (coords != coords[:, :1]).any(axis=1)
    return tuple(n for n, v in zip(mesh.axis_names, varies) if v)


def _walk(compiled_text: str, mesh):
    """(op, mesh axes, [(dtype, shape)], phase, form) of every collective
    in the text, once each."""
    n = int(mesh.devices.size)
    found, where = [], {}
    fused = False       # inside a fusion's computation, not the program's
    for line in compiled_text.splitlines():
        if line[:1] not in " }":            # a computation's header
            fused = line.lstrip("%").startswith(_FUSIONS)
            continue
        m = _LINE.search(line)
        if not m:
            continue
        # the TPU compiler prints one collective once in each computation of
        # its asynchronous form (start, steps, done): same channel, one op
        channel = _CHANNEL.search(line)
        key = channel.group(1) if channel else len(found)
        asynchronous = bool(m.group("start")) or fused
        if key in where:
            if asynchronous:
                found[where[key]][4] = "async"
            continue
        where[key] = len(found)
        arrays = [(d, tuple(_ints(s))) for d, s in _ARRAY.findall(
            m.group("result"))]
        if m.group("start") and m.group("op") != "all-reduce":
            # a start yields (operands..., results..., context scalars)
            arrays = [a for a in arrays if a[1] or a[0] not in ("u32", "s32")]
            arrays = arrays[len(arrays) // 2:]
        name = _OP_NAME.search(line)
        phase = "" if not name else (
            "backward" if "transpose(" in name.group(1) else
            "forward" if "jvp(" in name.group(1) else "")
        found.append([m.group("op"), _axes(_groups(line, n), mesh), arrays,
                      phase, "async" if asynchronous else "sync"])
    return found


def collectives(compiled_text: str, mesh):
    """[(op, mesh axes, dtype, shape)] for every collective in the text of a
    compiled (partitioned) program, one entry per array it yields.

    `mesh axes` is the tuple of `mesh.axis_names` the collective spans, `()`
    for one whose groups hold a single device; `shape` is the per-device
    shape of the result (of an async pair, the result its `-done` hands on).
    """
    return [(op, axes, dtype, shape)
            for op, axes, arrays, _, _ in _walk(compiled_text, mesh)
            for dtype, shape in arrays]


def collective_forms(compiled_text: str, mesh):
    """[(op, mesh axes, phase, form, bytes)], one entry per collective OP
    (a tuple all-reduce of forty gradients is one).

    `form` is "async" where the program can compute beside the collective:
    a `-start` / `-done` pair, or, as the TPU compiler writes an all-reduce
    it overlaps, a collective inside fusions' computations (the start's, the
    done's, and an `async_collective_fusion` for each product it runs
    under); "sync" for a plain op of the program itself, which the chip
    waits for. `phase` is read from the op's `op_name`: "backward" under a
    `transpose(jvp(..))`, "forward" under a `jvp(..)`, "" elsewhere.
    `bytes` is the per-device size of what it yields.
    """
    def nbytes(dtype, shape):       # bf16, f32, f8e4m3fn; pred has no digits
        bits = re.search(r"\d+", dtype)
        return math.prod(shape) * (int(bits.group()) if bits else 8) // 8

    return [(op, axes, phase, form, sum(nbytes(*a) for a in arrays))
            for op, axes, arrays, phase, form in _walk(compiled_text, mesh)]
