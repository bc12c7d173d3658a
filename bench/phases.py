"""Device time by the program's named phases.

The program names the phases of its round with ``jax.named_scope``: each
becomes one component of the ``op_name`` path of every op compiled inside
it, backward and rematerialised ones included
(``jit(round_step)/while/body/closed_call/fed.client.sgd/transpose(jvp())/
.../checkpoint/model.attn/dot_general``). A transformation wraps the
component it meets first (``vmap(fed.client.sgd)``).

A v5e trace keeps the path in the ``tf_op`` stat of each op's event
metadata, which ``jax.profiler.ProfileData`` (and so ``trace.load``) does not
read: ``op_names`` reads it from the ``.xplane.pb`` itself, as a map from
instruction name (``Op.name``) to path, per device plane. An op belongs to
the innermost ``fed.*`` phase among its path's components, and to a
``model.*`` sub-phase if one is among them too; an op under no ``fed.*``
phase is ``UNPHASED``.
"""
from __future__ import annotations

import functools
import re
from pathlib import Path

from bench import trace

#: the round's phases; they do not nest in each other
FED = ("fed.client.sgd", "fed.client.flatten", "fed.client.encode",
       "fed.server.fold", "fed.server.psum", "fed.server.apply")
#: sub-phases of the client step
MODEL = ("model.attn",)
UNPHASED = "unphased"

_WRAPPED = re.compile(r"[\w\-]+\((.*)\)")


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a protobuf message: an int, or the bytes of
    a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")
        yield key >> 3, val


def op_names(path) -> dict:
    """Device plane -> {instruction name: ``op_name`` path} from the op
    metadata of an ``.xplane.pb`` (XSpace.planes: name 2, event_metadata 4,
    stat_metadata 5; XEventMetadata: name 2, stats 5; XStat: metadata_id 1,
    str_value 5). A name two programs of the plane give different paths
    maps to None."""
    out = {}
    for field, plane in _fields(memoryview(Path(path).read_bytes())):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.extend(val for k, val in _fields(v) if k == 2)
            elif f == 5:
                md = dict(_fields(dict(_fields(v))[2]))
                stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        if not name.startswith(trace.DEVICE_PREFIX):
            continue
        names = out.setdefault(name, {})
        for ev in events:
            op, path_ = None, None
            for f, v in _fields(ev):
                if f == 2:
                    op = bytes(v).decode().split(" = ")[0]
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == "tf_op" and 5 in stat:
                        path_ = bytes(stat[5]).decode().rstrip(":")
            if op and path_:
                names[op] = path_ if names.get(op, path_) == path_ else None
    return out


def _unwrap(component: str) -> str:
    """``transpose(jvp(model.attn))`` -> ``model.attn``."""
    while (m := _WRAPPED.fullmatch(component)):
        component = m.group(1)
    return component


def phase_of(op: trace.Op, names: dict) -> tuple:
    """-> (innermost ``fed.*`` phase or None, innermost ``model.*`` sub-phase
    or None) of an op, from its ``op_name`` path in ``names`` (one plane's
    map of ``op_names``)."""
    return _phases(names.get(op.name) or "")


#: a loop's ops recur once per iteration under the same path
@functools.lru_cache(maxsize=1 << 16)
def _phases(path: str) -> tuple:
    fed = model = None
    for c in path.split("/"):
        c = _unwrap(c)
        if c in FED:
            fed = c
        elif c in MODEL:
            model = c
    return fed, model


def phase_seconds(tr: trace.Trace, phase: str, win: tuple,
                  names: dict) -> dict:
    """Device plane -> seconds of the leaf ops of ``phase`` (a ``fed.*``
    phase, a ``model.*`` sub-phase or ``UNPHASED``), within win, clipped as
    ``trace.op_seconds`` clips; ``names`` is ``op_names``' map."""
    slot, want = ((1, phase) if phase in MODEL else
                  (0, None if phase == UNPHASED else phase))
    return {d: trace.op_seconds(
                trace.Trace({d: ops}, tr.host),
                lambda o: phase_of(o, names.get(d, {}))[slot] == want,
                win)[d]
            for d, ops in tr.ops.items()}
