"""What `jax.profiler.ProfileData` does not show of an `.xplane.pb`: the
stats of a device event's METADATA.

On a TPU the device plane (`/device:TPU:<n>`) has one `XEventMetadata` per
HLO instruction, and its stats hold what XLA knew of the instruction:
`tf_op` (the JAX name stack with every `jax.named_scope`, e.g.
`jit(grow)/while/body/partition/while/body/select_n:`), `source`
(file:line), `program_id`, `hlo_category`, `bytes_accessed`, `flops`.
`ProfileData`'s `event.stats` walks the event's own stats only, so
`perfbench/trace.py` reads an empty scope.  This module reads the file's
protobuf wire format itself (varints and length-delimited fields; no
dependency) and returns, for each device plane, instruction ->
`Meta(scope, source, program_id)`, and joins it to `trace.Op`.

Field numbers (tensorflow/tsl/profiler/protobuf/xplane.proto):
`XSpace.planes=1`; `XPlane.name=2, event_metadata=4, stat_metadata=5` (maps:
entry key=1, value=2); `XEventMetadata.name=2, display_name=4, stats=5`;
`XStatMetadata.name=2`; `XStat.metadata_id=1, uint64_value=3, int64_value=4,
str_value=5, ref_value=7` (a `ref_value` names a stat metadata whose name is
the string).  A module event of the line `XLA Modules` is named
`<program>(<program_id>)`, so the id tells same-named programs apart.
"""
from __future__ import annotations

import gzip
import re
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

from .trace import DEVICE_PLANE, INSTRUCTION

MODULE = re.compile(r"^(.*)\((\d+)\)$")


class Meta(NamedTuple):
    scope: str          # the JAX name stack (stat `tf_op`), "" when absent
    source: str         # file:line as XLA states it, "" when absent
    program_id: int     # 0 when absent


# ------------------------------------------------------------- wire format
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, bytes for
    a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            length, i = _varint(buf, i)
            value = buf[i:i + length]
            i += length
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value = buf[i:i + width]
            i += width
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield number, value


def _entry(buf: bytes) -> Tuple[int, bytes]:
    """(key, value) of one entry of a map<int64, message>."""
    key, value = 0, b""
    for number, v in fields(buf):
        if number == 1:
            key = int(v)
        elif number == 2:
            value = bytes(v)
    return key, value


def _text(value: object) -> str:
    return value.decode("utf-8", "replace") if isinstance(value, bytes) \
        else str(value)


# ------------------------------------------------------------------ reading
def read_bytes(path: str) -> bytes:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def _plane(buf: bytes):
    """(name, {metadata id: XEventMetadata bytes}, {stat id: name})."""
    name, events, stats = "", {}, {}
    for number, value in fields(buf):
        if number == 2:
            name = _text(value)
        elif number == 4:
            key, body = _entry(value)
            events[key] = body
        elif number == 5:
            key, body = _entry(value)
            stats[key] = next((_text(v) for n, v in fields(body) if n == 2),
                              "")
    return name, events, stats


def _event_metadata(buf: bytes, stat_names: Dict[int, str]):
    """(instruction or module name, its stats by name)."""
    name = display = ""
    stats: Dict[str, object] = {}
    for number, value in fields(buf):
        if number == 2:
            name = _text(value)
        elif number == 4:
            display = _text(value)
        elif number == 5:
            key, val = "", None
            for n, v in fields(value):
                if n == 1:
                    key = stat_names.get(int(v), "")
                elif n == 7:
                    val = stat_names.get(int(v), "")
                elif n in (3, 4, 5):
                    val = v
            if key:
                stats[key] = val
    return name, display, stats


def device_meta(path: str) -> Dict[int, Dict[Tuple[str, str], Meta]]:
    """device -> (program, instruction) -> Meta.  `program` is the jitted
    program's name as `trace.Op.program` has it (fingerprint cut off).
    Where two programs of one name disagree on an instruction of one name
    its scope is left empty: it cannot be told which of them ran."""
    out: Dict[int, Dict[Tuple[str, str], Meta]] = {}
    for number, value in fields(read_bytes(path)):
        if number != 1:
            continue
        name, events, stat_names = _plane(bytes(value))
        m = DEVICE_PLANE.match(name)
        if not m:
            continue
        programs: Dict[int, str] = {}
        rows: List[Tuple[str, Meta]] = []
        for body in events.values():
            text, display, stats = _event_metadata(body, stat_names)
            mod = MODULE.match(text)
            if mod and not stats:
                programs[int(mod.group(2))] = mod.group(1)
                continue
            mi = INSTRUCTION.match(text)
            instr = display or (mi.group(1) if mi else text)
            pid = stats.get("program_id")
            rows.append((instr, Meta(
                _text(stats.get("tf_op") or ""),
                _text(stats.get("source") or ""),
                int(pid) if isinstance(pid, int) else 0)))
        table: Dict[Tuple[str, str], Meta] = {}
        for instr, meta in rows:
            key = (programs.get(meta.program_id, ""), instr)
            seen = table.get(key)
            if seen is not None and seen.scope != meta.scope:
                meta = Meta("", meta.source, 0)
            table[key] = meta
        out[int(m.group(2))] = table
    return out


def scope_of(table: Dict[int, Dict[Tuple[str, str], Meta]], op
             ) -> Optional[Meta]:
    """The metadata of a `trace.Op`, or None where the file has none."""
    return table.get(op.device, {}).get((op.program, op.name))
