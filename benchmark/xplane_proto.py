"""Read an ``.xplane.pb`` whole.  ``jax.profiler.ProfileData`` shows an
event's own stats only; the scope path of a device operation (its HLO
``op_name``: ``jit(f)/omni.attn/...``) is a stat of the event's
*metadata*, which it does not show.  So the few messages of the profiler's
``xplane.proto`` are declared here (field numbers as in the OpenXLA source,
tsl/profiler/protobuf/xplane.proto) and parsed with ``google.protobuf``,
which JAX's own installation brings; nothing of TensorFlow is imported.
"""

from __future__ import annotations

import functools
from pathlib import Path

_INT64, _UINT64, _DOUBLE, _STRING, _BYTES, _MESSAGE = 3, 4, 1, 9, 12, 11

#: message -> [(field, number, type, repeated, message type)]
_SCHEMA = {
    "XStat": [("metadata_id", 1, _INT64, False, None),
              ("double_value", 2, _DOUBLE, False, None),
              ("uint64_value", 3, _UINT64, False, None),
              ("int64_value", 4, _INT64, False, None),
              ("str_value", 5, _STRING, False, None),
              ("bytes_value", 6, _BYTES, False, None),
              ("ref_value", 7, _UINT64, False, None)],
    "XEvent": [("metadata_id", 1, _INT64, False, None),
               ("offset_ps", 2, _INT64, False, None),
               ("duration_ps", 3, _INT64, False, None),
               ("stats", 4, _MESSAGE, True, "XStat"),
               ("num_occurrences", 5, _INT64, False, None)],
    "XLine": [("id", 1, _INT64, False, None),
              ("name", 2, _STRING, False, None),
              ("timestamp_ns", 3, _INT64, False, None),
              ("events", 4, _MESSAGE, True, "XEvent"),
              ("duration_ps", 9, _INT64, False, None),
              ("display_id", 10, _INT64, False, None),
              ("display_name", 11, _STRING, False, None)],
    "XEventMetadata": [("id", 1, _INT64, False, None),
                       ("name", 2, _STRING, False, None),
                       ("metadata", 3, _BYTES, False, None),
                       ("display_name", 4, _STRING, False, None),
                       ("stats", 5, _MESSAGE, True, "XStat"),
                       ("child_id", 6, _INT64, True, None)],
    "XStatMetadata": [("id", 1, _INT64, False, None),
                      ("name", 2, _STRING, False, None),
                      ("description", 3, _STRING, False, None)],
    # a map<int64, M> on the wire is a repeated {key = 1, value = 2}
    "EventMetadataEntry": [("key", 1, _INT64, False, None),
                           ("value", 2, _MESSAGE, False, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, _INT64, False, None),
                          ("value", 2, _MESSAGE, False, "XStatMetadata")],
    "XPlane": [("id", 1, _INT64, False, None),
               ("name", 2, _STRING, False, None),
               ("lines", 3, _MESSAGE, True, "XLine"),
               ("event_metadata", 4, _MESSAGE, True, "EventMetadataEntry"),
               ("stat_metadata", 5, _MESSAGE, True, "StatMetadataEntry"),
               ("stats", 6, _MESSAGE, True, "XStat")],
    "XSpace": [("planes", 1, _MESSAGE, True, "XPlane")],
    # of xla/service/hlo.proto, what names an instruction and its scope
    "OpMetadata": [("op_type", 1, _STRING, False, None),
                   ("op_name", 2, _STRING, False, None)],
    "HloInstructionProto": [("name", 1, _STRING, False, None),
                            ("opcode", 2, _STRING, False, None),
                            ("metadata", 7, _MESSAGE, False, "OpMetadata")],
    "HloComputationProto": [("name", 1, _STRING, False, None),
                            ("instructions", 2, _MESSAGE, True,
                             "HloInstructionProto")],
    "HloModuleProto": [("name", 1, _STRING, False, None),
                       ("computations", 3, _MESSAGE, True,
                        "HloComputationProto"),
                       ("id", 5, _INT64, False, None)],
    "HloProto": [("hlo_module", 1, _MESSAGE, False, "HloModuleProto")],
}
_PACKAGE = "omni_benchmark_xplane"


@functools.cache
def _message_class(name: str):
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    file = descriptor_pb2.FileDescriptorProto(
        name=f"{_PACKAGE}.proto", package=_PACKAGE, syntax="proto3")
    for message, fields in _SCHEMA.items():
        m = file.message_type.add(name=message)
        for field, number, ftype, repeated, of in fields:
            f = m.field.add(name=field, number=number, type=ftype,
                            label=3 if repeated else 1)
            if of:
                f.type_name = f".{_PACKAGE}.{of}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.{name}"))


def read(path: Path):
    """The file as an ``XSpace`` message: ``planes``, each with ``lines``
    of ``events`` and its ``event_metadata`` / ``stat_metadata`` tables."""
    space = _message_class("XSpace")()
    space.ParseFromString(Path(path).read_bytes())
    return space


def op_names(hlo_proto: bytes) -> dict[str, str]:
    """{instruction name: its ``op_name``} of one serialized ``HloProto``
    (the ``Hlo Proto`` stat of a module in the ``/host:metadata`` plane:
    the module as it was compiled, fusions and all)."""
    hlo = _message_class("HloProto")()
    hlo.ParseFromString(hlo_proto)
    return {i.name: i.metadata.op_name
            for c in hlo.hlo_module.computations for i in c.instructions}


def stat_value(stat, stat_names: dict):
    """One stat's value; a ``ref_value`` names another entry of the
    plane's stat table (strings that repeat are stored once)."""
    if stat.str_value:
        return stat.str_value
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    if stat.bytes_value:
        return stat.bytes_value
    return stat.int64_value or stat.uint64_value or stat.double_value


def tables(plane) -> tuple[dict, dict]:
    """({event metadata id: its XEventMetadata}, {stat metadata id: name})
    of one plane."""
    return ({e.key: e.value for e in plane.event_metadata},
            {e.key: e.value.name for e in plane.stat_metadata})
