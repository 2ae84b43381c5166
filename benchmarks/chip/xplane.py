"""The profiler's ``XSpace`` protobuf (``tsl/profiler/protobuf/xplane.proto``),
read without TensorFlow: the messages the trace reduction reads, built
in a descriptor pool of their own, so they clash with no other copy of
the schema in the process.  Fields are given by their numbers in that
file; a field left out here is skipped when a trace is parsed.
"""
from __future__ import annotations

from functools import lru_cache

# (field, number, type, repeated, message type, oneof)
_FIELDS = {
    "XSpace": [("planes", 1, "message", True, "XPlane", None)],
    "XPlane": [("id", 1, "int64", False, None, None),
               ("name", 2, "string", False, None, None),
               ("lines", 3, "message", True, "XLine", None),
               ("event_metadata", 4, "message", True,
                "XPlane.EventMetadataEntry", None),
               ("stat_metadata", 5, "message", True,
                "XPlane.StatMetadataEntry", None),
               ("stats", 6, "message", True, "XStat", None)],
    "XLine": [("id", 1, "int64", False, None, None),
              ("display_id", 10, "int64", False, None, None),
              ("name", 2, "string", False, None, None),
              ("display_name", 11, "string", False, None, None),
              ("timestamp_ns", 3, "int64", False, None, None),
              ("duration_ps", 9, "int64", False, None, None),
              ("events", 4, "message", True, "XEvent", None)],
    "XEvent": [("metadata_id", 1, "int64", False, None, None),
               ("offset_ps", 2, "int64", False, None, "data"),
               ("num_occurrences", 5, "int64", False, None, "data"),
               ("duration_ps", 3, "int64", False, None, None),
               ("stats", 4, "message", True, "XStat", None)],
    "XStat": [("metadata_id", 1, "int64", False, None, None),
              ("double_value", 2, "double", False, None, "value"),
              ("uint64_value", 3, "uint64", False, None, "value"),
              ("int64_value", 4, "int64", False, None, "value"),
              ("str_value", 5, "string", False, None, "value"),
              ("bytes_value", 6, "bytes", False, None, "value"),
              ("ref_value", 7, "uint64", False, None, "value")],
    "XEventMetadata": [("id", 1, "int64", False, None, None),
                       ("name", 2, "string", False, None, None),
                       ("display_name", 4, "string", False, None, None),
                       ("metadata", 3, "bytes", False, None, None),
                       ("stats", 5, "message", True, "XStat", None),
                       ("child_id", 6, "int64", True, None, None)],
    "XStatMetadata": [("id", 1, "int64", False, None, None),
                      ("name", 2, "string", False, None, None),
                      ("description", 3, "string", False, None, None)],
}
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}
PACKAGE = "benchmarks.chip.xplane"


@lru_cache(maxsize=None)
def messages() -> dict:
    """``{name: message class}`` of the schema above."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    types = {"message": F.TYPE_MESSAGE, "string": F.TYPE_STRING,
             "bytes": F.TYPE_BYTES, "int64": F.TYPE_INT64,
             "uint64": F.TYPE_UINT64, "double": F.TYPE_DOUBLE}
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmarks/chip/xplane.proto", package=PACKAGE, syntax="proto3")

    def add_field(msg, name, number, kind, repeated, type_name, oneof):
        f = msg.field.add(name=name, number=number, type=types[kind],
                          label=F.LABEL_REPEATED if repeated
                          else F.LABEL_OPTIONAL)
        if type_name:
            f.type_name = f".{PACKAGE}.{type_name}"
        if oneof:
            names = [o.name for o in msg.oneof_decl]
            if oneof not in names:
                msg.oneof_decl.add(name=oneof)
                names.append(oneof)
            f.oneof_index = names.index(oneof)

    for name, fields in _FIELDS.items():
        msg = fd.message_type.add(name=name)
        for field in fields:
            add_field(msg, *field)
        if name == "XPlane":
            for entry, value in _MAPS.items():
                m = msg.nested_type.add(name=entry)
                m.options.map_entry = True
                add_field(m, "key", 1, "int64", False, None, None)
                add_field(m, "value", 2, "message", False, value, None)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{PACKAGE}.{name}")) for name in _FIELDS}


def read_space(path: str):
    """The ``XSpace`` in the file at ``path``."""
    with open(path, "rb") as f:
        return messages()["XSpace"].FromString(f.read())


def stat_value(stat, stat_names: dict):
    """The value of one ``XStat``; a reference resolves to the name it
    refers to."""
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return stat_names.get(stat.ref_value)
    return getattr(stat, kind) if kind else None
