"""Read a profiler's ``.xplane.pb`` without materializing event names.

The XSpace schema (``tsl/profiler/protobuf/xplane.proto``) is declared
here field by field, so the protobuf runtime parses the file in native
code and a reader walks the events as integers: an event names its
metadata by id, and a plane's metadata table holds each name once. A
device trace of one BERT-base request holds millions of events, each
named by a whole HLO instruction; reading it through an API that builds
every event's name string costs minutes.
"""

from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_I64, _U64, _F64 = _F.TYPE_INT64, _F.TYPE_UINT64, _F.TYPE_DOUBLE
_STR, _BYTES, _MSG = _F.TYPE_STRING, _F.TYPE_BYTES, _F.TYPE_MESSAGE
_ONE, _REP = _F.LABEL_OPTIONAL, _F.LABEL_REPEATED
_PKG = "benchxplane"

#: message -> [(field, number, type, label, message type or None)]
_SCHEMA = {
    "XSpace": [("planes", 1, _MSG, _REP, "XPlane"),
               ("errors", 2, _STR, _REP, None),
               ("warnings", 3, _STR, _REP, None),
               ("hostnames", 4, _STR, _REP, None)],
    "XPlane": [("id", 1, _I64, _ONE, None),
               ("name", 2, _STR, _ONE, None),
               ("lines", 3, _MSG, _REP, "XLine"),
               ("event_metadata", 4, _MSG, _REP,
                "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, _MSG, _REP, "XPlane.StatMetadataEntry"),
               ("stats", 6, _MSG, _REP, "XStat")],
    "XLine": [("id", 1, _I64, _ONE, None),
              ("display_id", 10, _I64, _ONE, None),
              ("name", 2, _STR, _ONE, None),
              ("display_name", 11, _STR, _ONE, None),
              ("timestamp_ns", 3, _I64, _ONE, None),
              ("duration_ps", 9, _I64, _ONE, None),
              ("events", 4, _MSG, _REP, "XEvent")],
    "XEvent": [("metadata_id", 1, _I64, _ONE, None),
               ("offset_ps", 2, _I64, _ONE, None),
               ("num_occurrences", 5, _I64, _ONE, None),
               ("duration_ps", 3, _I64, _ONE, None),
               ("stats", 4, _MSG, _REP, "XStat")],
    "XStat": [("metadata_id", 1, _I64, _ONE, None),
              ("double_value", 2, _F64, _ONE, None),
              ("uint64_value", 3, _U64, _ONE, None),
              ("int64_value", 4, _I64, _ONE, None),
              ("str_value", 5, _STR, _ONE, None),
              ("bytes_value", 6, _BYTES, _ONE, None),
              ("ref_value", 7, _U64, _ONE, None)],
    "XEventMetadata": [("id", 1, _I64, _ONE, None),
                       ("name", 2, _STR, _ONE, None),
                       ("display_name", 4, _STR, _ONE, None),
                       ("metadata", 3, _BYTES, _ONE, None),
                       ("stats", 5, _MSG, _REP, "XStat"),
                       ("child_id", 6, _I64, _REP, None)],
    "XStatMetadata": [("id", 1, _I64, _ONE, None),
                      ("name", 2, _STR, _ONE, None),
                      ("description", 3, _STR, _ONE, None)],
}
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}


def _message_class():
    fd = descriptor_pb2.FileDescriptorProto(
        name=f"{_PKG}/xplane.proto", package=_PKG, syntax="proto3")
    for name, fields in _SCHEMA.items():
        msg = fd.message_type.add(name=name)
        for fname, num, ftype, label, tname in fields:
            f = msg.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                f.type_name = f".{_PKG}.{tname}"
        if name == "XPlane":
            for entry, value in _MAPS.items():
                e = msg.nested_type.add(name=entry)
                e.options.map_entry = True
                e.field.add(name="key", number=1, type=_I64, label=_ONE)
                e.field.add(name="value", number=2, type=_MSG, label=_ONE,
                            type_name=f".{_PKG}.{value}")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PKG}.XSpace"))


def read_space(path: str):
    """The parsed XSpace message of an ``.xplane.pb`` file."""
    space = _message_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def line_events(line):
    """``(metadata_id, start_ns, duration_ns)`` of each event of a line."""
    base = line.timestamp_ns
    return [(ev.metadata_id, base + ev.offset_ps / 1000.0,
             ev.duration_ps / 1000.0) for ev in line.events]
