"""The XLA op metadata of a profiler trace, read with ``google.protobuf``.

``jax.profiler.ProfileData`` gives each event its metadata's name, but
not the metadata's stats.  On a TPU the metadata of an ``XLA Ops`` event
holds the stat ``tf_op``: the JAX name-scope path of the operation, e.g.
``jit(f)/while/body/mcts_playout/vmap(vmap())/while/body/go_flood_fill/
gather``.  This module reads those stats from the ``.xplane.pb`` file
through a minimal copy of the XSpace schema, built at run time
(``tsl/profiler/protobuf/xplane.proto``: only the fields read here, under
their own numbers; every other field is skipped as unknown).
"""
from __future__ import annotations

from typing import Dict

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
# message -> [(field, number, type, label, message type)]
_SCHEMA = {
    "XSpace": [("planes", 1, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XPlane")],
    "XPlane": [
        ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
        ("event_metadata", 4, _F.TYPE_MESSAGE, _F.LABEL_REPEATED,
         "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, _F.TYPE_MESSAGE, _F.LABEL_REPEATED,
         "XPlane.StatMetadataEntry")],
    "XEventMetadata": [
        ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
        ("stats", 5, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XStat")],
    "XStatMetadata": [("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None)],
    "XStat": [
        ("metadata_id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("str_value", 5, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
        ("ref_value", 7, _F.TYPE_UINT64, _F.LABEL_OPTIONAL, None)],
}
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}
_PACKAGE = "benchmarks_chip_xspace"


def _field(msg, name, number, ftype, label, type_name):
    f = msg.field.add(name=name, number=number, type=ftype, label=label)
    if type_name:
        f.type_name = f".{_PACKAGE}.{type_name}"


def _space_class():
    fd = descriptor_pb2.FileDescriptorProto(
        name=f"{_PACKAGE}.proto", package=_PACKAGE, syntax="proto3")
    for name, fields in _SCHEMA.items():
        msg = fd.message_type.add(name=name)
        for f in fields:
            _field(msg, *f)
        if name == "XPlane":
            for entry, value in _MAPS.items():
                e = msg.nested_type.add(name=entry)
                e.options.map_entry = True
                _field(e, "key", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None)
                _field(e, "value", 2, _F.TYPE_MESSAGE, _F.LABEL_OPTIONAL,
                       value)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


XSpace = _space_class()


def op_paths(data: bytes, stat: str = "tf_op") -> Dict[str, Dict[str, str]]:
    """``{plane name: {event metadata name: value of stat}}`` over the
    device planes of a serialized XSpace.

    A stat holds a string either itself or as a reference to the stat
    metadata whose name is the string.  Metadata without the stat are left
    out.
    """
    space = XSpace.FromString(data)
    out: Dict[str, Dict[str, str]] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        ids = [k for k, m in plane.stat_metadata.items() if m.name == stat]
        if not ids:
            continue
        want = ids[0]
        names = {k: m.name for k, m in plane.stat_metadata.items()}
        paths = out.setdefault(plane.name, {})
        for meta in plane.event_metadata.values():
            for s in meta.stats:
                if s.metadata_id == want:
                    paths[meta.name] = (s.str_value if s.str_value
                                        else names.get(s.ref_value, ""))
                    break
    return out
