"""Device time by ALS stage: each device operation's self time totalled
under the outermost ``als.*`` name scope of its op name.

The program names its stages with ``jax.named_scope`` (``als.herm`` with
``als.gather`` inside it, ``als.solve``; ``repro.kernels.ops``).  XLA keeps
the scope path in each instruction's ``op_name`` metadata, which a TPU
trace carries as the ``tf_op`` stat of the event *metadata* of every ``XLA
Ops`` event, e.g. ``jit(fused_herm)/als.herm/als.gather/jit(_take)/and:``.
``jax.profiler.ProfileData`` hands out only the events' own stats, so this
module reads the ``.xplane.pb`` itself, with message classes built here
from the few fields of ``tsl/profiler/protobuf/xplane.proto`` it needs
(the parser skips the rest).  Operations with no ``als.*`` scope count
under :data:`UNSCOPED`.  Self time is
:func:`chipbench.trace_reduce.self_times` (a loop's time less its body's),
so a stage's total is the device time spent in its own operations.
"""
from __future__ import annotations

import functools

from chipbench import trace_reduce as tr

#: the ``XLA Ops`` event-metadata stat that holds the instruction's op name
OP_NAME_STAT = "tf_op"
SCOPE_PREFIX = "als."
UNSCOPED = "unscoped"

_I64, _U64, _STR = "TYPE_INT64", "TYPE_UINT64", "TYPE_STRING"
#: message -> [(field, number, scalar type or message name, repeated)]
_FIELDS = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, _STR, False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, _I64, False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, _I64, False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, _STR, False), ("timestamp_ns", 3, _I64, False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _I64, False), ("offset_ps", 2, _I64, False),
               ("duration_ps", 3, _I64, False)],
    "XEventMetadata": [("name", 2, _STR, False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, _STR, False)],
    "XStat": [("metadata_id", 1, _I64, False), ("str_value", 5, _STR, False),
              ("ref_value", 7, _U64, False)],
}


@functools.cache
def xplane_messages() -> dict:
    """``{name: message class}`` for the messages of :data:`_FIELDS`."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench_xplane")
    F = descriptor_pb2.FieldDescriptorProto
    for msg, fields in _FIELDS.items():
        m = fdp.message_type.add(name=msg)
        for name, number, typ, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if typ in (_I64, _U64, _STR):
                f.type = F.Type.Value(typ)
            else:
                f.type = F.TYPE_MESSAGE
                f.type_name = f".chipbench_xplane.{typ}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return {msg: message_factory.GetMessageClass(
                pool.FindMessageTypeByName(f"chipbench_xplane.{msg}"))
            for msg in _FIELDS}


def outer_scope(op_name: str) -> str:
    """The first ``als.*`` component of a ``/``-separated op name."""
    for part in op_name.split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return UNSCOPED


def device_ops(data: bytes) -> dict:
    """``{plane: [(start_ns, end_ns, op name)]}`` of the ``XLA Ops``
    events of each device plane of a serialized XSpace (the op name ""
    where the event's metadata has no :data:`OP_NAME_STAT`)."""
    space = xplane_messages()["XSpace"]()
    space.ParseFromString(data)
    out = {}
    for p in space.planes:
        if not p.name.startswith(tr.DEVICE_PLANE):
            continue
        stat_names = {e.key: e.value.name for e in p.stat_metadata}
        names = {}
        for e in p.event_metadata:
            for s in e.value.stats:
                if stat_names.get(s.metadata_id) == OP_NAME_STAT:
                    names[e.key] = (s.str_value
                                    or stat_names.get(s.ref_value, ""))
        out[p.name] = [
            (line.timestamp_ns + ev.offset_ps * 1e-3,
             line.timestamp_ns + (ev.offset_ps + ev.duration_ps) * 1e-3,
             names.get(ev.metadata_id, ""))
            for line in p.lines if line.name == tr.OPS_LINE
            for ev in line.events]
    return out


def scope_seconds(ops_by_plane: dict, window: tr.Interval) -> dict:
    """``{stage: seconds}`` of device self time inside ``window`` (ns),
    averaged over the planes of :func:`device_ops`."""
    lo, hi = window
    if not ops_by_plane or hi <= lo:
        raise ValueError("no device plane or an empty window")
    out: dict = {}
    for ops in ops_by_plane.values():
        scoped = [(max(a, lo), min(b, hi), outer_scope(name))
                  for a, b, name in ops if b > lo and a < hi]
        for name, t in tr.self_times(scoped).items():
            out[name] = out.get(name, 0.0) + t
    return {k: v * tr.NS / len(ops_by_plane) for k, v in out.items()}
