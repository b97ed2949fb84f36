"""The profiler's trace format, ``XSpace``
(``tsl/profiler/protobuf/xplane.proto``), read for the per-op metadata
that ``jax.profiler.ProfileData`` does not expose: each op's ``tf_op``
(its named-scope path) and ``program_id``.

``_DESCRIPTOR`` is protoc's serialized descriptor of that file, as the
generated ``xplane_pb2`` module of TensorFlow's TSL carries it (Apache
License 2.0, Copyright The TensorFlow Authors).  The message classes are
built in a descriptor pool of their own, so that no other copy of the
file loaded in the process can clash with them, and TensorFlow is never
imported.
"""
from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_DESCRIPTOR = (
    b'\n"tsl/profiler/protobuf/xplane.proto\x12\x13tensorflow.profiler"j'
    b'\n\x06XSpace\x12+\n\x06planes\x18\x01 \x03(\x0b2\x1b.tensorflow.pro'
    b'filer.XPlane\x12\x0e\n\x06errors\x18\x02 \x03(\t\x12\x10\n\x08warni'
    b'ngs\x18\x03 \x03(\t\x12\x11\n\thostnames\x18\x04 \x03(\t"\xba\x03\n'
    b'\x06XPlane\x12\n\n\x02id\x18\x01 \x01(\x03\x12\x0c\n\x04name\x18'
    b'\x02 \x01(\t\x12)\n\x05lines\x18\x03 \x03(\x0b2\x1a.tensorflow.prof'
    b'iler.XLine\x12F\n\x0eevent_metadata\x18\x04 \x03(\x0b2..tensorflow.'
    b'profiler.XPlane.EventMetadataEntry\x12D\n\rstat_metadata\x18\x05 '
    b'\x03(\x0b2-.tensorflow.profiler.XPlane.StatMetadataEntry\x12)\n\x05'
    b'stats\x18\x06 \x03(\x0b2\x1a.tensorflow.profiler.XStat\x1aY\n\x12Ev'
    b'entMetadataEntry\x12\x0b\n\x03key\x18\x01 \x01(\x03\x122\n\x05value'
    b'\x18\x02 \x01(\x0b2#.tensorflow.profiler.XEventMetadata:\x028\x01'
    b'\x1aW\n\x11StatMetadataEntry\x12\x0b\n\x03key\x18\x01 \x01(\x03\x12'
    b'1\n\x05value\x18\x02 \x01(\x0b2".tensorflow.profiler.XStatMetadata:'
    b'\x028\x01"\xbb\x01\n\x05XLine\x12\n\n\x02id\x18\x01 \x01(\x03\x12'
    b'\x12\n\ndisplay_id\x18\n \x01(\x03\x12\x0c\n\x04name\x18\x02 \x01('
    b'\t\x12\x14\n\x0cdisplay_name\x18\x0b \x01(\t\x12\x14\n\x0ctimestamp'
    b'_ns\x18\x03 \x01(\x03\x12\x13\n\x0bduration_ps\x18\t \x01(\x03\x12+'
    b'\n\x06events\x18\x04 \x03(\x0b2\x1b.tensorflow.profiler.XEventJ\x04'
    b'\x08\x05\x10\x06J\x04\x08\x06\x10\x07J\x04\x08\x07\x10\x08J\x04\x08'
    b'\x08\x10\t"\x95\x01\n\x06XEvent\x12\x13\n\x0bmetadata_id\x18\x01 '
    b'\x01(\x03\x12\x13\n\toffset_ps\x18\x02 \x01(\x03H\x00\x12\x19\n\x0f'
    b'num_occurrences\x18\x05 \x01(\x03H\x00\x12\x13\n\x0bduration_ps\x18'
    b'\x03 \x01(\x03\x12)\n\x05stats\x18\x04 \x03(\x0b2\x1a.tensorflow.pr'
    b'ofiler.XStatB\x06\n\x04data"\xad\x01\n\x05XStat\x12\x13\n\x0bmetada'
    b'ta_id\x18\x01 \x01(\x03\x12\x16\n\x0cdouble_value\x18\x02 \x01(\x01'
    b'H\x00\x12\x16\n\x0cuint64_value\x18\x03 \x01(\x04H\x00\x12\x15\n'
    b'\x0bint64_value\x18\x04 \x01(\x03H\x00\x12\x13\n\tstr_value\x18\x05'
    b' \x01(\tH\x00\x12\x15\n\x0bbytes_value\x18\x06 \x01(\x0cH\x00\x12'
    b'\x13\n\tref_value\x18\x07 \x01(\x04H\x00B\x07\n\x05value"\x8f\x01\n'
    b'\x0eXEventMetadata\x12\n\n\x02id\x18\x01 \x01(\x03\x12\x0c\n\x04nam'
    b'e\x18\x02 \x01(\t\x12\x14\n\x0cdisplay_name\x18\x04 \x01(\t\x12\x10'
    b'\n\x08metadata\x18\x03 \x01(\x0c\x12)\n\x05stats\x18\x05 \x03(\x0b2'
    b'\x1a.tensorflow.profiler.XStat\x12\x10\n\x08child_id\x18\x06 \x03('
    b'\x03">\n\rXStatMetadata\x12\n\n\x02id\x18\x01 \x01(\x03\x12\x0c\n'
    b'\x04name\x18\x02 \x01(\t\x12\x13\n\x0bdescription\x18\x03 \x01(\tB'
    b'\x03\xf8\x01\x01b\x06proto3')


def _message(name: str):
    pool = descriptor_pool.DescriptorPool()
    pool.Add(descriptor_pb2.FileDescriptorProto.FromString(_DESCRIPTOR))
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"tensorflow.profiler.{name}"))


XSpace = _message("XSpace")


def read(path: str):
    """The ``XSpace`` in an ``.xplane.pb`` file."""
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space
