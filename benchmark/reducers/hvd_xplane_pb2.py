# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: hvd_xplane.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'\n\x10hvd_xplane.proto\x12\x10hvd_bench.xplane\"2\n\x06XSpace\x12(\n\x06planes\x18\x01 \x03(\x0b\x32\x18.hvd_bench.xplane.XPlane\"\xa8\x03\n\x06XPlane\x12\n\n\x02id\x18\x01 \x01(\x03\x12\x0c\n\x04name\x18\x02 \x01(\t\x12&\n\x05lines\x18\x03 \x03(\x0b\x32\x17.hvd_bench.xplane.XLine\x12\x43\n\x0e\x65vent_metadata\x18\x04 \x03(\x0b\x32+.hvd_bench.xplane.XPlane.EventMetadataEntry\x12\x41\n\rstat_metadata\x18\x05 \x03(\x0b\x32*.hvd_bench.xplane.XPlane.StatMetadataEntry\x12&\n\x05stats\x18\x06 \x03(\x0b\x32\x17.hvd_bench.xplane.XStat\x1aV\n\x12\x45ventMetadataEntry\x12\x0b\n\x03key\x18\x01 \x01(\x03\x12/\n\x05value\x18\x02 \x01(\x0b\x32 .hvd_bench.xplane.XEventMetadata:\x02\x38\x01\x1aT\n\x11StatMetadataEntry\x12\x0b\n\x03key\x18\x01 \x01(\x03\x12.\n\x05value\x18\x02 \x01(\x0b\x32\x1f.hvd_bench.xplane.XStatMetadata:\x02\x38\x01\"\xa0\x01\n\x05XLine\x12\n\n\x02id\x18\x01 \x01(\x03\x12\x12\n\ndisplay_id\x18\n \x01(\x03\x12\x0c\n\x04name\x18\x02 \x01(\t\x12\x14\n\x0c\x64isplay_name\x18\x0b \x01(\t\x12\x14\n\x0ctimestamp_ns\x18\x03 \x01(\x03\x12\x13\n\x0b\x64uration_ps\x18\t \x01(\x03\x12(\n\x06\x65vents\x18\x04 \x03(\x0b\x32\x18.hvd_bench.xplane.XEvent\"\x92\x01\n\x06XEvent\x12\x13\n\x0bmetadata_id\x18\x01 \x01(\x03\x12\x13\n\toffset_ps\x18\x02 \x01(\x03H\x00\x12\x19\n\x0fnum_occurrences\x18\x05 \x01(\x03H\x00\x12\x13\n\x0b\x64uration_ps\x18\x03 \x01(\x03\x12&\n\x05stats\x18\x04 \x03(\x0b\x32\x17.hvd_bench.xplane.XStatB\x06\n\x04\x64\x61ta\"\xad\x01\n\x05XStat\x12\x13\n\x0bmetadata_id\x18\x01 \x01(\x03\x12\x16\n\x0c\x64ouble_value\x18\x02 \x01(\x01H\x00\x12\x16\n\x0cuint64_value\x18\x03 \x01(\x04H\x00\x12\x15\n\x0bint64_value\x18\x04 \x01(\x03H\x00\x12\x13\n\tstr_value\x18\x05 \x01(\tH\x00\x12\x15\n\x0b\x62ytes_value\x18\x06 \x01(\x0cH\x00\x12\x13\n\tref_value\x18\x07 \x01(\x04H\x00\x42\x07\n\x05value\"\x8c\x01\n\x0eXEventMetadata\x12\n\n\x02id\x18\x01 \x01(\x03\x12\x0c\n\x04name\x18\x02 \x01(\t\x12\x14\n\x0c\x64isplay_name\x18\x04 \x01(\t\x12\x10\n\x08metadata\x18\x03 \x01(\x0c\x12&\n\x05stats\x18\x05 \x03(\x0b\x32\x17.hvd_bench.xplane.XStat\x12\x10\n\x08\x63hild_id\x18\x06 \x03(\x03\">\n\rXStatMetadata\x12\n\n\x02id\x18\x01 \x01(\x03\x12\x0c\n\x04name\x18\x02 \x01(\t\x12\x13\n\x0b\x64\x65scription\x18\x03 \x01(\tb\x06proto3')

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'hvd_xplane_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _XPLANE_EVENTMETADATAENTRY._options = None
  _XPLANE_EVENTMETADATAENTRY._serialized_options = b'8\001'
  _XPLANE_STATMETADATAENTRY._options = None
  _XPLANE_STATMETADATAENTRY._serialized_options = b'8\001'
  _XSPACE._serialized_start=38
  _XSPACE._serialized_end=88
  _XPLANE._serialized_start=91
  _XPLANE._serialized_end=515
  _XPLANE_EVENTMETADATAENTRY._serialized_start=343
  _XPLANE_EVENTMETADATAENTRY._serialized_end=429
  _XPLANE_STATMETADATAENTRY._serialized_start=431
  _XPLANE_STATMETADATAENTRY._serialized_end=515
  _XLINE._serialized_start=518
  _XLINE._serialized_end=678
  _XEVENT._serialized_start=681
  _XEVENT._serialized_end=827
  _XSTAT._serialized_start=830
  _XSTAT._serialized_end=1003
  _XEVENTMETADATA._serialized_start=1006
  _XEVENTMETADATA._serialized_end=1146
  _XSTATMETADATA._serialized_start=1148
  _XSTATMETADATA._serialized_end=1210
# @@protoc_insertion_point(module_scope)
