# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: volume_server.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'\n\x13volume_server.proto\x12\x10volume_server_pb\"A\n\x12\x42\x61tchDeleteRequest\x12\x10\n\x08\x66ile_ids\x18\x01 \x03(\t\x12\x19\n\x11skip_cookie_check\x18\x02 \x01(\x08\"F\n\x13\x42\x61tchDeleteResponse\x12/\n\x07results\x18\x01 \x03(\x0b\x32\x1e.volume_server_pb.DeleteResult\"]\n\x0c\x44\x65leteResult\x12\x0f\n\x07\x66ile_id\x18\x01 \x01(\t\x12\x0e\n\x06status\x18\x02 \x01(\x05\x12\r\n\x05\x65rror\x18\x03 \x01(\t\x12\x0c\n\x04size\x18\x04 \x01(\r\x12\x0f\n\x07version\x18\x05 \x01(\r\"-\n\x18VacuumVolumeCheckRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"2\n\x19VacuumVolumeCheckResponse\x12\x15\n\rgarbage_ratio\x18\x01 \x01(\x01\"D\n\x1aVacuumVolumeCompactRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x13\n\x0bpreallocate\x18\x02 \x01(\x03\"\x1d\n\x1bVacuumVolumeCompactResponse\".\n\x19VacuumVolumeCommitRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"2\n\x1aVacuumVolumeCommitResponse\x12\x14\n\x0cis_read_only\x18\x01 \x01(\x08\"/\n\x1aVacuumVolumeCleanupRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\x1d\n\x1bVacuumVolumeCleanupResponse\"-\n\x17\x44\x65leteCollectionRequest\x12\x12\n\ncollection\x18\x01 \x01(\t\"\x1a\n\x18\x44\x65leteCollectionResponse\"\x95\x01\n\x15\x41llocateVolumeRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x13\n\x0bpreallocate\x18\x03 \x01(\x03\x12\x13\n\x0breplication\x18\x04 \x01(\t\x12\x0b\n\x03ttl\x18\x05 \x01(\t\x12\x1e\n\x16memory_map_max_size_mb\x18\x06 \x01(\r\"\x18\n\x16\x41llocateVolumeResponse\",\n\x17VolumeSyncStatusRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\xa9\x01\n\x18VolumeSyncStatusResponse\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x13\n\x0breplication\x18\x04 \x01(\t\x12\x0b\n\x03ttl\x18\x05 \x01(\t\x12\x13\n\x0btail_offset\x18\x06 \x01(\x04\x12\x18\n\x10\x63ompact_revision\x18\x07 \x01(\r\x12\x15\n\ridx_file_size\x18\x08 \x01(\x04\"C\n\x1cVolumeIncrementalCopyRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x10\n\x08since_ns\x18\x02 \x01(\x04\"5\n\x1dVolumeIncrementalCopyResponse\x12\x14\n\x0c\x66ile_content\x18\x01 \x01(\x0c\"\'\n\x12VolumeMountRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\x15\n\x13VolumeMountResponse\")\n\x14VolumeUnmountRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\x17\n\x15VolumeUnmountResponse\"(\n\x13VolumeDeleteRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\x16\n\x14VolumeDeleteResponse\".\n\x19VolumeMarkReadonlyRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\x1c\n\x1aVolumeMarkReadonlyResponse\".\n\x19VolumeMarkWritableRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\x1c\n\x1aVolumeMarkWritableResponse\"v\n\x11VolumeCopyRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x13\n\x0breplication\x18\x03 \x01(\t\x12\x0b\n\x03ttl\x18\x04 \x01(\t\x12\x18\n\x10source_data_node\x18\x05 \x01(\t\"/\n\x12VolumeCopyResponse\x12\x19\n\x11last_append_at_ns\x18\x01 \x01(\x04\"0\n\x1bReadVolumeFileStatusRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\"\xec\x01\n\x1cReadVolumeFileStatusResponse\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\"\n\x1aidx_file_timestamp_seconds\x18\x02 \x01(\x04\x12\x15\n\ridx_file_size\x18\x03 \x01(\x04\x12\"\n\x1a\x64\x61t_file_timestamp_seconds\x18\x04 \x01(\x04\x12\x15\n\rdat_file_size\x18\x05 \x01(\x04\x12\x12\n\nfile_count\x18\x06 \x01(\x04\x12\x1b\n\x13\x63ompaction_revision\x18\x07 \x01(\r\x12\x12\n\ncollection\x18\x08 \x01(\t\"\xb3\x01\n\x0f\x43opyFileRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x0b\n\x03\x65xt\x18\x02 \x01(\t\x12\x1b\n\x13\x63ompaction_revision\x18\x03 \x01(\r\x12\x13\n\x0bstop_offset\x18\x04 \x01(\x04\x12\x12\n\ncollection\x18\x05 \x01(\t\x12\x14\n\x0cis_ec_volume\x18\x06 \x01(\x08\x12$\n\x1cignore_source_file_not_found\x18\x07 \x01(\x08\"(\n\x10\x43opyFileResponse\x12\x14\n\x0c\x66ile_content\x18\x01 \x01(\x0c\"\\\n\x17VolumeTailSenderRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x10\n\x08since_ns\x18\x02 \x01(\x04\x12\x1c\n\x14idle_timeout_seconds\x18\x03 \x01(\r\"]\n\x18VolumeTailSenderResponse\x12\x15\n\rneedle_header\x18\x01 \x01(\x0c\x12\x13\n\x0bneedle_body\x18\x02 \x01(\x0c\x12\x15\n\ris_last_chunk\x18\x03 \x01(\x08\"|\n\x19VolumeTailReceiverRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x10\n\x08since_ns\x18\x02 \x01(\x04\x12\x1c\n\x14idle_timeout_seconds\x18\x03 \x01(\r\x12\x1c\n\x14source_volume_server\x18\x04 \x01(\t\"\x1c\n\x1aVolumeTailReceiverResponse\"k\n\x1dVolumeEcShardsGenerateRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x0f\n\x07\x65ncoder\x18\x03 \x01(\t\x12\x12\n\nvolume_ids\x18\x04 \x03(\r\" \n\x1eVolumeEcShardsGenerateResponse\"\x87\x01\n\x1cVolumeEcShardsRebuildRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x0f\n\x07\x65ncoder\x18\x03 \x01(\t\x12\x12\n\nvolume_ids\x18\x04 \x03(\r\x12\x1b\n\x13\x63ondemned_shard_ids\x18\x05 \x03(\r\"K\n\x1bVolumeEcShardsRebuildResult\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x19\n\x11rebuilt_shard_ids\x18\x02 \x03(\r\"z\n\x1dVolumeEcShardsRebuildResponse\x12\x19\n\x11rebuilt_shard_ids\x18\x01 \x03(\r\x12>\n\x07results\x18\x02 \x03(\x0b\x32-.volume_server_pb.VolumeEcShardsRebuildResult\"\xb4\x01\n\x19VolumeEcShardsCopyRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x11\n\tshard_ids\x18\x03 \x03(\r\x12\x15\n\rcopy_ecx_file\x18\x04 \x01(\x08\x12\x18\n\x10source_data_node\x18\x05 \x01(\t\x12\x15\n\rcopy_ecj_file\x18\x06 \x01(\x08\x12\x15\n\rcopy_vif_file\x18\x07 \x01(\x08\"\x1c\n\x1aVolumeEcShardsCopyResponse\"W\n\x1bVolumeEcShardsDeleteRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x11\n\tshard_ids\x18\x03 \x03(\r\"\x1e\n\x1cVolumeEcShardsDeleteResponse\"V\n\x1aVolumeEcShardsMountRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x11\n\tshard_ids\x18\x03 \x03(\r\"\x1d\n\x1bVolumeEcShardsMountResponse\"D\n\x1cVolumeEcShardsUnmountRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x11\n\tshard_ids\x18\x03 \x03(\r\"\x1f\n\x1dVolumeEcShardsUnmountResponse\"o\n\x18VolumeEcShardReadRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x10\n\x08shard_id\x18\x02 \x01(\r\x12\x0e\n\x06offset\x18\x03 \x01(\x03\x12\x0c\n\x04size\x18\x04 \x01(\x03\x12\x10\n\x08\x66ile_key\x18\x05 \x01(\x04\"=\n\x19VolumeEcShardReadResponse\x12\x0c\n\x04\x64\x61ta\x18\x01 \x01(\x0c\x12\x12\n\nis_deleted\x18\x02 \x01(\x08\"e\n\x19VolumeEcBlobDeleteRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x10\n\x08\x66ile_key\x18\x03 \x01(\x04\x12\x0f\n\x07version\x18\x04 \x01(\r\"\x1c\n\x1aVolumeEcBlobDeleteResponse\"F\n\x1dVolumeEcShardsToVolumeRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\" \n\x1eVolumeEcShardsToVolumeResponse\"\x88\x01\n VolumeTierMoveDatToRemoteRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12 \n\x18\x64\x65stination_backend_name\x18\x03 \x01(\t\x12\x1b\n\x13keep_local_dat_file\x18\x04 \x01(\x08\"T\n!VolumeTierMoveDatToRemoteResponse\x12\x11\n\tprocessed\x18\x01 \x01(\x03\x12\x1c\n\x14processed_percentage\x18\x02 \x01(\x02\"i\n\"VolumeTierMoveDatFromRemoteRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x12\n\ncollection\x18\x02 \x01(\t\x12\x1c\n\x14keep_remote_dat_file\x18\x03 \x01(\x08\"V\n#VolumeTierMoveDatFromRemoteResponse\x12\x11\n\tprocessed\x18\x01 \x01(\x03\x12\x1c\n\x14processed_percentage\x18\x02 \x01(\x02\"\x19\n\tMemStatus\x12\x0c\n\x04heap\x18\x01 \x01(\x04\"B\n\nDiskStatus\x12\x0b\n\x03\x64ir\x18\x01 \x01(\t\x12\x0b\n\x03\x61ll\x18\x02 \x01(\x04\x12\x0c\n\x04used\x18\x03 \x01(\x04\x12\x0c\n\x04\x66ree\x18\x04 \x01(\x04\"\x1b\n\x19VolumeServerStatusRequest\"\x85\x01\n\x1aVolumeServerStatusResponse\x12\x33\n\rdisk_statuses\x18\x01 \x03(\x0b\x32\x1c.volume_server_pb.DiskStatus\x12\x32\n\rmemory_status\x18\x02 \x01(\x0b\x32\x1b.volume_server_pb.MemStatus\"\x1a\n\x18VolumeServerLeaveRequest\"\x1b\n\x19VolumeServerLeaveResponse\"@\n\x16VolumeConfigureRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x13\n\x0breplication\x18\x02 \x01(\t\"(\n\x17VolumeConfigureResponse\x12\r\n\x05\x65rror\x18\x01 \x01(\t\"(\n\x13VolumeStatusRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\",\n\x14VolumeStatusResponse\x12\x14\n\x0cis_read_only\x18\x01 \x01(\x08\"A\n\x19VolumeNeedleStatusRequest\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x11\n\tneedle_id\x18\x02 \x01(\x04\"~\n\x1aVolumeNeedleStatusResponse\x12\x11\n\tneedle_id\x18\x01 \x01(\x04\x12\x0e\n\x06\x63ookie\x18\x02 \x01(\r\x12\x0c\n\x04size\x18\x03 \x01(\r\x12\x15\n\rlast_modified\x18\x04 \x01(\x04\x12\x0b\n\x03\x63rc\x18\x05 \x01(\r\x12\x0b\n\x03ttl\x18\x06 \x01(\t\"\xa9\x01\n\x0cQueryRequest\x12\x15\n\rfrom_file_ids\x18\x01 \x03(\t\x12\x35\n\x06\x66ilter\x18\x02 \x01(\x0b\x32%.volume_server_pb.QueryRequest.Filter\x12\x12\n\nselections\x18\x03 \x03(\t\x1a\x37\n\x06\x46ilter\x12\r\n\x05\x66ield\x18\x01 \x01(\t\x12\x0f\n\x07operand\x18\x02 \x01(\t\x12\r\n\x05value\x18\x03 \x01(\t\" \n\rQueriedStripe\x12\x0f\n\x07records\x18\x01 \x01(\x0c\"a\n\x17VolumeScrubStartRequest\x12\x12\n\nvolume_ids\x18\x01 \x03(\r\x12\x15\n\rthrottle_mbps\x18\x02 \x01(\x02\x12\x0c\n\x04\x66ull\x18\x03 \x01(\x08\x12\r\n\x05\x61lone\x18\x04 \x01(\x08\"A\n\x18VolumeScrubStartResponse\x12\x0f\n\x07started\x18\x01 \x01(\x08\x12\x14\n\x0cpasses_ended\x18\x02 \x01(\x04\"\x19\n\x17VolumeScrubPauseRequest\"*\n\x18VolumeScrubPauseResponse\x12\x0e\n\x06paused\x18\x01 \x01(\x08\"D\n\x18VolumeScrubStatusRequest\x12\x0c\n\x04wait\x18\x01 \x01(\x08\x12\x1a\n\x12\x61\x66ter_passes_ended\x18\x02 \x01(\x04\"\xfe\x02\n\x19VolumeScrubStatusResponse\x12\r\n\x05state\x18\x01 \x01(\t\x12\x15\n\rbytes_scanned\x18\x02 \x01(\x04\x12\x18\n\x10needles_verified\x18\x03 \x01(\x04\x12\x18\n\x10stripes_verified\x18\x04 \x01(\x04\x12\x19\n\x11\x63orruptions_found\x18\x05 \x01(\x04\x12\x1c\n\x14\x63orruptions_repaired\x18\x06 \x01(\x04\x12\x15\n\runrecoverable\x18\x07 \x01(\x04\x12\x19\n\x11\x63urrent_volume_id\x18\x08 \x01(\r\x12\x18\n\x10passes_completed\x18\t \x01(\x04\x12\x16\n\x0elast_pass_unix\x18\n \x01(\x01\x12\x18\n\x10scan_lag_seconds\x18\x0b \x01(\x01\x12\x14\n\x0cpasses_ended\x18\x0c \x01(\x04\x12:\n\tlast_pass\x18\r \x01(\x0b\x32\'.volume_server_pb.VolumeScrubPassReport\"~\n\x15VolumeScrubPassReport\x12\x0e\n\x06\x66\x61iled\x18\x01 \x01(\x08\x12\r\n\x05\x65rror\x18\x02 \x01(\t\x12\x0f\n\x07seconds\x18\x03 \x01(\x01\x12\x35\n\x07volumes\x18\x04 \x03(\x0b\x32$.volume_server_pb.VolumeScrubVerdict\"s\n\x12VolumeScrubVerdict\x12\x11\n\tvolume_id\x18\x01 \x01(\r\x12\x19\n\x11rebuilt_shard_ids\x18\x02 \x03(\r\x12\x18\n\x10needles_repaired\x18\x03 \x01(\r\x12\x15\n\runrecoverable\x18\x04 \x01(\r2\xa2\"\n\x0cVolumeServer\x12\\\n\x0b\x42\x61tchDelete\x12$.volume_server_pb.BatchDeleteRequest\x1a%.volume_server_pb.BatchDeleteResponse\"\x00\x12n\n\x11VacuumVolumeCheck\x12*.volume_server_pb.VacuumVolumeCheckRequest\x1a+.volume_server_pb.VacuumVolumeCheckResponse\"\x00\x12t\n\x13VacuumVolumeCompact\x12,.volume_server_pb.VacuumVolumeCompactRequest\x1a-.volume_server_pb.VacuumVolumeCompactResponse\"\x00\x12q\n\x12VacuumVolumeCommit\x12+.volume_server_pb.VacuumVolumeCommitRequest\x1a,.volume_server_pb.VacuumVolumeCommitResponse\"\x00\x12t\n\x13VacuumVolumeCleanup\x12,.volume_server_pb.VacuumVolumeCleanupRequest\x1a-.volume_server_pb.VacuumVolumeCleanupResponse\"\x00\x12k\n\x10\x44\x65leteCollection\x12).volume_server_pb.DeleteCollectionRequest\x1a*.volume_server_pb.DeleteCollectionResponse\"\x00\x12\x65\n\x0e\x41llocateVolume\x12\'.volume_server_pb.AllocateVolumeRequest\x1a(.volume_server_pb.AllocateVolumeResponse\"\x00\x12k\n\x10VolumeSyncStatus\x12).volume_server_pb.VolumeSyncStatusRequest\x1a*.volume_server_pb.VolumeSyncStatusResponse\"\x00\x12|\n\x15VolumeIncrementalCopy\x12..volume_server_pb.VolumeIncrementalCopyRequest\x1a/.volume_server_pb.VolumeIncrementalCopyResponse\"\x00\x30\x01\x12\\\n\x0bVolumeMount\x12$.volume_server_pb.VolumeMountRequest\x1a%.volume_server_pb.VolumeMountResponse\"\x00\x12\x62\n\rVolumeUnmount\x12&.volume_server_pb.VolumeUnmountRequest\x1a\'.volume_server_pb.VolumeUnmountResponse\"\x00\x12_\n\x0cVolumeDelete\x12%.volume_server_pb.VolumeDeleteRequest\x1a&.volume_server_pb.VolumeDeleteResponse\"\x00\x12q\n\x12VolumeMarkReadonly\x12+.volume_server_pb.VolumeMarkReadonlyRequest\x1a,.volume_server_pb.VolumeMarkReadonlyResponse\"\x00\x12q\n\x12VolumeMarkWritable\x12+.volume_server_pb.VolumeMarkWritableRequest\x1a,.volume_server_pb.VolumeMarkWritableResponse\"\x00\x12Y\n\nVolumeCopy\x12#.volume_server_pb.VolumeCopyRequest\x1a$.volume_server_pb.VolumeCopyResponse\"\x00\x12w\n\x14ReadVolumeFileStatus\x12-.volume_server_pb.ReadVolumeFileStatusRequest\x1a..volume_server_pb.ReadVolumeFileStatusResponse\"\x00\x12U\n\x08\x43opyFile\x12!.volume_server_pb.CopyFileRequest\x1a\".volume_server_pb.CopyFileResponse\"\x00\x30\x01\x12m\n\x10VolumeTailSender\x12).volume_server_pb.VolumeTailSenderRequest\x1a*.volume_server_pb.VolumeTailSenderResponse\"\x00\x30\x01\x12q\n\x12VolumeTailReceiver\x12+.volume_server_pb.VolumeTailReceiverRequest\x1a,.volume_server_pb.VolumeTailReceiverResponse\"\x00\x12}\n\x16VolumeEcShardsGenerate\x12/.volume_server_pb.VolumeEcShardsGenerateRequest\x1a\x30.volume_server_pb.VolumeEcShardsGenerateResponse\"\x00\x12z\n\x15VolumeEcShardsRebuild\x12..volume_server_pb.VolumeEcShardsRebuildRequest\x1a/.volume_server_pb.VolumeEcShardsRebuildResponse\"\x00\x12q\n\x12VolumeEcShardsCopy\x12+.volume_server_pb.VolumeEcShardsCopyRequest\x1a,.volume_server_pb.VolumeEcShardsCopyResponse\"\x00\x12w\n\x14VolumeEcShardsDelete\x12-.volume_server_pb.VolumeEcShardsDeleteRequest\x1a..volume_server_pb.VolumeEcShardsDeleteResponse\"\x00\x12t\n\x13VolumeEcShardsMount\x12,.volume_server_pb.VolumeEcShardsMountRequest\x1a-.volume_server_pb.VolumeEcShardsMountResponse\"\x00\x12z\n\x15VolumeEcShardsUnmount\x12..volume_server_pb.VolumeEcShardsUnmountRequest\x1a/.volume_server_pb.VolumeEcShardsUnmountResponse\"\x00\x12p\n\x11VolumeEcShardRead\x12*.volume_server_pb.VolumeEcShardReadRequest\x1a+.volume_server_pb.VolumeEcShardReadResponse\"\x00\x30\x01\x12q\n\x12VolumeEcBlobDelete\x12+.volume_server_pb.VolumeEcBlobDeleteRequest\x1a,.volume_server_pb.VolumeEcBlobDeleteResponse\"\x00\x12}\n\x16VolumeEcShardsToVolume\x12/.volume_server_pb.VolumeEcShardsToVolumeRequest\x1a\x30.volume_server_pb.VolumeEcShardsToVolumeResponse\"\x00\x12\x88\x01\n\x19VolumeTierMoveDatToRemote\x12\x32.volume_server_pb.VolumeTierMoveDatToRemoteRequest\x1a\x33.volume_server_pb.VolumeTierMoveDatToRemoteResponse\"\x00\x30\x01\x12\x8e\x01\n\x1bVolumeTierMoveDatFromRemote\x12\x34.volume_server_pb.VolumeTierMoveDatFromRemoteRequest\x1a\x35.volume_server_pb.VolumeTierMoveDatFromRemoteResponse\"\x00\x30\x01\x12q\n\x12VolumeServerStatus\x12+.volume_server_pb.VolumeServerStatusRequest\x1a,.volume_server_pb.VolumeServerStatusResponse\"\x00\x12n\n\x11VolumeServerLeave\x12*.volume_server_pb.VolumeServerLeaveRequest\x1a+.volume_server_pb.VolumeServerLeaveResponse\"\x00\x12h\n\x0fVolumeConfigure\x12(.volume_server_pb.VolumeConfigureRequest\x1a).volume_server_pb.VolumeConfigureResponse\"\x00\x12_\n\x0cVolumeStatus\x12%.volume_server_pb.VolumeStatusRequest\x1a&.volume_server_pb.VolumeStatusResponse\"\x00\x12q\n\x12VolumeNeedleStatus\x12+.volume_server_pb.VolumeNeedleStatusRequest\x1a,.volume_server_pb.VolumeNeedleStatusResponse\"\x00\x12L\n\x05Query\x12\x1e.volume_server_pb.QueryRequest\x1a\x1f.volume_server_pb.QueriedStripe\"\x00\x30\x01\x12k\n\x10VolumeScrubStart\x12).volume_server_pb.VolumeScrubStartRequest\x1a*.volume_server_pb.VolumeScrubStartResponse\"\x00\x12k\n\x10VolumeScrubPause\x12).volume_server_pb.VolumeScrubPauseRequest\x1a*.volume_server_pb.VolumeScrubPauseResponse\"\x00\x12n\n\x11VolumeScrubStatus\x12*.volume_server_pb.VolumeScrubStatusRequest\x1a+.volume_server_pb.VolumeScrubStatusResponse\"\x00\x62\x06proto3')

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'volume_server_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _BATCHDELETEREQUEST._serialized_start=41
  _BATCHDELETEREQUEST._serialized_end=106
  _BATCHDELETERESPONSE._serialized_start=108
  _BATCHDELETERESPONSE._serialized_end=178
  _DELETERESULT._serialized_start=180
  _DELETERESULT._serialized_end=273
  _VACUUMVOLUMECHECKREQUEST._serialized_start=275
  _VACUUMVOLUMECHECKREQUEST._serialized_end=320
  _VACUUMVOLUMECHECKRESPONSE._serialized_start=322
  _VACUUMVOLUMECHECKRESPONSE._serialized_end=372
  _VACUUMVOLUMECOMPACTREQUEST._serialized_start=374
  _VACUUMVOLUMECOMPACTREQUEST._serialized_end=442
  _VACUUMVOLUMECOMPACTRESPONSE._serialized_start=444
  _VACUUMVOLUMECOMPACTRESPONSE._serialized_end=473
  _VACUUMVOLUMECOMMITREQUEST._serialized_start=475
  _VACUUMVOLUMECOMMITREQUEST._serialized_end=521
  _VACUUMVOLUMECOMMITRESPONSE._serialized_start=523
  _VACUUMVOLUMECOMMITRESPONSE._serialized_end=573
  _VACUUMVOLUMECLEANUPREQUEST._serialized_start=575
  _VACUUMVOLUMECLEANUPREQUEST._serialized_end=622
  _VACUUMVOLUMECLEANUPRESPONSE._serialized_start=624
  _VACUUMVOLUMECLEANUPRESPONSE._serialized_end=653
  _DELETECOLLECTIONREQUEST._serialized_start=655
  _DELETECOLLECTIONREQUEST._serialized_end=700
  _DELETECOLLECTIONRESPONSE._serialized_start=702
  _DELETECOLLECTIONRESPONSE._serialized_end=728
  _ALLOCATEVOLUMEREQUEST._serialized_start=731
  _ALLOCATEVOLUMEREQUEST._serialized_end=880
  _ALLOCATEVOLUMERESPONSE._serialized_start=882
  _ALLOCATEVOLUMERESPONSE._serialized_end=906
  _VOLUMESYNCSTATUSREQUEST._serialized_start=908
  _VOLUMESYNCSTATUSREQUEST._serialized_end=952
  _VOLUMESYNCSTATUSRESPONSE._serialized_start=955
  _VOLUMESYNCSTATUSRESPONSE._serialized_end=1124
  _VOLUMEINCREMENTALCOPYREQUEST._serialized_start=1126
  _VOLUMEINCREMENTALCOPYREQUEST._serialized_end=1193
  _VOLUMEINCREMENTALCOPYRESPONSE._serialized_start=1195
  _VOLUMEINCREMENTALCOPYRESPONSE._serialized_end=1248
  _VOLUMEMOUNTREQUEST._serialized_start=1250
  _VOLUMEMOUNTREQUEST._serialized_end=1289
  _VOLUMEMOUNTRESPONSE._serialized_start=1291
  _VOLUMEMOUNTRESPONSE._serialized_end=1312
  _VOLUMEUNMOUNTREQUEST._serialized_start=1314
  _VOLUMEUNMOUNTREQUEST._serialized_end=1355
  _VOLUMEUNMOUNTRESPONSE._serialized_start=1357
  _VOLUMEUNMOUNTRESPONSE._serialized_end=1380
  _VOLUMEDELETEREQUEST._serialized_start=1382
  _VOLUMEDELETEREQUEST._serialized_end=1422
  _VOLUMEDELETERESPONSE._serialized_start=1424
  _VOLUMEDELETERESPONSE._serialized_end=1446
  _VOLUMEMARKREADONLYREQUEST._serialized_start=1448
  _VOLUMEMARKREADONLYREQUEST._serialized_end=1494
  _VOLUMEMARKREADONLYRESPONSE._serialized_start=1496
  _VOLUMEMARKREADONLYRESPONSE._serialized_end=1524
  _VOLUMEMARKWRITABLEREQUEST._serialized_start=1526
  _VOLUMEMARKWRITABLEREQUEST._serialized_end=1572
  _VOLUMEMARKWRITABLERESPONSE._serialized_start=1574
  _VOLUMEMARKWRITABLERESPONSE._serialized_end=1602
  _VOLUMECOPYREQUEST._serialized_start=1604
  _VOLUMECOPYREQUEST._serialized_end=1722
  _VOLUMECOPYRESPONSE._serialized_start=1724
  _VOLUMECOPYRESPONSE._serialized_end=1771
  _READVOLUMEFILESTATUSREQUEST._serialized_start=1773
  _READVOLUMEFILESTATUSREQUEST._serialized_end=1821
  _READVOLUMEFILESTATUSRESPONSE._serialized_start=1824
  _READVOLUMEFILESTATUSRESPONSE._serialized_end=2060
  _COPYFILEREQUEST._serialized_start=2063
  _COPYFILEREQUEST._serialized_end=2242
  _COPYFILERESPONSE._serialized_start=2244
  _COPYFILERESPONSE._serialized_end=2284
  _VOLUMETAILSENDERREQUEST._serialized_start=2286
  _VOLUMETAILSENDERREQUEST._serialized_end=2378
  _VOLUMETAILSENDERRESPONSE._serialized_start=2380
  _VOLUMETAILSENDERRESPONSE._serialized_end=2473
  _VOLUMETAILRECEIVERREQUEST._serialized_start=2475
  _VOLUMETAILRECEIVERREQUEST._serialized_end=2599
  _VOLUMETAILRECEIVERRESPONSE._serialized_start=2601
  _VOLUMETAILRECEIVERRESPONSE._serialized_end=2629
  _VOLUMEECSHARDSGENERATEREQUEST._serialized_start=2631
  _VOLUMEECSHARDSGENERATEREQUEST._serialized_end=2738
  _VOLUMEECSHARDSGENERATERESPONSE._serialized_start=2740
  _VOLUMEECSHARDSGENERATERESPONSE._serialized_end=2772
  _VOLUMEECSHARDSREBUILDREQUEST._serialized_start=2775
  _VOLUMEECSHARDSREBUILDREQUEST._serialized_end=2910
  _VOLUMEECSHARDSREBUILDRESULT._serialized_start=2912
  _VOLUMEECSHARDSREBUILDRESULT._serialized_end=2987
  _VOLUMEECSHARDSREBUILDRESPONSE._serialized_start=2989
  _VOLUMEECSHARDSREBUILDRESPONSE._serialized_end=3111
  _VOLUMEECSHARDSCOPYREQUEST._serialized_start=3114
  _VOLUMEECSHARDSCOPYREQUEST._serialized_end=3294
  _VOLUMEECSHARDSCOPYRESPONSE._serialized_start=3296
  _VOLUMEECSHARDSCOPYRESPONSE._serialized_end=3324
  _VOLUMEECSHARDSDELETEREQUEST._serialized_start=3326
  _VOLUMEECSHARDSDELETEREQUEST._serialized_end=3413
  _VOLUMEECSHARDSDELETERESPONSE._serialized_start=3415
  _VOLUMEECSHARDSDELETERESPONSE._serialized_end=3445
  _VOLUMEECSHARDSMOUNTREQUEST._serialized_start=3447
  _VOLUMEECSHARDSMOUNTREQUEST._serialized_end=3533
  _VOLUMEECSHARDSMOUNTRESPONSE._serialized_start=3535
  _VOLUMEECSHARDSMOUNTRESPONSE._serialized_end=3564
  _VOLUMEECSHARDSUNMOUNTREQUEST._serialized_start=3566
  _VOLUMEECSHARDSUNMOUNTREQUEST._serialized_end=3634
  _VOLUMEECSHARDSUNMOUNTRESPONSE._serialized_start=3636
  _VOLUMEECSHARDSUNMOUNTRESPONSE._serialized_end=3667
  _VOLUMEECSHARDREADREQUEST._serialized_start=3669
  _VOLUMEECSHARDREADREQUEST._serialized_end=3780
  _VOLUMEECSHARDREADRESPONSE._serialized_start=3782
  _VOLUMEECSHARDREADRESPONSE._serialized_end=3843
  _VOLUMEECBLOBDELETEREQUEST._serialized_start=3845
  _VOLUMEECBLOBDELETEREQUEST._serialized_end=3946
  _VOLUMEECBLOBDELETERESPONSE._serialized_start=3948
  _VOLUMEECBLOBDELETERESPONSE._serialized_end=3976
  _VOLUMEECSHARDSTOVOLUMEREQUEST._serialized_start=3978
  _VOLUMEECSHARDSTOVOLUMEREQUEST._serialized_end=4048
  _VOLUMEECSHARDSTOVOLUMERESPONSE._serialized_start=4050
  _VOLUMEECSHARDSTOVOLUMERESPONSE._serialized_end=4082
  _VOLUMETIERMOVEDATTOREMOTEREQUEST._serialized_start=4085
  _VOLUMETIERMOVEDATTOREMOTEREQUEST._serialized_end=4221
  _VOLUMETIERMOVEDATTOREMOTERESPONSE._serialized_start=4223
  _VOLUMETIERMOVEDATTOREMOTERESPONSE._serialized_end=4307
  _VOLUMETIERMOVEDATFROMREMOTEREQUEST._serialized_start=4309
  _VOLUMETIERMOVEDATFROMREMOTEREQUEST._serialized_end=4414
  _VOLUMETIERMOVEDATFROMREMOTERESPONSE._serialized_start=4416
  _VOLUMETIERMOVEDATFROMREMOTERESPONSE._serialized_end=4502
  _MEMSTATUS._serialized_start=4504
  _MEMSTATUS._serialized_end=4529
  _DISKSTATUS._serialized_start=4531
  _DISKSTATUS._serialized_end=4597
  _VOLUMESERVERSTATUSREQUEST._serialized_start=4599
  _VOLUMESERVERSTATUSREQUEST._serialized_end=4626
  _VOLUMESERVERSTATUSRESPONSE._serialized_start=4629
  _VOLUMESERVERSTATUSRESPONSE._serialized_end=4762
  _VOLUMESERVERLEAVEREQUEST._serialized_start=4764
  _VOLUMESERVERLEAVEREQUEST._serialized_end=4790
  _VOLUMESERVERLEAVERESPONSE._serialized_start=4792
  _VOLUMESERVERLEAVERESPONSE._serialized_end=4819
  _VOLUMECONFIGUREREQUEST._serialized_start=4821
  _VOLUMECONFIGUREREQUEST._serialized_end=4885
  _VOLUMECONFIGURERESPONSE._serialized_start=4887
  _VOLUMECONFIGURERESPONSE._serialized_end=4927
  _VOLUMESTATUSREQUEST._serialized_start=4929
  _VOLUMESTATUSREQUEST._serialized_end=4969
  _VOLUMESTATUSRESPONSE._serialized_start=4971
  _VOLUMESTATUSRESPONSE._serialized_end=5015
  _VOLUMENEEDLESTATUSREQUEST._serialized_start=5017
  _VOLUMENEEDLESTATUSREQUEST._serialized_end=5082
  _VOLUMENEEDLESTATUSRESPONSE._serialized_start=5084
  _VOLUMENEEDLESTATUSRESPONSE._serialized_end=5210
  _QUERYREQUEST._serialized_start=5213
  _QUERYREQUEST._serialized_end=5382
  _QUERYREQUEST_FILTER._serialized_start=5327
  _QUERYREQUEST_FILTER._serialized_end=5382
  _QUERIEDSTRIPE._serialized_start=5384
  _QUERIEDSTRIPE._serialized_end=5416
  _VOLUMESCRUBSTARTREQUEST._serialized_start=5418
  _VOLUMESCRUBSTARTREQUEST._serialized_end=5515
  _VOLUMESCRUBSTARTRESPONSE._serialized_start=5517
  _VOLUMESCRUBSTARTRESPONSE._serialized_end=5582
  _VOLUMESCRUBPAUSEREQUEST._serialized_start=5584
  _VOLUMESCRUBPAUSEREQUEST._serialized_end=5609
  _VOLUMESCRUBPAUSERESPONSE._serialized_start=5611
  _VOLUMESCRUBPAUSERESPONSE._serialized_end=5653
  _VOLUMESCRUBSTATUSREQUEST._serialized_start=5655
  _VOLUMESCRUBSTATUSREQUEST._serialized_end=5723
  _VOLUMESCRUBSTATUSRESPONSE._serialized_start=5726
  _VOLUMESCRUBSTATUSRESPONSE._serialized_end=6108
  _VOLUMESCRUBPASSREPORT._serialized_start=6110
  _VOLUMESCRUBPASSREPORT._serialized_end=6236
  _VOLUMESCRUBVERDICT._serialized_start=6238
  _VOLUMESCRUBVERDICT._serialized_end=6353
  _VOLUMESERVER._serialized_start=6356
  _VOLUMESERVER._serialized_end=10742
# @@protoc_insertion_point(module_scope)
