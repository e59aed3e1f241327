"""Store-level EC lifecycle: generate -> mount -> read -> lose shards ->
rebuild -> decode back (the ec_test.go round-trip pattern at store scope)."""

import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import store_ec
from seaweedfs_tpu.ec.ec_volume import EcShardNotFound
from seaweedfs_tpu.ec.encoder import shard_file_name
from seaweedfs_tpu.storage.needle import Needle, NeedleError
from seaweedfs_tpu.storage.store import Store

SMALL = 1 << 12  # tiny block sizes keep fixture volumes small


@pytest.fixture()
def store(tmp_path):
    s = Store([str(tmp_path / "d1"), str(tmp_path / "d2")], ip="127.0.0.1",
              port=8080)
    yield s
    s.close()


def fill_volume(store, vid, count=12, size=700):
    store.add_volume(vid)
    needles = []
    for i in range(count):
        rng = np.random.default_rng(i)
        n = Needle(id=i + 1, cookie=0x2000 + i,
                   data=rng.integers(0, 256, size, dtype=np.uint8).tobytes())
        store.write_needle(vid, n)
        needles.append(n)
    return needles


def encode_and_mount(store, vid, small=SMALL):
    from seaweedfs_tpu.ec import encoder
    v = store.find_volume(vid)
    v.read_only = True
    v.sync()
    base = v.file_name()
    encoder.write_ec_files(base, small_block=small, large_block=small << 8)
    encoder.write_sorted_file_from_idx(base)
    loc = store.location_of(vid)
    loc.delete_volume(vid)
    ecv = store_ec.mount_ec_shards(store, vid, "", range(14))
    ecv.small_block = small
    ecv.large_block = small << 8
    return base, ecv


def test_generate_mount_read(store):
    needles = fill_volume(store, 1)
    base, ecv = encode_and_mount(store, 1)
    assert store.find_volume(1) is None
    assert store.find_ec_volume(1) is ecv
    for n in needles:
        got = store_ec.read_ec_needle(store, 1, Needle(id=n.id, cookie=n.cookie))
        assert got.data == n.data


def test_read_with_missing_shards_recovers(store):
    needles = fill_volume(store, 2)
    base, ecv = encode_and_mount(store, 2)
    # lose 4 shards (max tolerable)
    for sid in (0, 3, 7, 12):
        ecv.unmount_shard(sid)
        os.remove(shard_file_name(base, sid))
    for n in needles:
        got = store_ec.read_ec_needle(store, 2, Needle(id=n.id, cookie=n.cookie))
        assert got.data == n.data


def test_rebuild_restores_shard_files(store):
    needles = fill_volume(store, 3)
    base, ecv = encode_and_mount(store, 3)
    import hashlib
    want = {sid: hashlib.sha256(open(shard_file_name(base, sid), "rb").read())
            .hexdigest() for sid in range(14)}
    for sid in (1, 13):
        ecv.unmount_shard(sid)
        os.remove(shard_file_name(base, sid))
    rebuilt = store_ec.rebuild_ec_shards_batch(store, [3])[3]
    assert sorted(rebuilt) == [1, 13]
    for sid in (1, 13):
        got = hashlib.sha256(
            open(shard_file_name(base, sid), "rb").read()).hexdigest()
        assert got == want[sid]


def test_delete_needle_then_read_fails(store):
    needles = fill_volume(store, 4)
    base, ecv = encode_and_mount(store, 4)
    store_ec.delete_ec_needle(store, 4, Needle(id=needles[0].id))
    with pytest.raises(NeedleError):
        store_ec.read_ec_needle(
            store, 4, Needle(id=needles[0].id, cookie=needles[0].cookie))
    # others unaffected
    got = store_ec.read_ec_needle(
        store, 4, Needle(id=needles[1].id, cookie=needles[1].cookie))
    assert got.data == needles[1].data


def test_decode_back_to_volume(store):
    needles = fill_volume(store, 5)
    base, ecv = encode_and_mount(store, 5)
    store_ec.delete_ec_needle(store, 5, Needle(id=needles[3].id))
    store_ec.unmount_ec_shards(store, 5, range(14))
    store_ec.ec_shards_to_volume(store, 5, small_block=SMALL,
                                 large_block=SMALL << 8)
    v = store.find_volume(5)
    assert v is not None
    for n in needles:
        if n.id == needles[3].id:
            with pytest.raises(NeedleError):
                v.read_needle(Needle(id=n.id, cookie=n.cookie))
        else:
            assert v.read_needle(Needle(id=n.id, cookie=n.cookie)).data == n.data


def test_delete_all_shards_cleans_up(store):
    fill_volume(store, 6)
    base, ecv = encode_and_mount(store, 6)
    store_ec.delete_ec_shards(store, 6, "", range(14))
    assert store.find_ec_volume(6) is None
    assert not os.path.exists(base + ".ecx")
    assert not os.path.exists(base + ".ecj")
    with pytest.raises(EcShardNotFound):
        store_ec.read_ec_shard(store, 6, 0, 0, 10)


def test_heartbeat_reports_ec_shards(store):
    fill_volume(store, 7)
    encode_and_mount(store, 7)
    hb = store.collect_heartbeat()
    assert len(hb["ec_shards"]) == 1
    assert hb["ec_shards"][0]["id"] == 7
    assert hb["ec_shards"][0]["ec_index_bits"].shard_ids == list(range(14))


def test_collection_volumes_resolve_without_collection_arg(store):
    from seaweedfs_tpu.ec import encoder
    store.add_volume(8, collection="photos")
    rng = np.random.default_rng(7)
    n = Needle(id=1, cookie=0x77,
               data=rng.integers(0, 256, 500, dtype=np.uint8).tobytes())
    store.write_needle(8, n)
    v = store.find_volume(8)
    v.read_only = True
    v.sync()
    base = v.file_name()
    encoder.write_ec_files(base, small_block=SMALL, large_block=SMALL << 8)
    encoder.write_sorted_file_from_idx(base)
    store.location_of(8).delete_volume(8)
    # no collection passed anywhere below: discovery must find photos_8.*
    ecv = store_ec.mount_ec_shards(store, 8, "photos", range(14))
    ecv.small_block, ecv.large_block = SMALL, SMALL << 8
    os.remove(shard_file_name(base, 4))
    ecv.unmount_shard(4)
    assert store_ec.rebuild_ec_shards_batch(store, [8]) == {8: [4]}
    store_ec.unmount_ec_shards(store, 8, range(14))
    store_ec.ec_shards_to_volume(store, 8, small_block=SMALL,
                                 large_block=SMALL << 8)
    v2 = store.find_volume(8)
    assert v2.collection == "photos"
    assert v2.read_needle(Needle(id=1, cookie=0x77)).data == n.data


def test_decode_refuses_while_mounted(store):
    fill_volume(store, 9)
    encode_and_mount(store, 9)
    with pytest.raises(EcShardNotFound):
        store_ec.ec_shards_to_volume(store, 9, small_block=SMALL,
                                     large_block=SMALL << 8)
