"""Heat-driven lifecycle (ISSUE 9): the policy state machine, the
heartbeat heat plane, EC shard cloud-tiering, and the master-side
engine end to end.

Layout mirrors the subsystem: pure-planner unit tests on fabricated
views (the house planning-function pattern), heat-tracker EWMA /
forget hygiene, heartbeat wire plumbing, volume_tier's EC COLD leg,
then a real in-process cluster where the engine EC-encodes an idle
volume with no operator action and un-cools it after sustained reads
— byte-identical reads throughout, dry-run acting zero times.
"""

import json
import os
import time

import pytest

from seaweedfs_tpu.lifecycle import (COLD, HOT, WARM, LifecycleConfig,
                                     Transition, VolumeView,
                                     plan_transitions, reconcile_states)
from seaweedfs_tpu.lifecycle.policy import VolState

NOW = 10_000.0

CFG = LifecycleConfig(
    interval_s=1.0, cool_threshold=1.0, warm_threshold=10.0,
    hot_dwell_s=60.0, warm_dwell_s=60.0, cold_dwell_s=60.0,
    freeze_s=300.0, cold_backend="memory.cold", max_inflight=4)


def view(vid, tier=HOT, reads=0.0, ewma=None, size=1000, files=10,
         age=1e18):
    return VolumeView(vid=vid, tier=tier, size=size, file_count=files,
                      reads_window=reads,
                      ewma=reads if ewma is None else ewma,
                      modified_age_s=age)


def settled(state, ago=1000.0):
    return VolState(state, NOW - ago)


# -- policy: the pure state machine -------------------------------------------


def test_policy_cools_idle_hot_volume():
    views = {1: view(1, reads=0.0)}
    states = {1: settled(HOT)}
    plan = plan_transitions(views, states, CFG, NOW)
    assert [(t.vid, t.kind, t.target) for t in plan] == \
        [(1, "encode", WARM)]
    assert "cool" in plan[0].reason


def test_policy_dwell_blocks_fresh_state():
    views = {1: view(1, reads=0.0)}
    states = {1: VolState(HOT, NOW - 5.0)}    # 5s < hot_dwell 60s
    assert plan_transitions(views, states, CFG, NOW) == []


def test_policy_write_quiet_guard():
    # reads are zero but the volume was written 5s ago: never EC a
    # volume still being filled
    views = {1: view(1, reads=0.0, age=5.0)}
    states = {1: settled(HOT)}
    assert plan_transitions(views, states, CFG, NOW) == []


def test_policy_never_encodes_empty_volume():
    # a freshly-grown volume's .dat is just a superblock: size is
    # nonzero but file_count is the honest emptiness signal
    views = {1: view(1, reads=0.0, size=8, files=0)}
    states = {1: settled(HOT)}
    assert plan_transitions(views, states, CFG, NOW) == []


def test_policy_hysteresis_band_is_dead():
    # reads sit between cool (1) and warm (10): no move either way
    views = {1: view(1, tier=HOT, reads=5.0),
             2: view(2, tier=WARM, reads=5.0)}
    states = {1: settled(HOT), 2: settled(WARM)}
    assert plan_transitions(views, states, CFG, NOW) == []


def test_policy_ewma_must_agree_to_cool():
    # instantaneous window is quiet but the decayed rate says the
    # volume was busy moments ago: anti-flap, stay HOT
    views = {1: view(1, reads=0.0, ewma=7.0)}
    states = {1: settled(HOT)}
    assert plan_transitions(views, states, CFG, NOW) == []


def test_policy_warm_volume_reheats():
    views = {1: view(1, tier=WARM, reads=25.0)}
    states = {1: settled(WARM)}
    plan = plan_transitions(views, states, CFG, NOW)
    assert [(t.vid, t.kind, t.target) for t in plan] == \
        [(1, "decode", HOT)]


def test_policy_freeze_needs_backend_age_and_quiet():
    views = {1: view(1, tier=WARM, reads=0.0)}
    # warm long enough to freeze
    plan = plan_transitions(views, {1: settled(WARM, ago=400.0)},
                            CFG, NOW)
    assert [(t.kind, t.target) for t in plan] == [("offload", COLD)]
    # not yet past freeze_s (but past dwell): stays WARM
    assert plan_transitions(views, {1: settled(WARM, ago=100.0)},
                            CFG, NOW) == []
    # no cold backend configured: COLD is unreachable
    no_cold = CFG._replace(cold_backend="")
    assert plan_transitions(views, {1: settled(WARM, ago=400.0)},
                            no_cold, NOW) == []
    # freeze disabled
    no_freeze = CFG._replace(freeze_s=0.0)
    assert plan_transitions(views, {1: settled(WARM, ago=400.0)},
                            no_freeze, NOW) == []


def test_policy_cold_downloads_on_reheat():
    # a COLD volume looks WARM on the wire; state machine memory says
    # COLD, and sustained reads pull it back up one tier
    views = {1: view(1, tier=WARM, reads=50.0)}
    states = {1: settled(COLD)}
    plan = plan_transitions(views, states, CFG, NOW)
    assert [(t.kind, t.target) for t in plan] == [("download", WARM)]


def test_policy_inflight_cap_and_priority():
    # five cool-down candidates + one re-heat; cap leaves room for 2:
    # the user-facing decode always outranks housekeeping encodes
    views = {i: view(i, reads=0.0) for i in range(1, 6)}
    views[9] = view(9, tier=WARM, reads=99.0)
    states = {i: settled(HOT) for i in range(1, 6)}
    states[9] = settled(WARM)
    cfg = CFG._replace(max_inflight=3)
    plan = plan_transitions(views, states, cfg, NOW, in_flight=1)
    assert len(plan) == 2
    assert plan[0].kind == "decode" and plan[0].vid == 9
    assert plan[1].kind == "encode"
    # cap already spent: nothing planned
    assert plan_transitions(views, states, cfg, NOW, in_flight=3) == []


def test_reconcile_tracks_external_moves_and_departures():
    states = {1: settled(HOT), 2: settled(WARM), 3: settled(COLD),
              4: settled(HOT)}
    views = {1: view(1, tier=WARM),   # operator ran ec.encode
             2: view(2, tier=WARM),   # unchanged
             3: view(3, tier=WARM)}   # COLD rides the WARM wire shape
    out = reconcile_states(views, states, NOW)
    assert out[1] == VolState(WARM, NOW)          # dwell restarts
    assert out[2] == states[2]                    # untouched
    assert out[3] == states[3]                    # COLD memory survives
    assert 4 not in out                           # left the cluster
    # a brand-new vid enters in its observed tier, dwell from now
    out2 = reconcile_states({7: view(7, tier=HOT)}, {}, NOW)
    assert out2[7] == VolState(HOT, NOW)


def test_config_validation():
    with pytest.raises(ValueError):
        LifecycleConfig(cool_threshold=5.0, warm_threshold=5.0).validate()
    with pytest.raises(ValueError):
        LifecycleConfig(interval_s=0).validate()
    with pytest.raises(ValueError):
        LifecycleConfig(max_inflight=0).validate()
    assert CFG.validate() is CFG


# -- heat tracker: EWMA, summary, forget --------------------------------------


def test_heat_summary_carries_decaying_ewma():
    from seaweedfs_tpu.stats.heat import HeatTracker
    tr = HeatTracker(window_s=0.4)
    try:
        for _ in range(20):
            tr.record(5, 0xAB)
        s1 = {r["id"]: r for r in tr.summary()}
        assert s1[5]["reads_window"] == 20
        rate0 = s1[5]["ewma"]
        assert rate0 == pytest.approx(20 / 0.4)   # first sample seeds
        time.sleep(0.6)                           # window fully rotates
        s2 = {r["id"]: r for r in tr.summary()}
        assert s2[5]["reads_window"] == 0
        assert 0 < s2[5]["ewma"] < rate0          # decaying, not frozen
    finally:
        tr.forget(5)
        tr.close()


def test_heat_forget_drops_gauge_child():
    from seaweedfs_tpu.stats.heat import HeatTracker
    from seaweedfs_tpu.stats.metrics import VolumeHeatGauge
    tr = HeatTracker(window_s=30.0)
    try:
        tr.record(777123, 0x1)
        assert 'vid="777123"' in VolumeHeatGauge.collect()
        tr.forget(777123)
        assert 'vid="777123"' not in VolumeHeatGauge.collect()
        assert tr.window_reads(777123) == 0
        assert tr.summary() == []
        # re-heating re-registers from zero
        tr.record(777123, 0x1)
        assert 'vid="777123"' in VolumeHeatGauge.collect()
        assert tr.window_reads(777123) == 1
    finally:
        tr.forget(777123)
        tr.close()


def test_heat_forget_respects_sibling_trackers():
    # two in-process servers share a vid: forgetting on one must not
    # kill the gauge while the other still tracks it
    from seaweedfs_tpu.stats.heat import HeatTracker
    from seaweedfs_tpu.stats.metrics import VolumeHeatGauge
    a, b = HeatTracker(), HeatTracker()
    try:
        a.record(888321, 0)
        b.record(888321, 0)
        a.forget(888321)
        assert 'vid="888321"' in VolumeHeatGauge.collect()
        b.forget(888321)
        assert 'vid="888321"' not in VolumeHeatGauge.collect()
    finally:
        a.close()
        b.close()


# -- heartbeat wire plumbing --------------------------------------------------


def test_heartbeat_heat_roundtrip():
    from seaweedfs_tpu.pb import master_pb2
    from seaweedfs_tpu.server import convert
    hb = {"ip": "1.2.3.4", "port": 8080, "volumes": [], "ec_shards": [],
          "volume_heats": [{"id": 3, "reads_window": 41, "ewma": 2.5}]}
    pb = convert.heartbeat_to_pb(hb)
    assert len(pb.volume_heats) == 1
    back = convert.heartbeat_from_pb(master_pb2.Heartbeat.FromString(
        pb.SerializeToString()))
    assert back["volume_heats"][0]["id"] == 3
    assert back["volume_heats"][0]["reads_window"] == 41
    assert back["volume_heats"][0]["ewma"] == pytest.approx(2.5)


def test_heartbeat_without_heat_is_byte_identical_to_pre_lifecycle():
    """The disabled wire contract: a heat-less heartbeat serializes to
    exactly the pre-PR bytes (field 17 never appears)."""
    from seaweedfs_tpu.pb import master_pb2
    from seaweedfs_tpu.server import convert
    hb = {"ip": "9.9.9.9", "port": 8081, "max_volume_count": 8,
          "max_file_key": 123,
          "volumes": [{"id": 4, "size": 100, "collection": "c"}],
          "ec_shards": [{"id": 5, "ec_index_bits": 0b11}]}
    got = convert.heartbeat_to_pb(hb, "dc1", "r1").SerializeToString()
    want = master_pb2.Heartbeat(
        ip="9.9.9.9", port=8081, max_volume_count=8, max_file_key=123,
        data_center="dc1", rack="r1",
        volumes=[convert.volume_info_to_pb(
            {"id": 4, "size": 100, "collection": "c"})],
        ec_shards=[convert.ec_info_to_pb(
            {"id": 5, "ec_index_bits": 0b11})]).SerializeToString()
    assert got == want


def test_topology_aggregates_cluster_heat_and_prunes_gauge():
    from seaweedfs_tpu.stats.metrics import ClusterVolumeHeatGauge
    from seaweedfs_tpu.topology.topology import Topology

    def hb(port, heats):
        return {"ip": "10.0.0.1", "port": port, "volumes": [],
                "ec_shards": [], "volume_heats": heats}

    topo = Topology()
    topo.sync_heartbeat(hb(1, [{"id": 901234, "reads_window": 5,
                                "ewma": 1.0}]))
    topo.sync_heartbeat(hb(2, [{"id": 901234, "reads_window": 7,
                                "ewma": 2.0}]),
                        rack="r2")
    heat = topo.cluster_heat()
    assert heat[901234]["reads_window"] == 12
    assert heat[901234]["ewma"] == pytest.approx(3.0)
    assert sorted(heat[901234]["servers"]) == \
        ["10.0.0.1:1", "10.0.0.1:2"]
    out = ClusterVolumeHeatGauge.collect()
    assert 'vid="901234"' in out and " 12.0" in out
    # the vid cools out of both servers' summaries: child pruned
    topo.sync_heartbeat(hb(1, []))
    topo.sync_heartbeat(hb(2, []), rack="r2")
    assert 'vid="901234"' not in ClusterVolumeHeatGauge.collect()
    assert topo.cluster_heat() == {}


# -- EC shard cloud-tiering (the COLD leg) ------------------------------------


def _build_ec_store(tmp_path, n=40, vid=1):
    from seaweedfs_tpu.ec import encoder, store_ec
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store
    store = Store([str(tmp_path)])
    store.add_volume(vid)
    v = store.find_volume(vid)
    for i in range(1, n + 1):
        v.write_needle(Needle(id=i, cookie=9,
                              data=f"payload-{i}".encode() * 30))
    v.read_only = True
    v.sync()
    base = v.file_name()
    encoder.write_ec_files(base, backend="numpy")
    encoder.write_sorted_file_from_idx(base)
    store.location_of(vid).delete_volume(vid)
    store_ec.mount_ec_shards(store, vid, "", range(14))
    return store


def test_ec_shard_tier_roundtrip(tmp_path):
    from seaweedfs_tpu.ec import store_ec
    from seaweedfs_tpu.storage import backend as bk
    from seaweedfs_tpu.storage import volume_tier
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import VolumeError

    bk.register_backend(bk.MemoryBackendStorage("memory.cold"))
    store = _build_ec_store(tmp_path)
    want = {i: store_ec.read_ec_needle(
        store, 1, Needle(id=i, cookie=9)).data for i in (1, 7, 40)}
    ecv = store.find_ec_volume(1)

    total = volume_tier.move_ec_shards_to_remote(
        ecv, "memory.cold", owner="127.0.0.1:8080")
    assert total > 0
    assert all(s.is_remote for s in ecv.shards.values())
    assert not any(os.path.exists(s.path) for s in ecv.shards.values())
    assert os.path.exists(ecv.base_name + ".ecx")   # index stays local
    # reads keep flowing, byte-identical, through ranged backend GETs
    for i, blob in want.items():
        assert store_ec.read_ec_needle(
            store, 1, Needle(id=i, cookie=9)).data == blob
    # idempotence contract: a second upload attempt is a typed error
    # the shell skips on ("already tiered")
    with pytest.raises(VolumeError, match="already tiered"):
        volume_tier.move_ec_shards_to_remote(ecv, "memory.cold")

    volume_tier.move_ec_shards_from_remote(ecv)
    assert not any(s.is_remote for s in ecv.shards.values())
    assert all(os.path.exists(s.path) for s in ecv.shards.values())
    assert bk.read_ec_tier_info(ecv.base_name) is None
    for i, blob in want.items():
        assert store_ec.read_ec_needle(
            store, 1, Needle(id=i, cookie=9)).data == blob
    store.close()


def test_ec_tier_sidecar_survives_restart(tmp_path):
    from seaweedfs_tpu.ec import store_ec
    from seaweedfs_tpu.storage import backend as bk
    from seaweedfs_tpu.storage import volume_tier
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store

    bk.register_backend(bk.MemoryBackendStorage("memory.cold"))
    store = _build_ec_store(tmp_path)
    want = store_ec.read_ec_needle(store, 1, Needle(id=3, cookie=9)).data
    volume_tier.move_ec_shards_to_remote(
        store.find_ec_volume(1), "memory.cold")
    store.close()
    # a restarted server loads the COLD volume purely from .ecx +
    # .ectier — no local shard bytes on disk
    store2 = Store([str(tmp_path)])
    ecv = store2.find_ec_volume(1)
    assert ecv is not None and len(ecv.shards) == 14
    assert all(s.is_remote for s in ecv.shards.values())
    assert store_ec.read_ec_needle(
        store2, 1, Needle(id=3, cookie=9)).data == want
    store2.close()


# -- the engine on a live cluster ---------------------------------------------


@pytest.fixture(scope="module")
def lifecycle_cluster(tmp_path_factory):
    from tests.cluster_util import Cluster
    cfg = LifecycleConfig(
        dry_run=True,              # phase 1 of the E2E flips this off
        interval_s=0.25,
        cool_threshold=0.5, warm_threshold=3.0,
        hot_dwell_s=1.2, warm_dwell_s=0.4, cold_dwell_s=0.4,
        max_inflight=4)
    c = Cluster(tmp_path_factory.mktemp("lifecycle"),
                n_volume_servers=3, pulse_seconds=0.2,
                volume_kwargs={"heat_track": True, "heat_window_s": 1.0},
                master_kwargs={"lifecycle": cfg})
    yield c
    c.stop()


def test_engine_cools_and_reheats_end_to_end(lifecycle_cluster):
    """The acceptance scenario: an idle volume is EC-encoded by the
    policy loop with no operator action, then restored to a replicated
    volume after sustained reads re-heat it — byte-identical reads
    throughout, both transitions on the metrics ledger and the /status
    Lifecycle block, and dry-run mode deciding without acting."""
    from seaweedfs_tpu.stats.metrics import LifecycleTransitionsCounter
    c = lifecycle_cluster
    engine = c.master.lifecycle
    assert engine is not None

    fid = c.upload(b"lifecycle-blob " * 200)
    vid = int(fid.split(",")[0])
    assert c.fetch(fid).read() == b"lifecycle-blob " * 200

    # phase 1 — dry run: the engine must DECIDE to encode but act zero
    # times (the volume stays a normal volume while decisions accrue)
    def dry_decision():
        return [d for d in engine.status()["decisions"]
                if d["vid"] == vid and d["kind"] == "encode"
                and d["outcome"] == "dry_run"]
    c.wait_for(dry_decision, timeout=20,
               what="dry-run encode decision")
    assert c.master.topo.lookup(vid), \
        "dry run must never transition a volume"
    assert engine.transitions_ok == 0

    # phase 2 — live: flip dry-run off (the test hook; operators
    # restart without -lifecycle.dryRun); the idle volume EC-encodes
    engine.cfg = engine.cfg._replace(dry_run=False)
    c.wait_for(lambda: vid in c.master.topo.ec_locations, timeout=30,
               what="policy-driven ec encode")
    c.wait_for(lambda: not c.master.topo.lookup(vid), timeout=10,
               what="original replicas retired")
    assert c.fetch(fid).read() == b"lifecycle-blob " * 200
    # the engine books the transition when the shell command has
    # RETURNED, the topology changes while it still runs: wait for the
    # last thing the engine writes before reading its ledger
    c.wait_for(lambda: any(d["vid"] == vid and d["kind"] == "encode"
                           and d["outcome"] == "ok"
                           for d in engine.status()["decisions"]),
               timeout=10, what="encode transition recorded")
    assert LifecycleTransitionsCounter.labels("encode", "ok").value >= 1
    assert engine.status()["states"]["warm"] >= 1

    # /status Lifecycle block over HTTP (the operator's view)
    with c.http(f"{c.master.url}/status") as r:
        st = json.load(r)
    assert st["Lifecycle"]["enabled"] is True
    assert any(d["vid"] == vid and d["outcome"] == "ok"
               for d in st["Lifecycle"]["decisions"])

    # phase 3 — sustained reads re-heat the EC volume past
    # warmThreshold; the engine decodes it back to a replicated volume.
    # Reads DURING the decode window can blip (ec.decode unmounts the
    # shards before the .dat exists) — only successful reads must be
    # byte-identical, and the final state must serve perfectly.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if c.master.topo.lookup(vid):
            break
        for _ in range(6):
            try:
                data = c.fetch(fid).read()
            except (OSError, AssertionError):
                break          # mid-transition blip; outer loop re-checks
            assert data == b"lifecycle-blob " * 200
        time.sleep(0.2)
    assert c.master.topo.lookup(vid), "re-heated volume never decoded"
    c.wait_for(lambda: vid not in c.master.topo.ec_locations,
               timeout=10, what="ec shards retired after decode")
    assert c.fetch(fid).read() == b"lifecycle-blob " * 200
    c.wait_for(lambda: LifecycleTransitionsCounter.labels(
        "decode", "ok").value >= 1, timeout=10,
        what="decode transition recorded")


def test_engine_control_plane_and_shell(lifecycle_cluster):
    from seaweedfs_tpu.shell import CommandError, Shell
    c = lifecycle_cluster
    engine = c.master.lifecycle
    sh = Shell(c.master.url)

    out = sh.run_command("volume.lifecycle -status")
    assert "lifecycle: running" in out or "PAUSED" in out

    # pause first so the POLICY can't race the rest of this test; the
    # engine keeps reconciling states and honoring forced transitions
    sh.run_command("volume.lifecycle -pause")
    assert engine.paused
    assert "PAUSED" in sh.run_command("volume.lifecycle -status")

    # cluster heat flows master-side through heartbeats
    fid = c.upload(b"heat-me")
    vid = int(fid.split(",")[0])
    for _ in range(4):
        c.fetch(fid).read()
    c.wait_for(lambda: vid in c.master.topo.cluster_heat(), timeout=10,
               what="heartbeat heat reaching the master")
    out = sh.run_command("cluster.heat")
    assert f"volume {vid}:" in out
    with c.http(f"{c.master.url}/cluster/heat") as r:
        heat = json.load(r)["volumes"]
    assert heat[str(vid)]["reads_window"] >= 1

    # force: bypasses thresholds and dwell entirely (and runs even
    # while the policy loop is paused — an explicit operator ask)
    c.wait_for(lambda: vid in engine.states, timeout=10,
               what="engine tracking the new volume")
    out = sh.run_command(f"volume.lifecycle -force -volumeId={vid} "
                         f"-target=warm")
    assert "encode queued" in out
    c.wait_for(lambda: vid in c.master.topo.ec_locations, timeout=30,
               what="forced encode")
    assert c.fetch(fid).read() == b"heat-me"

    # bad force targets are typed errors, not crashes
    with pytest.raises(CommandError, match="unknown target state"):
        sh.run_command(f"volume.lifecycle -force -volumeId={vid} "
                       f"-target=blazing")
    c.wait_for(lambda: vid in engine.states
               and engine.states[vid].state == WARM, timeout=10,
               what="forced state settling")
    with pytest.raises(CommandError, match="no single transition"):
        sh.run_command(f"volume.lifecycle -force -volumeId={vid} "
                       f"-target=warm")

    sh.run_command("volume.lifecycle -resume")
    assert not engine.paused


def test_warm_to_hot_uncool_roundtrip(tmp_path):
    """Satellite: the dedicated VolumeEcShardsToVolume E2E — encode,
    decode back to a replicated volume, reads byte-identical to
    pre-EC, and the decode invalidates both the heat ledger and the
    tiered read cache on the converting server."""
    from tests.cluster_util import Cluster

    from seaweedfs_tpu.shell import Shell
    c = Cluster(tmp_path, n_volume_servers=2, pulse_seconds=0.2,
                volume_kwargs={"heat_track": True, "cache_size_mb": 8})
    try:
        # uploads round-robin over the grown volumes; keep only the
        # blobs that landed on fid0's volume (the one we'll cycle)
        all_blobs = {}
        for i in range(12):
            body = f"uncool-{i}".encode() * 100
            all_blobs[c.upload(body)] = body
        fid0 = next(iter(all_blobs))
        vid = int(fid0.split(",")[0])
        blobs = {f: b for f, b in all_blobs.items()
                 if int(f.split(",")[0]) == vid}
        sh = Shell(c.master.url)
        pre_ec = {f: c.fetch(f).read() for f in blobs}
        assert pre_ec == blobs
        sh.run_command(f"ec.encode -volumeId={vid}")
        c.wait_for(lambda: vid in c.master.topo.ec_locations,
                   timeout=10, what="ec registration")
        # EC-era reads: heat the vid and populate the read cache
        for f, body in blobs.items():
            assert c.fetch(f).read() == body

        sh.run_command(f"ec.decode -volumeId={vid}")
        c.wait_for(lambda: c.master.topo.lookup(vid), timeout=10,
                   what="decoded volume registration")
        target = next(vs for vs in c.volume_servers
                      if vs.store.find_volume(vid) is not None)
        # conversion hygiene BEFORE any post-decode read re-heats it:
        # the decode target's heat ledger reset (VolumeEcShardsToVolume
        # forgets the EC era) and every server's EC-era cache entries
        # for the vid invalidated (shard delete + decode both fire it)
        assert target.heat.window_reads(vid) == 0
        nid = int(fid0.split(",")[1][:-8], 16)
        for vs in c.volume_servers:
            key = vs.read_cache.needle_key(vid, nid)
            assert vs.read_cache.get(key) is None
        c.wait_for(lambda: vid not in c.master.topo.ec_locations,
                   timeout=10, what="ec shards retired")
        # byte-identical to pre-EC on every blob
        for f, body in blobs.items():
            assert c.fetch(f).read() == body
    finally:
        c.stop()


def test_tier_upload_skips_already_tiered_holders(tmp_path):
    """Satellite: volume.tier.upload is idempotent over holders — a
    holder whose copy is already tiered is skipped instead of aborting
    the remaining-holder loop (the re-run shape the policy loop needs
    after a partial failure)."""
    from tests.cluster_util import Cluster

    from seaweedfs_tpu.pb import volume_stub, volume_server_pb2
    from seaweedfs_tpu.shell import Shell
    from seaweedfs_tpu.storage import backend as bk

    bk.register_backend(bk.MemoryBackendStorage("memory.cold"))
    c = Cluster(tmp_path, n_volume_servers=2, pulse_seconds=0.2,
                racks=["r1", "r2"])
    try:
        vs0, vs1 = c.volume_servers
        vid = 44
        for vs in (vs0, vs1):
            vs.store.add_volume(vid, "", replica_placement="010")
            vs.trigger_heartbeat()
        c.wait_for(lambda: len(c.master.topo.lookup(vid)) == 2,
                   what="replica registration")
        from seaweedfs_tpu.storage.needle import Needle
        for vs in (vs0, vs1):
            vs.store.write_needle(vid, Needle(id=1, cookie=7,
                                              data=b"tier-me" * 50))
        # pre-tier ONE holder by hand (simulating a partially-applied
        # earlier run)
        vs0.store.mark_volume_readonly(vid)
        list(volume_stub(vs0.url).VolumeTierMoveDatToRemote(
            volume_server_pb2.VolumeTierMoveDatToRemoteRequest(
                volume_id=vid, destination_backend_name="memory.cold")))
        sh = Shell(c.master.url)
        out = sh.run_command(
            f"volume.tier.upload -volumeId={vid} -dest=memory.cold")
        assert "already tiered, skipped" in out
        # the OTHER holder still got tiered (the loop didn't abort)
        assert sum("bytes -> memory.cold" in line
                   for line in out.splitlines()) == 1
        for vs in (vs0, vs1):
            assert vs.store.find_volume(vid).is_remote
        # reads still flow on both
        got = c.fetch(f"{vid},1{7:08x}").read()
        assert got == b"tier-me" * 50

        # the symmetric leg: restore ONE holder by hand, then the
        # command must skip it and still restore the other
        list(volume_stub(vs0.url).VolumeTierMoveDatFromRemote(
            volume_server_pb2.VolumeTierMoveDatFromRemoteRequest(
                volume_id=vid)))
        out = sh.run_command(f"volume.tier.download -volumeId={vid}")
        assert "already local, skipped" in out
        assert sum("bytes restored" in line
                   for line in out.splitlines()) == 1
        for vs in (vs0, vs1):
            assert not vs.store.find_volume(vid).is_remote
        assert c.fetch(f"{vid},1{7:08x}").read() == b"tier-me" * 50
    finally:
        c.stop()
