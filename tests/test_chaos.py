"""Chaos harness (ISSUE 6): kill, stall, and fault-inject servers in a
real in-process cluster under concurrent load, and assert the
resilience invariants end to end:

  - reads return byte-identical data or a correct typed error, always
    before their deadline
  - the dead peer's circuit breaker opens, then recovers after the
    peer returns
  - hedged reads keep the stalled-shard tail bounded while spending
    <= 5% extra requests
  - no test leaks threads (the conftest non-daemon audit runs on
    every case here)

Volume placement is pinned by registering volumes directly on chosen
servers (heartbeats advertise them to the master like any other
volume), so each scenario targets exactly the replica pair it means
to."""

import threading
import time

import pytest

from seaweedfs_tpu.resilience import (DeadlineExceeded, Hedger, breaker,
                                      deadline, failpoint)
from seaweedfs_tpu.util import http_client
from tests.cluster_util import Cluster

COOKIE = 0xABCDEF01


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    yield
    failpoint.disarm()
    breaker.reset()
    http_client.close_all()


def _fid(vid: int, key: int) -> str:
    return f"{vid},{key:x}{COOKIE:08x}"


def _place_volume(cluster, vid: int, servers) -> None:
    """Register `vid` on exactly `servers` (replication 010 so writes
    fan out) and wait until the master's lookup sees every copy."""
    import json

    for vs in servers:
        vs.store.add_volume(vid, "", replica_placement="010")
        vs.trigger_heartbeat()

    def registered():
        with cluster.http(f"{cluster.master.url}/dir/lookup"
                          f"?volumeId={vid}") as r:
            locs = json.load(r).get("locations") or []
        return len(locs) == len(servers)

    cluster.wait_for(registered, what=f"volume {vid} on all replicas")


def _upload(url: str, fid: str, data: bytes) -> None:
    r = http_client.request("POST", f"{url}/{fid}", body=data,
                            headers={"Content-Type":
                                     "application/octet-stream"})
    assert r.status == 201, (r.status, r.body)


def _read_one(url: str, fid: str, timeout: float = 4.0) -> bytes:
    r = http_client.request("GET", f"{url}/{fid}", timeout=timeout)
    if r.status != 200:
        raise IOError(f"GET {url}/{fid}: http {r.status}")
    return r.body


def _p(values, q: float) -> float:
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, int(q * len(ordered)))]


def test_chaos_end_to_end(tmp_path):
    """The acceptance scenario: a dead replica (injected connect
    failure) and a 2s-stalled volume under 32-way concurrent load."""
    cluster = Cluster(tmp_path, n_volume_servers=3,
                      racks=["r1", "r2", "r3"])
    try:
        vs_healthy, vs_dead, vs_stall = cluster.volume_servers
        VID_DEAD, VID_STALL, VID_PLAIN = 101, 102, 103
        _place_volume(cluster, VID_DEAD, [vs_healthy, vs_dead])
        _place_volume(cluster, VID_STALL, [vs_healthy, vs_stall])
        _place_volume(cluster, VID_PLAIN, [vs_healthy, vs_dead])

        blobs = {}
        for i in range(1, 9):
            for vid, primary in ((VID_DEAD, vs_healthy),
                                 (VID_STALL, vs_healthy),
                                 (VID_PLAIN, vs_healthy)):
                fid = _fid(vid, i)
                blobs[fid] = (f"chaos-{fid}-".encode() * 97)[:4096]
                _upload(primary.url, fid, blobs[fid])

        breaker.configure(enable=True, threshold=3, cooldown_s=1.0)
        # wide lanes: 32 threads × (primary + hedge) must never force
        # the saturation fallback, or a stalled primary can't hedge
        hedger = Hedger(delay_floor_s=0.05, budget_pct=0.05,
                        max_inflight=96, name="chaos-hedge")

        def hedged_read(fid: str, candidates) -> bytes:
            with deadline.budget(5.0):
                urls = breaker.sort_candidates(candidates)
                return hedger.fetch(
                    [lambda u=u: _read_one(u, fid) for u in urls])

        # -- baseline: healthy tail, breakers closed ----------------------
        healthy_lat = []
        for i in range(1, 9):
            t0 = time.perf_counter()
            got = hedged_read(_fid(VID_PLAIN, i),
                              [vs_healthy.url, vs_dead.url])
            healthy_lat.append(time.perf_counter() - t0)
            assert got == blobs[_fid(VID_PLAIN, i)]

        # -- inject: vs_dead unreachable, VID_STALL stalled on vs_stall ---
        http_client.close_all()   # pooled sockets would dodge connect
        failpoint.arm("http.connect", "error",
                      match={"peer": vs_dead.url})
        failpoint.arm("volume.read", "delay", arg=2.0,
                      match={"server": vs_stall.url,
                             "vid": str(VID_STALL)})

        results = {}            # fid -> set of byte payloads seen
        errors = []
        stall_lat, all_lat = [], []
        lock = threading.Lock()
        READS_PER_THREAD = 50

        def worker(widx: int):
            for it in range(READS_PER_THREAD):
                key = (widx + it) % 8 + 1
                if it == 10 + widx % 20:
                    # one stalled-primary read per thread, spread out
                    fid = _fid(VID_STALL, key)
                    candidates = [vs_stall.url, vs_healthy.url]
                    bucket = stall_lat
                elif it % 8 == 0:
                    # dead-primary reads: breaker + failover path
                    fid = _fid(VID_DEAD, key)
                    candidates = [vs_dead.url, vs_healthy.url]
                    bucket = None
                else:
                    # plain reads are single-candidate: hedging only
                    # applies where another replica exists, and a GIL
                    # latency spike on a replica-less read must not
                    # burn hedge budget on a candidate that cannot help
                    fid = _fid(VID_PLAIN, key)
                    candidates = [vs_healthy.url]
                    bucket = None
                t0 = time.perf_counter()
                try:
                    got = hedged_read(fid, candidates)
                except Exception as e:  # noqa: BLE001 - asserted below
                    with lock:
                        errors.append((fid, repr(e)))
                    continue
                dt = time.perf_counter() - t0
                with lock:
                    all_lat.append(dt)
                    if bucket is not None:
                        bucket.append(dt)
                    results.setdefault(fid, set()).add(got)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads), "workers wedged"

        # 1. every read byte-identical or a typed error — here the
        # failover/hedge paths cover both faults, so no errors at all
        assert not errors, errors[:5]
        for fid, seen in results.items():
            assert seen == {blobs[fid]}, f"{fid}: non-identical bytes"
        # 2. every read beat its 5s budget (deadline honored e2e)
        assert max(all_lat) < 5.0
        # 3. the dead peer's breaker opened under load
        assert breaker.for_peer(vs_dead.url).state == breaker.OPEN
        # 4. hedged reads bounded the stalled tail — stated as COUNTS,
        # so it holds on a loaded machine (ROADMAP C8). A stalled read
        # may wait out the injected 2s stall only when the hedge budget
        # refused it: under CPU load plain two-candidate reads cross
        # the 50ms hedge delay too and spend part of the 5% budget
        # (invariant 5, which always wins), so the number of reads
        # that waited is bounded by the hedger's own count of budget
        # refusals, never by the clock. Every read that WAS hedged
        # beat the stall, and their p90 stays within 3x the healthy
        # p99 (with an absolute floor of half the stall for jitter).
        healthy_p99 = max(_p(healthy_lat, 0.99), _p(all_lat, 0.5))
        assert len(stall_lat) == 32
        rescued = [s for s in stall_lat if s < 1.9]
        waited = len(stall_lat) - len(rescued)
        assert waited <= hedger.denied, \
            f"{waited} stalled reads waited out the stall but the " \
            f"budget refused only {hedger.denied} hedges " \
            f"(slowest {max(stall_lat):.3f}s)"
        assert len(rescued) >= len(stall_lat) // 2, \
            f"only {len(rescued)} of {len(stall_lat)} stalled reads " \
            f"beat the stall ({hedger.denied} hedges refused)"
        assert _p(rescued, 0.9) <= max(3 * healthy_p99, 1.0), \
            f"hedged stalled p90 {_p(rescued, 0.9):.3f}s " \
            f"vs healthy {healthy_p99:.3f}s"
        # 5. hedge budget: <= 5% extra requests (+1 burst allowance)
        assert hedger.hedges <= 0.05 * hedger.requests + 2, \
            f"{hedger.hedges} hedges for {hedger.requests} requests"
        assert hedger.hedges >= len(stall_lat) // 2, \
            "stalled reads were not hedging at all"
        assert hedger.wins >= len(stall_lat) // 2, \
            "hedges were issued but never won against the stall"

        # -- recovery: the dead peer returns ------------------------------
        failpoint.disarm("http.connect")
        time.sleep(1.1)           # past the breaker cooldown
        got = hedged_read(_fid(VID_DEAD, 1), [vs_dead.url,
                                              vs_healthy.url])
        assert got == blobs[_fid(VID_DEAD, 1)]
        assert breaker.for_peer(vs_dead.url).state == breaker.CLOSED
    finally:
        cluster.stop()


def test_deadline_propagates_filer_to_volume(tmp_path):
    """X-Seaweed-Deadline rides the filer -> volume chain: a stalled
    volume read makes the filer give up when the CLIENT's budget says
    so, not after its own 60s timeouts."""
    cluster = Cluster(tmp_path, n_volume_servers=1, with_filer=True)
    try:
        vs = cluster.volume_servers[0]
        payload = b"deadline-payload " * 1024
        for name in ("f1", "f2"):
            with cluster.http(f"{cluster.filer.url}/chaos/{name}",
                              data=payload, method="POST") as r:
                assert r.status == 201
        # sanity: readable without a budget
        with cluster.http(f"{cluster.filer.url}/chaos/f1") as r:
            assert r.read() == payload

        failpoint.arm("volume.read", "delay", arg=1.5,
                      match={"server": vs.url})
        import urllib.error
        t0 = time.perf_counter()
        with pytest.raises(urllib.error.HTTPError) as ei:
            # f2 was never read, so the filer's chunk cache cannot
            # answer — the read MUST cross the stalled volume hop
            cluster.http(f"{cluster.filer.url}/chaos/f2",
                         headers={"X-Seaweed-Deadline": "0.4"})
        elapsed = time.perf_counter() - t0
        # the filer surfaced a typed failure (504 budget-spent or 500
        # no-reachable-replica after the budget-sized timeout) well
        # before the 1.5s stall, let alone its own 60s client timeout
        assert ei.value.code in (500, 504)
        assert elapsed < 1.2, f"filer ignored the budget ({elapsed:.2f}s)"

        failpoint.disarm()
        with cluster.http(f"{cluster.filer.url}/chaos/f2") as r:
            assert r.read() == payload
    finally:
        cluster.stop()


def test_deadline_refuses_work_client_side(tmp_path):
    """An exhausted ambient budget refuses outbound work instantly —
    no socket is opened for a caller that already gave up."""
    cluster = Cluster(tmp_path, n_volume_servers=1)
    try:
        fid = cluster.upload(b"x" * 100)
        import json
        with cluster.http(f"{cluster.master.url}/dir/lookup"
                          f"?volumeId={fid}") as r:
            url = json.load(r)["locations"][0]["url"]
        with deadline.budget(5.0):
            assert http_client.request(
                "GET", f"{url}/{fid}").status == 200
        with deadline.budget(0.0):
            t0 = time.perf_counter()
            with pytest.raises(DeadlineExceeded):
                http_client.request("GET", f"{url}/{fid}")
            assert time.perf_counter() - t0 < 0.1
    finally:
        cluster.stop()


@pytest.mark.slow
def test_chaos_kill_and_restart_replica(tmp_path):
    """REAL death (server stopped, port closed), not just an injected
    connect error: reads fail over, the breaker opens, and a
    replacement server on the same port brings the breaker back to
    closed."""
    from seaweedfs_tpu.server.volume import VolumeServer

    cluster = Cluster(tmp_path, n_volume_servers=2,
                      racks=["r1", "r2"])
    try:
        vs0, vs1 = cluster.volume_servers
        VID = 201
        _place_volume(cluster, VID, [vs0, vs1])
        blobs = {}
        for i in range(1, 5):
            fid = _fid(VID, i)
            blobs[fid] = (f"kill-{fid}-".encode() * 211)[:4096]
            _upload(vs0.url, fid, blobs[fid])

        breaker.configure(enable=True, threshold=3, cooldown_s=0.5)
        dead_port, dead_dir = vs1.port, vs1.store.locations[0].directory
        vs1.stop()
        http_client.close_all()

        def failover_read(fid):
            for u in breaker.sort_candidates([vs1.url, vs0.url]):
                try:
                    return _read_one(u, fid, timeout=2.0)
                except OSError:
                    continue
            raise IOError("no replica answered")

        for round_ in range(6):
            fid = _fid(VID, round_ % 4 + 1)
            assert failover_read(fid) == blobs[fid]
        assert breaker.for_peer(vs1.url).state == breaker.OPEN

        replacement = None
        deadline_t = time.monotonic() + 15
        while replacement is None:
            try:
                replacement = VolumeServer(
                    master_url=cluster.master.url,
                    directories=[dead_dir], port=dead_port,
                    pulse_seconds=0.2, ec_encoder="numpy", rack="r2")
                replacement.start()
            except OSError:
                replacement = None
                if time.monotonic() > deadline_t:
                    raise
                time.sleep(0.2)
        try:
            time.sleep(0.6)   # past the breaker cooldown
            for i in range(1, 5):
                fid = _fid(VID, i)
                assert failover_read(fid) == blobs[fid]
            cluster.wait_for(
                lambda: breaker.for_peer(vs1.url).state == breaker.CLOSED,
                what="breaker recovery after replica restart")
        finally:
            replacement.stop()
    finally:
        cluster.stop()
