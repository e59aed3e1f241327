"""Scrub daemon: the volume server's background integrity thread.

One daemon per server. Constructing it costs nothing — no thread, no
IO — until start() is called (the scrub-disabled perf gate in
tests/test_perf_gates.py holds the server to that). A pass walks every
mounted volume and EC volume:

  0. one verifier an EC volume: its shards are spread over servers, and
     in a pass that every holder runs (volume.scrub fanned out to all
     servers, or every server's scheduled pass) exactly one holder
     verifies it — of its holders in url order, the (vid mod their
     number)-th, or the first after it that answers — from the master's
     shard locations (`peers`), which every holder reads alike. The
     other holders defer the volume (SeaweedFS_scrub_ec_volumes_total
     {role}): they neither verify it nor sweep its needles, and their
     report does not name it. A pass started on this server alone
     (`alone`: volume.scrub -node) defers nothing. The verifier reads
     the rows it lacks from their holders; a volume one of whose rows
     no holder gives is declined (not held against its parity), and
     the pass goes on;
  1. needle sweep per normal volume (scanner.scan_volume), corrupt
     needles re-fetched from replicas (planner.repair_needle);
  2. ONE fused stripe verify across ALL the EC volumes the server
     verifies (fleet_verify_ec_files), on the encode and rebuild passes' loop
     and staging buffers. On the jax backend the stored parity goes to
     the device beside the data shards, [14, lanes] a dispatch, and
     counts come back, not parity; host codecs compare on the writer
     lanes. The EC needle sweep rides it: each volume's .ecx is walked
     before it (scanner.StagedSweep) and every live needle is checked
     in the data-shard bytes the verify has staged, on the volume's
     writer lane, so a pass reads its data shards once;
  3. the rest of the EC needle sweep: the copied path for a needle the
     staged check did not find clean, and a read (from the shard or its
     holder) of every needle it could not see (a volume the verify
     declined, a tiered shard, a needle over more than two spans, the
     mesh path), localizing bad data shards by exclusion;
  4. per damaged EC volume: classify -> quarantine .corrupt ->
     fleet rebuild -> re-verify (a data repair un-contaminates the
     parity evidence; round two condemns genuinely bad parity). A
     condemned shard another server holds is rebuilt BY that holder,
     in place (repair_held, asked through `peers`): shards never move.

Each step is a phase of SeaweedFS_scrub_phase_seconds (scan, scan_ec,
verify, repair, reverify) and, while the span ring is on, a span
scrub.<phase>. A pass leaves a report: what it concluded volume by
volume (PassResult.verdicts), or that it failed and why. wait_pass()
blocks until a pass has ended; `volume.scrub -wait` prints the report.

Pacing rides util.throttler.Throttler (burst-capped), so an idle-hour
backlog can't turn into a full-rate IO storm. pause() takes effect at
volume granularity; start() on a paused daemon resumes it. Counters
feed both the per-server status RPC and the global SeaweedFS_scrub_*
Prometheus families.
"""

from __future__ import annotations

import functools
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from seaweedfs_tpu.ec import fleet
from seaweedfs_tpu.scrub import planner, scanner
from seaweedfs_tpu.scrub.phases import phase
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import (
    ScrubCorruptionsFoundCounter, ScrubCorruptionsRepairedCounter,
    ScrubEcVolumesCounter, ScrubNeedlesVerifiedCounter,
    ScrubPassSecondsHistogram, ScrubScanLagGauge, ScrubScannedBytesCounter,
    ScrubStripesVerifiedCounter, ScrubUnrecoverableCounter)
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.util import wlog
from seaweedfs_tpu.util.throttler import Throttler

log = wlog.logger("scrub")

# children resolved once at import: labels() takes a lock per call
_EC_VOLUMES = {role: ScrubEcVolumesCounter.labels(role)
               for role in ("verified", "deferred")}


@dataclass
class VolumeVerdict:
    """What one pass concluded about one volume it covered: nothing set
    is `clean`."""

    rebuilt_shards: List[int] = field(default_factory=list)  # condemned,
    #                              quarantined and rebuilt (EC volumes)
    needles_repaired: int = 0    # rewritten from a replica
    unrecoverable: int = 0       # shards / needles left damaged


@dataclass
class PassResult:
    """What one scrub pass saw and did."""

    bytes_scanned: int = 0
    needles_verified: int = 0
    stripes_verified: int = 0
    corruptions_found: int = 0
    corruptions_repaired: int = 0
    unrecoverable: int = 0
    volumes: int = 0
    ec_volumes: int = 0
    details: List[str] = field(default_factory=list)
    # the pass's report (not summed into the ledger): every volume it
    # covered, and whether it ran to its end
    verdicts: Dict[int, VolumeVerdict] = field(default_factory=dict)
    failed: bool = False
    error: str = ""
    seconds: float = 0.0


class ScrubPaused(Exception):
    """Raised inside a pass when stop() interrupts it."""


class ScrubDaemon:
    """start/pause/status control plane over the scanner + planner."""

    def __init__(self, store: Store, mbps: float = 0.0,
                 backend: str = "auto", interval_s: float = 0.0,
                 replica_fetch: Optional[Callable] = None,
                 export_lag: bool = True,
                 on_repair: Optional[Callable[[int], None]] = None,
                 mesh_cfg: Optional[dict] = None,
                 peers=None):
        self.store = store
        self.mbps = mbps
        self.backend = backend
        self.interval_s = interval_s
        self.replica_fetch = replica_fetch
        # -ec.mesh* knobs: when set, the fused stripe verify rides the
        # unified pod-scale scheduler (parallel/mesh_fleet), falling
        # back to the host fleet verifier on any MeshError
        self.mesh_cfg = mesh_cfg
        # on_repair(vid) fires after scrub rewrites any bytes of a
        # volume (needle rewrite or EC shard reconstruction) — the
        # volume server hangs read-cache invalidation here so a repair
        # can never serve a pre-repair cached blob
        self.on_repair = on_repair
        # the other servers, as the volume server sees them: `url` (this
        # server's), `locations(vid)` ({shard id: [holder urls]}, asked
        # of the master afresh), `alive(url)`, `fill(vid, sid, offset,
        # view)` (a row from a holder, fleet.RemoteRows.fill), `reader(
        # vid)` (bytes of an interval from a holder, or None) and
        # `repair(url, vid, sids)` (a holder rebuilds condemned shards
        # it holds). None: every EC volume here is this server's alone
        self.peers = peers
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None  # guarded_by(self._lock)
        self._resume = threading.Event()
        self._resume.set()            # not paused
        self._wake = threading.Event()  # interval sleep interrupt
        # the pass thread polls these lock-free (loop conditions /
        # status); every WRITE takes the lock so start/stop/pause
        # serialize against each other
        self._stopping = False  # guarded_by(self._lock, writes)
        # overrides for the FIRST pass of a freshly-started thread
        # only: a targeted/throttled start must never narrow or
        # re-budget the later periodic passes (written under the lock
        # BEFORE the thread spawns — happens-before via Thread.start)
        self._pass_volume_ids: Optional[List[int]] = None  # guarded_by(self._lock, writes)
        self._pass_mbps: Optional[float] = None  # guarded_by(self._lock, writes)
        self._pass_alone = False  # guarded_by(self._lock, writes)
        self._state = "idle"  # guarded_by(self._lock, writes)
        self.current_volume_id = 0
        self.passes_completed = 0
        self.last_pass_unix = 0.0
        self.totals = PassResult()
        # passes that ran to an end, completed or failed, and the last
        # one's report: what wait_pass() waits on
        self._ended = threading.Condition()
        self.passes_ended = 0  # guarded_by(self._ended, writes)
        self.last_pass: Optional[PassResult] = None  # guarded_by(self._ended, writes)
        if export_lag:
            # weakref: the gauge is process-global and must neither pin
            # a dead daemon's Store in memory nor keep reporting it
            ref = weakref.ref(self)
            ScrubScanLagGauge.set_function(
                lambda: d._scan_lag() if (d := ref()) is not None else 0.0)

    def _scan_lag(self) -> float:
        """Seconds since the last completed pass — evaluated at metric
        COLLECTION time, so a stalled scrubber's lag keeps growing on
        every Prometheus scrape instead of freezing at the last
        status() call."""
        return round(time.time() - self.last_pass_unix, 3) \
            if self.last_pass_unix else 0.0

    # -- control -------------------------------------------------------------

    def start(self, volume_ids: Optional[Sequence[int]] = None,
              throttle_mbps: Optional[float] = None,
              full: bool = False, alone: bool = False) -> bool:
        """Begin a pass (or resume a paused one). Returns False when a
        pass is already running un-paused — and in that case changes
        NOTHING (a rejected start must not retarget or re-budget the
        running work). `alone`: no other holder runs this pass, so it
        verifies every EC volume held here and defers none."""
        with self._lock:
            if self._stopping:
                return False
            if self._thread is not None and self._thread.is_alive():
                if not self._resume.is_set():
                    self._state = "running"
                    self._resume.set()   # un-pause
                    return True
                self._wake.set()         # cut an interval sleep short
                return False
            if full:
                self.totals = PassResult()
                self.passes_completed = 0
            # overrides apply to the first pass only; the interval
            # loop reverts to whole-store scope and the server budget
            self._pass_volume_ids = list(volume_ids) if volume_ids else None
            self._pass_mbps = throttle_mbps \
                if throttle_mbps is not None and throttle_mbps > 0 else None
            self._pass_alone = alone
            self._state = "running"
            self._resume.set()
            # lint: thread-ok(scrub daemon paced by -scrubMBps; no request context)
            self._thread = threading.Thread(
                target=self._run, name="scrub-daemon", daemon=True)
            self._thread.start()
            return True

    def pause(self) -> bool:
        """Hold the pass at the next volume boundary. Returns True if
        there was a live pass to pause."""
        with self._lock:
            alive = self._thread is not None and self._thread.is_alive()
            if alive:
                self._state = "paused"
            self._resume.clear()
            return alive

    def stop(self) -> None:
        # _stopping must flip under the lock: the unlocked write could
        # land AFTER a concurrent start() passed its _stopping check
        # but BEFORE it spawned — stop() would then join the OLD
        # (dead) thread while a fresh pass thread sails on past
        # shutdown (guard-check finding, ISSUE 10; regression test
        # under the schedule explorer in tests/test_scheduler.py)
        with self._lock:
            self._stopping = True
            t = self._thread
        self._resume.set()
        self._wake.set()
        if t is not None:
            t.join(timeout=10)
        with self._lock:
            self._state = "idle"
        with self._ended:
            self._ended.notify_all()

    def wait_pass(self, after: int, timeout: Optional[float] = None) -> bool:
        """Block until more than `after` passes have ended (completed
        or failed; start() and status() give the count), the daemon is
        stopping, or `timeout` seconds are over. True unless it timed
        out."""
        with self._ended:
            return self._ended.wait_for(
                lambda: self.passes_ended > after or self._stopping,
                timeout)

    def _pass_ended(self, res: PassResult) -> None:
        with self._ended:
            self.passes_ended += 1
            self.last_pass = res
            self._ended.notify_all()

    def status(self) -> Dict:
        lag = self._scan_lag()
        t = self.totals
        return {
            "state": self._state,
            "bytes_scanned": t.bytes_scanned,
            "needles_verified": t.needles_verified,
            "stripes_verified": t.stripes_verified,
            "corruptions_found": t.corruptions_found,
            "corruptions_repaired": t.corruptions_repaired,
            "unrecoverable": t.unrecoverable,
            "current_volume_id": self.current_volume_id,
            "passes_completed": self.passes_completed,
            "last_pass_unix": self.last_pass_unix,
            "scan_lag_seconds": lag,
            "passes_ended": self.passes_ended,
        }

    # -- the pass ------------------------------------------------------------

    def _checkpoint(self, vid: int) -> None:
        """Between-volumes barrier: block while paused, abort on stop."""
        self.current_volume_id = vid
        while not self._resume.wait(timeout=0.5):
            if self._stopping:
                raise ScrubPaused()
        if self._stopping:
            raise ScrubPaused()

    def _run(self) -> None:
        # the whole daemon runs as the _internal QoS tenant: its
        # replica/shard fetches are weighted low on every fan-out pool
        # and exempt from admission shed (repair trades latency for
        # durability, never the other way). No-op context when QoS off.
        from seaweedfs_tpu import qos
        vids, mbps = self._pass_volume_ids, self._pass_mbps
        alone = self._pass_alone
        res = None                   # a pass that ended, not yet said so
        while not self._stopping:
            try:
                with qos.internal_context():
                    res, exc = self._sweep(vids, mbps, alone)
            except ScrubPaused:
                return
            if exc is not None:
                log.error("scrub pass failed", exc_info=exc)
            # later passes: whole store, server budget, every server's
            vids, mbps, alone = None, None, False
            if self.interval_s <= 0:
                break
            self._pass_ended(res)
            res = None
            self._wake.wait(timeout=self.interval_s)
            self._wake.clear()
        # The last thing this thread does is say that its pass has
        # ended, with the daemon already in its final state and free to
        # be started: whoever waited may start the next pass at once.
        with self._lock:
            if not self._stopping:   # stop() owns the final state
                self._state = "failed" if res is not None and res.failed \
                    else "idle"
                self._thread = None
        if res is not None:
            self._pass_ended(res)

    def run_pass(self, volume_ids: Optional[Sequence[int]] = None,
                 mbps: Optional[float] = None,
                 alone: bool = False) -> PassResult:
        """One synchronous sweep over everything mounted locally."""
        res, exc = self._sweep(volume_ids, mbps, alone)
        self._pass_ended(res)
        if exc is not None:
            raise exc
        return res

    def _sweep(self, volume_ids: Optional[Sequence[int]],
               mbps: Optional[float], alone: bool = False):
        """-> (the pass's report, what it raised or None). A pass that
        raised ended too, as failed: whoever waits for it learns so
        instead of reading an idle daemon. A stop (ScrubPaused) is no
        end and leaves no report."""
        res = PassResult()
        mbps = self.mbps if mbps is None else mbps
        throttler = Throttler(mbps) if mbps > 0 else None
        t0 = time.perf_counter()
        only = set(volume_ids) if volume_ids else None
        try:
            with trace.span("scrub.pass"):
                self._scan_volumes(res, throttler, only)
                self._scan_ec_volumes(res, throttler, only, alone)
        except ScrubPaused:
            raise
        except Exception as e:
            res.failed, res.error = True, f"{type(e).__name__}: {e}"
            res.seconds = time.perf_counter() - t0
            return res, e
        res.seconds = time.perf_counter() - t0
        ScrubPassSecondsHistogram.observe(res.seconds)
        self.last_pass_unix = time.time()
        self.passes_completed += 1
        self.current_volume_id = 0
        self._accumulate(res)
        return res, None

    def _accumulate(self, res: PassResult) -> None:
        t = self.totals
        t.bytes_scanned += res.bytes_scanned
        t.needles_verified += res.needles_verified
        t.stripes_verified += res.stripes_verified
        t.corruptions_found += res.corruptions_found
        t.corruptions_repaired += res.corruptions_repaired
        t.unrecoverable += res.unrecoverable
        t.volumes += res.volumes
        t.ec_volumes += res.ec_volumes
        t.details.extend(res.details)
        del t.details[:-100]   # ring: keep the newest hundred findings

    def _scan_volumes(self, res: PassResult, throttler, only) -> None:
        for loc in self.store.locations:
            for vid, v in list(loc.volumes.items()):
                if only is not None and vid not in only:
                    continue
                if v.is_remote:
                    continue  # cloud-tiered bytes are the backend's
                self._checkpoint(vid)
                scan = scanner.scan_volume(v, throttler)
                verdict = res.verdicts.setdefault(vid, VolumeVerdict())
                res.volumes += 1
                res.bytes_scanned += scan.bytes_scanned
                res.needles_verified += scan.needles_verified
                ScrubScannedBytesCounter.inc(scan.bytes_scanned)
                ScrubNeedlesVerifiedCounter.inc(scan.needles_verified)
                for offset, n in scan.corrupt:
                    res.corruptions_found += 1
                    ScrubCorruptionsFoundCounter.labels("needle").inc()
                    log.warning("volume %d: needle %x at %d fails CRC",
                                vid, n.id, offset)
                    if self.replica_fetch is not None and \
                            planner.repair_needle(v, n, self.replica_fetch):
                        res.corruptions_repaired += 1
                        verdict.needles_repaired += 1
                        ScrubCorruptionsRepairedCounter.labels(
                            "needle").inc()
                        if self.on_repair is not None:
                            self.on_repair(vid)
                        res.details.append(
                            f"volume {vid}: needle {n.id:x} rewritten "
                            f"from replica")
                    else:
                        res.unrecoverable += 1
                        verdict.unrecoverable += 1
                        ScrubUnrecoverableCounter.inc()
                        res.details.append(
                            f"volume {vid}: needle {n.id:x} corrupt, "
                            f"no healthy replica")

    # -- EC volumes: who verifies what -------------------------------------

    def _holders(self, vid: int, ecv) -> Dict[int, List[str]]:
        """{shard id: holder urls} of one EC volume, this server's own
        shards among them whatever the master has heard yet."""
        me = self.peers.url if self.peers is not None else ""
        locs = {} if self.peers is None else {
            sid: list(urls) for sid, urls in self.peers.locations(vid).items()}
        for sid in ecv.shards:
            if me not in locs.setdefault(sid, []):
                locs[sid].append(me)
        return locs

    def _verifier(self, vid: int, locs: Dict[int, List[str]], alive) -> str:
        """The one holder that verifies volume `vid` this pass, by the
        rule every holder applies to the same locations: of the holders
        in url order, the (vid mod their number)-th, or the first after
        it that answers. Consecutive ids on one set of holders take
        each holder in turn."""
        me = self.peers.url if self.peers is not None else ""
        holders = sorted({url for urls in locs.values() for url in urls})
        for j in range(len(holders)):
            url = holders[(vid + j) % len(holders)]
            if url == me or alive(url):
                return url
        return me

    def _remote_rows(self, vid: int, ecv, locs: Dict[int, List[str]]
                     ) -> Optional[fleet.RemoteRows]:
        """The rows of the shards of `vid` that other servers hold."""
        if self.peers is None:
            return None
        far = frozenset(sid for sid, urls in locs.items()
                        if sid not in ecv.shards
                        and any(u != self.peers.url for u in urls))
        if not far:
            return None
        return fleet.RemoteRows(vid, far, ecv.shard_size,
                                functools.partial(self.peers.fill, vid))

    def _mine(self, ecvs, alone: bool
              ) -> Tuple[list, Dict[int, Dict[int, List[str]]]]:
        """The EC volumes this server verifies this pass, with their
        holders; the rest are deferred to their verifier (none when the
        pass runs here `alone`)."""
        answered: Dict[str, bool] = {}   # one probe a peer a pass

        def alive(url: str) -> bool:
            if url not in answered:
                answered[url] = self.peers.alive(url)
            return answered[url]

        me = self.peers.url if self.peers is not None else ""
        mine, holders = [], {}
        for vid, ecv in ecvs:
            locs = self._holders(vid, ecv)
            if not alone and self._verifier(vid, locs, alive) != me:
                _EC_VOLUMES["deferred"].inc()
                continue
            _EC_VOLUMES["verified"].inc()
            mine.append((vid, ecv))
            holders[vid] = locs
        return mine, holders

    def _scan_ec_volumes(self, res: PassResult, throttler, only,
                         alone: bool = False) -> None:
        ecvs = [(vid, ecv)
                for loc in self.store.locations
                for vid, ecv in list(loc.ec_volumes.items())
                if only is None or vid in only]
        if not ecvs:
            return
        ecvs, holders = self._mine(ecvs, alone)
        if not ecvs:
            return
        remote = {}
        for vid, ecv in ecvs:
            rows = self._remote_rows(vid, ecv, holders[vid])
            if rows is not None:
                remote[ecv.base_name] = rows
        # each volume's .ecx walked once, at the pass's start: its
        # needles are checked in the bytes the stripe verify reads
        staged: Dict[str, scanner.StagedSweep] = {}
        for vid, ecv in ecvs:
            self._checkpoint(vid)
            with phase("scan_ec", vid=vid):
                staged[ecv.base_name] = scanner.StagedSweep(ecv)
            res.verdicts.setdefault(vid, VolumeVerdict())
            res.ec_volumes += 1
        # ONE fused verify across the whole fleet of EC volumes this
        # server verifies: spans from every volume share RS dispatches
        self._checkpoint(0)
        by_base = {ecv.base_name: (vid, ecv) for vid, ecv in ecvs}
        with phase("verify", volumes=len(by_base)):
            mesh_fleet = fleet.mesh_fleet_or_none() \
                if self.mesh_cfg is not None and not remote else None
            if mesh_fleet is not None:
                verified = mesh_fleet.pod_verify_ec_files(
                    list(by_base), backend=self.backend,
                    throttler=throttler, **self.mesh_cfg)
            else:
                verified = fleet.fleet_verify_ec_files(
                    list(by_base), backend=self.backend,
                    throttler=throttler, remote=remote,
                    on_span=lambda base, offset, valid, rows:
                    staged[base].take(offset, valid, rows))
        # the needle sweep's rest: the copied path for what the staged
        # check did not accept, the shard files (or their holders) for
        # what it never saw
        damages: Dict[int, planner.EcDamage] = {}
        for vid, ecv in ecvs:
            self._checkpoint(vid)
            scan = scanner.scan_ec_volume_needles(
                ecv, throttler=throttler, staged=staged[ecv.base_name],
                fetch=self._fetch(vid))
            res.bytes_scanned += scan.bytes_scanned
            res.needles_verified += scan.needles_verified
            ScrubScannedBytesCounter.inc(scan.bytes_scanned)
            ScrubNeedlesVerifiedCounter.inc(scan.needles_verified)
            if scan.corrupt:
                log.warning("ec volume %d: %d needle(s) fail CRC "
                            "(bad data shards: %s)", vid,
                            len(scan.corrupt),
                            sorted(scan.bad_data_shards) or "?")
            damages[vid] = planner.EcDamage(
                base=ecv.base_name, bad_data=scan.bad_data_shards)
        for base, vr in verified.items():
            vid, ecv = by_base[base]
            if not vr.verified and base in remote:
                log.warning("ec volume %d: not held against its parity "
                            "this pass: a row's holder did not answer", vid)
            d = damages[vid]
            d.parity_mismatch = dict(vr.parity_mismatch)
            d.first_mismatch = dict(vr.first_mismatch)
            d.parity_checked = list(vr.parity_checked)
            # a shard file gone while this server still has it mounted
            # is local damage; a shard no server holds is ec.rebuild's
            d.missing = [s for s in vr.missing if s in ecv.shards]
            res.stripes_verified += vr.spans
            res.bytes_scanned += vr.bytes_verified
            ScrubStripesVerifiedCounter.inc(vr.spans)
            ScrubScannedBytesCounter.inc(vr.bytes_verified)
        for vid, ecv in ecvs:
            self._repair_ec(vid, ecv, damages[vid], res, holders[vid],
                            remote.get(ecv.base_name))

    def _fetch(self, vid: int) -> Optional[Callable]:
        return None if self.peers is None else self.peers.reader(vid)

    def repair_held(self, vid: int, sids: Sequence[int]) -> List[int]:
        """A verifier's call (VolumeEcShardsRebuild with
        condemned_shard_ids): shards `sids` of EC volume `vid`, held
        here, failed its pass. Quarantine and rebuild them where they
        are, from the survivors here and, pulled, other holders'; the
        rebuilt shard ids. Raises KeyError for a shard not held here."""
        ecv = self.store.find_ec_volume(vid)
        if ecv is None or not set(sids) <= set(ecv.shards):
            raise KeyError(f"ec volume {vid}: shards {sorted(sids)} "
                           "are not all held here")
        rows = self._remote_rows(vid, ecv, self._holders(vid, ecv))
        rebuilt = planner.repair_ec_volume(
            ecv.base_name, list(sids), backend=self.backend,
            unmount=ecv.unmount_shard, remount=ecv.mount_shard,
            remote=rows)
        if self.on_repair is not None:
            self.on_repair(vid)
        return rebuilt

    def _repair_ec(self, vid: int, ecv, damage: planner.EcDamage,
                   res: PassResult, locs: Dict[int, List[str]],
                   remote: Optional[fleet.RemoteRows],
                   rounds: int = 2) -> None:
        """Classify -> quarantine -> rebuild -> re-verify, at most
        `rounds` times (round one clears data damage, whose recomputed
        parity contaminated round-zero evidence; round two then judges
        the parity shards on their own). A condemned shard another
        server holds is rebuilt by that holder."""
        for _ in range(rounds):
            checked = set(damage.parity_checked)
            if not damage.bad_data and len(checked) >= 2 and \
                    set(damage.parity_mismatch) == checked:
                # every LOCALLY-CHECKED parity stream disagrees but no
                # live needle is bad: dead-space damage in a data
                # shard. The syndrome probe names it, so the shard
                # itself comes back byte-identical instead of parity
                # being re-encoded around corrupt data (>=2 parity rows
                # are needed to discriminate; with every quotient test
                # ambiguous the probe returns nothing and the parity
                # verdict stands)
                damage.bad_data |= planner.localize_from_parity_deltas(
                    damage.base, sorted(set(damage.first_mismatch
                                            .values())),
                    parity_ids=sorted(checked), fetch=self._fetch(vid))
            verdict, bad = planner.classify_ec_damage(damage)
            if verdict == "clean":
                return
            kinds = ["ec_data" if s < fleet.DATA_SHARDS else "ec_parity"
                     for s in bad]
            for k in kinds:
                res.corruptions_found += 1
                ScrubCorruptionsFoundCounter.labels(k).inc()
            if verdict == "unrecoverable":
                res.unrecoverable += len(bad)
                res.verdicts[vid].unrecoverable += len(bad)
                ScrubUnrecoverableCounter.inc(len(bad))
                res.details.append(
                    f"ec volume {vid}: shards {bad} unrecoverable "
                    f"(>{fleet.TOTAL_SHARDS - fleet.DATA_SHARDS} damaged)")
                log.error("ec volume %d: shards %s unrecoverable",
                          vid, bad)
                return
            self._checkpoint(vid)
            log.warning("ec volume %d: rebuilding %s shard(s) %s",
                        vid, verdict, bad)
            here = [s for s in bad if s in ecv.shards or not any(
                u != self.peers.url for u in locs.get(s, ()))] \
                if self.peers is not None else bad
            try:
                if here:
                    planner.repair_ec_volume(
                        damage.base, here, backend=self.backend,
                        unmount=ecv.unmount_shard, remount=ecv.mount_shard,
                        remote=remote)
                for url, sids in _by_holder(
                        [s for s in bad if s not in here], locs,
                        self.peers.url if self.peers else "").items():
                    self.peers.repair(url, vid, sids)
            except (ValueError, OSError) as e:
                res.unrecoverable += len(bad)
                res.verdicts[vid].unrecoverable += len(bad)
                ScrubUnrecoverableCounter.inc(len(bad))
                res.details.append(
                    f"ec volume {vid}: rebuild of {bad} failed: {e}")
                log.error("ec volume %d: rebuild failed: %s", vid, e)
                return
            if self.on_repair is not None:
                self.on_repair(vid)
            vr = planner.verify_ec_repair(damage.base,
                                          backend=self.backend,
                                          remote=remote)
            res.stripes_verified += vr.spans
            ScrubStripesVerifiedCounter.inc(vr.spans)
            for k in kinds:
                res.corruptions_repaired += 1
                ScrubCorruptionsRepairedCounter.labels(k).inc()
            rebuilt = res.verdicts[vid].rebuilt_shards
            rebuilt[:] = sorted(set(rebuilt) | set(bad))
            res.details.append(
                f"ec volume {vid}: shards {bad} reconstructed")
            # evidence for the next round: repaired shards are clean
            # by construction, only fresh parity mismatches remain
            damage = planner.EcDamage(
                base=damage.base,
                parity_mismatch=dict(vr.parity_mismatch),
                first_mismatch=dict(vr.first_mismatch),
                parity_checked=list(vr.parity_checked))
            if vr.clean:
                return
        log.error("ec volume %d: still inconsistent after %d repair "
                  "rounds", vid, rounds)


def _by_holder(sids: Sequence[int], locs: Dict[int, List[str]],
               me: str) -> Dict[str, List[int]]:
    """{holder url: the shards of `sids` it holds}, this server left
    out: each copy of a condemned shard is rebuilt where it lies."""
    out: Dict[str, List[int]] = {}
    for sid in sids:
        for url in locs.get(sid, ()):
            if url != me:
                out.setdefault(url, []).append(sid)
    return out
