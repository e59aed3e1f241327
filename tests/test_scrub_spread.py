"""The scrub on the layout a multi-server cluster has: `ec.encode` spreads
each volume's 14 shards over four volume servers and `ec.balance` keeps
them spread (3-4 a server; here placed by its shard moves, the same in
every run), and `volume.scrub -full -wait` fans out to all four.

Each pass verifies every EC volume once, on ONE of its holders (in url
order, the (vid mod holders)-th, or the first after it that answers),
which reads the rows it lacks from their holders; the others defer the
volume. A shard that fails is rebuilt by the server that holds
it, in place. The files are held to the plain numpy encode of the same
`.dat`, kept before the encode deleted it.
"""

import io
import json
import os
import re
import shutil
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ec import encoder, fleet
from seaweedfs_tpu.ec.encoder import shard_file_name
from seaweedfs_tpu.ec.shard_bits import DATA_SHARDS, TOTAL_SHARDS
from seaweedfs_tpu.operation.file_id import parse_fid
from seaweedfs_tpu.scrub.daemon import ScrubDaemon
from seaweedfs_tpu.shell import Shell
from seaweedfs_tpu.shell.command_ec import apply_shard_move
from seaweedfs_tpu.shell.ec_common import ShardMove
from seaweedfs_tpu.stats.metrics import (FleetRemoteReadSecondsHistogram,
                                         FleetVerifyRowBytesCounter,
                                         ScrubEcVolumesCounter,
                                         ScrubNeedleSourceCounter)
from tests.cluster_util import Cluster

COLLECTION = "spread"
SERVERS = 4
VOLUMES = 4
NEEDLE = 64 << 10
PER_VOLUME = 180            # 64 KiB needles: ~11.3 MiB of a 12 MiB volume
SECTOR = 4096
LEDGER = re.compile(r"^(\S+): (\w+) passes:(\d+) scanned:\d+B needles:(\d+) "
                    r"stripes:\d+ found:(\d+) repaired:(\d+) "
                    r"unrecoverable:(\d+)", re.M)
VERDICT = re.compile(r"^(\S+): volume (\d+): (.+)$", re.M)


@pytest.fixture(scope="module")
def spread(tmp_path_factory):
    """Four servers, four EC volumes spread over them (jax codec), and
    each volume's sealed .dat kept under a second name."""
    root = tmp_path_factory.mktemp("spread")
    c = Cluster(root / "cluster", n_volume_servers=SERVERS,
                volume_size_limit_mb=12, ec_encoder="jax")
    try:
        with c.http(f"{c.master.url}/vol/grow?count={VOLUMES}"
                    f"&collection={COLLECTION}") as r:
            vids = sorted(json.load(r)["volumeIds"])
        rng = np.random.default_rng(43)
        filled = {vid: 0 for vid in vids}
        while min(filled.values()) < PER_VOLUME:
            fid = c.upload(rng.bytes(NEEDLE), collection=COLLECTION)
            filled[parse_fid(fid).volume_id] += 1
        kept = {}
        for vid in vids:
            v = next(vs.store.find_volume(vid) for vs in c.volume_servers
                     if vs.store.find_volume(vid) is not None)
            v.sync()
            kept[vid] = str(root / f"kept{vid}")
            shutil.copyfile(v.file_name() + ".dat", kept[vid] + ".dat")
        shell = Shell(c.master.url)
        out = shell.run_command("ec.encode -volumeId=%s -encoder=jax"
                                % ",".join(map(str, vids)))
        for vid in vids:
            assert f"volume {vid}: ec.encode done" in out
        # ... and kept spread, as ec.balance keeps it, the same way in
        # every run: shard s of the i-th volume on server (s + i) mod 4
        urls = sorted(vs.url for vs in c.volume_servers)
        for i, vid in enumerate(vids):
            c.wait_for(lambda vid=vid: len(_where(c, vid)) == TOTAL_SHARDS,
                       what=f"14 shards of volume {vid}")
            for sid, (src,) in sorted(_where(c, vid).items()):
                dst = urls[(sid + i) % SERVERS]
                if src != dst:
                    apply_shard_move(shell.env, ShardMove(vid, (sid,), src,
                                                          dst),
                                     COLLECTION, io.StringIO())
        for i, vid in enumerate(vids):
            c.wait_for(lambda i=i, vid=vid: _where(c, vid) == {
                sid: [urls[(sid + i) % SERVERS]]
                for sid in range(TOTAL_SHARDS)},
                what=f"volume {vid} in place")
        for vid in vids:   # the plain reference: numpy's encode
            encoder.write_ec_files(kept[vid], backend="numpy")
        yield c, shell, vids, kept
    finally:
        c.stop()


def _where(c, vid):
    """{shard id: [holder urls]} as the master shows it."""
    out = {}
    for url, bits in c.master.topo.lookup_ec(vid).items():
        for sid in bits.shard_ids:
            out.setdefault(sid, []).append(url)
    return {sid: sorted(urls) for sid, urls in out.items()}


def _layout(c, vid):
    """{shard id: holder url} as the master shows it."""
    out = {}
    for url, bits in c.master.topo.lookup_ec(vid).items():
        for sid in bits.shard_ids:
            assert sid not in out, f"shard {sid} of {vid} twice"
            out[sid] = url
    return out


def _path(c, vid, sid):
    """The file of shard `sid` of `vid` on whichever server holds it."""
    for vs in c.volume_servers:
        ecv = vs.store.find_ec_volume(vid)
        if ecv is not None and sid in ecv.shards:
            return shard_file_name(ecv.base_name, sid)
    raise AssertionError(f"no server holds shard {sid} of {vid}")


def _live(c, vid):
    return next(vs.store.find_ec_volume(vid).file_count()
                for vs in c.volume_servers
                if vs.store.find_ec_volume(vid) is not None)


def _scrub(shell):
    out = shell.run_command("volume.scrub -full -wait")
    ledgers = {m[0]: (m[1], int(m[2]), int(m[3]), int(m[4]), int(m[5]),
                      int(m[6])) for m in LEDGER.findall(out)}
    named = [(url, int(vid), verdict)
             for url, vid, verdict in VERDICT.findall(out)]
    return out, ledgers, named


def _same_as_reference(c, vid, kept):
    for sid in range(TOTAL_SHARDS):
        with open(_path(c, vid, sid), "rb") as f, \
                open(shard_file_name(kept, sid), "rb") as g:
            assert f.read() == g.read(), (vid, sid)


def test_every_server_holds_three_or_four_shards_of_each_volume(spread):
    """Every server holds 3-4 of every volume's 14 shards, interleaved,
    and 14 over the pool: no server holds all of a needle's blocks."""
    c, _, vids, _ = spread
    urls = sorted(vs.url for vs in c.volume_servers)
    total = dict.fromkeys(urls, 0)
    for vid in vids:
        held = {}
        for sid, url in _layout(c, vid).items():
            held.setdefault(url, []).append(sid)
            total[url] += 1
        assert sorted(held) == urls
        assert sorted(len(sids) for sids in held.values()) == [3, 3, 4, 4]
        assert all(b - a == SERVERS for sids in held.values()
                   for a, b in zip(sids, sids[1:])), held
    assert sorted(total.values()) == [TOTAL_SHARDS] * SERVERS, total


def test_a_clean_pass_verifies_each_volume_once_and_checks_each_needle_once(
        spread):
    c, shell, vids, kept = spread
    roles = {r: ScrubEcVolumesCounter.labels(r).value
             for r in ("verified", "deferred")}
    rows = {s: FleetVerifyRowBytesCounter.labels(s).value
            for s in ("local", "remote")}
    sources = {s: ScrubNeedleSourceCounter.labels(s).value
               for s in ("staged", "carried", "read")}
    fills = FleetRemoteReadSecondsHistogram.labels().count
    out, ledgers, named = _scrub(shell)
    assert sorted(ledgers) == sorted(vs.url for vs in c.volume_servers)
    assert all(led[:2] == ("idle", 1) and led[3:] == (0, 0, 0)
               for led in ledgers.values()), out
    # each volume named once, by one of its holders, and clean
    assert sorted(vid for _, vid, _ in named) == vids, out
    for url, vid, verdict in named:
        assert verdict == "clean"
        assert url in set(_layout(c, vid).values())
    # every live needle checked once over the four servers
    live = sum(_live(c, vid) for vid in vids)
    assert sum(led[2] for led in ledgers.values()) == live
    # consecutive volume ids on the same four holders: one each
    assert sorted(url for url, _, _ in named) == \
        sorted(vs.url for vs in c.volume_servers)
    moved = {r: ScrubEcVolumesCounter.labels(r).value - n
             for r, n in roles.items()}
    assert moved == {"verified": VOLUMES,
                     "deferred": VOLUMES * (SERVERS - 1)}
    # ... each in the bytes the stripe verify staged, remote rows too
    found = {s: ScrubNeedleSourceCounter.labels(s).value - n
             for s, n in sources.items()}
    assert found["read"] == 0 and found["staged"] + found["carried"] == live
    staged = {s: FleetVerifyRowBytesCounter.labels(s).value - n
              for s, n in rows.items()}
    shard = os.path.getsize(_path(c, vids[0], 0))
    own = sum(url == _layout(c, vid)[sid] for url, vid, _ in named
              for sid in range(TOTAL_SHARDS))
    assert staged == {"local": own * shard,
                      "remote": (VOLUMES * TOTAL_SHARDS - own) * shard}
    assert FleetRemoteReadSecondsHistogram.labels().count > fills
    for vid in vids:
        _same_as_reference(c, vid, kept[vid])


# where a sector is planted: (volume index, shard kind, offset). A
# volume's .dat ends early in its second row of 1 MiB blocks, so the
# data shards past its end hold zero padding there: bytes no live
# needle's CRC covers
PLANTED = {"data": (1, "data", 5 * SECTOR),
           "parity": (2, "parity", 5 * SECTOR),
           "dead-space": (3, "padding", (1 << 20) + 7 * SECTOR)}


@pytest.mark.parametrize("kind", sorted(PLANTED))
def test_a_sector_on_another_holder_is_rebuilt_there(spread, kind):
    """A sector planted in a shard that the volume's verifier does not
    hold: the verifier's report names it, its holder rebuilds it in
    place, the re-verify passes, all 14 files equal the numpy encode of
    the kept .dat, and no shard has moved."""
    c, shell, vids, kept = spread
    _, _, named = _scrub(shell)
    verifier = {vid: url for url, vid, _ in named}
    at, shards, offset = PLANTED[kind]
    vid = vids[at]
    before = _layout(c, vid)
    dat = os.path.getsize(kept[vid] + ".dat")
    wanted = {"data": range(DATA_SHARDS),
              "padding": [s for s in range(DATA_SHARDS)
                          if (DATA_SHARDS + s) << 20 >= dat],
              "parity": range(DATA_SHARDS, TOTAL_SHARDS)}[shards]
    sid = min(s for s in wanted if before[s] != verifier[vid])
    path = _path(c, vid, sid)
    with open(path, "r+b") as f:
        f.seek(offset)
        old = f.read(SECTOR)
        assert len(old) == SECTOR and (shards != "padding" or not any(old))
        f.seek(offset)
        f.write(bytes(b ^ 0xA5 for b in old))
    out, ledgers, named = _scrub(shell)
    assert sorted(v for _, v, _ in named) == vids, out
    for url, v, verdict in named:
        assert verdict == (f"rebuilt shards [{sid}]" if v == vid
                           else "clean"), out
        if v == vid:
            assert url == verifier[vid]
    found = {url: led[3:] for url, led in ledgers.items()}
    assert found[verifier[vid]] == (1, 1, 0)
    assert sum(f[0] for f in found.values()) == 1
    # the holder kept the damaged copy, beside the rebuilt shard
    quarantined = np.fromfile(path + ".corrupt", dtype=np.uint8)
    assert bytes(quarantined[offset:offset + SECTOR]) != old
    os.remove(path + ".corrupt")
    c.wait_for(lambda: sum(b.count for b in
                           c.master.topo.lookup_ec(vid).values())
               == TOTAL_SHARDS, what="14 shards at the master")
    assert _layout(c, vid) == before
    _same_as_reference(c, vid, kept[vid])


def test_a_parity_shard_cut_short_on_another_holder_is_rebuilt_there(spread):
    """A parity shard whose file on a holder other than the verifier ends
    a sector early: its row reads as zeros past the holder's end, the
    compare condemns it, and its holder rebuilds it in place."""
    c, shell, vids, kept = spread
    _, _, named = _scrub(shell)
    vid = vids[0]
    verifier = next(url for url, v, _ in named if v == vid)
    before = _layout(c, vid)
    sid = min(s for s in range(DATA_SHARDS, TOTAL_SHARDS)
              if before[s] != verifier)
    path = _path(c, vid, sid)
    size = os.path.getsize(path)
    os.truncate(path, size - SECTOR)
    out, ledgers, named = _scrub(shell)
    assert (verifier, vid, f"rebuilt shards [{sid}]") in named, out
    assert sum(led[3] for led in ledgers.values()) == 1, out
    assert os.path.getsize(path + ".corrupt") == size - SECTOR
    os.remove(path + ".corrupt")
    c.wait_for(lambda: sum(b.count for b in
                           c.master.topo.lookup_ec(vid).values())
               == TOTAL_SHARDS, what="14 shards at the master")
    assert _layout(c, vid) == before
    _same_as_reference(c, vid, kept[vid])


def test_a_pass_started_on_one_server_defers_nothing(spread):
    """`volume.scrub -node X`: no other holder runs the pass, so X
    verifies every volume it holds, whoever their verifier would be,
    and checks every needle of them."""
    c, shell, vids, kept = spread
    _, _, named = _scrub(shell)
    verifiers = {url for url, _, _ in named}
    url = min((vs.url for vs in c.volume_servers),
              key=lambda u: (u in verifiers, u))
    roles = {r: ScrubEcVolumesCounter.labels(r).value
             for r in ("verified", "deferred")}
    out = shell.run_command(f"volume.scrub -node {url} -full -wait")
    ledgers = {m[0]: m for m in LEDGER.findall(out)}
    assert list(ledgers) == [url], out
    assert ledgers[url][1:3] == ("idle", "1") and \
        ledgers[url][4:] == ("0", "0", "0"), out
    assert sorted((u, int(v), d) for u, v, d in VERDICT.findall(out)) == \
        [(url, vid, "clean") for vid in vids], out
    assert int(ledgers[url][3]) == sum(_live(c, vid) for vid in vids)
    assert {r: ScrubEcVolumesCounter.labels(r).value - n
            for r, n in roles.items()} == {"verified": VOLUMES,
                                           "deferred": 0}


class _Peers:
    """A holder's view, as far as the verifier rule reads it."""

    def __init__(self, url):
        self.url = url


@pytest.mark.parametrize("dead,want", [((), "bcda"), (("b",), "ccda"),
                                       (("b", "c"), "ddda")])
def test_every_holder_names_the_same_verifier(dead, want):
    """Volumes 5-8, each on holders a-d: of the holders in url order the
    (vid mod 4)-th verifies, or the first after it that answers; every
    holder that answers names the same one."""
    locs = {sid: ["abcd"[sid % 4]] for sid in range(TOTAL_SHARDS)}
    for vid, verifier in zip(range(5, 9), want):
        named = {ScrubDaemon(None, export_lag=False,
                             peers=_Peers(me))._verifier(
                                 vid, locs, lambda u: u not in dead)
                 for me in "abcd" if me not in dead}
        assert named == {verifier}, vid


# -- the fleet verify and rebuild with remote rows ---------------------------

SMALL = 4096


def _volume(tmp_path, seed):
    base = str(tmp_path / f"v{seed}")
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, 7 * DATA_SHARDS * SMALL + 1234,
                             dtype=np.uint8).tobytes())
    encoder.write_ec_files(base, backend="numpy", small_block=SMALL,
                           large_block=SMALL << 8)
    return base


def _flip(path, offset):
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0x3C]))


def _spread_copy(tmp_path, base, here):
    """A server's view of `base`: the files of shards `here`, and a
    RemoteRows that reads the others from `base`'s files."""
    mine = str(tmp_path / ("here-" + os.path.basename(base)))
    for sid in here:
        shutil.copyfile(shard_file_name(base, sid),
                        shard_file_name(mine, sid))

    def fill(sid, offset, view):
        with open(shard_file_name(base, sid), "rb") as f:
            f.seek(offset)
            data = f.read(len(view))
        view[:len(data)] = data
        return len(data)

    far = frozenset(range(TOTAL_SHARDS)) - set(here)
    size = os.path.getsize(shard_file_name(base, 0))
    return mine, fleet.RemoteRows(7, far, size, fill)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("here", [[0, 4, 8, 12], [3, 7, 11], [13]])
def test_verify_with_rows_remote_gives_what_all_local_gives(
        tmp_path, backend, here):
    bases = [_volume(tmp_path, seed) for seed in (5, 6)]
    _flip(shard_file_name(bases[0], 2), 3 * SMALL + 17)   # a data shard
    _flip(shard_file_name(bases[1], 12), 5 * SMALL + 1)   # a parity shard
    _flip(shard_file_name(bases[1], 10), 2 * SMALL + 99)
    want = fleet.fleet_verify_ec_files(bases, backend=backend,
                                       chunk=4 * DATA_SHARDS * SMALL)
    spread_bases, remote = [], {}
    for base in bases:
        mine, rows = _spread_copy(tmp_path, base, here)
        spread_bases.append(mine)
        remote[mine] = rows
    seen = {}
    got = fleet.fleet_verify_ec_files(
        spread_bases, backend=backend, chunk=4 * DATA_SHARDS * SMALL,
        remote=remote, on_span=lambda base, offset, valid, rows:
        seen.setdefault(base, []).append(
            (offset, bytes(np.ascontiguousarray(rows[:, :valid])))))
    for base, mine in zip(bases, spread_bases):
        w, g = want[base], got[mine]
        assert w.verified and g.verified
        assert g.parity_mismatch == w.parity_mismatch and w.parity_mismatch
        assert g.first_mismatch == w.first_mismatch
        assert g.parity_checked == w.parity_checked == list(range(10, 14))
        assert (g.bytes_verified, g.spans, g.missing) == \
            (w.bytes_verified, w.spans, [])
        # on_span saw every data row as the files hold it
        rows = np.stack([np.fromfile(shard_file_name(base, sid),
                                     dtype=np.uint8)
                         for sid in range(DATA_SHARDS)])
        for offset, staged in seen[mine]:
            n = len(staged) // DATA_SHARDS
            assert staged == bytes(np.ascontiguousarray(
                rows[:, offset:offset + n]))


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_a_row_no_holder_gives_declines_its_volume_alone(tmp_path, backend):
    """A remote row whose holder stops answering in the middle of the
    pass: that volume is declined with no evidence kept, and the other
    volume is verified as if all its rows were local."""
    bases = [_volume(tmp_path, seed) for seed in (5, 6)]
    _flip(shard_file_name(bases[1], 12), 5 * SMALL + 1)
    want = fleet.fleet_verify_ec_files(bases, backend=backend,
                                       chunk=4 * DATA_SHARDS * SMALL)
    spread_bases, remote = [], {}
    for base in bases:
        mine, rows = _spread_copy(tmp_path, base, [0, 4, 8, 12])
        spread_bases.append(mine)
        remote[mine] = rows
    gone, lost = spread_bases[0], remote[spread_bases[0]]
    calls = []

    def dying(sid, offset, view):   # the first span's rows, then nothing
        calls.append(sid)
        if len(calls) > len(lost.sids):
            raise ConnectionError("the holder stopped answering")
        return lost.fill(sid, offset, view)

    remote[gone] = fleet.RemoteRows(lost.vid, lost.sids, lost.shard_size,
                                    dying)
    seen = {}
    got = fleet.fleet_verify_ec_files(
        spread_bases, backend=backend, chunk=4 * DATA_SHARDS * SMALL,
        remote=remote, on_span=lambda base, offset, valid, rows:
        seen.setdefault(base, []).append(offset))
    assert len(calls) > len(lost.sids)   # the holder did stop answering
    declined = got[gone]
    assert not declined.verified and not declined.clean
    assert (declined.parity_mismatch, declined.first_mismatch,
            declined.parity_checked, declined.spans) == ({}, {}, [], 0)
    w, g = want[bases[1]], got[spread_bases[1]]
    assert g.verified and g.parity_mismatch == w.parity_mismatch
    assert (g.first_mismatch, g.spans, g.bytes_verified) == \
        (w.first_mismatch, w.spans, w.bytes_verified)
    assert len(seen[spread_bases[1]]) == w.spans > 1
    assert len(seen.get(gone, [])) < w.spans


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_rebuild_pulls_the_survivors_it_lacks(tmp_path, backend):
    base = _volume(tmp_path, 9)
    mine, rows = _spread_copy(tmp_path, base, [1, 5, 9, 13])
    pulls = []

    def counted(sid, offset, view):
        pulls.append(sid)
        return rows.fill(sid, offset, view)

    remote = {mine: fleet.RemoteRows(7, rows.sids, rows.shard_size,
                                     counted)}
    os.remove(shard_file_name(mine, 5))
    got = fleet.fleet_rebuild_ec_files([mine], backend=backend, wanted=[5],
                                       remote=remote,
                                       chunk=4 * DATA_SHARDS * SMALL)
    assert got == {mine: [5]}
    with open(shard_file_name(mine, 5), "rb") as f, \
            open(shard_file_name(base, 5), "rb") as g:
        assert f.read() == g.read()
    # three survivors here, seven pulled: the fewest that make ten
    assert sorted(set(pulls)) == [0, 2, 3, 4, 6, 7, 8]


# -- last: it leaves the cluster one server short -----------------------------


def test_a_holder_stopped_in_the_middle_of_a_pass_declines_only_what_it_held(
        spread):
    """One of the four servers stops while the others' passes pull rows
    from it. Those passes end, not failed: a volume whose row it held is
    declined (its stripes not held against parity), every volume the
    others verify is still named by one of them, and each of its needles
    is still checked once, the pieces on the stopped server
    reconstructed from the others."""
    c, shell, vids, kept = spread
    _, _, named = _scrub(shell)
    gone = min(c.volume_servers, key=lambda vs: vs.url)
    left = [vs for vs in c.volume_servers if vs is not gone]
    full = sum(vs.scrub.last_pass.stripes_verified for vs in left)
    theirs = sorted(vid for url, vid, _ in named if url != gone.url)
    held = {(vid, sid) for vid in vids
            for sid, url in _layout(c, vid).items() if url == gone.url}
    stopped = threading.Event()
    once = threading.Lock()

    def stopping(fill):
        def fill_or_stop(vid, sid, offset, view):
            if (vid, sid) in held:
                with once:
                    if not stopped.is_set():
                        gone.stop()
                        stopped.set()
            return fill(vid, sid, offset, view)
        return fill_or_stop

    for vs in left:
        vs.scrub.peers.fill = stopping(vs.scrub.peers.fill)
    ended = {vs.url: vs.scrub.passes_ended for vs in left}
    try:
        for vs in c.volume_servers:
            vs.scrub.start(full=True)
        for vs in left:
            assert vs.scrub.wait_pass(ended[vs.url], timeout=120)
    finally:
        for vs in left:
            del vs.scrub.peers.fill
        c.volume_servers.remove(gone)   # stopped once, not again
    assert stopped.is_set()
    passes = {vs.url: vs.scrub.last_pass for vs in left}
    assert not any(p.failed for p in passes.values()), \
        {u: p.error for u, p in passes.items()}
    assert sorted(vid for p in passes.values() for vid in p.verdicts) == \
        theirs
    assert all(p.corruptions_found == 0 for p in passes.values())
    assert sum(p.needles_verified for p in passes.values()) == \
        sum(_live(c, vid) for vid in theirs)
    assert sum(p.stripes_verified for p in passes.values()) < full
