"""Mount filesystem layer: dirty-page intervals (reference:
weed/filesys/dirty_page_interval_test.go) and Wfs ops over a real
cluster."""

import pytest

from seaweedfs_tpu.filesys import ContinuousIntervals, Wfs
from seaweedfs_tpu.filesys.wfs import FuseError
from tests.cluster_util import Cluster


class TestContinuousIntervals:
    def test_sequential_writes_merge(self):
        ci = ContinuousIntervals()
        ci.add_interval(b"aaa", 0)
        ci.add_interval(b"bbb", 3)
        assert len(ci.intervals) == 1
        assert ci.read_data(0, 6) == b"aaabbb"

    def test_overwrite_shadows(self):
        ci = ContinuousIntervals()
        ci.add_interval(b"xxxxxxxxxx", 0)
        ci.add_interval(b"YY", 4)
        assert ci.read_data(0, 10) == b"xxxxYYxxxx"

    def test_random_order_writes(self):
        ci = ContinuousIntervals()
        ci.add_interval(b"cc", 4)
        ci.add_interval(b"aa", 0)
        assert ci.read_data(0, 6) == b"aa\x00\x00cc"
        ci.add_interval(b"bb", 2)
        assert ci.read_data(0, 6) == b"aabbcc"
        assert len(ci.intervals) == 1  # fully merged

    def test_read_over_base(self):
        ci = ContinuousIntervals()
        ci.add_interval(b"NEW", 2)
        assert ci.read_data(0, 8, base=b"olddataX") == b"olNEWtaX"

    def test_total_size_and_pop(self):
        ci = ContinuousIntervals()
        ci.add_interval(b"abc", 10)
        assert ci.total_size == 13
        popped = ci.pop_all()
        assert [(iv.offset, iv.data) for iv in popped] == [(10, b"abc")]
        assert not ci


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = Cluster(tmp_path_factory.mktemp("wfs_cluster"),
                n_volume_servers=1, with_filer=True)
    yield c
    c.stop()


@pytest.fixture()
def wfs(cluster):
    w = Wfs(filer_url=cluster.filer.url)
    yield w
    w.stop()


class TestWfs:
    def test_create_write_read_cycle(self, wfs):
        fh = wfs.create("/w/f.txt")
        wfs.write(fh, b"hello ", 0)
        wfs.write(fh, b"world", 6)
        # read-before-flush sees dirty pages
        assert wfs.read(fh, 0, 100) == b"hello world"
        wfs.flush(fh)
        wfs.release(fh)
        # fresh handle reads flushed chunks
        fh2 = wfs.open("/w/f.txt")
        assert wfs.read(fh2, 0, 100) == b"hello world"
        assert wfs.read(fh2, 6, 5) == b"world"
        wfs.release(fh2)

    def test_overwrite_after_flush(self, wfs):
        fh = wfs.create("/w/ow.txt")
        wfs.write(fh, b"0123456789", 0)
        wfs.flush(fh)
        wfs.write(fh, b"XX", 4)
        assert wfs.read(fh, 0, 10) == b"0123XX6789"
        wfs.flush(fh)
        wfs.release(fh)
        fh2 = wfs.open("/w/ow.txt")
        assert wfs.read(fh2, 0, 10) == b"0123XX6789"
        wfs.release(fh2)

    def test_mkdir_readdir_unlink(self, wfs):
        wfs.mkdir("/w/dir1")
        fh = wfs.create("/w/dir1/a.txt")
        wfs.write(fh, b"a", 0)
        wfs.release(fh)
        names = sorted(e.name for e in wfs.readdir("/w/dir1"))
        assert names == ["a.txt"]
        wfs.unlink("/w/dir1/a.txt")
        assert wfs.readdir("/w/dir1") == []
        with pytest.raises(FuseError):
            wfs.getattr("/w/dir1/a.txt")

    def test_rename(self, wfs):
        fh = wfs.create("/w/old.txt")
        wfs.write(fh, b"data", 0)
        wfs.release(fh)
        wfs.rename("/w/old.txt", "/w/new.txt")
        fh2 = wfs.open("/w/new.txt")
        assert wfs.read(fh2, 0, 4) == b"data"
        wfs.release(fh2)
        with pytest.raises(FuseError):
            wfs.open("/w/old.txt")

    def test_open_missing_enoent(self, wfs):
        with pytest.raises(FuseError):
            wfs.open("/w/ghost.txt")

    def test_meta_cache_invalidation_from_other_client(self, cluster, wfs):
        # warm the cache
        wfs.mkdir("/w/shared")
        assert wfs.readdir("/w/shared") == []
        # another client (the filer HTTP API) adds a file
        cluster.http(f"http://{cluster.filer.url}/w/shared/ext.txt",
                     data=b"external", method="POST").close()
        cluster.wait_for(
            lambda: any(e.name == "ext.txt"
                        for e in wfs.readdir("/w/shared")),
            what="subscription invalidates meta cache")


def test_rmdir_refuses_non_empty(wfs):
    """Regression: rmdir used to recursively destroy directory
    contents; POSIX demands ENOTEMPTY."""
    wfs.mkdir("/w/full")
    fh = wfs.create("/w/full/keep.txt")
    wfs.write(fh, b"precious", 0)
    wfs.release(fh)
    with pytest.raises(FuseError) as ei:
        wfs.rmdir("/w/full")
    assert ei.value.errno == 39
    fh2 = wfs.open("/w/full/keep.txt")
    assert wfs.read(fh2, 0, 100) == b"precious"
    wfs.release(fh2)
    wfs.unlink("/w/full/keep.txt")
    wfs.rmdir("/w/full")  # empty now: succeeds
    with pytest.raises(FuseError):
        wfs.getattr("/w/full")


class TestLinksAndXattrs:
    """Wfs symlink/hardlink/xattr surface (reference filesys/xattr.go,
    dir_link.go)."""

    def test_symlink_readlink(self, wfs):
        import stat
        fh = wfs.create("/ln/real.txt")
        wfs.write(fh, b"pointed-at", 0)
        wfs.release(fh)
        wfs.symlink("/ln/real.txt", "/ln/alias")
        entry = wfs.getattr("/ln/alias")
        assert stat.S_ISLNK(entry.attributes.file_mode)
        assert wfs.readlink("/ln/alias") == "/ln/real.txt"
        # readlink on a regular file: EINVAL
        with pytest.raises(FuseError) as ei:
            wfs.readlink("/ln/real.txt")
        assert ei.value.errno == 22

    def test_hardlink_shares_content(self, wfs):
        fh = wfs.create("/hl/a.txt")
        wfs.write(fh, b"shared bytes", 0)
        wfs.release(fh)
        wfs.link("/hl/a.txt", "/hl/b.txt")
        ea, eb = wfs.getattr("/hl/a.txt"), wfs.getattr("/hl/b.txt")
        assert ea.hard_link_id and \
            bytes(ea.hard_link_id) == bytes(eb.hard_link_id)
        assert ea.hard_link_counter == eb.hard_link_counter == 2
        fh2 = wfs.open("/hl/b.txt")
        assert wfs.read(fh2, 0, 100) == b"shared bytes"
        wfs.release(fh2)
        # linking a directory: EMLINK
        wfs.mkdir("/hl/dir")
        with pytest.raises(FuseError):
            wfs.link("/hl/dir", "/hl/dir2")

    def test_xattr_lifecycle(self, wfs):
        fh = wfs.create("/xa/file.txt")
        wfs.release(fh)
        p = "/xa/file.txt"
        wfs.setxattr(p, "user.color", b"teal")
        wfs.setxattr(p, "user.shape", b"round")
        assert wfs.getxattr(p, "user.color") == b"teal"
        assert wfs.listxattr(p) == ["user.color", "user.shape"]
        # XATTR_CREATE on an existing name: EEXIST
        with pytest.raises(FuseError) as ei:
            wfs.setxattr(p, "user.color", b"x", wfs.XATTR_CREATE)
        assert ei.value.errno == 17
        # XATTR_REPLACE on a missing name: ENODATA
        with pytest.raises(FuseError) as ei:
            wfs.setxattr(p, "user.nope", b"x", wfs.XATTR_REPLACE)
        assert ei.value.errno == 61
        wfs.removexattr(p, "user.color")
        assert wfs.listxattr(p) == ["user.shape"]
        with pytest.raises(FuseError) as ei:
            wfs.getxattr(p, "user.color")
        assert ei.value.errno == 61
        with pytest.raises(FuseError):
            wfs.removexattr(p, "user.color")

    def test_hardlink_write_coherence(self, wfs):
        """Write through one link name, read through the sibling: the
        meta cache stores hardlinked entries as stubs over shared meta
        (reference meta_cache wraps FilerStoreWrapper), so siblings
        never serve stale chunk lists."""
        fh = wfs.create("/hc/a.txt")
        wfs.write(fh, b"original", 0)
        wfs.release(fh)
        wfs.link("/hc/a.txt", "/hc/b.txt")
        fh = wfs.open("/hc/a.txt")
        wfs.write(fh, b"UPDATED!", 0)
        wfs.release(fh)  # flush through name a
        fh2 = wfs.open("/hc/b.txt")
        assert wfs.read(fh2, 0, 100) == b"UPDATED!"
        wfs.release(fh2)

    def test_own_subscription_echo_is_skipped(self, wfs):
        """A lagging subscription echo of this mount's OWN mutation must
        not clobber newer local state (reference wfs.signature +
        meta_cache_subscribe skip). Deterministic replay of the race
        that flaked the hardlink coherence test under suite load."""
        from seaweedfs_tpu.pb import filer_pb2
        fh = wfs.create("/echo/f.txt")
        wfs.write(fh, b"new content", 0)
        wfs.release(fh)
        fresh = wfs.getattr("/echo/f.txt")
        # forge the delayed echo: this mount's own signature, stale body
        stale = filer_pb2.Entry(name="f.txt")
        rec = filer_pb2.SubscribeMetadataResponse(directory="/echo")
        rec.event_notification.new_entry.CopyFrom(stale)
        rec.event_notification.signatures.append(wfs.signature)
        wfs.meta_cache._apply(rec)
        assert wfs.getattr("/echo/f.txt").chunks == fresh.chunks
        # a FOREIGN event (no signature) still applies
        rec2 = filer_pb2.SubscribeMetadataResponse(directory="/echo")
        rec2.event_notification.new_entry.CopyFrom(stale)
        wfs.meta_cache._apply(rec2)
        assert not wfs.getattr("/echo/f.txt").chunks

    def test_xattrs_survive_hardlink_copy(self, wfs):
        fh = wfs.create("/xa/linked.txt")
        wfs.release(fh)
        wfs.setxattr("/xa/linked.txt", "user.tag", b"v1")
        wfs.link("/xa/linked.txt", "/xa/linked2.txt")
        assert wfs.getxattr("/xa/linked2.txt", "user.tag") == b"v1"

    def test_chown_utimens(self, wfs):
        fh = wfs.create("/at/f.txt")
        wfs.release(fh)
        wfs.chown("/at/f.txt", 1234, 0xFFFFFFFF)  # gid: leave as is
        e = wfs.getattr("/at/f.txt")
        assert e.attributes.uid == 1234
        wfs.utimens("/at/f.txt", 1234567890)
        assert wfs.getattr("/at/f.txt").attributes.mtime == 1234567890


# -- real kernel mount through the libfuse ctypes shim ------------------------


import contextlib


@contextlib.contextmanager
def kernel_mount(tmp_path_factory, tmp_path, name):
    """Real cluster mounted through /dev/fuse; yields the mountpoint.
    Skips where libfuse, /dev/fuse, or mount privilege is missing."""
    import os
    import threading
    import time

    from seaweedfs_tpu.filesys import fuse_shim
    from tests.cluster_util import Cluster

    if not fuse_shim.available():
        pytest.skip("libfuse / /dev/fuse not available")
    c = Cluster(tmp_path_factory.mktemp(name), n_volume_servers=1,
                with_filer=True)
    wfs = Wfs(c.filer.url)
    mp = str(tmp_path / "mnt")
    os.makedirs(mp)
    m = fuse_shim.FuseMount(wfs, mp)
    t = threading.Thread(target=m.mount, daemon=True)
    t.start()
    deadline = time.time() + 10
    while time.time() < deadline and not os.path.ismount(mp):
        time.sleep(0.1)
    if not os.path.ismount(mp):
        c.stop()
        pytest.skip("FUSE mount did not come up (no mount privilege?)")
    try:
        yield mp
    finally:
        m.unmount()
        t.join(timeout=5)
        wfs.stop()
        c.stop()
    # libfuse resets SIGHUP/SIGINT/SIGPIPE/SIGTERM to SIG_DFL on its
    # way out; mount() must put the process's own dispositions back —
    # an un-ignored SIGPIPE kills the process (a whole xdist worker) at
    # the next write to a socket whose peer has gone
    assert not t.is_alive(), "fuse loop did not end after unmount"
    assert _sigpipe_ignored(), \
        "the FUSE mount left SIGPIPE un-ignored for the whole process"


def _sigpipe_ignored() -> bool:
    """SIGPIPE's C-level disposition, from /proc (signal.getsignal only
    knows what Python itself installed)."""
    import signal
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("SigIgn:"):
                return bool(int(line.split()[1], 16)
                            >> (signal.SIGPIPE - 1) & 1)
    raise AssertionError("no SigIgn line in /proc/self/status")


def test_fuse_mount_end_to_end(tmp_path_factory, tmp_path):
    """Mount a real cluster through /dev/fuse and drive it with plain
    os/file calls. Skipped where libfuse or /dev/fuse is unavailable
    (the library-level tests above still cover the Wfs logic)."""
    import os

    with kernel_mount(tmp_path_factory, tmp_path, "fusemnt") as mp:
        # create + read back
        with open(f"{mp}/hello.txt", "w") as f:
            f.write("hello from fuse")
        assert os.listdir(mp) == ["hello.txt"]
        with open(f"{mp}/hello.txt") as f:
            assert f.read() == "hello from fuse"
        assert os.stat(f"{mp}/hello.txt").st_size == 15
        # append via truncate-less rewrite
        with open(f"{mp}/hello.txt", "w") as f:  # O_TRUNC path
            f.write("shorter")
        assert os.stat(f"{mp}/hello.txt").st_size == 7
        # directories + rename
        os.mkdir(f"{mp}/sub")
        os.rename(f"{mp}/hello.txt", f"{mp}/sub/hi.txt")
        assert os.listdir(mp) == ["sub"]
        with open(f"{mp}/sub/hi.txt") as f:
            assert f.read() == "shorter"
        # ENOENT surfaces as OSError
        with pytest.raises(FileNotFoundError):
            open(f"{mp}/nope.txt")
        # non-empty rmdir refused, then cleanup succeeds
        with pytest.raises(OSError):
            os.rmdir(f"{mp}/sub")
        os.remove(f"{mp}/sub/hi.txt")
        os.rmdir(f"{mp}/sub")
        assert os.listdir(mp) == []


def test_fuse_mount_links_xattrs(tmp_path_factory, tmp_path):
    """Kernel-level symlink / hardlink / xattr / utime through
    /dev/fuse (reference filesys/xattr.go, dir_link.go). Skipped where
    FUSE is unavailable; the library-level TestLinksAndXattrs still
    covers the Wfs logic."""
    import os
    import stat

    with kernel_mount(tmp_path_factory, tmp_path, "fuselnk") as mp:
        with open(f"{mp}/orig.txt", "w") as f:
            f.write("link target content")

        # symlink + readlink + lstat
        os.symlink(f"{mp}/orig.txt", f"{mp}/sym")
        assert os.readlink(f"{mp}/sym") == f"{mp}/orig.txt"
        assert stat.S_ISLNK(os.lstat(f"{mp}/sym").st_mode)
        with open(f"{mp}/sym") as f:  # kernel follows the link
            assert f.read() == "link target content"

        # hard link: same content, nlink=2 on both
        os.link(f"{mp}/orig.txt", f"{mp}/hard")
        assert os.stat(f"{mp}/hard").st_nlink == 2
        assert os.stat(f"{mp}/orig.txt").st_nlink == 2
        with open(f"{mp}/hard") as f:
            assert f.read() == "link target content"

        # write through one link name, read through the other: the
        # meta cache must resolve both names to the shared inode
        with open(f"{mp}/hard", "w") as f:
            f.write("rewritten via hard")
        with open(f"{mp}/orig.txt") as f:
            assert f.read() == "rewritten via hard"

        # xattrs through the kernel syscall surface. Sandboxed kernels
        # (gVisor-class: this CI image) answer EOPNOTSUPP from the VFS
        # layer without ever forwarding SETXATTR/GETXATTR over
        # /dev/fuse (verified: the shim's ctypes callbacks are never
        # invoked), so the xattr leg is skipped there — the Wfs xattr
        # logic itself is covered by TestLinksAndXattrs.
        import errno
        try:
            os.setxattr(f"{mp}/orig.txt", "user.k", b"v1")
            xattr_supported = True
        except OSError as e:
            if e.errno != errno.ENOTSUP:
                raise
            xattr_supported = False
        if xattr_supported:
            assert os.getxattr(f"{mp}/orig.txt", "user.k") == b"v1"
            assert "user.k" in os.listxattr(f"{mp}/orig.txt")
            os.setxattr(f"{mp}/orig.txt", "user.k", b"v2",
                        os.XATTR_REPLACE)
            assert os.getxattr(f"{mp}/orig.txt", "user.k") == b"v2"
            os.removexattr(f"{mp}/orig.txt", "user.k")
            assert "user.k" not in os.listxattr(f"{mp}/orig.txt")

        # utime persists an explicit mtime
        os.utime(f"{mp}/orig.txt", (1500000000, 1500000000))
        assert os.stat(f"{mp}/orig.txt").st_mtime == 1500000000
