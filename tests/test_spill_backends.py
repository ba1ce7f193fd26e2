"""Pluggable spill backends, chaos-injected spill IO, tiered restore
misses, and mid-pull holder failover (reference: external_storage.py
spill/restore URLs + pull_manager multi-location retries)."""

import os
import socket
import struct
import threading

import pytest

from ray_tpu._private import builtin_metrics, chaos, spill
from ray_tpu._private.dataplane import (NodeObjectTable, ObjectPullError,
                                        ObjectServer, pull_object)
from ray_tpu._private.ids import JobID, ObjectID, TaskID
from ray_tpu._private.object_store import ObjectStore
from ray_tpu._private.spill import (FileSpillBackend, MockS3SpillBackend,
                                    SessionSpillBackend, SpillFailure,
                                    backend_for_uri, read_uri,
                                    register_spill_backend)
from ray_tpu.exceptions import ObjectLostError

_LEN = struct.Struct(">q")


def _oid(i: int) -> ObjectID:
    return ObjectID.for_return(TaskID.for_normal_task(JobID(b"\x07" * 4)), i)


def _restore_failures() -> float:
    return builtin_metrics.object_spill_failures().series().get(
        ("restore",), 0.0)


def _write_failures() -> float:
    return builtin_metrics.object_spill_failures().series().get(
        ("write",), 0.0)


@pytest.fixture(autouse=True)
def _no_chaos():
    yield
    chaos.reset()


# -- backend round-trips --------------------------------------------------


def test_file_backend_round_trip(tmp_path):
    backend = FileSpillBackend(str(tmp_path))
    uri = backend.write("obj-1.bin", b"payload" * 100)
    assert uri.startswith("file://") and os.path.isabs(
        uri[len("file://"):])
    assert backend.read(uri, expected_size=700) == b"payload" * 100
    # Atomic write: no .tmp turd survives a successful commit.
    assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]
    # Absolute file:// URIs are readable without the writing backend.
    assert read_uri(uri, 700) == b"payload" * 100
    backend.delete(uri)
    assert backend.read(uri) is None


def test_file_backend_accepts_buffer_lists(tmp_path):
    backend = FileSpillBackend(str(tmp_path))
    uri = backend.write("parts.bin", [b"abc", memoryview(b"def"), b"g"])
    assert backend.read(uri, expected_size=7) == b"abcdefg"


def test_session_backend_survives_writer():
    sid = f"spilltest{os.getpid()}"
    writer = SessionSpillBackend(sid)
    try:
        uri = writer.write("spilled-x.bin", b"durable!")
        assert uri == f"session://{sid}/spilled-x.bin"
        # The writer "dies" — close() must leave durable files in place.
        writer.close()
        assert read_uri(uri, len(b"durable!")) == b"durable!"
    finally:
        import shutil

        from ray_tpu._private.ray_logging import session_dir_for
        shutil.rmtree(session_dir_for(sid), ignore_errors=True)


def test_mock_s3_backend_cross_instance(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_MOCK_S3_DIR", str(tmp_path / "s3"))
    writer = MockS3SpillBackend("bucket-a")
    uri = writer.write("obj.bin", b"\x00\x01\x02" * 64)
    assert uri == "mock-s3://bucket-a/obj.bin"
    writer.close()  # durable: leaves the "bucket" alone
    # A fresh reader (any node) resolves the same bucket directory.
    assert read_uri(uri, 192) == b"\x00\x01\x02" * 64


def test_truncated_spill_is_tier_miss_not_exception(tmp_path):
    backend = FileSpillBackend(str(tmp_path))
    uri = backend.write("t.bin", b"x" * 4096)
    path = backend.path_for(uri)
    with open(path, "r+b") as f:
        f.truncate(100)
    before = _restore_failures()
    assert backend.read(uri, expected_size=4096) is None
    assert _restore_failures() == before + 1
    # A missing file is the same tier-miss contract.
    os.unlink(path)
    assert backend.read(uri, expected_size=4096) is None


# -- URI dispatch / registration ------------------------------------------


def test_backend_for_uri_dispatch(tmp_path):
    assert isinstance(backend_for_uri("", fallback_dir=str(tmp_path)),
                      FileSpillBackend)
    b = backend_for_uri(f"file://{tmp_path}")
    assert isinstance(b, FileSpillBackend) and b.root == str(tmp_path)
    assert isinstance(backend_for_uri("session://", session_id="abc"),
                      SessionSpillBackend)
    assert isinstance(backend_for_uri("session://explicit-id"),
                      SessionSpillBackend)
    s3 = backend_for_uri("mock-s3://mybucket")
    assert isinstance(s3, MockS3SpillBackend) and s3.bucket == "mybucket"
    with pytest.raises(ValueError):
        backend_for_uri("session://")  # no session id known yet
    with pytest.raises(ValueError):
        backend_for_uri("s3://real-bucket")  # scheme not registered
    with pytest.raises(ValueError):
        backend_for_uri("not a uri at all here")


def test_register_spill_backend_custom_scheme(tmp_path):
    class UnitBackend(FileSpillBackend):
        scheme = "unit-test"

    register_spill_backend("unit-test",
                           lambda uri: UnitBackend(str(tmp_path)))
    try:
        b = backend_for_uri("unit-test://whatever")
        assert isinstance(b, UnitBackend)
        uri = b.write("k.bin", b"custom")
        # read_uri resolves registered schemes too.
        assert read_uri(uri, 6) == b"custom"
    finally:
        with spill._LOCK:
            spill._BACKENDS.pop("unit-test", None)


# -- chaos-injected spill IO ----------------------------------------------


def test_chaos_write_error_raises_spill_failure(tmp_path):
    backend = FileSpillBackend(str(tmp_path))
    chaos.configure("io_oserror:site=spill.write_error")
    before = _write_failures()
    with pytest.raises(SpillFailure):
        backend.write("doomed.bin", b"y" * 128)
    assert _write_failures() == before + 1
    assert not os.listdir(tmp_path)  # no torn file, no .tmp turd
    chaos.reset()
    uri = backend.write("doomed.bin", b"y" * 128)
    assert backend.read(uri, 128) == b"y" * 128


# -- the incremental writer: write()'s contract, part by part --------------


@pytest.mark.parametrize("make", [
    lambda tmp: FileSpillBackend(str(tmp)),
    lambda tmp: MockS3SpillBackend("parts"),
], ids=["file", "mock-s3"])
def test_writer_is_invisible_until_commit(tmp_path, monkeypatch, make):
    import numpy as np
    monkeypatch.setenv("RAY_TPU_MOCK_S3_DIR", str(tmp_path / "s3"))
    backend = make(tmp_path)
    parts = [b"abc", bytearray(b"defg"), memoryview(b"hi"),
             np.arange(5, dtype=np.uint8)]
    with backend.open_writer("big.bin") as writer:
        for part in parts:
            writer.write(part)
            assert backend.list_files() == []  # .tmp turds are not listed
            assert backend.size_of(backend.uri_for("big.bin")) is None
        uri = writer.commit()
    assert uri == backend.uri_for("big.bin")
    assert backend.list_files() == ["big.bin"]
    assert backend.read(uri) == b"abcdefghi" + bytes(range(5))
    assert os.listdir(backend.root) == ["big.bin"]
    # The same bytes as one write() of the same parts.
    backend.write("whole.bin", parts)
    assert backend.read(backend.uri_for("whole.bin")) == backend.read(uri)


@pytest.mark.parametrize("after", [0, 1, 3, 4],
                         ids=["open", "part-1", "part-3", "commit"])
def test_writer_chaos_error_at_any_point_leaves_nothing(tmp_path, after):
    """``spill.write_error`` is evaluated at the open, at every part and at
    the commit: wherever it fires, the ``.tmp`` is unlinked, one write
    failure is counted and the caller gets ``SpillFailure``."""
    backend = FileSpillBackend(str(tmp_path))
    backend.write("kept.bin", b"old")
    chaos.configure(
        f"io_oserror:site=spill.write_error:after={after}:times=1")
    before = _write_failures()
    with pytest.raises(SpillFailure, match="spill write of new.bin failed"):
        with backend.open_writer("new.bin") as writer:
            for part in (b"1", b"2", b"3"):
                writer.write(part)
            writer.commit()
    assert _write_failures() == before + 1
    assert os.listdir(tmp_path) == ["kept.bin"]
    chaos.reset()
    with backend.open_writer("new.bin") as writer:
        writer.write(b"again")
        assert backend.read(writer.commit()) == b"again"


def test_writer_sync_puts_the_parts_so_far_on_the_disk(tmp_path,
                                                      monkeypatch):
    """``sync`` is a wait, not a commit: the bytes are under ``.tmp``, the
    final name is still absent, and an ``OSError`` in it is a spill failure
    like any other."""
    backend = FileSpillBackend(str(tmp_path))
    synced = []
    fdatasync = os.fdatasync
    monkeypatch.setattr(os, "fdatasync",
                        lambda fd: synced.append(os.fstat(fd).st_size)
                        or fdatasync(fd))
    with backend.open_writer("big.bin") as writer:
        writer.write(b"abc")
        writer.sync()
        assert synced == [3] and backend.list_files() == []
        assert os.listdir(tmp_path) == ["big.bin.tmp"]
        writer.write(b"defg")
        assert backend.read(writer.commit()) == b"abcdefg"

    def refuse(fd):
        raise OSError(5, "Input/output error")

    monkeypatch.setattr(os, "fdatasync", refuse)
    before = _write_failures()
    with pytest.raises(SpillFailure, match="spill write of bad.bin failed"):
        with backend.open_writer("bad.bin") as writer:
            writer.write(b"x")
            writer.sync()
    assert _write_failures() == before + 1
    assert os.listdir(tmp_path) == ["big.bin"]


def test_writer_left_without_commit_aborts(tmp_path):
    """Another exception than an ``OSError`` (or no commit at all) is not a
    spill failure, but the ``.tmp`` goes all the same."""
    backend = FileSpillBackend(str(tmp_path))
    before = _write_failures()
    with pytest.raises(KeyError):
        with backend.open_writer("half.bin") as writer:
            writer.write(b"x" * 64)
            raise KeyError("the producer failed")
    with backend.open_writer("never.bin") as writer:
        writer.write(b"y")
    assert os.listdir(tmp_path) == []
    assert _write_failures() == before


def test_writer_replaces_an_older_payload_atomically(tmp_path):
    backend = FileSpillBackend(str(tmp_path))
    uri = backend.write("same.bin", b"old payload")
    with backend.open_writer("same.bin") as writer:
        writer.write(b"new")
        assert backend.read(uri) == b"old payload"
        writer.commit()
    assert backend.read(uri) == b"new"


def test_chaos_restore_error_is_tier_miss(tmp_path):
    backend = FileSpillBackend(str(tmp_path))
    uri = backend.write("r.bin", b"z" * 128)
    chaos.configure("io_oserror:site=spill.restore_error")
    before = _restore_failures()
    assert backend.read(uri, 128) is None
    assert _restore_failures() == before + 1
    chaos.reset()
    assert backend.read(uri, 128) == b"z" * 128  # file was never harmed


def test_store_keeps_value_in_memory_on_write_failure(tmp_path):
    """A failed spill degrades gracefully: the victim stays readable
    from memory and no half-written file becomes visible."""
    store = ObjectStore(spill_threshold_bytes=3 * 1024,
                        spill_directory=str(tmp_path), use_native=False)
    chaos.configure("io_oserror:site=spill.write_error")
    oids = [_oid(i) for i in range(1, 6)]
    for i, oid in enumerate(oids):
        store.put_inline(oid, bytes([i]) * 1024)
    assert store.spill_stats()["spill_count"] == 0
    assert not list(tmp_path.glob("spilled-*.bin"))
    for i, oid in enumerate(oids):
        assert store.get(oid) == bytes([i]) * 1024


# -- restored-object re-spill & restore-miss recovery ---------------------


def test_restored_object_respills_by_reference(tmp_path):
    """After a restore the spill file stays valid; renewed pressure
    drops the copy again WITHOUT re-serializing or re-writing."""
    store = ObjectStore(spill_threshold_bytes=1024,
                        spill_directory=str(tmp_path), use_native=False)
    a = _oid(1)
    store.put_inline(a, b"a" * 2048)  # over threshold → spilled at once
    assert store.spill_stats()["spill_count"] == 1
    assert store.get(a) == b"a" * 2048  # restored; file stays valid
    assert store.spill_stats()["restore_count"] == 1
    writes = []
    backend = store._backend()
    original_write = backend.write
    backend.write = lambda *args, **kw: writes.append(args) or \
        original_write(*args, **kw)
    # Re-pressure: the restored entry is the coldest candidate and its
    # file is still on disk, so it drops by reference — no write.
    store.put_inline(_oid(2), b"b" * 512)
    assert store.spill_stats()["spill_count"] == 2
    assert writes == []
    assert store.get(a) == b"a" * 2048  # second restore, same file


def test_restore_miss_without_hook_is_object_lost(tmp_path):
    store = ObjectStore(spill_threshold_bytes=1024,
                        spill_directory=str(tmp_path), use_native=False)
    a, b = _oid(1), _oid(2)
    store.put_inline(a, b"a" * 2048)
    store.put_inline(b, b"b" * 2048)  # pressure → a spills
    for f in tmp_path.glob("spilled-*.bin"):
        f.unlink()  # the durable copy vanishes out from under us
    with pytest.raises(ObjectLostError, match="no longer readable"):
        store.get(a)


def test_restore_miss_hook_recovers(tmp_path):
    """A hook that re-seals the object (what the runtime's lineage
    reconstruction does) turns the tier miss into a successful get."""
    store = ObjectStore(spill_threshold_bytes=1024,
                        spill_directory=str(tmp_path), use_native=False)
    a, b = _oid(1), _oid(2)
    store.put_inline(a, b"a" * 2048)
    store.put_inline(b, b"b" * 2048)
    for f in tmp_path.glob("spilled-*.bin"):
        f.unlink()
    calls = []

    def hook(oid):
        calls.append(oid)
        store.invalidate([oid])
        store.put_inline(oid, b"a" * 2048)  # "re-executed the producer"
        return True

    store.restore_miss_hook = hook
    assert store.get(a, timeout=10) == b"a" * 2048
    assert calls == [a]


# -- mid-pull holder failover ---------------------------------------------


@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PULL_CHUNK_BYTES", str(64 * 1024))
    monkeypatch.setenv("RAY_TPU_PULL_PARALLELISM", "4")


def _patterned(n: int) -> bytes:
    return bytes((i * 31 + (i >> 8)) & 0xFF for i in range(n))


def _recv_exact(sock, n):
    buf = b""
    while len(buf) < n:
        part = sock.recv(n - len(buf))
        if not part:
            raise ConnectionError("closed")
        buf += part
    return buf


class _HalfwayDeadServer:
    """Answers stats, then dies halfway through every ranged body —
    a holder that drops out MID-PULL."""

    def __init__(self, payload: bytes):
        self.payload = payload
        self._listener = socket.create_server(("127.0.0.1", 0))
        self.port = self._listener.getsockname()[1]
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while True:
            try:
                sock, _ = self._listener.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(sock,),
                             daemon=True).start()

    def _serve(self, sock):
        try:
            while True:
                (klen,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
                key = _recv_exact(sock, klen).decode()
                if key.startswith("?"):
                    sock.sendall(_LEN.pack(len(self.payload)))
                elif key.startswith("@"):
                    _, length, _ = key[1:].split(":", 2)
                    length = int(length)
                    sock.sendall(_LEN.pack(length)
                                 + self.payload[:length // 2])
                    return
                else:
                    sock.sendall(_LEN.pack(len(self.payload))
                                 + self.payload)
        except (ConnectionError, OSError):
            pass
        finally:
            sock.close()

    def close(self):
        self._listener.close()


def test_midpull_holder_death_resumes_from_second_holder(small_chunks):
    """The primary dies mid-chunk; the shared cursor fails the pull
    over to the backup holder and the landing is byte-identical —
    no error, no reconstruction."""
    payload = _patterned(512 * 1024)  # 8 chunks at 64 KB
    primary = _HalfwayDeadServer(payload)
    backup_table = NodeObjectTable()
    backup_table.put("vic", payload)
    backup = ObjectServer(backup_table, host="127.0.0.1")
    try:
        dst = NodeObjectTable()
        pull_object(("127.0.0.1", primary.port), "vic", dst,
                    retries=0, size_hint=len(payload),
                    fallback_addrs=[("127.0.0.1", backup.port)])
        with dst.pinned("vic") as got:
            assert got is not None
            assert bytes(got) == payload
    finally:
        primary.close()
        backup.close()


def test_dead_primary_fails_over_whole_pull(small_chunks):
    """A primary that refuses connections outright: the candidate loop
    retries the whole pull against the fallback holder."""
    payload = _patterned(8 * 1024)  # small → monolithic path
    dead = socket.create_server(("127.0.0.1", 0))
    dead_port = dead.getsockname()[1]
    dead.close()  # nothing listens here any more
    src = NodeObjectTable()
    src.put("k", payload)
    server = ObjectServer(src, host="127.0.0.1")
    try:
        dst = NodeObjectTable()
        pull_object(("127.0.0.1", dead_port), "k", dst,
                    retries=0, size_hint=len(payload),
                    fallback_addrs=[("127.0.0.1", server.port)])
        with dst.pinned("k") as got:
            assert bytes(got) == payload
    finally:
        server.close()


def test_all_holders_dead_raises_pull_error(small_chunks):
    dead = socket.create_server(("127.0.0.1", 0))
    port_a = dead.getsockname()[1]
    dead.close()
    dead = socket.create_server(("127.0.0.1", 0))
    port_b = dead.getsockname()[1]
    dead.close()
    dst = NodeObjectTable()
    with pytest.raises(ObjectPullError):
        pull_object(("127.0.0.1", port_a), "ghost", dst, retries=0,
                    fallback_addrs=[("127.0.0.1", port_b)])
    assert not dst.contains("ghost")
