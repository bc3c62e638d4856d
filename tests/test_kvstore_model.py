"""The encrypted share store against a plain ``dict``: the same seeded
sequence of operations, with the store closed and reopened at random points,
gives the same answers from both. And what the sealed log (``.log``, layout
3) promises on disk: a torn last record is dropped at open, a wrong password
or a damaged record fails there, a store of layout 2 or 1 is carried over,
nothing lies in the clear, a put is one write and a get one positioned read
on a handle held open, and a put's cost in index bytes does not grow with
the store."""
import errno
import hashlib
import io
import json
import os
import random
import re

import pytest

from mpcium_tpu.store import kvstore
from mpcium_tpu.store.kvstore import EncryptedFileKV
from mpcium_tpu.utils.metrics import MetricsRegistry

PW = "correct horse"
INDEX_BYTES = "store.index_bytes_written_total"
COMPACTIONS = "store.compactions_total"


def _index_bytes(kv) -> float:
    return kv.metrics.counter(INDEX_BYTES).value


def _compactions(kv) -> float:
    return kv.metrics.counter(COMPACTIONS).value


def _gauges(kv) -> dict:
    return kv.metrics.snapshot()["gauges"]


def _share(rng: random.Random) -> bytes:
    """A value of a share record's shape and size, seeded."""
    return json.dumps({"share": rng.getrandbits(252),
                       "public_key": rng.randbytes(32).hex()}).encode()


def _filled(root, n=20):
    kv = EncryptedFileKV(root, PW)
    for i in range(n):
        kv.put(f"eddsa:wallet-{i}", b"value-%d" % i)
    return kv


def _answers(kv, n=20):
    return {f"eddsa:wallet-{i}": kv.get(f"eddsa:wallet-{i}")
            for i in range(n)}, kv.keys()


def _on_disk(root) -> dict:
    return {os.path.relpath(os.path.join(d, f), root):
            open(os.path.join(d, f), "rb").read()
            for d, _dirs, files in os.walk(root) for f in files}


@pytest.fixture
def one_scrypt(monkeypatch):
    """The key derivation (50 ms) once a (password, salt), for the tests
    that open a store some hundreds of times."""
    real, seen = hashlib.scrypt, {}

    def scrypt(password, *, salt, **kw):
        if (password, salt) not in seen:
            seen[password, salt] = real(password, salt=salt, **kw)
        return seen[password, salt]

    monkeypatch.setattr(kvstore.hashlib, "scrypt", scrypt)


@pytest.mark.parametrize("seed", [35, 3_000_000_019])
def test_the_store_answers_as_a_dict_does(tmp_path, seed):
    rng = random.Random(seed)
    registry = MetricsRegistry()  # one set of books over every reopening
    kv, model = EncryptedFileKV(tmp_path / "db", PW, metrics=registry), {}
    names = [f"{kind}:w{seed}-{i}" for i in range(700)
             for kind in ("ecdsa", "eddsa")]
    reopened = 0
    for _ in range(6000):
        op = rng.random()
        key = rng.choice(names)
        if op < 0.40:  # a new key, or an overwrite
            value = _share(rng)
            kv.put(key, value)
            model[key] = value
        elif op < 0.55:
            kv.delete(key)  # present or absent
            model.pop(key, None)
        elif op < 0.90:
            assert kv.get(key) == model.get(key)
        elif op < 0.98:
            prefix = rng.choice(["", "ecdsa:", "eddsa:", f"eddsa:w{seed}-1",
                                 "none:"])
            assert kv.keys(prefix) == sorted(
                k for k in model if k.startswith(prefix))
        else:
            before = _compactions(kv)
            kv.close()
            kv = EncryptedFileKV(tmp_path / "db", PW, metrics=registry)
            reopened += 1
            assert kv.keys() == sorted(model)
            # an open leaves no dead byte, by a rewrite if it found one
            assert _gauges(kv)["store.dead_bytes"] == 0
            assert _compactions(kv) - before in (0, 1)
        gauges = _gauges(kv)
        assert gauges["store.dead_bytes"] <= (
            gauges["store.log_bytes"] - gauges["store.dead_bytes"])
    assert reopened >= 50 and len(model) >= 300
    assert _compactions(kv) >= reopened // 2  # at opens, and between them
    kv = EncryptedFileKV(tmp_path / "db", PW)
    assert {k: kv.get(k) for k in names if kv.get(k) is not None} == model
    assert kv.metrics.gauge("store.keys").value == len(model)
    assert sorted(os.listdir(tmp_path / "db")) == [".log", ".salt"]
    assert _gauges(kv)["store.log_bytes"] == os.path.getsize(
        tmp_path / "db" / ".log")


def test_a_running_store_writes_its_log_anew_when_the_dead_pass_the_live(
        tmp_path):
    """A reshare of the whole population (a put over every held key) costs
    one rewrite, not one a key: the log is compacted when its dead bytes
    pass its live bytes, so it never holds more than twice its live
    records and a record more."""
    kv = EncryptedFileKV(tmp_path, PW)
    n = 500
    for i in range(n):
        kv.put(f"ecdsa:w39-{i}", b"epoch-0 share %d" % i)
    live = _gauges(kv)["store.log_bytes"]
    for epoch in (1, 2, 3):
        for i in range(n):
            kv.put(f"ecdsa:w39-{i}", b"epoch-%d share %d" % (epoch, i))
            gauges = _gauges(kv)
            assert gauges["store.log_bytes"] <= 2 * live + 200
            assert gauges["store.log_bytes"] == os.path.getsize(
                tmp_path / ".log")
    assert _compactions(kv) == 2  # one after each reshare but the first
    assert kv.keys() == sorted(f"ecdsa:w39-{i}" for i in range(n))
    assert all(kv.get(f"ecdsa:w39-{i}") == b"epoch-3 share %d" % i
               for i in range(n))
    # the rewrite moved every record; a store opened over it agrees
    again = EncryptedFileKV(tmp_path, PW)
    assert all(again.get(f"ecdsa:w39-{i}") == b"epoch-3 share %d" % i
               for i in range(n))
    assert _gauges(again)["store.dead_bytes"] == 0
    assert not (tmp_path / ".log.tmp").exists()


def test_a_rewrite_that_fails_leaves_the_old_log_and_its_index(
        tmp_path, monkeypatch):
    """A compaction cut off (a full disk, a crash) before its rename: the
    running store still answers from the old log, and the next open drops
    the half-written one."""
    kv = _filled(tmp_path)
    for i in range(1, 6):
        kv.delete(f"eddsa:wallet-{i}")
    held = kv.keys()
    assert _compactions(kv) == 0 and len(held) == 15

    def full_disk(src, dst):
        raise OSError(errno.ENOSPC, "No space left on device")

    monkeypatch.setattr(os, "replace", full_disk)
    with pytest.raises(OSError, match="No space"):
        for key in held[1:]:
            kv.delete(key)
    monkeypatch.undo()
    assert (tmp_path / ".log.tmp").exists() and _compactions(kv) == 0
    assert kv.get(held[0]) == b"value-0"  # wallet-0, where it always lay
    again = EncryptedFileKV(tmp_path, PW)
    assert again.keys() == kv.keys() and again.get(held[0]) == b"value-0"
    assert _compactions(again) == 1
    assert sorted(os.listdir(tmp_path)) == [".log", ".salt"]


_READ = 1 << 16  # what layout 2's ``get`` asked of a read


@pytest.mark.parametrize("sealed", [_READ - 1, _READ, _READ + 1, 3 * _READ])
def test_a_value_around_one_reads_size_comes_back_whole(tmp_path, sealed):
    """A sealed value of tens of kilobytes (a GG18 share with its ``aux``)
    comes back whole: from the log, whose ``get`` asks for the indexed
    length and guesses no read size, and through the carry-over of a
    layout-2 file."""
    kv = EncryptedFileKV(tmp_path, PW)
    value = random.Random(sealed).randbytes(sealed - 28)  # nonce 12, tag 16
    kv.put("ecdsa:big", value)
    kv.put("ecdsa:after", b"small")
    assert kv._index["ecdsa:big"][1] == sealed
    assert kv.get("ecdsa:big") == value and kv.get("ecdsa:after") == b"small"
    assert EncryptedFileKV(tmp_path, PW).get("ecdsa:big") == value
    _as_layout_2(tmp_path, kv)
    assert os.path.getsize(tmp_path / kv.hashed_name("ecdsa:big")) == sealed
    carried = EncryptedFileKV(tmp_path, PW)
    assert carried.get("ecdsa:big") == value
    assert carried.get("ecdsa:after") == b"small"


def _last_record(kv) -> int:
    """The length of the log's last record, a put of wallet 19."""
    return kv._record_len("eddsa:wallet-19", kv._index["eddsa:wallet-19"][1])


@pytest.mark.parametrize("cut", [1, 7, 8, 40, 99, 107])
def test_a_torn_last_record_is_dropped_at_open(tmp_path, cut):
    """The log cut off ``cut`` bytes into its last record (inside the
    lengths, at their end, inside the name part, at its end, inside the
    value): every earlier key is there, the torn one is not, and the
    next open reads a whole log."""
    kv = _filled(tmp_path)
    log = tmp_path / ".log"
    whole = log.read_bytes()
    record = _last_record(kv)
    assert 107 < record == 8 + 28 + 64 + 28 + len(b"value-19")
    log.write_bytes(whole[: len(whole) - record + cut])
    kv = EncryptedFileKV(tmp_path, PW)
    assert kv.keys() == sorted(f"eddsa:wallet-{i}" for i in range(19))
    assert kv.get("eddsa:wallet-19") is None
    assert kv.get("eddsa:wallet-18") == b"value-18"
    assert log.stat().st_size == len(whole) - record  # anew, without it
    assert _compactions(kv) == 1
    kv.put("eddsa:wallet-19", b"again")
    again = EncryptedFileKV(tmp_path, PW)
    assert len(again.keys()) == 20 and _compactions(again) == 0
    assert again.get("eddsa:wallet-19") == b"again"


@pytest.mark.parametrize("last", ["put", "overwrite", "delete"])
def test_a_crash_at_every_byte_of_the_last_record_leaves_the_state_before(
        tmp_path, one_scrypt, last):
    kv = _filled(tmp_path)
    before = _answers(kv, n=21)
    log = tmp_path / ".log"
    start = log.stat().st_size
    if last == "put":
        kv.put("eddsa:wallet-20", b"value-20")
    elif last == "overwrite":
        kv.put("eddsa:wallet-7", b"another value")
    else:
        kv.delete("eddsa:wallet-7")
    after = _answers(kv, n=21)
    kv.close()
    assert after != before
    whole = log.read_bytes()  # as the running store left it
    record = len(whole) - start
    assert record == kv._record_len(
        "eddsa:wallet-20", {"put": 28 + 8, "overwrite": 28 + 13,
                            "delete": 0}[last])
    for cut in range(record):
        log.write_bytes(whole[: start + cut])
        assert _answers(EncryptedFileKV(tmp_path, PW), n=21) == before, cut
        assert log.stat().st_size == start
    log.write_bytes(whole)
    assert _answers(EncryptedFileKV(tmp_path, PW), n=21) == after


def test_a_last_record_of_the_right_length_and_the_wrong_bytes_is_torn(
        tmp_path):
    """Lengths that reached the disk before their bytes: a tail of zeros
    where the last value should be, or where its name part should."""
    kv = _filled(tmp_path)
    log = tmp_path / ".log"
    whole = log.read_bytes()
    log.write_bytes(whole[:-30] + bytes(30))  # in the value
    opened = EncryptedFileKV(tmp_path, PW)
    assert len(opened.keys()) == 19
    assert opened.get("eddsa:wallet-19") is None
    record = _last_record(kv)
    log.write_bytes(whole[:-record + 8] + bytes(record - 8))  # in both
    assert len(EncryptedFileKV(tmp_path, PW).keys()) == 19


def test_a_damaged_record_before_the_last_fails_at_open(tmp_path):
    kv = _filled(tmp_path)
    log = tmp_path / ".log"
    whole = log.read_bytes()
    at = kv._index["eddsa:wallet-9"][0] - 50  # inside its name part
    log.write_bytes(whole[:at] + bytes([whole[at] ^ 0x01]) + whole[at + 1:])
    with pytest.raises(ValueError, match="corrupted store"):
        EncryptedFileKV(tmp_path, PW)
    log.write_bytes(b"")  # not even the header
    with pytest.raises(ValueError, match="corrupted store"):
        EncryptedFileKV(tmp_path, PW)
    log.write_bytes(whole[:50])  # the header alone, cut short: no torn put
    with pytest.raises(ValueError, match="corrupted store"):
        EncryptedFileKV(tmp_path, PW)
    # a damaged VALUE before the last opens (an open reads no value but
    # the last) and fails the get of that key alone, as a damaged value
    # file did
    at = kv._index["eddsa:wallet-9"][0] + 20
    log.write_bytes(whole[:at] + bytes([whole[at] ^ 0x01]) + whole[at + 1:])
    opened = EncryptedFileKV(tmp_path, PW)
    with pytest.raises(Exception):  # noqa: B017 — the AEAD's InvalidTag
        opened.get("eddsa:wallet-9")
    assert opened.get("eddsa:wallet-10") == b"value-10"


def test_a_wrong_password_fails_at_open_and_changes_nothing(tmp_path):
    before = _answers(_filled(tmp_path))
    on_disk = _on_disk(tmp_path)
    with pytest.raises(ValueError, match="wrong encryption password"):
        EncryptedFileKV(tmp_path, "another")
    with pytest.raises(ValueError, match="password is required"):
        EncryptedFileKV(tmp_path, "")
    assert _on_disk(tmp_path) == on_disk
    assert _answers(EncryptedFileKV(tmp_path, PW)) == before
    # a store that holds one name only: its log's last record is its
    # header's neighbour, and still a wrong password is no torn tail
    one = EncryptedFileKV(tmp_path / "one", PW)
    one.put("eddsa:w", b"v")
    with pytest.raises(ValueError, match="wrong encryption password"):
        EncryptedFileKV(tmp_path / "one", "another")
    assert EncryptedFileKV(tmp_path / "one", PW).keys() == ["eddsa:w"]


def test_the_header_states_the_layout(tmp_path):
    assert kvstore.STORE_FORMAT == 2  # what a put promises: it appends
    assert kvstore.VALUE_LAYOUT == 3  # how the store lies on disk
    kv = _filled(tmp_path, n=2)
    log = tmp_path / ".log"
    whole = log.read_bytes()
    part, sealed = kvstore._HEAD.unpack_from(whole)
    assert sealed == 0
    assert kv.unseal(whole[8:8 + part], b"names").rstrip(b"\0") == (
        b"mpcium-log-3")
    # a log of another layout, sealed under the same key, is refused
    other = kv.seal(b"mpcium-log-4".ljust(64, b"\0"), b"names")
    log.write_bytes(kvstore._HEAD.pack(len(other), 0) + other
                    + whole[8 + part:])
    with pytest.raises(ValueError, match="corrupted store"):
        EncryptedFileKV(tmp_path, PW)


# -- the layouts before the log -----------------------------------------------

def _name_record(kv, plain: bytes) -> bytes:
    sealed = kv.seal(plain + b"\0" * (-len(plain) % 64), b"names")
    return kvstore._LEN.pack(len(sealed)) + sealed


def _as_layout_2(root, kv, model=None):
    """Lay ``root`` out as PR 35 to PR 38 wrote it (format 2): a file a
    key, named by the keyed hash of the key and holding the value sealed
    with the key as associated data, and the ``.names`` journal (a sealed
    header, then a ``+name`` record a key); no log."""
    model = model if model is not None else {k: kv.get(k) for k in kv.keys()}
    kv.close()
    journal = [_name_record(kv, b"mpcium-names-2")]
    for key, value in model.items():
        (root / kv.hashed_name(key)).write_bytes(kv.seal(value, key.encode()))
        journal.append(_name_record(kv, b"+" + key.encode()))
    (root / ".names").write_bytes(b"".join(journal))
    (root / ".log").unlink()


def _as_format_1(root, kv):
    """Lay ``root`` out as the commits before PR 35 wrote it: the same
    value files, and every name in one sealed ``.index`` (a JSON object,
    file name -> key, under the associated data ``index``); no journal."""
    index = {kv.hashed_name(k): k for k in kv.keys()}
    _as_layout_2(root, kv)
    (root / ".index").write_bytes(
        kv.seal(json.dumps(index).encode(), b"index"))
    (root / ".names").unlink()


def test_a_layout_2_store_is_carried_over_at_open(tmp_path):
    kv = _filled(tmp_path)
    before = _answers(kv)
    _as_layout_2(tmp_path, kv)
    # as a running format-2 store left it: a deleted name, a repeated one
    with open(tmp_path / ".names", "ab") as journal:
        journal.write(_name_record(kv, b"-eddsa:wallet-3"))
        journal.write(_name_record(kv, b"+eddsa:wallet-4"))
    (tmp_path / kv.hashed_name("eddsa:wallet-3")).unlink()
    # a put torn before its name was journaled, and one torn before that
    (tmp_path / kv.hashed_name("eddsa:unnamed")).write_bytes(b"sealed")
    (tmp_path / (kv.hashed_name("eddsa:other") + ".tmp")).write_bytes(b"se")
    (tmp_path / "wal").mkdir()
    (tmp_path / "wal" / (kv.hashed_name("wal:s") + ".wal")).write_bytes(b"w")
    on_disk = _on_disk(tmp_path)
    with pytest.raises(ValueError, match="wrong encryption password"):
        EncryptedFileKV(tmp_path, "another")
    assert _on_disk(tmp_path) == on_disk  # a failed open carries nothing
    registry = MetricsRegistry()
    carried = EncryptedFileKV(tmp_path, PW, metrics=registry)
    before[0]["eddsa:wallet-3"] = None
    before[1].remove("eddsa:wallet-3")
    assert _answers(carried) == before
    # a log afterwards: no value file, no journal; the session WAL's stay
    assert sorted(_on_disk(tmp_path)) == [
        ".log", ".salt", "wal/" + kv.hashed_name("wal:s") + ".wal"]
    snap = registry.snapshot()
    assert snap["gauges"]["store.keys"] == 19
    assert snap["gauges"]["store.log_bytes"] == (
        tmp_path / ".log").stat().st_size
    assert snap["histograms"]["store.open_s"]["count"] == 1
    # once: the next open finds the log and writes nothing
    on_disk = _on_disk(tmp_path)
    again = EncryptedFileKV(tmp_path, PW)
    assert _answers(again) == before and _index_bytes(again) == 0
    assert _on_disk(tmp_path) == on_disk
    carried.put("eddsa:wallet-3", b"back")
    assert EncryptedFileKV(tmp_path, PW).get("eddsa:wallet-3") == b"back"


def test_a_carry_over_cut_off_is_taken_up_by_the_next_open(tmp_path):
    kv = _filled(tmp_path)
    before = _answers(kv)
    _as_layout_2(tmp_path, kv)
    with open(tmp_path / ".names", "ab") as journal:  # and its tail torn
        journal.write(_name_record(kv, b"+eddsa:wallet-20")[:50])
    old = _on_disk(tmp_path)
    # cut off while the log was being written: the old layout still rules
    (tmp_path / ".log.tmp").write_bytes(b"half a log")
    assert _answers(EncryptedFileKV(tmp_path, PW)) == before
    assert sorted(_on_disk(tmp_path)) == [".log", ".salt"]
    # cut off after the log was in place, before the old files went
    log = (tmp_path / ".log").read_bytes()
    for name, blob in old.items():
        (tmp_path / name).write_bytes(blob)
    (tmp_path / ".log").write_bytes(log)
    opened = EncryptedFileKV(tmp_path, PW)
    opened.put("eddsa:wallet-0", b"newer than its old file")
    before[0]["eddsa:wallet-0"] = b"newer than its old file"
    assert _answers(opened) == before
    assert sorted(_on_disk(tmp_path)) == [".log", ".salt"]


def test_a_format_1_store_is_carried_over_at_open(tmp_path):
    kv = _filled(tmp_path)
    before = _answers(kv)
    _as_format_1(tmp_path, kv)
    with pytest.raises(ValueError, match="wrong encryption password"):
        EncryptedFileKV(tmp_path, "another")
    assert (tmp_path / ".index").exists()  # a failed open carries nothing
    assert not (tmp_path / ".log").exists()
    registry = MetricsRegistry()
    carried = EncryptedFileKV(tmp_path, PW, metrics=registry)
    assert _answers(carried) == before
    assert sorted(_on_disk(tmp_path)) == [".log", ".salt"]
    snap = registry.snapshot()
    assert snap["gauges"]["store.keys"] == 20
    assert snap["histograms"]["store.open_s"]["count"] == 1
    # once: the next open finds the log and writes no index byte
    again = EncryptedFileKV(tmp_path, PW)
    assert _answers(again) == before and _index_bytes(again) == 0
    assert kvstore.STORE_FORMAT == 2
    assert kvstore.VALUE_LAYOUT == 3


def test_nothing_under_the_root_is_in_the_clear(tmp_path):
    rng = random.Random(7)
    kv = EncryptedFileKV(tmp_path, PW)
    wallets = [f"wallet-{rng.getrandbits(64):016x}" for _ in range(50)]
    values = [_share(rng) for _ in wallets]
    for w, v in zip(wallets, values):
        kv.put(f"eddsa:{w}", v)
    kv.delete(f"eddsa:{wallets[0]}")
    EncryptedFileKV(tmp_path, PW)  # the log written anew: that too
    kv = EncryptedFileKV(tmp_path, PW)
    kv.put(f"ecdsa:{wallets[1]}", values[1])
    kv.put(f"eddsa:{wallets[2]}", values[3])  # a superseded record stays
    kv.delete(f"eddsa:{wallets[4]}")
    assert _gauges(kv)["store.dead_bytes"] > 0
    for d, _dirs, files in os.walk(tmp_path):
        assert files
        for f in files:
            blob = open(os.path.join(d, f), "rb").read()
            assert not any(w.encode() in blob or w in f for w in wallets), f
            assert not any(v in blob or v[10:40] in blob for v in values), f
            assert b"eddsa" not in blob and b"ecdsa" not in blob, f
    # names of one 64-byte bucket leave records of one length: the log
    # does not tell a short wallet id from a long one
    short, long_ = tmp_path / "short", tmp_path / "long"
    EncryptedFileKV(short, PW).put("eddsa:w", b"v")
    EncryptedFileKV(long_, PW).put("eddsa:" + "w" * 50, b"v")
    assert (short / ".log").stat().st_size == (
        long_ / ".log").stat().st_size


def test_the_store_keeps_no_value_in_memory(tmp_path):
    """The index holds two whole numbers a key: no sealed or opened value
    hangs from the store after a put, a get or an open."""
    kv = _filled(tmp_path)
    kv.get("eddsa:wallet-5")
    for store in (kv, EncryptedFileKV(tmp_path, PW)):
        assert all(type(at) is int and type(n) is int
                   for at, n in store._index.values())
        held = [v for v in vars(store).values()
                if isinstance(v, (bytes, bytearray, memoryview, str))]
        assert sorted(map(type, held), key=repr) == [bytes, str]  # key, path
        assert all(not isinstance(v, (list, set, tuple))
                   for v in vars(store).values())
        assert [k for k, v in vars(store).items()
                if isinstance(v, dict)] == ["_index"]


# -- the count of file operations, which is what a put and a get cost --------

class _Calls:
    """``os``'s file calls and the built-in ``open``, counted."""

    NAMES = ("open", "write", "pread", "read", "replace", "rename", "close",
             "unlink", "ftruncate", "fsync", "stat", "listdir")

    def __init__(self, monkeypatch):
        self.seen = []
        for name in self.NAMES:
            monkeypatch.setattr(os, name, self._counted(name,
                                                        getattr(os, name)))
        monkeypatch.setattr(io, "open", self._counted("io.open", io.open))
        monkeypatch.setattr("builtins.open", io.open)

    def _counted(self, name, real):
        def call(*a, **kw):
            self.seen.append(name)
            return real(*a, **kw)
        return call

    def taken(self):
        seen, self.seen = self.seen, []
        return seen


def test_a_put_is_one_write_and_a_get_one_positioned_read(
        tmp_path, monkeypatch):
    kv = EncryptedFileKV(tmp_path, PW)
    kv.put("eddsa:first", b"takes the handle")
    calls = _Calls(monkeypatch)
    rng = random.Random(39)
    for i in range(50):
        kv.put(f"eddsa:w39-{i}", _share(rng))
        assert calls.taken() == ["write"]  # no open, no rename, no close
    for i in range(50):
        assert kv.get(f"eddsa:w39-{i}") is not None
        assert calls.taken() == ["pread"]
    assert kv.get("eddsa:absent") is None and calls.taken() == []
    kv.delete("eddsa:absent")
    assert calls.taken() == []
    assert kv.keys("eddsa:w39-1")[:2] == ["eddsa:w39-1", "eddsa:w39-10"]
    assert calls.taken() == []
    # a put over a held key (a reshare's new epoch) and a delete: one
    # write each, while the dead bytes have not passed the live
    kv.put("eddsa:w39-7", b"epoch 1")
    assert calls.taken() == ["write"]
    kv.delete("eddsa:w39-8")
    assert calls.taken() == ["write"]
    # a store closed takes its handle again at the next use, once
    kv.close()
    assert calls.taken() == ["close"]
    assert kv.get("eddsa:w39-7") == b"epoch 1"
    assert calls.taken() == ["open", "pread"]
    kv.put("eddsa:w39-8", b"back")
    assert calls.taken() == ["write"]
    assert sorted(os.listdir(tmp_path)) == [".log", ".salt"]


def test_a_write_that_fails_leaves_no_half_record(tmp_path, monkeypatch):
    kv = _filled(tmp_path, n=5)
    size = (tmp_path / ".log").stat().st_size
    real = os.write

    def full_disk(fd, data):
        real(fd, bytes(data[:40]))
        raise OSError(errno.ENOSPC, "No space left on device")

    monkeypatch.setattr(os, "write", full_disk)
    with pytest.raises(OSError, match="No space"):
        kv.put("eddsa:wallet-5", b"value-5")
    monkeypatch.setattr(os, "write", real)
    assert (tmp_path / ".log").stat().st_size == size
    assert kv.get("eddsa:wallet-5") is None and len(kv.keys()) == 5
    kv.put("eddsa:wallet-6", b"value-6")
    again = EncryptedFileKV(tmp_path, PW)
    assert again.keys() == sorted(
        f"eddsa:wallet-{i}" for i in (0, 1, 2, 3, 4, 6))
    assert _compactions(again) == 0  # nothing torn, nothing dead


def test_opening_65536_records_is_one_bounded_pass(tmp_path):
    """The custody cell's population a node: the index is rebuilt in a
    bounded time on the CPU (a pass over the name parts: no value is read
    but the last), ``store.open_s`` records it, and no file but the log
    is opened for it."""
    kv = EncryptedFileKV(tmp_path, PW)
    value = _share(random.Random(39)) * 4  # a share record's 600 bytes
    n = 65_536
    for i in range(n):
        kv.put(f"eddsa:w39-{i}", value)
    kv.close()
    size = (tmp_path / ".log").stat().st_size
    assert size > n * 600
    registry = MetricsRegistry()
    opened = EncryptedFileKV(tmp_path, PW, metrics=registry)
    snap = registry.snapshot()
    took = snap["histograms"]["store.open_s"]
    assert took["count"] == 1 and 0 < took["sum"] < 5.0
    assert snap["gauges"]["store.keys"] == n
    assert snap["gauges"]["store.log_bytes"] == size
    assert snap["gauges"]["store.dead_bytes"] == 0
    assert snap["counters"].get(COMPACTIONS, 0) == 0
    assert snap["counters"].get(INDEX_BYTES, 0) == 0
    assert opened.get(f"eddsa:w39-{n - 1}") == value
    assert opened.get("eddsa:w39-0") == value
    assert re.fullmatch(r"eddsa:w39-\d+", opened.keys()[n // 2])


def test_a_put_costs_the_same_index_bytes_at_any_size(tmp_path):
    """A count, not a timing: the bytes that are not a value's (a
    record's lengths and its sealed name) for puts 9,001-10,000 are
    within twice those for puts 1-1,000 (format 1 wrote the whole index
    a put: ~19 times more there)."""
    kv = EncryptedFileKV(tmp_path, PW)
    at = {}
    for i in range(10_000):
        if i in (0, 1000, 9000):
            at[i] = _index_bytes(kv)
        kv.put(f"eddsa:w35-{i}", b"share")
    first, tenth = at[1000] - at[0], _index_bytes(kv) - at[9000]
    assert 0 < tenth <= 2 * first and first <= 2 * tenth
    assert first <= 1000 * 256  # a bounded number of bytes a put
    # every byte of the log is a value's or is counted here
    assert _index_bytes(kv) + 10_000 * (28 + len(b"share")) == (
        tmp_path / ".log").stat().st_size - 100  # less the header's
    # an overwrite and a delete record a name each, of the same cost
    before = _index_bytes(kv)
    kv.put("eddsa:w35-5", b"share again")
    assert _index_bytes(kv) - before == first / 1000
    kv.delete("eddsa:w35-5")
    assert _index_bytes(kv) - before == 2 * first / 1000
    snap = kv.metrics.snapshot()
    assert snap["histograms"]["store.put_s"]["count"] == 10_001
    assert snap["gauges"]["store.keys"] == 9_999
    assert kv.get("eddsa:w35-5") is None and kv.get("eddsa:w35-6") == b"share"
    assert snap["histograms"]["store.get_s"]["count"] == 0
    assert kv.metrics.snapshot()["histograms"]["store.get_s"]["count"] == 2
