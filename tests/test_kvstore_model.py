"""The encrypted share store against a plain ``dict``: the same seeded
sequence of operations, with the store closed and reopened at random points,
gives the same answers from both. And what the name journal (``.names``,
format 2) promises on disk: a torn last record is dropped at open, a wrong
password or a damaged record fails there, a format-1 store is carried over,
nothing lies in the clear, and a put's cost in index bytes does not grow
with the store."""
import json
import os
import random

import pytest

from mpcium_tpu.store import kvstore
from mpcium_tpu.store.kvstore import EncryptedFileKV
from mpcium_tpu.utils.metrics import MetricsRegistry

PW = "correct horse"
INDEX_BYTES = "store.index_bytes_written_total"


def _index_bytes(kv) -> float:
    return kv.metrics.counter(INDEX_BYTES).value


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


@pytest.mark.parametrize("seed", [35, 3_000_000_019])
def test_the_store_answers_as_a_dict_does(tmp_path, seed):
    rng = random.Random(seed)
    kv, model = EncryptedFileKV(tmp_path / "db", PW), {}
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
            kv.close()
            kv = EncryptedFileKV(tmp_path / "db", PW)
            reopened += 1
            assert kv.keys() == sorted(model)
    assert reopened >= 50 and len(model) >= 300
    kv = EncryptedFileKV(tmp_path / "db", PW)
    assert {k: kv.get(k) for k in names if kv.get(k) is not None} == model
    assert kv.metrics.gauge("store.keys").value == len(model)


@pytest.mark.parametrize("sealed", [kvstore._READ - 1, kvstore._READ,
                                    kvstore._READ + 1, 3 * kvstore._READ])
def test_a_value_around_one_reads_size_comes_back_whole(tmp_path, sealed):
    """``get`` stops at the first short read: a sealed file of exactly one
    read's size, or of several, still comes back whole (a GG18 share with
    its ``aux`` is tens of kilobytes)."""
    kv = EncryptedFileKV(tmp_path, PW)
    value = random.Random(sealed).randbytes(sealed - 28)  # nonce 12, tag 16
    kv.put("ecdsa:big", value)
    assert os.path.getsize(kv._fname("ecdsa:big")) == sealed
    assert kv.get("ecdsa:big") == value
    assert EncryptedFileKV(tmp_path, PW).get("ecdsa:big") == value


@pytest.mark.parametrize("cut", [1, 3, 4, 40, 95])
def test_a_torn_last_record_is_dropped_at_open(tmp_path, cut):
    """The journal cut off ``cut`` bytes into its last record (inside the
    length, at its end, inside the seal): every earlier key is there, the
    torn one's value is still found by ``get``, and the next open reads a
    whole journal."""
    _filled(tmp_path)
    journal = tmp_path / ".names"
    whole = journal.read_bytes()
    record = len(whole) // 21  # a header and twenty names, padded alike
    assert record * 21 == len(whole) and cut < record
    journal.write_bytes(whole[: 20 * record + cut])
    kv = EncryptedFileKV(tmp_path, PW)
    assert kv.keys() == sorted(f"eddsa:wallet-{i}" for i in range(19))
    assert kv.get("eddsa:wallet-19") == b"value-19"
    assert journal.stat().st_size == 20 * record  # written anew without it
    kv.put("eddsa:wallet-19", b"again")
    assert len(EncryptedFileKV(tmp_path, PW).keys()) == 20


def test_a_last_record_of_the_right_length_and_the_wrong_bytes_is_torn(
        tmp_path):
    _filled(tmp_path)
    journal = tmp_path / ".names"
    whole = journal.read_bytes()
    journal.write_bytes(whole[:-30] + bytes(30))
    assert len(EncryptedFileKV(tmp_path, PW).keys()) == 19


def test_a_damaged_record_before_the_last_fails_at_open(tmp_path):
    _filled(tmp_path)
    journal = tmp_path / ".names"
    whole = bytearray(journal.read_bytes())
    whole[len(whole) // 2] ^= 0x01
    journal.write_bytes(bytes(whole))
    with pytest.raises(ValueError, match="corrupted store"):
        EncryptedFileKV(tmp_path, PW)
    journal.write_bytes(b"")  # not even the header
    with pytest.raises(ValueError, match="corrupted store"):
        EncryptedFileKV(tmp_path, PW)


def test_a_wrong_password_fails_at_open_and_changes_nothing(tmp_path):
    before = _answers(_filled(tmp_path))
    on_disk = {p.name: p.read_bytes() for p in tmp_path.iterdir()}
    with pytest.raises(ValueError, match="wrong encryption password"):
        EncryptedFileKV(tmp_path, "another")
    with pytest.raises(ValueError, match="password is required"):
        EncryptedFileKV(tmp_path, "")
    assert {p.name: p.read_bytes() for p in tmp_path.iterdir()} == on_disk
    assert _answers(EncryptedFileKV(tmp_path, PW)) == before
    # a store that holds one name only: its journal's last record is its
    # header's neighbour, and still a wrong password is no torn tail
    one = EncryptedFileKV(tmp_path / "one", PW)
    one.put("eddsa:w", b"v")
    with pytest.raises(ValueError, match="wrong encryption password"):
        EncryptedFileKV(tmp_path / "one", "another")
    assert EncryptedFileKV(tmp_path / "one", PW).keys() == ["eddsa:w"]


def _as_format_1(root, kv):
    """Lay ``root`` out as the parent commit wrote it: the same value
    files, and every name in one sealed ``.index`` (a JSON object, file
    name -> key, under the associated data ``index``); no journal."""
    index = {kv.hashed_name(k): k for k in kv.keys()}
    (root / ".index").write_bytes(
        kv.seal(json.dumps(index).encode(), b"index"))
    (root / ".names").unlink()


def test_a_format_1_store_is_carried_over_at_open(tmp_path):
    kv = _filled(tmp_path)
    before = _answers(kv)
    _as_format_1(tmp_path, kv)
    with pytest.raises(ValueError, match="wrong encryption password"):
        EncryptedFileKV(tmp_path, "another")
    assert (tmp_path / ".index").exists()  # a failed open carries nothing
    registry = MetricsRegistry()
    carried = EncryptedFileKV(tmp_path, PW, metrics=registry)
    assert _answers(carried) == before
    assert (tmp_path / ".names").exists()
    assert not (tmp_path / ".index").exists()
    snap = registry.snapshot()
    assert snap["gauges"]["store.keys"] == 20
    assert snap["histograms"]["store.open_s"]["count"] == 1
    assert snap["counters"][INDEX_BYTES] == (
        tmp_path / ".names").stat().st_size
    # once: the next open finds format 2 and writes no index byte
    again = EncryptedFileKV(tmp_path, PW)
    assert _answers(again) == before and _index_bytes(again) == 0
    assert kvstore.STORE_FORMAT == 2


def test_nothing_under_the_root_is_in_the_clear(tmp_path):
    rng = random.Random(7)
    kv = EncryptedFileKV(tmp_path, PW)
    wallets = [f"wallet-{rng.getrandbits(64):016x}" for _ in range(50)]
    values = [_share(rng) for _ in wallets]
    for w, v in zip(wallets, values):
        kv.put(f"eddsa:{w}", v)
    kv.delete(f"eddsa:{wallets[0]}")
    EncryptedFileKV(tmp_path, PW)  # the journal written anew: that too
    kv.put(f"ecdsa:{wallets[1]}", values[1])
    for d, _dirs, files in os.walk(tmp_path):
        for f in files:
            blob = open(os.path.join(d, f), "rb").read()
            assert not any(w.encode() in blob or w in f for w in wallets), f
            assert not any(v in blob or v[10:40] in blob for v in values), f
            assert b"eddsa" not in blob and b"ecdsa" not in blob, f
    # names of one 64-byte bucket leave records of one length: the journal
    # does not tell a short wallet id from a long one
    short, long_ = tmp_path / "short", tmp_path / "long"
    EncryptedFileKV(short, PW).put("eddsa:w", b"v")
    EncryptedFileKV(long_, PW).put("eddsa:" + "w" * 50, b"v")
    assert (short / ".names").stat().st_size == (
        long_ / ".names").stat().st_size


def test_a_put_costs_the_same_index_bytes_at_any_size(tmp_path):
    """A count, not a timing: the bytes the name index cost for puts
    9,001-10,000 are within twice those for puts 1-1,000 (format 1 wrote
    the whole index a put: ~19 times more there)."""
    kv = EncryptedFileKV(tmp_path, PW)
    at = {}
    for i in range(10_000):
        if i in (0, 1000, 9000):
            at[i] = _index_bytes(kv)
        kv.put(f"eddsa:w35-{i}", b"share")
    first, tenth = at[1000] - at[0], _index_bytes(kv) - at[9000]
    assert 0 < tenth <= 2 * first and first <= 2 * tenth
    assert first <= 1000 * 256  # a bounded number of bytes a put
    # an overwrite records no name; a delete records one, of the same cost
    before = _index_bytes(kv)
    kv.put("eddsa:w35-5", b"share again")
    assert _index_bytes(kv) == before
    kv.delete("eddsa:w35-5")
    assert _index_bytes(kv) - before == first / 1000
    snap = kv.metrics.snapshot()
    assert snap["histograms"]["store.put_s"]["count"] == 10_001
    assert snap["gauges"]["store.keys"] == 9_999
    assert kv.get("eddsa:w35-5") is None and kv.get("eddsa:w35-6") == b"share"
    assert snap["histograms"]["store.get_s"]["count"] == 0
    assert kv.metrics.snapshot()["histograms"]["store.get_s"]["count"] == 2
