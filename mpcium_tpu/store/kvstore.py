"""Key/value stores: encrypted share store + plain control-plane KV.

Reference equivalents:
- encrypted Badger for key shares (pkg/kvstore/badger.go — encryption key
  MANDATORY, badger.go:21-24): here an AEAD-encrypted file-backed store
  (ChaCha20-Poly1305 per value, scrypt-derived master key, atomic writes).
- Consul KV for control plane (pkg/infra/consul.go `ConsulKV` iface:
  Put/Get/Delete/List): here :class:`MemoryKV` (in-process cluster fabric)
  and :class:`FileKV` (multi-process on shared disk).
"""
from __future__ import annotations

import abc
import hashlib
import json
import os
import re
import secrets
import struct
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from ..utils.metrics import MetricsRegistry

try:
    from cryptography.hazmat.primitives.ciphers.aead import ChaCha20Poly1305
except ImportError:  # bare env: RFC-vector-validated pure-python fallback
    from ..core.softcrypto import ChaCha20Poly1305


# what the encrypted store promises a caller of ``put``: 1 kept every name in
# one sealed ``.index``, rewritten whole by every put of a new key; from 2 on
# a put appends, and never rewrites an index (EncryptedFileKV)
STORE_FORMAT = 2
# how the store lies on disk, stated in the log's sealed header: 1 and 2 kept
# a file a key (beside ``.index``, then the ``.names`` journal); 3 keeps
# names and values in one sealed log
VALUE_LAYOUT = 3

_LEN = struct.Struct(">I")
_HEAD = struct.Struct(">II")  # a log record's name part and value, in bytes
_NAMES_AD = b"names"
_NAMES_HEADER = b"mpcium-names-2"
_LOG_HEADER = b"mpcium-log-%d" % VALUE_LAYOUT
# name records are padded to a multiple of this, so a record's length tells
# a reader of the disk no more of a name than which 64 bytes its length
# falls in (a uuid wallet id under ``eddsa:`` and one under ``ecdsa:`` alike)
_NAME_PAD = 64
_SEAL = 12 + 16  # what sealing adds: the nonce before, the tag behind
_CHUNK = 1 << 20  # the buffer a pass over the whole log reads and writes by
_VALUE_FILE = re.compile(r"[0-9a-f]{48}(\.tmp)?")  # layout 2's, a key


class KVStore(abc.ABC):
    """Reference kvstore.KVStore (kvstore.go:4-16) + Keys iterator."""

    @abc.abstractmethod
    def put(self, key: str, value: bytes) -> None: ...

    @abc.abstractmethod
    def get(self, key: str) -> Optional[bytes]: ...

    @abc.abstractmethod
    def delete(self, key: str) -> None: ...

    @abc.abstractmethod
    def keys(self, prefix: str = "") -> List[str]: ...

    def close(self) -> None:
        pass


class EncryptedFileKV(KVStore):
    """Encrypted share store. The encryption key is mandatory (reference
    badger.go:21-24 errors out without one). Every name and value lives in
    ONE append-only log under ``root`` (``.log``), as upstream's Badger
    keeps a value log: values sealed with ChaCha20-Poly1305 under the
    name as associated data, names sealed too, so nothing under the root
    tells a wallet id.

    A record is ``>II`` (the two parts' lengths), the name part (the seal
    of ``+name`` or ``-name`` padded to a multiple of ``_NAME_PAD`` bytes)
    and, after a ``+name``, the sealed value. The first record is a sealed
    header that states the layout. A ``put`` appends one record by ONE
    ``write`` on a handle held open from the first use to ``close()``; a
    ``get`` is ONE positioned read of exactly the value's length, found in
    an index ``name -> (offset, length)`` the store keeps in memory (no
    value, sealed or open, stays there). A put over a held key and a
    ``delete`` append a record that supersedes the older one. Opening
    rebuilds the index in one sequential pass over the name parts and
    writes the log anew, live records only, once it held a superseded or
    deleted record or a torn tail; a running store does the same when its
    dead bytes pass its live bytes. A store of layout 2 (a file a key
    beside the ``.names`` journal) or 1 (beside one sealed ``.index``) is
    carried over when it is opened. One process a store."""

    def __init__(self, root, password: str,
                 metrics: Optional[MetricsRegistry] = None):
        if not password:
            raise ValueError("encryption password is required")  # badger.go:23
        t0 = time.perf_counter()
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        salt_path = self.root / ".salt"
        if salt_path.exists():
            salt = salt_path.read_bytes()
        else:
            salt = secrets.token_bytes(16)
            salt_path.write_bytes(salt)
        self._key = hashlib.scrypt(
            password.encode(), salt=salt, n=2**14, r=8, p=1,
            maxmem=64 * 1024 * 1024, dklen=32,
        )
        self._aead = ChaCha20Poly1305(self._key)
        self._lock = threading.RLock()
        # the node's registry where the node was built with one (cluster.py,
        # node/daemon.py); a store on its own keeps its own books
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._m_put = self.metrics.histogram("store.put_s")
        self._m_get = self.metrics.histogram("store.get_s")
        self._m_keys = self.metrics.gauge("store.keys")
        self._m_index_bytes = self.metrics.counter(
            "store.index_bytes_written_total")
        self._m_log_bytes = self.metrics.gauge("store.log_bytes")
        self._m_dead_bytes = self.metrics.gauge("store.dead_bytes")
        self._m_compactions = self.metrics.counter("store.compactions_total")
        self._log_path = str(self.root / ".log")
        self._fd: Optional[int] = None  # the log, for appends and reads
        # name -> (where its sealed value starts in the log, its length)
        self._index: Dict[str, Tuple[int, int]] = {}
        self._end = 0  # the log's length: where the next record starts
        self._dead = 0  # bytes of it in superseded and delete records
        try:
            self._load()
        except Exception as e:  # noqa: BLE001 — fail fast at open
            raise ValueError(
                "wrong encryption password or corrupted store"
            ) from e
        self._books()
        self.metrics.histogram("store.open_s").observe(
            time.perf_counter() - t0)

    # public sealing surface: the session WAL (store/session_wal.py) seals
    # its entries with this store's AEAD + key-derived filenames so WAL
    # files leak exactly as little as the share log next to them
    def hashed_name(self, key: str) -> str:
        return hashlib.sha256(self._key + key.encode()).hexdigest()[:48]

    def seal(self, data: bytes, ad: bytes) -> bytes:
        return self._seal(data, ad)

    def unseal(self, blob: bytes, ad: bytes) -> bytes:
        return self._open(blob, ad)

    def _seal(self, data: bytes, ad: bytes) -> bytes:
        nonce = secrets.token_bytes(12)
        return nonce + self._aead.encrypt(nonce, data, ad)

    def _open(self, blob: bytes, ad: bytes) -> bytes:
        return self._aead.decrypt(blob[:12], blob[12:], ad)

    # -- the log --------------------------------------------------------------

    def _record(self, name: bytes, sealed_value: bytes = b"") -> bytes:
        part = self._seal(name + b"\0" * (-len(name) % _NAME_PAD), _NAMES_AD)
        return _HEAD.pack(len(part), len(sealed_value)) + part + sealed_value

    @staticmethod
    def _record_len(key: str, sealed: int) -> int:
        """The bytes ``key``'s record takes, from its value's alone."""
        name = 1 + len(key.encode())
        return _HEAD.size + _SEAL + name + (-name % _NAME_PAD) + sealed

    def _load(self) -> None:
        """The index from the log, in one pass over its name parts (a
        buffered reader steps over the values). A store with no log is a
        new one or one of an older layout: it is given its log first."""
        if not os.path.exists(self._log_path):
            self._carry_over()
        size = os.path.getsize(self._log_path)
        off, torn = 0, False
        with open(self._log_path, "rb", buffering=_CHUNK) as log:
            while off < size and not torn:
                head = log.read(_HEAD.size)
                # lengths cut short stand for a record that ends past the log
                part, sealed = (_HEAD.unpack(head)
                                if len(head) == _HEAD.size else (size, 0))
                end = off + _HEAD.size + part + sealed
                try:
                    if end > size:
                        raise ValueError("record cut short")
                    name = self._open(log.read(part),
                                      _NAMES_AD).rstrip(b"\0")
                    if end == size and sealed:
                        # the last put: a crash may have left its length
                        # and not its bytes, so its value has to open too
                        self._open(log.read(sealed), name[1:])
                except Exception:  # noqa: BLE001 — InvalidTag, ValueError
                    # a LAST record cut short or unsealable is a put or a
                    # delete that a crash tore: dropped, never read as
                    # data. Any other fails the open, the header first (a
                    # wrong password)
                    if not off or end < size:
                        raise
                    torn = True
                    continue
                if not off:
                    if name != _LOG_HEADER:
                        raise ValueError(f"not a layout-{VALUE_LAYOUT} log")
                else:
                    self._replay(name, off + _HEAD.size + part, sealed)
                log.seek(end)
                off = end
        if not off:
            raise ValueError("a log with no header")
        self._end = off
        if torn or self._dead:
            self._compact()
        if any((self.root / left).exists()
               for left in (".names", ".index", ".log.tmp")):
            self._remove_leftovers()

    def _replay(self, name: bytes, at: int, sealed: int) -> None:
        """One record's effect on the index and on the count of dead
        bytes: its own where it is a delete, and those of the record it
        supersedes."""
        op, key = name[:1], name[1:].decode()
        if op not in (b"+", b"-") or (op == b"-" and sealed):
            raise ValueError("unknown kind of record")
        old = self._index.pop(key, None)
        if old is not None:
            self._dead += self._record_len(key, old[1])
        if op == b"+":
            self._index[key] = (at, sealed)
        else:
            self._dead += self._record_len(key, 0)

    def _handle(self) -> int:
        if self._fd is None:  # held from the first use on
            self._fd = os.open(self._log_path, os.O_RDWR | os.O_APPEND)
        return self._fd

    def _append(self, name: bytes, sealed_value: bytes = b"") -> None:
        """One record at the log's end, by one ``write``: nothing is
        buffered in user space, so the record has reached the OS when the
        call returns."""
        rec = self._record(name, sealed_value)
        fd = self._handle()
        try:
            view = memoryview(rec)
            while view:  # once: a regular file takes a write whole
                view = view[os.write(fd, view):]
        except BaseException:
            # half a record would hide every record after it from an open
            os.ftruncate(fd, self._end)
            raise
        at = self._end + len(rec) - len(sealed_value)
        self._end += len(rec)
        self._m_index_bytes.inc(len(rec) - len(sealed_value))
        self._replay(name, at, len(sealed_value))
        if self._dead > self._end - self._dead:
            self._compact()
        self._books()

    def _books(self) -> None:
        self._m_keys.set(len(self._index))
        self._m_log_bytes.set(self._end)
        self._m_dead_bytes.set(self._dead)

    def _compact(self) -> None:
        """The log written anew beside itself and renamed into place: the
        header and every live record as they are (sealed once, never
        opened here), in their order, in one pass over the old log."""
        live = {at: key for key, (at, _) in self._index.items()}
        moved: Dict[str, Tuple[int, int]] = {}
        self.close()
        tmp = self._log_path + ".tmp"
        new_end = index_bytes = 0
        with open(self._log_path, "rb", buffering=_CHUNK) as old, \
                open(tmp, "wb", buffering=_CHUNK) as new:
            off = 0
            while off < self._end:
                part, sealed = _HEAD.unpack(old.read(_HEAD.size))
                size = _HEAD.size + part + sealed
                key = live.get(off + size - sealed) if sealed else None
                if key is not None or not off:
                    old.seek(off)
                    new.write(old.read(size))
                    if key is not None:
                        moved[key] = (new_end + size - sealed, sealed)
                    new_end += size
                    index_bytes += size - sealed
                else:
                    old.seek(off + size)
                off += size
        os.replace(tmp, self._log_path)
        # only now: a rewrite that failed leaves the old log and its index
        self._index, self._end, self._dead = moved, new_end, 0
        self._m_index_bytes.inc(index_bytes)
        self._m_compactions.inc()

    # -- the layouts before the log -------------------------------------------

    def _carry_over(self) -> None:
        """A log for a store that has none: of the header alone for a new
        store, and for one of layout 2 (or 1) of every name its journal
        (or ``.index``) holds with its value file's bytes, sealed as they
        are. Its password has opened the journal before a byte is
        written; the old files go once the log is in place."""
        names = sorted(self._old_names())
        tmp = self._log_path + ".tmp"
        with open(tmp, "wb", buffering=_CHUNK) as new:
            new.write(self._record(_LOG_HEADER))
            for key in names:
                try:
                    sealed = (self.root / self.hashed_name(key)).read_bytes()
                except FileNotFoundError:
                    continue  # a delete torn before its name was journaled
                new.write(self._record(b"+" + key.encode(), sealed))
        os.replace(tmp, self._log_path)

    def _old_names(self) -> Set[str]:
        """The names a store of layout 2 journaled in ``.names``, or one
        of layout 1 kept in its ``.index``; none for a new store."""
        root = self.root
        names: Set[str] = set()
        try:
            records = self._open_records((root / ".names").read_bytes())
        except FileNotFoundError:
            if (root / ".index").exists():
                names.update(json.loads(self._open(
                    (root / ".index").read_bytes(), b"index")).values())
            return names
        if records[:1] != [_NAMES_HEADER]:
            raise ValueError("not a layout-2 name journal")
        for rec in records[1:]:
            op, name = rec[:1], rec[1:].decode()
            if op == b"+":
                names.add(name)
            elif op == b"-":
                names.discard(name)
            else:
                raise ValueError("unknown kind of name record")
        return names

    def _open_records(self, blob: bytes) -> List[bytes]:
        """-> a layout-2 journal's records, opened, in order. A last
        record cut short or unsealable is a put or a delete that a crash
        tore: it is dropped, never read as data. Any other record that
        does not open raises, the header first (a wrong password)."""
        out: List[bytes] = []
        off = 0
        while off < len(blob):
            body = off + _LEN.size
            end = (body + _LEN.unpack_from(blob, off)[0]
                   if body <= len(blob) else body)
            try:
                if end > len(blob):
                    raise ValueError("record cut short")
                out.append(
                    self._open(blob[body:end], _NAMES_AD).rstrip(b"\0"))
            except Exception:  # noqa: BLE001 — InvalidTag, ValueError
                if out and end >= len(blob):
                    return out
                raise
            off = end
        return out

    def _remove_leftovers(self) -> None:
        """Every file a store of layout 1 or 2 kept, the journal last (a
        removal cut off is taken up again by the next open), and the half
        of a log that a rewrite cut off left beside it."""
        for f in os.listdir(self.root):
            if _VALUE_FILE.fullmatch(f):
                (self.root / f).unlink()
        for f in (".log.tmp", ".index", ".names.tmp", ".names"):
            (self.root / f).unlink(missing_ok=True)

    # -- the four operations ------------------------------------------------

    def put(self, key: str, value: bytes) -> None:
        t0 = time.perf_counter()
        with self._lock:
            self._append(b"+" + key.encode(),
                         self._seal(value, key.encode()))
        self._m_put.observe(time.perf_counter() - t0)

    def get(self, key: str) -> Optional[bytes]:
        t0 = time.perf_counter()
        with self._lock:
            held = self._index.get(key)
            value = None if held is None else self._open(
                os.pread(self._handle(), held[1], held[0]), key.encode())
        self._m_get.observe(time.perf_counter() - t0)
        return value

    def delete(self, key: str) -> None:
        with self._lock:
            if key in self._index:
                self._append(b"-" + key.encode())

    def keys(self, prefix: str = "") -> List[str]:
        with self._lock:
            return sorted(k for k in self._index if k.startswith(prefix))

    def close(self) -> None:
        """Let go of the log's handle (a later put or get takes it again)."""
        with self._lock:
            if self._fd is not None:
                os.close(self._fd)
                self._fd = None


class MemoryKV(KVStore):
    """In-process control-plane KV (the Consul analogue for loopback
    clusters); shared by reference `ConsulKV` consumers (registry, keyinfo,
    peers)."""

    def __init__(self):
        self._d: Dict[str, bytes] = {}
        self._lock = threading.RLock()

    def put(self, key: str, value: bytes) -> None:
        with self._lock:
            self._d[key] = value

    def get(self, key: str) -> Optional[bytes]:
        with self._lock:
            return self._d.get(key)

    def delete(self, key: str) -> None:
        with self._lock:
            self._d.pop(key, None)

    def keys(self, prefix: str = "") -> List[str]:
        with self._lock:
            return sorted(k for k in self._d if k.startswith(prefix))


class FileKV(KVStore):
    """Shared-disk control-plane KV for multi-process deployments (each key
    is a file; names are percent-encoded). Suitable for a docker-compose
    style dev stack on one host; production control planes plug in their
    own KVStore (etcd/Consul adapters)."""

    def __init__(self, root):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self._lock = threading.RLock()

    @staticmethod
    def _enc(key: str) -> str:
        import urllib.parse

        return urllib.parse.quote(key, safe="")

    @staticmethod
    def _dec(name: str) -> str:
        import urllib.parse

        return urllib.parse.unquote(name)

    def put(self, key: str, value: bytes) -> None:
        with self._lock:
            p = self.root / self._enc(key)
            tmp = str(p) + ".tmp"
            Path(tmp).write_bytes(value)
            os.replace(tmp, p)

    def get(self, key: str) -> Optional[bytes]:
        p = self.root / self._enc(key)
        try:
            return p.read_bytes()
        except FileNotFoundError:
            return None

    def delete(self, key: str) -> None:
        with self._lock:
            p = self.root / self._enc(key)
            if p.exists():
                p.unlink()

    def keys(self, prefix: str = "") -> List[str]:
        return sorted(
            self._dec(p.name)
            for p in self.root.iterdir()
            if not p.name.endswith(".tmp") and self._dec(p.name).startswith(prefix)
        )
