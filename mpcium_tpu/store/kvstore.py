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


# the encrypted store's on-disk format: 1 kept every name in one sealed
# ``.index``, rewritten whole by every put of a new key; 2 keeps them in
# the ``.names`` journal, one appended record a put (EncryptedFileKV)
STORE_FORMAT = 2

_LEN = struct.Struct(">I")
_NAMES_AD = b"names"
_NAMES_HEADER = b"mpcium-names-2"
# name records are padded to a multiple of this, so a record's length tells
# a reader of the disk no more of a name than which 64 bytes its length
# falls in (a uuid wallet id under ``eddsa:`` and one under ``ecdsa:`` alike)
_NAME_PAD = 64
_READ = 1 << 16  # bytes a read asks for: more than a sealed share record


def _write_file(path: str, blob: bytes) -> None:
    """``blob`` as the whole of ``path``, by three system calls. (The
    built-in ``open`` makes seven. Each is a release of the interpreter
    lock, which costs a thread its turn where three nodes' stores are
    written side by side in one process, and each costs 50-200 us where
    the file system is a sandbox's: PERF.md, PR 35.)"""
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o666)
    try:
        view = memoryview(blob)
        while view:
            view = view[os.write(fd, view):]
    finally:
        os.close(fd)


def _read_file(path: str) -> bytes:
    """The whole of ``path``, by three system calls where it is shorter
    than a read (a regular file's read comes back short only at its end,
    so no second read has to find nothing)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        parts = [os.read(fd, _READ)]
        while len(parts[-1]) == _READ:
            parts.append(os.read(fd, _READ))
        return b"".join(parts)
    finally:
        os.close(fd)


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
    badger.go:21-24 errors out without one). One file per key under
    ``root``; values sealed with ChaCha20-Poly1305; key names are hashed to
    filenames so the directory listing leaks no wallet ids.

    The names themselves (``keys``) live in ``.names``, a journal of sealed
    records, each ``>I`` length + seal of ``+name`` or ``-name`` padded to
    a multiple of ``_NAME_PAD`` bytes, after a sealed header: a ``put`` of
    a new key and a ``delete`` append one record, whatever the store
    holds, after the value file is in place (a crash between the two
    leaves a value ``get`` still finds). Opening replays the journal in
    one pass and, where it held a deleted name, a repeated one or a torn
    tail, writes it anew without them. A store of format 1 (one sealed
    ``.index`` of every name, rewritten whole a put) is carried over when
    it is opened. One process a store: the journal's handle is held from
    the first record to ``close()``."""

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
        self._names_path = self.root / ".names"
        self._names_file = None  # the journal, open for appending
        self._names: Set[str] = set()
        try:
            self._load_names()
        except Exception as e:  # noqa: BLE001 — fail fast at open
            raise ValueError(
                "wrong encryption password or corrupted store"
            ) from e
        self._m_keys.set(len(self._names))
        self.metrics.histogram("store.open_s").observe(
            time.perf_counter() - t0)

    def _fname(self, key: str) -> str:
        return os.path.join(self.root, self.hashed_name(key))

    # public sealing surface: the session WAL (store/session_wal.py) seals
    # its entries with this store's AEAD + key-derived filenames so WAL
    # files leak exactly as little as the share files next to them
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

    # -- the name journal ---------------------------------------------------

    def _name_record(self, plain: bytes) -> bytes:
        sealed = self._seal(plain + b"\0" * (-len(plain) % _NAME_PAD),
                            _NAMES_AD)
        return _LEN.pack(len(sealed)) + sealed

    def _load_names(self) -> None:
        """The names from ``.names`` (or from a format-1 ``.index``, which
        is carried over here), in one pass."""
        legacy = self.root / ".index"
        try:
            records, stale = self._open_records(
                self._names_path.read_bytes())
        except FileNotFoundError:  # a new store, or one of format 1
            records, stale = [_NAMES_HEADER], True
            if legacy.exists():
                self._names = set(json.loads(
                    self._open(legacy.read_bytes(), b"index")).values())
        if records[:1] != [_NAMES_HEADER]:
            raise ValueError(f"not a format-{STORE_FORMAT} name journal")
        for rec in records[1:]:
            op, name = rec[:1], rec[1:].decode()
            if op == b"+":
                stale |= name in self._names
                self._names.add(name)
            elif op == b"-":
                stale = True
                self._names.discard(name)
            else:
                raise ValueError("unknown kind of name record")
        if stale:
            self._rewrite_names()
        legacy.unlink(missing_ok=True)  # read above, or by an open cut off

    def _open_records(self, blob: bytes) -> Tuple[List[bytes], bool]:
        """-> (the journal's records, opened, in order; whether its tail
        was torn). A last record cut short or unsealable is a put or a
        delete that a crash tore: it is dropped, never read as data. Any
        other record that does not open raises, the header first (a wrong
        password)."""
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
                    return out, True
                raise
            off = end
        return out, False

    def _rewrite_names(self) -> None:
        blob = b"".join(
            [self._name_record(_NAMES_HEADER)]
            + [self._name_record(b"+" + k.encode())
               for k in sorted(self._names)])
        tmp = str(self._names_path) + ".tmp"
        Path(tmp).write_bytes(blob)
        os.replace(tmp, self._names_path)
        self._m_index_bytes.inc(len(blob))

    def _journal(self, op: bytes, key: str) -> None:
        rec = self._name_record(op + key.encode())
        if self._names_file is None:  # held from the first record on
            self._names_file = open(self._names_path, "ab", buffering=0)
        # unbuffered: the record has reached the OS when the call returns,
        # as the whole index had
        self._names_file.write(rec)
        self._m_index_bytes.inc(len(rec))
        self._m_keys.set(len(self._names))

    # -- the four operations ------------------------------------------------

    def put(self, key: str, value: bytes) -> None:
        t0 = time.perf_counter()
        with self._lock:
            path = self._fname(key)
            _write_file(path + ".tmp", self._seal(value, key.encode()))
            os.replace(path + ".tmp", path)
            if key not in self._names:
                self._names.add(key)
                self._journal(b"+", key)
        self._m_put.observe(time.perf_counter() - t0)

    def get(self, key: str) -> Optional[bytes]:
        t0 = time.perf_counter()
        with self._lock:
            try:
                value = self._open(_read_file(self._fname(key)),
                                   key.encode())
            except FileNotFoundError:
                value = None
        self._m_get.observe(time.perf_counter() - t0)
        return value

    def delete(self, key: str) -> None:
        with self._lock:
            try:
                os.unlink(self._fname(key))
            except FileNotFoundError:
                pass
            if key in self._names:
                self._names.discard(key)
                self._journal(b"-", key)

    def keys(self, prefix: str = "") -> List[str]:
        with self._lock:
            return sorted(k for k in self._names if k.startswith(prefix))

    def close(self) -> None:
        """Let go of the journal's handle (a later put takes it again)."""
        with self._lock:
            if self._names_file is not None:
                self._names_file.close()
                self._names_file = None


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
