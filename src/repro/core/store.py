"""On-disk bitstream store: compiled overlay kernels that survive the process.

The paper's economics rest on *pre-synthesized* bitstreams: assembly is cheap
at runtime because synthesis already happened.  PR 4 made our compiled
artifacts placement-free (one executable serves every placement), which is
exactly the property that makes them durable: a kernel keyed by
``kernel_key`` — name, abstract signature, mesh descriptor, code fingerprint —
is valid for any future process on the same jaxlib, regardless of where the
fabric ends up placing it.  ``BitstreamStore`` persists those artifacts to a
directory so a restarted ``EventLoopEngine`` (or a fresh ``FleetOverlay``
member) boots from disk instead of paying cold XLA compiles.

Format (one file per artifact, named ``sha256(key).bits``):

    MAGIC (8 bytes)  b"RPROBITS"
    header length    uint32 little-endian
    header           JSON: {"format_version", "jaxlib", "key", "kind",
                            "payload_sha256", "payload_len"}
    payload          pickle of ``(serialized_executable, in_tree, out_tree)``
                     from ``jax.experimental.serialize_executable``

Every load re-validates magic, format version, jaxlib version, key and the
payload checksum; *any* mismatch — truncation, corruption, a jaxlib upgrade —
logs a warning and returns ``None`` so the caller falls back to a cold
compile.  A store can therefore never crash a boot and never serves a stale
or foreign artifact.

Writes are atomic (temp file in the same directory + ``os.replace``) so
readers — including fleet members sharing one store directory — never observe
a half-written entry.  In-process, a single ``threading.Lock`` serializes
writers; across processes the atomic replace is the only contract (last
writer wins, which is safe because entries are content-keyed: both writers
hold the same bytes for the same key).

Alongside the artifacts the store keeps ``ledger.json``: the Fabric's
download-cost EWMA ledger and per-resident dispatch-latency histogram states,
so a warm boot re-seeds the placement planner's measurements instead of
starting blind (see ``Fabric.export_ledger`` / ``seed_ledger``).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
from dataclasses import dataclass, field

logger = logging.getLogger(__name__)

_MAGIC = b"RPROBITS"
FORMAT_VERSION = 1
_LEDGER_NAME = "ledger.json"


def _jaxlib_version() -> str:
    try:
        import jaxlib

        return getattr(jaxlib, "__version__", "unknown")
    except Exception:  # pragma: no cover - jaxlib always present in tree
        return "unknown"


@dataclass
class StoreStats:
    """Counters for one store instance (in-process; survives nothing)."""

    saves: int = 0
    loads: int = 0
    load_failures: int = 0
    invalidations: int = 0
    bytes_written: int = 0
    bytes_read: int = 0
    load_seconds: float = 0.0
    injected_write_faults: int = 0
    injected_read_faults: int = 0

    def as_dict(self) -> dict:
        return {
            "saves": self.saves,
            "loads": self.loads,
            "load_failures": self.load_failures,
            "invalidations": self.invalidations,
            "bytes_written": self.bytes_written,
            "bytes_read": self.bytes_read,
            "load_seconds": round(self.load_seconds, 6),
            "injected_write_faults": self.injected_write_faults,
            "injected_read_faults": self.injected_read_faults,
        }


@dataclass
class _Entry:
    key: str
    kind: str
    path: str
    payload_len: int


class BitstreamStore:
    """Directory-backed artifact store for compiled overlay kernels.

    Thread-safe; one instance may be shared by every member of a
    ``FleetOverlay`` (a single in-process lock serializes writers, and
    atomic replace keeps concurrent *processes* from corrupting entries).
    """

    __locklint_shared__ = {
        "_index": "BitstreamStore._lock",
    }

    def __init__(self, path: str, *, faults=None) -> None:
        self.path = os.path.abspath(str(path))
        os.makedirs(self.path, exist_ok=True)
        self.stats = StoreStats()
        # optional FaultPlan (DESIGN.md §12): "store_write" garbles a blob
        # before it lands on disk (an interrupted/corrupting write that the
        # next load must reject), "store_read" flips bytes before
        # validation (media corruption the checksum chain must catch)
        self.faults = faults
        self._lock = threading.Lock()
        # key -> _Entry for entries this instance has seen (written or
        # scanned); the filesystem stays the source of truth for loads.
        self._index: dict[str, _Entry] = {}
        self._scan()

    # -- naming ----------------------------------------------------------

    @staticmethod
    def _file_for(key: str) -> str:
        return hashlib.sha256(key.encode("utf-8")).hexdigest() + ".bits"

    def _path_for(self, key: str) -> str:
        return os.path.join(self.path, self._file_for(key))

    def _scan(self) -> None:
        """Index existing entries (header-only read; payloads stay lazy).
        Directory I/O runs outside the lock — only the index update is
        serialized."""
        try:
            names = os.listdir(self.path)
        except OSError:
            return
        found: list[_Entry] = []
        for name in names:
            if not name.endswith(".bits"):
                continue
            full = os.path.join(self.path, name)
            header = self._read_header(full)
            if header is None:
                continue
            found.append(_Entry(
                key=header["key"],
                kind=header.get("kind", "kernel"),
                path=full,
                payload_len=int(header.get("payload_len", 0)),
            ))
        with self._lock:
            for ent in found:
                self._index[ent.key] = ent

    @staticmethod
    def _read_header(path: str) -> dict | None:
        try:
            with open(path, "rb") as f:
                magic = f.read(len(_MAGIC))
                if magic != _MAGIC:
                    return None
                raw_len = f.read(4)
                if len(raw_len) != 4:
                    return None
                hdr_len = int.from_bytes(raw_len, "little")
                if hdr_len <= 0 or hdr_len > 1 << 20:
                    return None
                raw = f.read(hdr_len)
                if len(raw) != hdr_len:
                    return None
                header = json.loads(raw.decode("utf-8"))
                if not isinstance(header, dict) or "key" not in header:
                    return None
                return header
        except (OSError, ValueError, UnicodeDecodeError):
            return None

    # -- queries ---------------------------------------------------------

    def __contains__(self, key: str) -> bool:
        with self._lock:
            if key in self._index:
                return True
        return os.path.exists(self._path_for(key))

    def keys(self) -> list[str]:
        with self._lock:
            return list(self._index)

    def entry_kind(self, key: str) -> str | None:
        with self._lock:
            ent = self._index.get(key)
            return ent.kind if ent is not None else None

    # -- save / load -----------------------------------------------------

    def save(self, key: str, payload_blob: bytes, *, kind: str = "kernel") -> bool:
        """Atomically write one serialized artifact.

        ``payload_blob`` is the pickled ``(payload, in_tree, out_tree)``
        triple — serialization itself happens on the caller's (low-lane
        worker) thread so no jax work runs under the store lock.
        """
        header = {
            "format_version": FORMAT_VERSION,
            "jaxlib": _jaxlib_version(),
            "key": key,
            "kind": kind,
            "payload_sha256": hashlib.sha256(payload_blob).hexdigest(),
            "payload_len": len(payload_blob),
        }
        raw_header = json.dumps(header, sort_keys=True).encode("utf-8")
        blob = (
            _MAGIC
            + len(raw_header).to_bytes(4, "little")
            + raw_header
            + payload_blob
        )
        if self.faults is not None and self.faults.fires("store_write", key):
            # injected write corruption: the entry lands truncated mid-
            # payload, exactly like a torn write the atomic replace cannot
            # guard against (e.g. power loss after the replace).  The next
            # load's validation chain rejects it and cold-compiles.
            blob = blob[: max(len(_MAGIC), len(blob) // 2)]
            self.stats.injected_write_faults += 1
        final = self._path_for(key)
        tmp = final + f".tmp.{os.getpid()}.{threading.get_ident()}"
        with self._lock:
            try:
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, final)
            except OSError as exc:
                logger.warning("bitstream store: save failed for %r: %s", key, exc)
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                return False
            self._index[key] = _Entry(
                key=key, kind=kind, path=final, payload_len=len(payload_blob)
            )
            self.stats.saves += 1
            self.stats.bytes_written += len(blob)
        return True

    def load_blob(self, key: str) -> bytes | None:
        """Read + validate one entry; returns the pickled payload triple.

        Any failure — missing file, bad magic, version or jaxlib mismatch,
        truncated payload, checksum mismatch — warns and returns ``None``;
        the caller cold-compiles.  A failed entry is dropped from the index
        so repeated misses don't re-read a known-bad file.
        """
        path = self._path_for(key)
        with self._lock:
            reason = None
            try:
                with open(path, "rb") as f:
                    data = f.read()
            except OSError:
                return None  # plain miss: not an error
            if data and self.faults is not None \
                    and self.faults.fires("store_read", key):
                # injected read corruption: flip a byte mid-blob before
                # validation — the magic/header/checksum chain must catch
                # it and degrade to a cold compile, never crash
                mid = len(data) // 2
                data = data[:mid] + bytes([data[mid] ^ 0xFF]) + data[mid + 1:]
                self.stats.injected_read_faults += 1
            self.stats.bytes_read += len(data)
            header = None
            if data[: len(_MAGIC)] != _MAGIC:
                reason = "bad magic"
            else:
                off = len(_MAGIC)
                if len(data) < off + 4:
                    reason = "truncated header length"
                else:
                    hdr_len = int.from_bytes(data[off : off + 4], "little")
                    off += 4
                    if hdr_len <= 0 or len(data) < off + hdr_len:
                        reason = "truncated header"
                    else:
                        try:
                            header = json.loads(data[off : off + hdr_len])
                        except (ValueError, UnicodeDecodeError):
                            reason = "unparseable header"
                        off += hdr_len
            if reason is None and header is not None:
                payload = data[off:]
                if header.get("format_version") != FORMAT_VERSION:
                    reason = f"format version {header.get('format_version')!r}"
                elif header.get("jaxlib") != _jaxlib_version():
                    reason = (
                        f"jaxlib {header.get('jaxlib')!r} != {_jaxlib_version()!r}"
                    )
                elif header.get("key") != key:
                    reason = "key mismatch"
                elif len(payload) != header.get("payload_len"):
                    reason = "truncated payload"
                elif (
                    hashlib.sha256(payload).hexdigest()
                    != header.get("payload_sha256")
                ):
                    reason = "payload checksum mismatch"
                else:
                    self.stats.loads += 1
                    return payload
            self.stats.load_failures += 1
            self._index.pop(key, None)
            logger.warning(
                "bitstream store: entry for %r unusable (%s); cold compiling",
                key,
                reason,
            )
            return None

    def note_unusable(self, key: str) -> None:
        """Caller-side deserialization failed: count the failure and drop
        the entry — a payload that passes the checksum but cannot rebuild
        an executable is permanently bad for this runtime (e.g. pickled
        against an incompatible XLA build the header didn't capture)."""
        with self._lock:
            self.stats.load_failures += 1
            self._index.pop(key, None)
            try:
                os.unlink(self._path_for(key))
            except OSError:
                pass

    # -- invalidation ----------------------------------------------------

    def delete(self, key: str) -> bool:
        with self._lock:
            self._index.pop(key, None)
            try:
                os.unlink(self._path_for(key))
            except OSError:
                return False
            self.stats.invalidations += 1
            return True

    def delete_many(self, keys) -> int:
        dropped = 0
        for key in list(keys):
            if self.delete(key):
                dropped += 1
        return dropped

    def delete_prefix(self, prefix: str) -> int:
        """Drop every indexed entry whose key starts with ``prefix`` —
        e.g. ``f"{kernel_key}|spec|"`` sweeps all route-constant variants
        of a dropped kernel."""
        return self.delete_many([k for k in self.keys()
                                 if k.startswith(prefix)])

    # -- measurement ledger ----------------------------------------------

    def save_ledger(self, ledger: dict, *, merge: bool = True) -> bool:
        """Persist the fabric measurement ledger (download-cost EWMA +
        dispatch-latency histogram states).

        With ``merge`` (the default) existing on-disk entries for *other*
        residents are kept — fleet members sharing one directory each
        contribute their own rows without clobbering the others'.
        """
        path = os.path.join(self.path, _LEDGER_NAME)
        with self._lock:
            merged = ledger
            if merge:
                existing = self._read_ledger_unlocked(path)
                if existing:
                    merged = dict(existing)
                    for section, rows in ledger.items():
                        if isinstance(rows, dict):
                            base = dict(merged.get(section) or {})
                            base.update(rows)
                            merged[section] = base
                        else:
                            merged[section] = rows
            tmp = path + f".tmp.{os.getpid()}.{threading.get_ident()}"
            try:
                with open(tmp, "w", encoding="utf-8") as f:
                    json.dump(merged, f, sort_keys=True)
                os.replace(tmp, path)
            except (OSError, TypeError, ValueError) as exc:
                logger.warning("bitstream store: ledger save failed: %s", exc)
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                return False
        return True

    def load_ledger(self) -> dict | None:
        path = os.path.join(self.path, _LEDGER_NAME)
        with self._lock:
            return self._read_ledger_unlocked(path)

    @staticmethod
    def _read_ledger_unlocked(path: str) -> dict | None:
        try:
            with open(path, encoding="utf-8") as f:
                data = json.load(f)
        except OSError:
            return None
        except (ValueError, UnicodeDecodeError) as exc:
            logger.warning("bitstream store: ledger unreadable (%s); ignoring", exc)
            return None
        if not isinstance(data, dict):
            logger.warning("bitstream store: ledger malformed; ignoring")
            return None
        return data

    # -- artifact (de)serialization helpers ------------------------------

    @staticmethod
    def pack_executable(compiled) -> bytes:
        """Serialize a ``jax.stages.Compiled`` into a durable payload blob."""
        from jax.experimental import serialize_executable as _se

        payload, in_tree, out_tree = _se.serialize(compiled)
        return pickle.dumps((payload, in_tree, out_tree), protocol=4)

    @staticmethod
    def unpack_executable(blob: bytes):
        """Rebuild a loaded executable; raises on any malformed payload
        (callers catch and fall back to cold compile)."""
        from jax.experimental import serialize_executable as _se

        payload, in_tree, out_tree = pickle.loads(blob)
        return _se.deserialize_and_load(payload, in_tree, out_tree)

    def describe(self) -> dict:
        with self._lock:
            kinds: dict[str, int] = {}
            total = 0
            for ent in self._index.values():
                kinds[ent.kind] = kinds.get(ent.kind, 0) + 1
                total += ent.payload_len
            return {
                "path": self.path,
                "entries": len(self._index),
                "kinds": kinds,
                "payload_bytes": total,
                "stats": self.stats.as_dict(),
            }


__all__ = ["BitstreamStore", "StoreStats", "FORMAT_VERSION"]
