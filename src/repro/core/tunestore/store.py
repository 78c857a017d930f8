"""The on-disk tuning store (DESIGN.md §13): schema-versioned, atomically
written JSON that round-trips everything a warm process needs to reach
tuned steady state without a single measurement launch — per-family
``BucketCostModel`` tables (every execution path: s3 buckets, s2 coalesce
widths, fused waves), derived bucket ladders, ``inner_chunk`` choices,
the per-family ``selected_strategy``/``strategy_costs`` verdicts, and the
observed queue histograms the flush policies key on.

Keying (staleness = a key mismatch, never a guess):

* the file is valid only for ONE ``(schema, code salt)`` pair — the salt
  hashes the tuning-relevant sources, so measurements taken by different
  code are ignored wholesale (they may describe programs that no longer
  exist);
* each entry is keyed ``backend|device_kind|d<count>|TaskSignature
  .describe()`` — the same identity the in-process memoes use
  (``_backend_key``), so a table timed on one device (or one device
  topology) can never warm-start another;
* the payload carries a content hash; a truncated or hand-edited file
  fails closed (a warning and a cold start, never a crash and never a
  silently wrong ladder).

Writes go through a same-directory temp file + ``os.replace`` so a
concurrent reader sees either the old store or the new one, never a
torn JSON.  Configuring a store also turns on JAX's persistent
compilation cache (``repro.core.compile_cache``, which owns its place),
so one ``tune_store=`` knob removes both re-measurement AND
re-compilation from process two.
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile
import warnings
from typing import Any, Dict, Optional, Tuple

SCHEMA_VERSION = 1
STORE_FILENAME = "tunestore.json"

# env var consulted when no explicit ``tune_store`` path is configured —
# the production-serving knob: point every process of a deployment at one
# shared directory (documented in README "Warm start")
STORE_ENV_VAR = "REPRO_TUNE_STORE"

_SALT_SOURCES = ("aggregation.py",)   # relative to repro/core
_code_salt_memo: Optional[str] = None


def code_salt() -> str:
    """Hash of the tuning-relevant sources (the aggregation runtime and
    this module): measured choices describe compiled programs, so a store
    written by a different code version is stale by definition."""
    global _code_salt_memo
    if _code_salt_memo is None:
        h = hashlib.blake2b(digest_size=8)
        here = os.path.dirname(os.path.abspath(__file__))
        core = os.path.dirname(here)
        for path in [os.path.join(core, s) for s in _SALT_SOURCES] + [
                os.path.abspath(__file__)]:
            try:
                with open(path, "rb") as f:
                    h.update(f.read())
            except OSError:
                h.update(path.encode())
        _code_salt_memo = h.hexdigest()
    return _code_salt_memo


def entry_key(backend_key: Tuple[str, ...], family: str) -> str:
    """``backend|device_kind|d<count>|TaskSignature.describe()`` — the
    identity a stored tuning entry is valid for (mirrors the in-process
    memo key).  The key joins every ``_backend_key()`` component, so the
    device topology (``d1`` vs ``d8``) partitions the store: a ladder
    tuned single-device never warm-starts a sharded multi-device run."""
    return "|".join((*backend_key, family))


def _content_hash(entries: Dict[str, Any]) -> str:
    blob = json.dumps(entries, sort_keys=True,
                      separators=(",", ":")).encode()
    return hashlib.blake2b(blob, digest_size=16).hexdigest()


class TuneStoreWarning(UserWarning):
    """A store file was unusable (corrupt, stale schema/salt, bad hash);
    the process falls back to cold-start measurement."""


class TuneStore:
    """One warm-start store rooted at a directory.

    ``load()`` is fail-closed: any structural problem (unparsable JSON,
    missing keys, schema/salt mismatch, content-hash mismatch) degrades
    to an empty entry table with a :class:`TuneStoreWarning` — a warm
    start is an optimization, never a correctness dependency.
    ``save()`` is atomic (temp file + rename) and keyed writes merge
    into whatever valid entries the file already holds, so concurrent
    processes tuning DIFFERENT families do not clobber each other's
    last-writer entries wholesale.
    """

    def __init__(self, root: str):
        self.root = os.path.abspath(str(root))
        self.path = os.path.join(self.root, STORE_FILENAME)
        self._entries: Dict[str, Dict[str, Any]] = {}
        self._loaded = False

    # -- construction ------------------------------------------------------
    @classmethod
    def open(cls, spec: Any) -> Optional["TuneStore"]:
        """Resolve a config knob into a store: an existing
        :class:`TuneStore` passes through, a path string opens one, and
        ``None`` consults the ``REPRO_TUNE_STORE`` env var (unset env →
        no store, the cold-start default)."""
        if spec is None:
            spec = os.environ.get(STORE_ENV_VAR) or None
            if spec is None:
                return None
        if isinstance(spec, TuneStore):
            return spec
        return cls(str(spec))

    # -- persistence -------------------------------------------------------
    def _ensure_loaded(self) -> None:
        if not self._loaded:
            self._entries = self._read_file()
            self._loaded = True

    def _read_file(self) -> Dict[str, Dict[str, Any]]:
        try:
            with open(self.path) as f:
                payload = json.load(f)
        except FileNotFoundError:
            return {}
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as err:
            warnings.warn(
                f"tune store {self.path} is unreadable ({err}) — "
                f"falling back to cold-start measurement",
                TuneStoreWarning, stacklevel=3)
            return {}
        if not isinstance(payload, dict):
            warnings.warn(
                f"tune store {self.path} has a non-object top level — "
                f"ignoring it", TuneStoreWarning, stacklevel=3)
            return {}
        if payload.get("schema") != SCHEMA_VERSION:
            warnings.warn(
                f"tune store {self.path} has schema "
                f"{payload.get('schema')!r} (this code reads "
                f"{SCHEMA_VERSION}) — ignoring it",
                TuneStoreWarning, stacklevel=3)
            return {}
        if payload.get("salt") != code_salt():
            warnings.warn(
                f"tune store {self.path} was written by a different code "
                f"version (salt {payload.get('salt')!r} != {code_salt()!r})"
                f" — its measurements describe programs that no longer "
                f"exist; ignoring it", TuneStoreWarning, stacklevel=3)
            return {}
        entries = payload.get("entries")
        if not isinstance(entries, dict) or not all(
                isinstance(v, dict) for v in entries.values()):
            warnings.warn(
                f"tune store {self.path} has a malformed entry table — "
                f"ignoring it", TuneStoreWarning, stacklevel=3)
            return {}
        if payload.get("hash") != _content_hash(entries):
            warnings.warn(
                f"tune store {self.path} fails its content hash "
                f"(truncated or hand-edited write) — ignoring it",
                TuneStoreWarning, stacklevel=3)
            return {}
        return entries

    def save(self) -> None:
        """Atomic write: merge this process's entries over whatever valid
        entries are on disk, then temp-file + ``os.replace``.  When the
        merged content is byte-equivalent to what a valid on-disk file
        already holds, the write is SKIPPED — per-cadence checkpoint saves
        (``StrategyRunner.run(checkpoint_every=...)``) call this every few
        steps, and rewriting an unchanged store would only add fsync
        traffic and a needless crash window."""
        os.makedirs(self.root, exist_ok=True)
        self._ensure_loaded()
        with warnings.catch_warnings():
            # a corrupt on-disk file must not block the REPAIRING write
            warnings.simplefilter("ignore", TuneStoreWarning)
            on_disk = self._read_file()
        merged = dict(on_disk)
        merged.update(self._entries)
        self._entries = merged
        if on_disk == merged and os.path.exists(self.path):
            return
        payload = {"schema": SCHEMA_VERSION, "salt": code_salt(),
                   "entries": merged, "hash": _content_hash(merged)}
        fd, tmp = tempfile.mkstemp(dir=self.root, prefix=".tunestore-",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    # -- entry access ------------------------------------------------------
    def get(self, backend_key: Tuple[str, ...],
            family: str) -> Optional[Dict[str, Any]]:
        """The stored entry for one ``(backend, device_kind)`` + family
        describe key, or None.  Entries under other backend keys are
        simply different keys — a CPU process never sees TPU tables."""
        self._ensure_loaded()
        return self._entries.get(entry_key(backend_key, family))

    def put(self, backend_key: Tuple[str, ...], family: str,
            entry: Dict[str, Any]) -> None:
        self._ensure_loaded()
        self._entries[entry_key(backend_key, family)] = entry

    def entries(self) -> Dict[str, Dict[str, Any]]:
        self._ensure_loaded()
        return dict(self._entries)

    def __len__(self) -> int:
        self._ensure_loaded()
        return len(self._entries)
