"""On-disk persistence for tuned kernel launch configs (docs/TUNING.md).

One small JSON file per device kind, keyed by the chain signature string the
tuner builds (``tuner.chain_key``).  The file carries its schema version and
the device kind it was tuned on; a mismatch on either invalidates the whole
file (configs tuned for one device are meaningless on another, and schema
bumps must not resurrect stale entries).  Writes are atomic (tmp + rename)
so concurrent serving processes never observe a torn file.
"""
from __future__ import annotations

import contextlib
import json
import os
import tempfile
import threading
from typing import Dict, Optional

# Version 2: the fused chain became one dense-operator contraction, so the
# VMEM footprint of every tuned config changed.
CACHE_VERSION = 2


def default_cache_dir() -> str:
    """``$REPRO_AUTOTUNE_CACHE``, else ``.autotune_cache/`` at the checkout
    root — never a directory outside the checkout (repro/runtime.py)."""
    env = os.environ.get("REPRO_AUTOTUNE_CACHE", "")
    if env:
        return env
    from repro.runtime import CHECKOUT_ROOT
    return str(CHECKOUT_ROOT / ".autotune_cache")


def _slug(device_kind: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in device_kind.lower())


class TuningCache:
    """Load/store tuned configs for one device kind.

    ``get``/``put`` operate on plain dicts (the tuner owns the TunedConfig
    dataclass); the cache only enforces the version/device envelope.

    Thread-safe: the serve worker and a tenant-registration warmup can tune
    concurrently, so the lazy first load and every mutation serialize on one
    lock; ``load`` returns a snapshot copy rather than the live dict.
    """

    def __init__(self, device_kind: str, path: Optional[str] = None):
        self.device_kind = device_kind
        self.path = path or os.path.join(default_cache_dir(),
                                         f"{_slug(device_kind)}.json")
        self._lock = threading.Lock()
        self._entries: Optional[Dict[str, dict]] = None  # guarded-by: _lock

    # ------------------------------------------------------------------ load
    def load(self) -> Dict[str, dict]:
        with self._lock:
            return dict(self._load_locked())

    def _load_locked(self) -> Dict[str, dict]:  # requires-lock: _lock
        if self._entries is not None:
            return self._entries
        self._entries = {}
        # missing/corrupt file == empty cache
        with contextlib.suppress(OSError, ValueError):
            with open(self.path) as f:
                blob = json.load(f)
            if (blob.get("version") == CACHE_VERSION
                    and blob.get("device_kind") == self.device_kind
                    and isinstance(blob.get("entries"), dict)):
                self._entries = dict(blob["entries"])
        return self._entries

    def get(self, key: str) -> Optional[dict]:
        with self._lock:
            return self._load_locked().get(key)

    # ----------------------------------------------------------------- store
    def put(self, key: str, config: dict) -> None:
        with self._lock:
            entries = self._load_locked()
            entries[key] = config
            self._write(dict(entries))

    def _write(self, entries: Dict[str, dict]) -> None:
        blob = {"version": CACHE_VERSION, "device_kind": self.device_kind,
                "entries": entries}
        d = os.path.dirname(self.path)
        # read-only FS: keep the in-memory view
        with contextlib.suppress(OSError):
            os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                json.dump(blob, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)

    def clear(self) -> None:
        with self._lock:
            self._entries = {}
        with contextlib.suppress(OSError):
            os.unlink(self.path)
