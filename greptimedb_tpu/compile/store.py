"""Persistent AOT artifact store: CRC-enveloped serialized executables.

Disk layout under the store root::

    aot/<class_id>.<env_id>.gtc   one serialized executable per shape
                                  class and environment
    quarantine/                   corrupt artifacts, preserved for
                                  inspection (PR-9 discipline: corruption
                                  is quarantined loudly, never silently
                                  served)
    usage.json                    the shape-class usage journal
                                  (journal.py, same envelope)

Every file is wrapped in a ``GTC1 <crc32>`` envelope (the manifest's
GTM1 discipline, storage/manifest.py): the payload is CRC-verified on
every read, so a torn or bit-flipped artifact can NEVER deserialize into
a wrong executable — it quarantines and the caller recompiles.  The
artifact body additionally records (jaxlib version, jax version,
backend, device topology, machine tag): any mismatch means the artifact
was built for a different world and is evicted, not loaded — XLA:CPU
executables carry machine-feature-specific code (the bench's observed
'could lead to SIGILL' failure mode when round-3 carried AOT artifacts
across hosts).

Writes are atomic (unique tmp + fsync + ``os.replace`` + parent-dir
fsync) so concurrent processes sharing one cache directory can only ever
observe complete artifacts; duplicate concurrent saves of the same class
are idempotent last-writer-wins.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import threading
import zlib

from greptimedb_tpu.storage.object_store import _fsync_dir

_MAGIC = b"GTC1 "


def encode_envelope(body: bytes, magic: bytes = _MAGIC) -> bytes:
    return magic + b"%08x\n" % (zlib.crc32(body) & 0xFFFFFFFF) + body


def decode_envelope(data: bytes, magic: bytes = _MAGIC) -> bytes | None:
    """Envelope bytes → payload, or None on any corruption (short file,
    wrong magic, CRC mismatch)."""
    head = len(magic) + 9
    if len(data) < head or not data.startswith(magic):
        return None
    try:
        want = int(data[len(magic):len(magic) + 8], 16)
    except ValueError:
        return None
    body = data[head:]
    if (zlib.crc32(body) & 0xFFFFFFFF) != want:
        return None
    return body


def machine_tag() -> str:
    """Scope artifacts to this machine's CPU features: XLA:CPU AOT code
    compiled elsewhere may use instructions this host lacks (SIGILL)."""
    import platform

    basis = platform.machine() + platform.processor()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    basis += line
                    break
    except OSError:
        pass
    return hashlib.md5(basis.encode()).hexdigest()[:10]


def env_fingerprint() -> dict:
    """The compilation environment an artifact is only valid within."""
    import jax
    import jaxlib

    try:
        backend = jax.default_backend()
        ndev = jax.device_count()
    except RuntimeError:  # backend not initializable: caller handles
        backend, ndev = "none", 0
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": backend,
        "devices": ndev,
        "machine": machine_tag(),
    }


def env_id(env: dict) -> str:
    basis = "|".join(f"{k}={env[k]}" for k in sorted(env))
    return hashlib.sha256(basis.encode()).hexdigest()[:12]


def atomic_write(path: str, data: bytes) -> None:
    """Unique-tmp + fsync + replace + parent fsync: concurrent writers of
    the same path are each atomic; readers only ever see whole files."""
    d = os.path.dirname(path)
    tmp = os.path.join(
        d, f".tmp.{os.getpid()}.{threading.get_ident()}."
           f"{os.path.basename(path)}")
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_dir(d)


class ArtifactStore:
    """On-disk AOT executable store (see module docstring).

    Counter bookkeeping lives in service.py's registry metrics; the
    instance mirrors (loads/saves/corrupt/stale) exist so /status and
    tests read pressure without a registry scrape (memory.py
    discipline)."""

    def __init__(self, root: str, quota_bytes: int | None = None):
        self.root = root
        self.aot_dir = os.path.join(root, "aot")
        self.quarantine_dir = os.path.join(root, "quarantine")
        os.makedirs(self.aot_dir, exist_ok=True)
        os.makedirs(self.quarantine_dir, exist_ok=True)
        self.quota_bytes = quota_bytes
        self.env = env_fingerprint()
        self.env_id = env_id(self.env)
        self.loads = 0
        self.saves = 0
        self.corrupt = 0
        self.stale = 0

    # ------------------------------------------------------------------
    def _path(self, cid: str) -> str:
        return os.path.join(self.aot_dir, f"{cid}.{self.env_id}.gtc")

    def bytes(self) -> int:
        total = 0
        try:
            with os.scandir(self.aot_dir) as it:
                for e in it:
                    try:
                        total += e.stat().st_size
                    except OSError:
                        pass
        except OSError:
            pass
        return total

    # ------------------------------------------------------------------
    def load(self, cid: str, canon: str | None = None):
        """Deserialize the class's executable for THIS environment, or
        None.  Corrupt files quarantine; artifacts whose recorded
        environment drifted (a stale env_id collision, or a same-name
        file from an older jaxlib) are evicted."""
        path = self._path(cid)
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            # NOTE: a same-class artifact under another env_id is NOT
            # evicted here — a different live environment (other backend,
            # jaxlib mid-upgrade) may legitimately share this cache dir;
            # orphans from genuinely dead environments age out through
            # the quota's oldest-first reclaim instead
            return None
        body = decode_envelope(data)
        if body is None:
            self._quarantine(path)
            return None
        try:
            doc = pickle.loads(body)
            if doc.get("v") != 1 or doc.get("class_id") != cid:
                raise ValueError("artifact header mismatch")
            if doc.get("env") != self.env:
                # header is intact but the world changed (jaxlib upgrade,
                # different backend): evict, never load
                self.stale += 1
                try:
                    os.unlink(path)
                except OSError:
                    pass
                return None
            if canon is not None and doc.get("canon") not in (None, canon):
                raise ValueError("artifact canon mismatch")
            import jax
            from jax.experimental import serialize_executable as _se

            # load onto the devices the program was compiled for (one
            # device, or a mesh's): the default is EVERY device of the
            # backend, which breaks a one-device program's first call
            # wherever more than one device is visible
            by_id = {d.id: d for d in jax.devices()}
            fn = _se.deserialize_and_load(
                doc["payload"], doc["in_tree"], doc["out_tree"],
                execution_devices=[by_id[i] for i in doc["devices"]])
        except Exception:  # noqa: BLE001 — undeserializable ⇒ quarantine
            self._quarantine(path)
            return None
        self.loads += 1
        return fn

    def save(self, cid: str, canon: str | None, engine: str,
             compiled) -> bool:
        """Serialize + persist one compiled executable; False on any
        failure (serialization unsupported for this program, disk full —
        the caller keeps serving from the in-memory kernel)."""
        from jax.experimental import serialize_executable as _se

        try:
            payload, in_tree, out_tree = _se.serialize(compiled)
            body = pickle.dumps({
                "v": 1,
                "class_id": cid,
                "canon": canon,
                "engine": engine,
                "env": self.env,
                "devices": [d.id for d in
                            compiled.runtime_executable().local_devices()],
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
            })
            atomic_write(self._path(cid), encode_envelope(body))
        except Exception:  # noqa: BLE001 — persistence is best-effort
            return False
        self.saves += 1
        if self.quota_bytes is not None:
            over = self.bytes() - self.quota_bytes
            if over > 0:
                self.reclaim(over, keep=self._path(cid))
        return True

    # ------------------------------------------------------------------
    def reclaim(self, nbytes: int, keep: str | None = None) -> None:
        """Free at least ``nbytes`` by evicting oldest-modified artifacts
        (LRU by mtime — loads don't touch mtime, so this approximates
        oldest-written; good enough for a bounded disk cache)."""
        entries = []
        try:
            with os.scandir(self.aot_dir) as it:
                for e in it:
                    if e.path == keep:
                        continue
                    try:
                        st = e.stat()
                    except OSError:
                        continue
                    entries.append((st.st_mtime, st.st_size, e.path))
        except OSError:
            return
        freed = 0
        for _mt, size, path in sorted(entries):
            if freed >= nbytes:
                break
            try:
                os.unlink(path)
                freed += size
            except OSError:
                pass

    def _quarantine(self, path: str) -> None:
        self.corrupt += 1
        dst = os.path.join(
            self.quarantine_dir,
            f"{os.path.basename(path)}.{os.getpid()}.quarantine")
        try:
            os.replace(path, dst)
            _fsync_dir(self.quarantine_dir)
            _fsync_dir(self.aot_dir)
        except OSError:
            try:  # racing quarantiners: losing the rename is fine, the
                os.unlink(path)  # file must just leave the serving dir
            except OSError:
                pass
