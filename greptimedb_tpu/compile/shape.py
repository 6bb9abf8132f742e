"""Shape-class fingerprints: the canonical identity of a compiled kernel.

A *shape class* is everything that selects one compiled XLA program: the
plan's operator structure (already flattened into the executor cache
keys via ``SelectPlan.fingerprint()`` / ``WindowParams``), the static
geometry (padded rows, bucket counts, window widths, dictionary
cardinalities), and the resident-layout kind (bucket-major, dynamic-
slice, row, promql-sorted).  The runtime cache keys carry all of it —
this module turns those keys into a *restart-stable canonical string*
and a short content hash, so the persistent artifact store and the
usage journal can refer to a class from a different process.

The canonicalization is deliberately conservative: any key component it
cannot normalize losslessly (a closure, an unregistered object) makes
the class anonymous (``None``) — anonymous classes still compile and
serve normally, they just never persist or journal.
"""

from __future__ import annotations

import dataclasses
import hashlib

_PRIMS = (str, bytes)


def _norm(v) -> str | None:
    """Recursive, restart-stable text form of one key component."""
    if v is None:
        return "~"
    if isinstance(v, bool):
        return "b1" if v else "b0"
    # np integer/float scalars repr as "np.int64(5)" under numpy>=2 —
    # normalize through the python value instead of repr
    if isinstance(v, int) or hasattr(v, "__index__"):
        try:
            return f"i{int(v)}"
        except TypeError:
            return None
    if isinstance(v, float):
        return f"f{float(v)!r}"
    if isinstance(v, _PRIMS):
        return f"s{v!r}"
    if isinstance(v, (tuple, list)):
        parts = [_norm(x) for x in v]
        if any(p is None for p in parts):
            return None
        return "(" + ",".join(parts) + ")"
    if isinstance(v, frozenset):
        parts = sorted(p for p in (_norm(x) for x in v))
        if any(p is None for p in parts):
            return None
        return "{" + ",".join(parts) + "}"
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        # WindowParams and friends: field order is the class definition,
        # stable across processes; a field added later says
        # ``omit_default`` and is left out at its default, so the classes
        # that were there keep their ids (and their stored artifacts)
        fields = [(f.name, _norm(getattr(v, f.name)))
                  for f in dataclasses.fields(v)
                  if not (f.metadata.get("omit_default")
                          and getattr(v, f.name) == f.default)]
        if any(p is None for _n, p in fields):
            return None
        inner = ",".join(f"{n}={p}" for n, p in fields)
        return f"dc:{type(v).__name__}({inner})"
    try:  # float-like scalars (np.float32 etc.)
        return f"f{float(v)!r}"
    except (TypeError, ValueError):
        return None


def canon_key(engine: str, key) -> str | None:
    """Canonical class string for a runtime kernel-cache key, or None
    when the key contains components with no stable text form."""
    body = _norm(key)
    if body is None:
        return None
    return f"{engine}|{body}"


def class_id(canon: str) -> str:
    """Short content address of a canonical class string (the artifact
    filename stem and journal key)."""
    return hashlib.sha256(canon.encode()).hexdigest()[:24]
