"""On-disk persistence for the kernel-tier autotune dispatch tables.

``ops.sweep_decode_tiles`` times candidate (bk, bn) tiles per
(op, m, k, n[, r]) GEMV signature, and ``ops.sweep_paged_tiles`` times
pages-per-step per ``(paged_attn, T, Hq, Hkv, head_dim, block_size,
max_blocks)`` paged-attention signature — but only in-process, so every
server restart re-pays the sweep.  This module mirrors those tables to a
per-backend JSON file:

    $REPRO_TILE_CACHE_DIR/decode_tiles_{backend}.v{LAYOUT_VERSION}.json
    (default: ~/.cache/repro/)

``ops`` loads the file lazily on the first tile lookup and appends every
newly swept winner, so autotuning survives process restarts.  Tile
winners are backend-specific (a TPU sweep means nothing on CPU interpret
mode), hence the per-backend file.  Set ``REPRO_TILE_CACHE=0`` to disable
both load and store (hermetic CI runs).

File format: ``{"op|int|int|...": [int, ...], ...}`` — flat, mergeable,
and stable under concurrent writers (atomic replace; last writer wins on
a per-key basis after merging with the on-disk content).  Values are
kernel-family-shaped: ``[bk, bn]`` for the GEMV ops, ``[pages]`` for
paged attention — both keys and values are variable-arity int tuples, so
new kernel families extend the same file without a format bump.  A change
to what a tile means (the packed weight layout, or which tiles the kernels
accept) bumps ``LAYOUT_VERSION``, so winners swept under the old rules are
never loaded.
"""

from __future__ import annotations

import json
import logging
import os
import pathlib
import tempfile

_KEY_SEP = "|"
# 2: planar 64-row bit groups; K tiles hold whole groups (multiples of 256
# or the whole K), N tiles are multiples of 128 or the whole N
LAYOUT_VERSION = 2

_log = logging.getLogger(__name__)

# process-wide autotune-cache stats, answering "did this run pay sweep
# cost or reuse the cache": dispatch-table lookups (hits/misses), sweeps
# actually run, and milliseconds spent sweeping.  They live here (with the
# cache) rather than on an engine; the serving metrics registry pulls them
# in at snapshot time via a collector (``scheduler._tile_cache_stats``).
_STATS = {"hits": 0, "misses": 0, "sweeps": 0, "sweep_ms": 0.0}


def record_hit() -> None:
    _STATS["hits"] += 1


def record_miss() -> None:
    _STATS["misses"] += 1


def record_sweep_ms(ms: float) -> None:
    _STATS["sweeps"] += 1
    _STATS["sweep_ms"] += float(ms)


def stats() -> dict:
    """Copy of the process-wide autotune-cache stats."""
    return dict(_STATS)


def reset_stats() -> None:
    for k in _STATS:
        _STATS[k] = type(_STATS[k])()

# cache paths whose corruption has already been reported — warn once per
# path per process, not once per load
_CORRUPT_WARNED: set[str] = set()


def _quarantine_corrupt(path: pathlib.Path, err: Exception) -> None:
    """A cache file that does not parse is renamed to ``*.corrupt`` (so
    the next sweep starts a fresh file instead of silently re-hitting the
    same corruption forever) and reported once.  Best-effort: quarantine
    must never break inference either."""
    try:
        path.replace(path.with_suffix(path.suffix + ".corrupt"))
    except OSError:
        pass
    key = str(path)
    if key not in _CORRUPT_WARNED:
        _CORRUPT_WARNED.add(key)
        _log.warning(
            "tile cache %s is corrupt (%s); quarantined to %s.corrupt and "
            "starting fresh", path, err, path,
        )


def enabled() -> bool:
    return os.environ.get("REPRO_TILE_CACHE", "1") != "0"


def cache_path(backend: str) -> pathlib.Path:
    root = os.environ.get("REPRO_TILE_CACHE_DIR")
    base = pathlib.Path(root) if root else pathlib.Path.home() / ".cache" / "repro"
    return base / f"decode_tiles_{backend}.v{LAYOUT_VERSION}.json"


def _encode_key(key: tuple) -> str:
    return _KEY_SEP.join(str(p) for p in key)


def _decode_key(s: str) -> tuple:
    parts = s.split(_KEY_SEP)
    return (parts[0],) + tuple(int(p) for p in parts[1:])


def _valid_entry(key: tuple, val: tuple) -> bool:
    """Family-shaped EXACT arity check: paged_attn winners are (pages,),
    the GEMV families are (bk, bn).  A wrong-arity value — short or long —
    must be dropped at load time: dispatch tuple-unpacks these, and a
    broken cache file must never break inference."""
    return len(val) == (1 if key[0] == "paged_attn" else 2)


def load(backend: str) -> dict[tuple, tuple[int, ...]]:
    """Persisted winners for ``backend`` ({} on any miss/corruption,
    per-entry validation drops malformed keys/values —
    a broken cache file must never break inference).  A file that fails
    to parse at all is quarantined to ``*.corrupt`` (with one warning per
    path) so the corruption is visible and the next store starts clean."""
    if not enabled():
        return {}
    path = cache_path(backend)
    try:
        text = path.read_text()
    except OSError:
        return {}
    try:
        raw = json.loads(text)
        if not isinstance(raw, dict):
            raise ValueError(f"expected a JSON object, got {type(raw).__name__}")
    except ValueError as e:  # json.JSONDecodeError is a ValueError
        _quarantine_corrupt(path, e)
        return {}
    out = {}
    for k, v in raw.items():
        try:
            key = _decode_key(k)
            val = tuple(int(x) for x in v)
        except (ValueError, TypeError, IndexError):
            continue  # one bad entry must not poison the rest
        if key and _valid_entry(key, val):
            out[key] = val
    return out


def store(backend: str, table: dict[tuple, tuple[int, ...]]) -> None:
    """Merge ``table`` into the on-disk cache (best-effort: serving never
    fails because a cache dir is read-only).  Atomic replace so concurrent
    sweeps can't interleave partial JSON."""
    if not enabled() or not table:
        return
    path = cache_path(backend)
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        merged = load(backend)
        merged.update(table)
        payload = json.dumps(
            {_encode_key(k): list(v) for k, v in sorted(merged.items())},
            indent=0,
        )
        # crash/concurrency safety: write a temp file IN THE SAME
        # DIRECTORY, fsync it, then atomically os.replace it into place —
        # a reader (or a crash at any point) sees either the old complete
        # file or the new complete file, never a partial write.
        fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".tmp")
        ok = False
        try:
            with os.fdopen(fd, "w") as f:
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            ok = True
        finally:
            # remove the temp file on any failure without catching the
            # in-flight exception: KeyboardInterrupt/SystemExit (and real
            # write errors) propagate, and a failed unlink can never mask
            # the original error
            if not ok:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
    except OSError:
        pass
