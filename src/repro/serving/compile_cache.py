"""Persistent AOT compile cache — zero-warmup serving.

Every new ``(schedule_key, batch-shape bucket)`` pair used to pay a
first-request jit compile: a latency cliff on every engine start, deploy,
and new tenant target — exactly the regime the paper's multi-design-point
serving story cares about (the kernel is microseconds; the compile shell
around it is seconds).  This module closes that cliff the way AOT serving
frameworks do (export/compile ahead of time, load artifacts at serve time):

  * :class:`CompileCache` serializes compiled XLA executables
    (``jax.jit(...).lower(...).compile()`` +
    ``jax.experimental.serialize_executable``) to a cache directory, one
    file per content hash of ``{jax/jaxlib version, platform, device,
    cfg, schedule_key, fp, argument shapes}``.  An executable is bound to
    the device it was compiled for, so each device has its own entries and
    loads them back onto that device.  Any load / deserialize failure
    degrades gracefully to a fresh compile (warn, never crash) — a
    corrupted or stale entry costs one cold compile, not an outage.
  * :class:`CachedExecutor` wraps one jit'd function and dispatches each
    distinct argument-shape signature to its own compiled executable:
    warm signatures load from disk with ZERO jit traces; cold signatures
    lower/compile once (the wrapped function's trace-time side effects —
    the engines' trace counters — run exactly then) and are stored for
    the next process.
  * Writes are concurrency-safe for N worker replicas sharing one cache
    directory: serialize to a unique temp file, then atomic
    ``os.replace`` — readers only ever see complete entries.

Per-logical-key cold/warm/hot counters feed the engines' ``serve_report``
(the ``compile`` column: hit rate, the share of calls served from memory,
first-request compile seconds).

:func:`enable_jax_compilation_cache` is the one place entry points turn on
JAX's own persistent compilation cache.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time
import uuid
import warnings
from dataclasses import dataclass
from functools import lru_cache
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

from repro.serving.spans import tracer

#: bump to invalidate every existing cache entry (serialization layout)
_FORMAT_VERSION = 1

_SUFFIX = ".jaxcache"

_PACKAGE = Path(__file__).resolve().parents[1]

#: JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
#: unset: one fixed path per checkout, ignored by git
REPO_JAX_CACHE = _PACKAGE.parents[1] / ".jax_cache"


def enable_jax_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    already uses it and nothing else is set; otherwise the cache lives at
    :data:`REPO_JAX_CACHE`, a fixed path so that later runs find it."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_JAX_CACHE))
    return jax.config.jax_compilation_cache_dir


@lru_cache(maxsize=1)
def _source_digest() -> str:
    """Hash of this package's Python sources: an executable built from
    other kernel code must not load, even from a directory that outlives a
    checkout."""
    h = hashlib.sha256()
    for f in sorted(_PACKAGE.rglob("*.py")):
        h.update(f.relative_to(_PACKAGE).as_posix().encode())
        h.update(f.read_bytes())
    return h.hexdigest()[:16]


def _env_meta(device) -> Dict[str, str]:
    """The toolchain axes that invalidate a serialized executable: an
    artifact compiled by one jaxlib for one platform, or bound to one
    device, must never be fed to another."""
    import jax
    import jaxlib

    return {
        "format": str(_FORMAT_VERSION),
        "source": _source_digest(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": jax.default_backend(),
        "n_devices": str(len(jax.devices())),
        "device_kind": device.device_kind,
        "device_id": str(device.id),
    }


def fingerprint(meta: Dict[str, Any]) -> str:
    """Stable content hash of an entry's metadata (sorted-key JSON)."""
    blob = json.dumps(meta, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()[:24]


def _slug(name: str, limit: int = 48) -> str:
    safe = "".join(c if c.isalnum() or c in "-_." else "_" for c in name)
    return safe[:limit] or "entry"


@dataclass
class KeyCompileStats:
    """Per-logical-key (schedule key) compile accounting."""

    cold: int = 0                       # fresh lower+compile (one jit trace)
    warm: int = 0                       # served from a deserialized artifact
    hot: int = 0                        # calls whose executable was in memory
    errors: int = 0                     # load/store failures (fell back)
    quarantined: int = 0                # known-corrupt entries skipped
    first_compile_s: Optional[float] = None

    def summary(self) -> Dict[str, float]:
        total = self.cold + self.warm
        served = total + self.hot
        return {
            "cold": float(self.cold),
            "warm": float(self.warm),
            "hot": float(self.hot),
            "errors": float(self.errors),
            "quarantined": float(self.quarantined),
            "hit_rate": (self.warm / total) if total else 0.0,
            "hot_share": (self.hot / served) if served else 0.0,
            "first_compile_s": self.first_compile_s,
        }


class CompileCache:
    """Directory of serialized executables shared by serving engines.

    ``cache_dir=None`` disables persistence but keeps the accounting: every
    signature then costs exactly one in-process cold compile (the pre-PR
    behavior), and ``serve_report`` still shows honest cold counts.
    ``device`` is the device the cached executables run on (default: the
    first device).
    """

    def __init__(self, cache_dir: Optional[os.PathLike | str] = None,
                 device=None):
        import jax

        self.dir = Path(cache_dir) if cache_dir is not None else None
        self.enabled = self.dir is not None
        if self.enabled:
            self.dir.mkdir(parents=True, exist_ok=True)
        self.device = device if device is not None else jax.devices()[0]
        self._env = _env_meta(self.device)
        self._stats: Dict[str, KeyCompileStats] = {}
        # negative cache: entry paths that already failed to deserialize.
        # Without it a known-corrupt entry was re-read, re-unpickled and
        # re-warned on EVERY request (the warn-and-fall-back path has no
        # memory) — the fallback stayed correct but each request paid the
        # doomed deserialization attempt.  First failure warns and
        # quarantines; later lookups skip the file silently until a
        # successful store replaces it.
        self._quarantine: set = set()

    # -- accounting ----------------------------------------------------------

    def stats(self, key: str) -> KeyCompileStats:
        return self._stats.setdefault(key, KeyCompileStats())

    def report_row(self, key: str) -> Dict[str, float]:
        return self.stats(key).summary()

    def record_cold(self, key: str, compile_s: float) -> None:
        st = self.stats(key)
        st.cold += 1
        if st.first_compile_s is None:
            st.first_compile_s = compile_s

    def record_warm(self, key: str) -> None:
        self.stats(key).warm += 1

    @property
    def cold_compiles(self) -> int:
        return sum(s.cold for s in self._stats.values())

    @property
    def warm_hits(self) -> int:
        return sum(s.warm for s in self._stats.values())

    # -- entry identity ------------------------------------------------------

    def entry_meta(self, meta: Dict[str, Any]) -> Dict[str, Any]:
        return {**self._env, **meta}

    def entry_path(self, name_hint: str, meta: Dict[str, Any]) -> Path:
        assert self.dir is not None
        full = self.entry_meta(meta)
        return self.dir / f"{_slug(name_hint)}-{fingerprint(full)}{_SUFFIX}"

    # -- load / store --------------------------------------------------------

    def load(self, name_hint: str, meta: Dict[str, Any],
             key: str) -> Optional[Callable]:
        """Deserialize the entry for ``meta``; None on miss OR any failure
        (corrupted file, version skew inside the payload, pickle error) —
        the caller falls back to a cold compile."""
        if not self.enabled:
            return None
        path = self.entry_path(name_hint, meta)
        if str(path) in self._quarantine:
            # known corrupt: don't re-attempt (and re-warn) every request
            self.stats(key).quarantined += 1
            return None
        if not path.exists():
            return None
        try:
            from jax.experimental import serialize_executable
            with open(path, "rb") as f:
                doc = pickle.load(f)
            want = self.entry_meta(meta)
            if doc.get("meta") != want:
                raise ValueError(
                    f"entry metadata mismatch (hash collision or stale "
                    f"format): {path.name}")
            return serialize_executable.deserialize_and_load(
                doc["payload"], doc["in_tree"], doc["out_tree"],
                execution_devices=[self.device])
        except Exception as e:  # corrupted/stale entry: warn ONCE, fall back
            self.stats(key).errors += 1
            self._quarantine.add(str(path))
            warnings.warn(
                f"compile cache entry {path.name} unusable "
                f"({type(e).__name__}: {e}); falling back to jit compile "
                f"(entry quarantined — not re-read until overwritten)",
                RuntimeWarning, stacklevel=2)
            return None

    def store(self, name_hint: str, meta: Dict[str, Any], compiled: Any,
              key: str) -> bool:
        """Serialize ``compiled`` under its content hash.

        Write-temp-then-rename: safe under concurrent writers (N replicas
        sharing one directory race benignly — last complete write wins,
        readers never observe a partial file)."""
        if not self.enabled:
            return False
        path = self.entry_path(name_hint, meta)
        tmp = path.with_name(
            f"{path.name}.tmp.{os.getpid()}.{uuid.uuid4().hex[:8]}")
        try:
            from jax.experimental import serialize_executable
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            with open(tmp, "wb") as f:
                pickle.dump({"meta": self.entry_meta(meta),
                             "payload": payload,
                             "in_tree": in_tree,
                             "out_tree": out_tree}, f)
            os.replace(tmp, path)
            # a fresh, complete entry now lives at this path: lift any
            # quarantine from a corrupt predecessor
            self._quarantine.discard(str(path))
            return True
        except Exception as e:  # unserializable executable, full disk, ...
            self.stats(key).errors += 1
            warnings.warn(
                f"compile cache store failed for {path.name} "
                f"({type(e).__name__}: {e}); serving uncached",
                RuntimeWarning, stacklevel=2)
            try:
                if tmp.exists():
                    tmp.unlink()
            except OSError:
                pass
            return False


def _arg_signature(args: Tuple[Any, ...]) -> Tuple:
    """Hashable signature of one executable's arguments: the pytree
    structure and each array leaf's ``(shape, dtype)``, as the objects
    themselves.  Real arrays and ``jax.ShapeDtypeStruct`` avals of one
    shape and dtype give equal signatures.  Nothing is formatted as a
    string: this runs on every call."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return treedef, tuple((l.shape, l.dtype) for l in leaves)


def _signature_meta(sig: Tuple) -> Dict[str, Any]:
    """A signature's part of a persistent entry's metadata, as strings:
    the key of the entry's file name and content hash."""
    treedef, leaves = sig
    return {"treedef": str(treedef),
            "leaves": tuple((tuple(shape), str(dtype))
                            for shape, dtype in leaves)}


class CachedExecutor:
    """One jit'd function, dispatched per argument-shape signature to AOT
    executables that persist across processes.

    Call it exactly like the jit'd function (positional args).  The first
    call with a new signature either loads the serialized executable (warm
    — zero jit traces) or lowers/compiles once (cold — the wrapped
    function's trace-time side effects run) and stores the artifact; later
    calls find it in memory (hot).
    :meth:`warm` does the same from ``jax.ShapeDtypeStruct`` avals without
    executing — the engines' pre-warm path.
    """

    def __init__(self, jitted: Callable, cache: CompileCache, key: str,
                 meta: Dict[str, Any], name_hint: Optional[str] = None):
        self._jitted = jitted
        self._cache = cache
        self._stats = cache.stats(key)
        self.key = key
        self._meta = dict(meta)
        self._name = name_hint if name_hint is not None else key
        self._compiled: Dict[Tuple, Callable] = {}

    def _acquire(self, sig: Tuple, args: Tuple[Any, ...]) -> Callable:
        with tracer()("compile.acquire"):
            meta = {**self._meta, **_signature_meta(sig)}
            fn = self._cache.load(self._name, meta, self.key)
            if fn is not None:
                self._cache.record_warm(self.key)
            else:
                t0 = time.perf_counter()
                fn = self._jitted.lower(*args).compile()
                self._cache.record_cold(self.key, time.perf_counter() - t0)
                self._cache.store(self._name, meta, fn, self.key)
            self._compiled[sig] = fn
            return fn

    def __call__(self, *args):
        sig = _arg_signature(args)
        fn = self._compiled.get(sig)
        if fn is None:
            fn = self._acquire(sig, args)
        else:
            self._stats.hot += 1
        return fn(*args)

    def warm(self, *args) -> Dict[str, Any]:
        """Ensure the executable for this signature exists WITHOUT running
        it; args may mix real arrays and ``jax.ShapeDtypeStruct`` avals.
        Returns ``{"status": "hot"|"warm"|"cold", "compile_s": float}``."""
        sig = _arg_signature(args)
        if sig in self._compiled:
            return {"status": "hot", "compile_s": 0.0}
        cold_before = self._cache.stats(self.key).cold
        t0 = time.perf_counter()
        self._acquire(sig, args)
        dt = time.perf_counter() - t0
        cold = self._cache.stats(self.key).cold > cold_before
        return {"status": "cold" if cold else "warm",
                "compile_s": dt if cold else 0.0}

    def compiled_signatures(self) -> int:
        return len(self._compiled)

    def executables(self) -> list:
        """The compiled executables acquired so far, one per signature."""
        return list(self._compiled.values())
