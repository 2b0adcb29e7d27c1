"""Persistent AOT compile cache for the serving engine (ISSUE 16).

Replica boot pays an XLA trace+compile for every prefill bucket plus the
decode step — tens of seconds that every freshly scaled pod repeats even
though the executables are a pure function of (model config, mesh shape,
bucket set, engine shape knobs, jax/backend version). This module makes
the fleet compile once ever:

- :class:`AOTKey` canonicalizes that tuple into a content digest. Any
  field changing (a jax upgrade, a different bucket set, a resharded
  mesh) lands in a different cache line, so a stale executable can never
  be *found*, only missed.
- :class:`AOTCompileCache` is a two-layer store: a local directory of
  serialized executables (``jax.experimental.serialize_executable``)
  with a blake2b content gate in front of every deserialize, and an
  optional store-ring layer (PR 7 content-addressed put/get) so the
  first replica to compile publishes for the whole fleet.
- :func:`warm_engine` pre-compiles the engine's common-signature
  executables (prefill per bucket + the decode step/block) through the
  cache and hands the engine an executable table its dispatch sites
  consult before falling back to the traced jits.

Miss paths are typed and counted (``kt_aot_cache_total{result=...}``):
an absent entry, a key mismatch (``incompatible``), and a corrupted
payload all fall back to a fresh compile — never a wrong executable.
This module is the ONLY compile-path entry in ``serve/`` (lint #14 in
``scripts/check_resilience.py`` pins that).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

from ..exceptions import AOTCacheCorruptError, AOTCacheMissError

_DIGEST_LEN = 32          # hex chars of the key digest (128 bits)
_BIN_SUFFIX = ".bin"      # pickled (payload, in_tree, out_tree)
_META_SUFFIX = ".json"    # sidecar: content hash + provenance


def _canon(v: Any) -> Any:
    """Canonicalize a value for the key JSON: dataclasses to sorted
    dicts, tuples to lists, dtypes/callables/everything exotic to
    ``str`` — the digest must be stable across processes, so anything
    without a deterministic repr has no business in a key field."""
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return {k: _canon(getattr(v, k))
                for k in sorted(f.name for f in dataclasses.fields(v))}
    if isinstance(v, dict):
        return {str(k): _canon(v[k]) for k in sorted(v, key=str)}
    if isinstance(v, (list, tuple)):
        return [_canon(x) for x in v]
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


@dataclasses.dataclass(frozen=True)
class AOTKey:
    """Everything a serialized executable is a function of. Two engines
    with equal keys can exchange executables; anything else is a miss."""

    model: Any                      # model config (dataclass or dict)
    mesh_shape: Optional[tuple]     # ((axis, size), ...) or None (no mesh)
    buckets: tuple                  # engine._buckets (sorted, deduped)
    slots: int
    max_len: int
    quantize_kv: bool
    decode_block: int
    # top_k is an engine constructor knob independent of cfg, baked into
    # every executable as a lower-time static (engine.py dispatch sites
    # pass top_k=self.top_k) — it MUST participate in the digest or two
    # engines differing only in top_k would swap executables and sample
    # wrong
    top_k: Optional[int] = None
    # the axis order of the engine's slot grid, which every decode
    # executable takes and returns: an entry compiled for another grid
    # layout (before it was head-major: no tag) must MISS here, not load
    # and fail at call time on a shape mismatch
    grid_layout: str = ""
    jax_version: str = ""
    jaxlib_version: str = ""
    backend: str = ""

    @staticmethod
    def for_engine(engine) -> "AOTKey":
        import jax
        import jaxlib

        from .engine import _cache_ops

        mesh = getattr(engine, "_mesh", None)
        mesh_shape = (tuple(sorted(dict(mesh.shape).items()))
                      if mesh is not None else None)
        return AOTKey(
            model=_canon(engine.cfg),
            mesh_shape=mesh_shape,
            buckets=tuple(engine._buckets),
            slots=engine.slots,
            max_len=engine.max_len,
            quantize_kv=engine.quantize_kv,
            decode_block=engine.decode_block,
            top_k=engine.top_k,
            grid_layout=_cache_ops(engine.cfg).layout,
            jax_version=jax.__version__,
            jaxlib_version=getattr(jaxlib, "__version__", ""),
            backend=jax.default_backend(),
        )

    def describe(self) -> Dict[str, Any]:
        return _canon(self)

    def digest(self) -> str:
        blob = json.dumps(self.describe(), sort_keys=True,
                          separators=(",", ":")).encode()
        return hashlib.blake2b(blob, digest_size=_DIGEST_LEN // 2).hexdigest()


def default_cache_root() -> Path:
    """``KT_AOT_CACHE_DIR`` env → layered config ``aot_cache_dir`` →
    ``~/.cache/kubetorch_tpu/aot``."""
    env = os.environ.get("KT_AOT_CACHE_DIR", "").strip()
    if env:
        return Path(env)
    try:
        from ..config import config
        cfgd = str(config().get("aot_cache_dir", "") or "").strip()
        if cfgd:
            return Path(cfgd)
    except Exception:
        pass
    return Path.home() / ".cache" / "kubetorch_tpu" / "aot"


def _blake2b(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=16).hexdigest()


class AOTCompileCache:
    """Layered executable cache: local directory + optional store ring.

    Layout: ``<root>/<digest>/<name>.bin`` (pickled serialize() triple)
    beside ``<name>.json`` (blake2b of the bin, sizes, jax versions) and
    one ``key.json`` describing the digest's full key for operators.
    Writes commit through ``durable_replace`` so a crash mid-publish
    leaves no truncated payload under a final name; reads verify the
    sidecar hash BEFORE deserializing, so a corrupt entry becomes a
    typed :class:`AOTCacheCorruptError` (counted, then recompiled) and
    never reaches XLA's loader.
    """

    def __init__(self, root: Optional[os.PathLike] = None, *,
                 store: bool = False, store_url: Optional[str] = None):
        self.root = Path(root) if root is not None else default_cache_root()
        self.store = bool(store)
        self.store_url = store_url
        # local mirror of the kt_aot_cache_total counter: tests and
        # engine.aot_stats() read this without parsing telemetry text
        self.counts: Dict[str, int] = {}

    # -- accounting ---------------------------------------------------------

    def _count(self, result: str) -> None:
        self.counts[result] = self.counts.get(result, 0) + 1
        try:
            from .. import telemetry
            telemetry.cold_start_metrics()["aot"].inc(result=result)
        except Exception:
            pass

    # -- paths --------------------------------------------------------------

    def entry_dir(self, key: AOTKey) -> Path:
        return self.root / key.digest()

    def _store_key(self, key: AOTKey, name: str, content_hash: str) -> str:
        # the payload key is CONTENT-ADDRESSED: the blake2b of the bytes
        # is part of the name, so a fetched payload is verifiable against
        # its own key before anything deserializes it
        return f"aot/{key.digest()}/{name}/{content_hash}"

    def _store_ptr_key(self, key: AOTKey, name: str) -> str:
        return f"aot/{key.digest()}/{name}.ptr"

    # -- store ring layer ---------------------------------------------------
    #
    # Trust model: the executable payload rides pickle + XLA's loader, so
    # loading one is code execution. The content-addressed key pins the
    # payload to the hash its publisher named — a torn copy, a partial
    # overwrite, or a blob swapped under an existing key is rejected
    # before pickle ever sees it. What it cannot provide is provenance: a
    # writer who controls BOTH the pointer and the payload can still name
    # its own hash. Enabling ``store=True`` therefore asserts that every
    # principal with write access to the ``aot/`` prefix (and to the
    # local cache dir) is trusted to run code on this fleet — the same
    # trust the weight-distribution path already extends to the ring.

    def _store_fetch(self, key: AOTKey, name: str, bin_path: Path) -> bool:
        """Pull ``name`` from the store ring into the local layer. Any
        failure (store down, key absent, content-address mismatch) is a
        plain miss — the store is an accelerator, never a correctness
        dependency."""
        if not self.store:
            return False
        tmp = bin_path.with_name(f"{bin_path.name}.fetch.tmp")
        try:
            from ..data_store import commands as ds
            tmp.parent.mkdir(parents=True, exist_ok=True)
            try:
                ds.get(self._store_ptr_key(key, name), dest=str(tmp),
                       store_url=self.store_url)
                want = tmp.read_bytes().decode("ascii").strip()
            finally:
                tmp.unlink(missing_ok=True)
            if len(want) != 32 or not all(c in "0123456789abcdef"
                                          for c in want):
                self._count("store_corrupt")
                return False
            ds.get(self._store_key(key, name, want), dest=str(tmp),
                   store_url=self.store_url)
            data = tmp.read_bytes()
            tmp.unlink(missing_ok=True)
            if _blake2b(data) != want:
                # the payload does not match the hash its own key names:
                # never let it near pickle, never cache it locally
                self._count("store_corrupt")
                return False
            self._write_entry(key, name, data)
            self._count("store_hit")
            return True
        except Exception:
            tmp.unlink(missing_ok=True)
            return False

    def _store_publish(self, key: AOTKey, name: str, bin_path: Path) -> None:
        if not self.store:
            return
        try:
            from ..data_store import commands as ds
            content_hash = _blake2b(bin_path.read_bytes())
            # payload first, pointer last: a reader that wins the race
            # sees either a complete pair or a plain miss
            ds.put(self._store_key(key, name, content_hash), str(bin_path),
                   store_url=self.store_url)
            ptr = bin_path.with_name(f"{bin_path.name}.ptr.tmp")
            ptr.write_text(content_hash)
            try:
                ds.put(self._store_ptr_key(key, name), str(ptr),
                       store_url=self.store_url)
            finally:
                ptr.unlink(missing_ok=True)
            self._count("store_publish")
        except Exception:
            pass

    # -- local layer --------------------------------------------------------

    def _write_entry(self, key: AOTKey, name: str, data: bytes) -> None:
        from ..data_store.durability import durable_write_bytes
        import jax

        d = self.entry_dir(key)
        d.mkdir(parents=True, exist_ok=True)
        keyfile = d / "key.json"
        if not keyfile.exists():
            durable_write_bytes(keyfile, json.dumps(
                key.describe(), indent=2, sort_keys=True).encode())
        meta = {
            "blake2b": _blake2b(data),
            "nbytes": len(data),
            "jax": jax.__version__,
            "created": time.time(),
        }
        # bin first, meta last: a reader requires BOTH, so a crash
        # between the two commits reads as an absent entry, not a corrupt
        # one
        durable_write_bytes(d / f"{name}{_BIN_SUFFIX}", data)
        durable_write_bytes(d / f"{name}{_META_SUFFIX}",
                            json.dumps(meta).encode())

    def _other_digest_has(self, digest: str, name: str) -> bool:
        """A sibling cache line holding this executable name means the
        miss is a key MISMATCH (version/mesh/bucket drift), not a cold
        cache — operators want those distinguished."""
        try:
            for p in self.root.iterdir():
                if (p.is_dir() and p.name != digest
                        and (p / f"{name}{_BIN_SUFFIX}").exists()):
                    return True
        except OSError:
            pass
        return False

    def load(self, key: AOTKey, name: str):
        """Return the loaded executable for ``(key, name)`` or raise a
        typed miss. Never returns a wrong executable: the digest gates
        compatibility, the sidecar hash gates integrity."""
        d = self.entry_dir(key)
        bin_path = d / f"{name}{_BIN_SUFFIX}"
        meta_path = d / f"{name}{_META_SUFFIX}"
        if not (bin_path.exists() and meta_path.exists()):
            if not self._store_fetch(key, name, bin_path):
                reason = ("incompatible"
                          if self._other_digest_has(key.digest(), name)
                          else "absent")
                raise AOTCacheMissError(
                    f"AOT cache {reason} for {name!r}",
                    key=key.digest(), name=name, reason=reason)
        data = bin_path.read_bytes()
        try:
            meta = json.loads(meta_path.read_text())
            expected = meta["blake2b"]
        except Exception as e:
            raise AOTCacheCorruptError(
                f"AOT cache sidecar unreadable for {name!r}: {e}",
                key=key.digest(), name=name) from e
        actual = _blake2b(data)
        if actual != expected:
            raise AOTCacheCorruptError(
                f"AOT cache content hash mismatch for {name!r}",
                key=key.digest(), name=name,
                expected=expected, actual=actual)
        try:
            import jax
            from jax.experimental import serialize_executable
            payload, in_tree, out_tree, device_ids = pickle.loads(data)
            # load onto the devices it was compiled for: jax defaults
            # execution_devices to EVERY device of the backend, so on a
            # multi-device host a one-device executable would load fine
            # and then fail its first call on the shard count
            by_id = {d.id: d for d in jax.devices()}
            return serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except Exception as e:
            raise AOTCacheCorruptError(
                f"AOT cache deserialize failed for {name!r}: {e}",
                key=key.digest(), name=name, expected=expected,
                actual=actual) from e

    def put(self, key: AOTKey, name: str, compiled) -> None:
        """Serialize ``compiled`` under ``(key, name)`` and (when the
        store layer is on) publish it for the fleet."""
        from jax.experimental import serialize_executable

        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        device_ids = [d.id for d in
                      compiled.runtime_executable().local_devices()]
        data = pickle.dumps((payload, in_tree, out_tree, device_ids))
        self._write_entry(key, name, data)
        self._count("publish")
        self._store_publish(key, name,
                            self.entry_dir(key) / f"{name}{_BIN_SUFFIX}")

    def get_or_compile(self, key: AOTKey, name: str,
                       build: Callable[[], Any]) -> Tuple[Any, str]:
        """The engine-facing path: hit → loaded executable; any typed
        miss → ``build()`` a fresh one, publish it, return it. The second
        element is the result tag (``hit``/``miss``/``incompatible``/
        ``corrupt``) for callers that report boot anatomy."""
        try:
            exe = self.load(key, name)
            self._count("hit")
            return exe, "hit"
        except AOTCacheCorruptError:
            result = "corrupt"
        except AOTCacheMissError as e:
            result = e.reason if e.reason == "incompatible" else "miss"
        self._count(result)
        compiled = build()
        try:
            self.put(key, name, compiled)
        except Exception:
            # a failed publish (read-only dir, disk full) must never fail
            # the boot that just paid for the compile
            pass
        return compiled, result


# -- engine warm-up ----------------------------------------------------------

def warm_engine(engine, cache: AOTCompileCache,
                key: Optional[AOTKey] = None) -> Dict[tuple, Any]:
    """Pre-compile the engine's common-signature executables through the
    cache and return the dispatch table ``engine._aot_exec`` consults:

    - ``("prefill", bucket)`` for every prefill bucket — the plain
      admission path (no adapter / nucleus / penalty kwargs),
    - ``("decode", k)`` for the configured decode block — the common
      decode dispatch whose only extra kwarg is ``skeys``.

    Uncommon signatures (LoRA banks, top-p, penalties, logit bias) keep
    riding the traced jits; they are sticky per-engine and rare at boot.
    Arguments here MUST mirror the engine call sites exactly — a drifted
    aval would compile a valid-but-never-hit executable and the engine
    would silently re-trace (the equivalence test in
    ``tests/test_cold_start.py`` pins token-exact agreement).
    """
    import jax
    import jax.numpy as jnp

    from . import engine as _eng

    t0 = time.monotonic()
    if key is None:
        key = AOTKey.for_engine(engine)
    exes: Dict[tuple, Any] = {}
    rng = jax.random.PRNGKey(0)
    for b in engine._buckets:
        def build(b=b):
            tokens = jnp.zeros((1, b), jnp.int32)
            return _eng._prefill.lower(
                engine.params, tokens, jnp.int32(1), rng,
                jnp.zeros((1,), jnp.float32), engine.cfg,
                top_k=engine.top_k).compile()
        exes[("prefill", b)], _ = cache.get_or_compile(
            key, f"prefill_{b}", build)
    k = engine.decode_block
    pos = jnp.zeros((engine.slots,), jnp.int32)
    toks = jnp.zeros((engine.slots,), jnp.int32)
    temps = jnp.zeros((engine.slots,), jnp.float32)
    skeys = jnp.zeros((engine.slots, 2), jnp.uint32)

    def build_decode():
        if k > 1:
            return _eng._decode_block.lower(
                engine.params, engine._cache, pos, toks, rng, temps,
                engine.cfg, n_steps=k, top_k=engine.top_k,
                skeys=skeys).compile()
        return _eng._decode_step.lower(
            engine.params, engine._cache, pos, toks, rng, temps,
            engine.cfg, top_k=engine.top_k, skeys=skeys).compile()

    exes[("decode", k)], _ = cache.get_or_compile(
        key, f"decode_{k}", build_decode)
    try:
        from .. import telemetry
        telemetry.cold_start_metrics()["phase_seconds"].observe(
            time.monotonic() - t0, phase="compile_or_cache")
    except Exception:
        pass
    return exes
