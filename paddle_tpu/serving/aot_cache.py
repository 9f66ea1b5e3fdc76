"""AOT program cache — compiled engine programs as deployment
artifacts.

The Julia-to-TPU model (PAPERS.md, arXiv:1810.09868) treats the
whole-program XLA compilation as THE deployment artifact; this module
applies it to the serving engine's closed program set.  `LLMEngine`
compiles a small, countable family of executables (one prefill per
bucket + one decode + two sampler widths); every one of them is pure
data once compiled, so a cache directory keyed by an engine fingerprint
turns replica scale-out from "recompile the bucket ladder" into "mmap a
few files":

- **Fingerprint.**  :func:`engine_fingerprint` hashes everything a
  compiled program's correctness depends on — model config, engine
  geometry (slots/pages/buckets/dtype), parameter tree (names, shapes,
  dtypes — never values), mesh spec, the decode attention path (XLA
  composition, or the Pallas kernel and its revision) and the sampler's
  revision (the programs' CODE is otherwise not hashed), jax/jaxlib
  versions, and the
  platform and kind of each device the programs run on
  (:func:`program_devices`).  Any component changing produces a
  DIFFERENT fingerprint directory, so invalidation is structural: stale
  entries are never loaded, only orphaned (and reapable via
  :meth:`AOTProgramCache.evict_stale`).  Device *ids* are not part of
  it: replicas of one engine on different chips of one kind share a
  family, and :meth:`AOTProgramCache.load` places the executable on the
  loading engine's devices.
- **Entries.**  One file per program
  (``<cache_dir>/<fingerprint>/<program>.jaxprog``), written atomically
  (tmp + rename, the resilience checkpoint discipline) and containing a
  versioned pickle of ``jax.experimental.serialize_executable``'s
  ``(payload, in_tree, out_tree)`` triple plus the ids of the devices
  it was compiled on.
- **Degradation.**  A backend whose executables refuse serialization, a
  torn/corrupt entry, or a deserialize failure all degrade to a normal
  compile (recorded as a ``serving.aot_cache_miss`` span) — the cache
  can make a boot faster, never wronger.

The observability contract: a cache HIT loads an executable without
touching the recompile log at all — a warm replica boot registers ZERO
compile events — while misses flow through the engine's usual
``note_aot_compile`` choke point.  ``tests/test_serving_router.py``
asserts both directions.
"""
from __future__ import annotations

import hashlib
import io
import os
import pickle
import tempfile

import jax
from jax.experimental import serialize_executable as se

from paddle_tpu.observability import span
from paddle_tpu.serving import sampler

__all__ = ["AOTProgramCache", "engine_fingerprint", "program_devices"]

# bump when the on-disk entry layout changes; folded into every
# fingerprint so old trees are orphaned wholesale, never half-read
FORMAT_VERSION = 2


def _mesh_desc(mesh):
    if mesh is None:
        return None
    return tuple((str(a), int(s)) for a, s in mesh.shape.items())


def program_devices(params, mesh=None):
    """The devices an engine's programs are compiled for and run on, in
    a stable order: its mesh's devices, else the one device its
    parameters live on."""
    if mesh is not None:
        return list(mesh.devices.flat)
    return sorted(next(iter(params.values())).devices(),
                  key=lambda d: d.id)


def engine_fingerprint(model_config, engine_config, params, mesh=None, *,
                       attention, experts=None):
    """Hex digest naming the compiled-program family of one engine.

    `params` contributes structure (sorted name/shape/dtype) and
    placement, never values — weights can be hot-swapped under a
    fingerprint because XLA compiled against their avals.  `attention`
    names what the decode program was built from
    (``LLMEngine.attention_path``: what the engine's page pool states —
    serving/kv_pool.py: ``"xla"`` or the Pallas kernel with its
    revision — and what its kind of generation adds, serving/
    generation.py); with the sampler's revision it is the part of the
    CODE the digest covers, so two trees that differ in either never
    share an executable.  `experts` (a model with expert layers alone:
    ``distributed.moe.experts_path()``, the grouped product's kernel at
    its revision or ``ragged_dot``) enters the digest only when given, so
    the fingerprint of a model without experts is what it was.
    """
    import jaxlib

    ec = engine_config
    material = {
        "format": FORMAT_VERSION,
        "model_config": sorted(
            (k, repr(v)) for k, v in vars(model_config).items()
            if not k.startswith("_")),
        "params": [(k, tuple(int(d) for d in v.shape), str(v.dtype))
                   for k, v in sorted(params.items())],
        "engine": (ec.max_num_seqs, ec.page_size, ec.max_model_len,
                   ec.num_pages, tuple(ec.prefill_buckets),
                   str(ec.dtype.__name__ if hasattr(ec.dtype, "__name__")
                       else ec.dtype),
                   # an int8-pool program must never load for an f32
                   # engine (or vice versa) — the pool pytree differs
                   getattr(ec, "kv_cache_dtype", None),
                   # a guarded decode program has an extra operand and
                   # an extra output — structurally different family
                   bool(getattr(ec, "guard", False))),
        "mesh": _mesh_desc(mesh),
        "attention": str(attention),
        "sampler": sampler.SAMPLER_REVISION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "devices": [(d.platform, d.device_kind)
                    for d in program_devices(params, mesh)],
    }
    if experts is not None:
        material["experts"] = str(experts)
    return hashlib.sha256(repr(material).encode()).hexdigest()[:24]


class _Unpickler(se._JaxPjrtUnpickler):
    """jax's executable unpickler, loading onto the devices it is given.

    jax's own resolves the device references pickled inside the payload
    (shardings) by id and loads the executable with the device
    assignment it was compiled with.  A replica on another chip than
    the one that compiled the entry needs both rebound: `stored_ids`
    maps position for position onto the execution devices, and (`moved`)
    the executable is loaded under compile options that carry the new
    assignment (the override jax's compilation cache also loads with).
    """

    def __init__(self, file, devices, stored_ids, moved):
        super().__init__(file, devices[0].client, devices)
        self.devices_by_id = dict(zip(stored_ids, devices))
        self.compile_options = None
        if moved:
            from jax._src import compiler
            self.compile_options = compiler.get_compile_options(
                num_replicas=1, num_partitions=1,
                device_assignment=[[devices[0].id]], backend=self.backend)

    def persistent_load(self, pid):
        if pid[0] == "exec":
            return self.backend.deserialize_executable(
                pid[1], executable_devices=self.execution_devices,
                compile_options=self.compile_options)
        return super().persistent_load(pid)


def _load_executable(payload, in_tree, out_tree, stored_ids, devices):
    """``serialize_executable.deserialize_and_load`` onto `devices`
    (jax's entry point loads for every device of the backend unless
    told otherwise, so a one-device program reloaded on an N-device
    host expects N shards).  Only a one-device program may move to a
    device other than the one it was compiled on."""
    ids = [d.id for d in devices]
    moved = ids != list(stored_ids)
    if len(ids) != len(stored_ids) or (moved and len(ids) > 1):
        raise ValueError(
            f"entry compiled for devices {list(stored_ids)}, engine "
            f"runs on {ids}")
    unloaded, args_info_flat, no_kwargs = _Unpickler(
        io.BytesIO(payload), devices, stored_ids, moved).load()
    return jax.stages.Compiled(
        unloaded.load(), [], in_tree.unflatten(args_info_flat), out_tree,
        no_kwargs=no_kwargs)


class AOTProgramCache:
    """Persisted AOT engine programs under one cache directory.

    Safe to share between replicas (and between processes on one host):
    stores are atomic renames, loads never read a half-written entry,
    and a concurrent double-store of the same key is benign (last
    rename wins, both files identical by construction).
    """

    def __init__(self, cache_dir):
        self.cache_dir = str(cache_dir)
        os.makedirs(self.cache_dir, exist_ok=True)
        # telemetry counters (reporting only; exact counts come from the
        # engine's registry instruments)
        self.hit_count = 0
        self.miss_count = 0
        self.store_count = 0
        self.error_count = 0
        # flipped off after the first "backend refuses serialization" so
        # a TPU runtime without executable serialization pays the failed
        # attempt exactly once
        self._serialize_supported = True

    # ------------------------------------------------------------ paths
    def _entry_path(self, fingerprint, program):
        safe = "".join(c if (c.isalnum() or c in "._-") else "_"
                       for c in str(program))
        return os.path.join(self.cache_dir, fingerprint,
                            f"{safe}.jaxprog")

    def entries(self, fingerprint):
        """Program names currently persisted under `fingerprint`."""
        d = os.path.join(self.cache_dir, fingerprint)
        try:
            return sorted(f[:-len(".jaxprog")] for f in os.listdir(d)
                          if f.endswith(".jaxprog"))
        except OSError:
            return []

    # ------------------------------------------------------------- load
    def load(self, fingerprint, program, devices):
        """Deserialize one program onto `devices` (the loading engine's
        :func:`program_devices`); returns a callable
        ``jax.stages.Compiled`` or None (miss / corrupt / unsupported).
        A corrupt entry is unlinked so the follow-up compile's store
        replaces it."""
        path = self._entry_path(fingerprint, program)
        try:
            with open(path, "rb") as fh:
                version, payload, in_tree, out_tree, stored_ids = \
                    pickle.load(fh)
            if version != FORMAT_VERSION:
                raise ValueError(f"format {version} != {FORMAT_VERSION}")
            compiled = _load_executable(payload, in_tree, out_tree,
                                        stored_ids, devices)
        except FileNotFoundError:
            self.miss_count += 1
            return None
        except Exception as e:  # corrupt / incompatible entry
            self.error_count += 1
            with span("serving.aot_cache_miss", program=str(program),
                      why=type(e).__name__):
                pass
            try:
                os.unlink(path)
            except OSError:
                pass
            return None
        self.hit_count += 1
        return compiled

    # ------------------------------------------------------------ store
    def store(self, fingerprint, program, compiled, devices):
        """Serialize `compiled` (built for `devices`) under
        (fingerprint, program); returns True on success.  Never raises —
        an unserializable backend or a full disk degrades to "no
        cache", not a serving failure."""
        if not self._serialize_supported:
            return False
        try:
            payload, in_tree, out_tree = se.serialize(compiled)
        except Exception as e:
            # ValueError("Compilation does not support serialization")
            # on backends without executable serialization
            self._serialize_supported = False
            self.error_count += 1
            with span("serving.aot_cache_disabled", why=type(e).__name__):
                pass
            return False
        entry = self._entry_path(fingerprint, program)
        d = os.path.dirname(entry)
        try:
            os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as fh:
                    pickle.dump(
                        (FORMAT_VERSION, payload, in_tree, out_tree,
                         [d.id for d in devices]), fh)
                os.replace(tmp, entry)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception:
            self.error_count += 1
            return False
        self.store_count += 1
        return True

    # ------------------------------------------------------- maintenance
    def evict_stale(self, keep_fingerprint):
        """Remove every fingerprint directory EXCEPT `keep_fingerprint`
        (deploy hygiene after a model/config/backend change).  Returns
        the evicted fingerprints."""
        evicted = []
        try:
            names = os.listdir(self.cache_dir)
        except OSError:
            return evicted
        for name in names:
            d = os.path.join(self.cache_dir, name)
            if name == keep_fingerprint or not os.path.isdir(d):
                continue
            import shutil
            shutil.rmtree(d, ignore_errors=True)
            evicted.append(name)
        return evicted

    def stats(self):
        return {
            "dir": self.cache_dir,
            "hits": self.hit_count,
            "misses": self.miss_count,
            "stores": self.store_count,
            "errors": self.error_count,
            "serialize_supported": self._serialize_supported,
        }
