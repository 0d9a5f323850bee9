"""A pinned scoring program, loaded where its executable is on the disk.

``ServingEngine``'s warm-ups pin one compiled program a (bucket, path,
history pad).  With JAX's persistent compilation cache warm, a pin by
``fn.lower(*args, **statics).compile()`` is nine tenths Python: the
process traces and lowers some sixty jaxprs a program to a module whose
only use is to be hashed into the key under which the finished
executable already lies in the cache (0.25 s a program that excludes,
against 0.03 s for the cache's own load: ISSUE 51's profile).
:func:`pin` keys the executable by what ``lower()`` would READ instead
of by what it would derive, and keeps it, serialized
(``jax.experimental.serialize_executable``), in a sub-directory of the
compile cache's directory: a start that finds its key's file loads it
and neither traces nor lowers.

The store exists only where the compile cache does
(``jax_compilation_cache_dir`` set — ``utils.platform.
enable_persistent_compile_cache`` does — and ``jax_enable_compilation_
cache`` on): no option of its own, and with no directory nothing is
read or written.  To empty it, remove ``<cache directory>/tpu_als_pins``
(removing the cache directory does both).  A file holds a pickle
(deflated), as JAX's serialization makes it: the directory is to be one only this
program's processes write, which the compile cache's already has to be.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import logging
import os
import pickle
import threading
import time
import zlib

import jax
import jaxlib
from jax.experimental.serialize_executable import (
    deserialize_and_load, serialize)

STORE = "tpu_als_pins"
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENV = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")

log = logging.getLogger(__name__)


def store_dir():
    """Where pins are kept, or None: JAX's persistent compilation cache
    has no directory, or is off."""
    path = jax.config.jax_compilation_cache_dir
    if not path or not jax.config.jax_enable_compilation_cache:
        return None
    return os.path.join(path, STORE)


@functools.cache
def source_digest():
    """A digest of the package's own source — every ``.py`` under
    ``tpu_als/``, by relative path and contents, read once a process
    (milliseconds): any edit to the program makes every stored pin a
    miss, so no executable of an older tree answers after an upgrade."""
    h = hashlib.sha256()
    for root, dirs, files in os.walk(_PACKAGE):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(root, name)
                h.update(os.path.relpath(path, _PACKAGE).encode())
                with open(path, "rb") as f:
                    h.update(hashlib.sha256(f.read()).digest())
    return h.hexdigest()


def built(fn, builder, *params):
    """``fn``, named for :func:`key` by what built it: a program that a
    builder makes anew from its parameters (the mesh's, closed over a
    ``Mesh`` and widths) has a qualified name that says none of them."""
    fn.pin_name = (f"{builder.__module__}.{builder.__qualname__}",
                   tuple(_describe(p) for p in params))
    return fn


def _describe(x):
    """A builder's parameter or a static argument, as the key holds it."""
    if isinstance(x, jax.sharding.Mesh):
        return ("mesh", x.axis_names, x.devices.shape,
                x.device_ids.ravel().tolist())
    if callable(x):
        return f"{x.__module__}.{x.__qualname__}"
    return repr(x)


def _sharding(s):
    if s is None:
        return None
    mesh = getattr(s, "mesh", None)
    return (repr(s), mesh.device_ids.ravel().tolist() if mesh is not None
            else sorted(d.id for d in s.device_set))


def _leaf(x):
    aval = jax.typeof(x)
    return (aval.shape, str(aval.dtype), aval.weak_type,
            _sharding(getattr(x, "sharding", None)),
            getattr(x, "committed", None))


def key(fn, args, statics):
    """The name of the file that holds ``fn.lower(*args, **statics)
    .compile()``: a digest of everything ``lower()`` reads and nothing
    it would have to trace to learn — the function's name
    (:func:`built`'s where a builder made it), the arguments' tree
    structure and each leaf's shape, dtype, weak type, sharding and
    commitment, the static arguments, the versions of ``jax`` and
    ``jaxlib``, the backend's platform and version, the devices' kinds,
    every ``jax.config`` value (a flag that changes no lowering costs
    one compile when it flips), ``XLA_FLAGS`` / ``LIBTPU_INIT_ARGS`` and
    :func:`source_digest`."""
    leaves, tree = jax.tree_util.tree_flatten(args)
    backend = jax.devices()[0].client
    parts = (
        getattr(fn, "pin_name", None) or f"{fn.__module__}.{fn.__qualname__}",
        str(tree), [_leaf(x) for x in leaves],
        sorted((k, _describe(v)) for k, v in statics.items()),
        jax.__version__, jaxlib.__version__,
        backend.platform, backend.platform_version,
        [d.device_kind for d in jax.devices()], jax.process_count(),
        sorted((k, repr(v)) for k, v in jax.config.values.items()),
        [os.environ.get(name) for name in _ENV],
        source_digest())
    return hashlib.sha256(repr(parts).encode()).hexdigest()


def _devices(args):
    """The devices a program given ``args`` runs on, in the order its
    executable names them: the widest sharding's among the arguments (a
    mesh's in the mesh's order), the default device where none is
    placed."""
    shardings = [x.sharding for x in jax.tree_util.tree_leaves(args)
                 if hasattr(x, "sharding")]
    if not shardings:
        return jax.devices()[:1]
    s = max(shardings, key=lambda s: len(s.device_set))
    mesh = getattr(s, "mesh", None)
    return (list(mesh.devices.flat) if mesh is not None
            else sorted(s.device_set, key=lambda d: d.id))


def dumps(compiled):
    """A ``stages.Compiled`` as the bytes of its file: the runtime's
    serialized executable and the call's two tree structures, pickled
    and deflated (a v5e scoring program's 1.5–8 MB shrink four- to
    fivefold at level 1 for 5–10 ms of a load: PERF.md section 6, PR
    51).  Raises where the runtime serializes no such executable."""
    return zlib.compress(pickle.dumps(serialize(compiled)), 1)


def loads(blob, args):
    """The ``stages.Compiled`` that :func:`dumps` wrote, loaded onto the
    devices that a call with ``args`` runs on."""
    payload, in_tree, out_tree = pickle.loads(zlib.decompress(blob))
    return deserialize_and_load(payload, in_tree, out_tree,
                                execution_devices=_devices(args))


def pin(fn, args, statics):
    """``(compiled, source, bytes, split)`` of the ``stages.Compiled``
    that ``fn.lower(*args, **statics).compile()`` gives.  ``source``:
    ``loaded`` — the executable lay in the store under :func:`key` and
    was deserialized and loaded, nothing traced or lowered; ``compiled``
    — it did not (or there is no store): lowered and compiled, and
    written there; ``unreadable`` — its file did not load (truncated,
    another runtime's): compiled, and the file written over.  ``bytes``:
    the file's, read or written (0 without a store).  ``split``: the
    seconds by step — ``key_s`` and ``load_s``, or ``key_s``,
    ``lower_s``, ``compile_s`` and ``write_s`` (no ``key_s`` / ``write_s``
    without a store)."""
    split, last = {}, time.perf_counter()

    def lap(step):
        nonlocal last
        now = time.perf_counter()
        split[step], last = now - last, now

    root = store_dir()
    source, path = "compiled", None
    if root is not None:
        path = os.path.join(root, key(fn, args, statics))
        lap("key_s")
        try:
            with open(path, "rb") as f:
                blob = f.read()
            loaded = loads(blob, args)
            lap("load_s")
            return loaded, "loaded", len(blob), split
        except FileNotFoundError:
            pass
        except Exception:   # whatever a damaged file raises: a miss
            log.warning("pin %s did not load: compiling", path,
                        exc_info=True)
            source = "unreadable"
        last = time.perf_counter()      # a load that failed is no step
    lowered = fn.lower(*args, **statics)
    lap("lower_s")
    compiled = lowered.compile()
    lap("compile_s")
    if path is None:
        return compiled, source, 0, split
    nbytes = _write(path, compiled)
    lap("write_s")
    return compiled, source, nbytes, split


def _write(path, compiled):
    """``compiled`` serialized into ``path``, by a temporary file and a
    rename (two processes may share the directory); the bytes written, 0
    where the runtime serializes no such executable or the directory
    takes no file — the next start compiles again, as this one did."""
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        blob = dumps(compiled)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except (ValueError, NotImplementedError, OSError,
            jax.errors.JaxRuntimeError):
        log.warning("pin %s was not written", path, exc_info=True)
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        return 0
    return len(blob)
