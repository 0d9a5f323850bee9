"""Shared lazy g++ build for the native IO libraries.

One implementation of the build-if-stale pattern (fastcsv, fastbucket,
streamcsv): compile to a private temp file and ``os.rename`` into place,
so two processes racing to build (e.g. both pod workers of
``examples/04`` starting on a clean checkout) can never dlopen a
partially written .so — rename is atomic within a directory, and the
loser's rename simply replaces the winner's identical artifact.

Stale means "not built from exactly this source with these flags": the
library carries a sidecar ``<lib>.srckey`` holding a hash of both.  File
times say nothing once a tree has been copied or checked out, and the
libraries are git-ignored, so a copy of the disk can hold one that is
older than its source.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import tempfile


def _source_key(src, flags):
    h = hashlib.sha256(" ".join(flags).encode())
    with open(src, "rb") as f:
        h.update(f.read())
    return h.hexdigest()


def _read(path):
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def build_native(src, lib, extra_flags=()):
    """Build ``src`` -> ``lib`` with g++ unless ``lib`` was built from
    exactly this source.  Raises ``FileNotFoundError`` without a g++ and
    ``subprocess.CalledProcessError`` when the compile fails."""
    flags = ("-O3", "-shared", "-fPIC", *extra_flags)
    key = _source_key(src, flags)
    stamp = lib + ".srckey"
    if os.path.exists(lib) and _read(stamp) == key:
        return
    fd, tmp = tempfile.mkstemp(
        suffix=".so", prefix=os.path.basename(lib) + ".",
        dir=os.path.dirname(lib))
    os.close(fd)
    try:
        subprocess.run(["g++", *flags, src, "-o", tmp],
                       check=True, capture_output=True)
        os.rename(tmp, lib)
        with open(tmp, "w") as f:
            f.write(key)
        os.rename(tmp, stamp)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
