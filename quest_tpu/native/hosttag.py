"""Host/ISA fingerprint for native-library cache filenames.

Standalone and dependency-free ON PURPOSE: setup.py's build hook and
native/Makefile execute this file directly (no package import), so it must
not pull in quest_tpu/__init__ (which imports jax/numpy — unavailable in
an isolated pip build env).

Why the tag exists (advisor r4): the executor library is built with
-march=native; a package tree copied to a host with a different ISA
(container image, NFS) must not dlopen a stale AVX-512 binary and SIGILL.
Machine arch + a hash of the CPU feature flags keys the cache per host
class; :func:`build_tag` adds a digest of the source text and the build
command, so a library built from other source (or other flags) is never
loaded in place of the current one, whatever the files' mtimes say.
"""

import hashlib
import platform


def _host_tag() -> str:
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    digest = hashlib.sha1(
        (platform.machine() + ":" + flags).encode()).hexdigest()[:8]
    return f"{platform.machine()}-{digest}"


HOST_TAG = _host_tag()

# the compile command every on-demand native build shares; callers append
# their own flags
BASE_FLAGS = ("-O2", "-std=c++17", "-fPIC", "-Wall")


def build_tag(src_path: str, flags=()) -> str:
    """``HOST_TAG`` plus a digest of the source text, the compiler and the
    flags: the cache key of one built library."""
    import os
    h = hashlib.sha256()
    with open(src_path, "rb") as f:
        h.update(f.read())
    cxx = os.environ.get("CXX", "g++")
    h.update(repr((cxx, BASE_FLAGS, tuple(flags))).encode())
    return f"{HOST_TAG}.{h.hexdigest()[:12]}"

if __name__ == "__main__":
    print(HOST_TAG)
