"""Build-on-demand for the native (C++) runtime components.

The reference ships its native core prebuilt via bazel; here each library
is compiled with g++ on first use and cached under build/. The artefact's
NAME carries a digest of what it was built from — source bytes, flags and,
for ``-march=native`` builds, the host CPU's feature flags — so a source
edit, a flag change or a build/ directory copied from another machine
rebuilds, while a checkout whose mtimes were reset by a copy does not.
"""
from __future__ import annotations

import hashlib
import os
import subprocess
import threading

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_BUILD_DIR = os.path.join(_REPO_ROOT, "build")
_LOCK = threading.Lock()

_LIBS = {
    "raystore": ["src/store/store.cc", "src/store/data_server.cc"],
    "rayrpc": ["src/rpc/rpc_core.cc"],
    "rayquant": ["src/quant/quant.cc"],
}

# Per-lib extra flag sets, tried in order until one compiles. The quant
# kernels are pure elementwise/reduction loops whose whole value is
# vectorization: -march=native roughly triples their throughput on AVX2
# hosts; the host CPU's flags are part of the artefact key, so that
# binary is never loaded on a different CPU. The plain -O3 fallback
# keeps exotic toolchains working (slower, still correct).
# -ffp-contract=off is a CORRECTNESS flag, not tuning: the fused
# add-both kernel must stay mul+mul+add so deq(a)+deq(b) is
# bit-commutative — an FMA contraction would round rank 0's and
# rank 1's sums differently and break the collective's
# rank-identical-results property (and drift from the numpy fallback).
_EXTRA_FLAGS = {
    "rayquant": (["-O3", "-march=native", "-ffp-contract=off"],
                 ["-O3", "-ffp-contract=off"]),
}
_BASE_FLAGS = ["-std=c++17", "-shared", "-fPIC"]
_LINK_FLAGS = ["-lpthread", "-lrt"]


def _host_cpu_flags() -> str:
    """The CPU feature set ``-march=native`` compiles for."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return os.uname().machine


def _digest(sources: list[str], flags: list[str]) -> str:
    h = hashlib.sha256()
    for src in sources:
        with open(src, "rb") as f:
            h.update(f.read())
        h.update(b"\0")
    h.update(" ".join(flags).encode())
    if "-march=native" in flags:
        h.update(_host_cpu_flags().encode())
    return h.hexdigest()[:16]


def ensure_lib(name: str) -> str:
    """Compile lib<name> unless an artefact keyed to the current sources
    and flags already exists; return its path."""
    sources = [os.path.join(_REPO_ROOT, s) for s in _LIBS[name]]
    with _LOCK:
        candidates = []
        for extra in _EXTRA_FLAGS.get(name, (["-O2"],)):
            flags = [*extra, *_BASE_FLAGS]
            candidates.append((flags, os.path.join(
                _BUILD_DIR, f"lib{name}-{_digest(sources, flags)}.so")))
        for _, out in candidates:
            if os.path.exists(out):
                return out
        os.makedirs(_BUILD_DIR, exist_ok=True)
        last_err = None
        for flags, out in candidates:
            tmp = out + f".tmp.{os.getpid()}"
            cmd = ["g++", *flags, "-o", tmp, *sources, *_LINK_FLAGS]
            try:
                subprocess.run(cmd, check=True, capture_output=True,
                               text=True)
            except subprocess.CalledProcessError as e:
                last_err = e
                continue
            os.replace(tmp, out)
            return out
        raise last_err
