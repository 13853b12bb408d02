"""Shared pytest plumbing for the suite.

The full suite compiles a few hundred distinct XLA executables in one
process (every module builds its own reduced models and jits).  Left
unbounded, that accumulated native state can segfault jaxlib's CPU
compiler deep into the run — deterministically, on whichever test
crosses the threshold.  Dropping the jit/executable caches at module
boundaries keeps peak in-process XLA state bounded by the heaviest
single module; cross-module cache reuse is near zero anyway because
each module uses its own reduced configs.
"""

import gc
import os

# The SPMD data-plane tests (tests/test_spmd_engine.py) need a real
# multi-device mesh; on CPU runners that is emulated by asking XLA for
# 8 host-platform devices BEFORE jax initializes its backend (the flag
# is read once, at first device use). Single-device tests are
# unaffected: uncommitted arrays still land on device 0 and nothing
# shards unless a mesh is built explicitly.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import pytest


@pytest.fixture(autouse=True, scope="module")
def _bound_xla_state_per_module():
    yield
    jax.clear_caches()
    gc.collect()


class _Profile:
    """Profiles a block with the JAX profiler into a directory of its
    own and reads back the program's host spans."""

    def __init__(self, path):
        self.path = path

    def trace(self):
        return jax.profiler.trace(str(self.path))

    def spans(self, prefix="preble."):
        """(name, start_ns, end_ns) of every host event whose name
        starts with ``prefix``, in start order."""
        from jax.profiler import ProfileData
        out = []
        for f in self.path.rglob("*.xplane.pb"):
            for plane in ProfileData.from_file(str(f)).planes:
                if not plane.name.startswith("/host:"):
                    continue
                for line in plane.lines:
                    out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(prefix)]
        return sorted(out, key=lambda s: s[1])


@pytest.fixture
def profile(tmp_path):
    return _Profile(tmp_path / "profile")
