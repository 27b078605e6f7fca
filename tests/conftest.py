"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference strategy of running "multi-node" tests as multiple
local processes (SURVEY §4): SPMD sharding tests use
--xla_force_host_platform_device_count=8, and multi-process controller
tests spawn real subprocesses on localhost.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compile cache: the suite's wall clock is dominated by
# XLA-CPU compiles of the model-train-step tests (Inception train step
# alone ~200 s cold, ~24 s warm); repeat runs on one box hit the disk
# cache and skip them.  Every compile is kept, however short: the toy
# programs of the serving configurations compile in well under a second
# each but by the thousand (the Solar file's tests: 332 s of junit time
# cold, 160 s with its programs cached).  The cache lies in the checkout
# (tests/.jax_cache, ignored by git), so two checkouts never share one.
# Set through the environment (not jax.config) so every spawned worker
# subprocess — multiprocess batteries, estimators, multihost tests —
# inherits it.  Opt out with HOROVOD_TEST_COMPILE_CACHE=0 (e.g. when
# bisecting a compiler issue).
if os.environ.get("HOROVOD_TEST_COMPILE_CACHE", "1") != "0":
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                          "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
                          "-1")

# Flight-recorder failure dumps (HOROVOD_FLIGHT, on by default) resolve
# relative to the cwd: point them at /tmp so a fault-injection test can
# never litter the repo working tree.  Tests that assert on dumps set
# their own explicit paths (and inherit this default in workers).
os.environ.setdefault("HOROVOD_FLIGHT_FILE",
                      "/tmp/horovod_tpu_test_flight.json")

try:
    import jax
    jax.config.update("jax_platforms", "cpu")
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        # The env vars are read at jax import; set the config too (from
        # the env values, which setdefault left user-overridable) in case
        # a plugin imported jax before this file ran.
        jax.config.update("jax_compilation_cache_dir",
                          os.environ["JAX_COMPILATION_CACHE_DIR"])
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ.get(
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")))
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes",
            int(os.environ.get(
                "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")))
except ImportError:
    pass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
