"""common/prefetch.py: hvd.init() imports the TPU kernels' toolchain in
the background where the process is bound for a TPU, and only there."""
from __future__ import annotations

import sys
import threading

import pytest

from horovod_tpu.common import prefetch


@pytest.fixture
def unloaded(monkeypatch):
    """A toolchain that is not loaded yet, and a record of its import."""
    seen = []
    monkeypatch.setattr(prefetch, "KERNEL_MODULES",
                        ("hvd_no_such_kernel_module", "json"))
    monkeypatch.setattr(prefetch.importlib.util, "find_spec",
                        lambda name: object())
    real = prefetch.importlib.import_module
    monkeypatch.setattr(
        prefetch.importlib, "import_module",
        lambda name: seen.append((name, threading.current_thread().name))
        or real(name))
    return seen


@pytest.mark.parametrize("platforms, bound", [
    ("tpu,cpu", True), ("tpu", True), ("", True), ("cpu", False),
    ("cuda,cpu", False)])
def test_the_platform_is_read_and_nothing_is_opened(platforms, bound,
                                                    unloaded, monkeypatch):
    import jax  # noqa: F401 - a process that has JAX loaded
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    assert prefetch.bound_for_tpu() is bound
    started = prefetch.start_kernel_imports()
    assert (started is not None) is bound
    if started is not None:
        started.close()
        assert not started.thread.is_alive()
        # The first module is missing: the thread gives up, in silence.
        assert unloaded == [("hvd_no_such_kernel_module",
                             "hvd-kernel-import")]


def test_a_loaded_toolchain_or_a_process_without_jax_is_left_alone(
        unloaded, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setattr(prefetch, "KERNEL_MODULES", ("json",))
    assert prefetch.start_kernel_imports() is None         # loaded
    monkeypatch.setattr(prefetch, "KERNEL_MODULES", ("hvd_no_such",))
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    assert not prefetch.bound_for_tpu()
    assert prefetch.start_kernel_imports() is None
    monkeypatch.setattr(prefetch.importlib.util, "find_spec",
                        lambda name: None)                 # no libtpu
    assert not prefetch.bound_for_tpu()


def test_init_starts_it_and_a_cpu_process_runs_no_such_thread(unloaded,
                                                              monkeypatch):
    import jax  # noqa: F401
    import horovod_tpu as hvd
    for platforms, started in (("cpu", False), ("tpu,cpu", True)):
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
        del unloaded[:]
        hvd.shutdown()
        hvd.init()
        hvd.shutdown()             # the drain waits for the import
        assert bool(unloaded) is started
        assert "hvd-kernel-import" not in [
            thread.name for thread in threading.enumerate()]
