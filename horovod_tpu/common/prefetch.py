"""Start-up: the TPU kernels' toolchain, imported ahead of its first use.

On a TPU every hot op ends in a Pallas kernel (``ops/``:
``hvd.flash_fwd``, ``hvd.ssm_update``, ``hvd.decode_attend``), and the
first trace of one imports ``jax.experimental.pallas``: 1.3 to 1.5 s on
the serving replica's critical path (measured on a v5e host, PR 33:
0.6 s of it byte-compiling, since an installation ships no ``.pyc`` for
modules it never ran, 0.9 s the GPU dialects the package pulls in),
between the weights arriving and the first program compiling.
``hvd.init()`` starts that import in a daemon thread instead, where the
process is bound for a TPU, so that it runs while the program loads or
makes its weights, which waits on the device and on files, not on the
interpreter.  The first kernel then finds the modules loaded; if the
thread is still at it, the importer waits for it (a module's import lock),
and if it failed, imports them itself as before.

Nothing is opened or decided here: the platform is read from
``JAX_PLATFORMS`` and the presence of ``libtpu``, never from a backend,
and a process that has not imported JAX yet is left alone (two threads
would then race through JAX's own import).  The thread imports only
modules that nothing outside Pallas imports, so no import lock it holds
is one the main thread needs while holding another.
"""
from __future__ import annotations

import importlib
import importlib.util
import os
import sys
import threading

# What a TPU kernel's first trace and first lowering import.
KERNEL_MODULES = ("jax.experimental.pallas", "jax.experimental.pallas.tpu",
                  "jaxlib.mlir.dialects.mhlo")


def bound_for_tpu() -> bool:
    """Whether this process will run its programs on a TPU, without
    opening one: JAX is loaded, ``libtpu`` is installed, and
    ``JAX_PLATFORMS``, if set, names the TPU."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    return "jax" in sys.modules \
        and importlib.util.find_spec("libtpu") is not None


class KernelImport:
    """The import of ``KERNEL_MODULES`` in a daemon thread; ``close``
    (the runtime's resource drain at shutdown) waits for it to end."""

    def __init__(self) -> None:
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="hvd-kernel-import")
        self.thread.start()

    @staticmethod
    def _run() -> None:
        for name in KERNEL_MODULES:
            try:
                importlib.import_module(name)
            except Exception:  # noqa: BLE001 - the first kernel imports it
                return

    def close(self) -> None:
        self.thread.join()


def start_kernel_imports() -> KernelImport | None:
    """Start importing ``KERNEL_MODULES`` in the background, if this
    process is bound for a TPU and has not loaded them; what to close
    at shutdown, or None."""
    if KERNEL_MODULES[0] in sys.modules or not bound_for_tpu():
        return None
    return KernelImport()
