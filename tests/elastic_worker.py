"""Worker script for the end-to-end elastic integration test.

The analogue of the reference's test/integration elastic training scripts:
train a counter via hvd.elastic.run with commits every step; a designated
"host" (localhost alias) hard-exits mid-training to simulate a node failure,
and the survivors must restore committed state, re-rendezvous at a smaller
world size, and finish.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

XLA_WORLD = bool(os.environ.get("TEST_ELASTIC_XLA"))
if XLA_WORLD:
    # Elastic x XLA: form a multi-process JAX world each epoch.  Pin the
    # CPU backend before anything imports jax.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["HOROVOD_JAX_DISTRIBUTED"] = "1"
    os.environ["HOROVOD_XLA_OPERATIONS"] = "1"

import numpy as np

import horovod_tpu as hvd
from horovod_tpu.elastic import ObjectState
from horovod_tpu.elastic.run import run as elastic_run

FAIL_HOST = os.environ.get("TEST_ELASTIC_FAIL_HOST", "")
FAIL_EPOCH = int(os.environ.get("TEST_ELASTIC_FAIL_EPOCH", "2"))
TARGET = int(os.environ.get("TEST_ELASTIC_TARGET", "5"))
OUT_DIR = os.environ["TEST_ELASTIC_OUT"]


@elastic_run
def train(state):
    while state.epoch < TARGET:
        hostname = os.environ.get("HOROVOD_HOSTNAME", "")
        if hostname == FAIL_HOST and state.epoch == FAIL_EPOCH:
            os._exit(17)   # simulate sudden node death
        # Cross-rank step: every live rank must agree on the result.
        out = hvd.allreduce(np.ones(4, np.float32) * (state.epoch + 1),
                            average=False, name=f"step")
        expected = (state.epoch + 1) * hvd.size()
        np.testing.assert_allclose(np.asarray(out), np.full(4, expected),
                                   rtol=1e-6)
        if XLA_WORLD and hvd.size() > 1:
            # The collective must have ridden the freshly (re-)formed XLA
            # device plane, not fallen back to the TCP ring.
            from horovod_tpu.core import _global
            backend = _global.op_manager.backends[0]
            assert backend.name == "xla", backend.name
            assert backend.comm._cache, "xla plane never executed"
        state.epoch += 1
        state.commit()
    return state.epoch


def main() -> int:
    state = ObjectState(epoch=0)
    result = train(state)
    if result is None:
        return 0   # dropped from the world: clean exit
    marker = os.path.join(
        OUT_DIR, f"done.{os.environ.get('HOROVOD_HOSTNAME')}."
                 f"{os.environ.get('HOROVOD_LOCAL_RANK')}")
    with open(marker, "w") as f:
        f.write(f"{result} {hvd.size()} {hvd.rank()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
