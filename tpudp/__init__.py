"""tpudp — TPU-native distributed data-parallel training framework.

A from-scratch JAX/XLA re-design of the capability surface of the CS744
distributed-data-parallel reference (rawahars/CS744-Distributed-Data-Parallel):
the four-part ladder of gradient-synchronization strategies

  * ``none``        — single-device baseline           (reference ``src/Part 1``)
  * ``coordinator`` — gather → mean → broadcast        (reference ``src/Part 2a/main.py:117-127``)
  * ``allreduce``   — collective all-reduce, mean      (reference ``src/Part 2b/main.py:116-119``)
  * ``ring``        — hand-rolled ring all-reduce      (north-star extra; built from lax.ppermute)
  * ``auto``        — compiler-scheduled sync in jit   (reference ``src/Part 3/main.py:61`` / DDP)
  * ``allreduce_bf16`` — bfloat16-compressed collective (beyond-reference; half the wire bytes)

running SPMD over a ``jax.sharding.Mesh`` with XLA collectives on ICI/DCN —
no process groups, no Gloo, no torch.distributed.
"""

__version__ = "0.1.0"

from tpudp.mesh import make_mesh, make_mesh_nd, initialize_distributed  # noqa: F401
from tpudp.train import Trainer, TrainState, make_train_step, make_eval_step  # noqa: F401
from tpudp.parallel.sync import SYNC_STRATEGIES  # noqa: F401
from tpudp.strategy import STRATEGIES, build_strategy  # noqa: F401
