"""TPU-native distributed K-FAC: a JAX/XLA/Pallas rebuild of the
capabilities of MLHPC/Distributed_KFAC_Pytorch (kfac-pytorch 0.3.1).

Public API (parity with reference kfac/__init__.py:1-5):
  - ``KFAC``: the K-FAC gradient preconditioner (functional state pytree).
  - ``CommMethod``: COMM_OPT / MEM_OPT / HYBRID_OPT strategies.
  - ``KFACParamScheduler``: epoch-schedule decay of damping / update freqs.
  - ``KFACCapture``: hook-free activation/output-grad capture for flax.
plus the ``ops``, ``parallel`` and ``layers`` subpackages.
"""

__version__ = '0.1.0'

from distributed_kfac_pytorch_tpu import fp16
from distributed_kfac_pytorch_tpu import observability
from distributed_kfac_pytorch_tpu import ops
from distributed_kfac_pytorch_tpu import parallel
from distributed_kfac_pytorch_tpu import utils
from distributed_kfac_pytorch_tpu.capture import KFACCapture
from distributed_kfac_pytorch_tpu.optim import kfac_transform
from distributed_kfac_pytorch_tpu.parallel.distributed import (
    DistributedKFAC,
    make_kfac_mesh,
)
from distributed_kfac_pytorch_tpu.preconditioner import CommMethod, KFAC
from distributed_kfac_pytorch_tpu.scheduler import KFACParamScheduler
