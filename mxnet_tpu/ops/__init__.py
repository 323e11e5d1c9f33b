"""Operator library: importing this package registers every op.

Reference: ``src/operator/`` registration via NNVM_REGISTER_OP static
initializers; here registration runs at import of the submodules.
"""
from . import registry
from .registry import register, get_op, list_ops, invoke, OP_REGISTRY

from . import tensor      # noqa: F401  elemwise/broadcast/reduce/shape/index
from . import nn          # noqa: F401  Convolution/BatchNorm/RNN/...
from . import linalg      # noqa: F401  gemm/potrf/trsm
from . import optimizer_ops  # noqa: F401  fused sgd/adam/lamb updates
from . import contrib     # noqa: F401  transformer kernels, roialign, ...
from . import detection   # noqa: F401  SSD MultiBox prior/target/detection
from . import moe         # noqa: F401  MoE routing + expert FFN (GShard)
from . import ssm         # noqa: F401  Mamba-2 conv / selective scan / gated norm
from . import shortconv   # noqa: F401  LFM2 gated short convolution
from . import deltanet    # noqa: F401  gated delta rule: Gated DeltaNet, KDA
from . import quantization  # noqa: F401  int8 quantize/dequantize/qgemm
from . import pallas_kernels  # noqa: F401  flash attention (TPU/interpret)
from .. import random as _random_ops  # noqa: F401  sampling ops
