"""Operator library (TPU-native re-implementation of reference src/operator/).

Importing this package registers all operators. Op modules hold only pure jax
functions + registration; dispatch lives in .registry, the NDArray wrapper in
..ndarray.
"""
from . import registry  # noqa: F401
from .registry import get_op, list_ops, all_ops, register  # noqa: F401

from . import elemwise   # noqa: F401
from . import reduce     # noqa: F401
from . import matrix     # noqa: F401
from . import nn         # noqa: F401
from . import linalg     # noqa: F401
from . import contrib    # noqa: F401
from . import attention  # noqa: F401
from . import extra      # noqa: F401
from . import detection  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import misc       # noqa: F401
from . import random_pdf  # noqa: F401
from . import random_sample  # noqa: F401
from . import contrib_misc  # noqa: F401
from . import legacy     # noqa: F401
from . import quantized  # noqa: F401
from . import detection_extra  # noqa: F401
from . import dgl_ops    # noqa: F401
from . import ssm        # noqa: F401
from . import rotary     # noqa: F401
from . import moe        # noqa: F401
from . import sparse_select  # noqa: F401
