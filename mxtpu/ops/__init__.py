"""Operator library: importing this package registers every op (SURVEY.md §2.2 surface)."""

from . import registry
from .registry import OpDef, get_op, invoke, list_ops, register

# registration side effects
from . import elementwise  # noqa: F401
from . import reduce  # noqa: F401
from . import matrix  # noqa: F401
from . import init_ops  # noqa: F401
from . import order  # noqa: F401
from . import linalg  # noqa: F401
from . import sequence  # noqa: F401
from . import nn  # noqa: F401
from . import random  # noqa: F401
from . import contrib_ops  # noqa: F401
from . import quantization  # noqa: F401
from . import detection  # noqa: F401
from . import spatial  # noqa: F401
from . import rnn  # noqa: F401
from . import attention  # noqa: F401
from . import ssm  # noqa: F401
from . import grouped_matmul  # noqa: F401
from . import retention  # noqa: F401
from . import kda  # noqa: F401
from . import image_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
