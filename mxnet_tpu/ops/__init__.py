"""Op registry + families. Importing this package registers all ops.

TPU-native analogue of the reference's src/operator/ tree: each submodule
mirrors one reference op family (see the per-file docstrings for the
file:line provenance map).
"""
from .registry import Operator, register, get, list_ops, alias  # noqa: F401
from . import elemwise      # noqa: F401
from . import reduce        # noqa: F401
from . import shape_ops     # noqa: F401
from . import nn            # noqa: F401
from . import rnn           # noqa: F401
from . import flash_attention  # noqa: F401
from . import ragged_attention  # noqa: F401
from . import contrib_det   # noqa: F401
from . import contrib_det2  # noqa: F401
from . import extra         # noqa: F401
from . import linalg        # noqa: F401
from . import random_ops    # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import quantization  # noqa: F401
from . import lora          # noqa: F401
from . import moe           # noqa: F401
from . import ssd           # noqa: F401
from .invoke import apply_op, apply_fn  # noqa: F401
# mx.operator registers the 'Custom' op (user Python ops over
# jax.pure_callback); import it before the nd namespace is generated
from .. import operator as _custom_operator  # noqa: F401,E402
