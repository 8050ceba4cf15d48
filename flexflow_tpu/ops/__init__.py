"""Operator registry: import all op modules to populate OPS."""
from .registry import OPS, EmitCtx, OpDef, get_op_def, matmul  # noqa: F401


def ensure_weight_specs(layer):
    """Materialize (and memoize on the layer) a layer's WeightSpec list
    — THE shared wiring for every consumer that sizes or initializes
    weights (executor init, the overlap schedule builder): a future
    change to how specs derive happens here once, or per-consumer
    copies drift."""
    specs = layer.weights or get_op_def(layer.op_type).weights(
        layer.params, [t.shape for t in layer.inputs],
        [t.dtype for t in layer.inputs])
    layer.weights = specs
    return specs
from . import nn_ops        # noqa: F401
from . import element_ops   # noqa: F401
from . import tensor_ops    # noqa: F401
from . import moe_ops       # noqa: F401
from . import rnn_ops       # noqa: F401
from . import recurrent_ops  # noqa: F401
from . import hyper_ops     # noqa: F401
from . import parallel_ops  # noqa: F401
