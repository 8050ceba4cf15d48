"""Loss functions.

Reference parity: ``src/loss_functions/loss_functions.cc:41-160``. The
reference computes the gradient of the final op's output directly (e.g.
(probs - onehot)/B for softmax+CE). Here losses are scalar functions
differentiated by jax.grad; when the graph ends in Softmax and the loss is
cross-entropy, the executor passes the *logits* here and we use the fused
stable form — the resulting gradient is identical to the reference's
hand-written (probs - labels)/batch kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ffconst import LossType


def compute_loss(loss_type: LossType, pred, label, *, logits: bool = False,
                 weights=None):
    """Mean-reduced scalar loss. `pred` is the final op output (or pre-
    softmax logits when logits=True and the loss is a cross-entropy).
    ``weights`` (the sparse cross-entropy alone): one weight a row, and
    the loss is ``sum(w * nll) / rows``, the rows all counted whatever
    their weight (a masked-diffusion loss: ``w = masked / t``)."""
    loss_type = LossType(loss_type)
    pred = pred.astype(jnp.float32)

    if loss_type == LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY:
        label = label.reshape(pred.shape[:-1] + (-1,))[..., 0].astype(jnp.int32)
        if logits:
            logp = jax.nn.log_softmax(pred, axis=-1)
        else:
            logp = jnp.log(jnp.clip(pred, 1e-10, 1.0))
        nll = -jnp.take_along_axis(logp, label[..., None], axis=-1)[..., 0]
        if weights is not None:
            w = jax.lax.stop_gradient(weights.astype(jnp.float32))
            return jnp.sum(w.reshape(nll.shape) * nll) / nll.size
        return jnp.mean(nll)

    if weights is not None:
        raise NotImplementedError(
            f"{loss_type.name} takes no weights: rows are weighed in the "
            f"sparse categorical cross-entropy alone")

    if loss_type == LossType.LOSS_CATEGORICAL_CROSSENTROPY:
        label = label.astype(jnp.float32)
        if logits:
            logp = jax.nn.log_softmax(pred, axis=-1)
        else:
            logp = jnp.log(jnp.clip(pred, 1e-10, 1.0))
        # mean over batch rows, sum over classes (reference scale 1/batch)
        batch = pred.size // pred.shape[-1]
        return -jnp.sum(label * logp) / batch

    if loss_type == LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE:
        d = pred - label.astype(jnp.float32)
        # reference grad scale 2/volume (loss_functions.cc:51) == mean over
        # ALL elements (torch mse_loss equivalent)
        return jnp.mean(d * d)

    if loss_type == LossType.LOSS_MEAN_SQUARED_ERROR_SUM_REDUCE:
        d = pred - label.astype(jnp.float32)
        # reference grad = (pred-label)/batchSize (scale 1/batch,
        # loss_functions.cc:53 + .cu kernel) => loss = sum(d^2)/(2*batch)
        return 0.5 * jnp.sum(d * d) / d.shape[0]

    if loss_type == LossType.LOSS_IDENTITY:
        return jnp.mean(pred)

    raise ValueError(loss_type)


_CE_LOSSES = (LossType.LOSS_CATEGORICAL_CROSSENTROPY,
              LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)


def wants_logits(loss_type: LossType) -> bool:
    return LossType(loss_type) in _CE_LOSSES
