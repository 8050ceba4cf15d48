"""Metrics: PerfMetrics accumulation.

Reference parity: ``src/metrics_functions/metrics_functions.cc:68-130`` —
per-shard ``PerfMetrics`` reduced through a Legion future chain. Here the
per-batch metrics are computed inside the jitted step (so the reduction is
an XLA collective over the sharded batch) and accumulated on host floats.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

from ..ffconst import LossType, MetricsType

#: Prefix, among a step's metrics, of the device counters its ops added
#: to through ``EmitCtx.count``: whatever an op counts under ``<name>``
#: is the metric ``COUNTER_PREFIX + <name>``, a sum over the step's
#: layers, fetched with the loss and recorded as the ``obs.events``
#: counter ``<name>`` at each flush of the metrics buffer. No name is
#: listed here: the ops own theirs.
COUNTER_PREFIX = "counter/"

# batch-metric keys that are COUNTS over samples (vs per-sample means):
# accumulation/reduction layers must SUM these across micro-batches,
# never average (see Executor.make_train_step)
COUNT_KEYS = frozenset({"accuracy_correct"})


def is_count(key: str) -> bool:
    """A count (summed across micro-batches and replicas): one of
    ``COUNT_KEYS`` or an op's device counter."""
    return key in COUNT_KEYS or key.startswith(COUNTER_PREFIX)

# keys that are sqrt-of-a-mean: composing across micro-batches must
# average the SQUARES and take one sqrt at the end (mean of per-micro
# sqrts is not the full-batch RMSE)
RMS_KEYS = frozenset({"rmse_loss"})


@dataclasses.dataclass
class PerfMetrics:
    """Host-side accumulator (reference ``PerfMetrics`` struct parity)."""
    train_all: int = 0
    train_correct: int = 0
    cce_loss: float = 0.0
    sparse_cce_loss: float = 0.0
    mse_loss: float = 0.0
    rmse_loss: float = 0.0
    mae_loss: float = 0.0
    loss: float = 0.0

    _KEYS = ("cce_loss", "sparse_cce_loss", "mse_loss", "rmse_loss",
             "mae_loss", "loss")

    def update(self, batch_metrics: Dict[str, float], batch_size: int):
        self.train_all += batch_size
        if "accuracy_correct" in batch_metrics:
            self.train_correct += int(batch_metrics["accuracy_correct"])
        for k in self._KEYS:
            if k in batch_metrics:
                setattr(self, k, getattr(self, k)
                        + float(batch_metrics[k]) * batch_size)

    def report(self) -> Dict[str, float]:
        n = max(self.train_all, 1)
        out = {}
        if self.train_correct or self.train_all:
            out["accuracy"] = self.train_correct / n
        for k in self._KEYS:
            v = getattr(self, k)
            if v:
                out[k] = v / n
        return out


def compute_batch_metrics(metrics: Sequence[MetricsType], pred, label,
                          loss_type: LossType) -> Dict[str, jnp.ndarray]:
    """Inside-jit metric computation (reference ``Metrics::compute_task``)."""
    out: Dict[str, jnp.ndarray] = {}
    pf = pred.astype(jnp.float32)
    sparse = LossType(loss_type) == LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY
    for m in metrics:
        m = MetricsType(m)
        if m == MetricsType.METRICS_ACCURACY:
            yhat = jnp.argmax(pf, axis=-1)
            if sparse:
                y = label.reshape(yhat.shape + (-1,))[..., 0].astype(jnp.int32)
            else:
                y = jnp.argmax(label, axis=-1)
            out["accuracy_correct"] = jnp.sum(yhat == y).astype(jnp.float32)
        elif m == MetricsType.METRICS_CATEGORICAL_CROSSENTROPY:
            logp = jnp.log(jnp.clip(pf, 1e-10, 1.0))
            batch = pf.size // pf.shape[-1]
            out["cce_loss"] = -jnp.sum(label.astype(jnp.float32) * logp) / batch
        elif m == MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY:
            y = label.reshape(pf.shape[:-1] + (-1,))[..., 0].astype(jnp.int32)
            logp = jnp.log(jnp.clip(pf, 1e-10, 1.0))
            nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)
            out["sparse_cce_loss"] = jnp.mean(nll)
        elif m == MetricsType.METRICS_MEAN_SQUARED_ERROR:
            d = pf - label.astype(jnp.float32)
            out["mse_loss"] = jnp.mean(jnp.sum(d * d, axis=-1))
        elif m == MetricsType.METRICS_ROOT_MEAN_SQUARED_ERROR:
            d = pf - label.astype(jnp.float32)
            out["rmse_loss"] = jnp.sqrt(jnp.mean(jnp.sum(d * d, axis=-1)))
        elif m == MetricsType.METRICS_MEAN_ABSOLUTE_ERROR:
            d = jnp.abs(pf - label.astype(jnp.float32))
            out["mae_loss"] = jnp.mean(jnp.sum(d, axis=-1))
    return out
