"""flexflow_tpu: a TPU-native distributed DNN training framework with
automatic parallelization search (FlexFlow/Unity capabilities, JAX/XLA/
Pallas implementation).

Quick start::

    from flexflow_tpu import FFModel, FFConfig, SGDOptimizer
    ff = FFModel(FFConfig())
    x = ff.create_tensor((64, 784))
    t = ff.dense(x, 512, activation=ActiMode.AC_MODE_RELU)
    t = ff.dense(t, 10)
    out = ff.softmax(t)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy",
               ["accuracy"])
    ff.fit(x=images, y=labels, epochs=2)
"""
from .ffconst import (ActiMode, AggrMode, CompMode, DataType, InitializerType,
                      LossType, MetricsType, OperatorType, ParameterSyncType,
                      PoolType, RegularizerMode)
from .config import FFConfig, FFIterationConfig
from .core.tensor import Tensor, WeightSpec
from .core.layer import Layer
from .model import FFModel
from .parallel.machine import DeviceMesh, MachineSpec
from .parallel.ptensor import ParallelDim, ParallelTensorShape
from .parallel.strategy import OpSharding, ShardingStrategy
from .runtime.optimizers import AdamOptimizer, Optimizer, SGDOptimizer
from .runtime.dataloader import SingleDataLoader
from .runtime.metrics import PerfMetrics

__version__ = "0.1.0"
