"""deeplearning4j_tpu — a TPU-native deep-learning framework.

Brand-new JAX/XLA/Pallas/pjit implementation of the capabilities of
Deeplearning4J 0.7.x (reference: /root/reference, surveyed in SURVEY.md).
Not a port: layers are pure functions, backprop is autodiff, the cuDNN helper
tier is XLA, and ParallelWrapper/Spark/Aeron collapse into mesh collectives.
"""

import time as _time

_import_t0 = _time.perf_counter()

__version__ = "0.1.0"

from .nn.conf.inputs import InputType
from .nn.conf.multi_layer import MultiLayerConfiguration
from .nn.updaters import UpdaterConfig
from .nn.multilayer import MultiLayerNetwork
from .nn.layers.base import BaseLayer, register_layer
from .nn.conf.computation_graph import ComputationGraphConfiguration, GraphBuilder
from .nn.graph import (
    ComputationGraph,
    BaseVertex,
    LayerVertex,
    ElementWiseVertex,
    MergeVertex,
    SubsetVertex,
    StackVertex,
    UnstackVertex,
    ScaleVertex,
    ShiftVertex,
    L2Vertex,
    L2NormalizeVertex,
    PreprocessorVertex,
    LastTimeStepVertex,
    DuplicateToTimeSeriesVertex,
    ReshapeVertex,
)
from .nn.layers.dense import (
    DenseLayer,
    OutputLayer,
    LossLayer,
    ActivationLayer,
    DropoutLayer,
    EmbeddingLayer,
)
from .nn.layers.convolution import (
    ConvolutionLayer,
    Convolution1DLayer,
    ZeroPaddingLayer,
)
from .nn.layers.pooling import SubsamplingLayer, GlobalPoolingLayer
from .nn.layers.recurrent import (
    GravesLSTM,
    GravesBidirectionalLSTM,
    RnnOutputLayer,
    RnnEmbeddingLayer,
    LastTimeStepLayer,
)
from .nn.layers.normalization import BatchNormalization, LocalResponseNormalization
from .nn.layers.attention import (LatentAttentionLayer, LayerNormLayer,
                                  SelfAttentionLayer)
from .nn.layers.dense import GatedFeedForwardLayer
from .nn.layers.hyper_connections import (HyperConnectionMapsLayer,
                                          HyperConnectionVertex)
from .nn.layers.linear_attention import KimiDeltaAttentionLayer
from .nn.layers.moe import DroplessExpertsLayer, MixtureOfExpertsLayer
from .nn.layers.state_space import Mamba2Layer, RMSNormLayer
from .nn.layers.center_loss import CenterLossOutputLayer
from .datasets.iterators import (
    DataSet,
    MultiDataSet,
    DataSetIterator,
    NumpyDataSetIterator,
    ListDataSetIterator,
    AsyncDataSetIterator,
    MultipleEpochsIterator,
)
from .eval.evaluation import Evaluation
from .eval.roc import ROC, ROCMultiClass
from .eval.regression import RegressionEvaluation
from .nn.layers.frozen import FrozenLayer
from .nn.layers.pretrain import AutoEncoder, RBM
from .nn.layers.variational import (
    VariationalAutoencoder,
    BernoulliReconstruction,
    GaussianReconstruction,
    ExponentialReconstruction,
    CompositeReconstruction,
    LossFunctionWrapper,
)
from .nn.transferlearning import (
    TransferLearning,
    TransferLearningBuilder,
    TransferLearningGraphBuilder,
    FineTuneConfiguration,
)
from .optimize.listeners import (
    ComposableIterationListener,
    IterationListener,
    TrainingListener,
    ParamAndGradientIterationListener,
    ScoreIterationListener,
    CollectScoresIterationListener,
    PerformanceListener,
)
from .utils.serialization import write_model, restore_model
from .telemetry import (
    MetricsRegistry,
    Telemetry,
    Watchdog,
    get_registry,
)

__all__ = [
    "InputType",
    "MultiLayerConfiguration",
    "UpdaterConfig",
    "MultiLayerNetwork",
    "BaseLayer",
    "register_layer",
    "ComputationGraphConfiguration",
    "GraphBuilder",
    "ComputationGraph",
    "BaseVertex",
    "LayerVertex",
    "ElementWiseVertex",
    "MergeVertex",
    "SubsetVertex",
    "StackVertex",
    "UnstackVertex",
    "ScaleVertex",
    "ShiftVertex",
    "L2Vertex",
    "L2NormalizeVertex",
    "PreprocessorVertex",
    "LastTimeStepVertex",
    "DuplicateToTimeSeriesVertex",
    "ReshapeVertex",
    "DenseLayer",
    "OutputLayer",
    "LossLayer",
    "ActivationLayer",
    "DropoutLayer",
    "EmbeddingLayer",
    "ConvolutionLayer",
    "Convolution1DLayer",
    "ZeroPaddingLayer",
    "SubsamplingLayer",
    "GlobalPoolingLayer",
    "GravesLSTM",
    "GravesBidirectionalLSTM",
    "RnnOutputLayer",
    "RnnEmbeddingLayer",
    "LastTimeStepLayer",
    "BatchNormalization",
    "LocalResponseNormalization",
    "DataSet",
    "MultiDataSet",
    "DataSetIterator",
    "NumpyDataSetIterator",
    "ListDataSetIterator",
    "AsyncDataSetIterator",
    "MultipleEpochsIterator",
    "Evaluation",
    "ROC",
    "ROCMultiClass",
    "RegressionEvaluation",
    "FrozenLayer",
    "AutoEncoder",
    "RBM",
    "VariationalAutoencoder",
    "BernoulliReconstruction",
    "GaussianReconstruction",
    "ExponentialReconstruction",
    "CompositeReconstruction",
    "LossFunctionWrapper",
    "TransferLearning",
    "TransferLearningBuilder",
    "TransferLearningGraphBuilder",
    "FineTuneConfiguration",
    "IterationListener",
    "TrainingListener",
    "ComposableIterationListener",
    "ParamAndGradientIterationListener",
    "ScoreIterationListener",
    "CollectScoresIterationListener",
    "PerformanceListener",
    "write_model",
    "restore_model",
    "MetricsRegistry",
    "Telemetry",
    "Watchdog",
    "get_registry",
]

# seconds this import took, the packages it pulls in included (a reader's
# counter: the benchmark reports it as ``package_import_s``)
import_seconds = _time.perf_counter() - _import_t0
