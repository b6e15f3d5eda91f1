"""Model zoo: pure-JAX/flax models for the five baseline configs.

TPU-native replacement for the reference's model layer (SURVEY.md §2.1: the
reference carries *no* models of its own — it ships serialized Keras graphs
into Spark tasks via ``utils.serialize_keras_model``).  Here models are flax
modules built from a JSON-serializable config dict (``build_model``), which
is the wire-format analogue of the reference's architecture-JSON: the config
travels, not pickled code.
"""

from distkeras_tpu.models.core import (  # noqa: F401
    MODEL_REGISTRY,
    ModelSpec,
    build_model,
    init_model,
    model_config,
    register_model,
)
from distkeras_tpu.models.mlp import MLP  # noqa: F401
from distkeras_tpu.models.convnet import ConvNet  # noqa: F401
from distkeras_tpu.models.resnet import ResNet, ResNet18, ResNet50  # noqa: F401
from distkeras_tpu.models.lstm import BiLSTMClassifier  # noqa: F401
from distkeras_tpu.models.widedeep import WideAndDeep  # noqa: F401
from distkeras_tpu.models.transformer import TransformerLM  # noqa: F401
from distkeras_tpu.models.latent_moe import LatentMoELM  # noqa: F401
from distkeras_tpu.models.hybrid_moe import HybridMoELM  # noqa: F401
from distkeras_tpu.models.window_moe import WindowMoELM  # noqa: F401
from distkeras_tpu.models.generate import (  # noqa: F401
    beam_search,
    generate,
)
