from repro.configs.base import (
    CNNConfig,
    ConvLayerDef,
    INPUT_SHAPES,
    InputShape,
    LayerDef,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    SSMConfig,
    XLSTMConfig,
    YarnScaling,
)

__all__ = [
    "CNNConfig",
    "ConvLayerDef",
    "INPUT_SHAPES",
    "InputShape",
    "LayerDef",
    "MLAConfig",
    "ModelConfig",
    "MoEConfig",
    "SSMConfig",
    "XLSTMConfig",
    "YarnScaling",
]
