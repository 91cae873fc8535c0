from repro.configs.base import (SHAPES, LayerSpec, ModelConfig, ShapeConfig,
                                YarnScaling, get_config, list_archs, register,
                                supported_shapes)

__all__ = ["SHAPES", "LayerSpec", "ModelConfig", "ShapeConfig", "YarnScaling",
           "get_config", "list_archs", "register", "supported_shapes"]
