"""Assigned-architecture configs (+ the paper's own tensor-algebra ops)."""
from .base import SHAPES, InputShape, ModelConfig, cells_for
from .registry import ARCH_IDS, all_configs, get_config

__all__ = ["SHAPES", "InputShape", "ModelConfig", "cells_for", "ARCH_IDS",
           "all_configs", "get_config"]
