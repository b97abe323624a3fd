"""Model zoo (reference: gluon model_zoo/vision + GluonCV/GluonNLP model
families per BASELINE.json configs)."""
from __future__ import annotations

_FACTORIES = {}


def register_model(name):
    def deco(fn):
        _FACTORIES[name] = fn
        return fn
    return deco


def _ensure_registry():
    from . import (lenet, mlp, resnet, mobilenet, vgg, alexnet,  # noqa: F401
                   squeezenet, densenet, inception, bert, transformer,
                   llama, afmoe, jamba, sarvam, brumby, mellum, fm, word_embedding, ssd)
    return _FACTORIES


def list_models():
    """Names accepted by get_model (reference: model_zoo get_model
    listing)."""
    return sorted(_ensure_registry())


def get_model(name, **kwargs):
    name = name.lower()
    _ensure_registry()
    if name not in _FACTORIES:
        raise ValueError(f"unknown model {name}; have "
                         f"{sorted(_FACTORIES)}")
    return _FACTORIES[name](**kwargs)
