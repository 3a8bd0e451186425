"""Model zoo + the name-driven module factory.

Reference: ``ppfleetx/models/__init__.py:28-32`` resolves
``Model.module`` by name. Same contract here, without ``eval``.
"""

from __future__ import annotations

_REGISTRY = {}


def register_module(name: str):
    def deco(cls):
        _REGISTRY[name] = cls
        return cls
    return deco


def build_module(config):
    """Instantiate the module named by ``config.Model.module``."""
    # populate the registry lazily to avoid heavy imports at package load
    import importlib
    for mod in ("gpt.modules", "ernie.modules", "vit.modules",
                "imagen.modules", "deepseek_v3.modules"):
        try:
            importlib.import_module(f".{mod}", __package__)
        except ModuleNotFoundError as e:
            # tolerate only the module itself being absent (not yet
            # built); propagate broken imports inside an existing module
            if e.name != f"{__package__}.{mod}":
                raise
    name = config.Model.module
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown module {name!r}; available: {sorted(_REGISTRY)}")
    return _REGISTRY[name](config)
