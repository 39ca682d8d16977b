"""Typed, serializable configuration system.

The reference wires every knob through Tang ``@NamedParameter`` classes,
serializes whole config graphs to strings, ships them across processes, and
re-injects them (ref: ETDolphinLauncher.java:119-201, JobServerDriver.java:
243-245, TaskletRuntime forked injectors). This module is the TPU build's
equivalent: dataclass-based configs with

  * a class registry so polymorphic nested configs round-trip through JSON
    (``_type`` discriminator),
  * dotted-path symbol references for user callables/classes (trainers,
    update functions, parsers) — the analogue of Tang binding an
    implementation class by name.

Configs are plain data: JSON in, JSON out, no pickling, safe to send over the
control plane.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
from typing import Any, Dict, Type, TypeVar

_REGISTRY: Dict[str, type] = {}

T = TypeVar("T")


def register_config(cls: Type[T]) -> Type[T]:
    """Register a dataclass config type under its class name."""
    _REGISTRY[cls.__name__] = cls
    return cls


def config(cls: Type[T]) -> Type[T]:
    """Decorator: make ``cls`` a frozen-ish dataclass config and register it."""
    dc = dataclasses.dataclass(cls)
    return register_config(dc)


def symbol_name(obj: Any) -> str:
    """Dotted import path for a module-level callable/class."""
    module = getattr(obj, "__module__", None)
    qualname = getattr(obj, "__qualname__", None)
    if not module or not qualname or "<locals>" in qualname:
        raise ValueError(f"not an importable module-level symbol: {obj!r}")
    return f"{module}:{qualname}"


def resolve_symbol(path: str) -> Any:
    """Inverse of :func:`symbol_name`."""
    module, _, qual = path.partition(":")
    obj: Any = importlib.import_module(module)
    for part in qual.split("."):
        obj = getattr(obj, part)
    return obj


def _encode(value: Any) -> Any:
    if dataclasses.is_dataclass(value) and not isinstance(value, type):
        d = {"_type": type(value).__name__}
        for f in dataclasses.fields(value):
            d[f.name] = _encode(getattr(value, f.name))
        return d
    if isinstance(value, dict):
        enc = {k: _encode(v) for k, v in value.items()}
        if "_type" in value:
            # Escape user dicts that happen to carry the discriminator key so
            # they can't collide with (or hijack) registered config types.
            return {"_type": "__raw_dict__", "value": enc}
        return enc
    if isinstance(value, (list, tuple)):
        return [_encode(v) for v in value]
    return value


def _decode(value: Any) -> Any:
    if isinstance(value, dict):
        if "_type" in value:
            if value["_type"] == "__raw_dict__":
                return {k: _decode(v) for k, v in value["value"].items()}
            cls = _REGISTRY.get(value["_type"])
            if cls is None:
                raise KeyError(f"unregistered config type {value['_type']!r}")
            kwargs = {k: _decode(v) for k, v in value.items() if k != "_type"}
            # fields a config type once had (its RETIRED_FIELDS: name ->
            # (the default older writers serialized, what went)): dropped
            # at that default, refused at any other value — the record
            # asks for behaviour the program no longer has
            retired = getattr(cls, "RETIRED_FIELDS", {})
            for name, (old_default, why) in retired.items():
                got = kwargs.pop(name, old_default)
                if got != old_default:
                    raise ValueError(
                        f"{cls.__name__}.{name}={got!r} is refused: {why}; "
                        "the retired field decodes only at its old "
                        f"default {old_default!r}")
            return cls(**kwargs)
        return {k: _decode(v) for k, v in value.items()}
    if isinstance(value, list):
        return [_decode(v) for v in value]
    return value


class ConfigBase:
    """Mixin giving dataclass configs JSON round-trip and copy-with-changes."""

    def to_dict(self) -> Dict[str, Any]:
        return _encode(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> Any:
        return _decode(d)

    @staticmethod
    def from_json(s: str) -> Any:
        return _decode(json.loads(s))

    def replace(self: T, **changes: Any) -> T:
        return dataclasses.replace(self, **changes)  # type: ignore[type-var]
