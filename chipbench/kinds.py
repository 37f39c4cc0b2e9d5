"""Find the code of a kind by its name. A traffic file names the kind of
its keys (``chipbench/keygen/<kind>.py``) and of its arrivals
(``chipbench/loops/<kind>.py``); a configuration file names its engine
(``chipbench/engines/<kind>.py``). A new kind is a new file there."""

from __future__ import annotations

import importlib

GROUPS = ("keygen", "loops", "engines")


def load(group: str, kind: str):
    if group not in GROUPS:
        raise ValueError(f"no group {group!r} ({GROUPS})")
    if not kind.isidentifier():
        raise ValueError(f"kind {kind!r} is not a module name")
    return importlib.import_module(f"chipbench.{group}.{kind}")


def make_keys(params: dict, seed: int, chunk: int = 0):
    """The key generator of a traffic file's ``keys`` block."""
    return load("keygen", params["kind"]).make(params, seed, chunk)


def make_system(config: dict, devices):
    """The engine a configuration file names, on ``devices``."""
    return load("engines", config["engine"]).System(config, devices)
