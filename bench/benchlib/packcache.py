"""The pack cache: the ESPIM packs of one configuration, built once per
checkout and loaded by every later run.

Sparsity is static and known before inference (the paper's SDDS premise),
so a deployment builds its packs offline, once, and loads them at start.
The first run of a configuration in a checkout calls the program's pack
compiler and saves what it returns; later runs load it.  The key covers
the configuration file's bytes, the weight seed and every ``.py`` source
of the program, so any change to the program misses the cache.
"""
from __future__ import annotations

import hashlib
import os
import pathlib
import pickle
import time

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["source_digest", "cache_key", "load_or_build"]


def source_digest(src: pathlib.Path) -> str:
    """sha256 over every ``*.py`` under ``src``: relative path and bytes,
    in sorted order."""
    h = hashlib.sha256()
    for p in sorted(src.rglob("*.py")):
        h.update(str(p.relative_to(src)).encode())
        h.update(b"\0")
        h.update(p.read_bytes())
        h.update(b"\0")
    return h.hexdigest()


def cache_key(config_bytes: bytes, weight_seed: int, src_digest: str,
              extra: str = "") -> str:
    h = hashlib.sha256()
    for part in (config_bytes, str(int(weight_seed)).encode(),
                 src_digest.encode(), extra.encode()):
        h.update(part)
        h.update(b"\0")
    return h.hexdigest()[:24]


class _OnDevice:
    """A host copy of an array that lived on the device."""
    __slots__ = ("a",)

    def __init__(self, a):
        self.a = a

    def __reduce__(self):
        return (_OnDevice, (self.a,))


def _walk(x, leaf, memo=None):
    """``leaf`` applied to every leaf of nested dicts, lists and tuples,
    once per object: an array that the tree holds under two names (the
    sparse dict aliases its groups and pruned copies) stays one array."""
    memo = {} if memo is None else memo
    if isinstance(x, dict):
        return {k: _walk(v, leaf, memo) for k, v in x.items()}
    if isinstance(x, list):
        return [_walk(v, leaf, memo) for v in x]
    if isinstance(x, tuple) and not hasattr(x, "_fields"):
        return tuple(_walk(v, leaf, memo) for v in x)
    if id(x) not in memo:
        memo[id(x)] = (x, leaf(x))        # x kept alive: ids stay unique
    return memo[id(x)][1]


def _to_host(x):
    return _OnDevice(np.asarray(x)) if isinstance(x, jax.Array) else x


def _to_device(x):
    return jnp.asarray(x.a) if isinstance(x, _OnDevice) else x


def load_or_build(path: pathlib.Path, build):
    """Load the packs saved at ``path``, or ``build()`` them and save
    them there.  Returns ``(sparse, info)``, where ``info`` holds
    ``hit`` and the seconds of ``pack`` (a miss) or ``load``."""
    t = time.perf_counter()
    if path.exists():
        with open(path, "rb") as f:
            sparse = _walk(pickle.load(f), _to_device)
        return sparse, {"hit": True, "load": time.perf_counter() - t}
    sparse = build()
    jax.block_until_ready(_walk(sparse, lambda x: x if isinstance(
        x, jax.Array) else None))
    info = {"hit": False, "pack": time.perf_counter() - t}
    t = time.perf_counter()
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".tmp{os.getpid()}")
    with open(tmp, "wb") as f:
        pickle.dump(_walk(sparse, _to_host), f, protocol=5)
    os.replace(tmp, path)
    info["save"] = time.perf_counter() - t
    return sparse, info
