"""The benchmark's own weights: made on the device in one jitted call from
``--seed``, in the type the program holds them in (float32), for a tree of
shapes. The program's initializers are not used: they leave the rel-pos
tables and the positional embedding at zero, which would hide both from
``correct``, and they are the program's, so the reference could not take
them.

A configuration's file gives the rules under ``weights``: a list of
``{"match": regex over the leaf's path, "init": "normal"|"fan_in", ...}``,
the first match wins. ``normal`` draws ``mean + std * N(0, 1)``; ``fan_in``
draws ``gain / sqrt(prod(shape[:-1])) * N(0, 1)``; ``"center": [axes]`` takes
the mean over those axes off a kernel, so that its output has no offset from
the mean of its input over them (the objectness stack: without it the share
of the map over the threshold, and so the number of detections, swings from
none to thousands with the seed).
"""

from __future__ import annotations

import math
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, salt: str = ""):
    """A PRNG key for any whole-number seed (seeds over 2**31 included).
    ``rbg``: the device's own bit generator, so that drawing hundreds of
    millions of weights compiles and runs in seconds."""
    words = np.random.SeedSequence(
        [int(seed), zlib.crc32(salt.encode())]).generate_state(4, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="rbg")


def flatten(tree, prefix: str = "") -> dict:
    """Nested dicts -> ``{"a/b/c": leaf}``."""
    out = {}
    for name, sub in tree.items():
        path = f"{prefix}{name}"
        if isinstance(sub, dict):
            out.update(flatten(sub, path + "/"))
        else:
            out[path] = sub
    return out


def unflatten(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for name in parents:
            node = node.setdefault(name, {})
        node[last] = leaf
    return tree


def _rule_for(path: str, rules: list) -> dict:
    for rule in rules:
        if re.search(rule["match"], path):
            return rule
    raise KeyError(f"no weight rule matches the leaf {path!r}")


def make_weights(shapes: dict, rules: list, seed: int) -> dict:
    """``shapes``: flat ``{path: object with .shape}``. Returns the flat dict
    of float32 device arrays, all drawn inside one jitted program: leaves of
    one shape are drawn together, each then scaled by its own rule."""
    groups: dict = {}
    for path in sorted(shapes):
        rule = _rule_for(path, rules)
        shape = tuple(shapes[path].shape)
        if rule.get("init", "normal") == "fan_in":
            fan_in = math.prod(shape[:-1]) or 1
            mean, std = 0.0, float(rule.get("gain", 1.0)) / math.sqrt(fan_in)
        else:
            mean, std = float(rule.get("mean", 0.0)), float(rule.get("std", 0.0))
        groups.setdefault(shape, []).append(
            (path, mean, std, tuple(rule.get("center", ()))))

    @jax.jit
    def draw(key):
        out = {}
        for g, (shape, leaves) in enumerate(sorted(groups.items())):
            noise = jax.random.normal(jax.random.fold_in(key, g),
                                      (len(leaves),) + shape, jnp.float32)
            for i, (path, mean, std, center) in enumerate(leaves):
                w = std * noise[i]
                if center:
                    w = w - w.mean(axis=center, keepdims=True)
                out[path] = mean + w
        return out

    return draw(seed_key(seed, "weights"))
