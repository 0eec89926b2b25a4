"""The system under test's model object, built from a configuration file.

Everything here is by dotted name from ``configs/<name>.json``: no model's
name appears in code. The returned object is the program's own model class
with ``init`` replaced by the benchmark's seeded weights (``weights.make``).
"""

import importlib
import json
import os

from . import weights

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def merge(base, over):
    """Deep-merge ``over`` into a copy of ``base`` (dicts merge, rest replace)."""
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def by_name(dotted):
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def build(config, overrides=None):
    """(model, dims): the program's model for ``config`` (a loaded
    configuration file) whose ``init(rng)`` is ``weights.make``."""
    prog, dims = config["program"], config["dims"]
    cfg = by_name(prog["config"])(**{**prog["kwargs"], **(overrides or {})})
    cls = by_name(prog["model"])
    positions = cfg.n_positions
    multiple = cfg.pad_vocab_to_multiple

    class Seeded(cls):
        def init(self, rng):
            return weights.make(dims, rng, positions=positions,
                                vocab_multiple=multiple)

    Seeded.__name__ = cls.__name__
    return Seeded(cfg), dims


def seeded_weights(model, dims, seed, dtype=None):
    """The same parameters the engine made from ``engine_seed(seed)``, made
    again by the benchmark for the reference (optionally cast to the type
    they are served in). One jitted call; the key is an argument."""
    import jax
    cfg = model.config

    @jax.jit
    def make(key):
        w = weights.make(dims, key, positions=cfg.n_positions,
                         vocab_multiple=cfg.pad_vocab_to_multiple)
        return w if dtype is None else jax.tree.map(
            lambda a: a.astype(dtype), w)
    return make(weights.seed_key(seed))
