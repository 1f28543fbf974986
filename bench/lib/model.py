"""A configuration's architecture, its sizes, and its weights drawn from a
seed.

An architecture is a unit of files, named by the configuration file's
``reference`` key and found by path under the benchmark's root, so that
a configuration of a new architecture is added with new files alone:

``bench/arch/<name>.py``
    ``load_shape(config, name)``: a frozen, hashable shape (the plain
    reference is jitted with it static) that carries at least
    :data:`SHAPE_FIELDS`, the fields the harness reads on any
    architecture.
    ``weight_shapes(shape)``: ``(shapes, norms)``, the canonical weight
    names with their shapes, and the names that are norm scales.
    ``program_config(shape)`` and ``program_params(weights)``: the
    program's ``ModelConfig`` and its parameter tree of the canonical
    weights; the program is imported inside them, never at the top.
    The cost terms, which count only the work the served tokens need:
    ``matmul_params(shape)``; ``forward_gemms(shape, rows, head_rows)``,
    the (M, K, N) of every GEMM of one forward over ``rows`` token rows
    of which ``head_rows`` need logits (an expert layer lists the rows
    each expert serves); ``attention_flops(shape, keys)`` over ``keys``
    attended keys; and ``paged_decode_cost(shape, contexts)``, the
    (operations, bytes) of one decode step's paged attention over the
    live ``contexts`` (latent attention counts the latent bytes a key
    keeps).
``bench/reference/<name>.py``
    ``logits(weights, tokens, shape, int8=False)``: the plain reference
    over the canonical weights, importing nothing of the program.

The weights are canonical: one dict of stacked arrays that the plain
reference reads under its own names and that the architecture hands to
the program in its layout.  They are drawn on the device in one jitted
call, in the dtype the checkpoint is published in.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

SHAPE_FIELDS = ("name", "layers", "vocab", "dtype", "init_std", "reference")


def module(root: Path, kind: str, name: str):
    """``bench/<kind>/<name>.py`` under ``root``, loaded once per file.
    It is registered in ``sys.modules`` under a name of its path, so
    that a shape's class leads back to its module."""
    path = (Path(root) / "bench" / kind / f"{name}.py").resolve()
    key = (f"bench_{kind}_{name.replace('.', '_')}_"
           f"{hashlib.sha1(str(path).encode()).hexdigest()[:12]}")
    if key in sys.modules:
        return sys.modules[key]
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r}: {path} is "
                                f"missing")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


def load_shape(root: Path, path: Path, name: str):
    """The shape of the configuration file ``path``, by its
    architecture's module under ``root``; an architecture or reference
    that is not there fails here, before any weight is drawn."""
    c = json.loads(Path(path).read_text())
    arch = module(root, "arch", c["reference"])
    module(root, "reference", c["reference"])
    s = arch.load_shape(c, name)
    missing = [f for f in SHAPE_FIELDS if not hasattr(s, f)]
    if missing:
        raise TypeError(f"{arch.__file__}: its shape lacks {missing}")
    return s


def arch_of(s):
    """The architecture module that made the shape ``s``."""
    return sys.modules[type(s).__module__]


def reference_of(s):
    """The plain reference of ``s``, from the root its architecture
    module was found under."""
    return module(Path(arch_of(s).__file__).parents[2], "reference",
                  s.reference)


def _key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative whole-number seed."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_weights(s, seed: int) -> dict:
    """Every weight, drawn from ``seed`` on the default device in one
    jitted call: matrices N(0, init_std), norm scales 1 + 0.1 N(0, 1)."""
    shapes, norms = arch_of(s).weight_shapes(s)
    dtype = jnp.dtype(s.dtype)

    def draw(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            scale = z * 0.1 + 1.0 if name in norms else z * s.init_std
            out[name] = scale.astype(dtype)
        return out

    return jax.jit(draw)(_key(seed))
