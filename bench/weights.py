"""Seeded weights, made on the device in one jitted call, in the dtype
they are served in.

Every leaf is drawn from integer random bits (``jax.random.bits``,
uint16) mapped exactly to a uniform on (-1, 1) and scaled once, so the
values do not depend on how XLA fuses the call: the reference
(``bench/families/<family>.py``) makes any one layer again from the
same seed and gets the same numbers.  Matrices have standard deviation
1/sqrt(fan_in), the embedding and head tables 0.02, norm scales are
1 +- 0.2.  The leaves' names, shapes and dtypes are the program's
parameter layout, which the harness checks against the program's own
``eval_shape`` before it serves.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int) -> jax.Array:
    """A raw threefry key from any whole number, however large."""
    state = np.random.SeedSequence(
        [int(seed) & (2**64 - 1), int(seed) >> 64, 0x57]).generate_state(2)
    return jnp.asarray(state, jnp.uint32)


def _uniform(key, shape) -> jax.Array:
    bits = jax.random.bits(key, shape, jnp.uint16).astype(jnp.float32)
    return (bits - 32767.5) * (1.0 / 32768.0)


def _scale(path: str, shape) -> tuple:
    """(kind, amplitude): the uniform's half-width for the leaf."""
    if path.endswith("scale"):
        return "norm", 0.2
    if path.endswith("table"):
        return "matrix", 0.02 * math.sqrt(3.0)
    return "matrix", math.sqrt(3.0 / shape[-2])


def _leaf(key, path: str, shape, dtype):
    kind, a = _scale(path, shape)
    u = _uniform(key, shape)
    w = u * a + 1.0 if kind == "norm" else u * a
    return w.astype(dtype)


def _path_key(root, path: str):
    return jax.random.fold_in(root, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def layer_leaf(root, path: str, li, shape, dtype):
    """One layer's slice of a stacked leaf (``blocks/...``)."""
    return _leaf(jax.random.fold_in(_path_key(root, path), li), path,
                 shape, dtype)


def _name(kp) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def make(abstract, seed: int, n_layers: int, shardings=None):
    """Weights shaped like ``abstract`` (the program's parameter tree of
    ShapeDtypeStructs), drawn from ``seed`` in one jitted call; with
    ``shardings`` each device writes only its own shards."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def build(root):
        out = []
        for kp, sd in flat:
            path = _name(kp)
            if path.startswith("blocks/"):
                assert sd.shape[0] == n_layers, (path, sd.shape)
                leaf = jax.vmap(lambda li, p=path, s=sd: layer_leaf(
                    root, p, li, s.shape[1:], s.dtype))(jnp.arange(n_layers))
            else:
                leaf = _leaf(_path_key(root, path), path, sd.shape, sd.dtype)
            out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=shardings)(root_key(seed))


def make_layer(abstract, seed: int, li: int):
    """Layer ``li`` of every ``blocks/`` leaf, as the reference reads it:
    ``{path: array}`` without the layer axis."""
    flat, _ = jax.tree_util.tree_flatten_with_path(abstract)
    specs = tuple((_name(kp), sd.shape, sd.dtype) for kp, sd in flat
                  if _name(kp).startswith("blocks/"))
    return _jit_layer(specs)(root_key(seed), jnp.int32(li))


_LAYER_FNS: dict = {}


def _jit_layer(specs):
    if specs not in _LAYER_FNS:
        def build(root, li):
            return {p: layer_leaf(root, p, li, shape[1:], dtype)
                    for p, shape, dtype in specs}
        _LAYER_FNS[specs] = jax.jit(build)
    return _LAYER_FNS[specs]


def make_top(abstract, seed: int, path: str):
    """One leaf outside the layer stack (``embed/table``, ``head/table``,
    ``ln_f/scale``), as the reference reads it."""
    flat, _ = jax.tree_util.tree_flatten_with_path(abstract)
    sd = dict((_name(kp), sd) for kp, sd in flat)[path]
    key = (path, sd.shape, sd.dtype)
    if key not in _TOP_FNS:
        _TOP_FNS[key] = jax.jit(lambda r: _leaf(_path_key(r, path), path,
                                                sd.shape, sd.dtype))
    return _TOP_FNS[key](root_key(seed))


_TOP_FNS: dict = {}
