"""Weights of a decoder, drawn on the device from the run's seed.

``make_params`` builds the whole served tree in one jitted program, in the
served dtype and in the program's layout (stacked layers).  ``layer`` and
``top`` draw the same values again, one layer at a time, so that the
reference can rebuild them after the served copy is freed.  Every value is
a function of (seed, leaf, layer) alone: the same seed gives the same
weights in either form.

The leaves, their shapes and scales are the family's (``LAYER``, ``TOP``,
``layer_shapes``, ``top_shapes`` of ``bench/reference/<family>.py``, found
by the ``model`` block's ``reference``).  A leaf's key folds in its index in
the family's table.  Scales keep a 40-layer random stack well conditioned:
unit-variance embeddings, 1/sqrt(fan_in) projections, norms and biases drawn
away from their trivial values (1 and 0) so that a wrong or missing one
shows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import family


def _draw(key, spec, shape, dtype):
    """``spec``: (name, init, scale); "normal" draws scale * N(0, 1),
    "around_one" 1 + scale * N(0, 1).  A scale of None is 1/sqrt(fan_in),
    the fan-in of a matrix or a bank of them ``[..., in, out]`` being
    ``shape[-2]``."""
    _, init, scale = spec
    if scale is None:
        scale = shape[-2] ** -0.5
    x = jax.random.normal(key, shape, jnp.float32) * scale
    if init == "around_one":
        x = 1.0 + x
    return x.astype(dtype)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for name, x in flat.items():
        *path, leaf = name.split(".")
        node = out
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def _draw_all(key, specs, shapes, dtype) -> dict:
    return _nest({
        spec[0]: _draw(jax.random.fold_in(key, i), spec, shapes[spec[0]], dtype)
        for i, spec in enumerate(specs) if spec[0] in shapes
    })


def root_key(seed: int):
    """A key for any whole number up to 2**63: ``jax.random.key`` keeps only
    the low 32 bits, so the high ones are folded in."""
    return _root(jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF))


@jax.jit
def _root(lo, hi):
    return jax.random.key_data(jax.random.fold_in(jax.random.key(lo), hi))


def _key(data):
    return jax.random.wrap_key_data(data)


def layer(m: dict, key_data, index: int, dtype):
    """One layer's weights, nested as the program holds them."""
    return _layer(_freeze(m), key_data, jnp.uint32(index), jnp.dtype(dtype))


def top(m: dict, key_data, vocab_rows: int, dtype):
    return _top(_freeze(m), key_data, vocab_rows, jnp.dtype(dtype))


def make_params(m: dict, key_data, vocab_rows: int, dtype):
    """The whole served tree in one program: layers drawn one at a time
    (``lax.map``), so no leaf is ever held whole in float32."""
    return _params(_freeze(m), key_data, vocab_rows, jnp.dtype(dtype))


def _freeze(m: dict):
    return tuple(sorted(m.items()))


def _layer_body(mf, key_data, index, dtype):
    m = dict(mf)
    fam = family(m)
    key = jax.random.fold_in(jax.random.fold_in(_key(key_data), 1), index)
    return _draw_all(key, fam.LAYER, fam.layer_shapes(m), dtype)


def _top_body(mf, key_data, vocab_rows, dtype):
    m = dict(mf)
    fam = family(m)
    key = jax.random.fold_in(_key(key_data), 0)
    return _draw_all(key, fam.TOP, fam.top_shapes(m, vocab_rows), dtype)


_layer = jax.jit(_layer_body, static_argnums=(0, 3))
_top = jax.jit(_top_body, static_argnums=(0, 2, 3))


def _params_body(mf, key_data, vocab_rows, dtype):
    n = dict(mf)["n_layers"]
    tree = _top_body(mf, key_data, vocab_rows, dtype)
    tree["layers"] = jax.lax.map(
        lambda i: _layer_body(mf, key_data, i, dtype), jnp.arange(n, dtype=jnp.uint32))
    return tree


_params = jax.jit(_params_body, static_argnums=(0, 2, 3))
