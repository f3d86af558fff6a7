"""Seeded random Deep Potential weights, made on the device in one call.

The recipe follows DeePMD's initialisation, as the program's ``init_dp_params``
does: every linear layer has weights ~ N(0, 1 / (d_in + d_out)) and biases
~ N(0, 0.01). On top of it the configuration's ``weights.head_scale``
multiplies the fitting net's linear head, which scales every energy and
force by the same factor. Random weights put nothing that repels atoms at
short range, so a large head drives an NVE run away; the scale in each
configuration file is the one that its evidence (``assumed.weights``) shows
to keep the cell's runs finite and energy-conserving. ``dstd`` (the
environment-matrix scale) comes from the first frame, as DeePMD takes it
from data, and ``ebias`` is zero.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.reference import model_key
from bench.systems import seed_words


def _linear(key, d_in: int, d_out: int, scale: float = 1.0):
    kw, kb = jax.random.split(key)
    std = 1.0 / jnp.sqrt(float(d_in + d_out))
    return {"w": jax.random.normal(kw, (d_in, d_out), jnp.float32) * std * scale,
            "b": jax.random.normal(kb, (d_out,), jnp.float32) * 0.1}


def _mlp(key, widths, d_in: int):
    layers = []
    for k, w in zip(jax.random.split(key, len(widths)), widths):
        layers.append(_linear(k, d_in, int(w)))
        d_in = int(w)
    return layers


@functools.partial(jax.jit, static_argnames=("model_key", "head_scale"))
def _make(key, dstd, *, model_key, head_scale):
    model = dict(model_key)
    ntypes = int(model["ntypes"])
    k_embed, k_fit = jax.random.split(key)
    embed = {str(t): _mlp(k, model["embed_widths"], 1)
             for t, k in enumerate(jax.random.split(k_embed, ntypes))}
    d_desc = int(model["axis_neuron"]) * int(model["embed_widths"][-1])
    fit = {}
    for t, k in enumerate(jax.random.split(k_fit, ntypes)):
        k_hidden, k_head = jax.random.split(k)
        fit[str(t)] = {
            "hidden": _mlp(k_hidden, model["fit_widths"], d_desc),
            "head": _linear(k_head, int(model["fit_widths"][-1]), 1,
                            head_scale)}
    return {"embed": embed, "fit": fit, "dstd": dstd,
            "ebias": jnp.zeros((ntypes,), jnp.float32)}


def make_params(seed: int, model: Dict[str, Any], head_scale: float,
                dstd) -> Dict[str, Any]:
    """The float32 parameter pytree of ``model`` for ``seed``, on the
    default device."""
    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return _make(key, jnp.asarray(dstd, jnp.float32),
                 model_key=model_key(model), head_scale=float(head_scale))
