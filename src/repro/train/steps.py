"""Train / serve step builders for the LM zoo.

``make_train_step`` returns a pure (state, batch) -> (state, metrics)
function suitable for jit with FSDP in/out shardings; gradients flow
through bf16 compute against f32 master params, reduction order is left to
GSPMD (reduce-scatter under FSDP).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.models.losses import chunked_softmax_cross_entropy
from repro.models.zoo import ModelAPI
from repro.sharding.ctx import constrain
from repro.train.optim import AdamW, AdamWState


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState
    step: jax.Array


def init_train_state(api: ModelAPI, opt: AdamW, key: jax.Array) -> TrainState:
    # JAX's default partitionable threefry keeps the random init
    # sharding-invariant: FSDP and single-device runs train the same model
    # from step 0 (tests/distributed/run_lm_dist.py checks it bit-exact).
    params = api.init(key)
    return TrainState(params=params, opt=opt.init(params),
                      step=jnp.zeros((), jnp.int32))


def make_train_step(api: ModelAPI, opt: AdamW, aux_weight: float = 0.001,
                    loss_chunk: int = 512) -> Callable:
    cfg = api.cfg

    def loss_fn(params, batch):
        kw = {}
        if "frames" in batch:
            kw["frames"] = batch["frames"]
        if "embeds" in batch:
            hidden, aux = api.forward(params, embeds=batch["embeds"],
                                      return_hidden=True, **kw)
        else:
            hidden, aux = api.forward(params, tokens=batch["tokens"],
                                      return_hidden=True, **kw)
        # Loss runs seq-unsharded (hidden is only (B, S, d)); logits are
        # chunked so the (B, S, V) tensor never materializes.
        hidden = constrain(hidden, "batch", None, None)
        ce = chunked_softmax_cross_entropy(
            hidden, api.logits_fn(params), batch["labels"],
            batch.get("mask", None), chunk=loss_chunk)
        return ce + aux_weight * aux, (ce, aux)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        (loss, (ce, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, batch)
        new_params, new_opt, gnorm = opt.update(grads, state.opt, state.params)
        metrics = {"loss": loss, "ce": ce, "moe_aux": aux, "grad_norm": gnorm}
        return TrainState(params=new_params, opt=new_opt,
                          step=state.step + 1), metrics

    return train_step


def make_serve_step(api: ModelAPI) -> Callable:
    """One-token decode step: (params, tokens (B,1), cache) -> (logits, cache)."""

    def serve_step(params, tokens, cache):
        return api.decode_step(params, tokens, cache)

    return serve_step
