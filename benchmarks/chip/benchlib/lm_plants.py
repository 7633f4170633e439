"""Faults planted in a language-model cell's program, and its control, for
the readings its limits are set from (``lm_readings.py``).  Each patches
the program around set-up and window only, as ``readings.PLANTS`` does,
and is undone before the reference runs.

- ``capacity``: each held expert takes at most its mean share of the
  step's (token, slot) pairs (capacity factor 1.0), in token order; the
  pairs past it go to an expert the chip does not hold, so they add
  nothing;
- ``softmax_router``: expert affinities by softmax in place of the
  sigmoid;
- ``unchanged``: every user's contribution weighs nothing, so the round
  returns its state unchanged;
- ``bf16_master`` (the control): the master weights rounded to bfloat16
  after every SGD step.
``half_batch`` and ``altered`` are ``readings.FAULTS``'s."""
from __future__ import annotations

import contextlib

from .readings import FAULTS, _patched


@contextlib.contextmanager
def capacity(cfg: dict):
    import jax.numpy as jnp

    from repro.models import moe

    orig = moe.route_topk

    def capped(router_w, bias, mcfg, x2d):
        w, idx = orig(router_w, bias, mcfg, x2d)
        lo, hi, E = mcfg.expert_offset, \
            mcfg.expert_offset + mcfg.experts_held, mcfg.num_experts
        if hi - lo == E:
            raise ValueError("the capacity fault needs an expert the chip "
                             "does not hold")
        flat = idx.reshape(-1)
        cap = max(1, flat.size // E)
        one = flat[:, None] == jnp.arange(E)
        pos = jnp.sum(jnp.cumsum(one, axis=0) * one, axis=1) - 1
        over = (flat >= lo) & (flat < hi) & (pos >= cap)
        absent = hi if hi < E else lo - 1
        return w, jnp.where(over, absent, flat).reshape(idx.shape)

    with _patched(moe, "route_topk", capped):
        yield


@contextlib.contextmanager
def softmax_router(cfg: dict):
    import jax

    from repro.models import moe

    with _patched(moe, "router_scores",
                  lambda logits: jax.nn.softmax(logits, axis=-1)):
        yield


@contextlib.contextmanager
def unchanged(cfg: dict):
    from repro.core import schemes

    def nothing(self, params, snapshot, have_snap, arrived, **kw):
        return params, arrived.astype("float32") * 0.0

    with _patched(schemes.OptScheme, "pod_contribution", nothing):
        yield


@contextlib.contextmanager
def bf16_master(cfg: dict):
    import jax

    from repro.core import fused_round

    # reduce_precision, not a cast there and back, which XLA may drop
    def sgd(params, grads, lr):
        return jax.tree_util.tree_map(
            lambda w, g: jax.lax.reduce_precision(w - lr * g, 8, 7),
            params, grads)

    with _patched(fused_round, "sgd", sgd):
        yield


PLANTS = {"half_batch": FAULTS["half_batch"], "altered": FAULTS["altered"],
          "unchanged": unchanged, "capacity": capacity,
          "softmax_router": softmax_router, "bf16_master": bf16_master}
