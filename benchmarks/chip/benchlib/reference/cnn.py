"""The paper's 5-layer CNN in plain jax.numpy at float32: conv 3x3 (1->8)
+ ReLU + 2x2 max-pool, conv 3x3 (8->16) + ReLU + 2x2 max-pool, fc
784->128 + ReLU, fc 128->64 + ReLU, fc 64->10, mean softmax cross-entropy,
plain SGD.

Every product takes the precision the configuration states
(``matmul_precision``, a ``jax.lax.Precision`` name): "default" is XLA's
default for float32, one bf16 pass with float32 accumulation on a TPU and
exact float32 on a CPU, as the program's ``f32`` policy runs."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

LAYERS = ("conv1", "conv2", "fc1", "fc2", "fc3")


def init(seed: int, side: int = 28, classes: int = 10):
    """Seeded weights: N(0, 1/fan_in) convolutions, fan-in scaled normals
    truncated at two deviations for the dense layers, zero biases."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    flat = (side // 4) ** 2 * 16

    def dense(k, i, o):
        return jax.random.truncated_normal(k, -2.0, 2.0, (i, o)) * i ** -0.5

    return {
        "conv1": {"w": jax.random.normal(ks[0], (3, 3, 1, 8)) * 9 ** -0.5,
                  "b": jnp.zeros((8,))},
        "conv2": {"w": jax.random.normal(ks[1], (3, 3, 8, 16)) * 72 ** -0.5,
                  "b": jnp.zeros((16,))},
        "fc1": {"w": dense(ks[2], flat, 128), "b": jnp.zeros((128,))},
        "fc2": {"w": dense(ks[3], 128, 64), "b": jnp.zeros((64,))},
        "fc3": {"w": dense(ks[4], 64, classes), "b": jnp.zeros((classes,))},
    }


def _conv_pool(p, x, precision):
    y = lax.conv_general_dilated(x, p["w"], (1, 1), "SAME",
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                 precision=precision)
    y = jax.nn.relu(y + p["b"])
    return lax.reduce_window(y, -jnp.inf, lax.max, (1, 2, 2, 1),
                             (1, 2, 2, 1), "VALID")


def forward(params, x, precision):
    y = _conv_pool(params["conv2"], _conv_pool(params["conv1"], x, precision),
                   precision)
    y = y.reshape(y.shape[0], -1)
    y = jax.nn.relu(jnp.dot(y, params["fc1"]["w"], precision=precision)
                    + params["fc1"]["b"])
    y = jax.nn.relu(jnp.dot(y, params["fc2"]["w"], precision=precision)
                    + params["fc2"]["b"])
    return (jnp.dot(y, params["fc3"]["w"], precision=precision)
            + params["fc3"]["b"])


def loss_acc(params, x, y, precision):
    logits = forward(params, x, precision)
    nll = jax.nn.logsumexp(logits, axis=-1) \
        - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(nll), jnp.mean(jnp.argmax(logits, -1) == y)


def _epoch(params, xs, ys, lr, precision):
    def step(p, batch):
        g = jax.grad(lambda q: loss_acc(q, *batch, precision)[0])(p)
        return jax.tree_util.tree_map(lambda w, d: w - lr * d, p, g), None
    return lax.scan(step, params, (xs, ys))[0]


@partial(jax.jit, static_argnames="precision")
def epoch_users(params, xs, ys, lr, precision):
    """One local epoch for a stack of users: params leaves (K, ...),
    xs (K, steps, bs, 28, 28, 1), ys (K, steps, bs)."""
    return jax.vmap(partial(_epoch, precision=precision),
                    in_axes=(0, 0, 0, None))(params, xs, ys, lr)


evaluate = jax.jit(loss_acc, static_argnames="precision")


def param_count(params) -> int:
    return sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
