"""Compile the main path's kernels for a described TPU v5e, with no chip.

The TPU compiler is installed beside the CPU backend, and it compiles for
a topology that is described rather than attached
(``jax.experimental.topologies``).  Interpret mode hides what it refuses:
a slice off the (8, 128) tiling, a bf16 comparison the v5e's vector unit
lacks, a batched 3-D broadcast that aborted the compiler.  So every
kernel of the main path is compiled here at the paper's shapes (K=10
selected users, batch 10, 4 steps per epoch) and each compile that
should hold a Pallas kernel is checked for its ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a test worker that
decided at import whether these tests exist would collect a different
suite from its siblings.  The persistent compilation cache is off around
the compiles: an entry written for a described chip cannot be read back
without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.delta_codec.kernel import (BLOCK, dequantize_blocks,
                                              quantize_blocks)
from repro.kernels.fused_cnn import ForwardPolicy, make_stacked_epoch_fn
from repro.kernels.fused_cnn import kernel as knl
from repro.models.cnn import init_cnn

K, B, STEPS = 10, 10, 4                 # the paper's Sec. IV point
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    if old_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


def _on(sharding, tree, dtype=None):
    """Shape structs of ``tree`` placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype,
                                       sharding=sharding), tree)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _epoch_args(sharding):
    stacked = jax.eval_shape(
        lambda: jax.vmap(init_cnn)(jax.random.split(jax.random.PRNGKey(0),
                                                    K)))
    xs = jax.ShapeDtypeStruct((K, STEPS, B, 28, 28, 1), jnp.float32)
    ys = jax.ShapeDtypeStruct((K, STEPS, B), jnp.int32)
    return _on(sharding, (stacked, xs, ys))


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_stacked_epoch_compiles(one_chip, kernel, precision):
    """One local epoch of the whole cohort, as the fused round runs it."""
    policy = ForwardPolicy(kernel=kernel, precision=precision)
    hlo = _compile(make_stacked_epoch_fn(policy, 0.01),
                   *_epoch_args(one_chip))
    # per SGD step: conv1/conv2 forward, the fc chain both ways, and the
    # conv2 and conv1 backward — six kernels in the scanned step
    assert hlo.count("tpu_custom_call") == (6 if kernel == "pallas" else 0)


def _top_level_lines(hlo: str):
    """Instruction lines of every computation that is not a fusion's body:
    the ops the chip runs one by one."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    name = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
        elif name not in fused:
            yield line


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_stacked_epoch_folds_without_scatter_add(one_chip, precision):
    """conv2's input gradient folds the patch cotangent as one fusion: no
    op of the compiled epoch is a ``dynamic-update-slice`` from a
    scatter-add (the form that ran as nine padded ops per step)."""
    hlo = _compile(make_stacked_epoch_fn(ForwardPolicy(precision=precision),
                                         0.01), *_epoch_args(one_chip))
    dus = [l for l in _top_level_lines(hlo)
           if "dynamic-update-slice(" in l and "scatter-add" in l]
    assert not dus, dus[:2]


def test_quantize_blocks_compiles(one_chip):
    x = _on(one_chip, jax.ShapeDtypeStruct((216, BLOCK), jnp.float32))
    hlo = _compile(lambda a: quantize_blocks(a), x)
    assert hlo.count("tpu_custom_call") == 1


def test_dequantize_blocks_compiles(one_chip):
    q = _on(one_chip, jax.ShapeDtypeStruct((216, BLOCK), jnp.int8))
    s = _on(one_chip, jax.ShapeDtypeStruct((216, 1), jnp.float32))
    hlo = _compile(lambda a, b: dequantize_blocks(a, b), q, s)
    assert hlo.count("tpu_custom_call") == 1


def _conv_case(layer, direction, dt):
    h, c, o = (28, 1, 8) if layer == "conv1" else (14, 8, 16)
    x = jax.ShapeDtypeStruct((K, B, h, h, c), dt)
    w = jax.ShapeDtypeStruct((K, 3, 3, c, o), dt)
    b = jax.ShapeDtypeStruct((K, o), dt)
    if direction == "fwd":
        return (lambda x, w, b: knl.conv_pool_fwd_k(x, w, b)), (x, w, b)
    _, res = jax.eval_shape(knl.conv_pool_fwd_k, x, w, b)
    da = jax.ShapeDtypeStruct((K, B, h // 2, h // 2, o), dt)
    need_dx = layer == "conv2"           # conv1's image gradient is unused
    return ((lambda r, w, da: knl.conv_pool_bwd_k(r, w, da, need_dx)),
            (res, w, da))


def _fc_case(direction, dt):
    dims = ((784, 128), (128, 64), (64, 10))
    params = {f"fc{i + 1}": {"w": jax.ShapeDtypeStruct((K, m, n), dt),
                             "b": jax.ShapeDtypeStruct((K, n), dt)}
              for i, (m, n) in enumerate(dims)}
    flat = jax.ShapeDtypeStruct((K, B, 784), dt)
    if direction == "fwd":
        return (lambda f, p: knl.fc_chain_fwd_k(f, p)), (flat, params)
    h1 = jax.ShapeDtypeStruct((K, B, 128), dt)
    h2 = jax.ShapeDtypeStruct((K, B, 64), dt)
    g = jax.ShapeDtypeStruct((K, B, 10), dt)
    return ((lambda f, a, b, p, g: knl.fc_chain_bwd_k(f, (a, b), p, g)),
            (flat, h1, h2, params, g))


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("name", ["conv1_fwd", "conv1_bwd", "conv2_fwd",
                                  "conv2_bwd", "fc_fwd", "fc_bwd"])
def test_pallas_kernel_compiles(one_chip, name, precision):
    layer, direction = name.split("_")
    dt = DTYPES[precision]
    fn, args = (_fc_case(direction, dt) if layer == "fc"
                else _conv_case(layer, direction, dt))
    hlo = _compile(fn, *_on(one_chip, args))
    assert hlo.count("tpu_custom_call") == 1
