"""Readings that the limits are set from: the compared numbers of sound
runs of the program over many seeds, of the control (the program's own
bf16 path, one precision below the f32 policy the configuration states),
and of faults planted in the program under test.

Training's readings need no measured window: each reading builds the
cell's set-up, drives its first rounds and runs the reference.  The
control and the faults are planted around set-up and window only (the
harness's ``plant``), by switching the program's path or patching its
functions, and are undone before the reference runs."""
from __future__ import annotations

import contextlib
from types import SimpleNamespace

import numpy as np

from . import harness


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def half_batch(cfg: dict):
    """Half of each batch left out, the mean taken over the rest: the
    second half of every batch repeats the first, so the batch mean is the
    mean over the first half."""
    from repro.core import hsfl

    def cut(idx):
        idx = np.array(idx)
        h = idx.shape[-1] // 2
        idx[..., h:2 * h] = idx[..., :h]
        return idx

    orig = hsfl._epoch_indices
    with _patched(hsfl, "_epoch_indices",
                  lambda n, cfg, rng: cut(orig(n, cfg, rng))):
        yield


@contextlib.contextmanager
def unchanged(cfg: dict):
    """A round that returns its state unchanged."""
    from repro.core import schemes

    def keep(self, params, *a, **k):
        import jax.numpy as jnp
        return params, jnp.zeros_like(a[3])

    with _patched(schemes.OptScheme, "aggregate", keep), \
            _patched(schemes.Scheme, "aggregate", keep):
        yield


@contextlib.contextmanager
def altered(cfg: dict):
    """An answer altered where it is produced: the first scheduled user's
    final upload never counts as arrived."""
    import jax.numpy as jnp

    from repro.core import schemes

    def slack(self, tau_extra0):
        first = jnp.arange(tau_extra0.shape[0]) == 0
        return tau_extra0 * 0.0 + jnp.where(first, jnp.inf, 0.0)

    with _patched(schemes.Scheme, "final_slack", slack):
        yield


@contextlib.contextmanager
def bf16(cfg: dict):
    """The control: the program's own bf16 path in place of the f32 policy
    the configuration states."""
    old = cfg["precision"]
    cfg["precision"] = "bf16"
    try:
        yield
    finally:
        cfg["precision"] = old


FAULTS = {"half_batch": half_batch, "unchanged": unchanged,
          "altered": altered}
PLANTS = {"bf16": bf16, **FAULTS}


def reading(workload: str, seed: int, variant: str = "program",
            require_tpu: bool = True, detail: dict | None = None) -> dict:
    """The compared numbers of one run of ``workload``'s set-up and check
    under ``variant``: "program", a control or a fault."""
    from .spans import Spans

    bench = harness.load_benchmark()
    c = harness.cell(bench, workload)
    harness.enable_cache()
    devs = harness.devices(c["chips"], require_tpu)
    traffic = harness.load_traffic(c["traffic"])
    drv = harness.engine(traffic["engine"])
    cfg = harness.load_config(c["config"])
    ctx = SimpleNamespace(config=cfg, traffic=traffic,
                          seed=harness.program_seed(seed), spans=Spans(),
                          devices=devs, chips=c["chips"], workload=workload)
    with PLANTS.get(variant, lambda cfg: contextlib.nullcontext())(cfg):
        st = drv.setup(ctx, warm=False)
    drv.free(st)
    nums = drv.check(st, workload, detail=detail is not None)
    if detail is not None:
        detail.update(getattr(st, "detail", {}))
    return nums
