"""The only place the benchmark builds the program's own objects from a
configuration file."""
from __future__ import annotations

from dataclasses import fields


def hsfl_config(cfg: dict, seed: int):
    """``HSFLConfig`` holding every program field of ``cfg``."""
    from repro.core.channel_lib import ChannelParams
    from repro.core.hsfl import HSFLConfig

    names = {f.name for f in fields(HSFLConfig)}
    kw = {k: v for k, v in cfg.items() if k in names and v is not None}
    kw["channel"] = ChannelParams(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in cfg["channel"].items()})
    for k in ("flops_range", "schedule_override"):
        kw[k] = tuple(kw[k])
    return HSFLConfig(seed=seed, **kw)
