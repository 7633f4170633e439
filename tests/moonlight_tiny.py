"""A tiny Moonlight-16B-A3B (DeepSeek-V3) for the CPU tests: a dense layer
and 2 MoE layers, d = 64, 8 routed experts of which 4 are held (ids 2-5),
vocabulary 256, compute in float32 unless a test asks for bfloat16."""
import jax
import numpy as np

from repro.configs import get_config
from repro.models import moonlight

TINY = dict(num_layers=3, d_model=64, num_heads=4, head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, d_ff=128,
            moe_d_ff=32, num_experts=8, experts_per_token=3,
            experts_held=4, expert_offset=2, vocab_size=256,
            dtype="float32")

# the same widths as the config.json keys an ``HSFLConfig`` names
TINY_KEYS = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                 intermediate_size=128, moe_intermediate_size=32,
                 n_routed_experts=8, num_experts_per_tok=3, vocab_size=256)


def tiny(**kw):
    return get_config("moonlight-16b-a3b").replace(**{**TINY, **kw})


def register_tiny(monkeypatch, name, keys):
    """Name, for ``repro.configs.get_config``, the registry's model at the
    widths of the config.json ``keys`` (its vocabulary too), as a source
    of a tiny size would record them."""
    import repro.configs as configs
    from repro.configs.base import SOURCE_KEYS

    real = configs.get_config
    kw = {SOURCE_KEYS[k]: v for k, v in keys.items()
          if isinstance(SOURCE_KEYS.get(k), str)}
    if "vocab_size" in keys:
        kw["vocab_size"] = keys["vocab_size"]
    cfg = real("moonlight-16b-a3b").replace(name=name, **kw)
    monkeypatch.setattr(configs, "get_config",
                        lambda a: cfg if a == name else real(a))
    return cfg


def ref_config(cfg):
    """The reference's config.json-style dict of a ModelConfig."""
    return {"hidden_size": cfg.d_model,
            "num_attention_heads": cfg.num_heads,
            "qk_nope_head_dim": cfg.head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "kv_lora_rank": cfg.kv_lora_rank,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "n_routed_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.experts_per_token,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "first_k_dense_replace": cfg.first_k_dense,
            "layers": cfg.num_layers, "experts_held": cfg.experts_held,
            "expert_offset": cfg.expert_offset}


def params(cfg, seed=0):
    """Seeded weights of ``moonlight.init``'s tree, drawn with numpy (the
    jitted initialiser takes seconds to compile on a CPU): fan-in scaled
    normals, norm scales 1 +- 0.1."""
    rng = np.random.default_rng(seed)
    shapes = jax.eval_shape(lambda: moonlight.init(jax.random.PRNGKey(0),
                                                   cfg))

    def draw(path, s):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return (1.0 + 0.1 * rng.standard_normal(s.shape)).astype(
                np.float32)
        fan_in = s.shape[-1] if "table" in name else s.shape[-2]
        return (rng.standard_normal(s.shape) * fan_in ** -0.5).astype(
            np.float32)

    return jax.tree_util.tree_map_with_path(draw, shapes)
