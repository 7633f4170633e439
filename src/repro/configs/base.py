"""Config system: one frozen dataclass describes every supported architecture.

Every assigned architecture gets a module in this package exporting CONFIG;
``repro.configs.get_config(arch_id)`` resolves it.  ``reduced()`` produces the
CPU-smoke variant (2 layers, d_model<=512, <=4 experts) of the same family.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass
from typing import Tuple

_VOCAB_PAD_MULTIPLE = 256


def pad_vocab(v: int, multiple: int = _VOCAB_PAD_MULTIPLE) -> int:
    """Megatron-style vocab padding so the table shards over the model axis."""
    return ((v + multiple - 1) // multiple) * multiple


@dataclass(frozen=True)
class ModelConfig:
    # identity
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    citation: str = ""
    # trunk
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 512
    vocab_size: int = 1024
    # attention
    attn_free: bool = False        # rwkv6: no attention at all
    causal: bool = True            # False for encoder-only (hubert)
    qkv_bias: bool = False         # qwen2
    sliding_window: int = 0        # >0 enables windowed attention (long ctx)
    rope_theta: float = 10_000.0
    mrope_sections: Tuple[int, ...] = ()   # qwen2-vl M-RoPE [t, h, w] halves
    # moe
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0              # per-expert FFN width (granite: 512)
    router_aux_coef: float = 0.01  # load-balance loss coefficient
    # DeepSeek-V3 routing (moonlight): sigmoid scores, top-k of score +
    # correction bias, chosen scores normalised and scaled; shared experts
    # of width n_shared_experts * moe_d_ff; the first first_k_dense layers
    # keep the dense SwiGLU of width d_ff
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    first_k_dense: int = 0
    # expert parallelism: this chip holds routed experts
    # [expert_offset, expert_offset + experts_held) of num_experts (0 = all)
    experts_held: int = 0
    expert_offset: int = 0
    # multi-head latent attention (kv_lora_rank > 0): q = W_q x with
    # head_dim (nope) + qk_rope_head_dim per head, one shared RoPE key, a
    # kv_lora_rank latent expanded to head_dim keys and v_head_dim values
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # ssm / hybrid
    ssm_state: int = 0             # mamba state size N (hymba: 16)
    ssm_expand: int = 2            # mamba inner expansion
    ssm_conv: int = 4              # mamba depthwise conv width
    # modality frontends (stub carve-out)
    num_patches: int = 0           # vlm: patch-embedding stand-ins per sample
    frontend_stub: bool = False    # audio/vlm: input_specs provides embeddings
    # numerics / misc
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"        # activation/compute dtype
    param_dtype: str = "float32"   # parameter storage dtype

    # ---- derived ----------------------------------------------------------
    @property
    def vocab_padded(self) -> int:
        return pad_vocab(self.vocab_size)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def is_encoder_only(self) -> bool:
        return not self.causal

    @property
    def is_subquadratic(self) -> bool:
        """True if decode with 500k context needs no quadratic attention."""
        return (self.attn_free or self.family in ("ssm", "hybrid")
                or self.sliding_window > 0)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def cut(self, *, layers: int = 0, experts_held: int = 0,
            expert_offset: int = 0, vocab: int = 0) -> "ModelConfig":
        """One chip's share of the model: ``layers`` layers in all
        (leading dense ones included), the routed experts
        ``[expert_offset, expert_offset + experts_held)`` and the first
        ``vocab`` rows of the vocabulary; 0 keeps the model's own.  Every
        width, the router's outputs and its experts per token stay as they
        are."""
        if vocab > self.vocab_size:
            raise ValueError(f"{self.name}: a cut keeps at most the "
                             f"{self.vocab_size} rows of the vocabulary")
        return self.replace(
            num_layers=layers or self.num_layers,
            experts_held=experts_held or self.experts_held
            or self.num_experts,
            expert_offset=expert_offset,
            vocab_size=vocab or self.vocab_size)

    def check_source(self, keys: dict) -> "ModelConfig":
        """The model, once each of its source's config.json ``keys``
        (names as ``SOURCE_KEYS`` maps them) is found to hold the model's
        own value; raises on the first that differs.  Keys the layers do
        not implement must hold the value the layers assume."""
        for k, v in keys.items():
            field = SOURCE_KEYS[k]
            if field is None:
                continue
            want = field[1] if isinstance(field, tuple) \
                else getattr(self, field)
            if v != want:
                raise ValueError(f"{self.name}: the source's {k}={v!r}, "
                                 f"the model's {want!r}")
        return self

    def with_sliding_window(self, window: int = 8192) -> "ModelConfig":
        """First-class long-context variant for dense archs (DESIGN.md §5)."""
        return self.replace(sliding_window=window)

    # ---- parameter counting (used for roofline MODEL_FLOPS = 6·N·D) ------
    def param_count(self, active_only: bool = False) -> int:
        d, L = self.d_model, self.num_layers
        n = self.vocab_padded * d                      # embeddings
        if not self.tie_embeddings:
            n += self.vocab_padded * d                 # lm head
        per_layer = 0
        if not self.attn_free:
            per_layer += d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
            if self.qkv_bias:
                per_layer += self.q_dim + 2 * self.kv_dim
        if self.family == "ssm":                       # rwkv6 mixer
            H = d // self.head_dim
            per_layer += 4 * d * d + d * d             # r,k,v,g,o
            per_layer += H * self.head_dim             # decay params (approx)
        if self.family == "hybrid" and self.ssm_state:
            di = self.d_inner
            per_layer += d * 2 * di + di * d           # in/out proj
            per_layer += di * (2 * self.ssm_state + 1) # B,C,dt projections
        if self.num_experts:
            e = self.experts_per_token if active_only else self.num_experts
            ff = self.moe_d_ff or self.d_ff
            per_layer += e * (3 * d * ff)
            per_layer += d * self.num_experts          # router
        else:
            per_layer += 3 * d * self.d_ff             # swiglu
        per_layer += 2 * d                             # norms
        n += L * per_layer + d                         # final norm
        return n

    def reduced(self) -> "ModelConfig":
        """CPU smoke variant: same family/code path, tiny dims."""
        d = min(self.d_model, 256)
        hd = 32
        sections = self.mrope_sections
        if sections:
            # rescale (t,h,w) sections to the reduced head_dim/2
            half = hd // 2
            t = max(1, half - 2 * (half * sections[1] // sum(sections)))
            hw = (half - t) // 2
            sections = (half - 2 * hw, hw, hw)
        heads = max(2, min(4, self.num_heads))
        kv = max(1, min(heads, self.num_kv_heads
                        if self.num_kv_heads < self.num_heads else heads))
        return self.replace(
            num_layers=2,
            d_model=d,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=hd,
            d_ff=min(self.d_ff, 512),
            vocab_size=min(self.vocab_size, 512),
            num_experts=min(self.num_experts, 4) if self.num_experts else 0,
            experts_per_token=min(self.experts_per_token, 2) if self.num_experts else 0,
            moe_d_ff=min(self.moe_d_ff, 128) if self.moe_d_ff else 0,
            ssm_state=min(self.ssm_state, 8) if self.ssm_state else 0,
            mrope_sections=sections,
            num_patches=min(self.num_patches, 16) if self.num_patches else 0,
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else 0,
            dtype="float32",
            param_dtype="float32",
        )


# config.json keys of a DeepSeek-V3-style source and the ModelConfig field
# each must equal; (None, value) where the layers implement that value
# alone (grouped routing, multi-token prediction, MoE every other layer),
# None for the checkpoint's own settings the layers do not read.  A
# sliced vocabulary is a cut (``ModelConfig.cut``), not a source key
SOURCE_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "moe_intermediate_size": "moe_d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "qk_nope_head_dim": "head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "kv_lora_rank": "kv_lora_rank",
    "n_routed_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "n_shared_experts": "n_shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "first_k_dense_replace": "first_k_dense",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "n_group": (None, 1),
    "topk_group": (None, 1),
    "moe_layer_freq": (None, 1),
    "num_nextn_predict_layers": (None, 0),
    "max_position_embeddings": None,
    "ep_size": None,
}


ARCH_IDS = (
    "hymba-1.5b",
    "deepseek-67b",
    "rwkv6-7b",
    "qwen2-72b",
    "qwen2-vl-2b",
    "llama4-maverick-400b-a17b",
    "llama3.2-1b",
    "llama3-405b",
    "granite-moe-3b-a800m",
    "hubert-xlarge",
)

_MODULE_FOR = {
    "hymba-1.5b": "hymba_1_5b",
    "deepseek-67b": "deepseek_67b",
    "rwkv6-7b": "rwkv6_7b",
    "qwen2-72b": "qwen2_72b",
    "qwen2-vl-2b": "qwen2_vl_2b",
    "llama4-maverick-400b-a17b": "llama4_maverick",
    "llama3.2-1b": "llama3_2_1b",
    "llama3-405b": "llama3_405b",
    "granite-moe-3b-a800m": "granite_moe_3b",
    "hubert-xlarge": "hubert_xlarge",
    "paper-cnn": "paper_cnn",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULE_FOR:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULE_FOR)}")
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[arch_id]}")
    return mod.CONFIG


def tuned_opts(cfg: ModelConfig, shape_kind: str) -> dict:
    """Per-arch production defaults distilled from the §Perf hillclimbs
    (EXPERIMENTS.md): MoE dispatch strategy is per-arch, and training runs
    dots-remat with bf16 AdamW moments (fits llama3-405b in v5e HBM with a
    −12% memory / −26% compute term vs full remat)."""
    opts: dict = {}
    if cfg.num_experts:
        # fine-grained small experts (granite: 512-wide, top-8) win with the
        # dense all-expert einsum + fused combine (124x collective cut);
        # large top-1 expert pools (llama4: 128e) need capacity scatter
        # (dense measured 100x worse there).
        ff = cfg.moe_d_ff or cfg.d_ff
        opts["moe_dispatch"] = "dense" if (ff <= 1024 and
                                           cfg.experts_per_token >= 4) else "scatter"
    if shape_kind == "train":
        opts["remat"] = "dots"
        opts["adam_bf16_moments"] = True
    return opts


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}
