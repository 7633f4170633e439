"""Operations a language-model training step needs, counted from shapes
(DeepSeek-V3 blocks: MLA, dense SwiGLU, sigmoid router, shared and
routed experts, untied head), with the configuration file's config.json
keys.  One multiply-add is two operations; a training step costs three
forward passes.  Causal attention is counted over the S(S+1)/2 (query,
key) pairs of each sequence; the routed experts by the (token, slot)
pairs actually routed to the experts this chip holds."""
from __future__ import annotations


def forward_flops(c: dict, batch: int, seq: int) -> int:
    """Forward operations of one step of ``batch`` sequences of ``seq``
    tokens, all but the routed experts."""
    d, H, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    T = batch * seq
    dense = c["first_k_dense_replace"]
    moe = c["layers"] - dense
    proj = d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d
    pairs = batch * seq * (seq + 1) // 2
    attn = 2 * T * proj + 2 * pairs * H * (dn + dr + dv)
    swiglu = 3 * d
    per_moe = (2 * T * d * c["n_routed_experts"]
               + 2 * T * swiglu * c["n_shared_experts"]
               * c["moe_intermediate_size"])
    return (c["layers"] * attn + dense * 2 * T * swiglu
            * c["intermediate_size"] + moe * per_moe
            + 2 * T * d * c["vocab_size"])


def expert_forward_flops(c: dict, rows: int) -> int:
    """Forward operations of the held experts over ``rows`` routed (token,
    slot) pairs."""
    return 2 * rows * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def train_flops(c: dict, steps: int, batch: int, seq: int,
                routed: int) -> int:
    """``steps`` training steps whose held experts took ``routed`` pairs
    in all."""
    return 3 * (steps * forward_flops(c, batch, seq)
                + expert_forward_flops(c, routed))
