"""A stand-in reference of a latent-attention MoE decoder, for the tests.

It has what the harness asks of a reference (``refs/__init__.py``) but the
computation: sizes read from DeepSeek-V2's ``config.json`` keys, leaves with
latent attention's ``kv_norm``, program fields with nested ``moe`` and
``mla`` groups, a FLOP count of its own, and attention at ``d_qk != d_v``.
The tests put it in ``catalog.reference``'s place, to show that such a
configuration needs new files only.
"""

from __future__ import annotations

from dataclasses import dataclass

from benchmarks.chip import flops


@dataclass(frozen=True)
class Sizes:
    d_model: int
    n_heads: int
    q_lora: int
    kv_lora: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    d_ff_expert: int
    n_experts: int  # held on this chip
    top_k: int
    n_shared: int
    vocab: int
    n_layers: int
    rope_theta: float

    @classmethod
    def from_config(cls, c: dict) -> "Sizes":
        return cls(
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            q_lora=c["q_lora_rank"], kv_lora=c["kv_lora_rank"],
            qk_nope=c["qk_nope_head_dim"], qk_rope=c["qk_rope_head_dim"],
            v_dim=c["v_head_dim"], d_ff_expert=c["moe_intermediate_size"],
            n_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
            n_shared=c["n_shared_experts"], vocab=c["vocab_size"],
            n_layers=c["num_hidden_layers"], rope_theta=float(c["rope_theta"]))

    @property
    def d_qk(self) -> int:
        return self.qk_nope + self.qk_rope


def layout(s: Sizes) -> dict:
    D, H, L, E, F = s.d_model, s.n_heads, s.n_layers, s.n_experts, s.d_ff_expert
    layer = {
        "ln1": (D,), "attn/wq_a": (D, s.q_lora), "attn/q_norm": (s.q_lora,),
        "attn/wq_b": (s.q_lora, H * s.d_qk), "attn/wkv_a": (D, s.kv_lora + s.qk_rope),
        "attn/kv_norm": (s.kv_lora,), "attn/wkv_b": (s.kv_lora, H * (s.qk_nope + s.v_dim)),
        "attn/wo": (H * s.v_dim, D), "ln2": (D,), "moe/router": (D, E),
        "moe/gate": (E, D, F), "moe/up": (E, D, F), "moe/down": (E, F, D),
    }
    out = {"embed": (s.vocab, D), "final_norm": (D,), "head": (D, s.vocab)}
    out.update({f"groups/{k}": (L,) + v for k, v in layer.items()})
    return out


def program_fields(s: Sizes) -> dict:
    return {"n_layers": s.n_layers, "d_model": s.d_model, "n_heads": s.n_heads,
            "n_kv_heads": s.n_heads, "d_ff": s.d_ff_expert, "vocab_size": s.vocab,
            "head_dim": s.v_dim, "rope_theta": s.rope_theta,
            "moe": {"n_experts": s.n_experts, "top_k": s.top_k, "d_ff_expert": s.d_ff_expert,
                    "n_shared": s.n_shared},
            "mla": {"kv_lora": s.kv_lora, "q_lora": s.q_lora, "qk_nope": s.qk_nope,
                    "qk_rope": s.qk_rope, "v_dim": s.v_dim}}


def attention_dims(s: Sizes) -> tuple:
    return s.n_heads, s.n_heads, s.d_qk, s.v_dim


def train_flops_per_token(s: Sizes, seq: int) -> float:
    """6 per weight a token multiplies (the router, ``top_k`` routed experts
    and the shared ones), plus causal attention at ``d_qk`` and ``d_v``."""
    D, H = s.d_model, s.n_heads
    attn = (D * s.q_lora + s.q_lora * H * s.d_qk + D * (s.kv_lora + s.qk_rope)
            + s.kv_lora * H * (s.qk_nope + s.v_dim) + H * s.v_dim * D)
    experts = D * s.n_experts + 3 * D * s.d_ff_expert * (s.top_k + s.n_shared)
    pairs = 2 * H * (s.d_qk + s.v_dim) * flops.causal_pairs(seq) / seq
    return 6 * (s.n_layers * (attn + experts) + D * s.vocab) + 3 * s.n_layers * pairs
