"""A configuration file's published sizes, as the yardstick reads them.

Nothing here imports the program: the reference, the weights, the costs
and the check of the program's own config all start from this.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Spec:
    name: str
    arch: str
    family: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str            # "rms" | "layernorm"
    eps: float
    rope_theta: float
    rot_dim: int         # rotary features per head (even)
    tied: bool

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one position over all layers, in bf16."""
        return 2 * self.layers * self.kv_heads * self.head_dim * 2


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def spec_of(conf: dict) -> Spec:
    p = conf["published"]
    head_dim = p.get("head_dim") or p["hidden_size"] // p["num_attention_heads"]
    norm = p["norm"]
    eps = p["rms_norm_eps"] if norm == "rms" else p["norm_eps"]
    rot = int(head_dim * p.get("partial_rotary_factor", 1.0)) & ~1
    return Spec(name=conf["name"], arch=conf["arch"], family=conf["family"],
                layers=p["num_hidden_layers"], d_model=p["hidden_size"],
                heads=p["num_attention_heads"],
                kv_heads=p["num_key_value_heads"], head_dim=head_dim,
                d_ff=p["intermediate_size"], vocab=p["vocab_size"],
                norm=norm, eps=eps, rope_theta=p["rope_theta"],
                rot_dim=rot, tied=bool(p["tie_word_embeddings"]))


def program_mismatches(spec: Spec, arch_cfg) -> list[str]:
    """Where the program's ``ArchConfig`` departs from the published
    sizes.  A run on such a config is no run of the configuration."""
    want = {"n_layers": spec.layers, "d_model": spec.d_model,
            "n_heads": spec.heads, "n_kv_heads": spec.kv_heads,
            "resolved_head_dim": spec.head_dim, "d_ff": spec.d_ff,
            "vocab": spec.vocab, "tie_embeddings": spec.tied,
            "norm": "rms" if spec.norm == "rms" else "ln"}
    out = [f"{k}: program {getattr(arch_cfg, k)!r}, published {v!r}"
           for k, v in want.items() if getattr(arch_cfg, k) != v]
    rot = int(arch_cfg.resolved_head_dim * arch_cfg.rope_pct) & ~1
    if rot != spec.rot_dim:
        out.append(f"rotary features: program {rot}, published "
                   f"{spec.rot_dim}")
    return out
