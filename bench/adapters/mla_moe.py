"""The latent-attention + expert family (DeepSeek-V2) on
``repro.serve.ServeEngine``: a configuration file's published keys turned
into the program's ``ArchConfig``, the expert share it holds included."""

from __future__ import annotations

#: published settings the program computes exactly as given; any other
#: value names a mechanism the program does not have
FIXED = {
    "q_lora_rank": None,
    "scoring_func": "softmax",
    "topk_method": "greedy",
    "n_group": 1,
    "topk_group": 1,
    "routed_scaling_factor": 1,
    "moe_layer_freq": 1,
    "hidden_act": "silu",
    "attention_bias": False,
    "tie_word_embeddings": False,
}


def arch_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YarnScaling

    for key, want in FIXED.items():
        if config[key] != want:
            raise ValueError(f"{config['name']}: {key}={config[key]!r}; the "
                             f"program computes {key}={want!r} only")
    y = config["rope_scaling"]
    if y["type"] != "yarn":
        raise ValueError(f"{config['name']}: rope_scaling {y['type']!r}")
    published = config.get("published", {})
    return ArchConfig(
        name=config["name"],
        family="moe",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        d_head=config["v_head_dim"],
        moe=MoEConfig(
            n_experts=published.get("n_routed_experts",
                                    config["n_routed_experts"]),
            top_k=config["num_experts_per_tok"],
            d_expert=config["moe_intermediate_size"],
            n_shared=config["n_shared_experts"],
            norm_topk_prob=config["norm_topk_prob"],
            experts_first=config.get("experts_first", 0),
            n_held=config["n_routed_experts"],
        ),
        mla=MLAConfig(
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
        ),
        first_k_dense=config["first_k_dense_replace"],
        rope_theta=float(config["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(y["factor"]),
            original_max_position_embeddings=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]),
            beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"]),
        ),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=False,
        param_dtype=config["dtypes"]["param"],
        compute_dtype=config["dtypes"]["compute"],
    )


def _attn(w: dict, prefix: str) -> dict:
    return {k: w[prefix + k] for k in
            ("wq", "w_dkv", "kv_norm", "w_uk", "w_uv", "w_kr", "wo")}


def program_params(w: dict, cfg) -> dict:
    """The benchmark's weights (``reference.mla_moe.layout`` names) as the
    program's parameter tree; no array is copied."""
    from repro.models import lm

    dense, experts = lm.groups_of(cfg)
    return {
        "embed": {"embedding": w["embed"], "lm_head": w["lm_head"]},
        "blocks": {
            dense.key: {
                "ln1": w["d_ln1"], "attn": _attn(w, "d_"), "ln2": w["d_ln2"],
                "mlp": {"gate": w["d_w_gate"], "up": w["d_w_up"],
                        "down": w["d_w_down"]},
            },
            experts.key: {
                "ln1": w["ln1"], "attn": _attn(w, ""), "ln2": w["ln2"],
                "moe": {
                    "router": w["router"],
                    "w_gate": w["e_gate"], "w_up": w["e_up"],
                    "w_down": w["e_down"],
                    "shared": {"gate": w["s_gate"], "up": w["s_up"],
                               "down": w["s_down"]},
                },
            },
        },
        "final_norm": w["final_norm"],
    }


def engine(config: dict, cfg, params, seed: int):
    """A ``ServeEngine`` through its public constructor, sized by the
    configuration's ``engine`` section."""
    from repro.serve import ServeEngine

    e = config["engine"]
    return ServeEngine(
        cfg,
        params=params,
        n_slots=e["n_slots"],
        max_len=e["max_len"],
        page_size=e["page_size"],
        n_pages=e["n_pages"],
        prefill_bucket=e["prefill_bucket"],
        decode_impl=e["decode_impl"],
        seed=seed,
    )
