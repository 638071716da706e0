"""The dense decoder family on ``repro.serve.ServeEngine``."""

from __future__ import annotations


def arch_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=config["name"],
        family="dense",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        d_head=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config.get("rms_norm_eps", config.get("layer_norm_eps"))),
        tie_embeddings=config["tie_word_embeddings"],
        param_dtype=config["dtypes"]["param"],
        compute_dtype=config["dtypes"]["compute"],
    )


def program_params(w: dict, cfg) -> dict:
    """The benchmark's weights (``reference.dense.layout`` names) as the
    program's parameter tree; no array is copied."""
    from repro.models import lm

    (group,) = lm.groups_of(cfg)
    embed = {"embedding": w["embed"]}
    if not cfg.tie_embeddings:
        embed["lm_head"] = w["lm_head"]
    block = {
        "ln1": w["ln1"],
        "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]},
        "ln2": w["ln2"],
        "mlp": {"gate": w["w_gate"], "up": w["w_up"], "down": w["w_down"]},
    }
    return {
        "embed": embed,
        "blocks": {group.key: block},
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
