"""The program's ``ArchConfig`` for an MLA mixture-of-experts configuration
file (DeepSeek-V3 layout): latent attention with YaRN RoPE and its own value
head width, a dense prefix of ``first_k_dense_replace`` layers, then layers
whose FFN is a chip's share of the routed experts and the shared experts.

``n_routed_experts`` is the count of experts held here, from
``held_expert_offset``; ``router_experts`` is the count the router spans.
The trained correction bias is not published; the random draw that stands
in for it is N(0, std^2), std from ``assumed.e_score_correction_bias_std``.
"""

from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YaRN


def _yarn(rs):
    if not rs or rs.get("type") != "yarn":
        return None
    return YaRN(factor=float(rs["factor"]),
                original_max_position=int(rs["original_max_position_embeddings"]),
                beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
                mscale=float(rs["mscale"]),
                mscale_all_dim=float(rs["mscale_all_dim"]))


def arch_config(conf: dict) -> ArchConfig:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return ArchConfig(
        name=conf.get("model_type", "model"), family="moe",
        num_layers=conf["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf["v_head_dim"], rope_theta=float(conf["rope_theta"]),
        norm_eps=conf["rms_norm_eps"],
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)),
        mla=MLAConfig(
            q_lora_rank=conf["q_lora_rank"],
            kv_lora_rank=conf["kv_lora_rank"],
            qk_nope_head_dim=conf["qk_nope_head_dim"],
            qk_rope_head_dim=conf["qk_rope_head_dim"],
            v_head_dim=conf["v_head_dim"],
            yarn=_yarn(conf.get("rope_scaling"))),
        moe=MoEConfig(
            num_experts=conf["router_experts"],
            top_k=conf["num_experts_per_tok"],
            d_ff_expert=conf["moe_intermediate_size"],
            num_held=conf["n_routed_experts"],
            expert_offset=conf.get("held_expert_offset", 0),
            n_shared=conf["n_shared_experts"],
            scoring=conf["scoring_func"],
            n_group=conf["n_group"], topk_group=conf["topk_group"],
            routed_scale=float(conf["routed_scaling_factor"]),
            first_k_dense=conf["first_k_dense_replace"],
            bias_std=float(conf["assumed"]["e_score_correction_bias_std"])))
