"""The program's ``ArchConfig`` for an MLA configuration file (MiniCPM3
layout): latent attention, a dense MLP.

A family file maps a configuration file's keys onto the program's own
configuration; ``bench/program.py`` finds it by the file's ``family``. The
value head is ``hidden_size / num_attention_heads`` wide, as is the
program's ``head_dim``.
"""

from repro.configs.base import ArchConfig, MLAConfig


def arch_config(conf: dict) -> ArchConfig:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return ArchConfig(
        name=conf.get("model_type", "model"), family="dense",
        num_layers=conf["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=d // h, rope_theta=conf["rope_theta"],
        norm_eps=conf["rms_norm_eps"],
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)),
        mla=MLAConfig(
            q_lora_rank=conf["q_lora_rank"],
            kv_lora_rank=conf["kv_lora_rank"],
            qk_nope_head_dim=conf["qk_nope_head_dim"],
            qk_rope_head_dim=conf["qk_rope_head_dim"], v_head_dim=d // h))
