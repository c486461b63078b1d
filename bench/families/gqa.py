"""The program's ``ArchConfig`` for a GQA configuration file (Qwen3 layout).

A family file maps a configuration file's keys onto the program's own
configuration; ``bench/program.py`` finds it by the file's ``family``.
"""

from repro.configs.base import ArchConfig


def arch_config(conf: dict) -> ArchConfig:
    return ArchConfig(
        name=conf.get("model_type", "model"), family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf["head_dim"], rope_theta=conf["rope_theta"],
        norm_eps=conf["rms_norm_eps"],
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)))
