"""The program's model configuration, built from a configuration file of
``bench/configs`` (the sizes as they are run) and nothing else."""
from __future__ import annotations


def program_config(conf: dict):
    from repro.configs.base import ModelConfig
    s = conf["serving"]
    return ModelConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        act=conf["hidden_act"], norm="rmsnorm", rope_theta=conf["rope_theta"],
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["torch_dtype"], param_dtype=conf["torch_dtype"],
        cache_layout=s["cache_layout"], verify_fusion=s["verify_fusion"],
        spec_mode="tree")
