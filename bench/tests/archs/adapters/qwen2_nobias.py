"""Test architecture: the program's configuration of a Qwen2 layer
without q, k and v biases."""


def program_config(cfg: dict):
    from repro.configs.base import ModelConfig

    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"], head_dim=d // h,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        attn_bias=False, rope_theta=float(cfg["rope_theta"]),
        rmsnorm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"], source=cfg["source"])
