"""Forward FLOPs of the backbone for one token at a given context, from
the configuration's shapes: the projections and MLP of every layer held
(2 x parameters), attention scores and values against ``ctx`` earlier
positions, and the lm head.  Used for the model FLOP utilization: work per
token delivered, whatever the tree or the proposer computed besides."""


def per_token(conf: dict, ctx: int) -> float:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    h, hkv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    layer = d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * f
    attn = 4 * h * hd * (ctx + 1)
    return conf["num_hidden_layers"] * (2 * layer + attn) \
        + 2 * d * conf["vocab_size"]


def tokens(conf: dict, start: int, n: int) -> float:
    """FLOPs of ``n`` tokens at contexts start, start + 1, ..."""
    base = per_token(conf, start)
    step = 4 * conf["num_attention_heads"] * conf["head_dim"] \
        * conf["num_hidden_layers"]
    return n * base + step * n * (n - 1) / 2
