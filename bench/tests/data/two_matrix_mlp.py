"""Test-only reference sizes of a decoder layer as StarCoder2 publishes it:
a two-matrix MLP (``c_fc``, ``c_proj``) and a bias on every linear.  Only
``hyper()``, for the work counts; it runs nothing."""


def hyper(conf: dict) -> dict:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    heads, kv_heads = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // heads
    q, kv = heads * hd, kv_heads * hd
    b = bool(conf["use_bias"])
    return {
        "layers": conf["num_hidden_layers"], "d_model": d, "heads": heads,
        "kv_heads": kv_heads, "head_dim": hd, "d_ff": f,
        "vocab": conf["vocab_size"], "qkv_bias": b,
        "rope_theta": float(conf["rope_theta"]),
        "norm_eps": float(conf["norm_epsilon"]),
        "tied": bool(conf["tie_word_embeddings"]),
        "linears": (("wq", d, q, b), ("wk", d, kv, b), ("wv", d, kv, b),
                    ("wo", q, d, b), ("c_fc", d, f, b), ("c_proj", f, d, b)),
    }
