"""Reference of the dense decoder family (StableLM-3B-4E1T's configuration).

Pre-norm blocks, all products in float32, x = embed[tokens], per layer:
    x += Wo . attn(rope(rms(x) Wq), rope(rms(x) Wk), rms(x) Wv)   causal
    x += swiglu(rms(x))
loss = mean over tokens of cross-entropy of rms(x) @ head over the vocab.
Rotary embedding turns every pair of each head's dimensions.  The tree is
laid out as the program stores it (layers stacked, head padded to
``vocab_rows``).
"""
from __future__ import annotations

from bench.reference.common import (attention, heads, merge, rms_norm, rope,
                                    scan_layers, swiglu, xent_sum)


def dims(c: dict) -> dict:
    return {"D": c["hidden_size"], "H": c["num_attention_heads"],
            "KV": c["num_key_value_heads"], "F": c["intermediate_size"],
            "L": c["num_hidden_layers"], "V": c["vocab_size"],
            "Vp": c["assumed"]["vocab_rows"],
            "eps": c["assumed"]["norm_eps"], "theta": c["assumed"]["rope_theta"]}


def param_shapes(c: dict) -> dict:
    d = dims(c)
    D, F, L, Vp = d["D"], d["F"], d["L"], d["Vp"]
    if d["KV"] != d["H"]:
        raise ValueError("the decoder reference covers multi-head attention")
    return {
        "embed": {"w": (Vp, D)},
        "final_norm": {"w": (D,)},
        "blocks": {"attn": {k: (L, D, D) for k in ("wq", "wk", "wv", "wo")},
                   "ln1": {"w": (L, D)}, "ln2": {"w": (L, D)},
                   "mlp": {"wi": (L, D, F), "wg": (L, D, F), "wo": (L, F, D)}},
        "lm_head": {"w": (D, Vp)},
    }


def loss_sum(p: dict, batch: dict, c: dict, ops) -> "jnp.ndarray":
    d = dims(c)
    H, eps = d["H"], d["eps"]

    def layer(x, lp):
        h = rms_norm(x, lp["ln1"]["w"], eps)
        a = lp["attn"]
        q = rope(heads(ops.mm(h, a["wq"]), H), d["theta"])
        k = rope(heads(ops.mm(h, a["wk"]), H), d["theta"])
        o = attention(q, k, heads(ops.mm(h, a["wv"]), H), True, ops)
        x = x + ops.mm(merge(o), a["wo"])
        return x + swiglu(lp["mlp"], rms_norm(x, lp["ln2"]["w"], eps), ops)

    x = scan_layers(layer, p["embed"]["w"][batch["tokens"]], p["blocks"])
    x = rms_norm(x, p["final_norm"]["w"], eps)
    return xent_sum(x, p["lm_head"]["w"], batch["labels"], batch["mask"],
                    d["V"], ops)


def program_fields(c: dict) -> dict:
    """The program's configuration fields that must hold the file's numbers."""
    d = dims(c)
    return {"d_model": d["D"], "num_heads": d["H"], "num_kv_heads": d["KV"],
            "head_dim": d["D"] // d["H"], "d_ff": d["F"], "num_layers": d["L"],
            "vocab_size": d["V"], "padded_vocab": d["Vp"], "norm_eps": d["eps"],
            "rope_theta": d["theta"], "dtype": c["assumed"]["dtype"],
            "tie_embeddings": c["tie_word_embeddings"], "family": "dense",
            "sliding_window": 0, "moe": None}
