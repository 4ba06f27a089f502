"""Reference of the encoder-decoder family (whisper-base's configuration).

Pre-norm blocks, all products in float32:
  encoder (frames x: (B, Se, D), no positions), per layer:
    x += Wo . attn(rms(x) Wq, rms(x) Wk, rms(x) Wv)            bidirectional
    x += swiglu(rms(x));   then enc = rms(x) with the encoder's final gain
  decoder (x = embed[tokens]), per layer:
    x += Wo . attn(rope(q), rope(k), v)                         causal
    x += Wo' . attn(rms(x) Wq', enc Wk', enc Wv')               cross, no rope
    x += swiglu(rms(x))
  loss = mean over tokens of cross-entropy of rms(x) @ head over the vocab.
The parameter tree is laid out as the program stores it (layers stacked on a
leading axis, head columns padded to ``vocab_rows``) so that one set of
seeded weights feeds both.
"""
from __future__ import annotations

from bench.reference.common import (attention, heads, merge, rms_norm, rope,
                                    scan_layers, swiglu, xent_sum)


def dims(c: dict) -> dict:
    return {"D": c["d_model"], "H": c["encoder_attention_heads"],
            "F": c["encoder_ffn_dim"], "Le": c["encoder_layers"],
            "Ld": c["decoder_layers"], "V": c["vocab_size"],
            "Vp": c["assumed"]["vocab_rows"], "frames": c["max_source_positions"],
            "eps": c["assumed"]["norm_eps"], "theta": c["assumed"]["rope_theta"]}


def param_shapes(c: dict) -> dict:
    d = dims(c)
    D, F, Vp = d["D"], d["F"], d["Vp"]

    def stack(n, names):
        return {k: (n, D, D) for k in names}

    def mlp(n):
        return {"wi": (n, D, F), "wg": (n, D, F), "wo": (n, F, D)}

    Le, Ld = d["Le"], d["Ld"]
    return {
        "embed": {"w": (Vp, D)},
        "enc_blocks": {"attn": stack(Le, ("wq", "wk", "wv", "wo")),
                       "mlp": mlp(Le), "ln1": {"w": (Le, D)},
                       "ln2": {"w": (Le, D)}},
        "enc_norm": {"w": (D,)},
        "dec_blocks": {"attn": stack(Ld, ("wq", "wk", "wv", "wo")),
                       "xattn": stack(Ld, ("wq", "wk", "wv", "wo")),
                       "mlp": mlp(Ld), "ln1": {"w": (Ld, D)},
                       "lnx": {"w": (Ld, D)}, "ln2": {"w": (Ld, D)}},
        "final_norm": {"w": (D,)},
        "lm_head": {"w": (D, Vp)},
    }


def loss_sum(p: dict, batch: dict, c: dict, ops) -> "jnp.ndarray":
    """Sum over the batch's tokens of the cross-entropy; ``p`` float32."""
    d = dims(c)
    H, eps = d["H"], d["eps"]

    def enc_layer(x, lp):
        h = rms_norm(x, lp["ln1"]["w"], eps)
        a = lp["attn"]
        o = attention(heads(ops.mm(h, a["wq"]), H), heads(ops.mm(h, a["wk"]), H),
                      heads(ops.mm(h, a["wv"]), H), False, ops)
        x = x + ops.mm(merge(o), a["wo"])
        return x + swiglu(lp["mlp"], rms_norm(x, lp["ln2"]["w"], eps), ops)

    enc = scan_layers(enc_layer, batch["frames"], p["enc_blocks"])
    enc = rms_norm(enc, p["enc_norm"]["w"], eps)

    def dec_layer(x, lp):
        h = rms_norm(x, lp["ln1"]["w"], eps)
        a = lp["attn"]
        q = rope(heads(ops.mm(h, a["wq"]), H), d["theta"])
        k = rope(heads(ops.mm(h, a["wk"]), H), d["theta"])
        o = attention(q, k, heads(ops.mm(h, a["wv"]), H), True, ops)
        x = x + ops.mm(merge(o), a["wo"])
        h = rms_norm(x, lp["lnx"]["w"], eps)
        a = lp["xattn"]
        o = attention(heads(ops.mm(h, a["wq"]), H), heads(ops.mm(enc, a["wk"]), H),
                      heads(ops.mm(enc, a["wv"]), H), False, ops)
        x = x + ops.mm(merge(o), a["wo"])
        return x + swiglu(lp["mlp"], rms_norm(x, lp["ln2"]["w"], eps), ops)

    x = scan_layers(dec_layer, p["embed"]["w"][batch["tokens"]], p["dec_blocks"])
    x = rms_norm(x, p["final_norm"]["w"], eps)
    return xent_sum(x, p["lm_head"]["w"], batch["labels"], batch["mask"],
                    d["V"], ops)


def program_fields(c: dict) -> dict:
    """The program's configuration fields that must hold the file's numbers."""
    d = dims(c)
    return {"d_model": d["D"], "num_heads": d["H"], "num_kv_heads": d["H"],
            "head_dim": d["D"] // d["H"], "d_ff": d["F"], "num_layers": d["Ld"],
            "encoder_layers": d["Le"], "encoder_seq": d["frames"],
            "vocab_size": d["V"], "padded_vocab": d["Vp"], "norm_eps": d["eps"],
            "rope_theta": d["theta"], "dtype": c["assumed"]["dtype"],
            "tie_embeddings": False}
