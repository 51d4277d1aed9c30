"""Work counted from shapes: model FLOPs of a training step, the
coordinates one aggregation reduces, the bytes the MM aggregation needs,
and the chip's peaks.  Nothing here reads the program."""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a device that
    is not in the table is an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def padded_vocab(cfg: dict) -> int:
    """Rows of the embedding and columns of the head as the program
    stores them: the vocabulary padded to a multiple of 256."""
    return -(-cfg["vocab_size"] // 256) * 256


def layer_params(cfg: dict) -> int:
    """Parameters of one decoder layer: attention (q, k, v, o and the
    q/k norms), the SwiGLU MLP and the two RMSNorm weights."""
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, ff = cfg["head_dim"], cfg["intermediate_size"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d + 2 * hd
    return attn + 3 * d * ff + 2 * d


def train_flops(cfg: dict, tokens: int, seq_len: int) -> float:
    """Model FLOPs of one step: 6 x (non-embedding + head parameters) x
    tokens for the matmuls, plus 12 x L x T x S x H x hd x 0.5 for
    causal attention scores and values.  Recomputation is not counted."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    head = d * cfg["vocab_size"]
    n = L * layer_params(cfg) + d + head
    attn = 12 * L * tokens * seq_len * cfg["num_attention_heads"] \
        * cfg["head_dim"] * 0.5
    return 6.0 * n * tokens + attn


def aggregated_coords(cfg: dict) -> int:
    """Coordinates one gradient aggregation reduces: every parameter the
    program holds (embedding and untied head at the padded vocabulary,
    the layers, the final norm)."""
    d = cfg["hidden_size"]
    v = padded_vocab(cfg)
    emb = v * d * (1 if cfg["tie_word_embeddings"] else 2)
    return emb + cfg["num_hidden_layers"] * layer_params(cfg) + d


def mm_bytes(k: int, m: int, n_out: int = 1, itemsize: int = F32,
             weighted: bool = False) -> int:
    """HBM bytes one MM aggregation needs: one read of the (k, m) stack,
    the (k, n_out) weights when given, one write of the (n_out, m)
    result."""
    return k * m * itemsize + (k * n_out * F32 if weighted else 0) \
        + n_out * m * itemsize
