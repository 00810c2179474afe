"""Operations and bytes the algorithms REQUIRE, computed from shapes.

These are the yardstick's own counts: required work only (recomputed
operations do not count), causal attention counted as the half it is.
A share computed from them can therefore not pass 100% unless the time
leaves out part of the work.
"""

from .registry import arch


@arch("gpt2")
def gpt2_sizes(c: dict) -> dict:
    """GPT-2/Megatron layout: fused qkv, 4h MLP, learned positions, tied
    embedding used as the head matmul."""
    h, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    inter = c.get("intermediate_size") or 4 * h
    per_layer = 4 * h * h + 2 * h * inter
    # biases of qkv (3h), out (h), fc_in (inter), fc_out (h); two LayerNorms
    small = 3 * h + h + inter + h + 4 * h
    head = V * h
    n_params = (L * (per_layer + small) + V * h
                + c["max_position_embeddings"] * h + 2 * h)
    return dict(matmul_params=L * per_layer + head, n_params=n_params,
                layers=L, hidden=h, heads=c["num_attention_heads"],
                kv_heads=c["num_attention_heads"],
                head_dim=h // c["num_attention_heads"])


@arch("llama")
def llama_sizes(c: dict) -> dict:
    """Llama/Mistral layout: GQA, SwiGLU, RMSNorm, rotary, untied head
    (the input embedding is a gather, not a matmul)."""
    h, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    heads = c["num_attention_heads"]
    kv_heads = c.get("num_key_value_heads") or heads
    d = c.get("head_dim") or h // heads
    inter = c["intermediate_size"]
    per_layer = 2 * h * heads * d + 2 * h * kv_heads * d + 3 * h * inter
    tied = bool(c.get("tie_word_embeddings", False))
    n_params = L * (per_layer + 2 * h) + V * h * (1 if tied else 2) + h
    return dict(matmul_params=L * per_layer + V * h, n_params=n_params,
                layers=L, hidden=h, heads=heads, kv_heads=kv_heads,
                head_dim=d)


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    """Forward + backward of a dense causal decoder: 6 FLOPs per matmul
    parameter, and causal attention's two matmuls (QK^T, PV), each
    2*s*d per head and token over the full square, halved by the mask,
    times three for forward and backward."""
    attn = 6.0 * sizes["layers"] * sizes["heads"] * sizes["head_dim"] * seq_len
    return 6.0 * sizes["matmul_params"] + attn


def flash_train_flops(sizes: dict, batch: int, seq_len: int) -> float:
    """Causal flash attention, forward + backward, of ONE step over all
    layers: seven required matmuls a head (QK^T, PV forward; QK^T again,
    dP, dV, dQ, dK backward), each s*s*d multiply-adds halved by the
    mask, i.e. s*s*d FLOPs."""
    per_head = 7.0 * seq_len * seq_len * sizes["head_dim"]
    return per_head * batch * sizes["heads"] * sizes["layers"]


def flash_train_bytes(sizes: dict, batch: int, seq_len: int,
                      itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q, k, v and writes
    o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    q = batch * sizes["heads"] * seq_len * sizes["head_dim"] * itemsize
    kv = batch * sizes["kv_heads"] * seq_len * sizes["head_dim"] * itemsize
    return sizes["layers"] * ((2 * q + 2 * kv) + (5 * q + 4 * kv))


def weight_bytes(sizes: dict, itemsize: int = 2) -> float:
    return float(sizes["n_params"]) * itemsize


def kv_bytes_per_token(sizes: dict, itemsize: int = 2) -> float:
    """K and V of one cached token over all layers."""
    return 2.0 * sizes["layers"] * sizes["kv_heads"] * sizes["head_dim"] \
        * itemsize
