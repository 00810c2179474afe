"""TPU tile/VMEM geometry shared by the fused-decode kernel, the
memwatch planner, and the kernelcheck lint (r18).

One module, three consumers, zero duplicated formulas:

- ``paddle_tpu.kernels.fused_block_decode`` imports :func:`tile` and
  :data:`LANES` (its block tiling is derived HERE, not locally);
- ``paddle_tpu.observability.memory.plan_fused_layers`` prices the
  N-layer kernel's VMEM working set by walking the template tables
  below via :func:`price_fused_decode`;
- ``paddle_tpu.analysis.kernelcheck`` (KRN002) compares the scratch
  geometry it *extracts from the kernel source* against the SAME
  templates, so the planner and the lint can never disagree: drift the
  kernel's scratch list and the lint fires; drift a template and the
  planner/lint-agreement test fires.

Deliberately dependency-free (stdlib only): the lint and the standalone
``tools/`` loaders must import this without jax installed.

Hardware constants (TPU v4/v5 class, see the accelerator guide):
vector registers are (sublane, lane) = (8, 128) f32 tiles; narrower
dtypes pack more sublanes per tile (16 for bf16, 32 for int8); VMEM is
16 MB per core and Mosaic double-buffers every *streamed* block operand
(the next grid step's block DMAs while the current one computes).
"""

from __future__ import annotations

from typing import Dict, Mapping, Sequence, Tuple

LANES = 128                       # lane count: minor-most tile dim
VMEM_LIMIT_BYTES = 16 << 20       # per-core VMEM bound
DOUBLE_BUFFER = 2                 # Mosaic's streamed-operand buffering

# minor-to-second ("sublane") tile multiple per element width
SUBLANES: Dict[str, int] = {
    "float32": 8, "f32": 8, "int32": 8, "uint32": 8,
    "bfloat16": 16, "bf16": 16, "float16": 16, "f16": 16,
    "int8": 32, "uint8": 32, "float8_e4m3fn": 32, "float8_e5m2": 32,
}

DTYPE_BYTES: Dict[str, int] = {
    "float32": 4, "f32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "bf16": 2, "float16": 2, "f16": 2,
    "int8": 1, "uint8": 1, "float8_e4m3fn": 1, "float8_e5m2": 1,
}


def tile(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is <= target, preferring multiples
    of 128 (lane tiles); falls back to any divisor so odd dims stay
    correct (just less efficient)."""
    if n <= target:
        return n
    for cand in range(target - target % 128, 0, -128):
        if n % cand == 0:
            return cand
    for cand in range(min(target, n), 0, -1):
        if n % cand == 0:
            return cand
    return n


def sublane_multiple(dtype_name: str) -> int:
    """Required second-minor tile multiple for a dtype ('' unknown -> 0,
    meaning: no static claim)."""
    return SUBLANES.get(dtype_name.rsplit(".", 1)[-1], 0)


# --------------------------------------------------------- templates
# Symbolic shape templates of ``fused_multi_block_decode_pallas``.
# Every entry is a tuple of symbol names resolved against the dict
# :func:`fused_decode_env` builds; integer literals spell themselves.
# KRN002 normalizes the shapes it extracts from the kernel source to
# exactly these symbol spellings before comparing.

# streamed block operands (double-buffered by Mosaic)
FUSED_DECODE_WEIGHT_STREAM: Tuple[Tuple[str, ...], ...] = (
    ("1", "hidden"),            # ln1
    ("1", "hidden"),            # ln2
    ("tr_h", "tc_qkv"),         # wqkv tile
    ("tr_o", "tc_o"),           # wo tile
    ("tr_h", "tc_f"),           # wgu gate tile
    ("tr_h", "tc_f"),           # wgu up tile
    ("tr_i", "tc_d"),           # wd tile
)
# const-mapped activation in/out blocks (still double-buffered)
FUSED_DECODE_ACTIVATION_IO: Tuple[Tuple[str, ...], ...] = (
    ("b_pad", "hidden"),        # x in
    ("b_pad", "hidden"),        # out
    ("b_pad", "d"),             # sin
    ("b_pad", "d"),             # cos
    ("kv_heads", "b_pad", "d"),  # k_new
    ("kv_heads", "b_pad", "d"),  # v_new
)
# per-layer K/V page blocks (2 operands per grouped layer — the only
# term that scales with the fused-layer count N)
FUSED_DECODE_KV_BLOCK: Tuple[Tuple[str, ...], ...] = (
    ("1", "1", "page_size", "d"),
    ("1", "1", "page_size", "d"),
)
# persistent f32 VMEM scratch of the N-layer kernel — the multiset
# KRN002 checks the extracted ``scratch_shapes`` against
FUSED_DECODE_SCRATCH: Tuple[Tuple[str, ...], ...] = (
    ("b_pad", "hidden"),        # x carry
    ("b_pad", "hidden"),        # h (normed)
    ("qkv_heads", "b_pad", "d"),  # merged q|k|v, head-major
    ("heads", "b_pad", "d"),    # attn out, head-major
    ("b_pad", "hidden"),        # x2 (residual)
    ("b_pad", "inter"),         # silu(g)*u
    ("b_pad", "tc_max"),        # acc a
    ("b_pad", "tc_max"),        # acc b
    ("rep_rows", "d"),          # attn acc
    ("rep_rows", "LANES"),      # attn m
    ("rep_rows", "LANES"),      # attn l
)
# the single-layer kernel's scratch (``fused_block_decode_pallas``):
# same carries plus split q/k/v projections instead of the merged one
FUSED_DECODE_SINGLE_SCRATCH: Tuple[Tuple[str, ...], ...] = (
    ("b_pad", "hidden"),        # h (normed)
    ("heads", "b_pad", "d"),    # q, head-major
    ("kv_heads", "b_pad", "d"),  # k_new
    ("kv_heads", "b_pad", "d"),  # v_new
    ("heads", "b_pad", "d"),    # attn out
    ("b_pad", "hidden"),        # x2 (residual)
    ("b_pad", "inter"),         # silu(g)*u
    ("b_pad", "tc_max"),        # acc a
    ("b_pad", "tc_max"),        # acc b
    ("rep_rows", "d"),          # attn acc
    ("rep_rows", "LANES"),      # attn m
    ("rep_rows", "LANES"),      # attn l
)


def fused_decode_env(*, hidden: int, intermediate: int, heads: int,
                     kv_heads: int, head_dim: int, batch: int = 8,
                     page_size: int = 64) -> Dict[str, int]:
    """The symbol environment both the kernel and the planner tile
    from: every template symbol above resolves against this dict."""
    d = int(head_dim)
    rep = int(heads) // int(kv_heads)
    qw = int(heads) * d
    kvw = int(kv_heads) * d
    wq_cols = qw + 2 * kvw
    return {
        "hidden": int(hidden), "inter": int(intermediate), "d": d,
        "qw": qw, "kvw": kvw, "wq_cols": wq_cols,
        "heads": int(heads), "kv_heads": int(kv_heads),
        "qkv_heads": int(heads) + 2 * int(kv_heads),
        "b_pad": -(-int(batch) // 8) * 8,
        # phase A's softmax state: rep query heads x the slot's 8-row
        # sublane window
        "rep_rows": rep * 8,
        "tr_h": tile(int(hidden), 512),
        "tr_o": tile(qw, 512),
        "tr_i": tile(int(intermediate), 512),
        "tc_qkv": tile(wq_cols, 256),
        "tc_o": tile(int(hidden), 256),
        "tc_f": tile(int(intermediate), 256),
        "tc_d": tile(int(hidden), 256),
        "page_size": int(page_size),
        "LANES": LANES,
    }


def _finish_env(env: Dict[str, int]) -> Dict[str, int]:
    env = dict(env)
    env["tc_max"] = max(env["tc_qkv"], env["tc_o"], env["tc_f"],
                        env["tc_d"])
    return env


def template_elems(shapes: Sequence[Tuple[str, ...]],
                   env: Mapping[str, int]) -> int:
    """Total element count of a template table under ``env``."""
    total = 0
    for shape in shapes:
        n = 1
        for sym in shape:
            n *= int(sym) if sym.isdigit() else env[sym]
        total += n
    return total


def price_fused_decode(env: Mapping[str, int], *, fused_layers: int,
                       io_dtype_bytes: int = 2,
                       vmem_limit: int = VMEM_LIMIT_BYTES
                       ) -> Dict[str, int]:
    """Price the N-layer fused decode kernel's VMEM working set from
    the templates.  Streamed blocks (weights, activations, the
    per-layer page blocks) pay the Mosaic double-buffer factor at the
    streamed storage width; scratch is persistent f32."""
    n = int(fused_layers)
    if n < 1:
        raise ValueError(f"fused_layers must be >= 1, got {n}")
    env = _finish_env(dict(env))
    io = int(io_dtype_bytes)
    weight_stream = DOUBLE_BUFFER * io * template_elems(
        FUSED_DECODE_WEIGHT_STREAM, env)
    activation_io = DOUBLE_BUFFER * io * template_elems(
        FUSED_DECODE_ACTIVATION_IO, env)
    kv_page = DOUBLE_BUFFER * io * n * template_elems(
        FUSED_DECODE_KV_BLOCK, env)
    scratch = DTYPE_BYTES["float32"] * template_elems(
        FUSED_DECODE_SCRATCH, env)
    total = weight_stream + activation_io + kv_page + scratch
    return {
        "weight_stream_buffers": weight_stream,
        "activation_io_buffers": activation_io,
        "kv_page_buffers": kv_page,
        "scratch": scratch,
        "total": int(total),
        "vmem_limit": int(vmem_limit),
        "fits": total <= int(vmem_limit),
        "headroom_bytes": int(vmem_limit) - int(total),
    }
