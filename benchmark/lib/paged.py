"""Readers of the decode paged-attention kernel: its share of the
roofline and of the device's busy time. The work is counted from the
engine's own counter and the head's own width, whatever implements it:
a faster kernel, a denser pool or fewer idle rows all raise the share.

Imports nothing of the program. On a checkout whose trace holds no op of
that name, or whose engine lacks the counter, a reader returns None.
"""

from . import flops, trace
from .registry import reader

KERNEL = "paged_attention"


def _kernel_seconds(ctx):
    """Device seconds of the ops whose instruction name holds the decode
    kernel's (``paged_chunk_attention`` does not hold it), averaged over
    the devices; None without a trace or without such an op."""
    ops = ctx.get("device_ops")
    if not ops:
        return None
    total = sum(dur for dev in ops.values() for text, _, dur in dev
                if KERNEL in trace.parse_hlo(text)[0])
    return total / len(ops) / 1e9 or None


@reader("paged_attn_roofline")
def paged_attn_roofline(ctx):
    """The kernel is bound by memory: the least time is the cached K and
    V of the rows that DECODE (``serving_decode_live_tokens`` x bytes a
    token over the layers that have pages, at the head's own width) over
    the published HBM bandwidth; over the kernel's device time. Rows the
    rung computes without decoding, and lanes a padded pool moves, are
    time and not work: both pull the share down."""
    kernel_s = _kernel_seconds(ctx)
    live = ctx["scalars"].get("serving_decode_live_tokens")
    if not kernel_s or not live:
        return None
    need = live * flops.kv_bytes_per_token(ctx["sizes"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / kernel_s


@reader("paged_attn_share")
def paged_attn_share(ctx):
    kernel_s = _kernel_seconds(ctx)
    if not kernel_s or not ctx.get("busy_s"):
        return None
    return 100.0 * kernel_s / ctx["busy_s"]
