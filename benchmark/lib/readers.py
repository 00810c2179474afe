"""Readers: how a metric is taken from what a run saw. A metric's data
file (``end_to_end/<name>.json`` or ``layer_metrics/<name>.json``) names
one of these and gives its parameters. A reader that finds nothing to
read returns None, and the harness leaves that metric out of the line.

``ctx`` holds: ``series``, ``scalars`` (the loop's, plus ``setup_s`` and
``memory_peak_bytes``), ``device_ops``/``host_spans`` (a traced run's),
``busy_s``, ``sizes`` (the arch's counts), ``peaks``, ``chips``,
``traffic``, ``config``.
"""

from . import flops, stats, trace
from .registry import reader


def _total(ctx, keys):
    if isinstance(keys, str):
        keys = [keys]
    if any(k not in ctx["scalars"] for k in keys):
        return None
    return sum(ctx["scalars"][k] for k in keys)


@reader("scalar")
def scalar(ctx, key, scale=1.0):
    v = ctx["scalars"].get(key)
    return None if v is None else v * scale


@reader("percentile")
def percentile(ctx, series, q):
    return stats.percentile(ctx["series"].get(series, []), q)


@reader("rate")
def rate(ctx, num, den):
    """All the work of the window over all its time."""
    n, d = _total(ctx, num), _total(ctx, den)
    if n is None or not d:
        return None
    return stats.rate([n], d)


@reader("ratio")
def ratio(ctx, num, den, scale=1.0):
    n, d = _total(ctx, num), _total(ctx, den)
    if n is None or not d:
        return None
    return scale * n / d


@reader("train_mfu")
def train_mfu(ctx):
    """Model FLOP/s utilisation: required forward + backward FLOPs a
    token, times tokens a second, over chips times the published peak.
    A utilisation of the model, not a kernel's roofline share."""
    tok_s = rate(ctx, "tokens", "window_s")
    if tok_s is None:
        return None
    per_token = flops.train_flops_per_token(ctx["sizes"],
                                            ctx["traffic"]["seq_len"])
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * tok_s * per_token / peak


@reader("device_idle")
def device_idle(ctx):
    if ctx.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["scalars"]["window_s"])


@reader("flash_roofline")
def flash_roofline(ctx):
    """The flash-attention kernels' share of their roofline: the least
    time the chip could take for the causal forward + backward of the
    window's steps (the larger of FLOPs over peak FLOP/s and bytes over
    peak bytes/s), over the device time of the step's Pallas custom
    calls of attention shape (an operand [.., seq, head_dim])."""
    if not ctx.get("device_ops"):
        return None
    sizes, t = ctx["sizes"], ctx["traffic"]
    seq, d = t["seq_len"], sizes["head_dim"]
    kernel_s = trace.pallas_seconds(
        ctx["device_ops"],
        lambda ops: any(dims[-2:] == [seq, d] for dims in ops))
    if not kernel_s:
        return None
    steps = ctx["scalars"]["steps"]
    chips = ctx["chips"]
    need_flops = steps * flops.flash_train_flops(sizes, t["batch"], seq)
    need_bytes = steps * flops.flash_train_bytes(sizes, t["batch"], seq)
    by_flops = need_flops / chips / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = need_bytes / chips / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["notes"]["flash_bound"] = ("compute" if by_flops >= by_bytes
                                   else "memory")
    return 100.0 * max(by_flops, by_bytes) / kernel_s


@reader("decode_floor")
def decode_floor(ctx):
    """The decode steps' byte floor over the device's busy time: each
    decode step must read the weights once and the live KV cache once
    (the lengths are the benchmark's own count), at the published HBM
    bandwidth. The busy time includes the window's prefill work, so the
    share is a little low where prefill is not negligible."""
    if not ctx.get("busy_s"):
        return None
    s = ctx["scalars"]
    steps = s.get("serving_decode_steps")
    if not steps:
        return None
    need = (steps * flops.weight_bytes(ctx["sizes"])
            + s["live_tokens_sum"] * flops.kv_bytes_per_token(ctx["sizes"]))
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / ctx["busy_s"]


@reader("collective_exposed")
def collective_exposed(ctx):
    """Collective time during which nothing else runs on the device,
    over the window."""
    if not ctx.get("device_ops"):
        return None
    exposed = trace.collective_exposed_seconds(ctx["device_ops"])
    return 100.0 * exposed / ctx["scalars"]["window_s"]
