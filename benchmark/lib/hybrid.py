"""A decoder with recurrent (state-space) layers beside attention layers,
served: how its parameters, cached bytes and recurrent state are
counted (``@arch``), how the system under test is put together
(``@builder``), and the readers of what the recurrent-state store and
the state-update kernel add to the program.

Imports nothing of the program at module level: ``registry.load_all()``
imports this file for every cell, also on a checkout that has no such
model. There a reader finds no counter, gauge or kernel to read and
returns None.
"""

import math

from . import flops, trace
from .registry import arch, builder, reader

KERNEL = "ssm_decode_update"


# ------------------------------------------------------------- the counts
@arch("granite_hybrid")
def granite_hybrid_sizes(c: dict) -> dict:
    """``granitemoehybrid`` with no routed experts: per layer a Mamba-2
    mixer or GQA attention, then a SwiGLU MLP at
    ``shared_intermediate_size``; tied embedding. ``layers`` / ``kv_heads``
    / ``head_dim`` describe the layers that HAVE pages (the attention
    layers), so ``flops.kv_bytes_per_token`` counts pages that exist."""
    h, V = c["hidden_size"], c["vocab_size"]
    heads, kv_heads = c["num_attention_heads"], c["num_key_value_heads"]
    d = h // heads
    n_heads, p = c["mamba_n_heads"], c["mamba_d_head"]
    n, g, k = c["mamba_d_state"], c["mamba_n_groups"], c["mamba_d_conv"]
    d_inner = n_heads * p
    channels = d_inner + 2 * g * n
    mlp = 3 * h * c["shared_intermediate_size"]
    attention = 2 * h * d * (heads + kv_heads)
    mamba_matmul = h * (d_inner + channels + n_heads) + d_inner * h
    # convolution weight and bias, dt_bias, A_log, D, the gated norm
    mamba_small = channels * (k + 1) + 3 * n_heads + d_inner
    n_attention = sum(t == "attention" for t in c["layer_types"])
    n_mamba = len(c["layer_types"]) - n_attention
    state = n_heads * p * n * 4                  # float32
    window = (k - 1) * channels * 2              # bf16
    return dict(
        matmul_params=(n_attention * attention + n_mamba * mamba_matmul
                       + len(c["layer_types"]) * mlp + V * h),
        n_params=(n_attention * attention
                  + n_mamba * (mamba_matmul + mamba_small)
                  + len(c["layer_types"]) * (mlp + 2 * h) + V * h + h),
        layers=n_attention, hidden=h, heads=heads, kv_heads=kv_heads,
        head_dim=d, recurrent_layers=n_mamba,
        state_bytes_per_slot=n_mamba * (state + window),
        ssm_update_bytes=ssm_update_bytes(n_heads, p, n, g))


def ssm_update_bytes(n_heads: int, d_head: int, d_state: int,
                     groups: int) -> float:
    """Bytes ONE ``ssm_decode_update`` call must move for ONE row: the
    float32 state read and written, the decay and ``dt * x`` rows read,
    ``y`` written, ``B`` and ``C`` read. (The kernel's operands as it
    takes them; what pads in memory is not required.)"""
    state = n_heads * d_head * d_state * 4
    rows = 3 * n_heads * d_head * 4
    return float(2 * state + rows + 2 * groups * d_state * 4)


# ------------------------------------------------------------ the weights
def ssm_law(key, model: dict) -> dict:
    """One Mamba-2 layer's small parameters by the published law
    (``mamba_ssm``'s ``Mamba2.__init__``, kept by ``transformers``):
    ``A ~ U[1, 16]``, ``A_log = log A``; ``dt ~ logU[1e-3, 1e-1]``,
    ``dt_bias = dt + log(-expm1(-dt))``; ``D = 1``; the depthwise
    convolution's weight and bias ``U(-1/sqrt(d_conv), 1/sqrt(d_conv))``
    (``nn.Conv1d``'s default). float32, keyed by the parameter's last
    name."""
    import jax
    import jax.numpy as jnp
    n_heads, k = model["mamba_n_heads"], model["mamba_d_conv"]
    channels = (n_heads * model["mamba_d_head"]
                + 2 * model["mamba_n_groups"] * model["mamba_d_state"])
    ka, kd, kw, kb = jax.random.split(key, 4)
    dt = jnp.exp(jax.random.uniform(kd, (n_heads,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    bound = 1.0 / math.sqrt(k)
    return {
        "A_log": jnp.log(jax.random.uniform(ka, (n_heads,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "D": jnp.ones((n_heads,), jnp.float32),
        "conv_weight": jax.random.uniform(kw, (k, channels), jnp.float32,
                                          -bound, bound),
        "conv_bias": jax.random.uniform(kb, (channels,), jnp.float32,
                                        -bound, bound),
    }


def redraw_ssm_weights(weights: dict, model: dict, seed: int) -> dict:
    """``weights`` with every Mamba-2 layer's ``A_log``, ``dt_bias``,
    ``D`` and convolution drawn from the seed by :func:`ssm_law`, in the
    dtype each already has. ``make_weights`` sets them to one (and the
    biases to zero): a decay of 0.15 a token, under which a state
    carried wrongly is forgotten before a check could see it."""
    import jax
    from .system import seed_key
    layers = [i for i, t in enumerate(model["layer_types"]) if t == "mamba"]

    def draw(key):
        out = {}
        for i in layers:
            law = ssm_law(jax.random.fold_in(key, i), model)
            for name, value in law.items():
                full = f"model.layers.{i}.mamba.{name}"
                out[full] = value.astype(weights[full].dtype)
        return out

    key = jax.random.fold_in(seed_key(seed), 0x55D)
    return {**weights, **jax.jit(draw)(key)}


class HybridServeSystem:
    """``system.ServeSystem``'s recipe (meta model, every weight in one
    jitted call from the seed, the program's own ``ServingEngine``), with
    the Mamba-2 layers' small parameters redrawn by their law before the
    engine takes the weights; the reference is handed the same."""

    def __init__(self, config, traffic, seed):
        import jax
        from paddle_tpu.generation.serving import ServingEngine
        from . import system

        self.phases = system.Phases()
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.sizes = system.sizes_of(config)
        self.vocab = int(config["model"]["vocab_size"])
        self.cfg, self.model = system.lazy_model(config)
        self.phases.mark("import_and_model")
        self.weights = redraw_ssm_weights(
            system.make_weights(self.model, self.seed), config["model"],
            self.seed)
        self.model.load_raw_state(self.weights)
        jax.block_until_ready(self.weights)
        self.phases.mark("weights")
        self.model.eval()
        self.ref = system.load_reference(config["name"])
        self.engine = ServingEngine(self.model, **config["serve"])
        self.phases.mark("engine_built")
        self.devices = jax.devices()[:1]


@builder("serve_hybrid")
def build_serve_hybrid(config, traffic, seed, chips):
    return HybridServeSystem(config, traffic, seed)


# ------------------------------------------------------------ the readers
def _kernel_seconds(ctx):
    """Device seconds of the ops whose instruction is named after the
    state-update kernel, averaged over the devices; None without a
    trace or without such an op."""
    ops = ctx.get("device_ops")
    if not ops:
        return None
    total = sum(dur for dev in ops.values() for text, _, dur in dev
                if KERNEL in trace.parse_hlo(text)[0])
    return total / len(ops) / 1e9 or None


@reader("ssm_update_roofline")
def ssm_update_roofline(ctx):
    """The kernel's share of its roofline: it is bound by memory, so the
    least time is the bytes its calls must move (one call a recurrent
    layer a decode step, ``serving_decode_rows`` rows in all) over the
    published HBM bandwidth; over the kernel's device time."""
    kernel_s = _kernel_seconds(ctx)
    rows = ctx["scalars"].get("serving_decode_rows")
    sizes = ctx["sizes"]
    if not kernel_s or not rows or "ssm_update_bytes" not in sizes:
        return None
    need = rows * sizes["recurrent_layers"] * sizes["ssm_update_bytes"]
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / kernel_s


@reader("ssm_update_share")
def ssm_update_share(ctx):
    kernel_s = _kernel_seconds(ctx)
    if not kernel_s or not ctx.get("busy_s"):
        return None
    return 100.0 * kernel_s / ctx["busy_s"]


@reader("hybrid_decode_floor")
def hybrid_decode_floor(ctx):
    """The decode steps' byte floor over ALL the device's busy time:
    each step reads the weights once, reads and writes the recurrent
    state of its decoding rows, and reads their cached KV (the engine's
    own counts), at the published HBM bandwidth. Prefill's device time
    is in the denominator, so this is the cell's share of the whole
    step and cannot pass 100."""
    s, sizes = ctx["scalars"], ctx["sizes"]
    steps, rows, live = (s.get("serving_decode_steps"),
                         s.get("serving_decode_rows"),
                         s.get("serving_decode_live_tokens"))
    if (not ctx.get("busy_s") or not steps or rows is None or live is None
            or "state_bytes_per_slot" not in sizes):
        return None
    need = (steps * flops.weight_bytes(sizes)
            + rows * 2.0 * sizes["state_bytes_per_slot"]
            + live * flops.kv_bytes_per_token(sizes))
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / ctx["busy_s"]


@reader("gauge_value")
def gauge_value(ctx, name, scale=1.0):
    """A gauge of the program's registry as it stands when the line is
    written (the window has closed; a gauge is a level, not a delta),
    summed over its label series. None where the program has no such
    gauge."""
    from paddle_tpu import observability as obs
    fam = obs.registry().snapshot()["metrics"].get(name)
    if not fam or fam["type"] != "gauge":
        return None
    return scale * float(sum(s["value"] for s in fam["series"]))
