"""Training throughput metrics — tokens/sec/chip and MFU.

The north-star metric (BASELINE.md): first-class, not an afterthought.
MFU = achieved_flops / peak_flops with achieved ≈ 6N per token (dense
decoder fwd+bwd) plus the attention term 12·L·h·s per token.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax

# Peak dense bf16 FLOP/s of ONE chip, keyed by the exact
# ``jax.Device.device_kind`` string, each with its published source. A
# device that is not in the table has no peak: asking for one raises —
# an MFU against a guessed peak is not a measurement. Add a chip here,
# with its source, when the code first runs on it.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # Google Cloud documentation, "TPU v5e"
}


def peak_flops(device_kind: str) -> float:
    """Published peak bf16 FLOP/s of one ``device_kind`` chip."""
    try:
        return PEAK_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}: add it to "
            f"paddle_tpu.utils.metrics.PEAK_FLOPS with its source") from None


def detect_peak_flops() -> Optional[float]:
    """Peak of the attached accelerator. ``None`` on a CPU run (a CPU has
    no place in an MFU); an accelerator the table lacks raises."""
    d = jax.devices()[0]
    if d.platform == "cpu":
        return None
    return peak_flops(d.device_kind)


def train_flops_per_token(n_params: int, n_layers: int = 0, hidden: int = 0,
                          seq_len: int = 0) -> float:
    """6N + attention correction 12·L·h·s (fwd+bwd, dense decoder)."""
    flops = 6.0 * n_params
    if n_layers and hidden and seq_len:
        flops += 12.0 * n_layers * hidden * seq_len
    return flops


@dataclass
class SpeedMeter:
    """Step-time tracker producing tokens/sec/chip + MFU.

    Call ``start()`` then ``step(n_tokens)`` after each synchronized train
    step. Warmup steps are excluded from the medians (compile time).
    ``peak_flops`` defaults to the attached chip's published peak; on a
    CPU run there is none, and the summary carries throughput only.
    """

    n_params: int
    n_layers: int = 0
    hidden: int = 0
    seq_len: int = 0
    n_chips: int = 1
    warmup: int = 2
    peak_flops: Optional[float] = None
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    _t0: Optional[float] = None

    def __post_init__(self):
        if self.peak_flops is None:
            self.peak_flops = detect_peak_flops()

    def start(self):
        self._t0 = time.perf_counter()

    def step(self, n_tokens: int):
        now = time.perf_counter()
        if self._t0 is not None:
            self.times.append(now - self._t0)
            self.tokens.append(n_tokens)
        self._t0 = now

    def _steady(self):
        return self.times[self.warmup:] if len(self.times) > self.warmup else self.times

    def step_time(self) -> float:
        import numpy as np
        s = self._steady()
        return float(np.median(s)) if s else float("nan")

    def tokens_per_sec_per_chip(self) -> float:
        s = self._steady()
        tk = self.tokens[self.warmup:] if len(self.tokens) > self.warmup else self.tokens
        if not s:
            return 0.0
        return (sum(tk) / sum(s)) / max(self.n_chips, 1)

    def mfu(self) -> float:
        if self.peak_flops is None:
            raise ValueError("no peak FLOP/s for this device: MFU is "
                             "defined against a published accelerator peak")
        tps = self.tokens_per_sec_per_chip()
        fpt = train_flops_per_token(self.n_params, self.n_layers, self.hidden,
                                    self.seq_len)
        return tps * fpt / self.peak_flops

    def summary(self) -> dict:
        out = {
            "median_step_time_s": self.step_time(),
            "tokens_per_sec_per_chip": self.tokens_per_sec_per_chip(),
            "n_chips": self.n_chips,
            "n_params": self.n_params,
        }
        if self.peak_flops is not None:
            out["mfu"] = self.mfu()
            out["peak_flops"] = self.peak_flops
        return out

    def log_line(self) -> str:
        return json.dumps(self.summary())
