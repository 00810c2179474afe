"""Readers of the program's step clock: counters the engine and the
train step publish as each step ends (``paddle_tpu/observability/
tracing.py`` ``StepClock``), so an untraced run reads them too."""

from .registry import reader


@reader("difference_ratio")
def difference_ratio(ctx, num, less, den, scale=1.0):
    """(``num`` - ``less``) over ``den``: a total less a part of it, a
    step. None where the program wrote none of them (a parent commit
    without the clock)."""
    s = ctx["scalars"]
    if num not in s or less not in s or not s.get(den):
        return None
    return scale * (s[num] - s[less]) / s[den]
