"""Reader of what a serving program's call hands over and gets back:
the program's gauge ``serving_program_buffers{kind,side}``, set when a
program is built (``paddle_tpu/generation/program_cache.py``), so an
untraced run reads it too."""

from .registry import reader


@reader("gauge_series")
def gauge_series(ctx, name, labels):
    """The one series of the gauge ``name`` whose labels are ``labels``,
    as it stands when the line is written. None where the program has no
    such gauge or series (a parent commit that does not publish it)."""
    from paddle_tpu import observability as obs
    fam = obs.registry().snapshot()["metrics"].get(name)
    if not fam or fam["type"] != "gauge":
        return None
    return next((float(s["value"]) for s in fam["series"]
                 if s["labels"] == labels), None)
