"""Contracts and verifiers (``core/contracts.py``, ``core/quality.py``):
the ``verifier`` spans per run. Moves ``run_s``."""
import layers


def read(ctx):
    return layers.span_ms(ctx, "verifier")
