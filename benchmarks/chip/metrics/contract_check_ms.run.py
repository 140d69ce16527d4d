"""Contracts and verifiers (``core/contracts.py``): the
``contract_check`` spans (``validate_table`` of each node output, inside
the node) per run. Moves ``run_s``."""
import layers


def read(ctx):
    return layers.span_ms(ctx, "contract_check")
